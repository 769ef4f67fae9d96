"""Fixtures shared by the rec-file tests here and in tests_tpu/ (whose
conftest puts this directory on the path): a small synthetic JPEG set and
its .rec pack."""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def gen_dataset(workdir, n, size):
    """n JPEGs with enough structure that decode cost is realistic."""
    from PIL import Image

    rng = np.random.RandomState(0)
    img_dir = os.path.join(workdir, "imgs")
    os.makedirs(img_dir, exist_ok=True)
    lst_path = os.path.join(workdir, "data.lst")
    with open(lst_path, "w") as lst:
        for i in range(n):
            # blocky texture compresses like a photo, not like noise
            base = rng.rand(size // 8, size // 8, 3) * 255
            arr = np.kron(base, np.ones((8, 8, 1)))[:size, :size]
            arr += rng.randn(size, size, 3) * 8
            im = Image.fromarray(np.clip(arr, 0, 255).astype(np.uint8))
            name = "img_%05d.jpg" % i
            im.save(os.path.join(img_dir, name), quality=90)
            lst.write("%d\t%d\t%s\n" % (i, i % 10, name))
    return img_dir, lst_path


def pack(workdir, img_dir, lst_path):
    """Pack via tools/im2rec.py (pass-through: store the JPEG bytes, the
    iterator decodes) — the reference's im2rec workflow."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from tools import im2rec

    prefix = lst_path[:-4]
    old_argv = sys.argv
    sys.argv = ["im2rec.py", prefix, img_dir + os.sep, "--pass-through"]
    try:
        im2rec.main()
    finally:
        sys.argv = old_argv
    rec = prefix + ".rec"
    assert os.path.exists(rec), "im2rec did not produce %s" % rec
    return rec
