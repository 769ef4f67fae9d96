"""Execute every ```python block in docs/tutorials/*.md top-to-bottom
(reference: tests/nightly/test_tutorial.py, which ran the notebook-backed
tutorials; here the tutorials are markdown whose code is the test).

Blocks fenced as ```python run, sharing one namespace per file, with cwd
set to a scratch dir so file artifacts (checkpoints, .rec files) land
outside the repo.  Blocks fenced ```python norun (cluster-scale or
device-specific commands) are shown but skipped, as are non-python fences.
Blocks fenced ```python pjrt start the Python-free native runtime, which
needs a PJRT plugin (its default is libtpu): where MXTPU_PJRT_PLUGIN is not
set the file's run stops there as skipped, as tests/test_predict_native.py
does.
"""
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TUTORIALS = sorted(glob.glob(os.path.join(ROOT, "docs", "tutorials", "*.md")))

FENCE = re.compile(r"^```(\S*)[ \t]*(\S*)[ \t]*$")


def _python_blocks(path):
    blocks, cur, lang, word = [], None, None, ""
    with open(path) as f:
        lines = f.readlines()
    for line in lines:
        m = FENCE.match(line.rstrip("\n"))
        if m and cur is None:
            lang, word = m.group(1), m.group(2)
            cur = []
        elif m and cur is not None:
            if lang == "python" and word != "norun":
                blocks.append(("".join(cur), word == "pjrt"))
            cur, lang = None, None
        elif cur is not None:
            cur.append(line)
    assert cur is None, "%s: unterminated code fence" % path
    return blocks


def test_tutorials_exist():
    names = {os.path.basename(p) for p in TUTORIALS}
    assert {"index.md", "ndarray.md", "symbol.md", "module.md", "data.md",
            "mnist.md", "linear_regression.md", "rnn.md", "kvstore.md",
            "parallel.md", "custom_op.md"} <= names


@pytest.mark.parametrize("path", TUTORIALS,
                         ids=[os.path.basename(p) for p in TUTORIALS])
def test_tutorial_code_runs(path, tmp_path, monkeypatch):
    blocks = _python_blocks(path)
    if not blocks:
        pytest.skip("no runnable blocks")
    monkeypatch.chdir(tmp_path)
    ns = {"__name__": "__tutorial__"}
    for i, (block, needs_plugin) in enumerate(blocks):
        if needs_plugin and not os.environ.get("MXTPU_PJRT_PLUGIN"):
            pytest.skip("block %d starts the native runtime and no PJRT "
                        "plugin is given (set MXTPU_PJRT_PLUGIN); the %d "
                        "before it ran" % (i, i))
        try:
            exec(compile(block, "%s[block %d]" % (os.path.basename(path), i),
                         "exec"), ns)
        except Exception as e:
            raise AssertionError(
                "%s block %d failed: %r\n---\n%s" %
                (os.path.basename(path), i, e, block)) from e
