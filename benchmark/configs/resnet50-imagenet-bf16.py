"""ResNet-50 as the repository's model zoo builds it, through Module.fit.

A plain ``jax.numpy`` reference of ResNet-50's forward, loss and gradients
does not exist yet (PERF.md, open questions); ``correct`` for this
configuration rests on the loss checks of ``drivers/fit.py``.
"""


def build_symbol(cfg):
    from mxnet_tpu import models

    return models.resnet(**cfg["model"])


def input_shapes(cfg, batch):
    shape = tuple(int(x) for x in cfg["model"]["image_shape"].split(","))
    return {"data": (batch,) + shape, "softmax_label": (batch,)}


def num_classes(cfg):
    return int(cfg["model"]["num_classes"])
