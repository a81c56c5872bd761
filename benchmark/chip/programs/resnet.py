"""The program's ResNet v1, built from a configuration file: what the system
under test trains. The leaves come out in the order `reference/resnet.py`
lists."""
from __future__ import annotations


def build(cfg, traffic):
    """(net, sample): the uninitialised block and a one-row host sample for
    the deferred shape inference."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1

    net = ResNetV1(BottleneckV1, cfg["layers"], cfg["channels"],
                   classes=cfg["classes"])
    size = traffic["image"]
    return net, nd.zeros((1, 3, size, size), ctx=mx.cpu())
