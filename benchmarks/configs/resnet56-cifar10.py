"""Plain reference of ``resnet56-cifar10``: the CIFAR ResNet of He et
al. 2016 section 4.2 at depth 6n+2 = 56 (n = 9 basic blocks a stage,
16/32/64 channels, BatchNorm), written out in ``lib/refnet.py``."""

ARCH = {
    "norm": "bn",
    "in_channels": 3,
    "stem": 16,
    "classes": 10,
    # [width, blocks, stride of the stage's first block]
    "stages": [[16, 9, 1], [32, 9, 2], [64, 9, 2]],
}
