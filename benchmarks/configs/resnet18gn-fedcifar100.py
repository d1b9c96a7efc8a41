"""Plain reference of ``resnet18gn-fedcifar100``: ResNet-18 (He et al.
2016 table 1: four stages of two basic blocks, 64/128/256/512 channels)
with GroupNorm in place of BatchNorm (Hsieh et al. 2020; Reddi et al.
2021 appendix C), on 32x32 inputs: a 3x3 stride-1 stem and no
max-pooling. Written out in ``lib/refnet.py``."""

ARCH = {
    "norm": "gn",
    "in_channels": 3,
    "stem": 64,
    "classes": 100,
    "stages": [[64, 2, 1], [128, 2, 2], [256, 2, 2], [512, 2, 2]],
}
