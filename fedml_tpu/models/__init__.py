"""Model factory.

Mirrors the reference ``create_model(args, model_name, output_dim)``
dispatch (``fedml_experiments/distributed/fedavg/main_fedavg.py:354-389``)
but returns a functional :class:`~fedml_tpu.models.base.FedModel`.
"""

from __future__ import annotations

import jax.numpy as jnp

from fedml_tpu.config import ModelConfig
from fedml_tpu.models.base import FedModel
from fedml_tpu.models import nlp, vision
from fedml_tpu.models.vision import (
    CNNDropOut,
    CNNOriginalFedAvg,
    CNNParameterised,
    LogisticRegression,
    MobileNet,
    ResNet18GN,
    ResNetCIFAR,
    VGG,
)
from fedml_tpu.models.nlp import CharLSTM, NWPLSTM, TagLogisticRegression


def create_model(cfg: ModelConfig) -> FedModel:
    name = cfg.name.lower()
    nc = cfg.num_classes
    extra = cfg.extra_dict()
    if name == "lr":
        return FedModel(LogisticRegression(nc), cfg.input_shape)
    if name == "cnn":  # reference "cnn" == CNN_DropOut (main_fedavg.py:360)
        return FedModel(CNNDropOut(nc), cfg.input_shape, has_dropout=True)
    if name == "cnn_fedavg":
        return FedModel(CNNOriginalFedAvg(nc), cfg.input_shape)
    if name == "cnn_custom":
        # fork's parameterised CNN with conv widths from the client config
        # ("layers" entries in experiment_client_configs/*.json;
        # model/cv/cnn_custom.py:8)
        return FedModel(
            CNNParameterised(
                nc,
                tuple(extra.get("convs", (16, 32))),
                tuple(extra.get("denses", (128,))),
                extra.get("dropout", 0.0),
            ),
            cfg.input_shape,
            has_dropout=extra.get("dropout", 0.0) > 0,
        )
    if name in ("cnn_small", "cnn_medium", "cnn_large"):
        plans = {
            "cnn_small": ((16, 32), (64,)),
            "cnn_medium": ((32, 64), (128,)),
            "cnn_large": ((64, 128, 256), (256,)),
        }
        convs, denses = plans[name]
        return FedModel(
            CNNParameterised(nc, convs, denses, extra.get("dropout", 0.0)),
            cfg.input_shape,
            has_dropout=extra.get("dropout", 0.0) > 0,
        )
    if name.startswith("resnet"):
        if name == "resnet18_gn":
            if "norm" in extra:
                raise ValueError(
                    "resnet18_gn is the fixed GroupNorm ImageNet-style "
                    "model (reference resnet_gn.py); a norm override does "
                    "not apply — use resnet<depth> with extra norm instead"
                )
            return FedModel(ResNet18GN(nc), cfg.input_shape)
        # name grammar: resnet<depth>[_gn][_s2d]; the norm default comes
        # from the suffix, and extra=(("norm", "syncbn:data"),) overrides
        # it for EVERY resnet variant (exact cross-shard BN on the named
        # mesh axis — models.vision.SyncBatchNorm)
        base = name[len("resnet"):]
        if base.endswith("_s2d_exact"):
            # EXACT s2d execution layout of the standard (BN) ResNet:
            # weight-compatible with resnet<depth> checkpoints through
            # models.s2d_exact.convert_resnet_checkpoint_to_s2d
            from fedml_tpu.models.s2d_exact import ResNetCIFARS2DExact

            depth = int(base[: -len("_s2d_exact")])
            return FedModel(
                ResNetCIFARS2DExact(depth, nc), cfg.input_shape,
                has_batch_stats=True,
            )
        s2d = base.endswith("_s2d")
        if s2d:
            base = base[: -len("_s2d")]
        gn = base.endswith("_gn")
        if gn:
            base = base[: -len("_gn")]
        depth = int(base)
        norm = extra.get("norm", "gn" if gn else "bn")
        return FedModel(
            ResNetCIFAR(depth, nc, norm=norm, space_to_depth=s2d),
            cfg.input_shape,
            has_batch_stats=norm != "gn",
        )
    if name == "mobilenet":
        return FedModel(
            MobileNet(nc, extra.get("width_mult", 1.0)),
            cfg.input_shape,
            has_batch_stats=True,
        )
    if name == "vgg11":
        return FedModel(VGG(nc), cfg.input_shape)
    if name == "mobilenet_v3":
        from fedml_tpu.models.vision_extra import MobileNetV3

        return FedModel(
            MobileNetV3(nc, extra.get("width_mult", 1.0)),
            cfg.input_shape, has_batch_stats=True,
        )
    if name.startswith("efficientnet"):
        from fedml_tpu.models.vision_extra import EfficientNet

        # efficientnet-b0..b7 compound coefficients
        # (reference efficientnet_utils.py efficientnet_params)
        params = {
            "b0": (1.0, 1.0), "b1": (1.0, 1.1), "b2": (1.1, 1.2),
            "b3": (1.2, 1.4), "b4": (1.4, 1.8), "b5": (1.6, 2.2),
            "b6": (1.8, 2.6), "b7": (2.0, 3.1),
        }
        suffix = name[len("efficientnet"):].lstrip("-_") or "b0"
        if suffix not in params:
            raise ValueError(
                f"unknown efficientnet variant: {cfg.name} (use "
                f"efficientnet-b0 .. efficientnet-b7)"
            )
        w, d = params[suffix]
        return FedModel(
            EfficientNet(nc, w, d), cfg.input_shape, has_batch_stats=True
        )
    if name == "lenet":
        from fedml_tpu.models.vision_extra import LeNet

        return FedModel(LeNet(nc), cfg.input_shape)
    if name in ("rnn", "char_lstm"):  # shakespeare
        return FedModel(
            CharLSTM(vocab_size=extra.get("vocab_size", 90)),
            cfg.input_shape,
            input_dtype=jnp.int32,
        )
    if name in ("rnn_stackoverflow", "nwp_lstm"):
        return FedModel(
            NWPLSTM(vocab_size=extra.get("vocab_size", 10004)),
            cfg.input_shape,
            input_dtype=jnp.int32,
        )
    if name in ("tag_lr", "stackoverflow_lr"):
        return FedModel(TagLogisticRegression(nc), cfg.input_shape)
    if name in ("transformer", "transformer_lm"):
        from fedml_tpu.models.transformer import TransformerLM

        # vocab defaults to num_classes so the CLI's --num_classes is
        # sufficient for token datasets (an under-sized embed table
        # silently corrupts every out-of-range lookup)
        return FedModel(
            TransformerLM(
                vocab_size=extra.get("vocab_size", nc),
                num_layers=extra.get("num_layers", 2),
                num_heads=extra.get("num_heads", 4),
                embed_dim=extra.get("embed_dim", 128),
                max_len=extra.get("max_len", 512),
            ),
            cfg.input_shape,
            input_dtype=jnp.int32,
        )
    if name == "decoder":
        from fedml_tpu.models.decoder import (
            attention_counters, decoder_from_extra,
        )
        from fedml_tpu.ops.moe import MOE_COUNTERS

        module = decoder_from_extra(extra, nc)
        sparse = "sparse" in dict(module.cfg)["mlp_layer_types"]
        attended = attention_counters(dict(module.cfg)["layer_types"])
        return FedModel(
            module, cfg.input_shape, input_dtype=jnp.int32,
            counters=(MOE_COUNTERS if sparse else ()) + attended,
            client_counters=MOE_COUNTERS[:1] if sparse else (),
            jit_init=True,
        )
    if name in ("deeplab", "deeplab_lite"):  # fedseg (FedSegAPI.py:19)
        from fedml_tpu.models.segmentation import DeepLabLite

        return FedModel(
            DeepLabLite(
                nc,
                encoder_features=extra.get("encoder_features", (32, 64, 128)),
            ),
            cfg.input_shape,
            has_batch_stats=True,
        )
    raise ValueError(f"unknown model: {cfg.name}")
