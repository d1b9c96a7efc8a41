"""Model zoo smoke tests: init + forward shapes for every factory entry."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from fedml_tpu.config import ModelConfig
from fedml_tpu.models import create_model


IMG_CASES = [
    ("lr", (28, 28, 1), 10),
    ("cnn", (28, 28, 1), 62),
    ("cnn_fedavg", (28, 28, 1), 62),
    ("cnn_small", (32, 32, 3), 10),
    ("resnet20", (32, 32, 3), 10),  # resnet56 shape-checked at depth 20 for CI speed
    ("resnet18_gn", (32, 32, 3), 100),
    ("mobilenet", (32, 32, 3), 10),
    ("vgg11", (32, 32, 3), 10),
    ("mobilenet_v3", (32, 32, 3), 10),
    ("efficientnet-b0", (32, 32, 3), 10),
    ("lenet", (32, 32, 3), 10),
    ("cnn_custom", (28, 28, 1), 10),
]


@pytest.mark.parametrize("name,shape,nc", IMG_CASES)
def test_vision_forward(name, shape, nc):
    model = create_model(ModelConfig(name=name, num_classes=nc, input_shape=shape))
    variables = model.init(jax.random.key(0))
    x = jnp.zeros((2,) + shape)
    logits = model.apply_eval(variables, x)
    assert logits.shape == (2, nc)
    logits2, new_vars = model.apply_train(variables, x, jax.random.key(1))
    assert logits2.shape == (2, nc)
    assert jax.tree.structure(new_vars) == jax.tree.structure(variables)


def test_char_lstm():
    model = create_model(
        ModelConfig(name="rnn", num_classes=90, input_shape=(80,))
    )
    variables = model.init(jax.random.key(0))
    tokens = jnp.zeros((2, 80), jnp.int32)
    logits = model.apply_eval(variables, tokens)
    assert logits.shape == (2, 80, 90)


def test_nwp_lstm():
    model = create_model(
        ModelConfig(
            name="nwp_lstm",
            num_classes=2000,
            input_shape=(20,),
            extra=(("vocab_size", 2000),),
        )
    )
    variables = model.init(jax.random.key(0))
    logits = model.apply_eval(variables, jnp.zeros((2, 20), jnp.int32))
    assert logits.shape == (2, 20, 2000)


def test_tag_lr():
    model = create_model(
        ModelConfig(name="tag_lr", num_classes=50, input_shape=(1000,))
    )
    variables = model.init(jax.random.key(0))
    logits = model.apply_eval(variables, jnp.zeros((2, 1000)))
    assert logits.shape == (2, 50)


def test_resnet_has_batch_stats():
    model = create_model(
        ModelConfig(name="resnet20", num_classes=10, input_shape=(32, 32, 3))
    )
    variables = model.init(jax.random.key(0))
    assert "batch_stats" in variables


def test_sync_batchnorm_exact_across_shards():
    """SyncBatchNorm under a 4-way data shard_map == plain BN on the full
    concatenated batch — forward outputs AND running-stat updates
    (reference SynchronizedBatchNorm parity; our previous sync-BN-lite
    only pmean'd the stats after the fact)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from fedml_tpu.models.vision import SyncBatchNorm

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    x = jax.random.normal(jax.random.key(0), (16, 8, 8, 6)) * 2.0 + 1.0

    ref_bn = nn.BatchNorm(use_running_average=False, momentum=0.9,
                          use_bias=True, use_scale=True)
    sync = SyncBatchNorm(axis_name="data", momentum=0.9)
    v = sync.init({"params": jax.random.key(1)}, x[:4], train=False)

    # reference: flax BN on the FULL batch (same init: scale 1, bias 0)
    rv = ref_bn.init({"params": jax.random.key(1)}, x)
    ref_out, ref_mut = ref_bn.apply(rv, x, mutable=["batch_stats"])

    def shard_fn(v, xs):
        out, mut = sync.apply(v, xs, train=True, mutable=["batch_stats"])
        return out, mut

    out, mut = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P("data"), P()),
        check_vma=False,
    )(v, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-5, rtol=1e-5)
    # running stats: flax BN EMA uses momentum on (mean, var) the same way
    np.testing.assert_allclose(
        np.asarray(mut["batch_stats"]["mean"]),
        np.asarray(ref_mut["batch_stats"]["mean"]), atol=1e-5, rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(mut["batch_stats"]["var"]),
        np.asarray(ref_mut["batch_stats"]["var"]), atol=1e-4, rtol=1e-3,
    )

    # the "syncbn:<axis>" norm kind wires it through the ResNet zoo
    from fedml_tpu.models.vision import ResNetCIFAR

    m = ResNetCIFAR(depth=8, num_classes=4, norm="syncbn:data")
    def init_fn(xs):
        return m.init({"params": jax.random.key(2)}, xs, train=False)
    v2 = shard_map(
        init_fn, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False,
    )(x[:, :, :, :3])
    assert "batch_stats" in v2


def test_s2d_exact_matches_standard_resnet():
    """The exact s2d execution layout + checkpoint converter: a standard
    ResNetCIFAR's variables converted through
    convert_resnet_checkpoint_to_s2d produce the SAME function (eval
    logits and train-mode forward) in the TPU-friendly layout — the
    parity bridge that lets reference-layout checkpoints run s2d."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.s2d_exact import (
        ResNetCIFARS2DExact,
        convert_resnet_checkpoint_to_s2d,
    )
    from fedml_tpu.models.vision import ResNetCIFAR

    depth = 20  # n=3: same structure class as 56, 3x faster to compile
    std = ResNetCIFAR(depth=depth, num_classes=10, norm="bn")
    s2d = ResNetCIFARS2DExact(depth=depth, num_classes=10)
    x = jax.random.normal(jax.random.key(0), (4, 32, 32, 3))
    v_std = std.init(jax.random.key(1), x, train=False)
    v_s2d = convert_resnet_checkpoint_to_s2d(v_std, depth=depth)

    # structure check against a fresh init
    ref_tree = jax.tree.structure(
        s2d.init(jax.random.key(2), x, train=False)
    )
    assert jax.tree.structure(v_s2d) == ref_tree

    want = std.apply(v_std, x, train=False)
    got = s2d.apply(v_s2d, x, train=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )

    # train mode: phase-pooled BN must reproduce the original batch
    # statistics (forward outputs equal)
    want_t, wmut = std.apply(
        v_std, x, train=True, mutable=["batch_stats"]
    )
    got_t, gmut = s2d.apply(
        v_s2d, x, train=True, mutable=["batch_stats"]
    )
    np.testing.assert_allclose(
        np.asarray(got_t), np.asarray(want_t), rtol=2e-4, atol=2e-4
    )
    # updated running stats of the stem BN: converted = tile4(original)
    src_bn = wmut["batch_stats"]["BatchNorm_0"]["mean"]
    dst_bn = gmut["batch_stats"]["PhasePooledBatchNorm_0"]["mean"]
    np.testing.assert_allclose(
        np.asarray(dst_bn), np.tile(np.asarray(src_bn), 4),
        rtol=1e-4, atol=1e-5,
    )


def test_s2d_exact_cohort_equals_vmap_single_apply():
    """The exact-s2d model's cohort-grouped (fat) application equals the
    vmapped per-client application to f32 round-off (trajectory-level
    equality is chaos-bounded like every BN net; single applications are
    the layout pin)."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.config import ModelConfig
    from fedml_tpu.models import create_model

    m = create_model(
        ModelConfig(name="resnet8_s2d_exact", num_classes=10,
                    input_shape=(32, 32, 3))
    )
    assert m.supports_cohort()
    C, B = 3, 4
    k = jax.random.key(0)
    v = m.init(k)
    stacked = jax.tree.map(
        lambda a: jnp.stack([a + 0.01 * i for i in range(C)]), v
    )
    x = jax.random.normal(jax.random.fold_in(k, 1), (C, B, 32, 32, 3))
    lv, lvars = jax.vmap(
        lambda sv, xb: m.apply_train(sv, xb, jax.random.key(9))
    )(stacked, x)
    cv, cvars = m.apply_cohort_train(stacked, x, jax.random.key(9))
    np.testing.assert_allclose(
        np.asarray(cv), np.asarray(lv), rtol=1e-5, atol=2e-6
    )
    for a, b in zip(jax.tree.leaves(lvars), jax.tree.leaves(cvars)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=2e-6
        )
