"""The programs of the main path compile for a DESCRIBED v5e.

The TPU compiler is installed without a chip attached: it compiles for
a topology that is described, not present, and refuses what the chip
would refuse (tiling, fast-memory and HBM limits, partitioning). These
tests keep the Pallas kernel, the ResNet-56 cohort local update at
headline width and the four-chip ``ShardedFedAvg`` round compiling for
``v5e:2x2`` at no chip time. Nothing runs: a compile that passes is not
a chip run (``chip_smoke.py`` is).

Only one process may load the TPU library, so the topology is described
inside a module-scoped fixture of THIS file — never at import, never in
``conftest.py`` — and every such compile lives here, in one file, which
xdist's ``loadfile`` hands to one worker.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out of there
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _grouped_products_in(text: str) -> bool:
    """Whether a program holds the sparse layers' grouped products: the
    compiler's ``ragged_dot`` kernels or, where the shape rule
    (``ops/moe.py:product_tiles``) tiles the rows, ``ops/grouped.py``'s."""
    return "ragged-dot" in text or "moe_rows_product" in text


def _tree_bytes(tree) -> int:
    return int(sum(
        np.prod(x.shape) * np.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
    ))


def test_flash_attention_kernel_compiles_for_v5e(one_chip):
    from fedml_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((4, 2048, 8, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
    ).lower(x, x, x).compile()
    # the kernel itself is in the program, not an interpreted expansion
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)])
def test_decoder_attention_compiles_for_v5e(one_chip, monkeypatch, heads,
                                            window):
    """The decoder stack's attention at Laguna-XS.2's widths (48 full /
    64 sliding query heads over 8 key-value heads of 128, 2 sequences of
    2,048, window 512), forward and backward: the blockwise kernel is in
    the program, two calls of it (forward, and the ONE backward kernel:
    dq comes out of the dk/dv kernel as two partials)."""
    from fedml_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    q = jax.ShapeDtypeStruct((2, 2048, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 2048, 8, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return A.causal_attention(q, k, v, window=window).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "splash_mqa_dkv" in text and "splash_mqa_dq" not in text
    # no [T, T] score tensor: the scratch is far under one head's scores
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2048 * (
        2048 * heads * 2)


def test_latent_attention_kernel_compiles_for_v5e(one_chip, monkeypatch):
    """The blockwise kernel at JoyAI-LLM-Flash's latent-attention shapes
    (one sequence of 8,192; 32 key-value heads of group 1; keys of 192,
    one and a half lane tiles, beside values of 128), forward and the
    one-walk backward: Mosaic takes 192 as it is, and the output and
    the values' gradient come out at the values' size."""
    from fedml_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    qk = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return A.causal_attention(q, k, v).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(qk, qk, v)
    assert [g.shape[-1] for g in lowered.out_info] == [192, 192, 128]
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "splash_mqa_dkv" in text and "splash_mqa_dq" not in text


def test_attention_kernel_at_heads_of_64_compiles_for_v5e(
        one_chip, monkeypatch):
    """The blockwise kernel at LFM2-8B-A1B's attention shapes (one
    sequence of 8,192; 32 query heads over 8 key-value heads of 64, half
    a lane tile), forward and the one-walk backward: Mosaic takes 64 as
    it is, and every gradient comes out at 64."""
    from fedml_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return A.causal_attention(q, k, v).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    assert [g.shape[-2:] for g in lowered.out_info] == [
        (32, 64), (8, 64), (8, 64)]
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "splash_mqa_dkv" in text and "splash_mqa_dq" not in text


def test_sparse_attention_compiles_for_v5e(one_chip, monkeypatch):
    """Learned sparse attention at Keye-VL-2.0-30B-A3B's widths (one
    sequence of 8,192; an index of 16 heads of 64 over one key head that
    keeps 2,048 keys a query; 32 query heads over 4 key-value heads of
    128): the index and selection kernels, and attention over the
    selection forward and backward — four Mosaic kernels in the program
    (the backward pass is ONE: dq comes out of the dk/dv kernel) and no
    ``[heads, T, T]`` tensor in its scratch."""
    from fedml_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    t = 8192
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)

    def loss(q, k, v, qi, ki, w):
        selection = A.select_top_k(A.index_scores(qi, ki, w), 2048)
        return A.causal_attention(q, k, v, selection=selection).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds(1, t, 32, 128), sds(1, t, 4, 128), sds(1, t, 4, 128),
        sds(1, t, 16, 64), sds(1, t, 64), sds(1, t, 16)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 4
    assert "splash_mqa_dkv" in text and "splash_mqa_dq" not in text
    assert "sparse_index_scores" in text and "sparse_select_top_k" in text
    # the scores (268 MB), the selection and its two blocked copies
    # (67 MB each), dq's 8 partials (67 MB each): far under one
    # key-value head's [8, T, T] scores
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_rematerialised_sparse_layer_compiles_for_v5e(one_chip, monkeypatch):
    """A training step (bfloat16) of ONE decoder layer at
    Keye-VL-2.0-30B-A3B's widths — learned sparse attention over a
    sequence of 8,192, then 16 held of 128 experts of width 768, 8 a
    token — under ``DecoderLM``'s remat. The layer keeps its kernel's
    output, log-sum-exp and its selection, so the program holds the
    forward kernel, the index and the top-k ONCE beside the ONE backward
    kernel (dq comes out of the dk/dv kernel as a partial a key block of
    1,024: 8 x 67 MB, summed after the call); and it keeps the sparse
    layer's routing and ``(rows, into, out)`` of the bounded buffer of
    16,384 rows, so each side of the row buffer's branch holds its three
    grouped products once forward and twice backward, and no second
    forward. What it keeps (135 MB of attention, 185 MB of rows, 5 MB
    of routing: the router's logits in float32 and six arrays of
    integers) fits beside the recomputed rest."""
    from fedml_tpu.models.decoder import decoder_from_extra
    from fedml_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    model = decoder_from_extra({
        "hidden_size": 2048, "head_dim": 128, "num_key_value_heads": 4,
        "heads_per_layer": [32], "layer_types": ["sparse_attention"],
        "mlp_layer_types": ["sparse"], "intermediate_size": 768,
        "num_experts": 128, "num_experts_per_tok": 8,
        "experts_held": [0, 16], "moe_intermediate_size": 768,
        "router_scoring": "softmax", "qk_norm": True,
        "rope": {"sparse_attention": {"rope_theta": 1e7}},
        "sparse_attention": {"index_heads": 16, "index_head_dim": 64,
                             "topk": 2048}}, 1024)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(model.init, jax.random.key(0),
                       jnp.zeros((1, 8192), jnp.int32))["params"])

    def loss(params, tokens):
        logits, _ = model.apply({"params": params}, tokens,
                                mutable=["counters"])
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss)).lower(params, tokens).compile()
    kernels = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    calls = lambda name: sum(f"%{name}" in line.split("=")[0]
                             for line in kernels)
    assert calls("splash_mqa_fwd") == 1
    # one walk over the score blocks: dq comes out of the dk/dv kernel
    assert calls("splash_mqa_dq") == 0 and calls("splash_mqa_dkv") == 1
    assert calls("sparse_select_top_k") == calls("sparse_index_scores") == 1
    # the router ranks once, in its own kernel: the routing is kept
    assert calls("moe_rank_top_k") == 1
    # the side that fits the bounded buffer: 3 forward, 6 backward; the
    # worst-case side: 3 forward, and 3 + 6 in its own backward rule
    # (27 with a second forward of both sides) — all the compiler's
    # ``ragged_dot`` or all the row-tiled kernels (two by the rows for
    # each one by the matrices), as the shape rule sends this shape
    from fedml_tpu.ops.moe import product_tiles
    products = {"ragged-dot-none": 3 + 6 + 3 + 9, "moe_rows_product": 0,
                "moe_matrices_product": 0}
    if product_tiles(16384, 2048, 768, 16, jnp.bfloat16) is not None:
        products = {"ragged-dot-none": 0, "moe_rows_product": 3 + 3 + 6 + 3,
                    "moe_matrices_product": 3 + 3}
    assert {name: calls(name) for name in products} == products
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


@pytest.mark.parametrize("tokens, experts, top_k, scoring, biased", [
    (8192, 512, 22, "sigmoid", False), (8192, 128, 8, "softmax", False),
    (4096, 256, 8, "sigmoid", False), (8192, 64, 6, "softmax", False),
    (8192, 256, 8, "sigmoid", True)],
    ids=["nemotron", "keye", "laguna", "smallthinker", "joyai"])
def test_the_routers_choice_and_rule_compile_for_v5e(
        one_chip, monkeypatch, tokens, experts, top_k, scoring, biased):
    """A router's ``top_k`` and its backward rule at the five decoder
    cells' shapes, alone and under the cohort's ``vmap`` of one client:
    ONE Mosaic kernel ranks (``moe_rank_top_k``: the rule reads what it
    kept) and the compiled program holds neither a sort — ``lax.top_k``
    of ``[N, E]`` is one on this chip — nor a scatter."""
    from fedml_tpu.ops import attention as A
    from fedml_tpu.ops import moe as MOE

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    bias = (sds(experts),) if biased else ()

    def loss(scores, g, *bias):
        return jnp.sum(g * MOE.route_top_k(
            scores, top_k, 2.5, scoring, *bias)[1])

    for grad, lead in ((jax.grad(loss), ()), (jax.vmap(jax.grad(loss)), (1,))):
        text = jax.jit(grad).lower(
            sds(*lead, tokens, experts), sds(*lead, tokens, top_k),
            *(sds(*lead, *b.shape) for b in bias)).compile().as_text()
        kernels = [line for line in text.splitlines()
                   if "tpu_custom_call" in line and " custom-call(" in line]
        assert len(kernels) == 1 and "moe_rank_top_k" in kernels[0]
        assert " sort(" not in text and " scatter(" not in text


def test_grouped_expert_products_compile_for_v5e(one_chip):
    """One sparse layer's share at Laguna-XS.2's widths (4,096 tokens,
    8 of 256 experts a token, 32 held of width 512), forward and
    backward: the grouped products are the compiler's ragged-dot
    kernels, not a dense expansion over the experts, and the scope map
    still names them."""
    from fedml_tpu.core.memscope import parse_scopes
    from fedml_tpu.ops.moe import moe_layer

    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    params = {"router": sds((2048, 256)), "w1": sds((32, 2048, 512)),
              "w3": sds((32, 2048, 512)), "w2": sds((32, 512, 2048)),
              "shared": (sds((2048, 512)), sds((2048, 512)),
                         sds((512, 2048)))}

    def loss(params, h):
        y, counters = moe_layer(params, h, (0, 32), 8, 2.5)
        return y.astype(jnp.float32).sum(), counters

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        params, sds((4096, 2048))).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 9  # 3 products x (1 + 2)
    # the bounded row buffers ([8192, 2048] bfloat16) and, on the side
    # of the branch a call over them takes, the worst-case ones
    # ([32768, 2048] = 134 MB each): never 32 experts x 32,768 rows
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    # forward rule, backward rule, and the forward combine's own on
    # either side (zeros where no row is held)
    assert 2 <= text.count(" conditional(") <= 4
    scopes = parse_scopes(text)
    assert {v for k, v in scopes.items()
            if k.startswith("ragged-dot-none")} == {
                "fedml.model.moe.experts"}


@pytest.mark.parametrize("tokens, width, inner, held, experts, top_k", [
    (8192, 2048, 1792, 8, 32, 4),  # LFM2's share
    (8192, 2560, 768, 16, 64, 6),  # SmallThinker's
])
def test_row_tiled_expert_products_compile_for_v5e(
        one_chip, monkeypatch, tokens, width, inner, held, experts, top_k):
    """A training step of one sparse layer's share at the widths whose
    grouped products the shape rule sends to the row-tiled kernels
    (``ops/grouped.py``): forward three calls of ``moe_rows_product``
    and in the rule three more and three of ``moe_matrices_product`` on
    the bounded side (the worst-case side runs its forward pass again),
    no ``ragged_dot`` left, every kernel call under the experts' scope
    by its own ``op_name``, whole matrices in fast memory within what
    the kernels ask for, and no more scratch than the plain form's."""
    from fedml_tpu.core.memscope import parse_scopes
    from fedml_tpu.ops import attention as A
    from fedml_tpu.ops.moe import moe_layer

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)
    params = {"router": sds(width, experts), "w1": sds(held, width, inner),
              "w3": sds(held, width, inner), "w2": sds(held, inner, width)}

    def loss(params, h, g):
        y, counters = moe_layer(params, h, (0, held), top_k, 1.0)
        return jnp.sum((y * g).astype(jnp.float32)), counters

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)).lower(
            params, sds(tokens, width), sds(tokens, width)).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line
               and "_product" in line]
    count = lambda name: sum(name + "/pallas_call" in k for k in kernels)
    assert count("moe_rows_product") == 6 + 9
    assert count("moe_matrices_product") == 3 + 3
    scopes = parse_scopes(text)
    assert {scopes[k.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")]
            for k in kernels} == {"fedml.model.moe.experts"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1.85e9


def test_sparse_layer_reads_back_without_a_zero_row_for_v5e(
        one_chip, monkeypatch):
    """A training step of ONE sparse layer's share at SmallThinker's
    widths (8,192 tokens of 2,560, 6 of 64 experts a token, 16 held: a
    bounded buffer of 24,576 rows), value and gradients: no zero row is
    appended to a buffer (no array of 24,577 or 49,153 rows); the
    combine gathers from the buffer's leading 16,384 rows, a slice made
    in the chip's fast memory; forward no select runs over a token's
    rows, the weights carry the zero; no loop fills a buffer; and the
    scratch is the plain form's 1.77 GB."""
    from fedml_tpu.ops import attention as A
    from fedml_tpu.ops.moe import RELU_GATED, moe_layer

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)
    params = {"router": sds(2560, 64), "w1": sds(16, 2560, 768),
              "w3": sds(16, 2560, 768), "w2": sds(16, 768, 2560)}

    def loss(params, h, g):
        y, counters = moe_layer(params, h, (0, 16), 6, 1.0, "softmax",
                                RELU_GATED)
        return jnp.sum((y * g).astype(jnp.float32)), counters

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)).lower(
            params, sds(8192, 2560), sds(8192, 2560)).compile()
    text = compiled.as_text()
    assert "[24577,2560]" not in text and "[49153,2560]" not in text
    # (no array of the rows' dtype is padded: the walk of the row-tiled
    # products pads a few dozen integers for its own gathers)
    assert not [line for line in text.splitlines() if " pad(" in line
                and line.split("=")[1].lstrip().startswith("bf16[")]
    assert " while(" not in text
    # the leading 16,384 rows (80 MiB), sliced straight into the fast
    # memory space: forward and in the rule, on the bounded side
    parts = [line for line in text.splitlines() if " slice(" in line
             and line.split("=")[1].lstrip().startswith("bf16[16384,2560]")]
    assert len(parts) >= 2 and all("S(1)" in line.split(" slice(")[0]
                                   for line in parts)
    # a select over all a token's rows stands in the rule alone, whose
    # sum has no weights to carry the zero
    assert not [line for line in text.splitlines()
                if " select(" in line and "2560]" in line.split("=")[1]
                and "transpose(jvp" not in line]
    assert compiled.memory_analysis().temp_size_in_bytes < 1.85e9


def test_resnet56_cohort_update_compiles_for_v5e(one_chip):
    """The headline local update: the whole 10-client cohort as one
    widened ResNet-56 (``ops/cohort_conv``'s custom primitive), batch
    32, bf16 — ``chip_smoke.headline_config``'s widths."""
    import chip_smoke
    from fedml_tpu.algorithms.base import build_cohort_local_update
    from fedml_tpu.algorithms.fedavg import FedAvgSim
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model

    cfg = chip_smoke.headline_config()
    assert cfg.train.compute_dtype == "bfloat16"
    sim = FedAvgSim(create_model(cfg.model), load_dataset(cfg.data), cfg)
    cohort = cfg.fed.clients_per_round
    update = build_cohort_local_update(
        sim.model, sim.task, cfg.train, sim.batch_size,
        sim.arrays.max_client_samples, cohort,
    )
    a = sim.arrays
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), cohort)
    )
    rows = lambda t: jax.ShapeDtypeStruct(
        (cohort,) + t.shape[1:], t.dtype, sharding=one_chip
    )
    compiled = jax.jit(update).lower(
        _shapes(jax.eval_shape(sim.init).variables, one_chip),
        rows(a.idx), rows(a.mask),
        _shapes(a.x, one_chip), _shapes(a.y, one_chip),
        _shapes(keys, one_chip),
    ).compile()
    assert " convolution(" in compiled.as_text()
    ma = compiled.memory_analysis()
    need = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert 0 < need < V5E_HBM_BYTES, ma


@pytest.fixture(scope="module")
def mesh_sim(topo):
    """The headline job as ``ShardedFedAvg`` built on four of this
    process's devices, its mesh then steered onto the described chips:
    same axes, same layout."""
    import chip_smoke
    from fedml_tpu.data.loaders import load_dataset
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel import ShardedFedAvg, make_mesh

    cfg = chip_smoke.headline_config()
    cfg = dataclasses.replace(
        cfg, fed=dataclasses.replace(cfg.fed, clients_per_round=8)
    )
    sim = ShardedFedAvg(
        create_model(cfg.model), load_dataset(cfg.data), cfg,
        make_mesh(client_axis=4, data_axis=1, devices=jax.devices()[:4]),
    )
    # what the constructor placed is spread over its mesh
    assert len(sim.banks.x.sharding.device_set) == 4
    assert len(sim._test_rows[0].sharding.device_set) == 4
    sim.mesh = Mesh(
        np.array(topo.devices[:4]).reshape(4, 1), sim.mesh.axis_names
    )
    return sim


def test_sharded_round_compiles_for_four_v5e_chips(mesh_sim):
    """``ShardedFedAvg``'s round over a 4-chip ``clients`` mesh: the
    aggregation is a collective, and each chip is handed a quarter of
    the sample banks, not all of them."""
    sim = mesh_sim
    rep = NamedSharding(sim.mesh, P())
    by_client = NamedSharding(sim.mesh, P(sim.client_axis))
    state = _shapes(jax.eval_shape(sim.init), rep)
    banks = _shapes(sim.banks, by_client)
    compiled = jax.jit(sim._sharded_round, donate_argnums=(0,)).lower(
        state, banks
    ).compile()
    assert "all-reduce" in compiled.as_text()
    # each chip's arguments are the replicated state (which comes back
    # as the output, in the same padded device layout) and its quarter
    # of the banks — not the whole set
    ma = compiled.memory_analysis()
    banks_per_chip = ma.argument_size_in_bytes - ma.output_size_in_bytes
    quarter = _tree_bytes(banks) / 4
    assert 0.9 * quarter < banks_per_chip < 1.2 * quarter, (
        banks_per_chip, quarter
    )


def test_mesh_evaluator_compiles_for_four_v5e_chips(mesh_sim):
    """The mesh's evaluation of the global test set: each chip is handed
    a quarter of the rows and the replicated variables, and the metric
    sums cross the chips in a collective."""
    from fedml_tpu.algorithms.base import build_evaluator

    sim = mesh_sim
    rep = NamedSharding(sim.mesh, P())
    by_row = NamedSharding(sim.mesh, P(sim.mesh.axis_names))
    variables = _shapes(jax.eval_shape(sim.init).variables, rep)
    rows = _shapes(sim._test_rows, by_row)
    compiled = build_evaluator(sim.model, sim.task, mesh=sim.mesh).lower(
        variables, *rows
    ).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    n, *image = rows[0].shape
    per_chip = ",".join(map(str, [n // 4, *image]))
    whole = ",".join(map(str, [n, *image]))
    assert f"f32[{per_chip}]" in text and f"f32[{whole}]" not in text


def _kernel_calls(text: str, name: str) -> list[str]:
    """The lines of a compiled program's text that call the Pallas
    kernel ``name``."""
    return [line for line in text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line
            and f"%{name}" in line.split("=")[0]]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_rematerialised_delta_layer_compiles_for_v5e(
        one_chip, monkeypatch, dtype):
    """A training step of ONE gated delta-rule layer at Ling-3.0-flash's
    widths (16 held heads of 128 x 128, 8,192 tokens in 128 chunks of
    64; queries and keys float32 from their normalisation, values in
    the step's dtype) under ``DecoderLM``'s remat: Mosaic takes the two
    chunk kernels at the published shapes in both dtypes; the layer
    keeps ``o`` and the states entering the chunks, all the forward
    kernel makes, so the program holds it ONCE beside the one backward
    kernel; both are booked under ``fedml.model.delta.scan``; and no
    solve of the compiler's is left."""
    from fedml_tpu.models.decoder import decoder_from_extra
    from fedml_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    model = decoder_from_extra({
        "hidden_size": 2560, "head_dim": 128, "num_key_value_heads": 32,
        "heads_per_layer": [32], "query_heads_held": [0, 16],
        "layer_types": ["delta_attention"], "mlp_layer_types": ["none"],
        "delta_attention": {"head_dim": 128, "conv_kernel": 4,
                            "gate_lower_bound": -5, "chunk_size": 64},
        "rope": {}, "intermediate_size": 6144}, 1024)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.key(0),
                       jnp.zeros((1, 8192), jnp.int32))["params"])

    def loss(params, tokens):
        logits, _ = model.apply({"params": params}, tokens,
                                mutable=["counters"])
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss)).lower(params, tokens).compile()
    text = compiled.as_text()
    forward = _kernel_calls(text, "delta_chunk_fwd")
    backward = _kernel_calls(text, "delta_chunk_bwd")
    assert len(forward) == 1 and len(backward) == 1
    assert all("fedml.model.delta.scan" in line
               for line in forward + backward)
    assert "triangular" not in text.lower()
    print("one delta-rule layer, compiler's bytes:",
          compiled.memory_analysis())


def _round_and_evaluator_compile(cell_name, parameters, one_chip,
                                 monkeypatch):
    """One decoder cell as it runs — ``FedAvgSim``'s bulk round at a
    block of one and the evaluator, from the configuration's and the
    traffic's own files at the published widths — for a described v5e:
    the blockwise kernel and the grouped products are in the round
    program, and each program's scratch with its arguments and its code
    stays under the chip's memory (the round holds the global copy, one
    client's copy, its gradient, the running sum and a bfloat16 cast).
    -> the round's compiled text and the compiler's count of its bytes."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    import run
    from fedml_tpu.ops import attention as A
    from lib import traffic as TR

    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the chip's branch
    cell = run.load_cell(cell_name)
    config, traffic = cell["config"], cell["traffic"]
    sim = run.build_sim(
        run.experiment_config(config, traffic), traffic,
        TR.make_population(config["dataset"], traffic, 1),
        cell["reference"].TASK)
    state = _shapes(jax.eval_shape(sim.init), one_chip)
    assert _tree_bytes(state.variables) == 4 * parameters
    compiled = jax.jit(
        sim._round, donate_argnums=sim._donate_argnums()
    ).lower(state, _shapes(sim.arrays, one_chip), None, None).compile()
    text = compiled.as_text()
    assert "splash_mqa_fwd" in text and _grouped_products_in(text)
    ma = compiled.memory_analysis()
    need = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.generated_code_size_in_bytes)
    # under the 16.9 GB the chip's allocator reports
    assert 10e9 < need < 16.9e9, ma
    evaluator, operands = sim._global_eval()
    ev = evaluator.lower(
        state.variables, *_shapes(operands, one_chip)
    ).compile().memory_analysis()
    # beside the state a window holds (the global copy)
    assert ev.temp_size_in_bytes + ev.argument_size_in_bytes < (
        V5E_HBM_BYTES - _tree_bytes(state.variables)), ev
    return text, ma


# slow: 160 s on many threads; beside five other workers of the fast
# tier it starved timing-bound tests (CHANGES.md, PR 33)
@pytest.mark.slow
def test_nemotron_round_and_evaluator_compile_for_v5e(one_chip, monkeypatch):
    """``nemotron3-super-share64`` as its cell runs it — ``FedAvgSim``'s
    bulk round at a block of one and the evaluator, built from the
    configuration's and the traffic's own files at the published widths
    (508 M parameters, one sequence of 8,192 tokens a step) — for a
    described v5e: the attention layer's blockwise kernel and the
    experts' grouped products are in the round program, and the scratch
    of each program with its arguments and its code stays under the
    chip's memory (the round holds the global copy, one client's copy,
    its gradient, the running sum and a bfloat16 cast)."""
    # 15.16 GB by the compiler's count (12.56 of it scratch; 15.95 and
    # 13.34 before PR 35's combine read a row a held expert), of the
    # 16.9 GB the chip's allocator reports; the chip itself read 14.1
    # GB (PR 33's tree 14.2) where this count read 15.5 (PERF.md)
    _round_and_evaluator_compile(
        "nemotron3s-c2of32-b1x8192", 508_187_120, one_chip, monkeypatch)


# slow for the same reason: 100 s of compiling on many threads
@pytest.mark.slow
def test_smallthinker_round_and_evaluator_compile_for_v5e(
        one_chip, monkeypatch):
    """``smallthinker-21b-share4`` as its cell runs it — ``FedAvgSim``'s
    bulk round at a block of one and the evaluator, from the
    configuration's and the traffic's own files at the published widths
    (593,615,360 parameters, one sequence of 8,192 tokens a step, a
    quarter of the vocabulary: 1.24 GB of float32 logits) — for a
    described v5e: the blockwise kernel and the grouped products are in
    the round program, and each program's scratch with its arguments
    and its code stays under the chip's memory."""
    # 14.93 GB by the compiler's count (12.26 of it scratch); the chip
    # itself read 13.75 GB (PERF.md, PR 39)
    _round_and_evaluator_compile(
        "smallthinker-c2of32-b1x8192", 593_615_360, one_chip, monkeypatch)


# slow for the same reason: 100 s of compiling on many threads
@pytest.mark.slow
def test_joyai_round_and_evaluator_compile_for_v5e(one_chip, monkeypatch):
    """``joyai-llm-flash-share16`` as its cell runs it — ``FedAvgSim``'s
    bulk round at a block of one and the evaluator, from the
    configuration's and the traffic's own files at the published widths
    (564,954,112 parameters, one sequence of 8,192 tokens a step through
    32 latent-attention heads) — for a described v5e: the blockwise
    kernel and the grouped products are in the round program, and each
    program's scratch with its arguments and its code stays under the
    chip's memory."""
    # 14.66 GB by the compiler's count (11.99 of it scratch)
    _round_and_evaluator_compile(
        "joyai-flash-c2of32-b1x8192", 564_954_112, one_chip, monkeypatch)


# slow for the same reason: 90 s of compiling on many threads
@pytest.mark.slow
def test_lfm2_round_and_evaluator_compile_for_v5e(one_chip, monkeypatch):
    """``lfm2-8b-a1b-share4`` as its cell runs it — ``FedAvgSim``'s bulk
    round at a block of one and the evaluator, from the configuration's
    and the traffic's own files at the published widths (568,647,936
    parameters, ONE table for embedding and head, one sequence of 8,192
    tokens a step through five gated short convolutions and one
    attention layer of 64-wide heads) — for a described v5e: the
    blockwise kernel and the grouped products are in the round program,
    and each program's scratch with its arguments and its code stays
    under the chip's memory."""
    # 15.76 GB by the compiler's count (13.23 of it scratch)
    _round_and_evaluator_compile(
        "lfm2-8b-c2of32-b1x8192", 568_647_936, one_chip, monkeypatch)


# slow for the same reason: 180 s of compiling on many threads
@pytest.mark.slow
def test_ling_round_and_evaluator_compile_for_v5e(one_chip, monkeypatch):
    """``ling3-flash-share64`` as its cell runs it (586,929,872
    parameters, one sequence of 8,192 tokens a step through five gated
    delta-rule mixers of 16 held heads — 128 chunks of 64 a layer — and
    one latent-attention layer): the router's ranking kernel is in the
    round program beside the blockwise kernel and the grouped products,
    and the delta rule's two chunk kernels ONCE a layer and step each (a
    rematerialised layer keeps all the forward kernel makes), every call
    under ``fedml.model.delta.scan``, with no solve of the compiler's
    left."""
    # PR 49's plain form: 16.62 GB by the compiler's count (13.40 of it
    # scratch, 0.86 code); one delta-rule layer's forward and backward
    # alone held 2.4 GB
    text, ma = _round_and_evaluator_compile(
        "ling3-flash-c2of32-b1x8192", 586_929_872, one_chip, monkeypatch)
    assert "moe_rank_top_k" in text and "triangular" not in text.lower()
    forward = _kernel_calls(text, "delta_chunk_fwd")
    backward = _kernel_calls(text, "delta_chunk_bwd")
    # one a layer in each copy of the step the round program holds (its
    # ONE latent-attention layer's forward kernel counts the copies)
    steps = len(_kernel_calls(text, "splash_mqa_fwd"))
    assert len(forward) == len(backward) == 5 * steps > 0
    assert all("fedml.model.delta.scan" in line
               for line in forward + backward)
    print("ling3-flash round, compiler's bytes:", ma)

