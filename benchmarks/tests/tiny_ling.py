"""``ling3-flash-share64`` at a size a CPU test can hold: the
configuration's OWN ``.py`` (copied as it is) beside its own ``.json``
with the sizes overridden and every ratio kept — five gated delta-rule
layers then a latent-attention layer, the first two under dense
feed-forwards and four under sparse ones, over 64 tokens (four chunks of
16), hidden 64; 4 heads of 16 of which 2 are held, in both kinds of
mixer; 4 taps; latent keys of 16 + 8 beside values of 16 out of a latent
of 16, queries direct, a gate a head; 64 experts of width 32 in 8
groups, 4 a token out of 4 open groups, 8 held (one group), beside a
shared one, under a score-correction bias — its cell's traffic at 8
clients, and the benchmark's own readers."""

from __future__ import annotations

import copy
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG, CELL, TRAFFIC = (
    "ling3-flash-share64", "ling3-flash-c2of32-b1x8192", "c2of32-block1-s2")

#: a layer is a mixer (``k`` delta rule, ``l`` latent attention) and a
#: feed-forward (``D`` dense, ``S`` sparse, ``N`` none)
PATTERN = ("kD", "kD", "kS", "kS", "kS", "lS")
MIXERS = {"k": "delta_attention", "l": "latent_attention"}
KINDS = {"D": "dense", "S": "sparse", "N": "none"}
SEQ, VOCAB, HEADS, HELD = 64, 96, 4, [0, 2]
DELTA = {"head_dim": 16, "conv_kernel": 4, "gate_lower_bound": -5,
         "chunk_size": 16}
LATENT = {"q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "v_head_dim": 16}
# bfloat16 program against the float8 control at this size (seeds 1-6 /
# 1-3, this sandbox's CPU; the chip's readings at the published widths
# are in the configuration's .json). head_grad_rel_err decides: sound
# 0.0472-0.0919, control 0.2619 / 0.2886 / 0.2928, limit 0.155 (the
# geometric middle: 1.7 times the sound runs' largest, 0.59 of the
# control's smallest). The others at three times the largest of the
# sound seeds 2-6: loss_rel_gap 0.00123 (control 0.0009-0.0092, not a
# precision number at 64 tokens), first_grad_norm_gap 0.0894 (control
# 0.0603-0.4744), change_norm_gap 0.0281 (control 0.0518-0.2658). Seed 1
# reads 0.4402 and 0.2590 there in bfloat16, beside its control's 0.4744
# and 0.2658: at 64 tokens ONE token whose open groups differ in a lower
# precision moves a large part of a sparse layer's few rows, so those
# two rows tell nothing apart on that seed and the rehearsal
# (test_ling_cell.py) runs seeds 2-4
LIMITS = {"loss_rel_gap": 0.0037, "head_grad_rel_err": 0.155,
          "first_grad_norm_gap": 0.27, "change_norm_gap": 0.085}


def sizes(pattern=PATTERN) -> dict:
    """``model.extra`` overrides of a tiny stack of ``pattern``."""
    sparse = any(layer[1] == "S" for layer in pattern)
    return {
        "hidden_size": 64, "head_dim": 16, "num_key_value_heads": HEADS,
        "heads_per_layer": [HEADS] * len(pattern), "query_heads_held": HELD,
        "delta_attention": dict(DELTA), "latent_attention": dict(LATENT),
        "layer_types": [MIXERS[layer[0]] for layer in pattern],
        "mlp_layer_types": [KINDS[layer[1]] for layer in pattern],
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "num_experts": 64,
        "num_experts_per_tok": 4, "router_groups": [8, 4],
        "experts_held": [0, 8], "router_score_bias": sparse,
        "vocab_size": VOCAB}


def real_config() -> dict:
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny_config(compute_dtype="float32", pattern=PATTERN, **extra) -> dict:
    """The configuration's own file with the sizes of :func:`sizes`
    (and ``extra``) in place of the published ones."""
    config = copy.deepcopy(real_config())
    config["model"]["extra"].update({**sizes(pattern), **extra})
    config["model"].update(num_classes=VOCAB, input_shape=[SEQ])
    config["dataset"].update(vocab=VOCAB, seq_len=SEQ, classes=8,
                             n_train=16, n_test=8)
    config["train"]["compute_dtype"] = compute_dtype
    config["correct_limits"] = LIMITS
    return config


def write_config(directory, config) -> str:
    """``config`` beside a copy of the configuration's reference file.
    -> the ``.py``'s path."""
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, CONFIG + ".json"), "w") as f:
        json.dump(config, f)
    return shutil.copy(os.path.join(BENCH, "configs", CONFIG + ".py"),
                       directory)


def load_reference(directory, config=None):
    import run

    return run._load_py(
        write_config(directory, config or tiny_config()), "tiny_ling_ref")


def make_tree(root, compute_dtype="float32"):
    """BENCHMARK.json with the one configuration and its cell, the
    configuration's files shrunk, its traffic at 8 clients."""
    bench = os.path.join(root, "benchmarks")
    write_config(os.path.join(bench, "configs"), tiny_config(compute_dtype))
    os.makedirs(os.path.join(bench, "traffic"), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"),
                    os.path.join(bench, "layer_metrics"), dirs_exist_ok=True)
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    traffic.update(population=8, eval_every=2)
    with open(os.path.join(bench, "traffic", TRAFFIC + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        real = json.load(f)
    doc = {**real, "run_seconds": 1,
           "configs": [c for c in real["configs"] if c["name"] == CONFIG],
           "workloads": [w for w in real["workloads"] if w["name"] == CELL]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root
