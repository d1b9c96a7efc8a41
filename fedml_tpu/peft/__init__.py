"""Parameter-efficient federated fine-tuning (PEFT/LoRA).

The subsystem has three layers (docs/PERFORMANCE.md
"Parameter-efficient federated fine-tuning"):

- :mod:`fedml_tpu.peft.lora` — adapter injection: wrap the
  transformer's named Dense projections with zero-initialized
  low-rank branches (round 0 byte-identical to the base model);
- :mod:`fedml_tpu.peft.partition` — the trainable/frozen parameter
  partition threaded through every path a delta is built or applied
  on: local SGD runs only on the trainable subtree (frozen base
  closed over as a constant — no optimizer state, no delta, no wire
  bytes), and the server folds O(adapter)-sized updates;
- :mod:`fedml_tpu.peft.personal` — private per-client adapter banks
  (only the shared head aggregates).

:func:`build_peft` is the single entry the simulators call; the
compatibility matrix is enforced loudly by :func:`check_peft_compat`
(and at run.py parse time), never silently approximated.
"""

from __future__ import annotations

from typing import Any

from fedml_tpu.peft.lora import (
    LORA_MODELS,
    LORA_TARGETS,
    LoRADense,
    LoRASpec,
    apply_lora,
    check_model_supported,
    dense_factory,
)
from fedml_tpu.peft.partition import (
    ParamPartition,
    PeftPlan,
    adapter_partition,
    private_partition,
)

Pytree = Any

__all__ = [
    "LORA_MODELS",
    "LORA_TARGETS",
    "LoRADense",
    "LoRASpec",
    "ParamPartition",
    "PeftPlan",
    "adapter_partition",
    "apply_lora",
    "build_peft",
    "check_model_supported",
    "check_peft_compat",
    "compound_wire_ratio",
    "dense_factory",
    "private_partition",
]


def check_peft_compat(fed, adversary=None, checkpoint_every: int = 0) -> None:
    """Reject configurations the PEFT paths cannot express EXACTLY —
    raised at simulator construction (and at run.py parse time). The
    non-personalized adapter path composes with everything (codec,
    bulk streaming, round fusion, elastic buckets, defenses, the
    sharded runtime — the aggregation stack is tree-generic and just
    sees a smaller tree). Personalization's per-client bank now lives
    in a client-id-keyed :class:`~fedml_tpu.core.statebank.
    ClientStateBank`, which rides the bulk scan carry, the fused-round
    scan carry, the elastic bucket (sentinel-padded, non-live rows
    preserved), the sharded runtime's client axis, AND the round
    checkpoint composite — those PR 15 walls have fallen. What remains
    rejected, with reasons:

    - ``compress``: the codec's error-feedback residual assumes the
      aggregated subtree is the whole client update, but a
      personalized client also carries private adapters that never
      ride the wire;
    - defended ``robust_method``: the selection rules are untested
      against the head-only shared aggregate and are rejected loudly
      rather than run unvalidated;
    - ``adversary``: the injection gate rewrites the aggregated
      stacked variables and has no private-bank seam."""
    spec = LoRASpec.from_fed(fed)
    del checkpoint_every  # the bank rides the checkpoint composite now
    personalize = bool(getattr(fed, "peft_personalize", False))
    if not personalize:
        return
    if spec is None:
        raise ValueError(
            "peft_personalize requires peft='lora': without adapters "
            "there is no private subtree to personalize"
        )
    if getattr(fed, "compress", "none") not in ("none", "", None):
        raise ValueError(
            "peft_personalize is incompatible with compress: the "
            "wire codec's per-slot error-feedback residual assumes "
            "the aggregated subtree is the whole client update, but "
            "a personalized client also carries private adapters "
            "that never ride the wire. Compress composes with "
            "NON-personalized peft='lora'."
        )
    if getattr(fed, "robust_method", "mean") not in ("mean", "", None):
        raise ValueError(
            "peft_personalize supports robust_method='mean' only: "
            "the defended selection rules are untested against the "
            "head-only shared aggregate and are rejected loudly "
            "rather than run unvalidated"
        )
    if adversary is not None and adversary.enabled():
        raise ValueError(
            "peft_personalize is incompatible with adversary "
            "injection: the injection gate rewrites the aggregated "
            "stacked variables and has no private-bank seam — run "
            "Byzantine scenarios on non-personalized peft='lora'"
        )


def build_peft(model, cfg) -> tuple[Any, "PeftPlan | None"]:
    """Resolve the PEFT configuration for one simulator: returns
    ``(model, None)`` when off, else ``(lora-injected model, plan)``.
    Validates the whole compatibility matrix first so a bad combo
    fails at construction, not mid-round."""
    fed = cfg.fed
    spec = LoRASpec.from_fed(fed)
    check_peft_compat(fed, cfg.adversary,
                      checkpoint_every=cfg.checkpoint_every)
    if spec is None:
        return model, None
    plan = PeftPlan(
        part=adapter_partition(spec.targets),
        personalized=bool(fed.peft_personalize),
    )
    return apply_lora(model, spec), plan


def compound_wire_ratio(plan: "PeftPlan", cspec, params: Pytree) -> float:
    """Full-model-equivalent wire reduction: dense bytes of the
    full-delta BASELINE (the base model's payload — adapter leaves
    excluded on both sides of the comparison, see
    :meth:`PeftPlan.full_wire_bytes`) over the (optionally
    codec-compressed) bytes of the aggregated adapter subtree — the
    multiplicative stack of the partition (adapter/full) and the PR 7
    codec (compressed/dense), reported as the ``peft.wire_ratio``
    gauge."""
    from fedml_tpu.core import compress as C
    from fedml_tpu.peft.partition import _leaf_bytes

    agg = plan.agg_part.trainable(params)
    dense_full = plan.full_wire_bytes(params)
    dense_agg = _leaf_bytes(agg)
    codec_ratio = (
        C.wire_ratio(cspec, agg)
        if cspec is not None and cspec.enabled() else 1.0
    )
    return (dense_full / max(1, dense_agg)) * codec_ratio
