"""LoRA adapter injection for :class:`~fedml_tpu.models.transformer.TransformerLM`.

Low-rank adaptation (Hu et al. 2021): each targeted Dense layer
``y = x W`` gains a rank-``r`` branch

    y = x W + (alpha / r) * (x A) B

with the base ``W`` frozen, ``A`` seeded-init and ``B`` ZERO-init —
so at round 0 the adapted model is **byte-identical** to the base
model: the branch contributes exactly ``0.0`` and, critically, the
base parameters' init draws are unchanged (flax derives each param's
init rng from its path + name, so adding ``lora_a``/``lora_b`` under
the same module scope does not perturb ``kernel``/``bias`` — pinned
bitwise in ``tests/test_peft.py``).

Injection is a **dense factory**: :class:`TransformerLM` builds its
projections through an overridable constructor
(``dense_cls``), and :func:`dense_factory` substitutes
:class:`LoRADense` for exactly the targeted names
(``q_proj``/``k_proj``/``v_proj``/``attn_out``/``mlp_up``/``mlp_down``,
selected via ``--lora_targets``). The pluggable ``attn_fn``
(flash/ring) contract is untouched — LoRA wraps the projections
AROUND the attention call, never the attention itself.

What federates is decided by :mod:`fedml_tpu.peft.partition`: the
adapter leaves plus the LM head are the trainable subtree; everything
else is frozen base that never sees an optimizer state, a delta, or a
wire byte.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import flax.linen as nn

#: the injectable Dense names of the TransformerLM block, in model order
LORA_TARGETS = (
    "q_proj", "k_proj", "v_proj", "attn_out", "mlp_up", "mlp_down",
)

#: model names create_model resolves to a TransformerLM (the only
#: architecture with the named-projection contract LoRA injects into)
LORA_MODELS = ("transformer", "transformer_lm")


@dataclasses.dataclass(frozen=True)
class LoRASpec:
    """Frozen description of the adapter configuration (rides
    ``FedConfig.peft`` / ``lora_*``; hashable like every config)."""

    rank: int = 4
    alpha: float = 8.0
    targets: tuple[str, ...] = ("q_proj", "v_proj")

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(
                f"lora_rank must be >= 1, got {self.rank}"
            )
        if not (self.alpha > 0):
            raise ValueError(
                f"lora_alpha must be > 0, got {self.alpha}"
            )
        bad = [t for t in self.targets if t not in LORA_TARGETS]
        if bad or not self.targets:
            raise ValueError(
                f"unknown lora_targets {bad or '(empty)'}: the "
                f"TransformerLM injectable Dense names are "
                f"{list(LORA_TARGETS)}"
            )

    @staticmethod
    def from_fed(fed) -> "LoRASpec | None":
        """None when ``fed.peft`` is off; validates on construction."""
        method = getattr(fed, "peft", "none") or "none"
        if method == "none":
            return None
        if method != "lora":
            raise ValueError(
                f"peft must be 'none' or 'lora', got {method!r}"
            )
        return LoRASpec(
            rank=fed.lora_rank,
            alpha=fed.lora_alpha,
            targets=tuple(fed.lora_targets),
        )


class LoRADense(nn.Module):
    """``nn.Dense`` plus a zero-initialized low-rank branch.

    The base ``kernel``/``bias`` params mirror ``nn.Dense`` exactly —
    same names, same initializers, same ``dot_general`` contraction —
    so swapping this module in under the same scope name leaves the
    base parameters AND the round-0 forward bitwise unchanged (the
    branch is ``(x A) B`` with ``B = 0``, an exact float zero)."""

    features: int
    rank: int
    alpha: float
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (in_features, self.features),
        )
        bias = (
            self.param(
                "bias", nn.initializers.zeros_init(), (self.features,)
            )
            if self.use_bias else None
        )
        lora_a = self.param(
            "lora_a", nn.initializers.lecun_normal(),
            (in_features, self.rank),
        )
        lora_b = self.param(
            "lora_b", nn.initializers.zeros_init(),
            (self.rank, self.features),
        )
        contract = lambda v, w: jax.lax.dot_general(
            v, w, (((v.ndim - 1,), (0,)), ((), ()))
        )
        y = contract(x, kernel)
        y = y + (self.alpha / self.rank) * contract(
            contract(x, lora_a), lora_b
        )
        if bias is not None:
            y = y + jnp.reshape(bias, (1,) * (y.ndim - 1) + (-1,))
        return y


def dense_factory(spec: LoRASpec):
    """The ``dense_cls`` hook for :class:`TransformerLM`: targeted
    names get a :class:`LoRADense`, everything else the stock
    ``nn.Dense`` — byte-identical module tree outside the targets."""

    def make(features: int, use_bias: bool, name: str) -> nn.Module:
        if name in spec.targets:
            return LoRADense(
                features=features, rank=spec.rank, alpha=spec.alpha,
                use_bias=use_bias, name=name,
            )
        return nn.Dense(features, use_bias=use_bias, name=name)

    return make


def apply_lora(model, spec: LoRASpec):
    """Inject adapters into a transformer :class:`FedModel`: returns a
    new handle whose module builds targeted projections through
    :class:`LoRADense`. Raises for architectures without the named
    Dense contract — injection must never silently no-op."""
    import dataclasses as dc

    from fedml_tpu.models.transformer import TransformerLM

    if dict(getattr(model.module, "cfg", ())).get("tie_word_embeddings"):
        raise ValueError(
            "peft='lora' beside tie_word_embeddings: a tied stack has no "
            "lm_head module to train densely, and no adapter on the tied "
            "table is built")
    if not isinstance(model.module, TransformerLM):
        raise ValueError(
            f"peft='lora' targets the TransformerLM's named Dense "
            f"projections ({list(LORA_TARGETS)}); "
            f"{type(model.module).__name__} has no such contract — "
            "use --model transformer/transformer_lm"
        )
    return dc.replace(
        model, module=model.module.clone(dense_cls=dense_factory(spec))
    )


def check_model_supported(model_name: str) -> None:
    """Parse-time twin of the :func:`apply_lora` architecture check
    (run.py validates before any model is built)."""
    if model_name.lower() not in LORA_MODELS:
        raise ValueError(
            f"--peft lora requires a transformer model "
            f"({'/'.join(LORA_MODELS)}); got --model {model_name!r} "
            "(LoRA injects into the TransformerLM's named Dense "
            f"projections {list(LORA_TARGETS)})"
        )
