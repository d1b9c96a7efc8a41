"""Model wrapper: a uniform functional interface over flax modules.

The reference's single pluggable training abstraction is the ``ModelTrainer``
ABC (``fedml_core/trainer/model_trainer.py:7-41``) holding a mutable
``nn.Module``. The TPU-native equivalent is a *pure-function triple*: the
model is a flax module, the state is a variables pytree (``params`` +
optional ``batch_stats``), and train/eval applications are pure so they can
be vmapped across clients and jitted.

FedAvg aggregates the reference's full ``state_dict`` — including BatchNorm
running stats (``FedAVGAggregator.py:73-81``); we mirror that by treating the
whole variables pytree as the unit of aggregation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

Variables = Any


@dataclasses.dataclass(frozen=True)
class FedModel:
    """Functional handle on one architecture."""

    module: nn.Module
    input_shape: tuple[int, ...]
    has_batch_stats: bool = False
    has_dropout: bool = False
    # inputs may be int tokens (NLP) rather than floats
    input_dtype: Any = jnp.float32
    # scalars the module sows into its ``counters`` collection in train
    # mode (the decoder's expert-load counts): the local update sums
    # them over a client's steps beside the metric sums, and the round
    # reports the cohort's totals — and, for ``client_counters``, the
    # value of every sampled client (``<name>_by_client``)
    counters: tuple[str, ...] = ()
    client_counters: tuple[str, ...] = ()
    # make the weights in ONE compiled program (the forward pass an
    # eager init would run op by op is dead code there): for models of
    # gigabytes
    jit_init: bool = False

    def init(self, rng: jax.Array) -> Variables:
        dummy = jnp.zeros((1,) + tuple(self.input_shape), self.input_dtype)
        init = lambda r: self.module.init({"params": r}, dummy, train=False)
        variables = (jax.jit(init) if self.jit_init else init)(rng)
        # init makes every collection; what a module counts is no state
        return {k: v for k, v in variables.items() if k != "counters"}

    def apply_train(
        self, variables: Variables, x: jax.Array, rng: jax.Array
    ) -> tuple[jax.Array, Variables]:
        """Forward in train mode; returns (logits, updated variables)."""
        rngs = {"dropout": rng} if self.has_dropout else None
        if self.has_batch_stats:
            logits, mutated = self.module.apply(
                variables, x, train=True, rngs=rngs, mutable=["batch_stats"]
            )
            return logits, {**variables, **mutated}
        logits = self.module.apply(variables, x, train=True, rngs=rngs)
        return logits, variables

    def apply_train_counted(
        self, variables: Variables, x: jax.Array, rng: jax.Array
    ) -> tuple[jax.Array, Variables, dict]:
        """:meth:`apply_train` plus what the module counted in this
        call: ``{name: scalar}`` for every name of ``counters``."""
        if not self.counters:
            logits, new_vars = self.apply_train(variables, x, rng)
            return logits, new_vars, {}
        rngs = {"dropout": rng} if self.has_dropout else None
        mutable = ["counters"] + (
            ["batch_stats"] if self.has_batch_stats else []
        )
        logits, mutated = self.module.apply(
            variables, x, train=True, rngs=rngs, mutable=mutable
        )
        counted = mutated.pop("counters")
        return (
            logits, {**variables, **mutated},
            {name: counted[name] for name in self.counters},
        )

    def apply_eval(self, variables: Variables, x: jax.Array) -> jax.Array:
        return self.module.apply(variables, x, train=False)

    # -- cohort-grouped fast path (see fedml_tpu.models.cohort) ------------

    def supports_cohort(self) -> bool:
        """Whether this architecture can run the whole sampled cohort as
        one cohort-grouped network (conv zoo modules expose a ``cohort``
        width-multiplier field). Dropout is excluded: the grouped form
        draws one mask over the widened activations, which changes the
        per-client noise stream vs the vmapped form."""
        return (
            getattr(self.module, "cohort", None) == 1
            and not self.has_dropout
        )

    def apply_cohort_train(
        self, stacked_vars: Variables, x: jax.Array, rng: jax.Array
    ) -> tuple[jax.Array, Variables]:
        """Train-mode forward of C clients at once in cohort-grouped form.

        ``stacked_vars`` has leading client axis C on every leaf; ``x`` is
        ``[C, B, H, W, cin]``. Returns (logits ``[C, B, K]``, updated
        stacked variables). Numerically identical to
        ``vmap(apply_train)`` — the grouped network IS the per-client
        network, re-laid-out (channel groups = clients)."""
        from fedml_tpu.models.cohort import fat_to_stack, stack_to_fat

        C = x.shape[0]
        if C == 1:
            # degenerate cohort (e.g. one client per mesh shard): the
            # widened network IS the base network; dense scopes store
            # stacked [1, f, o] kernels the base head can't consume, so
            # squeeze through the ordinary per-client apply instead
            squeezed = jax.tree.map(lambda v: v[0], stacked_vars)
            logits, new_vars = self.apply_train(squeezed, x[0], rng)
            return (
                logits[None],
                jax.tree.map(lambda v: v[None], new_vars),
            )
        module = self.module.clone(cohort=C)
        fat = stack_to_fat(stacked_vars, C)
        xg = jnp.moveaxis(x, 0, 3).reshape(x.shape[1:4] + (-1,))
        rngs = {"dropout": rng} if self.has_dropout else None
        if self.has_batch_stats:
            logits, mutated = module.apply(
                fat, xg, train=True, rngs=rngs, mutable=["batch_stats"]
            )
            return logits, {**stacked_vars, **fat_to_stack(mutated, C)}
        logits = module.apply(fat, xg, train=True, rngs=rngs)
        return logits, stacked_vars


LossFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
