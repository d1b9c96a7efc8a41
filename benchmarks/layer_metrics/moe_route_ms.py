"""Device ms a traced round routing: ``fedml.model.moe.route`` (router,
top-k, ordering the assignments, gathering rows, weighting and summing
them back) and what of ``fedml.model.moe`` lies outside its sub-scopes
(the layer's norm)."""

from lib import decoder_kernels


def read(ctx):
    return decoder_kernels.scope_ms(
        ctx, "fedml.model.moe.route", "fedml.model.moe")
