"""Median ``fedml.fetch`` span of the traced part: the one batched
``jax.device_get`` of a round's metrics — the host blocked on the device,
i.e. the device round as the loop sees it."""

from lib import program_spans


def read(ctx):
    return program_spans.metric(ctx, "fetch_wait_ms")
