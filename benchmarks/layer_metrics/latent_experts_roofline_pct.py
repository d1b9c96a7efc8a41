"""The latent squared-ReLU experts' share of their roofline: the least
time the chip could take for two grouped products a row the traced
rounds really routed here (their own ``moe_rows_held``;
``lib/state_space.latent_experts_work``) over the device time under
``fedml.model.moe.experts``."""

from lib import state_space


def read(ctx):
    return state_space.latent_experts_roofline_pct(ctx)
