"""The chunked state-space scan's share of its roofline: the least time
the chip could take for the scan calls of the traced rounds
(``lib/state_space.scan_work``: by the passes the program really makes)
over the device time under ``fedml.model.ssm.scan``."""

from lib import state_space


def read(ctx):
    return state_space.scan_roofline_pct(ctx)
