"""The gated delta rule's recurrence's share of its roofline: the least
time the chip could take for the recurrence of the traced rounds
(``lib/delta_rule.scan_work``: the chunked form's products at the
record's chunk size over the heads held, forward once and twice that
backward) over the device time under ``fedml.model.delta.scan``."""

from lib import delta_rule


def read(ctx):
    return delta_rule.scan_roofline_pct(ctx)
