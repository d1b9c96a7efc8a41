"""Device ms a traced round in the blockwise attention kernel's SEPARATE
dq pass: the round program's operations named ``splash_mqa_dq*`` (chip
0's). A backward pass that walks its score blocks once forms dQ inside
the dk/dv kernel and runs no such operation: 0 there. None on a program
without the kernel's scope (``fedml.model.attn.kernel``), or without a
trace."""

from lib import program_spans
from lib.sparse_attention import KERNEL as KERNEL_SCOPE

DQ_PASS = "splash_mqa_dq"


def read(ctx):
    t = program_spans.analyse(ctx)
    if (t is None or not t["scopes"]
            or KERNEL_SCOPE not in t["scope_busy_s"]):
        return None
    busy = sum(s for (family, _), s in t["family_scope_s"].items()
               if family.startswith(DQ_PASS))
    return 1e3 * busy / t["rounds"]
