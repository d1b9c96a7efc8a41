"""Grouped-product calls a sparse layer makes an optimizer step: the
round program's operations named ``ragged-dot-none*`` under
``fedml.model.moe.experts`` that chip 0 STARTED in the traced rounds —
counted, not timed — over (client steps x sparse layers). Each matrix
of the experts costs one product forward and two backward (by the rows,
by the matrices): 9 calls with three matrices and 6 with two where a
layer's forward pass runs once a step, 12 and 8 where its
rematerialisation runs the forward products a second time. None
without a trace, a scope map or a decoder configuration with sparse
layers, and on a program that ran no such operation."""

import os

from lib import decoder_kernels as K
from lib import program_spans, xplane

SCOPE = "fedml.model.moe.experts"
PRODUCT = "ragged-dot-none"


def product_calls(data, scopes: dict, lo: float, hi: float) -> int:
    """Events of chip 0's op line in ``[lo, hi)`` of family
    :data:`PRODUCT` that lie in a round program's module event and that
    its scope map puts under :data:`SCOPE`."""
    plane = xplane.device_planes(data)[0]
    modules = [(s, e, n.split("(")[0]) for s, e, n in xplane.events(
        xplane._line(plane, xplane.MODULES_LINE))]
    rounds = [m for m in modules if "round" in m[2]]
    calls = 0
    for s, _, n in xplane.events(xplane._line(plane, xplane.OPS_LINE)):
        if not lo <= s < hi or xplane.op_family(n) != PRODUCT:
            continue
        module = next((m for a, b, m in rounds if a <= s < b), None)
        if (scopes.get(module) or {}).get(xplane.hlo_name(n)) == SCOPE:
            calls += 1
    return calls


def read(ctx):
    t = program_spans.analyse(ctx)
    if t is None or not t["scopes"] or not ctx.get("client_steps"):
        return None
    sizes = K.decoder_sizes(ctx)
    layers = K.sparse_layers(sizes[0]) if sizes else 0
    if not layers:
        return None
    cell = ctx["cell"]
    path = xplane.find_xplane(
        os.path.join(cell["bench_dir"], ".trace", cell["name"]))
    calls = product_calls(
        xplane.load(path), program_spans.load_scopes(os.path.dirname(path)),
        *t["window"])
    return calls / (ctx["client_steps"] * layers) if calls else None
