"""Device time of the operations under the train step's ``dfxp_store``
named scope (new parameters and momentum quantized at the update width)
in the traced window, per step that ended in it.  None where no
operation carries the scope."""
import pathlib

from bench import program_trace
from bench.readers import span

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(ctx, trace):
    pt = program_trace.for_run(ROOT, ctx)
    if pt is None:
        return None
    a, b = span(ctx)
    steps = sum(1 for t in ctx["step_ends"] if a < t <= b)
    return pt.scope_ms_per_step("dfxp_store", steps)
