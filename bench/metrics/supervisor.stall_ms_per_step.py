"""Device idle inside the train supervisor's serial host phases
(``repro:train.batch``, ``train.launch`` and ``train.record`` spans) in
the traced window, per step that ended in it: host work the device does
not overlap.  None without the program's spans."""
import pathlib

from bench import program_trace

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(ctx, trace):
    pt = program_trace.for_run(ROOT, ctx)
    return None if pt is None else pt.stall_ms_per_step()
