"""Device-to-host fetches per supervised step attempt: the program's
``train_host_fetches`` counter, as each ``repro:train.step`` span that
ended in the traced window notes its attempt's share (``fetches``).
None without the program's spans."""
import pathlib

from bench import program_trace

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(ctx, trace):
    pt = program_trace.for_run(ROOT, ctx)
    return None if pt is None else pt.fetches_per_step()
