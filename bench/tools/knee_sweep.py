#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop serving cell sustains, by
one sweep on the chip, in one process:

    python bench/tools/knee_sweep.py <workload> <seconds> <rate> [rate ...]

For each rate the cell is set up afresh (programs stay compiled in the
process), driven for ``seconds`` and drained.  A rate is sustained when
the queue does not grow through the window: the queue wait of the
window's last third stays within twice that of its first third, and
every request due in the window got its first token in the drain.  The
cell's mix then runs at about four fifths of the highest such rate.
"""
from __future__ import annotations

import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv):
    import jax

    from bench import run as R
    from bench.readers import pct
    from bench.serve_cell import Cell

    workload, seconds = argv[0], float(argv[1])
    spec = R.load_spec(ROOT, workload)
    dev = R.devices(spec["cell"]["chips"])
    R.use_compile_cache(ROOT)
    os.environ["REPRO_AUTOTUNE_CACHE"] = R.autotune_table(
        ROOT, jax.__version__, dev["kind"])
    for rate in map(float, argv[2:]):
        mix = dict(spec["mix"], rate_per_s=rate)
        cell = Cell(spec["config"], mix, 1000 + int(rate * 100))
        cell.setup()
        cell.window(seconds, R.no_trace)
        out = cell.drain()
        ctx = out["ctx"]
        t0, t1 = ctx["t0"], ctx["t_close"]
        subs = sorted((due, cell.eng.metrics.traces[u].t_admit)
                      for u, (_, due, _) in cell.submitted.items()
                      if cell.eng.metrics.traces[u].t_admit is not None)
        third = (t1 - t0) / 3
        first = [a - d for d, a in subs if d < t0 + third]
        last = [a - d for d, a in subs if d >= t1 - third]
        row = {"rate": rate, **{k: round(v, 3) for k, v in
                                out["metrics"].items()},
               "attempted": out["attempted"], "failed": out["failed"],
               "wait_p90_first_ms": round(1e3 * (pct(first, 90) or 0), 1),
               "wait_p90_last_ms": round(1e3 * (pct(last, 90) or 0), 1),
               "drain_s": round(cell._steps[-1][1] - t1, 2)
               if cell._steps else 0.0}
        print(json.dumps(row), flush=True)
        cell.release()
        cell.params = None
        del cell
        gc.collect()


if __name__ == "__main__":
    main(sys.argv[1:])
