#!/usr/bin/env python3
"""Read the numbers a cell's correctness check compares, over many seeds
and for the control and the planted faults, in one process on the chip
(the readings the limits in ``bench/limits/<cell>.json`` are set from):

    python bench/tools/readings.py <workload> <seconds> <variant>:<seeds> ...

``variant`` is ``program``, ``control`` (training: the program at the
control policy of the configuration file; serving: the plain reference
with its KV rounded to ``control_kv_bits``, read on the program runs'
own samples), a planted fault (``half_batch``, ``frozen``,
``alter_token``) or, for training, the program on one of its own wider
paths (``comp16``: 16-bit computations, the same storage; ``float32``:
plain float arithmetic, compared with the reference in float32
throughout); ``seeds`` is ``first-last``.  Serving cells run a short
window of ``seconds`` at the cell's own load.  One JSON line per run;
training lines carry every leaf's share and the worst leaves.
"""
from __future__ import annotations

import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


WIDER = {"comp16": {"comp_width": 16}, "float32": {"arithmetic": "float32"}}


def main(argv):
    import jax

    from bench import run as R

    workload, seconds = argv[0], float(argv[1])
    spec = R.load_spec(ROOT, workload)
    dev = R.devices(spec["cell"]["chips"])
    R.use_compile_cache(ROOT)
    os.environ["REPRO_AUTOTUNE_CACHE"] = R.autotune_table(
        ROOT, jax.__version__, dev["kind"])
    config = spec["config"]
    train = spec["mix"]["mode"] == "train"
    for item in argv[2:]:
        variant, seeds = item.split(":")
        lo, hi = (int(x) for x in seeds.split("-"))
        for seed in range(lo, hi + 1):
            kw = {}
            if variant == "control" and train:
                kw["policy_overrides"] = config["control_policy"]
            elif variant in WIDER:
                kw["policy_overrides"] = WIDER[variant]
            elif variant not in ("program", "control"):
                kw["fault"] = variant
            t = time.perf_counter()
            cell = R.make_cell(spec, seed, **kw)
            cell.setup()
            row = {"variant": variant, "seed": seed}
            if train:
                cell.release()
                row.update(cell.numbers(cell.reference(), detail=True))
                row["outcomes"] = cell.readings["outcomes"]
            else:
                cell.window(seconds, R.no_trace)
                out = cell.drain()
                cell.release()
                row["failed"] = out["failed"]
                row.update(cell.gaps())
                if variant == "program":
                    ctl = cell.gaps(config["control_kv_bits"])
                    row["control_logit_gap"] = ctl["logit_gap"]
                    row["control_mismatch_share"] = ctl["mismatch_share"]
            row["seconds"] = round(time.perf_counter() - t, 1)
            print(json.dumps(row), flush=True)
            cell.params = None
            del cell
            gc.collect()


if __name__ == "__main__":
    main(sys.argv[1:])
