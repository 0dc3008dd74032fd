#!/usr/bin/env python3
"""The benchmark: one cell, one seed, one measured window.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file (``bench/configs/``), its traffic mix
(``bench/traffic/<traffic>.json``), the limits of its correctness check
(``bench/limits/<cell>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).  Adding a cell or a metric adds files
and entries; no file here changes.

The run happens in this one process (a TPU belongs to one process).  It
refuses to run, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.  ``setup_s`` runs from the start of this
script to the first timed operation: weights made on the device from the
seed, the program built, every shape the cell's traffic uses compiled
(or loaded from JAX's compile cache in ``<checkout>/.cache/jax``) and
run once.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
result carries the per-layer metrics, the device's busy time and a
breakdown, read from a profile of the window's middle 8 seconds.  After the window the program's state is freed, the device's
peak memory read, and the output compared with the plain reference; each
number compared is printed beside its limit, last on stderr and last in
the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


class NoChip(RuntimeError):
    pass


# -- the benchmark's data -----------------------------------------------------

def load_spec(root: pathlib.Path, workload: str) -> dict:
    """Everything the harness needs for ``workload``, read from
    ``BENCHMARK.json`` and the files it names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    limits_path = root / "bench" / "limits" / f"{workload}.json"
    limits = (json.loads(limits_path.read_text())
              if limits_path.exists() else {})

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in names]
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "end_to_end": e2e, "per_layer": layer, "root": root}


def metric_reader(root: pathlib.Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the chip -----------------------------------------------------------------

def devices(chips: int, require_chip: bool = True) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_chip and (dev["platform"] != "tpu" or dev["count"] < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{dev}")
    return dev


def autotune_table(root: pathlib.Path, jax_version: str, kind: str) -> str:
    """The harness's own kernel-tile table, named for what the tiles
    depend on (kernel sources, JAX version, chip), so later runs of a
    checkout reuse the first run's tiles and find its compiled programs."""
    h = hashlib.sha256(f"{jax_version}|{kind}".encode())
    kernels = root / "src" / "repro" / "kernels"
    for path in sorted(kernels.rglob("*.py")):
        h.update(path.relative_to(kernels).as_posix().encode())
        h.update(path.read_bytes())
    return str(root / ".cache" / "bench" / f"autotune-{h.hexdigest()[:16]}"
               ".json")


class CompileCounter:
    """XLA compiles (and compile-cache loads) seen through
    ``jax.monitoring``; ``window`` counts those inside the window."""

    def __init__(self):
        self.total = self.hits = 0
        self.in_window = None

    def on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += 1

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)


def use_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.cache/jax``, handed to the program through
    ``JAX_COMPILATION_CACHE_DIR`` (``repro.compile_cache`` takes it from
    there), every program persisted however quickly it compiled, and no
    size limit inherited from the environment evicting entries."""
    import jax
    path = str(root / ".cache" / "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro.compile_cache import use_compile_cache as program_cache
    program_cache()
    return path


def peak_bytes(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


TRACE_SECONDS = 8.0


class TraceWindow:
    """Profiles ``seconds`` from the middle of the measured window: the
    profiler drops events past some hundred thousand a run, so a whole
    window of a busy cell would read partly empty.  The cell calls
    :meth:`poll` once an iteration; ``t0``/``t1`` are the host clock's
    bounds of the traced part, which the ``bench:window`` span marks in
    the trace."""

    def __init__(self, log_dir: str, seconds: float, window: float):
        self.log_dir, self.seconds = log_dir, min(seconds, window)
        self.offset = (window - self.seconds) / 2
        self.start = self.t0 = self.t1 = None
        self._span = None

    def poll(self) -> None:
        import jax
        now = time.perf_counter()
        if self.start is None:
            self.start = now
        if self.t0 is None and now - self.start >= self.offset:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench:window")
            self._span.__enter__()
            self.t0 = time.perf_counter()
        elif self.t1 is None and self.t0 is not None \
                and now - self.t0 >= self.seconds:
            self.close()

    def close(self) -> None:
        import jax
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()


def no_trace() -> None:
    pass


def make_cell(spec: dict, seed: int, **kw):
    """The cell of the mix's ``mode``: class ``Cell`` of
    ``bench/<mode>_cell.py``, found by name."""
    import importlib
    mod = importlib.import_module(f"bench.{spec['mix']['mode']}_cell")
    return mod.Cell(spec["config"], spec["mix"], seed, **kw)


# -- one run ------------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: pathlib.Path = ROOT, require_chip: bool = True,
        cell_kwargs: dict | None = None, t_start: float = T_START) -> dict:
    spec = load_spec(root, workload)
    import jax
    dev = devices(spec["cell"]["chips"], require_chip)
    from bench import peaks
    peak = peaks.peaks(dev["kind"]) if require_chip else None
    use_compile_cache(root)
    # before repro.kernels.dispatch is imported: it loads the table then
    os.environ["REPRO_AUTOTUNE_CACHE"] = autotune_table(
        root, jax.__version__, dev["kind"])
    compiles = CompileCounter()
    compiles.install()

    cell = make_cell(spec, seed, **(cell_kwargs or {}))
    cell.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = root / ".cache" / "bench" / "trace" / workload
    tracer = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = TraceWindow(str(trace_dir), TRACE_SECONDS, seconds)
    before = compiles.total
    cell.window(seconds, tracer.poll if tracer else no_trace)
    in_window = compiles.total - before
    if tracer:
        tracer.close()
    out = cell.drain()
    mem = peak_bytes(jax)
    cell.release()
    gc.collect()
    numbers = cell.numbers(cell.reference())

    # the limits file names the numbers compared; a number it leaves out
    # (one no control or fault separates, PERF.md) is not judged
    checks = {name: {"value": numbers.get(name), "limit": lim["limit"]}
              for name, lim in spec["limits"].items()}
    correct = (bool(checks) and out["failed"] == 0
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))

    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {},
              "device": {"platform": dev["platform"], "kind": dev["kind"],
                         "count": spec["cell"]["chips"],
                         "memory_peak_bytes": mem}}
    if not trace:
        values = {"setup_s": setup_s, **out["metrics"]}
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        from bench import trace_reduce
        summary = trace_reduce.reduce(str(trace_dir))
        ctx = {**out["ctx"], "peaks": peak, "config": spec["config"],
               "mix": spec["mix"], "compiles_in_window": in_window,
               "trace_t0": tracer.t0, "trace_t1": tracer.t1}
        for m in spec["per_layer"]:
            value = metric_reader(root, m["name"])(ctx, summary)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": units[m["name"]]}
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
        result["idle_by_phase"] = summary.idle_by_phase
    result["compiles_in_window"] = in_window
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
