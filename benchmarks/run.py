# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# Paper artifacts (Table 3, Figures 1-4) train the maxout network under
# each arithmetic on the scaled synthetic task; ``derived`` is the final
# loss normalized by the fp32 baseline (the paper's normalized test error).
# Kernel rows report microseconds per call; ``derived`` is MFLOP for
# matmuls. Run with: PYTHONPATH=src python -m benchmarks.run [--quick]
#
# JSON-emitting suites each persist their rows to a per-suite file —
# ``kernels`` → BENCH_kernels.json (jnp-composite vs fused Pallas pairs),
# ``serve`` → BENCH_serve.json (sequential vs continuous-batched,
# f32 vs packed-cache tok/s) — the perf-trajectory record; ``--tiny``
# shrinks both to CI-smoke shapes that assert execution, not perf.
# ``--json-out`` overrides the path when exactly one such suite runs.
import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="table3 + kernels only")
    ap.add_argument("--only", default="")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-smoke shapes for the kernels/serve suites")
    ap.add_argument("--json-out", default="",
                    help="override the JSON path (needs exactly one "
                         "JSON-emitting suite selected, e.g. --only serve)")
    ap.add_argument("--profile", action="store_true",
                    help="enable repro.kernels.dispatch profiling and print "
                         "the per-bucket call/hit/compile table to stderr "
                         "(fails if nothing was recorded)")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache

    from . import kernels_bench, paper_tables, serve_bench

    use_compile_cache()

    if args.profile:
        from repro.kernels import dispatch
        dispatch.profile_enable(True)

    suites = [
        ("table3", paper_tables.table3_formats, None),
        ("fig1", paper_tables.fig1_radix, None),
        ("fig2", paper_tables.fig2_comp_width, None),
        ("fig3", paper_tables.fig3_update_width, None),
        ("fig4", paper_tables.fig4_overflow_rate, None),
        ("kernels", lambda: kernels_bench.run(tiny=args.tiny),
         "BENCH_kernels.json"),
        ("serve", lambda: serve_bench.run(tiny=args.tiny),
         "BENCH_serve.json"),
    ]
    if args.quick:
        suites = [s for s in suites if s[0] in ("table3", "kernels")]
    if args.only:
        suites = [s for s in suites if s[0] in args.only.split(",")]
    json_suites = [name for name, _, path in suites if path]
    if args.json_out and len(json_suites) != 1:
        ap.error(f"--json-out needs exactly one JSON-emitting suite "
                 f"selected, got {json_suites}")

    print("name,us_per_call,derived")
    for name, fn, json_path in suites:
        try:
            rows = list(fn())
            if not rows:
                # a suite that silently emits nothing would commit an empty
                # BENCH_*.json and read as "measured, no regression"
                raise RuntimeError(f"suite {name!r} emitted no rows")
            for row in rows:
                print(f"{row[0]},{row[1]:.1f},{row[2]:.4f}", flush=True)
            # rows may carry a 4th "kind" field ("time" default; "mem"
            # rows are byte counts the gate diffs as direct ratios)
        except Exception as e:  # keep the suite running
            print(f"{name}/ERROR,0,0  # {e}", file=sys.stderr)
            raise
        if json_path:
            import jax
            out_path = args.json_out or json_path
            payload = {
                "meta": {"backend": jax.default_backend(),
                         "suite": name, "tiny": args.tiny},
                "rows": [{"name": r[0], "us_per_call": round(r[1], 1),
                          "derived": r[2],
                          "kind": r[3] if len(r) > 3 else "time"}
                         for r in rows],
            }
            if name == "serve" and serve_bench.OBS:
                # per-row obs metrics snapshots (TTFT/tok-s histograms);
                # render with: python -m benchmarks.make_report --serve-json
                payload["obs"] = serve_bench.OBS
            with open(out_path, "w") as f:
                json.dump(payload, f, indent=1)
            print(f"# wrote {len(rows)} {name} rows -> {out_path}",
                  file=sys.stderr)

    if args.profile:
        from repro.kernels import dispatch
        stats = dispatch.profile_stats()
        if not stats:
            raise SystemExit("--profile: dispatch recorded no buckets — "
                             "profiling hooks are broken or no kernel "
                             "dispatch ran")
        print(dispatch.profile_table(), file=sys.stderr)


if __name__ == '__main__':
    main()
