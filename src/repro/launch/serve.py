"""Serving CLI over the ``repro.serve`` continuous-batching engine.

Mixed-length prompts, per-request budgets, greedy/temperature/top-k
sampling, an optionally DFXP-packed KV-cache pool, the fused
flash-decode attention kernel (``--fused-decode``: dequantize in the
attention tile loads, no per-layer f32 K/V materialization), and
chunked prefill (``--prefill-chunk C``: immediate admission, one
C-token chunk per engine step interleaved with decode, one prefill jit
for any prompt length):

  PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b --smoke \
      --num-requests 4 --prompt-len 8,16,32 --max-new 16 --cache-bits 8 \
      --fused-decode --prefill-chunk 8

Robustness controls: ``--queue-cap`` (reject-on-full admission),
``--deadline-ms`` (queued and in-flight expiry), and ``--chaos [SEED]``
(seeded fault-injection sweep — logit NaNs, KV bit flips, admission
delays, page squeezes — with the event log printed and optionally
written to ``--fault-log``).  A per-request status table prints at exit
either way; see ``repro.serve.engine.RequestStatus``.

``Engine`` below is the *lockstep reference*: batched prefill, then every
sequence decodes the same number of steps at one shared position. It frees
no slots and admits nothing mid-decode — kept (batch is implied by the
prompts' shape) because its greedy tokens are the bit-for-bit anchor the
float32-mode ``repro.serve.ServeEngine`` is tested against.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro import configs
from repro.compile_cache import use_compile_cache
from repro.core import ScaleState
from repro.core.policy import PrecisionPolicy
from repro.dist import MeshConfigError, serve_pod_ctx
from repro.launch.mesh import make_serve_mesh
from repro.models import transformer as T
from repro.serve import (
    EngineOptions,
    FaultHarness,
    SamplerConfig,
    ServeEngine,
    chaos_plan,
)


class Engine:
    """Lockstep reference: batched prefill + fixed-step greedy decode."""

    def __init__(self, cfg, policy, params, *, max_len: int):
        self.cfg, self.policy, self.params = cfg, policy, params
        self.max_len = max_len
        gs = T.group_shapes(cfg)
        self.exps = ScaleState.create(gs, -6.0).exps
        self.sinks = {n: jnp.zeros(s + (3,), jnp.float32)
                      for n, s in gs.items() if n.startswith("g:")}
        self._decode = jax.jit(self._decode_impl)
        self._prefill = jax.jit(self._prefill_impl)

    def _prefill_impl(self, tokens):
        batch = {"tokens": tokens}
        logits, _, cache = T.prefill(self.cfg, self.policy, self.params,
                                     batch, self.exps, self.sinks,
                                     max_cache_len=self.max_len)
        return logits, cache

    def _decode_impl(self, cache, tok, pos):
        logits, _, cache = T.decode_step(self.cfg, self.policy, self.params,
                                         cache, tok, pos, self.exps,
                                         self.sinks)
        return logits, cache

    def generate(self, prompts: jnp.ndarray, max_new: int):
        """``prompts``: [B, S] token ids. Returns [B, max_new] (greedy)."""
        B, S = prompts.shape
        logits, cache = self._prefill(prompts)
        outs = []
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(max_new):
            outs.append(tok)
            logits, cache = self._decode(cache, tok, jnp.int32(S + i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return jnp.stack(outs, axis=1)


def _parse_lens(spec: str):
    return [int(x) for x in spec.split(",") if x]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--arithmetic", default="dfxp")
    ap.add_argument("--num-requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=0,
                    help="concurrent slots (default: min(num-requests, 4))")
    ap.add_argument("--prompt-len", default="32",
                    help="prompt length, or comma list cycled over requests "
                         "(mixed lengths prefill as separate length groups)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-bits", type=int, default=0, choices=(0, 8, 16),
                    help="KV-cache storage: 0=float32, 8/16=DFXP-packed "
                         "mantissas with per-slot controller-managed scales")
    ap.add_argument("--fused-decode", action="store_true",
                    help="run decode attention as the fused Pallas "
                         "flash-decode kernel directly on the KV pool's "
                         "storage (packed pools dequantize int mantissas "
                         "in the tile loads; no f32 K/V materialization)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: admit any request into any free "
                         "slot immediately and prefill C tokens per engine "
                         "step interleaved with decode (one jit for any "
                         "prompt length; chunk K/V quantized straight into "
                         "the packed pool). 0 = whole-prompt prefill (the "
                         "bit-for-bit reference). Attention-family archs "
                         "only; MoE/SSM stay on the whole-prompt path")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV pool: page size P in tokens (0 = "
                         "slot-major rings). Pages carry their own DFXP "
                         "exponents; requests sharing a prompt prefix map "
                         "the same pages (refcounted, copy-on-write on "
                         "divergence). Implies --prefill-chunk P unless "
                         "set. Dense global-attention archs only")
    ap.add_argument("--mesh", default="",
                    help="device mesh as DATAxMODEL (e.g. 2x1, 1x4): the "
                         "data axis shards the decode KV window (context "
                         "parallelism), the model axis shards the pool's "
                         "kv heads (tensor parallelism). Mutually "
                         "exclusive with --tp/--cp")
    ap.add_argument("--tp", type=int, default=1,
                    help="serving tensor parallelism: shard the KV pool's "
                         "kv-head axis over N devices (params replicated; "
                         "greedy streams bit-identical to single-device "
                         "with interpret-mode kernels, logits within "
                         "rounding on a TPU)")
    ap.add_argument("--cp", type=int, default=1,
                    help="serving context parallelism: shard the decode KV "
                         "window over N devices (long-context slots; exact "
                         "log-sum-exp merge). Slot-major pools only — "
                         "incompatible with --page-size")
    ap.add_argument("--sampler", default="greedy",
                    choices=("greedy", "temperature", "top_k"))
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="admission control: bound the waiting queue; a "
                         "submit finding it full resolves REJECTED (empty "
                         "result, terminal status) instead of queueing. "
                         "0 = unbounded")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline from submit; expired "
                         "requests (queued or mid-decode) resolve "
                         "TIMED_OUT with the tokens harvested so far. "
                         "0 = no deadline")
    ap.add_argument("--chaos", type=int, nargs="?", const=0, default=None,
                    metavar="SEED",
                    help="fault-injection sweep: drive a seeded random mix "
                         "of logit NaNs, KV bit flips, admission delays, "
                         "and (paged pools) a page squeeze through the "
                         "run, then print the fault log. The engine must "
                         "drain with terminal statuses either way")
    ap.add_argument("--fault-log", default="",
                    help="with --chaos: write the harness event log (JSON) "
                         "to this path")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(engine-step spans, request lifecycle instants, "
                         "fault events, queue counters) to this path — "
                         "open in chrome://tracing or ui.perfetto.dev")
    ap.add_argument("--numerics-log", default="",
                    help="write the §5 numeric-health timeline (per-layer/"
                         "per-slot KV exponents, overflow rates, controller "
                         "up/down moves) as JSONL to this path; packed "
                         "pools (--cache-bits 8|16) only")
    ap.add_argument("--numerics-every", type=int, default=0,
                    help="numerics sampling cadence in engine steps "
                         "(default: the cache controller's update "
                         "interval — one sample per decision window)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve the live metrics registry as Prometheus "
                         "text on http://127.0.0.1:PORT/metrics (stdlib "
                         "http.server; 0 picks an ephemeral port)")
    ap.add_argument("--metrics-out", default="",
                    help="append a final JSONL snapshot of the metrics "
                         "registry (counters/gauges/histograms) to this "
                         "path at exit")
    ap.add_argument("--profile", action="store_true",
                    help="profile kernel dispatch: per-bucket block-"
                         "selection calls, autotune cache hits/misses, "
                         "compiles and measured us, printed as a table "
                         "(and dumped to the trace when --trace-out)")
    args = ap.parse_args(argv)
    use_compile_cache()

    demo_chaos = args.chaos is not None and not args.smoke \
        and args.arch == "llama3_8b"
    if demo_chaos:
        # the bare `--chaos` sweep is a diagnostic demo: run it on the
        # smoke config with the stack that exercises every code path the
        # trace/numerics outputs exist to show — int8 packed pages
        # (controller moves), a deliberately tight page arena
        # (exhaustion -> preemption), a fast controller cadence
        args.smoke = True
        if args.cache_bits == 0:
            args.cache_bits = 8
        if args.page_size == 0:
            args.page_size = 4

    # mesh resolution: reject incoherent combinations here, as typed
    # MeshConfigErrors, instead of letting them surface as late jit or
    # GSPMD failures mid-serve
    tp, cp = args.tp, args.cp
    if args.mesh:
        if tp != 1 or cp != 1:
            raise MeshConfigError("--mesh and --tp/--cp are mutually "
                                  "exclusive; pick one spelling")
        try:
            cp, tp = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            raise MeshConfigError(
                f"--mesh {args.mesh!r} is not DATAxMODEL (e.g. 2x1, 1x4)")
    if cp > 1 and args.page_size:
        raise MeshConfigError(
            "--cp cannot shard a paged arena (--page-size): pages tile "
            "the window axis CP would shard — drop one of the two")
    dist = mesh = None
    if tp > 1 or cp > 1:
        dist = serve_pod_ctx(tp=tp, cp=cp)
        mesh = make_serve_mesh(tp=tp, cp=cp)   # raises if devices < tp*cp
        print(f"mesh: data={cp} (cp) x model={tp} (tp) over "
              f"{jax.device_count()} devices")

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    policy = PrecisionPolicy(args.arithmetic, fused_decode=args.fused_decode,
                             prefill_chunk=args.prefill_chunk,
                             page_size=args.page_size)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    lens = _parse_lens(args.prompt_len)
    slots = args.slots or min(args.num_requests, 4)
    scfg = SamplerConfig(kind=args.sampler, temperature=args.temperature,
                         top_k=args.top_k if args.sampler == "top_k" else 0)

    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer()
    num_log = None
    if args.numerics_log:
        from repro.obs import NumericsLog
        num_log = NumericsLog(args.numerics_log)
    if args.profile:
        from repro.kernels import dispatch
        dispatch.profile_enable(True)

    cache_cfg = None
    n_pages = None
    if demo_chaos and args.cache_bits:
        from repro.serve import CacheQuantConfig
        cache_cfg = CacheQuantConfig(width=args.cache_bits,
                                     update_interval=4)
        if args.page_size:
            # under-provision the arena: roughly two slots' worth of
            # pages short of full residency, so concurrent decode
            # exhausts it and the preemption path shows up on the trace
            nblocks = -(-(max(lens) + args.max_new) // args.page_size)
            n_pages = 1 + nblocks * max(slots - 2, 1)

    harness = None
    if args.chaos is not None:
        harness = FaultHarness(
            chaos_plan(args.chaos, list(range(args.num_requests)),
                       n_steps=4 * args.max_new,
                       squeeze_pages=4 if args.page_size else 0),
            seed=args.chaos)
    opts = EngineOptions(cache_bits=args.cache_bits, sampler_cfg=scfg,
                         cache_cfg=cache_cfg, n_pages=n_pages,
                         seed=args.seed,
                         queue_cap=args.queue_cap or None,
                         deadline_ms=args.deadline_ms or None,
                         faults=harness,
                         tracer=tracer, numerics_log=num_log,
                         numerics_every=args.numerics_every or None)
    max_len = max(lens) + args.max_new
    if cp > 1 and max_len % cp:
        max_len += cp - max_len % cp   # the KV window shards evenly
    eng = ServeEngine(cfg, policy, params, max_slots=slots,
                      max_len=max_len, options=opts,
                      dist=dist, mesh=mesh)
    server = None
    if args.metrics_port is not None:
        from repro.obs import start_http_server
        server = start_http_server(eng.metrics.registry, args.metrics_port)
        print(f"metrics: http://127.0.0.1:{server.server_address[1]}/metrics")
    prompts = {}
    for i in range(args.num_requests):
        plen = lens[i % len(lens)]
        prompt = jax.random.randint(jax.random.PRNGKey(1000 + i), (plen,), 0,
                                    cfg.vocab_size)
        prompts[eng.submit(prompt, max_new=args.max_new)] = prompt
    uids = list(prompts)
    out = eng.run()
    stats = eng.stats()
    print(f"served {stats['requests_finished']} requests, "
          f"{stats['new_tokens']} tokens in {stats['wall_s']:.2f}s "
          f"({stats['tok_per_s']:.1f} tok/s, "
          f"ttft mean {stats['ttft_mean_s'] * 1e3:.0f}ms)")
    print("stats:", json.dumps({k: round(v, 4) if isinstance(v, float) else v
                                for k, v in stats.items()}))
    print("sample:", out[0][:8].tolist())
    print(f"{'uid':>5} {'status':>10} {'tokens':>7} {'preempts':>9}")
    for u in uids:
        st = eng.status(u)
        tr = eng.metrics.traces[u]
        print(f"{u:>5} {st.value if st else '?':>10} {out[u].size:>7} "
              f"{tr.preempts:>9}")
    if harness is not None:
        print("faults:", json.dumps(harness.summary()["event_counts"]))
        if args.fault_log:
            with open(args.fault_log, "w") as f:
                json.dump(harness.summary(), f, indent=2)
            print(f"fault log written to {args.fault_log}")
    if args.profile:
        from repro.kernels import dispatch
        if tracer is not None:
            dispatch.profile_trace_counters(tracer)
        print("dispatch profile:")
        print(dispatch.profile_table())
    if tracer is not None:
        spans = len(tracer.span_names())
        tracer.export(args.trace_out)
        print(f"trace: {spans} spans, {len(tracer.events)} events -> "
              f"{args.trace_out}")
    if num_log is not None:
        from repro.obs import count_moves
        print(f"numerics: {len(num_log.records)} records, "
              f"{count_moves(num_log.records)} controller moves -> "
              f"{args.numerics_log}")
        num_log.close()
    if args.metrics_out:
        eng.metrics.registry.snapshot_jsonl(args.metrics_out,
                                            {"final": True})
        print(f"metrics snapshot appended to {args.metrics_out}")
    if server is not None:
        server.shutdown()
    return {"tokens": out, "stats": stats, "prompts": prompts,
            "status": {u: eng.status(u) for u in uids}, "engine": eng}


if __name__ == "__main__":
    main()
