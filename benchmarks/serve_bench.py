"""Serving benchmarks: sequential vs continuous-batched, f32 vs packed,
fused vs unfused decode attention, whole-prompt vs chunked prefill.

Rows follow the repo convention ``(name, us_per_call, derived)`` where
``us_per_call`` is microseconds per generated token and ``derived`` is the
aggregate tok/s.  The ``serve_mem_*`` rows carry a fourth ``"mem"`` kind
field: their value column is **pool HBM bytes per request** (slot-major:
the full ``max_len`` reservation one slot holds; paged: page size × the
wave's peak resident pages / requests) and ``derived`` is the whole
arena in MB — deterministic at fixed shapes, so the regression gate
diffs them as direct ratios instead of median-normalized times.  Four
time comparisons matter:

* ``serve_sequential_f32`` vs ``serve_batched_f32`` — the continuous-
  batching win: N requests through 1 slot vs N slots.
* ``serve_batched_f32`` vs ``serve_batched_int8``/``int16`` — the packed
  KV-pool tax/win. On CPU the packing math is overhead; on an HBM-bound
  accelerator the 4×/2× smaller cache is the capacity multiplier.
* ``serve_batched_*`` vs ``serve_batched_*_fused`` — the flash-decode
  kernel (``--fused-decode``) vs the ``codec.load`` + einsum composite,
  per cache width. On CPU the fused rows time interpret-mode Pallas
  (reference semantics, slower); on a compiled TPU backend the fused
  int8/int16 rows are where the smaller cache turns into decode
  *bandwidth* — no per-layer f32 K/V materialization on the hot path
  (``benchmarks/roofline.py --kv-report`` prints the expected ratios).
* ``serve_batched_*`` vs ``serve_batched_*_chunked`` — the chunked
  prefill scheduler (``--prefill-chunk``): mixed-length requests admit
  immediately and prefill one chunk per step interleaved with decode,
  ONE prefill jit total, vs the grouped whole-prompt path compiling per
  (group, length).  The bench prompt mix has non-partnered lengths, so
  the chunked rows also price the TTFT scheduling the gate protects.

The ``serve_sharded_*`` rows time the mesh-sharded engine (TP over the
KV pool's head axis; CP over the decode window) on meshes cut from this
process's devices (a CPU run forces 2 for ``--tiny``, 4 for the full
shapes, with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``) —
``us_per_call`` stays microseconds per generated token and ``derived``
is **per-device** tok/s (aggregate / mesh size), the
scaling number the nightly lane tracks.  On CPU the virtual devices
share cores, so these rows price the sharding machinery (shard_map
dispatch, o-gather, constraint re-application), not real-accelerator
scaling; the gate keeps them honest the same way as every other row.

``tiny=True`` is the CI smoke contract (2 mixed-length requests, int8
cache, every request finishing with its full budget — execution, not
perf) AND the recording protocol of the committed ``BENCH_serve.json``:
the CI bench-regression gate (``benchmarks/check_regression.py``) diffs a
fresh ``--tiny`` run against the committed file row-by-row, so the
baseline must be recorded at the same shapes.  Tiny records tp2 sharded
rows only; the full (nightly) shapes add tp4 and cp2.

Each timed row also captures the engine's ``repro.obs`` metrics-registry
snapshot (TTFT / queue-wait / tok-per-request histograms, counters) into
the module-level ``OBS`` dict — ``benchmarks/run.py`` persists it as the
``"obs"`` key of ``BENCH_serve.json`` and ``benchmarks/make_report.py
--serve-json`` renders the histograms from it.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro import configs
from repro.core.policy import PrecisionPolicy
from repro.models import transformer as T
from repro.serve import ServeEngine

# row name -> obs metrics-registry snapshot of that row's measured waves
# (filled by run(); persisted into BENCH_serve.json by benchmarks/run.py)
OBS: dict = {}


def _wave(eng, prompts, max_new):
    uids = [eng.submit(p, max_new=max_new) for p in prompts]
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    assert set(uids) <= set(out), "request dropped"
    assert all(len(out[u]) == max_new for u in uids), "short generation"
    return sum(len(out[u]) for u in uids), dt


def _drive(cfg, params, prompts, max_new, *, slots, cache_bits, fused=False,
           chunk=0, waves=1, page=0):
    eng = ServeEngine(cfg, PrecisionPolicy("float32", fused_decode=fused,
                                           prefill_chunk=chunk,
                                           page_size=page),
                      params, max_slots=slots,
                      max_len=max(len(p) for p in prompts) + max_new,
                      cache_bits=cache_bits)
    _wave(eng, prompts, max_new)            # warmup: pays every compile
    eng.reset_metrics()
    best = None
    for _ in range(waves):                  # best-of: the gate's metric —
        toks, dt = _wave(eng, prompts, max_new)   # shared CI machines
        if best is None or dt < best[1]:          # jitter the mean badly
            best = (toks, dt)
    # obs snapshot spans every measured wave (warmup excluded by the reset)
    return best + (eng.metrics.registry.snapshot(),)


def run(tiny: bool = False):
    cfg = configs.get_smoke("llama3_8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    if tiny:
        lens, max_new, slots, chunk = (5, 9), 4, 2, 4
    else:
        lens, max_new, slots, chunk = \
            (16, 32, 32, 16, 32, 32, 16, 32), 24, 4, 16
    prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(100 + i),
                                             (plen,), 0, cfg.vocab_size))
               for i, plen in enumerate(lens)]

    rows = []
    OBS.clear()
    variants = [("serve_sequential_f32", 1, 0, False, 0),
                ("serve_batched_f32", slots, 0, False, 0),
                ("serve_batched_f32_fused", slots, 0, True, 0),
                ("serve_batched_f32_chunked", slots, 0, False, chunk),
                ("serve_batched_int8", slots, 8, False, 0),
                ("serve_batched_int8_fused", slots, 8, True, 0),
                ("serve_batched_int8_chunked", slots, 8, False, chunk),
                ("serve_batched_int8_chunked_fused", slots, 8, True, chunk),
                ("serve_batched_int16", slots, 16, False, 0),
                ("serve_batched_int16_fused", slots, 16, True, 0)]
    for name, n_slots, bits, fused, pc in variants:
        toks, dt, snap = _drive(cfg, params, prompts, max_new, slots=n_slots,
                                cache_bits=bits, fused=fused, chunk=pc,
                                waves=3 if tiny else 1)
        OBS[name] = snap
        rows.append((name, dt / toks * 1e6, toks / dt))
    rows += _memory_rows(cfg, params, prompts, max_new, slots=slots,
                         page=chunk)
    rows += _sharded_rows(lens, max_new, slots, tiny=tiny)
    return rows


def _sharded_rows(lens, max_new, slots, *, tiny):
    """Mesh-sharded engine rows, built in this process from its devices.

    A TPU belongs to one process, so the meshes are cut from the devices
    this process already sees; a CPU run forces host devices before it
    starts (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    Fewer devices than a mesh needs raise ``MeshConfigError``.  The timer
    brackets only ``eng.run()``; the warmup wave pays every compile.
    """
    from repro.dist import serve_pod_ctx
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import EngineOptions

    variants = [("serve_sharded_tp2_f32", 2, 1, 0, False),
                ("serve_sharded_tp2_int8_fused", 2, 1, 8, True)]
    if not tiny:
        variants += [("serve_sharded_tp4_int8_fused", 4, 1, 8, True),
                     ("serve_sharded_cp2_f32", 1, 2, 0, False)]
    rows = []
    for name, tp, cp, bits, fused in variants:
        mesh = make_serve_mesh(tp=tp, cp=cp)
        cfg = configs.get_smoke("llama3_8b")
        if tp > cfg.num_kv_heads:
            cfg = dataclasses.replace(cfg, num_kv_heads=tp)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(100 + i),
                                                 (n,), 0, cfg.vocab_size))
                   for i, n in enumerate(lens)]
        max_len = max(lens) + max_new
        max_len += -max_len % cp              # CP shards the window evenly
        eng = ServeEngine(cfg, PrecisionPolicy("float32", fused_decode=fused),
                          params, max_slots=slots, max_len=max_len,
                          options=EngineOptions(cache_bits=bits),
                          dist=serve_pod_ctx(tp=tp, cp=cp), mesh=mesh)
        _wave(eng, prompts, max_new)          # warmup: pays every compile
        best = min((_wave(eng, prompts, max_new)
                    for _ in range(3 if tiny else 1)), key=lambda r: r[1])
        toks, dt = best
        rows.append((name, dt / toks * 1e6, toks / dt / (tp * cp)))
    return rows


def _memory_rows(cfg, params, prompts, max_new, *, slots, page):
    """Pool HBM bytes/request, paged-vs-slot, f32/int8 — the capacity
    comparison the paged pool exists for.  Slot-major reserves the
    worst-case ``max_len`` ring per slot up front; paged residency is
    the wave's peak page count, measured by actually serving the wave
    (page size == the chunk size the timed ``*_chunked`` rows use).
    ``kind="mem"``: the CI gate diffs these rows as direct ratios."""
    from repro.serve import paged as paged_mod

    max_len = max(len(p) for p in prompts) + max_new
    rows = []
    for bits in (0, 8):
        tag = "f32" if bits == 0 else f"int{bits}"
        eng = ServeEngine(cfg, PrecisionPolicy("float32"), params,
                          max_slots=slots, max_len=max_len,
                          cache_bits=bits)
        per_req = float(paged_mod.slot_nbytes(eng._pool))
        rows.append((f"serve_mem_{tag}_slot", per_req,
                     per_req * slots / 1e6, "mem"))
        eng = ServeEngine(cfg, PrecisionPolicy("float32",
                                               prefill_chunk=page,
                                               page_size=page),
                          params, max_slots=slots, max_len=max_len,
                          cache_bits=bits)
        _wave(eng, prompts, max_new)
        st = eng.stats()
        page_b = paged_mod.page_nbytes(eng._pool)
        per_req = page_b * st["pages_in_use_peak"] / len(prompts)
        rows.append((f"serve_mem_{tag}_paged", per_req,
                     page_b * eng._alloc.n_pages / 1e6, "mem"))
    return rows
