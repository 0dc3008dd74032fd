#!/usr/bin/env python3
"""Run the main path once on a TPU: the quickest proof the system starts.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # sharded serving on a 2x2 host

With no option, on one chip, in one process:

  1. kernels — the fused qmatmul on 10-bit operands against their exact
     product, and each fused attention kernel against its jnp reference
     on a small input, all compiled;
  2. train — ``repro.launch.train`` runs full-width ``mamba2_370m`` under
     DFXP 10/12 with the fused qmatmul for 6 steps; every step must
     resolve OK with a finite loss;
  3. serve — ``repro.launch.serve`` runs full-width ``granite_moe_1b`` on
     the int8 KV pool with the fused decode kernel, 4 requests of 16
     tokens, twice (the first run pays the compiles); every request
     must end OK with all its tokens.

``--four-chips`` runs only the serve phase at ``--tp 4`` and at
``--mesh 2x2`` and compares their logits and greedy tokens with the
same requests on one chip (see :func:`four_chip_phase` for the
tolerance).

Every phase also fails if a kernel ran in interpret mode.  The last
line of stdout is ``{"ok": true, "device": {...}}`` as JAX reports the
devices; a failed check, or a machine without a TPU, exits non-zero
without it.  Everything runs in this one process: a TPU belongs to one
process at a time.

JAX's compilation cache is kept where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<checkout>/.cache/jax``.  The autotuner's measurements
go to a table of this script's own, never the shared
``.cache/autotune.json``, named for the kernel sources, the JAX version
and the chip: a table measured by other code or on another chip is never
read, while a second run of the same checkout reuses the first run's
tiles — and so finds its programs in the compilation cache.  Compile
seconds are JAX's own backend-compile events (a cache hit counts its
load time).
"""
from __future__ import annotations

import argparse
import gc
import hashlib
import json
import math
import os
import pathlib
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TRAIN_ARGV = ["--arch", "mamba2_370m", "--arithmetic", "dfxp",
              "--fused-matmul", "--calibrate-steps", "2", "--steps", "6",
              "--global-batch", "2", "--seq-len", "256", "--log-every", "0"]
SERVE_ARGV = ["--arch", "granite_moe_1b", "--cache-bits", "8",
              "--fused-decode", "--num-requests", "4", "--slots", "4",
              "--prompt-len", "64,128", "--max-new", "16"]
# Attention kernel outputs against the jnp reference, as a share of the
# output's scale.  Both keep f32 products (repro.core.policy.
# MATMUL_PRECISION); f32 rounding through 64-wide dots and a softmax over
# 300 positions stays below about 5e-5 of it.  An f32 dot run as one bf16
# MXU pass (8 significant bits) is off by 2e-3 or more, and a wrong head,
# mask or split combine by the size of a value row, ~1.
KERNEL_RTOL = 1e-4
# Sharded serving against one chip: teacher-forced logits may differ by
# this share of the request's max|logit|.  Sharding reorders f32 sums
# only, but a reordering can flip a DFXP rounding (activations at 10
# bits, K/V at 8) or a top-k expert choice at a near-tie, and the flip
# carries through the layers above and the later positions: on a TPU
# v5e, --tp 4 moved granite_moe_1b's logits by up to 0.036 of max|logit|
# with all 64 greedy tokens equal.  A shard holding the wrong kv heads,
# or a context-parallel merge that skips its rescale, moved them by 0.34
# to 1.43 (smoke size, CPU).
LOGIT_TOL = 2.0 ** -4


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _flag(argv, name):
    return argv[argv.index(name) + 1]


def qmatmul_check():
    """The fused DFXP qmatmul, compiled, in all three layouts: 10-bit
    operands must multiply exactly.

    One operand takes full 10-bit mantissas (9 significant bits, which
    one bf16 MXU pass would round), the other 8-bit ones, so every
    partial sum is an integer of at most 256 * 512 * 128 = 2**24 steps:
    f32 holds it exactly in any order of summation, and the result must
    equal the float64 product bit for bit.  Run with each operand wide.
    """
    import jax
    import numpy as np

    from repro.kernels.qmatmul.ops import qmm

    n, e_a, e_b = 256, -4.0, -6.0
    rng = np.random.default_rng(0)

    def mantissas(shape, bits):
        return rng.integers(-2 ** (bits - 1), 2 ** (bits - 1), shape)

    for kind in ("nn", "nt", "tn"):
        for wide in ("a", "b"):
            ma = mantissas((n, n), 10 if wide == "a" else 8)
            mb = mantissas((n, n), 10 if wide == "b" else 8)
            exact = {"nn": ma @ mb, "nt": ma @ mb.T,
                     "tn": ma.T @ mb}[kind] * 2.0 ** (e_a + e_b)
            a = (ma * 2.0 ** e_a).astype(np.float32)
            b = (mb * 2.0 ** e_b).astype(np.float32)
            got = np.asarray(jax.block_until_ready(qmm(
                a, b, e_a, e_b, kind=kind, width_a=10, width_b=10,
                blocks=(128, 128, 128), interpret=False)), np.float64)
            err = float(np.max(np.abs(got - exact)))
            print(f"kernel qmatmul {kind} {wide} 10-bit: max|err| {err!r} "
                  f"against the exact product", flush=True)
            check(err == 0.0, f"qmatmul {kind}: 10-bit product off by {err}")


def kernel_phase():
    """The fused qmatmul and each fused attention kernel, compiled, vs
    their references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    qmatmul_check()

    from repro.kernels.attn import ops as A
    from repro.kernels.attn import ref as R

    K, G, hd, B, W, C, P, nb = 8, 2, 64, 2, 300, 16, 16, 8
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32)

    def pool(shape, width):
        if width is None:
            return normal(shape)
        return jax.random.randint(next(keys), shape, -128, 128).astype(
            jnp.int8)

    q, qc = normal((B, K, G, hd)), normal((B, C, K, G, hd))
    kn, vn = normal((B, C, K, hd)), normal((B, C, K, hd))
    e = jnp.array([-5.0, -6.0])
    pos = jnp.broadcast_to(jnp.where(jnp.arange(W) < 280, jnp.arange(W), -1),
                           (B, W)).astype(jnp.int32)
    n_pages = 1 + B * nb
    bt = (1 + jnp.arange(B * nb, dtype=jnp.int32)).reshape(B, nb)
    ppos = jnp.broadcast_to(jnp.where(jnp.arange(nb * P) < 120,
                                      jnp.arange(nb * P), -1),
                            (B, nb * P)).astype(jnp.int32)
    pe = -5.0 - (jnp.arange(n_pages) % 3).astype(jnp.float32)
    nv = jnp.full((B,), C, jnp.int32)
    kw = dict(scale=0.125)

    for width in (8, None):
        tag = "int8" if width else "f32"
        k, v = pool((B, W, K, hd), width), pool((B, W, K, hd), width)
        pk, pv = (pool((n_pages, P, K, hd), width),
                  pool((n_pages, P, K, hd), width))
        ekw = dict(width=width, **kw)
        rkw = dict(k_exp=e, v_exp=e, **ekw)
        pkw = dict(k_exp=pe, v_exp=pe, **ekw)
        qp, p0 = jnp.array([279, 250]), jnp.array([280, 200])
        pqp, pp0 = jnp.array([119, 100]), jnp.array([120, 100])
        runs = {
            "flash_decode": (
                lambda: A.flash_decode(q, k, v, pos, qp, e, e, block_w=128,
                                       interpret=False, **ekw),
                lambda: R.decode_attention_ref(q, k, v, pos, qp, **rkw)),
            "flash_prefill": (
                lambda: A.flash_prefill(qc, kn, vn, k, v, pos, p0, nv, e, e,
                                        block_w=128, interpret=False, **ekw),
                lambda: R.prefill_attention_ref(qc, k, v, pos, kn, vn, p0,
                                                nv, **rkw)),
            "flash_decode_paged": (
                lambda: A.flash_decode_paged(q, pk, pv, bt, ppos, pqp, pe, pe,
                                             interpret=False, **ekw),
                lambda: R.paged_decode_attention_ref(q, pk, pv, bt, ppos, pqp,
                                                     **pkw)),
            "flash_prefill_paged": (
                lambda: A.flash_prefill_paged(qc, kn, vn, pk, pv, bt, ppos,
                                              pp0, nv, pe, pe,
                                              interpret=False, **ekw),
                lambda: R.paged_prefill_attention_ref(qc, pk, pv, bt, ppos,
                                                      kn, vn, pp0, nv,
                                                      **pkw)),
        }
        for name, (kernel, reference) in runs.items():
            t0 = time.perf_counter()
            got = np.asarray(jax.block_until_ready(kernel()))
            dt = time.perf_counter() - t0
            ref = np.asarray(reference())
            err = float(np.max(np.abs(got - ref)))
            tol = KERNEL_RTOL * max(1.0, float(np.max(np.abs(ref))))
            print(f"kernel {name} {tag}: max|err| {err!r} (tol {tol!r}), "
                  f"compile+run {dt:.2f}s", flush=True)
            check(np.all(np.isfinite(got)), f"{name} {tag}: non-finite")
            check(err <= tol, f"{name} {tag}: max|err| {err} > {tol}")


def train_phase(argv=TRAIN_ARGV):
    """``repro.launch.train.main``: every step OK, finite losses."""
    from repro.launch import train

    t0 = time.perf_counter()
    res = train.main(argv)
    wall = time.perf_counter() - t0
    steps = res["steps"]
    for s in steps:
        print(f"train step {s['cursor']}: {s['outcome']} loss {s['loss']!r} "
              f"{s['seconds']:.4f}s", flush=True)
    n = int(_flag(argv, "--steps"))
    check(len(steps) == n and all(s["outcome"] == "ok" for s in steps),
          f"train: outcomes {res['outcomes']} over {len(steps)} attempts, "
          f"want {n} ok")
    check(all(math.isfinite(s["loss"]) for s in steps),
          "train: non-finite loss")
    first = steps[0]["seconds"]
    steady = statistics.median(s["seconds"] for s in steps[1:])
    tokens = int(_flag(argv, "--global-batch")) * int(_flag(argv,
                                                            "--seq-len"))
    print(f"train: first step {first:.2f}s (compile + run), steady step "
          f"{steady:.4f}s ({tokens / steady:.0f} tok/s), phase wall "
          f"{wall:.1f}s (calibration included), final loss "
          f"{res['final_loss']!r}", flush=True)


def forced_logits(eng, prompts, streams):
    """Logits [requests, tokens, vocab] of ``streams`` ({uid: tokens}),
    teacher-forced through ``eng``'s model, pool layout and mesh the way
    its own run went: each prompt prefilled alone into its slot, then all
    slots decoding their stream's tokens in lock step."""
    import contextlib
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.serve import kv_pool, request_key

    cfg, pol, dist, codec = eng.cfg, eng.policy, eng.dist, eng.codec
    kvp = kv_pool.make_kv_pool(
        cfg, pol, dist, max_slots=eng.max_slots, max_len=eng.max_len,
        cache_bits=eng.cache_cfg.width if eng.cache_cfg else 0,
        cache_cfg=eng.cache_cfg, mesh=eng.mesh)

    def pin(pool):
        if kvp.shardings is None:
            return pool
        return jax.lax.with_sharding_constraint(pool, kvp.shardings)

    @jax.jit
    def prefill(w, tokens):
        logits, _, entry = T.prefill(cfg, pol, w[0], {"tokens": tokens},
                                     w[1], w[2], dist,
                                     max_cache_len=eng.max_len)
        return logits, entry

    @functools.partial(jax.jit, donate_argnums=(0,))
    def insert(pool, entry, slot, key):
        return pin(kv_pool.insert(pool, entry, slot, codec, key))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(w, pool, tok, pos):
        logits, _, pool = T.decode_step(cfg, pol, w[0], pool, tok, pos, w[1],
                                        w[2], dist, kv_codec=codec)
        return logits, pin(pool)

    uids = sorted(streams)
    n = len(streams[uids[0]])
    out = np.zeros((len(uids), n, cfg.vocab_size), np.float32)
    w = (eng.params, eng.exps, eng.sinks)
    with (jax.set_mesh(eng.mesh) if eng.mesh is not None
          else contextlib.nullcontext()):
        pool = kvp.pool
        for s, u in enumerate(uids):
            logits, entry = prefill(w, jnp.asarray(prompts[u])[None])
            out[s, 0] = np.asarray(logits)[0]
            pool = insert(pool, entry, jnp.asarray([s], jnp.int32),
                          request_key(eng.seed, u)[None])
        pos = np.array([len(prompts[u]) for u in uids], np.int32)
        for i in range(n - 1):
            tok = np.array([streams[u][i] for u in uids], np.int32)
            logits, pool = decode(w, pool, jnp.asarray(tok),
                                  jnp.asarray(pos + i))
            out[:, i + 1] = np.asarray(logits)
    return out


def serve_phase(argv=SERVE_ARGV, label="serve", forced=False, along=None):
    """``repro.launch.serve.main``: every request OK with all its tokens.

    Returns ``{uid: tokens}`` (numpy) and, with ``forced``, the logits of
    ``along`` (default: the tokens just served) teacher-forced through
    this run's engine (:func:`forced_logits`).
    """
    import numpy as np

    from repro.launch import serve
    from repro.serve import RequestStatus

    t0 = time.perf_counter()
    res = serve.main(argv)
    wall = time.perf_counter() - t0
    max_new = int(_flag(argv, "--max-new"))
    toks = {u: np.asarray(t) for u, t in res["tokens"].items()}
    bad = {u: (st.value, toks[u].size) for u, st in res["status"].items()
           if st is not RequestStatus.OK or toks[u].size != max_new}
    check(len(res["status"]) == int(_flag(argv, "--num-requests"))
          and not bad, f"{label}: requests not OK with {max_new} tokens: "
                       f"{bad}")
    st = res["stats"]
    print(f"{label}: {st['new_tokens']} tokens in {st['wall_s']:.2f}s, "
          f"{st['tok_per_s']:.1f} tok/s, ttft mean "
          f"{st['ttft_mean_s'] * 1e3:.0f} ms max "
          f"{st['ttft_max_s'] * 1e3:.0f} ms, {st['decode_steps']} decode "
          f"steps, phase wall {wall:.1f}s", flush=True)
    logits = None
    if forced:
        logits = forced_logits(res["engine"], res["prompts"], along or toks)
    del res
    gc.collect()           # the engine's params leave the device now
    return toks, logits


def four_chip_phase(argv=SERVE_ARGV,
                    meshes=(("--tp", "4"), ("--mesh", "2x2"))):
    """Sharded serving vs one chip, same requests, same process.

    The one-chip run's greedy streams are teacher-forced through the
    one-chip engine and through each sharded one; at every position the
    two logit vectors must agree within :data:`LOGIT_TOL` of the
    request's max|logit|.  The sharded run's own greedy tokens may then
    leave the one-chip stream only at a near-tie: where a stream first
    differs, its token must lie within ``2 * LOGIT_TOL`` of the one-chip
    best logit there (logits within the tolerance cannot rank two tokens
    further apart).  After that point the contexts differ and nothing
    more is compared.
    """
    import numpy as np

    ref, ref_lg = serve_phase(argv, "serve one chip", forced=True)
    scale = {u: float(np.max(np.abs(ref_lg[i])))
             for i, u in enumerate(sorted(ref))}
    same = sum(int(np.sum(np.argmax(ref_lg[i], -1) == ref[u]))
               for i, u in enumerate(sorted(ref)))
    total = sum(t.size for t in ref.values())
    print(f"serve one chip: distinct tokens per request "
          f"{ {u: len(set(t.tolist())) for u, t in ref.items()} }, "
          f"forced logits reproduce {same}/{total} served tokens",
          flush=True)
    failures = []
    for extra in meshes:
        label = "serve " + " ".join(extra)
        got, lg = serve_phase(argv + list(extra), label, forced=True,
                              along=ref)
        diff, regret = {}, {}
        for i, u in enumerate(sorted(ref)):
            per_pos = np.max(np.abs(lg[i] - ref_lg[i]), axis=-1) / scale[u]
            print(f"{label} request {u}: max|diff|/max|logit| by position "
                  f"{np.array2string(per_pos, precision=4)}", flush=True)
            diff[u] = float(per_pos.max())
            if not np.array_equal(got[u], ref[u]):
                f = int(np.argmin(got[u] == ref[u]))
                row = ref_lg[i, f]
                regret[u] = (f, float(row.max() - row[got[u][f]]) / scale[u])
        equal = sum(int(np.sum(got[u] == ref[u])) for u in ref)
        print(f"{label} vs one chip: forced logits max|diff|/max|logit| "
              f"per request { {u: f'{d:.3g}' for u, d in diff.items()} } "
              f"(tol {LOGIT_TOL!r})", flush=True)
        print(f"{label} vs one chip: {equal}/{total} tokens equal, "
              + ("identical" if not regret else
                 "first divergence (position, regret) per request "
                 f"{ {u: (f, f'{r:.3g}') for u, (f, r) in regret.items()} }"),
              flush=True)
        if max(diff.values()) > LOGIT_TOL:
            failures.append(f"{label}: logits off by {max(diff.values())} "
                            f"> {LOGIT_TOL}")
        if any(r > 2 * LOGIT_TOL for _, r in regret.values()):
            failures.append(f"{label}: a token leaves the one-chip stream "
                            f"away from a near-tie: {regret}")
    check(not failures, "; ".join(failures))


def _peak_bytes(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# XLA compiles (or persistent-cache loads) in this process, counted from
# JAX's monitoring events; a hit records its load time as the duration.
COMPILES = {"seconds": 0.0, "programs": 0, "cache_hits": 0}


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES["seconds"] += secs
        COMPILES["programs"] += 1


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        COMPILES["cache_hits"] += 1


def timed_phase(name, fn, *args, **kwargs):
    """Run one phase and print the compile seconds it spent."""
    before = dict(COMPILES)
    out = fn(*args, **kwargs)
    d = {k: COMPILES[k] - before[k] for k in COMPILES}
    print(f"compile [{name}]: {d['seconds']:.2f}s over {d['programs']} "
          f"programs, {d['cache_hits']} from the persistent cache",
          flush=True)
    return out


def autotune_table(jax, device_kind: str) -> str:
    """This script's own autotune table, named for what its tiles depend
    on: the kernel and dispatch sources, the JAX version and the chip."""
    h = hashlib.sha256(f"{jax.__version__}|{device_kind}".encode())
    kernels = pathlib.Path(ROOT, "src", "repro", "kernels")
    for path in sorted(kernels.rglob("*.py")):
        h.update(path.relative_to(kernels).as_posix().encode())
        h.update(path.read_bytes())
    return os.path.join(ROOT, ".cache", "chip_smoke",
                        f"autotune-{h.hexdigest()[:16]}.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded serving (--tp 4, --mesh 2x2) "
                         "against one chip")
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU, JAX found {dev}", file=sys.stderr)
        return 2
    # before repro.kernels.dispatch is imported: it loads the table then
    os.environ["REPRO_AUTOTUNE_CACHE"] = autotune_table(jax, dev["kind"])
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    try:
        from repro.kernels import _tiling, dispatch

        print(f"device: {dev}", flush=True)
        print(f"compile cache: {cache_dir}", flush=True)
        print(f"autotune table: {os.environ['REPRO_AUTOTUNE_CACHE']} "
              f"({len(dispatch.autotune_cache())} entries loaded)",
              flush=True)
        check(not _tiling.default_interpret(),
              "kernels would run in interpret mode on this backend")
        dispatch.profile_enable(True)
        t0 = time.perf_counter()
        if args.four_chips:
            check(dev["count"] >= 4, f"--four-chips needs 4 devices: {dev}")
            timed_phase("four chips", four_chip_phase)
        else:
            timed_phase("kernels", kernel_phase)
            timed_phase("train", train_phase)
            print(f"peak_bytes_in_use after train: {_peak_bytes(jax)}",
                  flush=True)
            gc.collect()           # the train state leaves the device now
            timed_phase("serve (first run)", serve_phase,
                        label="serve (first run)")
            timed_phase("serve (second run)", serve_phase,
                        label="serve (second run)")
        print(f"compile seconds: {COMPILES['seconds']:.2f} over "
              f"{COMPILES['programs']} programs, {COMPILES['cache_hits']} "
              f"from the persistent cache", flush=True)
        print(f"peak_bytes_in_use: {_peak_bytes(jax)}", flush=True)
        print(f"total seconds: {time.perf_counter() - t0:.1f}", flush=True)
        print("dispatch profile:")
        print(dispatch.profile_table(), flush=True)
        interp = [k for k in dispatch.profile_stats() if "interp" in k]
        check(not interp, f"interpret-mode dispatch buckets: {interp}")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
