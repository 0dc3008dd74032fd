"""Kernel dispatch: routes quantized matmuls onto the fused Pallas path.

This is the production entry point for the DFXP matmul family.  It owns
four concerns the kernels themselves stay agnostic of:

  * **differentiability** — :func:`fused_dot` wraps the forward kernel in
    a ``jax.custom_vjp`` whose backward runs two more Pallas kernels:
    dgrad (``q_g(ct) @ q(B)^T``, layout ``nt``) and wgrad
    (``q(A)^T @ q_g(ct)``, layout ``tn``), with the cotangent's DFXP
    rounding fused into the tile loads (``grad_width``), matching the
    ``qbound`` numerics;
  * **shape collapsing** — batched/ND left operands ``[..., K]`` are
    flattened to ``[M, K]`` around the kernel call (reshape is exact and
    linear, so autodiff through it is free);
  * **block selection** — by the shape called, with a measured autotune
    cache: on compiled backends the first matmul of a 128-rounded shape
    times tiles generated from that shape (large enough to keep the MXU
    fed, sized to divide the dims where they can), with the operand
    widths the call uses, and caches the winner; timings within 3% tie
    and a fixed order settles them, so checkouts on one chip agree.  In
    interpret mode the exact full-shape blocks stand (bit-identity);
  * **backend detection** — compiled Pallas on TPU, interpret elsewhere,
    resolved once per process (``_tiling.default_interpret``).

The same machinery dispatches the fused decode-attention kernel
(:mod:`repro.kernels.attn`): :func:`attn_blocks_for` picks the split-K
size from the same measured cache, keyed ``("attn", Ŵ, K, G, hd, width)``.

Measured entries **persist across processes**: every successful timing
is serialized to ``<checkout>/.cache/autotune.json`` (override the path
with the ``REPRO_AUTOTUNE_CACHE`` env var) and loaded back on import, so
a compiled-TPU autotune run survives restarts instead of re-timing every
shape per process.  Heuristic fallbacks are never persisted — only
numbers an actual backend produced.  A candidate the compiler refuses is
counted (the ``refused`` column of the profile table); a shape whose
every candidate is refused raises with the compiler's message rather
than falling back to a tiling nobody compiled.

``QTape.dot`` calls :func:`tape_dot` when the policy enables the fused
path (``PrecisionPolicy.fused_matmul``); numerics are bit-identical to
the ``ste_quant`` + ``jnp.matmul`` composite it replaces.
"""
from __future__ import annotations

import concurrent.futures
import functools
import itertools
import json
import math
import os
import statistics
import time
from typing import Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from repro.compile_cache import CACHE_DIR
from repro.kernels._tiling import (default_interpret, resolve_interpret,
                                   round_up)
from repro.kernels.qmatmul.ops import qmm
from repro.kernels.qmatmul.qmatmul_kernel import VMEM_LIMIT_BYTES

Array = jax.Array


# ---------------------------------------------------------------------------
# block selection with a measured autotune cache
# ---------------------------------------------------------------------------

# qmatmul tiles are generated from the shape called, per axis
# (block_r, block_c, block_d): the two largest 128-multiples up to the
# axis' largest tile that divide the 128-rounded dim, and the one with
# the fewest tiles that pads it by at most _QMM_PAD.
_QMM_TILE_MAX = (512, 1024, 1024)
_QMM_PAD = 0.02
# The tile estimate's limit: half the kernel's scoped VMEM, the other
# half for Mosaic's own temporaries.
_QMM_VMEM_BUDGET = VMEM_LIMIT_BYTES // 2
# Candidates timed within this share of the fastest are tied; ties go to
# the fewest grid steps, then the least padding, then the smaller tile.
_QMM_TIE = 0.03
# A qmatmul timing runs the kernel this many times in one program, so
# that it reads the device and not the host's dispatch of each call.
_QMM_LOOP = 16
_TIMED_CALLS = 5            # after one warm call; the median counts
# Candidate split-K sizes (block_w) for the flash-decode attention kernel.
_ATTN_CANDIDATES = [128, 256, 512, 1024, 2048]
_VMEM_BUDGET = 8 * 1024 * 1024  # bytes of attention tiles per grid step
# What a compiler that refuses a tiling raises: Pallas' block-shape checks
# (ValueError), unsupported lowerings, and Mosaic/XLA compile failures
# such as a VMEM overflow.
_REFUSALS = (ValueError, NotImplementedError, jax.errors.JaxRuntimeError)

_AUTOTUNE: Dict[str, object] = {"measure": True}
_BLOCK_CACHE: Dict[tuple, Tuple[int, ...]] = {}
_MEASURED: Set[tuple] = set()   # keys whose blocks came from a real timing

# -- dispatch profiling (behind --profile; one dict check when off) ----------
#
# Per cache key: block-selection call count, autotune cache hit/miss
# split, kernel compiles + wall µs spent inside the measurement loops,
# and the blocks chosen.  Selection runs at trace time (jit caches the
# result), so recording here never touches a per-token path; with
# profiling off the only cost is the ``_PROFILE["enabled"]`` check.
_PROFILE: Dict[str, bool] = {"enabled": False}
_PROF: Dict[tuple, dict] = {}
_COUNTS = {"compiles": 0, "refused": 0}   # bumped by _timings


def profile_enable(on: bool = True) -> None:
    """Turn dispatch profiling on/off (``launch.serve --profile``,
    ``benchmarks/run.py --profile``)."""
    _PROFILE["enabled"] = bool(on)


def reset_profile() -> None:
    _PROF.clear()
    _COUNTS.update(compiles=0, refused=0)


def profile_stats() -> Dict[tuple, dict]:
    """Copy of the per-key profile: ``{key: {calls, hits, misses,
    compiles, refused, measure_us, blocks}}`` (empty unless profiling
    ran)."""
    return {k: dict(v) for k, v in _PROF.items()}


def _prof(key: tuple, *, hit: bool, blocks=None, measure_us: float = 0.0,
          compiles: int = 0, refused: int = 0) -> None:
    if not _PROFILE["enabled"]:
        return
    d = _PROF.get(key)
    if d is None:
        d = _PROF[key] = {"calls": 0, "hits": 0, "misses": 0,
                          "compiles": 0, "refused": 0, "measure_us": 0.0,
                          "blocks": None}
    d["calls"] += 1
    if hit:
        d["hits"] += 1
    else:
        d["misses"] += 1
    d["compiles"] += compiles
    d["refused"] += refused
    d["measure_us"] += measure_us
    if blocks is not None:
        d["blocks"] = tuple(blocks)


def profile_table() -> str:
    """The dispatch profile as an aligned text table (one row per key;
    ``steps`` is a qmatmul call's grid steps)."""
    rows = [("key", "calls", "hit", "miss", "compiles", "refused",
             "measure_ms", "blocks", "steps")]
    for key in sorted(_PROF, key=str):
        d = _PROF[key]
        qmm_key = key[0] in ("nn", "nt", "tn") and d["blocks"]
        rows.append(("|".join(map(str, key)), str(d["calls"]),
                     str(d["hits"]), str(d["misses"]), str(d["compiles"]),
                     str(d["refused"]), f"{d['measure_us'] / 1e3:.2f}",
                     "x".join(map(str, d["blocks"] or ())),
                     str(_qmm_order(d["blocks"], *key[1:])[0])
                     if qmm_key else ""))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
                     for r in rows)


def profile_trace_counters(tracer) -> None:
    """Dump the profile onto a :class:`repro.obs.Tracer` as counter events
    (one multi-series counter per key, on the ``dispatch`` track)."""
    for key in sorted(_PROF, key=str):
        d = _PROF[key]
        tracer.counter("dispatch/" + "|".join(map(str, key)),
                       {"calls": d["calls"], "hits": d["hits"],
                        "misses": d["misses"], "compiles": d["compiles"],
                        "refused": d["refused"],
                        "measure_us": d["measure_us"]}, tid="dispatch")


def _bucket(n: int) -> int:
    """Round up to the next power of two (min 8): the attention window's
    cache granularity."""
    b = 8
    while b < n:
        b *= 2
    return b


def autotune_cache() -> Dict[tuple, Tuple[int, int, int]]:
    """The live {key: blocks} cache, qmatmul keys ``(kind, R̂, Ĉ, D̂)``
    (:func:`_qmm_key`; mutable; compiled path only — interpret mode
    always uses exact full-shape blocks)."""
    return _BLOCK_CACHE


def reset_autotune() -> None:
    _BLOCK_CACHE.clear()
    _MEASURED.clear()


# -- persistence ------------------------------------------------------------

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_CACHE_DEFAULT = str(CACHE_DIR / "autotune.json")


def _cache_path(path: Optional[str] = None) -> str:
    return path or os.environ.get(_CACHE_ENV) or _CACHE_DEFAULT


def save_autotune(path: Optional[str] = None) -> Optional[str]:
    """Serialize the *measured* entries to the autotune cache file.

    Called automatically whenever a measurement lands in the cache;
    heuristic fallbacks are excluded (they cost nothing to recompute and
    would shadow a future real measurement).  Entries already on disk are
    merged, not clobbered — successive/concurrent processes measure
    different keys and each must keep the others' work.  Returns the
    path written, or None when there is nothing measured to persist.
    """
    entries = {"|".join(map(str, key)): list(_BLOCK_CACHE[key])
               for key in sorted(_MEASURED, key=str) if key in _BLOCK_CACHE}
    if not entries:
        return None
    p = _cache_path(path)
    try:
        with open(p) as f:
            on_disk = json.load(f)
        if isinstance(on_disk, dict):
            entries = {**on_disk, **entries}
    except Exception:
        pass
    d = os.path.dirname(p)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(p, "w") as f:
        json.dump(entries, f, indent=1, sort_keys=True)
    return p


def _valid_entry(key: tuple, blocks: tuple) -> bool:
    """Semantic check on a persisted entry: arity, positivity, VMEM fit.

    Guards against hand-edited files, entries written by a different
    version, or measurements from hardware with other limits — a bad
    entry would otherwise be trusted forever (loaded entries count as
    measured, so nothing ever re-measures the key).
    """
    if key[0] == "attn":
        return (len(key) == 6 and len(blocks) == 1 and blocks[0] > 0
                and _attn_fits(blocks[0], *key[2:5], key[5] or None))
    if key[0] == "prefill":
        return (len(key) == 6 and len(blocks) == 1 and blocks[0] > 0
                and _prefill_fits(blocks[0], *key[1:5], key[5] or None))
    if key[0] in ("nn", "nt", "tn"):
        return (len(key) == 4 and len(blocks) == 3
                and all(b > 0 for b in blocks)
                and _fits(key[0], blocks, *key[1:]))
    return False


def load_autotune(path: Optional[str] = None) -> int:
    """Load persisted measurements into the live cache (run at import).

    Returns the number of entries loaded; missing/corrupt files and
    entries that fail :func:`_valid_entry` load 0/are skipped (a stale
    cache must never break dispatch — worst case we re-measure).
    """
    p = _cache_path(path)
    if not os.path.exists(p):
        return 0
    try:
        with open(p) as f:
            data = json.load(f)
        items = [((parts[0],) + tuple(int(x) for x in parts[1:]),
                  tuple(int(b) for b in blocks))
                 for ks, blocks in data.items()
                 for parts in [ks.split("|")]]
    except Exception:   # wrong shape, truncated, hand-edited, unreadable —
        return 0        # a stale cache must never break dispatch
    n = 0
    for key, blocks in items:
        if not _valid_entry(key, blocks):
            continue
        _BLOCK_CACHE[key] = blocks
        _MEASURED.add(key)
        n += 1
    return n


def set_autotune(measure: Optional[bool] = None) -> None:
    if measure is not None:
        _AUTOTUNE["measure"] = measure


# -- qmatmul tiles -----------------------------------------------------------

def _sublane_only(kind: str) -> Tuple[bool, bool, bool]:
    """Which of (R, C, D) lie only on sublane axes of the tiles: R in
    ``nn``/``nt``, D in ``tn`` (see ``mm_blocks``).  Those take multiples
    of 8; every other axis is a lane axis somewhere and takes 128."""
    return (False, False, True) if kind == "tn" else (True, False, False)


def _qmm_key(kind: str, R: int, C: int, D: int) -> tuple:
    """Cache key: the shape rounded to 128, or to 8 on a sublane-only
    axis shorter than 128 (decode rows)."""
    return (kind,) + tuple(
        round_up(n, 8) if sub and n < 128 else round_up(n, 128)
        for n, sub in zip((R, C, D), _sublane_only(kind)))


def _qmm_vmem(br: int, bc: int, bd: int) -> int:
    """VMEM bytes of one grid step: f32 input and output tiles, double
    buffered; the accumulator and the dot's f32 result; the three bf16
    pieces the full-precision dot splits each input tile into."""
    ins, out = br * bd + bd * bc, br * bc
    return 4 * 2 * ins + 4 * 2 * out + 4 * 2 * out + 2 * 3 * ins


def _fits(kind: str, blocks, R: int, C: int, D: int) -> bool:
    """Aligned (8 or 128 per axis), no larger than the key's rounded
    dims (no pure-padding tile), and inside the VMEM estimate."""
    return (all(0 < b <= n and b % (8 if sub else 128) == 0
                for b, n, sub in zip(blocks, (R, C, D), _sublane_only(kind)))
            and _qmm_vmem(*blocks) <= _QMM_VMEM_BUDGET)


def _axis_tiles(n: int, cap: int) -> list:
    """Tile sizes for an axis of rounded length ``n``: the two largest
    128-multiples up to ``cap`` that divide ``n``, and the one with the
    fewest tiles (then the least padding) that pads ``n`` by at most
    ``_QMM_PAD``.  A sublane axis under 128 is one tile."""
    if n < 128:
        return [n]
    sizes = range(128, min(cap, n) + 1, 128)
    divisors = [t for t in sizes if n % t == 0][-2:]
    fewest = min((t for t in sizes if round_up(n, t) <= n * (1 + _QMM_PAD)),
                 key=lambda t: (round_up(n, t) // t, round_up(n, t)))
    return sorted(set(divisors) | {fewest})


def _qmm_order(blocks, R: int, C: int, D: int) -> tuple:
    """The fixed order ties are broken by: fewest grid steps, then the
    least padding, then the tile itself."""
    padded = [round_up(n, b) for n, b in zip((R, C, D), blocks)]
    steps = math.prod(n // b for n, b in zip(padded, blocks))
    return steps, math.prod(padded), tuple(blocks)


def _qmm_candidates(kind: str, R: int, C: int, D: int) -> list:
    """Candidate tiles for a key's rounded shape, in :func:`_qmm_order`:
    every combination of :func:`_axis_tiles` that fits, and the smallest
    tile (128, or the whole of a short sublane axis), which always
    fits."""
    axes = [_axis_tiles(n, cap)
            for n, cap in zip((R, C, D), _QMM_TILE_MAX)]
    cands = {c for c in itertools.product(*axes) if _fits(kind, c, R, C, D)}
    cands.add(tuple(min(n, 128) for n in (R, C, D)))
    return sorted(cands, key=lambda c: _qmm_order(c, R, C, D))


# -- measurement --------------------------------------------------------------

def _timings(key: tuple, cands, make) -> Dict[tuple, float]:
    """Median seconds of ``make(c)()`` for every candidate the compiler
    takes.

    Each candidate's program is built once and run once to warm it (its
    compile), then ``_TIMED_CALLS`` times, each timed to completion.  A
    candidate the compiler refuses is skipped and counted.  When it
    refuses them all, raise with its last message: a heuristic tiling in
    their place would only fail later, inside a serve or train step.
    """
    times, err = {}, None
    for blocks in cands:
        run = make(blocks)
        try:
            jax.block_until_ready(run())  # compile
        except _REFUSALS as e:
            _COUNTS["refused"] += 1
            err = e
            continue
        _COUNTS["compiles"] += 1
        ts = []
        for _ in range(_TIMED_CALLS):
            t0 = time.perf_counter()
            jax.block_until_ready(run())
            ts.append(time.perf_counter() - t0)
        times[blocks] = statistics.median(ts)
    if not times:
        raise RuntimeError(
            f"kernel dispatch: the compiler refused every candidate tiling "
            f"of {key} ({len(cands)} tried); last error: "
            f"{type(err).__name__}: {err}") from err
    return times


def _fastest(times: Dict[tuple, float], order=None) -> tuple:
    """The fastest candidate; with ``order``, every candidate within
    ``_QMM_TIE`` of it is tied and the least by ``order`` wins, so that
    timing noise does not decide between tiles of equal speed."""
    best = min(times.values())
    if order is None:
        return min(times, key=times.get)
    return min((c for c, t in times.items() if t <= best * (1 + _QMM_TIE)),
               key=order)


def _on_one_device(fn):
    """``fn()`` in a fresh thread, where the candidates really run.

    Block selection runs while a jit traces the kernel's caller, often
    under a mesh.  JAX keeps its trace and mesh contexts per thread: in
    the caller's thread the candidates would only be traced (timing the
    tracing, and never meeting a compile error) or partitioned over the
    mesh, which a Mosaic kernel refuses; in a fresh thread they compile
    and run eagerly on the default device.
    """
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        return pool.submit(fn).result()


def _measured(key: tuple, measure, default: tuple) -> tuple:
    """Cache miss: measure (or take the heuristic), record, persist."""
    n0, r0, t0 = (_COUNTS["compiles"], _COUNTS["refused"],
                  time.perf_counter())
    try:
        measured = _on_one_device(measure) if _AUTOTUNE["measure"] else None
    finally:   # a key whose every candidate was refused still shows
        _prof(key, hit=False, measure_us=(time.perf_counter() - t0) * 1e6,
              compiles=_COUNTS["compiles"] - n0,
              refused=_COUNTS["refused"] - r0)
    blocks = measured or default
    if key in _PROF:
        _PROF[key]["blocks"] = tuple(blocks)
    _BLOCK_CACHE[key] = blocks
    if measured:
        _MEASURED.add(key)
        save_autotune()
    return blocks


def _measure(kind: str, R: int, C: int, D: int, width_a, width_b) -> tuple:
    """Time the candidate tiles at a key's rounded shape, with the call's
    operand widths, on zero operands; return the winner.

    Each timing runs the kernel ``_QMM_LOOP`` times in one program: the
    host's dispatch of one call costs about as much as the kernel.  The
    operands pass an optimization barrier with the loop index, so that
    every iteration pads and computes anew.
    """
    sa = {"nn": (R, D), "nt": (R, D), "tn": (D, R)}[kind]
    sb = {"nn": (D, C), "nt": (C, D), "tn": (D, C)}[kind]
    a = jnp.zeros(sa, jnp.float32)
    b = jnp.zeros(sb, jnp.float32)
    e = jnp.float32(0.0)

    def make(blocks):
        @jax.jit
        def loop(a, b, e):
            def body(i, acc):
                ai, bi, _ = jax.lax.optimization_barrier((a, b, i))
                c = qmm(ai, bi, e, e, kind=kind, width_a=width_a,
                        width_b=width_b, blocks=blocks, interpret=False)
                return acc + c.reshape(-1)[0]
            return jax.lax.fori_loop(0, _QMM_LOOP, body, jnp.float32(0.0))
        return lambda: loop(a, b, e)

    times = _timings((kind, R, C, D), _qmm_candidates(kind, R, C, D), make)
    return _fastest(times, lambda c: _qmm_order(c, R, C, D))


def blocks_for(kind: str, R: int, C: int, D: int, *, interpret: bool,
               width_a=10, width_b=10) -> tuple:
    """Cached tile choice for the shape called (measured on compiled TPU).

    In interpret mode the blocks are the exact operand dims (one grid
    step, zero padding): the kernel body then executes literally the
    composite's dot on the composite's shapes, which is what makes the
    fused path *bit*-identical to the jnp composite — f32 accumulation
    order on CPU backends depends on operand shapes, so padding or
    splitting the reduction would drift ULPs on raw (straight-through)
    operands.  Compiled TPU tilings are keyed by the 128-rounded shape
    (:func:`_qmm_key`): the first call of a key times the candidates
    generated from that shape (:func:`_qmm_candidates`) with the call's
    operand widths (``None``: raw) and caches the winner; there the MXU
    accumulation contract is the spec.  With measurement off, the first
    candidate in tie-break order stands in.
    """
    if interpret:
        _prof(("mm", kind, "interp"), hit=True, blocks=(R, C, D))
        return R, C, D
    key = _qmm_key(kind, R, C, D)
    blocks = _BLOCK_CACHE.get(key)
    if blocks is None:
        blocks = _measured(
            key, lambda: _measure(*key, width_a, width_b),
            _qmm_candidates(*key)[0])
    else:
        _prof(key, hit=True, blocks=blocks)
    return blocks


# ---------------------------------------------------------------------------
# decode-attention split selection (repro.kernels.attn)
# ---------------------------------------------------------------------------

def _kv_tile_bytes(rows: int, K: int, hd: int, width) -> int:
    """VMEM bytes of one [rows, K, hd] K/V tile.

    The kernels carry every kv head of a window block in one tile, so
    its minor (K, hd) pair pads to the (sublane, 128) tiling: 8 sublanes
    of f32, 16 of int16, 32 of int8.
    """
    nbytes = 1 if (width or 32) <= 8 else (2 if (width or 32) <= 16 else 4)
    return rows * round_up(K, 32 // nbytes) * round_up(hd, 128) * nbytes


def _f32_rows_bytes(K: int, rows: int, hd: int) -> int:
    """VMEM bytes of a per-head f32 [K, rows, hd] tile (q, acc)."""
    return 4 * K * round_up(rows, 8) * round_up(hd, 128)


def _attn_fits(block_w: int, K: int, G: int, hd: int, width) -> bool:
    vmem = (2 * _kv_tile_bytes(block_w, K, hd, width)  # k + v tiles
            + 2 * _f32_rows_bytes(K, G, hd)            # q tile + acc
            + 4 * 2 * round_up(G, 8) * block_w         # scores + probs
            + 4 * 8 * block_w)                         # pos tile
    return vmem <= _VMEM_BUDGET


def _default_split(W: int, fits) -> tuple:
    """Heuristic split: the largest fitting candidate up to ``min(512,
    Ŵ→128)``."""
    ok = [c for c in _ATTN_CANDIDATES
          if c <= min(512, round_up(W, 128)) and fits(c)]
    return (ok[-1] if ok else _ATTN_CANDIDATES[0],)


def _measure_attn(W: int, K: int, G: int, hd: int, width) -> tuple:
    """Time candidate split sizes for one attention bucket (compiled only)."""
    from repro.core.packed import container_dtype
    from repro.kernels.attn.ops import flash_decode
    B = 1
    dt = jnp.float32 if width is None else container_dtype(width)
    q = jnp.zeros((B, K, G, hd), jnp.float32)
    kv = jnp.zeros((B, W, K, hd), dt)
    pos = jnp.zeros((B, W), jnp.int32)
    qp = jnp.full((B,), W - 1, jnp.int32)
    e = jnp.zeros((B,), jnp.float32)
    cands = [(c,) for c in _ATTN_CANDIDATES
             if c <= round_up(W, 128) and _attn_fits(c, K, G, hd, width)]
    return _fastest(_timings(
        ("attn", W, K, G, hd, width), cands,
        lambda bw: lambda: flash_decode(q, kv, kv, pos, qp, e, e,
                                        width=width, scale=1.0,
                                        block_w=bw[0], interpret=False)))


def attn_blocks_for(W: int, K: int, G: int, hd: int, *, width=None,
                    interpret: bool) -> int:
    """Split-K size (``block_w``) for the flash-decode kernel.

    Interpret mode returns the whole window — one grid step on exact
    full-shape blocks, which is the bit-equality contract against
    ``attn/ref.py`` (see :func:`blocks_for` for why padding/splitting
    would drift ULPs on CPU).  Compiled buckets key on
    (Ŵ, K, G, hd, width) and come from the measured cache, heuristic
    fallback :func:`_default_split`.
    """
    if interpret:
        _prof(("attn", "interp"), hit=True, blocks=(W,))
        return W
    key = ("attn", _bucket(W), K, G, hd, width or 0)
    blocks = _BLOCK_CACHE.get(key)
    if blocks is None:
        blocks = _measured(
            key, lambda: _measure_attn(key[1], K, G, hd, width),
            _default_split(W, lambda c: _attn_fits(c, K, G, hd, width)))
    else:
        _prof(key, hit=True, blocks=blocks)
    return blocks[0]


# ---------------------------------------------------------------------------
# chunked-prefill split selection (repro.kernels.attn flash_prefill)
# ---------------------------------------------------------------------------

# Representative history length the prefill autotuner measures at: the
# bucket key deliberately drops W (the split size barely depends on it —
# it tiles the history walk), so one measurement serves every pool depth.
_PREFILL_MEASURE_W = 4096


def _prefill_fits(block_w: int, C: int, K: int, G: int, hd: int,
                  width) -> bool:
    rows = C * G
    vmem = (2 * _kv_tile_bytes(block_w, K, hd, width)  # k + v history
            + 2 * _kv_tile_bytes(C, K, hd, None)       # f32 chunk k/v
            + 2 * _f32_rows_bytes(K, rows, hd)         # q tile + acc
            + 4 * 2 * round_up(rows, 8) * max(block_w, C)  # scores+probs
            + 4 * 8 * block_w)                         # pos tile
    return vmem <= _VMEM_BUDGET


def _measure_prefill(C: int, K: int, G: int, hd: int, width) -> tuple:
    """Time candidate split sizes for one prefill bucket (compiled only)."""
    from repro.core.packed import container_dtype
    from repro.kernels.attn.ops import flash_prefill
    B, W = 1, _PREFILL_MEASURE_W
    dt = jnp.float32 if width is None else container_dtype(width)
    q = jnp.zeros((B, C, K, G, hd), jnp.float32)
    kn = jnp.zeros((B, C, K, hd), jnp.float32)
    kv = jnp.zeros((B, W, K, hd), dt)
    pos = jnp.zeros((B, W), jnp.int32)
    p0 = jnp.full((B,), W, jnp.int32)
    nv = jnp.full((B,), C, jnp.int32)
    e = jnp.zeros((B,), jnp.float32)
    cands = [(c,) for c in _ATTN_CANDIDATES
             if c <= round_up(W, 128)
             and _prefill_fits(c, C, K, G, hd, width)]
    return _fastest(_timings(
        ("prefill", C, K, G, hd, width), cands,
        lambda bw: lambda: flash_prefill(q, kn, kn, kv, kv, pos, p0, nv, e,
                                         e, width=width, scale=1.0,
                                         block_w=bw[0], interpret=False)))


def prefill_blocks_for(W: int, C: int, K: int, G: int, hd: int, *,
                       width=None, interpret: bool) -> int:
    """History split size (``block_w``) for the flash-prefill kernel.

    Interpret mode returns the whole window — one grid step on exact
    full-shape blocks, the bit-equality contract against
    ``attn/ref.chunk_attend``.  Compiled buckets key on
    ``("prefill", C, K, G, hd, width)`` — W is deliberately not part of
    the key (see ``_PREFILL_MEASURE_W``) — and come from the same
    persisted measured cache as the decode splits; heuristic fallback
    :func:`_default_split`.
    """
    if interpret:
        _prof(("prefill", "interp"), hit=True, blocks=(W,))
        return W
    key = ("prefill", C, K, G, hd, width or 0)
    blocks = _BLOCK_CACHE.get(key)
    if blocks is None:
        blocks = _measured(
            key, lambda: _measure_prefill(C, K, G, hd, width),
            _default_split(W, lambda c: _prefill_fits(c, C, K, G, hd,
                                                      width)))
    else:
        _prof(key, hit=True, blocks=blocks)
    return blocks[0]


# ---------------------------------------------------------------------------
# paged-attention split validation (repro.kernels.attn *_paged)
# ---------------------------------------------------------------------------

def paged_attn_blocks_for(P: int, K: int, G: int, hd: int, *, width=None,
                          interpret: bool) -> int:
    """Split size for the paged flash-decode kernel — always the page.

    The paged grid walks the block table one physical page per step, so
    the page size *is* the split size and there is nothing to tune; this
    is the dispatch layer's validation hook instead: a ``--page-size``
    whose (P, hd) tile would bust the VMEM budget fails loudly at the
    first call, not as a compiler OOM deep in a serve step.  Interpret
    mode has no VMEM and accepts any page.
    """
    _prof(("paged_attn", P, K, G, hd, width or 0), hit=True, blocks=(P,))
    if not interpret and not _attn_fits(P, K, G, hd, width):
        raise ValueError(
            f"page_size {P} (K={K}, G={G}, hd={hd}, width={width}) exceeds "
            f"the {_VMEM_BUDGET >> 20}MB VMEM tile budget of the paged "
            "flash-decode kernel; use a smaller --page-size")
    return P


def paged_prefill_blocks_for(P: int, C: int, K: int, G: int, hd: int, *,
                             width=None, interpret: bool) -> int:
    """Split size for the paged flash-prefill kernel — always the page.

    Same contract as :func:`paged_attn_blocks_for`, with the chunk's
    ``C·G`` score rows included in the fit check.
    """
    _prof(("paged_prefill", P, C, K, G, hd, width or 0), hit=True,
          blocks=(P,))
    if not interpret and not _prefill_fits(P, C, K, G, hd, width):
        raise ValueError(
            f"page_size {P} (C={C}, K={K}, G={G}, hd={hd}, width={width}) "
            f"exceeds the {_VMEM_BUDGET >> 20}MB VMEM tile budget of the paged "
            "flash-prefill kernel; use a smaller --page-size or chunk")
    return P


# ---------------------------------------------------------------------------
# differentiable fused matmul
# ---------------------------------------------------------------------------

def _qmm_auto(a, b, e_a, e_b, *, kind, width_a, width_b, cast, out_dtype,
              interpret):
    """qmm with dispatch-selected blocks for the (collapsed) 2D shapes."""
    if kind == "nn":
        (R, D), C = a.shape, b.shape[1]
    elif kind == "nt":
        (R, D), C = a.shape, b.shape[0]
    else:
        (D, R), C = a.shape, b.shape[1]
    blocks = blocks_for(kind, R, C, D, interpret=interpret,
                        width_a=width_a, width_b=width_b)
    return qmm(a, b, e_a, e_b, kind=kind, width_a=width_a, width_b=width_b,
               blocks=blocks, cast=cast, out_dtype=out_dtype,
               interpret=interpret)


@functools.lru_cache(maxsize=None)
def _make_fused(width_a, width_b, grad_width, transpose_b: bool,
                cast, interpret: bool):
    """Build the custom-VJP fused matmul for one static configuration.

    Forward: ``q(a) @ q(b)`` (or ``q(a) @ q(b)^T`` with ``transpose_b``),
    each quantization optional (``width=None`` → raw operand, matching
    the straight-through composite).  Backward (STE through the operand
    rounding, quantized co-operands):

        da = q_g(ct) @ q(b)[^T]          db = q(a)^T @ q_g(ct)

    with ``q_g`` the optional ``grad_width`` cotangent rounding.
    """
    fwd_kind = "nt" if transpose_b else "nn"

    def _forward(a, b, e_a, e_b):
        return _qmm_auto(a, b, e_a, e_b, kind=fwd_kind, width_a=width_a,
                         width_b=width_b, cast=cast, out_dtype=a.dtype,
                         interpret=interpret)

    @jax.custom_vjp
    def fused(a, b, e_a, e_b, e_g):
        del e_g
        return _forward(a, b, e_a, e_b)

    def fwd(a, b, e_a, e_b, e_g):
        return _forward(a, b, e_a, e_b), (a, b, e_a, e_b, e_g)

    def bwd(res, ct):
        a, b, e_a, e_b, e_g = res
        if transpose_b:
            # y[M,V] = qa[M,D] @ qb[V,D]^T
            da = _qmm_auto(ct, b, e_g, e_b, kind="nn", width_a=grad_width,
                           width_b=width_b, cast=cast, out_dtype=a.dtype,
                           interpret=interpret)
            db = _qmm_auto(ct, a, e_g, e_a, kind="tn", width_a=grad_width,
                           width_b=width_a, cast=cast, out_dtype=b.dtype,
                           interpret=interpret)
        else:
            # y[M,N] = qa[M,K] @ qb[K,N]
            da = _qmm_auto(ct, b, e_g, e_b, kind="nt", width_a=grad_width,
                           width_b=width_b, cast=cast, out_dtype=a.dtype,
                           interpret=interpret)
            db = _qmm_auto(a, ct, e_a, e_g, kind="tn", width_a=width_a,
                           width_b=grad_width, cast=cast, out_dtype=b.dtype,
                           interpret=interpret)
        return (da, db, jnp.zeros_like(e_a), jnp.zeros_like(e_b),
                jnp.zeros_like(e_g))

    fused.defvjp(fwd, bwd)
    return fused


def fused_dot(a, b, e_a, e_b, *, width: int, grad_width: Optional[int] = None,
              e_g=0.0, quant_a: bool = True, quant_b: bool = True,
              transpose_b: bool = False, cast=jnp.float32,
              interpret: Optional[bool] = None) -> Array:
    """Differentiable fused DFXP matmul ``q(a) @ q(b)[^T]``.

    ``a``: [..., K] (leading dims collapsed around the kernel), ``b``:
    [K, N] (or [N, K] with ``transpose_b``).  ``grad_width`` enables the
    fused cotangent rounding (exponent ``e_g``) in both backward kernels;
    ``quant_a=False`` / ``quant_b=False`` pass that operand through raw —
    the straight-through composite contract used by ``QTape.dot``.
    """
    interpret = resolve_interpret(interpret)
    f = _make_fused(width if quant_a else None, width if quant_b else None,
                    grad_width, transpose_b, cast, interpret)
    e_a = jnp.asarray(e_a, jnp.float32)
    e_b = jnp.asarray(e_b, jnp.float32)
    e_g = jnp.asarray(e_g, jnp.float32)
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1]) if a.ndim != 2 else a
    y = f(a2, b, e_a, e_b, e_g)
    return y.reshape(*lead, y.shape[-1]) if a.ndim != 2 else y


def tape_dot(x, w, e_w, *, width: int, transpose_b: bool = False,
             interpret: Optional[bool] = None) -> Array:
    """The ``QTape.dot`` fused path: raw activations × quantized weight.

    Bit-identical to the composite ``jnp.matmul(x, ste_quant(w))`` — the
    activation operand and the backward cotangent are *not* re-rounded
    here (the surrounding ``tape.act`` sites already hold them on the
    DFXP grid), and the weight gradient passes straight through, exactly
    like ``ste_quant``'s identity backward.
    """
    return fused_dot(x, w, 0.0, e_w, width=width, quant_a=False,
                     transpose_b=transpose_b, cast=x.dtype,
                     interpret=interpret)


__all__ = ["fused_dot", "tape_dot", "blocks_for", "attn_blocks_for",
           "prefill_blocks_for", "paged_attn_blocks_for",
           "paged_prefill_blocks_for", "autotune_cache", "reset_autotune",
           "set_autotune", "save_autotune", "load_autotune",
           "default_interpret", "profile_enable", "reset_profile",
           "profile_stats", "profile_table", "profile_trace_counters"]

load_autotune()   # persisted measurements survive process restarts
