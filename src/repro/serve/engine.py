"""Continuous-batching decode engine over a fixed slot array.

Replaces the lockstep loop (prefill a batch, decode everyone for exactly
``max_new`` steps) with a real request lifecycle:

  queued → admitted into a free slot (prefill) → decoding at its own
  position → finished (EOS or its own ``max_new``) → slot freed →
  next queued request admitted **mid-decode**.

Every device computation is fixed-shape and jitted once per shape:

* ``_decode`` runs over all ``max_slots`` rows each step — per-slot
  position vector (``transformer.decode_step`` with ``pos: [B]``),
  per-slot PRNG streams, one compile for the engine's lifetime.  Free
  slots decode garbage into their own cache rows; row independence means
  active slots are unaffected, and admission overwrites the row anyway.
* ``_prefill`` (whole-prompt mode, ``prefill_chunk=0``) compiles per
  ``(group_size, prompt_len)``: admission groups queued requests of
  equal prompt length into one batch, so a burst of same-length requests
  costs one prefill — and an engine admitting B equal-length prompts
  into B free slots reproduces the lockstep engine's prefill bit-for-bit
  (the equivalence test's anchor).  Variable-length prompts prefill as
  separate length groups, never padded — padding would perturb MoE
  capacity routing and SSM state.  MoE models admit one request per
  prefill for the same reason: expert capacity is computed over the
  whole prefill batch, and the engine guarantees a request's tokens
  don't depend on who it shares with.
* ``_insert`` scatters the fresh cache entry into pool rows (axis 1) and,
  in packed mode, quantizes it first (``kv_pool.PackedKVCodec``).
* ``_chunk`` (**chunked-prefill mode**, ``prefill_chunk=C > 0``,
  attention-family models): any queued request is admitted into any free
  slot immediately, and each engine step runs ONE fixed-size prefill
  chunk for the oldest prefilling slot, interleaved with the decode
  batch.  While a slot is mid-prefill the decode batch's append is
  masked off for it (``append_mask``), so its pool row and controller
  state stay byte-identical to a solo run.  Whole-prompt mode remains
  the bit-for-bit reference path.

The KV pool stores K/V float32 (bit-identical to ``transformer.init_cache``)
or as DFXP-packed int8/int16 mantissas with controller-managed per-slot
exponents (``cache_bits=8|16``) — halving/quartering cache HBM and hence
multiplying concurrent slot capacity.

Robustness layer (admission control, preemption, quarantine)
------------------------------------------------------------

Production serving fails in exactly the ways low-precision numerics make
survivable *per request* — if the engine can detect, quarantine, and
recover instead of crashing the batch:

* **admission control** — ``queue_cap`` bounds the queue (submit beyond
  it resolves the request ``REJECTED``, it never raises);
  ``deadline_ms`` (engine default, overridable per submit) expires
  queued *and* in-flight requests to ``TIMED_OUT`` with whatever tokens
  they harvested.  Every request ends in a terminal
  :class:`RequestStatus` readable via :meth:`ServeEngine.status`.
* **preemption under page exhaustion** — when the paged arena runs dry
  mid-step, the engine picks a victim (the *youngest decoding* request,
  falling back to the youngest prefilling one), releases its non-shared
  pages, and requeues it at the front of the queue with its
  generated-so-far tokens carried as prompt suffix.  Re-admission
  re-prefills prompt + carry through the chunked-prefill path — prefix
  caching makes the prompt part free when its pages are still registered
  — and the sampler keys on ``(seed, uid, absolute position)``, so the
  resumed stream continues exactly where it left off.  A request
  preempted more than ``max_preempts`` times resolves ``FAILED`` instead
  of thrashing; exhaustion with no preemptible sibling resolves the
  requester ``FAILED``.  ``run()`` never raises for page exhaustion.
* **numeric sentinels** — every decode/prefill jit guards its logits
  device-side (``sampler.guard_logits``): a NaN/Inf row flags ``bad``
  for its slot, harvested with the sampled tokens in the same device
  sync.  A flagged slot is quarantined ``FAILED`` — its poisoned token
  dropped, its slot freed and thereby masked out of subsequent appends —
  while sibling slots' streams are untouched (row independence + masked
  appends).  ``runaway_ovf`` adds a §5 overflow-rate runaway threshold:
  slots whose cumulative cache overflow rate exceeds it (the paper's
  controller has lost the race) quarantine the same way.
* **drain-timeout** — ``run()`` out of step budget resolves every
  in-flight request ``TIMED_OUT`` (queued preempted ones ``PREEMPTED``)
  and returns all harvested tokens instead of raising and discarding
  them.

Deterministic fault injectors driving all of this live in
:mod:`repro.serve.faults`.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ScaleState
from repro.core.policy import PrecisionPolicy
from repro.dist import DistCtx, MeshConfigError, serve_pod_ctx
from repro.models import transformer as T
from repro.obs.trace import span as obs_span

from . import kv_pool, metrics, paged, sampler

Array = jax.Array


class RequestStatus(enum.Enum):
    """Terminal state of a request. The engine resolves every submitted
    uid to exactly one of these instead of raising mid-drain."""

    OK = "ok"                  # finished: EOS or its max_new budget
    REJECTED = "rejected"      # admission control: queue was full
    TIMED_OUT = "timed_out"    # deadline expired / drain ran out of steps
    PREEMPTED = "preempted"    # evicted for pages, still queued at drain end
    FAILED = "failed"          # quarantined: NaN/Inf logits, §5 runaway,
    #                            or page exhaustion with no victim


@dataclasses.dataclass
class Request:
    """One generation request. ``tokens``: 1-D prompt ids.

    ``deadline`` is an absolute ``time.perf_counter`` stamp (set by the
    engine from ``deadline_ms``); ``carry`` holds tokens generated
    before a preemption (they ride along as prompt suffix on requeue and
    are prepended to the final result); ``n_preempt`` counts evictions.
    """

    uid: int
    tokens: np.ndarray
    max_new: int = 16
    eos_id: Optional[int] = None
    deadline: Optional[float] = None
    carry: Tuple[int, ...] = ()
    n_preempt: int = 0


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Every :class:`ServeEngine` knob beyond the model triple, the slot
    geometry, and the mesh, as one typed value.

    Groups the pool layout (``cache_bits``/``cache_cfg``/``page_size``/
    ``n_pages``/``prefill_chunk``), sampling (``sampler_cfg``/``seed``),
    admission control (``queue_cap``/``deadline_ms``), resilience
    (``runaway_ovf``/``max_preempts``/``faults``), and observability
    (``tracer``/``numerics_log``/``numerics_every``) knobs that used to
    travel as loose keyword arguments.  Field semantics are documented on
    :class:`ServeEngine` (they are the same knobs, one release of
    deprecation apart); defaults reproduce the bare
    ``ServeEngine(cfg, policy, params, max_slots=…, max_len=…)`` engine
    bit-for-bit.
    """

    cache_bits: int = 0
    sampler_cfg: sampler.SamplerConfig = sampler.SamplerConfig()
    cache_cfg: Optional[kv_pool.CacheQuantConfig] = None
    seed: int = 0
    init_exp: float = -6.0
    prefill_chunk: Optional[int] = None
    page_size: Optional[int] = None
    n_pages: Optional[int] = None
    queue_cap: Optional[int] = None
    deadline_ms: Optional[float] = None
    runaway_ovf: Optional[float] = None
    max_preempts: int = 4
    faults: object = None
    tracer: object = None
    numerics_log: object = None
    numerics_every: Optional[int] = None


_LEGACY_ENGINE_KWARGS = frozenset(
    f.name for f in dataclasses.fields(EngineOptions))


class ServeEngine:
    """Continuous-batching engine over ``max_slots`` concurrent sequences.

    Construction is ``ServeEngine(cfg, policy, params, max_slots=…,
    max_len=…, options=EngineOptions(…))`` plus, for multi-device
    serving, ``dist=serve_pod_ctx(tp=…, cp=…)`` and
    ``mesh=make_serve_mesh(tp=…, cp=…)``.  Passing the options fields as
    loose keyword arguments still works for one release and warns
    (``DeprecationWarning``); unknown keywords raise ``TypeError``.

    Multi-device serving shards the **KV pool** (the HBM-bound tensor):
    kv heads over the mesh's ``model`` axis (TP), and — with
    ``dist.cp_decode`` — the decode KV window over ``data`` (CP, exact
    log-sum-exp merge).  Parameters stay replicated and the attention
    output is gathered before the ``wo`` contraction, so the sharded
    engine's greedy token streams are bit-identical to single-device
    with interpret-mode kernels; on a TPU its logits agree within
    rounding (``chip_smoke.py --four-chips`` states the tolerance).
    Incoherent requests (active ``dist`` without its mesh, CP over a
    paged arena, a window CP doesn't divide) raise
    :class:`repro.dist.MeshConfigError` at construction.

    Parameters
    ----------
    cfg, policy, params: the functional model triple.
    max_slots: concurrent sequences (the decode batch shape).
    max_len: per-slot KV capacity; every request needs
        ``prompt_len + max_new <= max_len``.
    options: an :class:`EngineOptions`; the per-knob semantics below.
    dist: a :class:`repro.dist.DistCtx` naming the mesh axes in play
        (``serve_pod_ctx``); ``None`` with a ``mesh`` derives one from
        the mesh's axis sizes; both ``None`` = single-device (today's
        engine, bit-for-bit).
    mesh: the device mesh (``launch.mesh.make_serve_mesh``) backing an
        active ``dist``.
    cache_bits: 0 → float32 KV pool (bit-identical to the lockstep
        engine); 8/16 → DFXP-packed mantissa pool.  With
        ``policy.fused_decode`` the decode attention runs as the fused
        Pallas flash-decode kernel straight on the pool's storage
        (packed mantissas dequantized in the tile loads — no per-layer
        f32 K/V materialization on the hot path).
    sampler_cfg: greedy / temperature / top-k, per-request PRNG streams.
    cache_cfg: overrides the packed pool's controller settings.
    prefill_chunk: chunk size ``C`` for chunked prefill (see module
        docstring); ``None`` takes ``policy.prefill_chunk``, 0 keeps the
        whole-prompt reference path.  Attention-family models only — MoE
        keeps the solo whole-prompt carve-out (batch-coupled expert
        capacity) and SSM/hybrid carry recurrent state across the
        prompt; both silently stay on the whole-prompt path.
    page_size: ``P > 0`` switches the KV pool to **paged** storage
        (:mod:`repro.serve.paged`): fixed-size pages + per-request block
        tables, refcounted prompt-prefix sharing with copy-on-write, and
        page-granular DFXP exponents.  Forces chunked prefill (``C``
        defaults to ``P``) and requires the dense attention family with
        global (non-windowed) attention; ``None``/0 takes
        ``policy.page_size``.  Prefix sharing is disabled under
        stochastic rounding (a shared page cannot replay two requests'
        PRNG streams) — pages and copy-on-write still apply.
    n_pages: paged-pool page budget (default: full residency — every
        slot can map its whole ``max_len`` — plus the null page).  A
        smaller budget recycles freed/evicted pages; exhaustion
        mid-step **preempts** the youngest decoding request (released
        pages recycle, the victim requeues and resumes) instead of
        raising.
    queue_cap: bound on the waiting queue; a submit finding it full
        resolves the new request ``REJECTED`` (empty result, terminal
        status) instead of queueing or raising.  ``None`` = unbounded.
    deadline_ms: default per-request deadline, measured from submit;
        expired requests — queued or in-flight — resolve ``TIMED_OUT``
        with the tokens harvested so far.  ``None`` = no deadline.
    runaway_ovf: §5 overflow-rate runaway threshold.  Each decode step
        harvests every slot's cumulative cache overflow rate
        (``kv_pool.slot_overflow_rates``, computed in-jit) with the
        tokens; an active slot whose rate exceeds this quarantines as
        ``FAILED``.  ``None`` disables the sentinel.
    max_preempts: a request evicted this many times resolves ``FAILED``
        on the next eviction attempt instead of requeueing (bounds
        preemption ping-pong on pathologically small arenas).
    faults: optional deterministic fault harness
        (:class:`repro.serve.faults.FaultHarness`) — injects NaN logits,
        KV bit flips, forced page exhaustion, and admission delays for
        chaos testing.  ``None`` in production.
    tracer: optional :class:`repro.obs.Tracer` — records every engine
        phase (submit/admit/prefill-chunk/decode-step/preempt/finish)
        as span/instant events plus queue-depth counters, exportable as
        Chrome-trace JSON.  ``None`` (the default) records nothing: all
        hooks are guarded by a single ``is not None`` check — no device
        syncs, no extra per-token host work, token streams bit-identical.
    numerics_log: optional :class:`repro.obs.NumericsLog` (or a path
        string) receiving the §5 numeric-health timeline: per-layer/
        per-slot K/V exponents, overflow rates, and controller up/down
        moves, sampled every ``numerics_every`` steps via one batched
        jit + device fetch (``kv_pool.numerics_snapshot``) — a single
        device sync per sample, nothing added to undisturbed steps.
        Packed pools only (float32 pools have no controller to watch).
    numerics_every: sampling cadence in engine steps; default: the
        packed pool's controller ``update_interval`` (one sample per
        controller decision window).
    """

    def __init__(self, cfg: T.ModelConfig, policy: PrecisionPolicy, params,
                 *, max_slots: int, max_len: int,
                 options: Optional[EngineOptions] = None,
                 dist: Optional[DistCtx] = None, mesh=None, **legacy):
        if legacy:
            unknown = sorted(set(legacy) - _LEGACY_ENGINE_KWARGS)
            if unknown:
                raise TypeError(
                    f"ServeEngine got unexpected keyword arguments "
                    f"{unknown}")
            warnings.warn(
                "passing ServeEngine configuration as loose keyword "
                "arguments is deprecated; pass options=EngineOptions(...)",
                DeprecationWarning, stacklevel=2)
            options = dataclasses.replace(options or EngineOptions(),
                                          **legacy)
        opts = options or EngineOptions()
        if cfg.input_mode != "tokens" or cfg.encoder_layers:
            raise ValueError("ServeEngine serves token-in decoder models")
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if dist is None and mesh is not None:
            # derive the serving context from the mesh's axis sizes
            dist = serve_pod_ctx(tp=int(mesh.shape.get("model", 1)),
                                 cp=int(mesh.shape.get("data", 1)))
        self.dist = dist or DistCtx()
        self.mesh = mesh
        if self.dist.active and mesh is None:
            raise MeshConfigError(
                "an active DistCtx needs the mesh it names; pass "
                "mesh=launch.mesh.make_serve_mesh(...)")
        self.cfg, self.policy, self.params = cfg, policy, params
        self.max_slots, self.max_len = max_slots, max_len
        self.options = opts
        self.sampler_cfg = opts.sampler_cfg
        self.seed = opts.seed
        self.queue_cap = opts.queue_cap
        self.deadline_ms = opts.deadline_ms
        self.runaway_ovf = opts.runaway_ovf
        self.max_preempts = opts.max_preempts
        self._faults = opts.faults
        gs = T.group_shapes(cfg)
        self.exps = ScaleState.create(gs, opts.init_exp).exps
        self.sinks = {n: jnp.zeros(s + (3,), jnp.float32)
                      for n, s in gs.items() if n.startswith("g:")}

        # pool construction is factory-owned: layout choice, codec
        # capabilities, validation, and (mesh runs) sharded placement
        kvp = kv_pool.make_kv_pool(
            cfg, policy, self.dist, max_slots=max_slots, max_len=max_len,
            cache_bits=opts.cache_bits, cache_cfg=opts.cache_cfg,
            page_size=opts.page_size, n_pages=opts.n_pages, mesh=mesh)
        self.kv = kvp
        self.codec = kvp.codec
        self.cache_cfg = kvp.cache_cfg
        self.page_size = kvp.page_size
        self._paged = kvp.paged
        self._packed = kvp.packed
        self._pool = kvp.pool
        self._pool_shardings = kvp.shardings
        if self.dist.active:
            # params/exps/sinks stay REPLICATED: every contraction that
            # could reorder partial sums runs identically on all devices,
            # which is what keeps sharded greedy streams bit-identical
            # under interpret-mode kernels
            rep = jax.sharding.NamedSharding(mesh,
                                             jax.sharding.PartitionSpec())
            self.params = jax.device_put(self.params, rep)
            self.exps = jax.device_put(self.exps, rep)
            self.sinks = jax.device_put(self.sinks, rep)
        # the weights ride into every jit as an argument: a closed-over
        # array would be baked into the program as a constant (GBs of
        # HLO for a full-width model)
        self._w = (self.params, self.exps, self.sinks)
        if self._paged:
            self._alloc = paged.PageAllocator(kvp.total_pages,
                                              self.page_size, kvp.nblocks)
            # a shared page cannot replay two requests' stochastic PRNG
            # chains — sharing off, COW/paging still on
            self._share_prefix = not (self._packed
                                      and self.cache_cfg.stochastic)
            self._reset_slot = jax.jit(paged.reset_slot,
                                       donate_argnums=(0,))
            self._cow = jax.jit(paged.cow_page, donate_argnums=(0,))
            self._set_block = jax.jit(paged.set_block, donate_argnums=(0,))

        # per-slot host state
        B = max_slots
        self._tok = np.zeros(B, np.int32)
        self._pos = np.zeros(B, np.int32)
        self._active = np.zeros(B, bool)
        self._reqs: List[Optional[Request]] = [None] * B
        self._gen: List[List[int]] = [[] for _ in range(B)]
        self._keys = np.zeros((B, 2), np.uint32)
        self._seq = np.zeros(B, np.int64)     # admission order (victim pick)
        self._queue: collections.deque = collections.deque()
        self._results: Dict[int, np.ndarray] = {}
        self._status: Dict[int, RequestStatus] = {}
        self._next_uid = 0
        self._admit_counter = 0
        self._step_idx = 0
        self._budget = 1 << 62                # run() tightens this
        self._auto_budget = True
        self._ovf = np.zeros(3, np.float64)   # harvested at request finish
        self.metrics = metrics.ServeMetrics()

        # observability (every hook below guards on `is not None`; with
        # all three unset the step loop is bit-identical to an unobserved
        # engine — no spans, no samples, no extra syncs)
        tracer = opts.tracer
        numerics_log = opts.numerics_log
        self._tracer = tracer
        if tracer is not None and self._faults is not None and \
                getattr(self._faults, "tracer", None) is None:
            self._faults.tracer = tracer  # fault injections land on trace
        if isinstance(numerics_log, str):
            from repro.obs import NumericsLog
            numerics_log = NumericsLog(numerics_log)
        self._numerics = numerics_log if self._packed else None
        if opts.numerics_every is not None:
            self._num_every = max(int(opts.numerics_every), 1)
        elif self._packed:
            self._num_every = max(int(self.cache_cfg.update_interval), 1)
        else:
            self._num_every = 1
        self._num_prev: Optional[dict] = None
        self._num_snap = None         # jitted numerics_snapshot, on demand

        # chunked prefill: attention-family only (MoE capacity and SSM
        # state couple a whole prompt; they keep the whole-prompt path)
        pc = opts.prefill_chunk if opts.prefill_chunk is not None else \
            int(getattr(policy, "prefill_chunk", 0))
        if self._paged and not pc:
            pc = self.page_size   # paged mode always prefills in chunks
        chunkable = (cfg.family == "dense" and not cfg.num_experts
                     and not cfg.encoder_layers)
        self.prefill_chunk = pc if (pc and chunkable) else 0
        self._pfill = np.zeros(B, np.int32)       # prefill frontier per slot
        self._pstarted = np.zeros(B, bool)        # paged: block table mapped
        self._prefilling: collections.deque = collections.deque()  # slot FIFO

        # the pool argument is donated: decode/insert rewrite it in place
        # instead of holding two full copies live (the packed pool exists
        # to shrink cache HBM — doubling it back would defeat the point)
        self._prefill = jax.jit(self._prefill_impl)   # per (g, L) shape
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        if self.prefill_chunk:
            # ONE compile for any prompt length / slot: chunk shape is
            # static, slot index / start / valid count are traced
            self._chunk = jax.jit(self._chunk_impl, donate_argnums=(1,))
            self._seed_keys = jax.jit(kv_pool.seed_slot_keys,
                                      donate_argnums=(0,))
            self._decode = jax.jit(self._decode_masked_impl,
                                   donate_argnums=(1,))
        else:
            self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._slot_tot = jax.jit(kv_pool.slot_totals)
        # MoE prefill routes with a capacity computed over the whole batch,
        # so batching prompts would couple their routing — admit one at a
        # time to keep the solo == shared token guarantee exact
        self._admit_group_cap = 1 if cfg.num_experts else max_slots

    # -- jitted device steps ----------------------------------------------
    def _constrain_pool(self, pool):
        """Pin the donated pool to its canonical sharded layout.

        Applied at every jit's pool output on mesh runs, so GSPMD cannot
        drift the resident layout between steps; identity single-device.
        """
        if self._pool_shardings is None:
            return pool
        return jax.lax.with_sharding_constraint(pool, self._pool_shardings)

    def _prefill_impl(self, w, tokens, keys):
        params, exps, sinks = w
        logits, _, cache = T.prefill(self.cfg, self.policy, params,
                                     {"tokens": tokens}, exps, sinks,
                                     self.dist, max_cache_len=self.max_len)
        # first generated token sits at absolute position L = prompt length
        pos = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
        safe, bad = sampler.guard_logits(logits)
        first = sampler.sample(safe, sampler.position_keys(keys, pos),
                               self.sampler_cfg)
        return first, bad, cache

    def _insert_impl(self, pool, entry, slots, keys):
        return self._constrain_pool(
            kv_pool.insert(pool, entry, slots, self.codec, keys))

    def _sample_guarded(self, logits, pos, keys, nan_mask):
        """Shared decode tail: fault mask → sentinel → sample."""
        logits = jnp.where(nan_mask[:, None], jnp.float32(jnp.nan), logits)
        safe, bad = sampler.guard_logits(logits)
        nxt = sampler.sample(safe, sampler.position_keys(keys, pos + 1),
                             self.sampler_cfg)
        return nxt, bad

    def _decode_impl(self, w, pool, tok, pos, keys, nan_mask):
        params, exps, sinks = w
        logits, _, pool = T.decode_step(self.cfg, self.policy, params,
                                        pool, tok, pos, exps, sinks,
                                        self.dist, kv_codec=self.codec)
        nxt, bad = self._sample_guarded(logits, pos, keys, nan_mask)
        rate = kv_pool.slot_overflow_rates(pool, self.max_slots)
        return nxt, bad, rate, self._constrain_pool(pool)

    def _decode_masked_impl(self, w, pool, tok, pos, keys, mask, nan_mask):
        # chunked mode: slots mid-prefill (or free) decode garbage whose
        # cache append must be dropped — their pool rows and controller
        # state must stay byte-identical to a solo run
        params, exps, sinks = w
        logits, _, pool = T.decode_step(self.cfg, self.policy, params,
                                        pool, tok, pos, exps, sinks,
                                        self.dist, kv_codec=self.codec,
                                        append_mask=mask)
        nxt, bad = self._sample_guarded(logits, pos, keys, nan_mask)
        rate = kv_pool.slot_overflow_rates(pool, self.max_slots)
        return nxt, bad, rate, self._constrain_pool(pool)

    def _chunk_impl(self, w, pool, tokens, slot, p0, n_valid, keys):
        """One prefill chunk for one slot. ``tokens``: [1, C] (padded);
        ``slot``/``p0``/``n_valid``: traced scalars; ``keys``: [1, 2]."""
        # paged-aware slicing: slot-indexed leaves narrow to B=1, page
        # arenas pass through whole (the chunk scatters into its own
        # slot's pages); reduces to the plain tree_map for slot-major
        sub = paged.slice_slot(pool, slot)
        params, exps, sinks = w
        logits, _, sub = T.prefill_chunk_step(
            self.cfg, self.policy, params, sub, tokens, p0[None],
            n_valid[None], exps, sinks, self.dist, kv_codec=self.codec)
        pool = self._constrain_pool(paged.merge_slot(pool, sub, slot))
        # the first generated token sits at absolute position p0 + n_valid
        # (== prompt length when this is the final chunk) — the same key
        # fold as whole-prompt _prefill_impl
        safe, bad = sampler.guard_logits(logits)
        tok = sampler.sample(safe,
                             sampler.position_keys(keys, (p0 + n_valid)[None]),
                             self.sampler_cfg)
        return tok, bad, pool

    # -- request lifecycle -------------------------------------------------
    def submit(self, prompt, max_new: int = 16,
               eos_id: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Queue one request; returns its uid.

        Malformed requests (empty prompt, zero budget, over capacity)
        still raise — those are caller bugs, not load.  Load shedding is
        status-typed: a full queue resolves the request ``REJECTED``
        immediately (empty result, no exception); ``deadline_ms``
        (default: the engine's) stamps an expiry the scheduler enforces.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if prompt.size + max_new > self.max_len:
            raise ValueError(
                f"prompt_len {prompt.size} + max_new {max_new} exceeds "
                f"max_len {self.max_len}")
        # ssm/hybrid prompts need NOT align to ssm_chunk: ssm_forward pads
        # the final chunk and masks the pad positions' dt, so the decode
        # cache is exactly the state after the real tokens
        uid = self._next_uid
        self._next_uid += 1
        self.metrics.on_submit(uid, prompt.size)
        if self._tracer is not None:
            self._tracer.instant("submit", tid="requests", uid=uid,
                                 prompt_len=int(prompt.size))
        if self.queue_cap is not None and len(self._queue) >= self.queue_cap:
            self._results[uid] = np.zeros(0, np.int32)
            self._status[uid] = RequestStatus.REJECTED
            self.metrics.on_reject(uid)
            if self._tracer is not None:
                self._tracer.instant("reject", tid="requests", uid=uid)
            return uid
        dl = deadline_ms if deadline_ms is not None else self.deadline_ms
        deadline = metrics._now() + dl / 1e3 if dl is not None else None
        self._queue.append(Request(uid, prompt, max_new, eos_id,
                                   deadline=deadline))
        self.metrics.observe_queue_depth(len(self._queue))
        return uid

    def status(self, uid: int) -> Optional[RequestStatus]:
        """Terminal status of ``uid`` (None while queued / in flight)."""
        return self._status.get(uid)

    @property
    def statuses(self) -> Dict[int, RequestStatus]:
        return dict(self._status)

    def _release_slot(self, slot: int) -> None:
        """Drop the slot's host state and (paged) its page references."""
        if self._paged:
            # registered prefix pages stay resident for reuse; everything
            # else decrefs back to the free list
            self._alloc.free_slot(slot)
            self._pstarted[slot] = False
        if slot in self._prefilling:
            self._prefilling.remove(slot)
        self._active[slot] = False
        self._reqs[slot] = None
        self._gen[slot] = []

    def _finish(self, slot: int,
                status: RequestStatus = RequestStatus.OK) -> None:
        req = self._reqs[slot]
        self._results[req.uid] = np.asarray(
            list(req.carry) + self._gen[slot], np.int32)
        self._status[req.uid] = status
        self.metrics.on_finish(req.uid, status.value)
        # harvest BEFORE the page release below makes the reads stale —
        # but only if this request actually wrote the slot (a request
        # resolved before its first chunk would harvest the previous
        # occupant's counters twice)
        started = not self.prefill_chunk or (
            self._pstarted[slot] if self._paged else self._pfill[slot] > 0)
        if self._packed and started:
            self._ovf += np.asarray(self._slot_tot(self._pool, slot),
                                    np.float64)
        if self._tracer is not None:
            self._tracer.instant("finish", tid="requests", uid=req.uid,
                                 slot=slot, status=status.value,
                                 new_tokens=len(self._gen[slot]))
        self._release_slot(slot)

    def _finish_queued(self, req: Request, status: RequestStatus) -> None:
        """Resolve a request that never (re)reached a slot."""
        self._results[req.uid] = np.asarray(list(req.carry), np.int32)
        self._status[req.uid] = status
        self.metrics.on_finish(req.uid, status.value)
        if self._tracer is not None:
            self._tracer.instant("finish", tid="requests", uid=req.uid,
                                 status=status.value)

    def _maybe_finish(self, slot: int, tok: int) -> bool:
        """Finish the slot if its budget is spent or ``tok`` is its EOS."""
        req = self._reqs[slot]
        if len(self._gen[slot]) >= req.max_new or \
                (req.eos_id is not None and tok == req.eos_id):
            self._finish(slot)
            return True
        return False

    # -- preemption --------------------------------------------------------
    def _preempt(self, victim: int) -> None:
        """Evict ``victim`` to the queue front, tokens-so-far carried.

        The requeued request's prompt is ``original prompt + generated
        tokens``: re-admission chunk-prefills it (sharing any still
        registered prefix pages), and the first token it samples sits at
        absolute position ``len(prompt) + len(carry)`` — exactly the key
        fold the uninterrupted decode would have used, so greedy and
        sampled streams resume bit-identically.  A request past
        ``max_preempts`` resolves FAILED instead (thrash bound).
        """
        req = self._reqs[victim]
        with obs_span("preempt", self._tracer, uid=req.uid, slot=victim,
                      n_preempt=req.n_preempt):
            self._preempt_impl(victim, req)

    def _preempt_impl(self, victim: int, req: Request) -> None:
        if req.n_preempt >= self.max_preempts:
            self._finish(victim, RequestStatus.FAILED)
            return
        gen = self._gen[victim]
        tokens = np.concatenate(
            [req.tokens, np.asarray(gen, np.int32)]) if gen else req.tokens
        nr = Request(req.uid, tokens, req.max_new - len(gen), req.eos_id,
                     deadline=req.deadline,
                     carry=tuple(req.carry) + tuple(gen),
                     n_preempt=req.n_preempt + 1)
        self._release_slot(victim)
        self._queue.appendleft(nr)
        self._status[req.uid] = RequestStatus.PREEMPTED
        self.metrics.on_preempt(req.uid)
        if self._auto_budget and self.prefill_chunk:
            # the requeue re-prefills and re-decodes: extend the drain
            # budget so an auto-budgeted run() still terminates cleanly
            self._budget += (-(-int(tokens.size) // self.prefill_chunk)
                             + nr.max_new + 2)

    def _handle_exhaustion(self, slot: int) -> bool:
        """Free pages for ``slot`` by preempting a sibling.

        Victim order: youngest *decoding* request first (most recent
        admission — least sunk cost, shortest re-prefill), then youngest
        prefilling one.  Never the requester itself: its re-admission
        would need at least the pages it already holds, so
        self-preemption cannot make progress.  Returns False when no
        sibling exists (the caller resolves the requester FAILED).
        """
        cands = [s for s in range(self.max_slots)
                 if s != slot and self._reqs[s] is not None
                 and self._active[s]]
        if not cands:
            cands = [s for s in range(self.max_slots)
                     if s != slot and self._reqs[s] is not None]
        if not cands:
            return False
        victim = max(cands, key=lambda s: self._seq[s])
        self._preempt(victim)
        return True

    def _ensure_blocks_safe(self, slot: int, start: int, n: int) -> bool:
        """`_ensure_blocks` that converts exhaustion into preemption.

        Retries after each preemption (freed pages recycle immediately;
        ``ensure_block`` is idempotent for blocks already made private).
        When no victim remains the requester resolves FAILED with its
        harvested tokens.  Never raises ``PageExhausted``.
        """
        while True:
            try:
                self._ensure_blocks(slot, start, n)
                return True
            except paged.PageExhausted:
                if not self._handle_exhaustion(slot):
                    self._finish(slot, RequestStatus.FAILED)
                    return False

    # -- deadlines ---------------------------------------------------------
    def _expire_queue(self) -> None:
        if not self._queue:
            return
        now = metrics._now()
        kept: collections.deque = collections.deque()
        for r in self._queue:
            if r.deadline is not None and now > r.deadline:
                self._finish_queued(r, RequestStatus.TIMED_OUT)
            else:
                kept.append(r)
        self._queue = kept

    def _expire_inflight(self) -> None:
        stamped = [s for s in range(self.max_slots)
                   if self._reqs[s] is not None
                   and self._reqs[s].deadline is not None]
        if not stamped:
            return
        now = metrics._now()
        for s in stamped:
            if self._reqs[s] is not None and now > self._reqs[s].deadline:
                self._finish(s, RequestStatus.TIMED_OUT)

    # -- admission ---------------------------------------------------------
    def _mark_admitted(self, slot: int, req: Request) -> None:
        self._admit_counter += 1
        self._seq[slot] = self._admit_counter
        self.metrics.on_admit(req.uid)
        if self._tracer is not None:
            self._tracer.instant("admitted", tid="requests", uid=req.uid,
                                 slot=slot)

    def _admit(self) -> None:
        """Fill free slots from the queue, grouping equal prompt lengths."""
        free = list(np.where(~self._active)[0])
        while self._queue and free:
            if self._faults is not None and not self._faults.admit_ok(
                    self._queue[0].uid, self._step_idx):
                break
            plen = self._queue[0].tokens.size
            cap = min(len(free), self._admit_group_cap)
            group: List[Request] = []
            while (self._queue and len(group) < cap
                   and self._queue[0].tokens.size == plen):
                group.append(self._queue.popleft())
            slots = [int(free.pop(0)) for _ in group]
            tokens = jnp.asarray(np.stack([r.tokens for r in group]))
            keys = jnp.stack([sampler.request_key(self.seed, r.uid)
                              for r in group])
            first, bad, entry = self._prefill(self._w, tokens, keys)
            self._pool = self._insert(self._pool, entry,
                                      jnp.asarray(slots, jnp.int32), keys)
            first = np.asarray(first)
            bad = np.asarray(bad)
            for r, s, tok, b in zip(group, slots, first, bad):
                self._mark_admitted(s, r)
                self._reqs[s], self._gen[s] = r, []
                self._tok[s], self._pos[s] = tok, plen
                self._keys[s] = np.asarray(
                    sampler.request_key(self.seed, r.uid))
                self._active[s] = True
                if b:   # NaN/Inf prefill logits: quarantine at admission
                    self._finish(s, RequestStatus.FAILED)
                    free.append(s)
                    continue
                self.metrics.on_token(r.uid)
                self._gen[s] = [int(tok)]
                if self._maybe_finish(s, int(tok)):
                    free.append(s)

    def _admit_chunked(self) -> None:
        """Assign queued requests to free slots immediately (no grouping,
        no prefill compute yet — chunks run one per engine step)."""
        free = [s for s in range(self.max_slots) if self._reqs[s] is None]
        i = 0
        while self._queue and free and i < len(self._queue):
            r = self._queue[i]
            if self._faults is not None and not self._faults.admit_ok(
                    r.uid, self._step_idx):
                i += 1          # held back: later requests may still admit
                continue
            del self._queue[i]
            s = free.pop(0)
            self._reqs[s] = r
            self._pfill[s] = 0
            self._pstarted[s] = False
            self._pos[s] = 0
            self._gen[s] = []
            self._active[s] = False
            key = sampler.request_key(self.seed, r.uid)
            self._keys[s] = np.asarray(key)
            if self._packed and self.cache_cfg.stochastic:
                # seed the slot's cache PRNG chains before its first chunk
                self._pool = self._seed_keys(self._pool, jnp.int32(s), key)
            self._prefilling.append(s)
            self._mark_admitted(s, r)

    def _ensure_blocks(self, slot: int, start: int, n: int) -> None:
        """Paged mode: make the blocks covering rows ``[start, start+n)``
        privately writable — allocate fresh pages at block boundaries and
        fork (copy-on-write) shared pages the slot is about to write."""
        P = self.page_size
        for b in range(start // P, (start + n - 1) // P + 1):
            act = self._alloc.ensure_block(slot, b)
            if act is None:
                continue
            kind, src, dst = act
            if kind == "cow":
                self._pool = self._cow(self._pool, jnp.int32(src),
                                       jnp.int32(dst))
            self._pool = self._set_block(self._pool, jnp.int32(slot),
                                         jnp.int32(b), jnp.int32(dst))

    def _step_prefill_chunk(self) -> None:
        """Run ONE chunk for the oldest prefilling slot (FIFO)."""
        if not self._prefilling:
            return
        s = self._prefilling[0]
        r = self._reqs[s]
        if self._paged and not self._pstarted[s]:
            # first chunk for this request: map its block table, reusing
            # any registered prefix pages (refcounted, read-only until a
            # write forces a copy-on-write fork).  FIFO chunk order means
            # an earlier request registers its prefix before a later
            # request's first chunk looks it up.
            pages, shared = (self._alloc.match_prefix(r.tokens)
                             if self._share_prefix else ([], 0))
            row = self._alloc.new_slot(s, pages)
            self._pool = self._reset_slot(
                self._pool, jnp.int32(s), jnp.int32(shared),
                jnp.asarray(row), jnp.float32(shared))
            self._pfill[s] = shared   # shared rows are already written
            self._pstarted[s] = True
        f = int(self._pfill[s])
        C = self.prefill_chunk
        n = min(C, r.tokens.size - f)
        toks = np.zeros((1, C), np.int32)
        toks[0, :n] = r.tokens[f:f + n]
        if self._paged and not self._ensure_blocks_safe(s, f, n):
            return                    # requester quarantined (no victim)
        first, bad, self._pool = self._chunk(
            self._w, self._pool, jnp.asarray(toks), jnp.int32(s), jnp.int32(f),
            jnp.int32(n), jnp.asarray(self._keys[s:s + 1]))
        self._pfill[s] = f + n
        self._pos[s] = f + n          # frontier (RoPE-safe while masked)
        self.metrics.on_prefill_chunk(r.uid)
        if f + n == r.tokens.size:    # final chunk: first token sampled
            self._prefilling.popleft()
            if self._paged and self._share_prefix:
                self._alloc.register_prefix(s, r.tokens)
            if bool(np.asarray(bad)[0]):
                # NaN/Inf prefill logits: quarantine before the poisoned
                # token enters the stream (carried tokens survive)
                self._active[s] = True
                self._finish(s, RequestStatus.FAILED)
                return
            tok = int(np.asarray(first)[0])
            self.metrics.on_token(r.uid)
            self._gen[s] = [tok]
            self._tok[s] = tok
            self._active[s] = True
            self._maybe_finish(s, tok)

    def step(self) -> None:
        """Admit what fits, run one prefill chunk (chunked mode), then
        decode one token on every active slot."""
        if self.mesh is not None:
            # mesh runs trace their jits under the ambient mesh: the
            # fused kernels' shard_map, the CP merge, and the attention
            # output gather all resolve axis names against it
            with jax.set_mesh(self.mesh):
                return self._step_body()
        return self._step_body()

    def _step_body(self) -> None:
        self._step_idx += 1
        tr = self._tracer
        if self._faults is not None:
            self._faults.on_step(self)
        self._expire_queue()
        with obs_span("admit", tr, queued=len(self._queue)):
            if self.prefill_chunk:
                self._admit_chunked()
            else:
                self._admit()
        if self.prefill_chunk and self._prefilling:
            s = self._prefilling[0]
            with obs_span("prefill_chunk", tr, uid=self._reqs[s].uid,
                          slot=int(s), p0=int(self._pfill[s])):
                self._step_prefill_chunk()
        if self._active.any():
            nan_mask = np.zeros(self.max_slots, bool)
            if self._faults is not None:
                nan_mask = self._faults.nan_mask(self)
            if self.prefill_chunk and self._paged:
                # each active slot appends one row at _pos this step —
                # fresh page at a block boundary, COW if still shared;
                # exhaustion preempts the youngest sibling, never raises
                for s in np.where(self._active)[0]:
                    s = int(s)
                    if self._active[s]:   # earlier preemption may clear it
                        self._ensure_blocks_safe(s, int(self._pos[s]), 1)
        if self._active.any():
            with obs_span("decode_step", tr,
                          n_active=int(self._active.sum())):
                if self.prefill_chunk:
                    nxt, bad, rate, self._pool = self._decode(
                        self._w, self._pool, jnp.asarray(self._tok),
                        jnp.asarray(self._pos), jnp.asarray(self._keys),
                        jnp.asarray(self._active), jnp.asarray(nan_mask))
                else:
                    nxt, bad, rate, self._pool = self._decode(
                        self._w, self._pool, jnp.asarray(self._tok),
                        jnp.asarray(self._pos), jnp.asarray(self._keys),
                        jnp.asarray(nan_mask))
                nxt, bad, rate = (np.asarray(nxt), np.asarray(bad),
                                  np.asarray(rate))
                self.metrics.on_decode_step()
                for s in np.where(self._active)[0]:
                    s = int(s)
                    if bad[s]:
                        # NaN/Inf decode logits: drop the poisoned token,
                        # quarantine the request, keep siblings untouched
                        self._finish(s, RequestStatus.FAILED)
                        continue
                    if self.runaway_ovf is not None and \
                            rate[s] > self.runaway_ovf:
                        # §5 overflow runaway: the controller lost the race
                        self._finish(s, RequestStatus.FAILED)
                        continue
                    tok = int(nxt[s])
                    self._gen[s].append(tok)
                    self._pos[s] += 1
                    self._tok[s] = tok
                    self.metrics.on_token(self._reqs[s].uid)
                    self._maybe_finish(s, tok)
        self._expire_inflight()
        if tr is not None:
            tr.counter("queue", {"queue_depth": len(self._queue),
                                 "active_slots": int(self._active.sum())})
        if self._numerics is not None and \
                self._step_idx % self._num_every == 0:
            self._sample_numerics()

    def _sample_numerics(self) -> None:
        """One §5 numeric-health sample: a single batched device fetch of
        the packed pool's exponents + overflow counters, diffed against
        the previous sample into per-slot JSONL records (controller
        up/down moves).  Runs only on the sampling cadence with a
        ``numerics_log`` attached — never on an unobserved step."""
        from repro.obs import serve_records
        if self._num_snap is None:
            self._num_snap = jax.jit(
                lambda pool: kv_pool.numerics_snapshot(pool, self.max_slots))
        snap = jax.device_get(self._num_snap(self._pool))
        uids = {s: self._reqs[s].uid for s in range(self.max_slots)
                if self._reqs[s] is not None and self._active[s]}
        if uids:
            recs = serve_records(snap, self._num_prev, step=self._step_idx,
                                 t=metrics._now(), slot_uids=uids)
            for rec in recs:
                self._numerics.record(rec)
            if self._tracer is not None and recs:
                rates = [r for rec in recs for r in rec["ovf_rate"]]
                exps = [e for rec in recs for e in rec["k_e"]]
                self._tracer.counter(
                    "numerics", {"ovf_rate_max": max(rates),
                                 "k_e_mean": sum(exps) / len(exps)},
                    tid="numerics")
        self._num_prev = snap

    def _drain_timeout(self) -> None:
        """Out of steps: resolve everything in flight instead of raising.

        In-flight slots resolve TIMED_OUT with every harvested token;
        queued requests resolve TIMED_OUT, except preempted ones which
        keep their terminal PREEMPTED (they had a slot and lost it)."""
        for s in range(self.max_slots):
            if self._reqs[s] is not None:
                self._finish(s, RequestStatus.TIMED_OUT)
        while self._queue:
            r = self._queue.popleft()
            self._finish_queued(r, RequestStatus.PREEMPTED if r.n_preempt
                                else RequestStatus.TIMED_OUT)

    def run(self, max_steps: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Drive until the queue drains; returns ``{uid: generated ids}``.

        Never raises for scheduling reasons: when the step budget runs
        out (``max_steps``, or the auto budget on a wedged engine) every
        in-flight request resolves ``TIMED_OUT`` with its harvested
        tokens and the partial results are returned — check
        :meth:`status` / :attr:`statuses` for per-request outcomes.
        """
        if max_steps is not None:
            self._budget = max_steps
            self._auto_budget = False
        else:
            pending = list(self._queue) + [r for r in self._reqs
                                           if r is not None]
            chunks = 0
            if self.prefill_chunk:
                chunks = sum(-(-r.tokens.size // self.prefill_chunk)
                             for r in pending)
            self._budget = (sum(r.max_new for r in pending) + chunks
                            + len(self._queue) + self.max_slots + 4)
            self._auto_budget = True
        steps = 0
        while self._queue or self._prefilling or self._active.any():
            if steps >= self._budget:
                self._drain_timeout()
                break
            self.step()
            steps += 1
        return dict(self._results)

    # -- introspection -----------------------------------------------------
    def reset_metrics(self) -> None:
        """Start a fresh measurement window (latency/throughput/overflow).

        Aggregates otherwise span the engine's whole lifetime — on an
        engine reused across waves, ``wall_s`` includes host idle time
        between ``run()`` calls, so reset before a wave you want to
        measure in isolation.
        """
        self.metrics = metrics.ServeMetrics()
        self._ovf = np.zeros(3, np.float64)

    def cache_stats(self) -> dict:
        """Append overflow rate over finished requests + in-flight slots."""
        live = kv_pool.overflow_summary(self._pool, self._active)
        ovf = self._ovf[0] + live["cache_overflow_rate"] * \
            live["cache_appends_quantized"]
        tot = self._ovf[2] + live["cache_appends_quantized"]
        return {"cache_overflow_rate": float(ovf / tot) if tot else 0.0,
                "cache_appends_quantized": float(tot)}

    def stats(self) -> dict:
        extra = self.cache_stats()
        if self._paged:
            extra.update(self._alloc.stats())
        return self.metrics.summary(extra=extra)
