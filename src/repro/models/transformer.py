"""Unified decoder/encoder-decoder LM covering all assigned architectures.

A model is a sequence of **stages**; each stage is a ``lax.scan`` over
``count`` repetitions of a *super-block* (an ordered tuple of sub-blocks).
This single mechanism expresses every assigned family without giving up
scan-over-layers (compact HLO, remat-friendly):

  * dense (llama3/qwen3/phi3/qwen2-vl):  stage = (attn, ffn) × L
  * gemma3 5:1 local:global:             super-block = 5×(local attn, ffn)
                                         + 1×(global attn, ffn), count=L//6
  * MoE (llama4 period 2, granite 1):    super-block interleaves ffn/moe
  * SSM (mamba2):                        stage = (mamba,) × L
  * hybrid (zamba2):                     super-block = 5×mamba + **shared**
                                         attn + shared ffn (weights stored
                                         once, closed over by the scan)
  * enc-dec (seamless):                  encoder stage (non-causal) +
                                         decoder stage with cross-attn

Sub-block window/theta are static per sub-block, so masks lower to compact
HLO. Quantization group names are derived statically from the same stage
structure (``group_shapes``), which is what sizes the DFXP ScaleState.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import MATMUL_PRECISION, PrecisionPolicy
from repro.core.tape import QTape
from repro.dist.context import DistCtx

from . import layers as L
from . import moe as M
from . import ssm as S

Array = jax.Array


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"          # dense|moe|ssm|hybrid|encdec
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 1024
    vocab_size: int = 1024
    # attention variants
    qk_norm: bool = False
    rope_theta: float = 1e6
    mrope_sections: Tuple[int, ...] = ()
    window: int = 0                # >0: sliding window for local layers
    local_global_pattern: int = 0  # N: N local then 1 global (gemma3: 5)
    local_rope_theta: float = 1e4  # theta for local (windowed) layers
    embed_scale: bool = False      # multiply embeds by sqrt(d_model) (gemma)
    # ffn
    ffn_kind: str = "swiglu"       # swiglu|gelu|maxout
    maxout_k: int = 2
    # moe
    num_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    moe_period: int = 1            # MoE every k-th layer (llama4: 2)
    shared_expert: bool = False
    capacity_factor: float = 1.25
    # ssm / hybrid
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    hybrid_period: int = 0         # zamba2: shared attn+ffn every N mamba
    # enc-dec
    encoder_layers: int = 0
    # io
    input_mode: str = "tokens"     # tokens|embeds
    tie_embeddings: bool = True

    @property
    def attn_spec(self) -> L.AttnSpec:
        return L.AttnSpec(self.d_model, self.num_heads, self.num_kv_heads,
                          self.head_dim, qk_norm=self.qk_norm,
                          rope_theta=self.rope_theta,
                          mrope_sections=self.mrope_sections)

    @property
    def ssm_spec(self) -> S.SSMSpec:
        return S.SSMSpec(self.d_model, self.ssm_state, self.ssm_headdim,
                         self.ssm_expand, chunk=self.ssm_chunk)

    @property
    def moe_spec(self) -> M.MoESpec:
        return M.MoESpec(self.d_model, self.moe_d_ff or self.d_ff,
                         self.num_experts, self.top_k,
                         capacity_factor=self.capacity_factor,
                         shared_expert_d_ff=self.d_ff if self.shared_expert
                         else 0)


@dataclasses.dataclass(frozen=True)
class SubBlock:
    kind: str                      # attn|xattn|ffn|moe|mamba
    window: int = 0                # 0 = global
    shared: bool = False
    causal: bool = True
    rope_theta: float = 0.0        # 0 → cfg.rope_theta


@dataclasses.dataclass(frozen=True)
class Stage:
    name: str
    count: int
    blocks: Tuple[SubBlock, ...]
    decoder: bool = True           # participates in decode path


def build_stages(cfg: ModelConfig) -> Tuple[Stage, ...]:
    stages = []
    if cfg.encoder_layers:
        stages.append(Stage("enc", cfg.encoder_layers,
                            (SubBlock("attn", causal=False),
                             SubBlock("ffn")), decoder=False))

    Ld = cfg.num_layers
    if cfg.family == "ssm":
        stages.append(Stage("dec", Ld, (SubBlock("mamba"),)))
    elif cfg.family == "hybrid":
        p = cfg.hybrid_period or 6
        reps, rem = divmod(Ld, p)
        blocks = tuple(SubBlock("mamba") for _ in range(p)) + (
            SubBlock("attn", shared=True), SubBlock("ffn", shared=True))
        stages.append(Stage("dec", reps, blocks))
        if rem:
            stages.append(Stage("dec_tail", 1,
                                tuple(SubBlock("mamba") for _ in range(rem))))
    elif cfg.local_global_pattern:
        n = cfg.local_global_pattern
        reps, rem = divmod(Ld, n + 1)
        local = (SubBlock("attn", window=cfg.window,
                          rope_theta=cfg.local_rope_theta), SubBlock("ffn"))
        glob = (SubBlock("attn"), SubBlock("ffn"))
        stages.append(Stage("dec", reps, local * n + glob))
        if rem:
            stages.append(Stage("dec_tail", 1, local * rem))
    elif cfg.num_experts:
        p = cfg.moe_period
        reps, rem = divmod(Ld, p)
        blocks = []
        for i in range(p):
            blocks.append(SubBlock("attn"))
            blocks.append(SubBlock("moe" if i == p - 1 else "ffn"))
        stages.append(Stage("dec", reps, tuple(blocks)))
        assert rem == 0, "num_layers must divide moe_period"
    else:
        blocks = [SubBlock("attn", window=cfg.window)]
        if cfg.encoder_layers:
            blocks.append(SubBlock("xattn"))
        blocks.append(SubBlock("ffn"))
        stages.append(Stage("dec", Ld, tuple(blocks)))
    return tuple(stages)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, blk: SubBlock) -> dict:
    p = {"norm": jnp.ones((cfg.d_model,), jnp.float32)}
    if blk.kind in ("attn", "xattn"):
        spec = cfg.attn_spec
        if blk.rope_theta:
            spec = dataclasses.replace(spec, rope_theta=blk.rope_theta)
        p.update(L.init_attn(key, spec))
    elif blk.kind == "ffn":
        if cfg.ffn_kind == "swiglu":
            p.update(L.init_swiglu(key, cfg.d_model, cfg.d_ff))
        elif cfg.ffn_kind == "gelu":
            p.update(L.init_gelu_ffn(key, cfg.d_model, cfg.d_ff))
        else:
            p.update(L.init_maxout(key, cfg.d_model, cfg.d_ff, cfg.maxout_k))
    elif blk.kind == "moe":
        p.update(M.init_moe(key, cfg.moe_spec))
    elif blk.kind == "mamba":
        p.update(S.init_ssm(key, cfg.ssm_spec))
    else:
        raise ValueError(blk.kind)
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    stages = build_stages(cfg)
    keys = jax.random.split(key, len(stages) + 3)
    params: dict = {"stages": {}}
    for si, stage in enumerate(stages):
        stacked, shared = {}, {}
        for i, blk in enumerate(stage.blocks):
            bkey = f"{i}:{blk.kind}"
            k = jax.random.fold_in(keys[si], i)
            if blk.shared:
                shared[bkey] = _init_block(k, cfg, blk)
            else:
                ks = jax.random.split(k, stage.count)
                stacked[bkey] = jax.vmap(
                    lambda kk: _init_block(kk, cfg, blk))(ks)
        params["stages"][stage.name] = {"stacked": stacked, "shared": shared}
    if cfg.input_mode == "tokens":
        params["embed"] = L.init_embed(keys[-3], cfg.vocab_size, cfg.d_model)
    if not (cfg.tie_embeddings and cfg.input_mode == "tokens"):
        params["head"] = L.init_dense(keys[-2], cfg.d_model, cfg.vocab_size,
                                      scale=0.02)
    params["final_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
    if cfg.encoder_layers:
        params["enc_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
    return params


# ---------------------------------------------------------------------------
# quantization groups
# ---------------------------------------------------------------------------

_SITES = {
    "attn": (("wq", "wk", "wv", "wo"), ("qkv", "k", "v", "out", "res")),
    "xattn": (("wq", "wk", "wv", "wo"), ("qkv", "k", "v", "out", "res")),
    "ffn": {
        "swiglu": (("w_gate", "w_up", "w_down"), ("pre", "out", "res")),
        "gelu": (("w_in", "w_out"), ("pre", "out", "res")),
        "maxout": (("w",), ("out", "res")),
    },
    "moe": (("w_gate", "w_up", "w_down"),
            ("dispatch", "pre", "expert_out", "out", "res")),
    "mamba": (("in_proj", "out_proj"), ("x", "y", "out", "state", "res")),
}


def _block_sites(cfg: ModelConfig, blk: SubBlock):
    if blk.kind == "ffn":
        w, a = _SITES["ffn"][cfg.ffn_kind]
    else:
        w, a = _SITES[blk.kind]
    if blk.kind == "moe" and cfg.shared_expert:
        w = w + ("shared/w_gate", "shared/w_up", "shared/w_down")
        a = a + ("shared/pre", "shared/out")
    return w, a


def group_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    """All quantization scale groups and their shapes (() or (count,))."""
    groups: Dict[str, tuple] = {}
    for stage in build_stages(cfg):
        for i, blk in enumerate(stage.blocks):
            pfx = f"{stage.name}/{i}:{blk.kind}"
            shape = () if blk.shared else (stage.count,)
            w_sites, a_sites = _block_sites(cfg, blk)
            for s in w_sites:
                groups[f"w:{pfx}/{s}"] = shape
            for s in a_sites:
                groups[f"a:{pfx}/{s}"] = shape
                groups[f"g:{pfx}/{s}"] = shape
    if cfg.input_mode == "tokens":
        groups["w:emb/w"] = ()
    for g in ("a:emb/out", "g:emb/out", "w:head/w", "a:head/logits",
              "g:head/logits"):
        groups[g] = ()
    return groups


def _subdict(d: Dict[str, Array], keys) -> Dict[str, Array]:
    return {k: d[k] for k in keys if k in d}


def _stage_group_names(cfg, stage, shared: bool):
    names = []
    for i, blk in enumerate(stage.blocks):
        if blk.shared != shared:
            continue
        pfx = f"{stage.name}/{i}:{blk.kind}"
        w_sites, a_sites = _block_sites(cfg, blk)
        names += [f"w:{pfx}/{s}" for s in w_sites]
        for s in a_sites:
            names += [f"a:{pfx}/{s}", f"g:{pfx}/{s}"]
    return names


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _ring_cache(k: Array, v: Array, cap: int):
    """Pack full-sequence KV [B,S,K,hd] into a ring buffer of ``cap`` slots.

    Position ``p`` lives in slot ``p % cap``; the slots are static, so the
    ring is built by padding (``S <= cap``) or one rotation of the last
    ``cap`` rows — slices, not a scatter (sibling scatters with shared
    indices crash the TPU compiler's scatter emitter).
    """
    B, S = k.shape[:2]
    if S <= cap:
        pad = ((0, 0), (0, cap - S)) + ((0, 0),) * (k.ndim - 2)
        ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
    else:   # kept row j holds position S - cap + j, i.e. slot (j + S) % cap
        ck = jnp.roll(k[:, S - cap:], S % cap, axis=1)
        cv = jnp.roll(v[:, S - cap:], S % cap, axis=1)
    pos_keep = np.arange(max(S - cap, 0), S)
    pos = np.full(cap, -1, np.int32)
    pos[pos_keep % cap] = pos_keep
    return {"k": ck, "v": cv,
            "pos": jnp.broadcast_to(jnp.asarray(pos), (B, cap))}


def _apply_block(cfg: ModelConfig, blk: SubBlock, pfx: str, bp, x, positions,
                 tape: QTape, dist: DistCtx, memory, mode: str,
                 cache_in=None, max_cache_len: int = 0, kv_codec=None,
                 n_valid=None, append_mask=None):
    """Apply one sub-block (pre-norm residual). Returns (x, cache_out)."""
    h = L.rmsnorm(x, bp["norm"])
    cache_out = None
    window = blk.window if blk.window > 0 else None
    if mode == "chunk" and blk.kind not in ("attn", "ffn"):
        # chunked prefill is attention-family only: MoE capacity and SSM
        # state couple a whole prompt (ServeEngine keeps those on the
        # whole-prompt path), and xattn needs an encoder pass
        raise ValueError(f"chunked prefill does not support {blk.kind!r}")
    if blk.kind in ("attn", "xattn"):
        spec = cfg.attn_spec
        if blk.rope_theta:
            spec = dataclasses.replace(spec, rope_theta=blk.rope_theta)
        if not blk.causal:
            spec = dataclasses.replace(spec, causal=False)
        kv_src = memory if blk.kind == "xattn" else None
        if mode == "train" or blk.kind == "xattn" and mode == "prefill":
            if dist.attn_seq_shard and dist.token_axes:
                # heads don't divide the TP degree (e.g. phi3 40H/10KV):
                # shard attention over the *sequence* instead of replicating
                from jax.sharding import PartitionSpec as _P
                h = jax.lax.with_sharding_constraint(
                    h, _P(dist.token_axes, "model", None))
            y = L.attention_train(bp, spec, h, positions, tape, pfx,
                                  window=window, kv_source=kv_src)
            if dist.attn_seq_shard and dist.token_axes:
                from jax.sharding import PartitionSpec as _P
                y = jax.lax.with_sharding_constraint(
                    y, _P(dist.token_axes, None, None))
            if blk.kind == "xattn" and mode == "prefill":
                # cross-attn KV is static over decode: cache it once
                Sk = memory.shape[1]
                k = tape.dot(f"{pfx}/wk", memory, bp["wk"]).reshape(
                    memory.shape[0], Sk, spec.num_kv_heads, spec.head_dim)
                v = tape.dot(f"{pfx}/wv", memory, bp["wv"]).reshape(
                    memory.shape[0], Sk, spec.num_kv_heads, spec.head_dim)
                cache_out = {"k": k, "v": v}
        elif mode == "prefill":
            y, (k, v) = L.attention_prefill(bp, spec, h, positions, tape,
                                            pfx, window=window)
            cap = min(window, max_cache_len) if window else max_cache_len
            cache_out = _ring_cache(k, v, cap)
        elif mode == "chunk":
            y, cache_out = L.attention_prefill_chunk(
                bp, spec, h, positions, cache_in, tape, pfx,
                n_valid=n_valid, window=window, dist=dist, codec=kv_codec)
        else:  # decode
            if blk.kind == "xattn":
                y = _xattn_decode(bp, spec, h, cache_in, tape, pfx)
                cache_out = cache_in
            else:
                y, cache_out = L.attention_decode(
                    bp, spec, h, positions, cache_in, tape, pfx,
                    window=window, dist=dist, codec=kv_codec,
                    append_mask=append_mask)
    elif blk.kind == "ffn":
        if cfg.ffn_kind == "swiglu":
            y = L.swiglu(bp, h, tape, pfx)
        elif cfg.ffn_kind == "gelu":
            y = L.gelu_ffn(bp, h, tape, pfx)
        else:
            y = L.maxout(bp, h, tape, pfx)
    elif blk.kind == "moe":
        y = M.moe_ffn(bp, cfg.moe_spec, h, tape, pfx, dist,
                      dropless=(mode == "decode"))
    elif blk.kind == "mamba":
        if mode == "decode":
            y, cache_out = S.ssm_decode(bp, cfg.ssm_spec, h, cache_in, tape,
                                        pfx)
        else:
            y, cache_out = S.ssm_forward(bp, cfg.ssm_spec, h, tape, pfx,
                                         return_cache=(mode == "prefill"))
    else:
        raise ValueError(blk.kind)
    x = x + y.astype(x.dtype)
    x = tape.act(f"{pfx}/res", x)
    return x, cache_out


def _xattn_decode(bp, spec, h, cache, tape, pfx):
    """Cross-attention during decode: static KV from the prefill cache."""
    B = h.shape[0]
    q = tape.dot(f"{pfx}/wq", h, bp["wq"]).reshape(
        B, 1, spec.num_heads, spec.head_dim)
    k, v = cache["k"], cache["v"]
    K, G = spec.num_kv_heads, spec.num_heads // spec.num_kv_heads
    qg = q.reshape(B, 1, K, G, spec.head_dim)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(spec.head_dim))
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32),
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, spec.q_dim).astype(h.dtype)
    y = tape.dot(f"{pfx}/wo", o, bp["wo"])
    return tape.act(f"{pfx}/out", y)


def _run_stage(cfg, policy, stage: Stage, sp, x, positions, scales, sinks,
               dist, memory, mode: str, cache=None, remat: str = "none",
               max_cache_len: int = 0, kv_codec=None, n_valid=None,
               append_mask=None):
    """Scan one stage. Returns (x, stats, cache_out)."""
    stacked_names = _stage_group_names(cfg, stage, shared=False)
    shared_names = _stage_group_names(cfg, stage, shared=True)
    sc_stacked = _subdict(scales, stacked_names)
    sk_stacked = _subdict(sinks, [n for n in stacked_names
                                  if n.startswith("g:")])
    sc_shared = _subdict(scales, shared_names)
    sk_shared = _subdict(sinks, [n for n in shared_names
                                 if n.startswith("g:")])

    def body(x, xs):
        p_st, sc_st, sk_st, cache_st = xs
        tape = QTape(policy, {**sc_st, **sc_shared}, {**sk_st, **sk_shared})
        cache_out = {}
        for i, blk in enumerate(stage.blocks):
            bkey = f"{i}:{blk.kind}"
            bp = sp["shared"][bkey] if blk.shared else p_st[bkey]
            ci = None if cache_st is None else cache_st.get(bkey)
            x, co = _apply_block(cfg, blk, f"{stage.name}/{bkey}", bp, x,
                                 positions, tape, dist, memory, mode, ci,
                                 max_cache_len=max_cache_len,
                                 kv_codec=kv_codec, n_valid=n_valid,
                                 append_mask=append_mask)
            if co is not None:
                cache_out[bkey] = co
        return x, (tape.stats, cache_out)

    if remat != "none" and mode == "train":
        pol = (jax.checkpoint_policies.checkpoint_dots if remat == "dots"
               else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=pol)

    xs = (sp["stacked"], sc_stacked, sk_stacked, cache)
    x, (stats, cache_out) = jax.lax.scan(body, x, xs, length=stage.count)
    # shared groups: one scale, stats summed over iterations
    stats = {n: (s.sum(0) if n in shared_names else s)
             for n, s in stats.items()}
    return x, stats, cache_out


def forward(cfg: ModelConfig, policy: PrecisionPolicy, params, batch,
            scales: Dict[str, Array], sinks: Dict[str, Array],
            dist: DistCtx = DistCtx(), *, mode: str = "train",
            remat: str = "none", max_cache_len: int = 0):
    """Full forward. Returns (logits, stats, cache|None).

    ``batch``: dict with ``tokens`` [B,S] or ``embeds`` [B,S,D]; optional
    ``positions`` ([B,S] or [3,B,S] for M-RoPE); encoder-decoder models add
    ``src_embeds`` [B,Ssrc,D].
    """
    tape = QTape(policy, scales, sinks)   # for embed/head sites
    stats: Dict[str, Array] = {}

    if cfg.input_mode == "tokens":
        x = L.embed(params["embed"], batch["tokens"], tape)
    else:
        x = tape.act("emb/out", batch["embeds"])
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    x = x.astype(jnp.dtype(policy.compute_dtype))

    B, Sq = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Sq), (B, Sq))

    # encoder (if any)
    memory = None
    stages = build_stages(cfg)
    if cfg.encoder_layers:
        src = batch["src_embeds"]
        mpos = jnp.broadcast_to(jnp.arange(src.shape[1]),
                                (src.shape[0], src.shape[1]))
        enc_stage = stages[0]
        memory, st, _ = _run_stage(cfg, policy, enc_stage,
                                   params["stages"]["enc"], src, mpos,
                                   scales, sinks, dist, None, "train",
                                   remat=remat)
        memory = L.rmsnorm(memory, params["enc_norm"])
        stats.update(st)
        stages = stages[1:]

    cache_all = {}
    block_mode = "train" if mode == "hidden" else mode
    for stage in stages:
        x, st, cache_out = _run_stage(cfg, policy, stage,
                                      params["stages"][stage.name], x,
                                      positions, scales, sinks, dist, memory,
                                      block_mode, remat=remat,
                                      max_cache_len=max_cache_len)
        stats.update(st)
        if cache_out:
            cache_all[stage.name] = cache_out

    if mode == "prefill":
        # decode only needs the last position: skip the full-seq head matmul
        x = x[:, -1:, :]
    x = L.rmsnorm(x, params["final_norm"])
    if mode == "hidden":
        # caller fuses head + loss (chunked CE): don't materialize logits
        stats.update(tape.stats)
        return x, stats, None
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        logits = L.lm_head(params["embed"], x, tape, tied=True)
    else:
        logits = L.lm_head(params["head"], x, tape, tied=False)

    stats.update(tape.stats)
    if mode == "prefill" and memory is not None:
        cache_all["enc_memory"] = memory
    return logits, stats, (cache_all or None)


def prefill(cfg: ModelConfig, policy, params, batch, scales, sinks,
            dist: DistCtx = DistCtx(), *, max_cache_len: int):
    """Prefill: returns (last-position logits, decode cache)."""
    logits, stats, cache = forward(cfg, policy, params, batch, scales, sinks,
                                   dist, mode="prefill",
                                   max_cache_len=max_cache_len)
    return logits[:, -1, :], stats, cache


def decode_step(cfg: ModelConfig, policy, params, cache, tokens_or_embeds,
                pos, scales, sinks, dist: DistCtx = DistCtx(),
                kv_codec=None, append_mask=None):
    """One decoding step. ``tokens_or_embeds``: [B] ids or [B,1,D] embeds;
    ``pos``: current position — a scalar int (lockstep decode) or a
    per-sequence ``[B]`` vector (continuous batching: every slot decodes
    at its own position). ``kv_codec``: optional KV-cache storage codec
    (see :class:`repro.models.layers.RawKVCodec`); the default is the
    float ring buffer. ``append_mask`` (bool [B], optional) drops the
    cache append for masked-off rows — slots mid-chunked-prefill decode
    garbage that must not be written. Returns (logits [B,V], stats,
    cache')."""
    tape = QTape(policy, scales, sinks)
    stats: Dict[str, Array] = {}
    if cfg.input_mode == "tokens":
        x = L.embed(params["embed"], tokens_or_embeds[:, None], tape)
    else:
        x = tape.act("emb/out", tokens_or_embeds)
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    x = x.astype(jnp.dtype(policy.compute_dtype))
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = (jnp.broadcast_to(pos, (B, 1)) if pos.ndim == 0
                 else pos.reshape(B, 1))

    memory = cache.get("enc_memory") if cfg.encoder_layers else None
    new_cache = dict(cache)
    for stage in build_stages(cfg):
        if not stage.decoder:
            continue
        x, st, cache_out = _run_stage(cfg, policy, stage,
                                      params["stages"][stage.name], x,
                                      positions, scales, sinks, dist, memory,
                                      "decode", cache=cache[stage.name],
                                      kv_codec=kv_codec,
                                      append_mask=append_mask)
        stats.update(st)
        new_cache[stage.name] = cache_out

    x = L.rmsnorm(x, params["final_norm"])
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        logits = L.lm_head(params["embed"], x, tape, tied=True)
    else:
        logits = L.lm_head(params["head"], x, tape, tied=False)
    stats.update(tape.stats)
    return logits[:, -1, :], stats, new_cache


def prefill_chunk_step(cfg: ModelConfig, policy, params, cache, tokens,
                       p0, n_valid, scales, sinks, dist: DistCtx = DistCtx(),
                       kv_codec=None):
    """One chunked-prefill step: ``C`` prompt positions against the cache.

    ``tokens``: [B, C] ids — positions ``p0 + i`` of the prompt, rows
    ``>= n_valid`` zero-padded (a ragged final chunk; masked in-kernel).
    ``cache``: a decode cache/pool (attention ring entries only — chunked
    prefill is attention-family only, see ``_apply_block``).  Each layer
    attends the chunk against its already-written history plus the
    chunk's own K/V causally, then writes the chunk K/V through
    ``kv_codec`` (packed pools quantize on write; ``p0 == 0`` resets and
    calibrates the slot).  Returns (last-valid-position logits [B, V],
    stats, cache') — the logits sample the request's first token when the
    chunk is final, exactly where whole-prompt ``prefill`` samples it.
    """
    if cfg.input_mode != "tokens":
        raise ValueError("chunked prefill serves token-in models")
    tape = QTape(policy, scales, sinks)
    stats: Dict[str, Array] = {}
    x = L.embed(params["embed"], tokens, tape)
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    x = x.astype(jnp.dtype(policy.compute_dtype))
    B, C = tokens.shape
    p0 = jnp.asarray(p0, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    positions = p0[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]

    new_cache = dict(cache)
    for stage in build_stages(cfg):
        if not stage.decoder:
            continue
        x, st, cache_out = _run_stage(cfg, policy, stage,
                                      params["stages"][stage.name], x,
                                      positions, scales, sinks, dist, None,
                                      "chunk", cache=cache[stage.name],
                                      kv_codec=kv_codec, n_valid=n_valid)
        stats.update(st)
        new_cache[stage.name] = cache_out

    # only the last valid position's logits matter (first sampled token)
    idx = jnp.clip(n_valid - 1, 0, C - 1)
    x = jnp.take_along_axis(
        x, jnp.broadcast_to(idx[:, None, None], (B, 1, x.shape[-1])), axis=1)
    x = L.rmsnorm(x, params["final_norm"])
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        logits = L.lm_head(params["embed"], x, tape, tied=True)
    else:
        logits = L.lm_head(params["head"], x, tape, tied=False)
    stats.update(tape.stats)
    return logits[:, -1, :], stats, new_cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               src_len: int = 0, dtype=jnp.float32) -> dict:
    """Zero decode cache for ``batch`` sequences of capacity ``max_len``."""
    cache: dict = {}
    for stage in build_stages(cfg):
        if not stage.decoder:
            continue
        sc: dict = {}
        for i, blk in enumerate(stage.blocks):
            bkey = f"{i}:{blk.kind}"
            n = stage.count
            if blk.kind == "attn":
                cap = min(blk.window, max_len) if blk.window else max_len
                K, hd = cfg.num_kv_heads, cfg.head_dim
                sc[bkey] = {
                    "k": jnp.zeros((n, batch, cap, K, hd), dtype),
                    "v": jnp.zeros((n, batch, cap, K, hd), dtype),
                    "pos": jnp.full((n, batch, cap), -1, jnp.int32),
                }
            elif blk.kind == "xattn":
                K, hd = cfg.num_kv_heads, cfg.head_dim
                sc[bkey] = {
                    "k": jnp.zeros((n, batch, src_len, K, hd), dtype),
                    "v": jnp.zeros((n, batch, src_len, K, hd), dtype),
                }
            elif blk.kind == "mamba":
                s = cfg.ssm_spec
                sc[bkey] = {
                    "conv": jnp.zeros((n, batch, s.conv_kernel - 1,
                                       s.conv_dim), dtype),
                    "state": jnp.zeros((n, batch, s.heads, s.headdim,
                                        s.state), jnp.float32),
                }
        cache[stage.name] = sc
    if cfg.encoder_layers:
        cache["enc_memory"] = jnp.zeros((batch, src_len, cfg.d_model), dtype)
    return cache


def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss_fn(cfg, policy, params, batch, scales, sinks,
            dist: DistCtx = DistCtx(), remat: str = "none",
            ce_chunk: int = 0):
    """Mean cross-entropy; returns (loss, stats).

    ``ce_chunk``: if >0, the LM-head matmul + softmax-CE are computed over
    sequence chunks of this many positions inside a rematerialized scan, so
    the [tokens, vocab] logits tensor never materializes (decisive for 256k
    vocabularies at 4k×256 batches).
    """
    labels = batch["labels"]
    if not ce_chunk:
        logits, stats, _ = forward(cfg, policy, params, batch, scales, sinks,
                                   dist, mode="train", remat=remat)
        ll = _ce(logits, labels)
        mask = batch.get("loss_mask")
        if mask is None:
            loss = -ll.mean()
        else:
            loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return loss, stats

    hidden, stats, _ = forward(cfg, policy, params, batch, scales, sinks,
                               dist, mode="hidden", remat=remat)
    tape = QTape(policy, scales, sinks)
    tied = cfg.tie_embeddings and cfg.input_mode == "tokens"
    w = tape.weight("head/w", params["embed"] if tied else params["head"])
    B, S, D = hidden.shape
    assert S % ce_chunk == 0, (S, ce_chunk)
    nch = S // ce_chunk
    xc = hidden.reshape(B, nch, ce_chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nch, ce_chunk).transpose(1, 0, 2)
    fmt = policy.comp_format()
    head_sink = sinks.get("g:head/logits", jnp.zeros((3,), jnp.float32))

    def body(acc, xs):
        xch, lch = xs
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", xch, w.astype(xch.dtype),
                                precision=MATMUL_PRECISION,
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bsd,dv->bsv", xch, w.astype(xch.dtype),
                                precision=MATMUL_PRECISION,
                                preferred_element_type=jnp.float32)
        from repro.core.quant import q_stats, qbound
        logits = qbound(logits, fmt, fmt, scales.get("a:head/logits", 0.0),
                        scales.get("g:head/logits", 0.0), head_sink)
        st = q_stats(logits, fmt, scales.get("a:head/logits", 0.0))
        return acc + jnp.sum(_ce(logits, lch)), st

    body = jax.checkpoint(body)
    total, head_stats = jax.lax.scan(body, jnp.float32(0), (xc, lc))
    stats["a:head/logits"] = head_stats.sum(0)
    stats.update(tape.stats)
    return -total / (B * S), stats
