import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# A compile-only tool: on a TPU host it must not take the chip, and its
# --all children inherit the pin.
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both MUST precede any jax import: jax locks the backend on first init.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real jit program (train_step for train shapes,
prefill/serve_step for inference shapes) with production in/out shardings,
``.lower().compile()``s it for the 16×16 single-pod (256 chips) and 2×16×16
two-pod (512 chips) meshes, and records:
  * per-device memory (argument/temp/output bytes — proves it fits),
  * per-device HLO FLOPs + bytes accessed (cost_analysis),
  * per-collective bytes parsed from the partitioned HLO,
into a JSON-lines results file that §Roofline reads.

Usage:
  python -m repro.launch.dryrun --arch llama3_8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--out results.jsonl]   # subprocess/cell
"""
import argparse
import dataclasses
import gzip
import json
import os as _os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.shapes import SHAPES, input_specs
from repro.core.policy import PrecisionPolicy
from repro.dist.context import multi_pod_ctx, single_pod_ctx
from repro.dist.sharding import ShardingRules
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim.opt import OptConfig, sgd_init
from repro.train import init_train_state, make_train_step

# Per-arch dry-run settings: paper-faithful DFXP (10/12) everywhere;
# float16 containers hold the DFXP grid exactly (≤12 bits) at half the HBM
# of f32 — used where f32 activations/storage cannot fit; llama4's 400B
# params additionally need packed int16 storage (DESIGN.md §2).
ARCH_SETTINGS = {
    "zamba2_1p2b": dict(compute="float32", storage="sim", microbatches=8),
    "llama3_8b": dict(compute="float32", storage="sim", microbatches=8),
    "qwen3_14b": dict(compute="float32", storage="sim", microbatches=8),
    "phi3_medium_14b": dict(compute="float32", storage="sim", microbatches=8),
    "gemma3_27b": dict(compute="float16", storage="sim", microbatches=16),
    "seamless_m4t_medium": dict(compute="float32", storage="sim",
                                microbatches=8),
    "llama4_maverick_400b": dict(compute="float16", storage="packed",
                                 microbatches=16),
    "granite_moe_1b": dict(compute="float32", storage="sim", microbatches=8),
    "mamba2_370m": dict(compute="float32", storage="sim", microbatches=8),
    "qwen2_vl_72b": dict(compute="float16", storage="sim", microbatches=16),
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16,
                "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3fnuz": 1,
                "f8e5m2fnuz": 1, "f8e4m3b11fnuz": 1, "f8e3m4": 1}


OVERRIDES: dict = {}


def policy_for(arch: str) -> PrecisionPolicy:
    s = ARCH_SETTINGS[arch]
    return PrecisionPolicy("dfxp", comp_width=10, update_width=12,
                           update_interval=100, storage=s["storage"],
                           compute_dtype=OVERRIDES.get("compute",
                                                       s["compute"]),
                           a2a_compress_bits=OVERRIDES.get("a2a_bits", 0))


_SHAPE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes per collective kind from partitioned HLO.

    Handles both forms:
      %all-gather.3 = bf16[8,5120,8192]{1,0} all-gather(%p) ...
      %all-to-all.12 = (s8[2,8,1024]{2,1,0}, s8[...], ...) all-to-all(...)
    (multi-operand collectives — e.g. the int8 lanes of
    ``compressed_all_to_all`` — lower to the tuple form; every element
    counts toward the wire bytes).
    """
    out = {k: 0.0 for k in COLLECTIVES}
    count = {k: 0 for k in COLLECTIVES}
    pat = re.compile(r"= (\([^)]*\)|\S+) ("
                     + "|".join(COLLECTIVES) + r")\(")
    for m in pat.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        size = 0.0
        for dt, dims in _SHAPE.findall(shapes):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _DTYPE_BYTES.get(dt, 4)
        out[kind] += size
        count[kind] += 1
    return {"bytes": out, "count": count,
            "total_bytes": sum(out.values())}


def _loss_builder(cfg, policy, dist, remat, ce_chunk=512):  # noqa: D103
    def loss_fn(p, b, s, exps):
        return T.loss_fn(cfg, policy, p, b, exps, s, dist=dist, remat=remat,
                         ce_chunk=ce_chunk)
    return loss_fn


def build_cell(arch: str, shape_name: str, multi_pod: bool):
    """Returns (jitted, example_args) ready to .lower(*args)."""
    cfg = configs.get(arch)
    if OVERRIDES.get("ssm_chunk"):
        cfg = dataclasses.replace(cfg, ssm_chunk=OVERRIDES["ssm_chunk"])
    shape = SHAPES[shape_name]
    policy = policy_for(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    dist = multi_pod_ctx() if multi_pod else single_pod_ctx()
    if OVERRIDES.get("attn_seq_shard"):
        dist = dataclasses.replace(dist, attn_seq_shard=True)
    if OVERRIDES.get("moe_stationary"):
        dist = dataclasses.replace(dist, moe_stationary=True)
    gs = T.group_shapes(cfg)
    cdtype = jnp.dtype(policy.compute_dtype)
    specs = input_specs(cfg, shape)

    long_ctx = shape_name == "long_500k"
    if long_ctx:
        # KV window is sharded (seq_shard_cache below): decode attention
        # must run the context-parallel exact-merge path over it.
        dist = dataclasses.replace(dist, cp_decode=True)
    rules = ShardingRules(mesh, multi_pod=multi_pod,
                          shard_batch=not long_ctx,
                          seq_shard_cache=long_ctx)

    if shape.kind == "train":
        mb = OVERRIDES.get("microbatches", ARCH_SETTINGS[arch]["microbatches"])
        if multi_pod:
            mb = min(mb, shape.global_batch // (2 * 16))
        opt_cfg = OptConfig(kind="sgd", lr=0.01, lr_decay_steps=100_000)
        loss_fn = _loss_builder(cfg, policy, dist,
                                remat=OVERRIDES.get("remat", "full"),
                                ce_chunk=OVERRIDES.get("ce_chunk", 512))
        step = make_train_step(loss_fn, gs, policy, opt_cfg,
                               microbatches=mb, compute_dtype=cdtype)

        def make_state():
            params = T.init_params(cfg, jax.random.PRNGKey(0))
            return init_train_state(params, sgd_init(params), gs, policy,
                                    init_exp=-8.0)

        state_shape = jax.eval_shape(make_state)
        state_sh = rules.state_shardings(state_shape)
        batch_sh = rules.batch_shardings(specs["batch"])
        rng_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
        jitted = jax.jit(step,
                         in_shardings=(state_sh, batch_sh, None),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        return jitted, (state_shape, specs["batch"], rng_s)

    # inference cells: params + scales only (no optimizer state)
    params_shape = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    params_sh = rules.params_shardings(params_shape)
    exps_shape = jax.eval_shape(
        lambda: {n: jnp.zeros(s, jnp.float32) for n, s in gs.items()})

    if shape.kind == "prefill":
        def prefill_step(params, batch, exps):
            sinks = {n: jnp.zeros(s + (3,), jnp.float32)
                     for n, s in gs.items() if n.startswith("g:")}
            logits, _, cache = T.forward(
                cfg, policy, params, batch, exps, sinks, dist,
                mode="prefill", max_cache_len=shape.seq_len)
            return logits[:, -1, :], cache

        batch_sh = rules.batch_shardings(specs["batch"])
        cache_shape = jax.eval_shape(
            lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len,
                                 src_len=(shape.seq_len if cfg.encoder_layers
                                          else 0), dtype=cdtype))
        cache_sh = rules.cache_shardings(cache_shape)
        logits_sh = jax.NamedSharding(
            mesh, jax.sharding.PartitionSpec(rules.dp, "model"))
        jitted = jax.jit(prefill_step,
                         in_shardings=(params_sh, batch_sh, None),
                         out_shardings=(logits_sh, cache_sh))
        return jitted, (params_shape, specs["batch"], exps_shape)

    # decode
    def serve_step(params, cache, tok, pos, exps):
        sinks = {n: jnp.zeros(s + (3,), jnp.float32)
                 for n, s in gs.items() if n.startswith("g:")}
        logits, _, cache2 = T.decode_step(cfg, policy, params, cache, tok,
                                          pos, exps, sinks, dist)
        return logits, cache2

    src_len = shape.seq_len if cfg.encoder_layers else 0
    cache_shape = jax.eval_shape(
        lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len,
                             src_len=src_len, dtype=cdtype))
    cache_sh = rules.cache_shardings(cache_shape)
    tok_spec = specs["tokens"]
    tok_sh = (jax.NamedSharding(mesh, jax.sharding.PartitionSpec(rules.dp))
              if rules.shard_batch else None)
    if cfg.input_mode == "embeds" and rules.shard_batch:
        tok_sh = jax.NamedSharding(
            mesh, jax.sharding.PartitionSpec(rules.dp, None, None))
    logits_sh = jax.NamedSharding(
        mesh, jax.sharding.PartitionSpec(
            rules.dp if rules.shard_batch else None, "model"))
    jitted = jax.jit(serve_step,
                     in_shardings=(params_sh, cache_sh, tok_sh, None, None),
                     out_shardings=(logits_sh, cache_sh),
                     donate_argnums=(1,))
    return jitted, (params_shape, cache_shape, tok_spec, specs["pos"],
                    exps_shape)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             hlo_dir: str = "hlo") -> dict:
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted, args = build_cell(arch, shape_name, multi_pod)
        lowered = jitted.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    if hlo_dir:
        _os.makedirs(hlo_dir, exist_ok=True)
        fname = f"{hlo_dir}/{arch}_{shape_name}_{rec['mesh']}.hlo.gz"
        with gzip.open(fname, "wt") as f:
            f.write(txt)
        rec["hlo"] = fname
    # loop-aware cost model (cost_analysis counts while bodies once;
    # benchmarks/hlo_cost multiplies by known_trip_count)
    try:
        from benchmarks.hlo_cost import analyze_text
        rec["loop_aware"] = analyze_text(txt)
    except Exception as e:  # keep the record even if the parser trips
        rec["loop_aware_error"] = str(e)[:200]
    rec.update({
        "ok": True,
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "per_device": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "flops": ca.get("flops", 0.0),
        "bytes_accessed": ca.get("bytes accessed", 0.0),
        "transcendentals": ca.get("transcendentals", 0.0),
        "collectives": collective_bytes(txt),
    })
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    # perf-iteration overrides (recorded via --tag)
    ap.add_argument("--tag", default="")
    ap.add_argument("--compute", default="")
    ap.add_argument("--remat", default="")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--a2a-bits", type=int, default=0)
    ap.add_argument("--ce-chunk", type=int, default=0)
    ap.add_argument("--ssm-chunk", type=int, default=0)
    ap.add_argument("--attn-seq-shard", action="store_true")
    ap.add_argument("--moe-stationary", action="store_true")
    args = ap.parse_args()
    if args.ssm_chunk:
        OVERRIDES["ssm_chunk"] = args.ssm_chunk
    if args.attn_seq_shard:
        OVERRIDES["attn_seq_shard"] = True
    if args.moe_stationary:
        OVERRIDES["moe_stationary"] = True
    if args.compute:
        OVERRIDES["compute"] = args.compute
    if args.remat:
        OVERRIDES["remat"] = args.remat
    if args.microbatches:
        OVERRIDES["microbatches"] = args.microbatches
    if args.a2a_bits:
        OVERRIDES["a2a_bits"] = args.a2a_bits
    if args.ce_chunk:
        OVERRIDES["ce_chunk"] = args.ce_chunk

    if args.all:
        cells = [(a, s, mp) for a in configs.ARCHS
                 for s in configs.cells(a) for mp in (False, True)]
        done = set()
        try:
            with open(args.out) as f:
                for line in f:
                    r = json.loads(line)
                    if r.get("ok"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
        except FileNotFoundError:
            pass
        for a, s, mp in cells:
            mesh_name = "2x16x16" if mp else "16x16"
            if (a, s, mesh_name) in done:
                print(f"skip (done): {a} {s} {mesh_name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--out", args.out]
            if mp:
                cmd.append("--multi-pod")
            print(f"=== {a} {s} {mesh_name}", flush=True)
            r = subprocess.run(cmd)
            if r.returncode != 0:
                with open(args.out, "a") as f:
                    f.write(json.dumps({"arch": a, "shape": s,
                                        "mesh": mesh_name, "ok": False}) + "\n")
        return

    rec = run_cell(args.arch, args.shape, args.multi_pod)
    if args.tag:
        rec["tag"] = args.tag
        rec["overrides"] = dict(OVERRIDES)
    line = json.dumps(rec)
    print(line)
    with open(args.out, "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
