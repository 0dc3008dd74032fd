#!/usr/bin/env python3
"""Compile each cell's programs at their real sizes for a described TPU
v5e (no chip attached) and print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python bench/tools/rehearse.py [workload ...]

Only shapes are passed: nothing is allocated for the model, and nothing
runs.  The program's kernels are steered onto their compiled (not
interpreted) path with heuristic tiles, as the autotuner would time
candidates on this CPU.  The plain references are compiled too, since
they run on the chip after each window.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

GIB = 2 ** 30


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes / GIB:.2f} GiB, "
          f"outputs {m.output_size_in_bytes / GIB:.2f}, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f}, aliased "
          f"{m.alias_size_in_bytes / GIB:.2f}: total {total / GIB:.2f} GiB",
          flush=True)


def main(argv):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import run as R
    from bench import weights
    from repro import configs
    from repro.core.policy import PrecisionPolicy
    from repro.kernels import _tiling, dispatch
    from repro.models import transformer as T

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    _tiling._BACKEND["interpret"] = False
    dispatch.set_autotune(measure=False)

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        spec = R.load_spec(ROOT, name)
        config, mix = spec["config"], spec["mix"]
        cfg = configs.get(config["repro_config"])
        m = config["model"]
        kind = config["reference"]
        params = place(weights.abstract_params(kind, m))
        ref = weights.reference(kind)
        if mix["mode"] == "train":
            from repro.optim.opt import OptConfig, sgd_init
            from repro.train import benign_injection, init_train_state
            from repro.train.step import make_train_step
            pol = PrecisionPolicy(**config["policy"])
            gs = T.group_shapes(cfg)
            step = jax.jit(make_train_step(
                lambda p, b, s, e: T.loss_fn(cfg, pol, p, b, e, s), gs,
                pol, OptConfig(**config["optimizer"]), supervise=True))
            state = place(jax.eval_shape(
                lambda p: init_train_state(p, sgd_init(p), gs, pol),
                params))
            B, L = mix["batch"], mix["seq_len"]
            batch = {"tokens": S((B, L), jnp.int32),
                     "labels": S((B, L), jnp.int32)}
            report(f"{name} train step", step.lower(
                state, batch, S((2,), jnp.uint32), {},
                place(jax.eval_shape(benign_injection))).compile())
            rstep = ref.make_step(m)
            report(f"{name} reference step", rstep.lower(
                params, params, batch, S((), jnp.float32),
                S((), jnp.float32)).compile())
        else:
            from repro.serve import EngineOptions, ServeEngine
            eng = ServeEngine(
                cfg, PrecisionPolicy(**config["policy"]), params,
                max_slots=mix["slots"], max_len=mix["max_len"],
                options=EngineOptions(
                    cache_bits=config["serve"]["cache_bits"]))
            w, pool = place(eng._w), place(eng._pool)
            keys = S((1, 2), jnp.uint32)
            for L in mix["prompt_lens"]:
                args = (w, S((1, L), jnp.int32), keys)
                report(f"{name} prefill L={L}",
                       eng._prefill.lower(*args).compile())
            entry = place(jax.eval_shape(eng._prefill, *args)[2])
            report(f"{name} insert", eng._insert.lower(
                pool, entry, S((1,), jnp.int32), keys).compile())
            n = mix["slots"]
            report(f"{name} decode", eng._decode.lower(
                w, pool, S((n,), jnp.int32), S((n,), jnp.int32),
                S((n, 2), jnp.uint32), S((n,), jnp.bool_)).compile())
            del eng
            report(f"{name} reference logits T={mix['max_len']}",
                   ref.make_logits(m).lower(
                       params, S((mix["max_len"],), jnp.int32),
                       S((), jnp.int32)).compile())


if __name__ == "__main__":
    main(sys.argv[1:])
