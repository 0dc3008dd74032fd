"""The harness end to end on the CPU at smoke size: cells and metrics
found by name from files alone, a sound run judged correct, and each
fault a cell can have judged not correct.  The harness's look for a chip
is skipped (``require_chip=False``); everything else runs as on the
chip.  Run with ``python -m pytest bench/tests``."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from bench import run as R

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 77

# Limits at smoke size, set from smoke readings (CPU, 4 layers): a sound
# run read loss_gap <= 5e-4, grad_gap <= 0.03, update_gap <= 0.04,
# grad_diff <= 0.15, grad_diff_med <= 0.12, update_diff <= 0.45 and
# logit_gap <= 0.041; the controls read grad_diff 0.42-0.46 and
# grad_diff_med 0.37 (computations at 8 bits) and logit_gap 0.12-0.31
# (the reference's KV at 4 bits); the planted faults read grad_gap >=
# 0.33 and grad_diff_med >= 0.97 (half batch), 1 (frozen step) and
# logit_gap >= 0.85 (altered tokens).
SMOKE_LIMITS = {
    "train": {"loss_gap": {"limit": 2e-3}, "grad_gap": {"limit": 0.1},
              "update_gap": {"limit": 0.3}, "grad_diff": {"limit": 0.3},
              "grad_diff_med": {"limit": 0.2},
              "update_diff": {"limit": 0.7}},
    "serve": {"logit_gap": {"limit": 0.1}},
}
TINY_GRANITE = dict(hidden_size=128, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, intermediate_size=64, num_local_experts=8,
                    num_experts_per_tok=4, vocab_size=512, rope_theta=1e6)
TINY_MAMBA = dict(d_model=128, n_layer=4, vocab_size=512, d_state=16,
                  headdim=32, chunk_size=16)
DUMMY_METRIC = '''
def read(ctx, trace):
    return float(ctx["dummy_count"])
'''


def write(path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding only a benchmark of dummy smoke cells: every
    configuration, mix, limit and metric is a file found by name."""
    r = tmp_path_factory.mktemp("bench_root")
    for name, base, tiny in (("mamba2_tiny", "mamba2_370m", TINY_MAMBA),
                             ("granite_tiny", "granite_moe_1b",
                              TINY_GRANITE)):
        conf = json.loads((ROOT / "bench" / "configs" / f"{base}.json")
                          .read_text())
        conf.update(name=name, preset="smoke")
        conf["model"].update(tiny)
        write(r / "bench" / "configs" / f"{name}.json", conf)
    write(r / "bench" / "traffic" / "train.tiny.json",
          {"mode": "train", "batch": 2, "seq_len": 32, "vocab_ids": 500})
    write(r / "bench" / "traffic" / "train.tiny_row.json",
          {"mode": "train", "batch": 1, "seq_len": 32, "vocab_ids": 500})
    serve = {"mode": "serve", "prompt_lens": [8, 16],
             "prompt_weights": [0.5, 0.5],
             "output": {"median": 6, "sigma": 0.4, "min": 4, "max": 10},
             "slots": 4, "max_len": 32, "vocab_ids": 500,
             "check_requests": 3}
    write(r / "bench" / "traffic" / "serve.tiny_open.json",
          dict(serve, loop="open", rate_per_s=20.0))
    write(r / "bench" / "traffic" / "serve.tiny_closed.json",
          dict(serve, loop="closed", clients=4, per_client=4))
    cells = [("mamba2_tiny", "train.tiny", "train"),
             ("granite_tiny", "serve.tiny_open", "serve"),
             ("granite_tiny", "serve.tiny_closed", "serve"),
             ("mamba2_tiny", "train.tiny_row", "train")]
    for conf, traffic, mode in cells:
        write(r / "bench" / "limits" / f"{conf}.{traffic}.json",
              SMOKE_LIMITS[mode])
    write(r / "bench" / "metrics" / "dummy.count.py", DUMMY_METRIC)
    names = [f"{c}.{t}" for c, t, _ in cells]
    write(r / "BENCHMARK.json", {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "test", "file":
                     f"bench/configs/{n}.json", "reduced": [], "why": "t"}
                    for n in ("mamba2_tiny", "granite_tiny")],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "t"} for n, (c, t, _) in zip(names, cells)],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.03, "source": "host_clock",
             "workloads": [names[0], names[3]]},
            {"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": [names[1]]},
            {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": names[1:3]},
            {"name": "serve_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": names[1:3]}],
        "per_layer": [{"name": "dummy.count", "unit": "count",
                       "better": "lower", "source": "program_counter",
                       "layer": "test", "moves": "train_tokens_per_s",
                       "workloads": [names[0]]}]})
    return r


def go(root, workload, **kw):
    return R.run(workload, SEED, 1.0, False, root=root, require_chip=False,
                 cell_kwargs=kw, t_start=time.perf_counter())


def test_dummy_cell_and_metric_are_found_by_name(root):
    spec = R.load_spec(root, "mamba2_tiny.train.tiny")
    assert spec["config"]["name"] == "mamba2_tiny"
    assert spec["mix"]["seq_len"] == 32
    assert set(spec["limits"]) == {"loss_gap", "grad_gap", "update_gap",
                                   "grad_diff", "grad_diff_med",
                                   "update_diff"}
    assert [m["name"] for m in spec["end_to_end"]] == \
        ["setup_s", "train_tokens_per_s"]
    assert [m["name"] for m in spec["per_layer"]] == ["dummy.count"]
    assert R.metric_reader(root, "dummy.count")({"dummy_count": 3},
                                                None) == 3.0
    serve = R.load_spec(root, "granite_tiny.serve.tiny_closed")
    assert [m["name"] for m in serve["end_to_end"]] == \
        ["setup_s", "itl_p95_ms", "serve_tokens_per_s"]
    assert serve["per_layer"] == []


TRAIN_CELLS = ["mamba2_tiny.train.tiny", "mamba2_tiny.train.tiny_row"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_cell_sound_run_is_correct(root, cell):
    res = go(root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", TRAIN_CELLS)
@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_train_cell_faults_are_not_correct(root, cell, fault):
    """A frozen step, and half the rows (or, in a one-row batch, half
    the row's tokens) left out of the step."""
    res = go(root, cell, fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ["serve.tiny_open", "serve.tiny_closed"])
def test_serve_cell_sound_run_is_correct(root, cell):
    res = go(root, f"granite_tiny.{cell}")
    assert res["correct"], res["checks"]
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_train_control_is_not_correct(root):
    """The program at the configuration's control policy (computations
    two bits narrower) fails the check."""
    conf = R.load_spec(root, "mamba2_tiny.train.tiny")["config"]
    res = go(root, "mamba2_tiny.train.tiny",
             policy_overrides=conf["control_policy"])
    assert not res["correct"], res["checks"]


def test_serve_control_is_not_correct(root):
    """The reference with its KV at the control's width, read on the
    same served requests, lies beyond the limit."""
    spec = R.load_spec(root, "granite_tiny.serve.tiny_open")
    spec["mix"]["check_requests"] = 12   # a sample as wide as the chip's
    cell = R.make_cell(spec, SEED)
    cell.setup()
    cell.window(2.0, R.no_trace)
    cell.drain()
    cell.release()
    gap = cell.gaps(spec["config"]["control_kv_bits"])["logit_gap"]
    assert gap > spec["limits"]["logit_gap"]["limit"]


def test_serve_cell_altered_token_is_not_correct(root):
    res = go(root, "granite_tiny.serve.tiny_open", fault="alter_token")
    assert not res["correct"], res["checks"]


def run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mamba2_370m.train.b1s512", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_chip_exits_nonzero_without_a_result():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
