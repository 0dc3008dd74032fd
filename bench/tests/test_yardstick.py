"""The benchmark's own arithmetic: peaks, operation and byte counts,
the trace reduction and the traffic generator.  Run with
``python -m pytest bench/tests`` (outside the repository's tier-1
``testpaths``)."""
from __future__ import annotations

import json
import pathlib
import types

import numpy as np
import pytest

from bench import flops, loadgen, peaks, trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[2]


def model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["model"]


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_granite_kv_bytes_per_token():
    # 24 layers x (K and V) x 8 kv heads x 64 dims x 1 byte
    assert flops.kv_bytes_per_token(model("granite_moe_1b"), 8) == 24576


def test_granite_active_parameters():
    m = model("granite_moe_1b")
    attn = 1024 * 1024 + 2 * 1024 * 512 + 1024 * 1024      # q, k, v, o
    experts = 8 * 3 * 1024 * 512                             # top 8 SwiGLU
    router = 1024 * 32
    head = 49408 * 1024
    assert flops.moe_active_params(m) == 24 * (attn + experts + router) \
        + head
    # the "a400m" of the model's name, with the tied head counted
    assert 4.2e8 < flops.moe_active_params(m) < 4.4e8


def test_granite_decode_work_by_hand():
    m = model("granite_moe_1b")
    rows, ctx = 3, 100 + 200 + 300
    att = 4 * 16 * 64 * ctx * 24
    assert flops.moe_decode_flops(m, rows, ctx) == \
        2 * flops.moe_active_params(m) * rows + att
    assert flops.flash_decode_bytes(m, 8, rows, ctx) == \
        24576 * ctx + rows * 24 * 2 * 16 * 64 * 4


def test_mamba2_train_flops_by_hand():
    m = model("mamba2_370m")
    in_dim = 2 * 2048 + 2 * 128 + 32                         # z, x, B, C, dt
    dots = 2 * (48 * 1024 * in_dim + 48 * 2048 * 1024 + 1024 * 50432)
    ssd = 2 * (256 / 2 * 128 + 256 / 2 * 32 * 64 + 2 * 32 * 64 * 128)
    assert flops.mamba2_train_flops_per_token(m) == 3 * (dots + 48 * ssd)
    # about 1.25 TFLOP for a 2 x 256 step
    assert 1.2e12 < 512 * flops.mamba2_train_flops_per_token(m) < 1.3e12


def test_qmatmul_work_by_hand():
    m = {"d_model": 4, "n_layer": 1, "vocab_size": 8, "d_state": 1,
         "headdim": 2, "expand": 2, "ngroups": 1}
    # in_proj 4 x (16 + 2 + 4) = 4 x 22, out_proj 8 x 4, head 4 x 8
    M = 3
    ops, byts = flops.qmatmul_step_work(m, M)
    shapes = [(4, 22), (8, 4), (4, 8)]
    assert ops == sum(3 * 2 * M * n * k for k, n in shapes)
    want = sum((M * k + k * n) * 2 + M * n * 4 + (M * n + k * n) * 2
               + M * k * 4 + (M * k + M * n) * 2 + k * n * 4
               for k, n in shapes)
    assert byts == want


# -- trace reduction --------------------------------------------------------

def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def recorded():
    """A small trace: two devices' op lines and the host's spans."""
    host = ("/host:CPU", [("python", [
        ev("bench:window", 0, 1000),
        ev("bench:engine_step", 0, 600),
        ev("bench:submit", 650, 100),
        ev("unrelated", 0, 1000)])])
    d0 = ("/device:TPU:0", [
        ("XLA Ops", [
            ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), calls=%c", 100,
               200),
            ev("%qmm_2d.7 = f32[8,8]{1,0:T(8,128)} custom-call(f32[8]{0} "
               "%a), custom_call_target=\"tpu_custom_call\"", 250, 150),
            ev("%while.3 = (s32[]{:T(128)}, f32[2]{0}) while((s32[], f32[2])"
               " %t), body=%b", 850, 300),
            ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 900, 200)]),
        ("XLA Modules", [ev("jit_decode(123)", 50, 300),
                         ev("jit_step(456)", 360, 100),
                         ev("jit_decode(123)", 850, 300)])])
    d1 = ("/device:TPU:1", [
        ("XLA Ops", [ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0,
                        500)]),
        ("XLA Modules", [ev("jit_decode(9)", 0, 500)])])
    # a plane the TPU runtime writes beside the chips', with no ops
    other = ("/device:CUSTOM:Megascale Trace", [("Steps", [])])
    return [host, d0, other, d1]


def test_trace_reduce_on_recorded_trace():
    s = trace_reduce.reduce_planes(recorded())
    assert s.window_s == pytest.approx(1e-6)
    # device 0 busy 100..400 and 850..1000 (clipped): 450 ns;
    # device 1 busy 0..500: 500 ns; mean 475 ns
    assert s.busy_s == pytest.approx(475e-9)
    assert s.idle_share == pytest.approx(0.525)
    assert s.op_seconds(r"qmm") == pytest.approx(150e-9 / 2)
    # the kernel started at 250, inside the first decode launch (50..350)
    assert s.program_seconds(r"decode") == pytest.approx(
        (200 + 150 + 150 + 100 + 500) * 1e-9 / 2)
    assert s.program_seconds(r"step") is None
    assert s.op_seconds(r"nothing") is None
    # device 0 gaps: 0..100 (engine_step), 400..850 (midpoint 625 lies
    # in no phase); device 1: 500..1000 (750: submit)
    assert s.idle_by_phase["engine_step"] == pytest.approx(100e-9 / 2)
    assert s.idle_by_phase["idle"] == pytest.approx(450e-9 / 2)
    assert s.idle_by_phase["submit"] == pytest.approx(500e-9 / 2)
    assert s.gaps[0] == ("submit", pytest.approx(500e-9))
    bd = s.breakdown()
    # control flow is left out of the top ops: its ops are listed apart
    assert bd["device_ops"][0][0] == "jit_decode/%fusion.1 fusion"
    assert not any("while" in k for k, _ in bd["device_ops"])
    assert len(bd["idle_gaps"]) <= 10


def test_trace_without_device_ops_raises():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([recorded()[0]])


# -- traffic ----------------------------------------------------------------

MIX = json.loads((ROOT / "bench" / "traffic" / "serve.decode_open.json")
                 .read_text())


def fingerprint(reqs):
    return [(r.due_s, r.max_new, r.prompt.tolist()) for r in reqs]


def test_same_seed_same_requests_other_seed_other_requests():
    big = 2 ** 31 + 12345
    a = loadgen.open_loop(MIX, big, 20)
    assert fingerprint(a) == fingerprint(loadgen.open_loop(MIX, big, 20))
    b = loadgen.open_loop(MIX, big + 1, 20)
    assert fingerprint(a) != fingerprint(b)
    # the same work in another order: sizes and gaps are one multiset
    assert sorted((len(r.prompt), r.max_new) for r in a) == \
        sorted((len(r.prompt), r.max_new) for r in b)
    # on one arrival schedule
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert len(a) == len(b) == round(MIX["rate_per_s"] * 20)


def test_menu_shares_and_output_bounds():
    sz = loadgen.sizes(MIX, 7, 50)
    lens = [L for L, _ in sz]
    assert [lens.count(L) for L in MIX["prompt_lens"]] == [20, 15, 10, 5]
    outs = [o for _, o in sz]
    assert min(outs) >= MIX["output"]["min"]
    assert max(outs) <= MIX["output"]["max"]


def test_closed_loop_and_training_rows_are_seeded():
    mix = dict(MIX, clients=3, loop="closed")
    a = loadgen.closed_loop(mix, 5, 4)
    assert [fingerprint(q) for q in a] == \
        [fingerprint(q) for q in loadgen.closed_loop(mix, 5, 4)]
    assert fingerprint(a[0]) != fingerprint(a[1])
    rows = loadgen.BigramRows(1000, 4, 16, 2 ** 33 + 1)
    b0 = rows.batch(0)
    assert np.array_equal(b0["tokens"],
                          loadgen.BigramRows(1000, 4, 16, 2 ** 33 + 1)
                          .batch(0)["tokens"])
    assert len({tuple(r) for r in b0["tokens"].tolist()}) == 4
    assert not np.array_equal(b0["tokens"], rows.batch(1)["tokens"])
    assert np.array_equal(b0["labels"][:, :-1], b0["tokens"][:, 1:])
