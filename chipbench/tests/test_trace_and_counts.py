"""The trace reduction and the operation and byte counts, against hand
counts."""
from __future__ import annotations

import json
import math

import pytest

import tiny
import flops
import spec
import trace_reduce as tr

MS = 1e6     # nanoseconds


def _trace():
    """Window 0-100 ms. Device programs: decode 10-14 and 20-24, train
    40-90, a copy 13-15 overlapping a decode, a prefill after the window.
    Host spans:
    engine steps 9-18 and 19-25, a wait 25-38, a BE step 38-92."""
    mods = [("jit__decode_impl(3)", 10 * MS, 4 * MS),
            ("jit_copy(5)", 13 * MS, 2 * MS),
            ("jit__decode_impl(3)", 20 * MS, 4 * MS),
            ("jit_train_step(7)", 40 * MS, 50 * MS),
            ("jit_prefill(9)", 120 * MS, 5 * MS)]          # after the window
    spans = [("chipbench.window", 0, 100 * MS),
             ("chipbench.engine_step", 9 * MS, 9 * MS),
             ("chipbench.engine_step", 19 * MS, 6 * MS),
             ("chipbench.wait_arrival", 25 * MS, 13 * MS),
             ("chipbench.be_step", 38 * MS, 54 * MS),
             ("chipbench.engine_step", 38 * MS, 54 * MS)]
    return {"devices": {"/device:TPU:0": {"XLA Modules": mods}},
            "spans": spans}


def test_reduce_by_hand():
    red = tr.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx((5 + 4 + 50) / 1e3)
    assert red["programs"] == {
        "jit_copy": {"count": 1, "seconds": pytest.approx(0.002)},
        "jit__decode_impl": {"count": 2, "seconds": pytest.approx(0.008)},
        "jit_train_step": {"count": 1, "seconds": pytest.approx(0.05)}}
    assert tr.program(red, "jit__decode_impl") == (2, pytest.approx(0.008))
    assert tr.program(red, "jit_prefill") == (0, 0)
    # gaps: 0-10, 15-20, 24-40, 90-100; each labelled by its innermost span
    assert [(n, pytest.approx(s)) for n, s in red["gaps"]] == [
        ("chipbench.wait_arrival", 0.016), ("outside_spans", 0.010),
        ("outside_spans", 0.010), ("chipbench.engine_step", 0.005)]
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["jit_train_step", pytest.approx(0.05)]
    assert len(b["idle_gaps"]) == 4


def test_union_and_program_names():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.program_name("jit_train_step(1234)") == "jit_train_step"


def test_recorded_trace_fixture():
    """A cut of a real TPU v5e trace of mamba2-130m.chat_burst."""
    path = tiny.HERE / "data" / "trace_cut.json"
    fx = json.loads(path.read_text())
    red = tr.reduce(fx["trace"])
    want = fx["hand"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for name, (n, sec) in want["programs"].items():
        assert tr.program(red, name) == (n, pytest.approx(sec, rel=1e-9))


def _cfg(name):
    return spec.Bench().config(name)


def test_matmul_params_by_hand():
    # mamba2-130m: per layer in_proj 768 x (2*1536 + 2*128 + 24), out_proj
    # 1536 x 768; tied head 768 x 50288
    layer = 768 * (3072 + 256 + 24) + 1536 * 768
    assert flops.matmul_params(_cfg("mamba2-130m")) == 24 * layer + 768 * 50288
    # mistral-nemo share: q,o 5120x4096 each, k,v 5120x1024 each, MLP 3 x
    # 5120 x 14336; head 5120 x 32768
    layer = 2 * 5120 * 4096 + 2 * 5120 * 1024 + 3 * 5120 * 14336
    assert flops.matmul_params(_cfg("mistral-nemo-12b.pp10")) == \
        4 * layer + 5120 * 32768


def test_decode_least_and_shares_stay_under_100():
    cfg = _cfg("mamba2-130m")
    P = flops.matmul_params(cfg)
    f, b = flops.decode_least(cfg, {"active": 16, "kv_tokens": 0})
    state = 24 * 64 * 128 + 3 * (1536 + 256)
    assert f == 2 * P * 16 + 4 * 24 * 64 * 128 * 24 * 16
    assert b == 2 * P + 2 * 2 * state * 24 * 16
    dense = _cfg("mistral-nemo-12b.pp10")
    # the span's kv_tokens leave out the token each slot decodes: 2 slots
    # holding 998 attend over 1000 positions
    step = {"active": 2, "kv_tokens": 998}
    f, b = flops.decode_least(dense, step)
    Pd = flops.matmul_params(dense)
    assert f == 2 * Pd * 2 + 4 * 32 * 128 * 1000 * 4
    assert b == 2 * Pd + 2 * 2 * 8 * 128 * 1000 * 4
    # a decode program that took exactly its least time reads 100%
    bench = spec.Bench()
    pk = bench.peaks("TPU v5 lite")
    least = max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
    ctx = {"trace": {"programs": {"jit__decode_impl": {
        "count": 3, "seconds": 3 * least}}},
        "program_spans": [("tally.serve.step", 0, 9, {})]
        + [("tally.serve.decode", 0, 1, step)] * 5,
        "cfg": dense, "peaks": pk}
    assert bench.metric_reader("hp_decode_roofline")(ctx) == \
        pytest.approx(100.0)
    mfu = bench.metric_reader("hp_decode_mfu")(ctx)
    assert mfu == pytest.approx(100.0 * 2 * Pd * 2 / pk["flops_bf16"] / least)
    assert mfu <= 100.0


def test_train_mfu_by_hand():
    bench = spec.Bench()
    cfg = _cfg("mamba2-130m")
    job = cfg["be"]
    pk = bench.peaks("TPU v5 lite")
    ctx = {"trace": {"programs": {"jit_train_step": {"count": 2,
                                                     "seconds": 0.4}}},
           "be_cfg": cfg, "job": job, "peaks": pk}
    want = 100 * 6 * flops.matmul_params(cfg) * 8192 / (0.2 * 197e12)
    assert bench.metric_reader("be_train_mfu")(ctx) == pytest.approx(want)
    assert bench.metric_reader("be_step_ms")(ctx) == pytest.approx(200.0)
    assert math.isfinite(want)
