"""The engine's phase spans in a trace (``phases.py``) and the readers of the
engine's request stamps, against hand counts."""
from __future__ import annotations

import copy
import json
from types import SimpleNamespace

import pytest

import tiny
import phases
import spec
import trace_reduce as tr
from test_trace_and_counts import MS, _trace

P = "tally.serve."


def _spans():
    """Engine spans inside the hand trace's benchmark spans (ms): a step
    9.5-17.6 that decodes; a step 19.1-24.9 that admits, then decodes; a
    step 38.5-91.5 that runs a BE quantum; a decoding step after the
    window. The device is busy 10-15, 20-24 and 40-90."""
    def s(name, a, b, **stats):
        return (P + name, a * MS, (b - a) * MS, stats)
    return [s("step", 9.5, 17.6), s("decode", 9.5, 10, active=2,
                                    kv_tokens=40),
            s("decode_wait", 10, 15), s("emit", 15, 17.6),
            s("step", 19.1, 24.9), s("admit", 19.1, 19.4, rid=7),
            s("prefill", 19.1, 19.2, rid=7), s("insert", 19.2, 19.3, rid=7),
            s("first_token", 19.3, 19.4, rid=7),
            s("decode", 19.4, 20, active=3, kv_tokens=90),
            s("decode_wait", 20, 24), s("emit", 24, 24.9),
            s("step", 38.5, 91.5), s("be_quantum", 38.6, 91.4),
            s("step", 110, 120), s("decode", 110, 111, active=1,
                                   kv_tokens=5)]


W = (0, 100 * MS)


def test_idle_inside_each_span_by_hand():
    t, sp = _trace(), _spans()
    assert phases.idle_inside(t, sp, P + "step", W) == [
        pytest.approx(0.0031), pytest.approx(0.0018), pytest.approx(0.003)]
    assert [s[1] for s in phases.decoding_steps(sp)] == [
        9.5 * MS, 19.1 * MS, 110 * MS]
    assert phases.step_idle_ms(t, sp, W) == pytest.approx((3.1 + 1.8) / 2)
    idle = phases.idle_by_phase(t, sp, W)
    want = {"step": 7.9, "admit": 0.3, "prefill": 0.1, "insert": 0.1,
            "first_token": 0.1, "decode": 1.1, "decode_wait": 0.0,
            "emit": 3.5, "be_quantum": 2.8}
    assert idle == {P + k: pytest.approx(v / 1e3) for k, v in want.items()}
    assert phases.span_ms(sp, P + "admit", W) == pytest.approx(0.3)
    assert phases.span_ms(sp, P + "admit", (0, 1)) is None


def test_idle_is_averaged_over_the_devices_that_ran():
    t = _trace()
    t["devices"]["/device:TPU:1"] = {"XLA Modules": [
        ("jit__decode_impl(3)", 9.5 * MS, 8.1 * MS)]}
    assert phases.idle_inside(t, _spans(), P + "step", W)[0] == \
        pytest.approx(0.0031 / 2)
    t["devices"] = {}
    assert phases.idle_inside(t, _spans(), P + "step", W) == []
    assert phases.step_idle_ms(t, _spans(), W) is None


def _with_engine_spans(trace, spans):
    """The trace as ``trace_reduce.load_xplane`` gives it when the host
    plane also holds the engine's spans."""
    return dict(trace, spans=trace["spans"] + [s[:3] for s in spans],
                program_spans=spans)


def test_gaps_are_named_by_the_innermost_engine_span():
    gaps = tr.reduce(_with_engine_spans(_trace(), _spans()))["gaps"]
    # gaps 24-40, 0-10, 90-100, 15-20 (middle 17.5: inside the emit loop of
    # the first step, which the benchmark's engine_step 9-18 holds)
    assert [(n, pytest.approx(s)) for n, s in gaps] == [
        ("chipbench.wait_arrival", 0.016), ("outside_spans", 0.010),
        ("outside_spans", 0.010), (P + "emit", 0.005)]
    # of two spans equally long, the engine's names the gap, wherever it
    # lies in the list
    same = [(P + "step", 9 * MS, 9 * MS, {})]
    assert tr.reduce(_with_engine_spans(_trace(), same))["gaps"][3][0] == \
        P + "step"
    assert tr.label([(P + "step", 9 * MS, 9 * MS)]
                    + _trace()["spans"], 17.5 * MS) == P + "step"


def test_step_idle_reader_by_hand():
    """``hp_step_idle_ms`` reads the mean idle inside the two steps of the
    window that decoded; the BE step and the step after the window do not
    count, and a trace with no device gives nothing."""
    read = spec.Bench().metric_reader("hp_step_idle_ms")
    ctx = {"events": _with_engine_spans(_trace(), _spans()),
           "program_spans": _spans()}
    assert read(ctx) == pytest.approx((3.1 + 1.8) / 2)
    ctx["events"] = dict(ctx["events"], devices={})
    assert read(ctx) is None


def _fixture():
    return json.loads((tiny.HERE / "data" / "trace_cut.json").read_text())


def test_the_existing_reduction_and_readers_are_unchanged():
    """Busy time, programs, window, gaps and every reader of the trace alone
    read the same on the recorded v5e cut with engine spans beside it."""
    fx = _fixture()
    trace = fx["trace"]
    before = copy.deepcopy(trace)
    red = tr.reduce(trace)
    lo, hi = tr.window_of(trace)
    sp = [(P + "step", lo + 10 * MS, 30 * MS, {}),
          (P + "decode", lo + 11 * MS, 1 * MS, {"active": 4,
                                                "kv_tokens": 900})]
    phases.idle_by_phase(trace, sp, (lo, hi))
    phases.step_idle_ms(trace, sp, (lo, hi))
    assert trace == before and tr.reduce(trace) == red
    assert red["busy_s"] == pytest.approx(fx["hand"]["busy_s"], rel=1e-9)
    merged = tr.reduce(_with_engine_spans(trace, sp))
    assert {k: v for k, v in merged.items() if k != "gaps"} == \
        {k: v for k, v in red.items() if k != "gaps"}
    bench = spec.Bench()
    ctx = {"trace": red, "program_spans": sp}
    for name in ("hp_decode_ms", "hp_prefill_ms", "be_step_ms",
                 "device_idle_share.burst", "device_idle_share.steady"):
        got = bench.metric_reader(name)(ctx)
        assert got == bench.metric_reader(name)({"trace": red})
    assert bench.metric_reader("hp_decode_ms")(ctx) is not None


def _window(*reqs):
    recs = [SimpleNamespace(req=r) for r in reqs]
    return SimpleNamespace(due_in_window=lambda: recs)


def _req(submit, admit, first):
    wait = None if admit is None else admit - submit
    return SimpleNamespace(submit_t=submit, admit_t=admit,
                           first_token_t=first, queue_wait=wait)


def test_the_stamp_readers_by_hand():
    bench = spec.Bench()
    wait = bench.metric_reader("hp_queue_wait_ms")
    admit = bench.metric_reader("hp_admit_ms")
    ctx = {"window": _window(_req(1.0, 1.004, 1.010),
                             _req(2.0, 2.002, 2.008),
                             _req(3.0, None, None))}   # still queued
    assert wait(ctx) == pytest.approx(3.0)
    assert admit(ctx) == pytest.approx(6.0)
    # a program whose requests carry no admission stamp gives nothing
    old = SimpleNamespace(submit_t=1.0, first_token_t=1.01)
    ctx = {"window": _window(old, old)}
    assert wait(ctx) is None and admit(ctx) is None


def test_a_traced_cpu_run_reads_the_engine_spans(tmp_path, capsys):
    """On the CPU: the engine's spans reach the trace and the readers of its
    stamps read in both windows; no device plane, so no device idle."""
    root = tiny.make_root(tmp_path)
    assert phases.main(["--workload", "tiny-ssm.tiny", "--seed",
                        str(2**33 + 5), "--seconds", "1", "--root",
                        str(root)], require_tpu=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    t = out["traced"]
    assert t["program_spans"] > 0 and t["step_idle_ms"] is None
    assert t["span_ms_by_phase"][P + "admit"] > 0 and t["decoding_steps"] > 0
    for side in (out["untraced"], t):
        assert side["hp_steps"] > 0 and side["compiles"] == 0
        assert side["hp_admit_ms"] > 0 and side["hp_queue_wait_ms"] >= 0
