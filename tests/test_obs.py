"""Telemetry layer (repro.obs): registry semantics, exposition round
trips, audit-log behaviour, and the three-part contract — opt-in,
observation-only (bit-exact results with telemetry on, across both
engines), and zero structural cost when off."""
import json
import math

import numpy as np
import pytest

from repro.core.device_model import A100
from repro.core.metrics import P2Quantile, WindowQuantile
from repro.core.simulator import simulate
from repro.core.traffic import maf2_like_trace, scale_to_load
from repro.core.workloads import isolated_time, paper_workload
from repro.obs import (AuditLog, BinnedSeries, Histogram, MetricsRegistry,
                       ObsHub, SelfProfiler, ServingProbe, binned_rate,
                       parse_prometheus_text, prometheus_text,
                       registry_from_jsonl, render_dashboard, resample,
                       to_jsonl)

from tests._hypothesis_compat import given, settings, st


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_families_and_labels():
    r = MetricsRegistry()
    c = r.counter("reqs_total", "requests", ("device",))
    c.labels(device=0).inc()
    c.labels(device=0).inc(2.0)
    c.labels(device=1).inc()
    assert c.labels(device=0).value == 3.0
    assert c.child("1").value == 1.0          # positional == keyword child
    g = r.gauge("clock", "clock")
    g.child().set(4.5)
    assert g.child().value == 4.5


def test_registration_idempotent_and_conflicts_raise():
    r = MetricsRegistry()
    a = r.counter("x_total", "x", ("device",))
    assert r.counter("x_total", "x", ("device",)) is a
    with pytest.raises(ValueError):
        r.gauge("x_total", "x", ("device",))          # kind conflict
    with pytest.raises(ValueError):
        r.counter("x_total", "x", ("job",))           # label conflict


def test_histogram_buckets_and_quantile_vs_numpy():
    h = Histogram(buckets=[i / 10 for i in range(1, 11)])
    rng = np.random.default_rng(0)
    xs = rng.uniform(0.0, 1.0, size=5000)
    for x in xs:
        h.observe(float(x))
    assert h.count == 5000
    assert math.isclose(h.sum, float(xs.sum()), rel_tol=1e-9)
    # interpolated quantiles land within one bucket width of the truth
    for q in (0.5, 0.9, 0.99):
        assert abs(h.quantile(q) - float(np.quantile(xs, q))) < 0.1
    # cumulative pairs are monotone and end at (+inf, n)
    pairs = h.bucket_pairs()
    assert pairs[-1] == (math.inf, 5000)
    assert all(a[1] <= b[1] for a, b in zip(pairs, pairs[1:]))


def test_histogram_overflow_clamps_to_top_bucket():
    h = Histogram(buckets=[1.0, 2.0])
    for v in (5.0, 7.0, 9.0):
        h.observe(v)
    assert h.counts[-1] == 3
    assert h.quantile(0.99) == 2.0            # clamped, not extrapolated


def test_binned_series_accumulates_and_clamps():
    b = BinnedSeries(span=10.0, n_bins=10)
    b.add(0.5, 2.0)
    b.add(9.99, 1.0)
    b.add(50.0, 4.0)          # past the span -> last bin
    assert b.bins[0] == 2.0 and b.bins[-1] == 5.0
    centers, rates = binned_rate(b)
    assert len(centers) == 10 and rates[0] == 2.0  # width 1.0 -> rate == sum


# ---------------------------------------------------------------------------
# Quantile cross-checks: histogram vs the streaming estimators the SLO
# checker uses (same data, independent summaries)
# ---------------------------------------------------------------------------


def _cross_check(xs, q=0.99, bucket_w=0.05):
    h = Histogram(buckets=[bucket_w * i for i in range(1, 21)])
    p2 = P2Quantile(q)
    wq = WindowQuantile(q, capacity=len(xs))
    for x in xs:
        h.observe(x)
        p2.add(x)
        wq.add(x)
    exact = float(np.quantile(np.asarray(xs), q))
    assert abs(h.quantile(q) - exact) <= bucket_w
    assert abs(wq.value() - exact) < 1e-12      # exact within capacity
    return exact, p2.value()


def test_quantile_cross_check_uniform():
    rng = np.random.default_rng(3)
    xs = rng.uniform(0.0, 1.0, size=4000).tolist()
    exact, p2v = _cross_check(xs)
    assert abs(p2v - exact) < 0.05


def test_quantile_cross_check_bimodal():
    rng = np.random.default_rng(4)
    xs = np.concatenate([rng.uniform(0.0, 0.2, 3000),
                         rng.uniform(0.8, 1.0, 1000)]).tolist()
    exact, p2v = _cross_check(xs)
    assert abs(p2v - exact) < 0.1


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=1.0,
                          allow_nan=False), min_size=32, max_size=400))
def test_quantile_cross_check_property(xs):
    h = Histogram(buckets=[i / 20 for i in range(1, 21)])
    wq = WindowQuantile(0.9, capacity=len(xs))
    for x in xs:
        h.observe(x)
        wq.add(x)
    exact = float(np.quantile(np.asarray(xs), 0.9))
    assert abs(wq.value() - exact) < 1e-9
    # the histogram interpolates inside the bucket holding rank q * n, so
    # its reference is the order statistic of that rank: np.quantile
    # interpolates between order statistics, which on sparse data (28
    # zeros and 4 ones) can lie in another bucket
    rank = math.ceil(0.9 * len(xs))
    order_stat = float(np.sort(np.asarray(xs))[rank - 1])
    assert abs(h.quantile(0.9) - order_stat) <= 0.05 + 1e-9


# ---------------------------------------------------------------------------
# Exposition round trips
# ---------------------------------------------------------------------------


def _populated_registry() -> MetricsRegistry:
    r = MetricsRegistry()
    c = r.counter("obs_reqs_total", "requests", ("device", "job"))
    c.child("0", "a").inc(3)
    c.child("1", "b").inc(0.5)
    r.gauge("obs_clock_seconds", "clock").child().set(1.25)
    h = r.histogram("obs_lat_seconds", "latency", ("device",),
                    buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.05, 5.0):
        h.child("0").observe(v)
    t = r.timeline("obs_series", "points", ("device",))
    t.child("0").append(0.5, 1.0)
    t.child("0").append(1.5, -1.0)
    b = r.binned("obs_binned", "binned", ("job",), span=10.0, n_bins=4)
    b.child("a").add(0.1, 2.0)
    return r


def test_prometheus_text_round_trip():
    r = _populated_registry()
    text = prometheus_text(r)
    types, samples = parse_prometheus_text(text)
    assert types["obs_reqs_total"] == "counter"
    assert samples[("obs_reqs_total",
                    (("device", "0"), ("job", "a")))] == 3.0
    assert samples[("obs_clock_seconds", ())] == 1.25
    # histogram exposition: cumulative buckets + sum + count
    assert samples[("obs_lat_seconds_count", (("device", "0"),))] == 3.0
    assert samples[("obs_lat_seconds_bucket",
                    (("device", "0"), ("le", "+Inf")))] == 3.0
    # timelines/binned are JSONL-only
    assert "obs_series" not in text and "obs_binned" not in text


def test_jsonl_round_trip_is_byte_exact():
    r = _populated_registry()
    text = to_jsonl(r)
    r2 = registry_from_jsonl(text)
    assert to_jsonl(r2) == text
    assert prometheus_text(r2) == prometheus_text(r)
    tl = r2.get("obs_series").child("0")
    assert tl.ts == [0.5, 1.5] and tl.vs == [1.0, -1.0]


def test_resample_modes():
    ts, vs = [0.0, 1.0, 2.0], [1.0, 3.0, 2.0]
    grid = [0.5, 1.5, 2.5]
    prev = resample(ts, vs, grid, kind="previous")
    assert list(prev) == [1.0, 3.0, 2.0]
    lin = resample(ts, vs, grid, kind="linear")
    assert list(np.round(lin, 6)) == [2.0, 2.5, 2.0]
    s = resample(ts, vs, grid, kind="sum")
    assert float(np.sum(s)) == pytest.approx(6.0)


# ---------------------------------------------------------------------------
# Audit log
# ---------------------------------------------------------------------------


def test_audit_ring_buffer_and_filters():
    log = AuditLog(capacity=3)
    for i in range(5):
        log.record(float(i), "placement", f"job{i}", i % 2)
    assert len(log) == 3 and log.total == 5 and log.dropped == 2
    assert [r.job for r in log] == ["job2", "job3", "job4"]
    assert [r.t for r in log.filter(device=0)] == [2.0, 4.0]
    assert log.why("job3")[0].kind == "placement"
    assert log.why("job3", t=3.0)[0].job == "job3"
    assert log.why("job3", t=9.0) == []


def test_audit_jsonl_round_trip():
    log = AuditLog()
    log.record(1.0, "migration", "be-1", 0, dst=2, window_p99=0.5,
               bound=0.25)
    log.record(2.0, "failure", "", 3, requeued=["a", "b"])
    text = log.to_jsonl()
    back = AuditLog.from_jsonl(text)
    assert back.fingerprint() == log.fingerprint()
    assert json.loads(text.splitlines()[0])["details"]["dst"] == 2


def test_selfprofiler_sections_sum_to_total():
    prof = SelfProfiler()
    prof.start()
    prof.push("a")
    prof.push("b")
    prof.pop()
    prof.pop()
    prof.stop()
    rep = prof.report()
    assert set(k for k in rep if k.endswith("_s")) >= {
        "a_s", "b_s", "total_s", "other_s"}
    assert rep["total_s"] >= rep["a_s"] + rep["b_s"]


# ---------------------------------------------------------------------------
# The contract on the engines: opt-in, zero-cost off, bit-exact on
# ---------------------------------------------------------------------------


def _sim_inputs(duration=10.0):
    hp = paper_workload("resnet50-infer", 0)
    bes = [paper_workload("gpt2-train", 1)]
    iso = isolated_time(hp, A100)
    base = maf2_like_trace(duration=duration, mean_rate=0.5 / iso, seed=7)
    return hp, bes, scale_to_load(base, iso, 0.5)


def test_bare_run_has_no_obs_state():
    """obs=None must leave every hook site structurally disabled."""
    from repro.core.simulator import DeviceEngine

    eng = DeviceEngine(A100, 1.0, 0.0316e-3)
    assert eng.obs is None and eng.book.obs is None
    assert eng.ex.obs is None and eng.sched.obs is None


def test_obs_only_supported_on_priority_engines():
    hp, bes, trace = _sim_inputs(duration=2.0)
    with pytest.raises(ValueError, match="telemetry"):
        simulate("time_slicing", hp, bes, trace, A100, duration=2.0,
                 obs=ObsHub())


def test_telemetry_identical_fast_vs_reference_and_results_unperturbed():
    hp, bes, trace = _sim_inputs()
    runs = {}
    for fast in (True, False):
        bare = simulate("tally", hp, bes, trace, A100, duration=10.0,
                        fast=fast)
        hub = ObsHub()
        obs = simulate("tally", hp, bes, trace, A100, duration=10.0,
                       fast=fast, obs=hub)
        # observation-only: the simulated outcome is untouched
        assert obs.latency.latencies == bare.latency.latencies
        assert obs.be_tput["gpt2-train"].samples == \
            bare.be_tput["gpt2-train"].samples
        runs[fast] = hub
    # bit-exact across engines: byte-identical exposition
    assert prometheus_text(runs[True].registry) == \
        prometheus_text(runs[False].registry)
    assert to_jsonl(runs[True].registry) == to_jsonl(runs[False].registry)
    # and the registry actually saw the run
    fam = runs[True].registry.get("tally_hp_requests_done_total")
    assert fam.child("0").value > 0


def test_registry_matches_engine_counts():
    hp, bes, trace = _sim_inputs()
    hub = ObsHub()
    book = simulate("tally", hp, bes, trace, A100, duration=10.0, obs=hub)
    r = hub.registry
    assert r.get("tally_hp_requests_done_total").child("0").value == \
        book.latency.count
    assert r.get("tally_be_samples_total").child("0", "gpt2-train").value \
        == book.be_tput["gpt2-train"].samples
    h = r.get("tally_hp_request_latency_seconds").child("0")
    assert h.count == book.latency.count
    assert h.sum == pytest.approx(sum(book.latency.latencies))
    tl = r.get("tally_hp_request_latency_series").child("0")
    assert tl.vs == list(book.latency.latencies)
    # end-of-run gauges
    assert r.get("tally_device_requests_done").child("0").value == \
        book.latency.count


def test_serving_probe_registers_and_observes():
    hub = ObsHub()
    p = ServingProbe(hub)
    p.admitted(0.01, 0.002)
    p.retired(0.05)
    p.be_quantum()
    p.slots(2.0)
    p.decoded(3)
    p.decoded(2)
    r = hub.registry
    assert r.get("tally_serving_requests_total").child().value == 1.0
    assert r.get("tally_serving_ttft_seconds").child().count == 1
    assert r.get("tally_serving_queue_wait_seconds").child().sum == 0.002
    assert r.get("tally_serving_decode_steps_total").child().value == 2.0
    assert r.get("tally_serving_decode_slots_total").child().value == 5.0
    assert hub.serving() is hub.serving()      # memoized


def test_serving_weight_bytes_gauge_counts_the_served_tree():
    import jax

    from repro.configs import get_config
    from repro.models.transformer import build_model
    from repro.serving import ServingConfig, ServingEngine

    model = build_model(get_config("mamba2-130m").reduced())
    params = model.init(jax.random.PRNGKey(0))
    want = {}
    for x in jax.tree.leaves(model.serving_params(params)):
        want[str(x.dtype)] = want.get(str(x.dtype), 0) + x.nbytes
    assert sorted(want) == ["bfloat16", "float32"]

    hub = ObsHub()
    eng = ServingEngine(model, params, ServingConfig(capacity=1, max_len=16),
                        obs=hub)
    fam = hub.registry.get("tally_serving_weight_bytes")
    assert {k[0]: c.value for k, c in fam.items()} == want
    assert sum(x.nbytes for x in jax.tree.leaves(eng.params)) == \
        sum(want.values())
    bare = ServingEngine(model, params, ServingConfig(capacity=1, max_len=16))
    assert bare.obs is None


def test_dashboard_renders_from_small_fleet_run():
    from repro.core.fleet import FleetSimulator, be_job, hp_service

    hub = ObsHub()
    res = FleetSimulator(2, "first_fit", horizon=6.0, check_interval=2.0,
                         min_window=10, obs=hub).run(
        [hp_service("svc", paper_workload("bert-infer", 0), load=0.4,
                    seed=1),
         be_job("tr", paper_workload("gpt2-train", 1))])
    html = render_dashboard(res, hub)
    assert "<html" in html and "Run summary" in html and "<svg" in html
