"""Serving engine: continuous batching parity with sequential decode,
slot lifecycle, opportunistic best-effort hook, and the request-level
robustness layer (EDF admission, timeout retries, hedging, brownout)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import build_model
from repro.serving import (BrownoutPolicy, HedgePolicy, RetryPolicy,
                           ServingConfig, ServingEngine)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2.5-14b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ref_decode(model, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = model.forward_train(params, jnp.asarray([toks]))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


@pytest.mark.slow
def test_continuous_batching_matches_sequential(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, ServingConfig(capacity=3,
                                                     max_len=48))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 7, 6)]          # 4 reqs > 3 slots
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    for r, p in zip(reqs, prompts):
        assert r.tokens[:5] == _ref_decode(model, params, p, 5)


def test_slots_are_reused(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, ServingConfig(capacity=1,
                                                     max_len=48))
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, size=4)
                       .astype(np.int32), max_new_tokens=3)
            for _ in range(3)]
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    assert eng.n_active == 0


def test_be_hook_only_when_idle(setup):
    cfg, model, params = setup
    calls = []
    eng = ServingEngine(model, params, ServingConfig(capacity=2,
                                                     max_len=48),
                        best_effort_hook=lambda: calls.append(
                            eng.n_active))
    rng = np.random.default_rng(2)
    eng.submit(rng.integers(0, cfg.vocab_size, size=4).astype(np.int32),
               max_new_tokens=3)
    eng.run_until_idle()
    assert eng.n_active == 0
    # invoke a few idle steps
    for _ in range(3):
        eng.step()
    assert calls and all(n == 0 for n in calls)   # hook never preempted HP


def test_latency_metrics_populated(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, ServingConfig(capacity=2,
                                                     max_len=48))
    r = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    eng.run_until_idle()
    assert r.done and r.ttft is not None and r.latency >= r.ttft


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_deadline_sheds_queued_requests(setup):
    cfg, model, params = setup
    clk = _FakeClock()
    eng = ServingEngine(model, params, ServingConfig(capacity=1,
                                                     max_len=48),
                        clock=clk)
    held = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=40)
    eng.step()                       # `held` takes the only slot
    starved = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                         timeout=5.0)
    clk.t = 6.0                      # past starved's deadline
    eng.step()
    assert starved.shed and starved in eng.shed_requests
    assert starved.first_token_t is None      # dropped without prefilling
    assert not held.shed
    assert len(eng.queue) == 0


def test_deadline_evicts_stuck_slot(setup):
    cfg, model, params = setup
    clk = _FakeClock()
    eng = ServingEngine(model, params, ServingConfig(capacity=1,
                                                     max_len=48),
                        clock=clk)
    # an EOS that never arrives: without the deadline the slot would be
    # occupied until max_new_tokens
    stuck = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=30,
                       timeout=3.0)
    eng.step()
    assert eng.n_active == 1
    clk.t = 4.0
    assert eng.step()                # shed counts as work done
    assert stuck.shed and eng.n_active == 0
    nxt = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
    eng.run_until_idle()
    assert nxt.done and not nxt.shed


def test_config_default_timeout_and_probe(setup):
    from repro.obs import ObsHub

    cfg, model, params = setup
    clk = _FakeClock()
    hub = ObsHub()
    eng = ServingEngine(model, params,
                        ServingConfig(capacity=1, max_len=48,
                                      request_timeout=2.0),
                        obs=hub, clock=clk)
    r1 = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=40)
    r2 = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
    assert r1.deadline == r2.deadline == 2.0    # config default at submit
    eng.step()
    clk.t = 2.5
    eng.step()
    assert r1.shed and r2.shed       # r1 evicted from its slot, r2 queued
    shed = hub.registry.get("tally_serving_sheds_total")
    assert {k: c.v for k, c in shed.items()} \
        == {("queued",): 1.0, ("slot",): 1.0}


def test_no_deadline_never_sheds(setup):
    cfg, model, params = setup
    clk = _FakeClock()
    eng = ServingEngine(model, params, ServingConfig(capacity=1,
                                                     max_len=48),
                        clock=clk)
    r = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
    clk.t = 1e9
    eng.run_until_idle()
    assert r.done and not r.shed and eng.shed_requests == []


# ---------------------------------------------------------------------------
# Request-level robustness (PR 9): EDF admission, retries, hedging, brownout
# ---------------------------------------------------------------------------


def test_edf_admission_prevents_deadline_starvation(setup):
    """Regression (two-request counterexample): under FIFO admission a
    late-arriving tight-deadline request starves behind an earlier lax
    one and gets shed; EDF (least deadline slack first) admits it first
    and it completes."""
    cfg, model, params = setup
    clk = _FakeClock()
    eng = ServingEngine(model, params, ServingConfig(capacity=1,
                                                     max_len=48),
                        clock=clk)
    lax = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                     timeout=100.0)
    tight = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                       timeout=5.0)      # later arrival, tighter deadline
    eng.step()                           # EDF: `tight` takes the slot first
    assert tight.done and not lax.done   # completed within its budget
    eng.run_until_idle()
    assert tight.done and not tight.shed
    assert lax.done and not lax.shed     # lax still makes its lax cutoff


def test_retry_requeues_with_deterministic_backoff(setup):
    cfg, model, params = setup
    clk = _FakeClock()
    rp = RetryPolicy(max_retries=2, backoff_base=1.0, backoff_factor=2.0,
                     backoff_max=10.0, jitter=0.0)
    eng = ServingEngine(model, params, ServingConfig(capacity=1,
                                                     max_len=48),
                        clock=clk, retry=rp)
    blocker = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=40)
    eng.step()                           # blocker occupies the only slot
    r = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                   timeout=2.0)
    clk.t = 3.0                          # r expires in queue -> retry #1
    eng.step()
    assert not r.shed and r.attempt == 1 and r in eng.queue
    assert r.eligible_t == pytest.approx(3.0 + 1.0)   # backoff gate
    assert r.deadline == pytest.approx(4.0 + 2.0)     # re-armed timeout
    # gated: not admissible before eligible_t even with a free slot
    while eng.n_active:                  # let the blocker finish
        eng.step()
    eng.step()
    assert r not in eng.done and eng.n_active == 0
    clk.t = 4.5                          # gate open
    eng.run_until_idle()
    assert r.done and not r.shed
    assert r.latency == pytest.approx(r.done_t - 0.0)  # from original submit


def test_retry_exhaustion_sheds_terminally(setup):
    cfg, model, params = setup
    clk = _FakeClock()
    rp = RetryPolicy(max_retries=1, backoff_base=0.5, jitter=0.0)
    eng = ServingEngine(model, params, ServingConfig(capacity=1,
                                                     max_len=48),
                        clock=clk, retry=rp)
    blocker = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=40)
    eng.step()
    r = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                   timeout=1.0)
    clk.t = 1.5                          # first expiry -> retry
    eng.step()
    assert r.attempt == 1 and not r.shed
    clk.t = 10.0                         # re-armed deadline also blown
    eng.step()
    assert r.shed and r in eng.shed_requests


def test_hedge_spawns_and_primary_win_cancels_clone(setup):
    from repro.obs import ObsHub

    cfg, model, params = setup
    clk = _FakeClock()
    hub = ObsHub()
    eng = ServingEngine(model, params, ServingConfig(capacity=2,
                                                     max_len=48),
                        clock=clk, obs=hub,
                        hedge=HedgePolicy(min_delay=1.0, max_hedges=1))
    b1 = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
    b2 = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
    eng.step()                           # both slots taken
    r = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
    clk.t = 2.0                          # r stuck in queue past the delay
    eng.step()
    assert r.rid in eng._hedge_group
    assert len(eng.queue) == 2           # primary + its hedge clone
    eng.run_until_idle()
    # primary admitted first (EDF rid tiebreak) and won; clone cancelled
    assert r.done and not r.shed
    assert sum(1 for q in eng.done if q.rid == r.rid) == 1
    assert eng._hedge_group == {}
    hedges = hub.registry.get("tally_serving_hedges_total")
    assert {k: c.v for k, c in hedges.items()} \
        == {("spawned",): 1.0, ("lost",): 1.0}


def test_hedge_clone_wins_while_primary_backoff_gated(setup):
    from repro.obs import ObsHub

    cfg, model, params = setup
    clk = _FakeClock()
    hub = ObsHub()
    eng = ServingEngine(
        model, params, ServingConfig(capacity=1, max_len=48),
        clock=clk, obs=hub,
        retry=RetryPolicy(max_retries=3, backoff_base=50.0,
                          backoff_max=100.0, jitter=0.0),
        hedge=HedgePolicy(min_delay=1.0, max_hedges=1))
    blocker = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=40)
    eng.step()
    r = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                   timeout=2.0)
    clk.t = 3.0                          # r times out -> gated until t=53
    eng.step()
    assert r.attempt == 1 and r.eligible_t == pytest.approx(53.0)
    clk.t = 5.0                          # stuck > hedge delay -> clone
    eng.step()
    assert r.rid in eng._hedge_group
    while eng.n_active:                  # drain the blocker
        eng.step()
    eng.run_until_idle()                 # clone admits (primary gated), wins
    assert r.done and not r.shed and len(r.tokens) == 2
    assert sum(1 for q in eng.done if q.rid == r.rid) == 1
    assert r not in eng.queue            # first-wins cancelled the primary
    hedges = hub.registry.get("tally_serving_hedges_total")
    assert {k: c.v for k, c in hedges.items()} \
        == {("spawned",): 1.0, ("won",): 1.0}


def test_brownout_shrinks_batch_and_sheds_least_slack_first(setup):
    from repro.obs import ObsHub

    cfg, model, params = setup
    clk = _FakeClock()
    hub = ObsHub()
    eng = ServingEngine(
        model, params, ServingConfig(capacity=2, max_len=48),
        clock=clk, obs=hub,
        retry=RetryPolicy(max_retries=3, backoff_base=0.1, jitter=0.0),
        brownout=BrownoutPolicy(queue_delay=1.0, min_capacity=1,
                                exit_delay=0.5))
    tight = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                       timeout=2.0)
    lax = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                     timeout=50.0)
    free1 = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
    free2 = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
    clk.t = 1.5                          # oldest wait 1.5 > queue_delay
    eng.step()
    assert eng.brownout_active
    # least slack shed first (tight, then lax, then free1 by rid) until
    # the queue fits the shrunk batch; brownout sheds are terminal even
    # with a retry policy attached
    assert tight.shed and lax.shed and free1.shed
    assert tight.attempt == 0
    shed = hub.registry.get("tally_serving_sheds_total")
    assert {k: c.v for k, c in shed.items()} == {("brownout",): 3.0}
    eng.run_until_idle()
    assert free2.done and not free2.shed
    eng.step()                           # pressure gone -> exit brownout
    assert not eng.brownout_active
    trans = hub.registry.get("tally_serving_brownout_transitions_total")
    assert {k: c.v for k, c in trans.items()} \
        == {("enter",): 1.0, ("exit",): 1.0}


def test_serving_model_published_widths():
    """``serve(..., reduced=False)`` builds the published config; checked
    from shapes alone (``jax.eval_shape``), nothing is allocated."""
    from repro.launch.serve import serving_model
    model = serving_model("mamba2-130m", reduced=False)
    cfg = model.cfg
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (24, 768, 50280)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert shapes["embed"].shape == (50280, 768)
    assert shapes["lm_head"].shape == (768, 50280)
    ssm = shapes["layers"]["p0"]["ssm"]
    assert ssm["wx"].shape == (24, 768, 1536)          # expand 2
    assert ssm["wB"].shape == (24, 768, 128)           # d_state 128
    assert ssm["wdt"].shape == (24, 768, 24)           # 24 heads of 64
    assert ssm["out_proj"].shape == (24, 1536, 768)
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 167e6 < n < 169e6
    assert serving_model("mamba2-130m").cfg.d_model == 64  # CPU preset


# ---------------------------------------------------------------------------
# Phase spans, request stamps and probe counters
# ---------------------------------------------------------------------------


def _serve_spans(trace_dir):
    """The engine's ``tally.serve.*`` host spans in a profiler trace:
    (name, start_ns, end_ns, stats), by start."""
    from jax.profiler import ProfileData
    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("tally.serve.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _spy_decode(eng):
    """Record (active slots, their cached tokens) at each decode call."""
    calls, decode = [], eng._decode

    def spy(params, tokens, cache, lengths):
        act = np.flatnonzero(eng._active)
        calls.append((len(act), int(eng._lengths[act].sum())))
        return decode(params, tokens, cache, lengths)
    eng._decode = spy
    return calls


PROMPTS = ((5, 3), (6, 4), (4, 2))      # (prompt length, max_new_tokens)


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    cfg, model, params = setup
    eng = ServingEngine(model, params, ServingConfig(capacity=2, max_len=48),
                        best_effort_hook=lambda: None)
    calls = _spy_decode(eng)
    reqs = [eng.submit(np.arange(n, dtype=np.int32), max_new_tokens=k)
            for n, k in PROMPTS]
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        eng.run_until_idle()
        eng.step()                       # idle: one best-effort quantum
    finally:
        jax.profiler.stop_trace()
    return reqs, calls, _serve_spans(d)


def test_one_admit_span_per_admitted_request(traced):
    reqs, _, spans = traced
    admits = [st for n, _, _, st in spans if n == "tally.serve.admit"]
    assert sorted(a["rid"] for a in admits) == [r.rid for r in reqs]
    for a in admits:
        r = reqs[a["rid"]]
        assert a["prompt_len"] == len(r.prompt)
        assert a["wait_us"] == pytest.approx(1e6 * r.queue_wait)


def test_decode_span_stats_match_slot_state(traced):
    _, calls, spans = traced
    got = [(st["active"], st["kv_tokens"]) for n, _, _, st in spans
           if n == "tally.serve.decode"]
    assert got == calls
    assert len(calls) == 3               # 2 slots: steps of 2, 2, then 1
    assert sum(a for a, _ in calls) == sum(k - 1 for _, k in PROMPTS)


def test_admission_phases_nest_inside_admit(traced):
    _, _, spans = traced
    admits = [s for s in spans if s[0] == "tally.serve.admit"]
    steps = [s for s in spans if s[0] == "tally.serve.step"]
    for _, s0, e0, st in admits:
        inner = [(n, s, e) for n, s, e, x in spans
                 if x.get("rid") == st["rid"] and n != "tally.serve.admit"]
        assert [n for n, _, _ in inner] == [
            "tally.serve.prefill", "tally.serve.insert",
            "tally.serve.first_token"]
        assert all(s0 <= s and e <= e0 for _, s, e in inner)
        assert any(s <= s0 and e0 <= e for _, s, e, _ in steps)
    for name in ("tally.serve.decode", "tally.serve.decode_wait",
                 "tally.serve.emit", "tally.serve.be_quantum"):
        phase = [s for s in spans if s[0] == name]
        assert phase and all(any(s <= p[1] and p[2] <= e
                                 for _, s, e, _ in steps) for p in phase)


def test_queue_wait_stamp_resets_on_retry(setup):
    cfg, model, params = setup
    clk = _FakeClock()
    eng = ServingEngine(model, params, ServingConfig(capacity=1, max_len=48),
                        clock=clk, retry=RetryPolicy(max_retries=2,
                                                     backoff_base=1.0,
                                                     jitter=0.0))
    r = eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=20,
                   timeout=2.0)
    assert r.admit_t is None and r.queue_wait is None
    clk.t = 0.25
    eng.step()
    assert r.admit_t == 0.25 and r.queue_wait == 0.25
    clk.t = 3.0                          # stuck in its slot -> retry #1
    eng.step()
    assert r.attempt == 1 and r in eng.queue
    assert r.admit_t is None and r.queue_wait is None
    clk.t = 4.5                          # backoff gate (4.0) open
    eng.run_until_idle()
    assert r.done and not r.shed
    assert r.admit_t == 4.5
    assert r.queue_wait == r.admit_t - r.submit_t == 4.5


def test_probe_counts_decode_steps_slots_and_admissions(setup):
    from repro.obs import ObsHub

    cfg, model, params = setup
    hub = ObsHub()
    eng = ServingEngine(model, params, ServingConfig(capacity=2, max_len=48),
                        obs=hub)
    calls = _spy_decode(eng)
    reqs = [eng.submit(np.arange(n, dtype=np.int32), max_new_tokens=k)
            for n, k in PROMPTS]
    eng.run_until_idle()
    r = hub.registry
    assert r.get("tally_serving_decode_steps_total").child().value == \
        len(calls)
    assert r.get("tally_serving_decode_slots_total").child().value == \
        sum(a for a, _ in calls) == sum(k - 1 for _, k in PROMPTS)
    waits = r.get("tally_serving_queue_wait_seconds").child()
    assert waits.count == len(reqs)
    assert waits.sum == pytest.approx(sum(q.queue_wait for q in reqs))


def _plain_serving(model, params, prompts, n_new, capacity, max_len):
    """The engine's algorithm with nothing around it: FIFO admission into
    the lowest free slot, a B=1 prefill written into the slot cache, one
    greedy decode step over every slot per iteration."""
    from repro.configs.base import kv_cache_specs
    from repro.models.transformer import pad_cache

    specs = kv_cache_specs(model.cfg, capacity, max_len)
    cache = {k: jnp.zeros(s.shape, s.dtype) for k, s in specs.items()}
    prefill = jax.jit(model.prefill)

    def impl(params, tokens, cache, lengths):
        logits, cache = model.decode_step(params, tokens, cache, lengths)
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), cache
    decode = jax.jit(impl)
    queue, out = list(range(len(prompts))), [[] for _ in prompts]
    slot_of = [None] * capacity
    lengths = np.zeros(capacity, np.int32)
    nxt = np.zeros(capacity, np.int32)
    while queue or any(i is not None for i in slot_of):
        while queue and None in slot_of:
            slot, i = slot_of.index(None), queue.pop(0)
            logits, c = prefill(params, jnp.asarray(prompts[i][None, :]))
            for k, a in pad_cache(c, max_len).items():
                cache[k] = jax.lax.dynamic_update_slice(
                    cache[k], a.astype(cache[k].dtype),
                    (0, slot) + (0,) * (a.ndim - 2))
            out[i].append(int(jnp.argmax(logits[0, -1])))
            slot_of[slot] = i
            lengths[slot], nxt[slot] = len(prompts[i]), out[i][-1]
        tok, cache = decode(params, jnp.asarray(nxt[:, None]), cache,
                            jnp.asarray(lengths))
        tok = np.asarray(tok)
        for slot, i in enumerate(slot_of):
            if i is None:
                continue
            out[i].append(int(tok[slot]))
            lengths[slot] += 1
            nxt[slot] = tok[slot]
            if len(out[i]) >= n_new[i] or lengths[slot] + 1 >= max_len:
                slot_of[slot], lengths[slot] = None, 0
    return out


def test_tokens_unchanged_with_spans_off(setup):
    cfg, model, params = setup
    assert not jax.profiler.TraceAnnotation.is_enabled()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n, _ in PROMPTS]
    n_new = [k + 2 for _, k in PROMPTS]
    eng = ServingEngine(model, params, ServingConfig(capacity=2, max_len=48))
    reqs = [eng.submit(p, max_new_tokens=k) for p, k in zip(prompts, n_new)]
    eng.run_until_idle()
    assert [r.tokens for r in reqs] == _plain_serving(
        model, params, prompts, n_new, capacity=2, max_len=48)
