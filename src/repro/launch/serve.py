"""Serving driver: batched inference + Tally-co-located best-effort training.

Demonstrates the paper's end-to-end scenario on real (reduced) models:
a high-priority serving engine handles MAF2-style traffic while a
best-effort training job consumes idle quanta through the opportunistic
hook — the engine-level mirror of Fig. 4 (the kernel-level path is
``core.virtualization``).

    python -m repro.launch.serve --arch qwen2.5-14b --requests 24 \
        --colocate-train

Request-level resilience (PR 9): ``--chaos`` injects a mid-run outage
(the engine blocks, queued requests blow their per-request timeout);
``--failover`` arms the client-side failover stack — timeout retries
with deterministic backoff, hedged requests, brownout degradation — so
the outage degrades latency instead of losing requests.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import all_arch_names, get_config
from repro.core.metrics import LatencyStats
from repro.core.traffic import maf2_like_trace
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import TransformerLM, build_model
from repro.serving import (BrownoutPolicy, HedgePolicy, Request,
                           RetryPolicy, ServingConfig, ServingEngine)


def serving_model(arch: str, reduced: bool = True) -> TransformerLM:
    """The served model: ``arch`` at its published widths, or its
    ``.reduced()`` CPU-test preset."""
    cfg = get_config(arch)
    return build_model(cfg.reduced() if reduced else cfg)


def serve(arch: str, *, requests: int = 16, capacity: int = 4,
          max_len: int = 96, max_new_tokens: int = 8,
          colocate_train: bool = False, seed: int = 0,
          mean_rate: float = 50.0, obs=None,
          timeout: Optional[float] = None, chaos: bool = False,
          failover: bool = False, stall_s: float = 8.0,
          reduced: bool = True) -> dict:
    model = serving_model(arch, reduced)
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(seed))

    be_state = {"quanta": 0, "loss": None}
    be_step = None
    if colocate_train:
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_optimizer, make_train_step
        from repro.data import DataConfig, SyntheticLMDataset
        mesh = make_host_mesh()
        bundle = make_train_step(model, mesh, ShapeConfig("be", 32, 2,
                                                          "train"))
        be_fn = jax.jit(bundle.fn)
        be_params = model.init(jax.random.PRNGKey(seed + 1))
        be_opt = make_optimizer(cfg).init(be_params)
        ds = SyntheticLMDataset(DataConfig(cfg.vocab_size, 32, 2,
                                           seed=seed))

        def be_step():
            nonlocal be_params, be_opt
            b = {k: jnp.asarray(v)
                 for k, v in ds.batch_at(be_state["quanta"]).items()}
            be_params, be_opt, m = be_fn(be_params, be_opt, b)
            be_state["quanta"] += 1
            be_state["loss"] = m["loss"]

    if chaos and timeout is None:
        # chaos without deadlines is invisible; the default budget sits
        # above the baseline p99 of a reduced model on the host CPU
        # (queueing-dominated, seconds; a host-side CPU timing, not a
        # device one) but below the injected outage, so only outage
        # victims time out
        timeout = 6.0
    retry = hedge = brownout = None
    if failover and timeout is not None:
        # thresholds scale off the request budget: retries re-arm fast,
        # hedges fire at half a budget of queue wait, brownout only under
        # pressure far beyond one budget (it sheds terminally)
        retry = RetryPolicy(max_retries=3, backoff_base=0.1,
                            backoff_factor=2.0, jitter=0.25)
        hedge = HedgePolicy(min_delay=timeout / 2)
        brownout = BrownoutPolicy(queue_delay=3.0 * timeout,
                                  min_capacity=max(1, capacity // 2),
                                  exit_delay=1.5 * timeout)
    engine = ServingEngine(model, params,
                           ServingConfig(capacity, max_len,
                                         request_timeout=timeout),
                           best_effort_hook=be_step, obs=obs,
                           retry=retry, hedge=hedge, brownout=brownout)
    rng = np.random.default_rng(seed)
    trace = maf2_like_trace(duration=requests / mean_rate * 2,
                            mean_rate=mean_rate, seed=seed)
    arrivals = trace.arrivals[:requests]
    t0 = time.monotonic()
    submitted = 0
    stall_after = len(arrivals) // 2 if chaos else None
    lat = LatencyStats()
    while submitted < len(arrivals) or engine.queue or engine.n_active:
        now = time.monotonic() - t0
        while submitted < len(arrivals) and arrivals[submitted] <= now:
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=int(rng.integers(4, 12)))
            engine.submit(prompt.astype(np.int32),
                          max_new_tokens=max_new_tokens)
            submitted += 1
        if stall_after is not None and submitted >= stall_after:
            # injected outage: the engine goes dark mid-run; everything
            # queued/in-flight blows its per-request timeout
            stall_after = None
            time.sleep(stall_s)
        if not engine.step():
            time.sleep(0.001)
    for r in engine.done:
        lat.record(r.latency)
    return {
        "arch": arch,
        "requests": len(engine.done),
        "shed": len(engine.shed_requests),
        "retries": sum(r.attempt for r in engine.done
                       + engine.shed_requests),
        "p50_ms": lat.p50() * 1e3,
        "p99_ms": lat.p99() * 1e3,
        "be_quanta": be_state["quanta"],
        "be_loss": (None if be_state["loss"] is None
                    else float(be_state["loss"])),
        "wall_s": time.monotonic() - t0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=all_arch_names(),
                    default="qwen2.5-14b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--colocate-train", action="store_true")
    ap.add_argument("--chaos", action="store_true",
                    help="inject a mid-run engine outage (arms per-request "
                         "timeouts)")
    ap.add_argument("--failover", action="store_true",
                    help="client-side failover stack: timeout retries, "
                         "hedged requests, brownout degradation")
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-request timeout in seconds")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="serve at the published widths")
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = serve(args.arch, requests=args.requests, capacity=args.capacity,
                max_new_tokens=args.max_new_tokens,
                colocate_train=args.colocate_train, chaos=args.chaos,
                failover=args.failover, timeout=args.timeout,
                reduced=args.reduced)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
