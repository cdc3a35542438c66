"""One run of one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

In one process: build both tenants from the seed on the device, take the
BE trainer's first three steps (set-up, and the output check's readings),
warm up the cell's own shapes, then serve the cell's open-loop traffic for
``--seconds`` with the BE trainer in the engine's idle gaps, and drain.
Then free the program's state and check its outputs against the plain
reference. The last line of standard output is one JSON object; the last
lines of standard error give each number compared beside its limit.

``--trace 1`` profiles a window of at most ``TRACE_S`` seconds and prints
the cell's per-layer metrics instead of its end-to-end ones. Without a TPU,
or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

DRAIN_S = 60.0        # a request due in the window may finish this late
CONTROL = "fp8"       # the output check's control: the next precision below
                      # the configurations' bfloat16
TRACE_S = 15.0        # the traced window's length at most


class GcClock:
    """The interpreter's garbage collections, timed (``gc.callbacks``)."""

    def __init__(self):
        self.spans = []          # (start, end, generation), host clock
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            self.spans.append((self._t0, time.monotonic(), info["generation"]))

    def within(self, lo: float, hi: float) -> float:
        return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e, _ in self.spans)


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def end_to_end(w, be, setup_s: float) -> dict:
    recs = w.due_in_window()
    ttft = [((r.req.first_token_t if r.req.first_token_t is not None
              else w.end_drain) - r.due) * 1e3 for r in recs]
    itl = [(b - a) * 1e3 for r in recs for a, b in zip(r.times, r.times[1:])]
    lo, hi = w.start, w.start + w.seconds
    be_steps = sum(1 for s, e in be.spans if lo <= e <= hi)
    return {
        "setup_s": {"value": setup_s, "unit": "s"},
        "hp_ttft_p95_ms": {"value": percentile(ttft, 95), "unit": "ms"},
        "hp_itl_p95_ms": {"value": percentile(itl, 95), "unit": "ms"},
        "be_tokens_per_s": {"value": be_steps * be.tokens_per_step
                            / w.seconds, "unit": "tokens/s"},
    }


def cell_files(bench, name: str) -> dict:
    """A cell's workload, configuration, traffic mix and BE job."""
    wl = bench.workload(name)
    cfg = bench.config(wl["config"])
    return {"wl": wl, "cfg": cfg, "mix": bench.traffic(wl["traffic"]),
            "job": cfg["be"], "be_cfg": bench.config(cfg["be"]["config"])}


def build(cell: dict, seed: int):
    """Set-up: both tenants from ``seed``, the BE trainer's first three
    steps (returned: the output check's readings) and the HP warm-up."""
    import tenants
    be = tenants.BE(cell["be_cfg"], cell["job"], seed)
    hp = tenants.HP(cell["cfg"], cell["wl"], seed, hook=be.step)
    prog_train = be.first_steps(3)
    hp.warm_up(cell["mix"]["prompt"]["buckets"], seed)
    return be, hp, prog_train


def prompts_for(cell: dict, arrivals, seed: int) -> list:
    import generator
    import weights
    vocab = weights.embedding_rows(cell["cfg"])
    return [generator.prompt_tokens(seed, i, a.prompt_len, vocab)
            for i, a in enumerate(arrivals)]


def check_outputs(cell: dict, seed: int, samples, prog_train,
                  control: bool = False) -> dict:
    """The numbers compared (see ``check.py``). With ``control``, also the
    fp8 control's and the planted faults' readings, under prefixes."""
    import check
    import weights
    cfg, wl = cell["cfg"], cell["wl"]
    params = weights.params_fn(cfg)(weights.key_from_seed(seed, 10))
    gap, gap_c = check.token_gaps(cfg, params, samples, wl["max_len"],
                                  control=CONTROL if control else None)
    out = {"hp_token_gap": gap}
    if control:
        out["control.hp_token_gap"] = gap_c
        vocab = weights.embedding_rows(cfg)
        altered = [(p, np.concatenate([t[:-1], [(t[-1] + 1) % vocab]]
                                      ).astype(np.int32))
                   for p, t in samples]
        out["fault_token.hp_token_gap"] = check.token_gaps(
            cfg, params, altered, wl["max_len"])[0]
    del params
    if prog_train is None:
        return out
    ref = check.reference_training(cell["be_cfg"], cell["job"], seed)
    out.update(check.training_numbers(prog_train, ref))
    if control:
        d = check.training_numbers(prog_train, ref, detail=True)
        out.update({"detail." + k: d[k] for k in d if k not in out})
        q = check.reference_training(cell["be_cfg"], cell["job"], seed,
                                     mode=CONTROL)
        out.update({"control." + k: v for k, v in check.training_numbers(
            q, ref, detail=True).items() if k.startswith("be_")})
        half = check.reference_training(
            cell["be_cfg"], cell["job"], seed,
            rows=range(cell["job"]["batch"] // 2))
        out.update({"fault_half_batch." + k: v for k, v in
                    check.training_numbers(half, ref, detail=True).items()
                    if k.startswith("be_")})
        out["detail.control_losses"] = q["losses"]
        out["detail.half_batch_losses"] = half["losses"]
    return out


def start_jax(bench, name: str, require_tpu: bool):
    """JAX's devices, or None (with the reason on standard error) when
    there is no TPU or fewer chips than the cell asks for. Turns on the
    persistent compilation cache and returns a compile counter."""
    sys.path.insert(0, str(bench.root / "src"))
    cells = {c["name"]: c for c in bench.benchmark()["workloads"]}
    chips = cells.get(name, {}).get("chips", 1)
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"run: JAX found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return None, None
    if len(devices) < chips:
        print(f"run: {len(devices)} chips, the cell needs {chips}",
              file=sys.stderr)
        return None, None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0]

    def on_event(ev, _d, **_kw):
        if ev == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return devices, (lambda: compiles[0])


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=None,
                    help="repository root holding BENCHMARK.json and the "
                         "program (default: the parent of this directory)")
    args = ap.parse_args(argv)
    seed = args.seed % (1 << 64)

    from spec import Bench
    bench = Bench(args.root)
    cell = cell_files(bench, args.workload)
    devices, compile_count = start_jax(bench, args.workload, require_tpu)
    if devices is None:
        return 2
    import jax
    import check
    import driver
    import generator

    wl = cell["wl"]
    seconds = min(args.seconds, TRACE_S) if args.trace else args.seconds
    arrivals = generator.generate(cell["mix"], wl["rate_rps"], seconds)
    prompts = prompts_for(cell, arrivals, seed)
    be, hp, prog_train = build(cell, seed)
    gc.collect()      # set-up's garbage, then keep set-up's objects (JAX's
    gc.freeze()       # traces among them) out of the window's collections
    trace_dir = bench.root / ".chipbench_trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # tracing each Python call slows
        opts.enable_hlo_proto = False       # the host loop several times
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.monotonic() - T_START
    gcs = GcClock()
    gc.callbacks.append(gcs)
    w = driver.run_window(hp.engine, arrivals, prompts, seconds, DRAIN_S,
                          compile_count)
    gc.callbacks.remove(gcs)
    if args.trace:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    metrics = end_to_end(w, be, setup_s)
    recs = w.due_in_window()
    late = [(r.submitted - r.due) * 1e3 for r in recs] or [0.0]
    print(f"window: {len(recs)} requests due, {w.compiles_in_window} "
          f"compilations in the window and drain, generator late by mean "
          f"{np.mean(late):.3f} ms and at most {np.max(late):.3f} ms, "
          f"{len(be.spans)} BE steps, drained in "
          f"{w.end_drain - w.start - seconds:.3f} s, longest engine step "
          f"{w.longest_step * 1e3:.1f} ms ({w.longest_step_admitted} "
          f"admitted)", file=sys.stderr)
    longest_gc = max((e - s for s, e, _ in gcs.spans), default=0.0)
    print(f"gc: {len(gcs.spans)} collections in the window and drain "
          f"({sum(1 for *_, g in gcs.spans if g == 2)} of generation 2), "
          f"longest {longest_gc * 1e3:.1f} ms; {len(w.stalls)} engine steps "
          f"over {driver.STALL_S * 1e3:.0f} ms (at s, ms, main-thread CPU ms, "
          f"GC ms, admitted): " + ", ".join(
              f"({x.start - w.start:.2f}, {x.seconds * 1e3:.1f}, "
              f"{x.cpu_s * 1e3:.1f}, "
              f"{gcs.within(x.start, x.start + x.seconds) * 1e3:.1f}, "
              f"{x.admitted})" for x in w.stalls[:12]), file=sys.stderr)

    samples = check.sample_requests(recs, seed)
    be_spans = list(be.spans)
    del hp, be
    gc.unfreeze()     # the tenants' reference cycles hold device memory
    gc.collect()
    numbers = check_outputs(cell, seed, samples, prog_train)
    limits = wl["limits"]
    correct = bool(samples) and all(numbers[k] <= limits[k] for k in limits)

    out = {"correct": correct, "attempted": len(recs),
           "failed": sum(1 for r in recs if not r.req.done)}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if args.trace:
        import trace_reduce
        files = sorted(trace_dir.glob("**/*.xplane.pb"))
        events = trace_reduce.load_xplane(str(files[-1]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = trace_reduce.window_of(events)
        red = trace_reduce.reduce(events, (lo, hi))
        ctx = {"trace": red, "events": events, "program_spans": [
                   s for s in events["program_spans"] if lo <= s[1] < hi],
               "window": w, "be_spans": be_spans, "cfg": cell["cfg"],
               "be_cfg": cell["be_cfg"], "job": cell["job"], "workload": wl,
               "mix": cell["mix"], "peaks": bench.peaks(devices[0].device_kind)}
        out["metrics"] = {}
        for m in bench.per_layer(args.workload):
            v = bench.metric_reader(m["name"])(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["device"] = device
        out["breakdown"] = trace_reduce.breakdown(red)
    else:
        wanted = [m["name"] for m in bench.end_to_end(args.workload)]
        out["metrics"] = {k: metrics[k] for k in wanted}
        out["device"] = device
    out["compared"] = {k: {"value": numbers[k], "limit": limits[k]}
                       for k in limits}
    print(json.dumps(out), flush=True)
    for k in limits:
        print(f"compared {k} {numbers[k]!r} limit {limits[k]!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
