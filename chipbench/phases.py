"""The serving engine's phases in a profiler trace: device idle per phase.

    python3 chipbench/phases.py --workload <cell> --seed <n> [--seconds 15]

The engine marks the phases of ``ServingEngine.step`` with host spans named
``tally.serve.*`` (``step``; per admission ``admit`` around ``prefill``,
``insert`` and ``first_token``; ``decode`` with stats ``active`` and
``kv_tokens``, ``decode_wait``, ``emit``, ``be_quantum``). They are on the
profiler's clock, like the device's ``XLA Modules`` line that
``trace_reduce`` reads, so each stretch of device idle time falls inside
the phase the host was in. A program without these spans gives empty
results here, not an error.

The command serves the cell's traffic twice in one process after the usual
set-up: a window with no trace, then the same schedule traced. It prints
one JSON line: the mean host time of an ``engine.step()`` that did HP work
in each window (what the spans cost while a trace records them, beside the
profiler's own cost) and the readings of ``hp_admit_ms`` and
``hp_queue_wait_ms``; then the mean length of each phase and the device
idle inside it, the longest idle
gaps named by the innermost benchmark or engine span around them, and the
mean idle inside an engine step that decoded (``step_idle_ms``).
"""
from __future__ import annotations

import argparse
import bisect
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce  # noqa: E402

PREFIX = "tally.serve."
STEP = PREFIX + "step"
DECODE = PREFIX + "decode"
STAMP_METRICS = ("hp_admit_ms", "hp_queue_wait_ms")   # read in each window
Span = trace_reduce.ProgramSpan            # name, start_ns, duration_ns, stats


def busy_intervals(trace: dict) -> List[List[Tuple[float, float]]]:
    """Per device that ran anything, the union of its program executions
    (``trace_reduce.reduce``'s busy intervals, unclipped)."""
    out = []
    for lines in trace["devices"].values():
        ivs = trace_reduce.union([(s, s + d) for _, s, d in
                                  lines.get("XLA Modules", [])])
        if ivs:
            out.append(ivs)
    return out


def _busy_within(ivs, starts, lo: float, hi: float) -> float:
    busy = 0.0
    for s, e in ivs[max(bisect.bisect_right(starts, lo) - 1, 0):]:
        if s >= hi:
            break
        busy += max(0.0, min(e, hi) - max(s, lo))
    return busy


def idle_inside(trace: dict, spans: List[Span], name: str,
                window: Tuple[float, float]) -> List[float]:
    """Device-idle seconds inside each span ``name`` that starts in
    ``window``, averaged over the devices that ran anything, as ``reduce``
    averages busy time."""
    devs = [(ivs, [s for s, _ in ivs]) for ivs in busy_intervals(trace)]
    if not devs:
        return []
    lo, hi = window
    out = []
    for n, s, d, _ in spans:
        if n == name and lo <= s < hi:
            busy = sum(_busy_within(ivs, st, s, s + d) for ivs, st in devs)
            out.append((d - busy / len(devs)) / 1e9)
    return out


def decode_stats(spans: List[Span]) -> List[dict]:
    """The stats of each ``decode`` span, one mapping per decode step."""
    return [stats for n, _, _, stats in spans if n == DECODE]


def decoding_steps(spans: List[Span]) -> List[Span]:
    """The ``step`` spans that hold a ``decode`` span."""
    decodes = sorted(s for n, s, _, _ in spans if n == DECODE)
    out = []
    for sp in spans:
        if sp[0] == STEP:
            i = bisect.bisect_left(decodes, sp[1])
            if i < len(decodes) and decodes[i] < sp[1] + sp[2]:
                out.append(sp)
    return out


def step_idle_ms(trace: dict, spans: List[Span],
                 window: Tuple[float, float]):
    """Mean device-idle ms inside an engine step that decoded, or None."""
    idle = idle_inside(trace, decoding_steps(spans), STEP, window)
    return 1e3 * sum(idle) / len(idle) if idle else None


def span_ms(spans: List[Span], name: str, window: Tuple[float, float]):
    """Mean duration in ms of the spans ``name`` that start in ``window``."""
    lo, hi = window
    ds = [d for n, s, d, _ in spans if n == name and lo <= s < hi]
    return sum(ds) / len(ds) / 1e6 if ds else None


def idle_by_phase(trace: dict, spans: List[Span],
                  window: Tuple[float, float]) -> Dict[str, float]:
    """Device-idle seconds inside the spans of each phase name in the
    window; ``step`` and ``admit`` hold the leaves beside them."""
    names = sorted({n for n, *_ in spans if n.startswith(PREFIX)})
    return {n: sum(idle_inside(trace, spans, n, window)) for n in names}


class StepClock:
    """The engine, with each ``step()`` timed on the host clock and marked
    as a BE quantum, other work, or nothing done."""

    def __init__(self, engine):
        self.engine = engine
        self.steps: List[Tuple[float, str]] = []

    def submit(self, *args, **kwargs):
        return self.engine.submit(*args, **kwargs)

    def step(self) -> bool:
        quanta = self.engine.be_quanta
        t0 = time.perf_counter()
        worked = self.engine.step()
        dt = time.perf_counter() - t0
        kind = ("be" if self.engine.be_quanta != quanta
                else "hp" if worked else "idle")
        self.steps.append((dt, kind))
        return worked

    def hp_step_ms(self):
        hp = [dt for dt, k in self.steps if k == "hp"]
        return (1e3 * sum(hp) / len(hp) if hp else None), len(hp)


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--root", default=None)
    args = ap.parse_args(argv)
    seed = args.seed % (1 << 64)

    import run
    from spec import Bench
    bench = Bench(args.root)
    cell = run.cell_files(bench, args.workload)
    readers = {m: Bench().metric_reader(m) for m in STAMP_METRICS}
    devices, compile_count = run.start_jax(bench, args.workload, require_tpu)
    if devices is None:
        return 2
    import jax
    import driver
    import generator

    arrivals = generator.generate(cell["mix"], cell["wl"]["rate_rps"],
                                  args.seconds)
    prompts = run.prompts_for(cell, arrivals, seed)
    be, hp, _ = run.build(cell, seed)
    gc.collect()
    gc.freeze()
    out = {"workload": args.workload, "seed": args.seed,
           "device": devices[0].device_kind}
    trace_dir = bench.root / ".chipbench_trace"
    for traced in (False, True):
        eng = StepClock(hp.engine)
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        w = driver.run_window(eng, arrivals, prompts, args.seconds,
                              run.DRAIN_S, compile_count)
        if traced:
            jax.profiler.stop_trace()
        ms, n = eng.hp_step_ms()
        key = "traced" if traced else "untraced"
        out[key] = {"hp_step_ms": ms, "hp_steps": n,
                    "be_steps": sum(1 for _, k in eng.steps if k == "be"),
                    "compiles": w.compiles_in_window}
        out[key].update({m: readers[m]({"window": w}) for m in readers})
        hp.engine.done.clear()
    trace = trace_reduce.load_xplane(
        str(sorted(trace_dir.glob("**/*.xplane.pb"))[-1]))
    spans = trace["program_spans"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    window = trace_reduce.window_of(trace)
    red = trace_reduce.reduce(trace, window)
    out["traced"].update({
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "program_spans": len(spans),
        "step_idle_ms": step_idle_ms(trace, spans, window),
        "decoding_steps": len(decoding_steps(spans)),
        "span_ms_by_phase": {n: span_ms(spans, n, window)
                             for n in sorted({n for n, *_ in spans})},
        "idle_s_by_phase": idle_by_phase(trace, spans, window),
        "idle_s_outside_steps": (red["window_s"] - red["busy_s"] - sum(
            idle_inside(trace, spans, STEP, window))
            if red["devices"] else None),
        "gaps": red["gaps"]})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
