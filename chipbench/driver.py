"""The open-loop window: submit each request when it is due, step the engine.

One thread. Each request is timed from its due time, so a stall of the
loop (a BE step in flight, a burst of prefills) counts against every
request that fell due meanwhile. A token's time is when the host saw it:
the first from the engine's own stamp after the prefill, the others when
``engine.step()`` returned, which waits for the decode step to finish.
An engine step longer than ``STALL_S`` is kept with the main thread's CPU
time in it, which tells a loop that computed (Python, a garbage
collection) from one that waited (on the device or for a CPU core).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List

import jax
import numpy as np

STALL_S = 0.3


@dataclass
class Record:
    due: float                      # absolute host clock
    submitted: float
    prompt: np.ndarray
    req: object                     # repro.serving.Request
    times: List[float] = field(default_factory=list)   # one per token


@dataclass
class Stall:
    start: float                    # host clock
    seconds: float
    cpu_s: float                    # the main thread's CPU time in it
    admitted: int


@dataclass
class Window:
    start: float
    seconds: float
    end_drain: float = 0.0
    records: List[Record] = field(default_factory=list)
    compiles_in_window: int = 0
    longest_step: float = 0.0       # seconds of the longest engine.step()
    longest_step_admitted: int = 0  # requests it admitted
    stalls: List[Stall] = field(default_factory=list)

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records if r.due < self.start + self.seconds]


def run_window(engine, arrivals, prompts, seconds: float, drain_s: float,
               compile_count) -> Window:
    """Serve ``arrivals`` (due offsets in seconds) for ``seconds``, then
    serve what is left for at most ``drain_s`` more."""
    n = len(arrivals)
    c0 = compile_count()
    w = Window(start=time.monotonic(), seconds=seconds)
    deadline = w.start + seconds
    due = [w.start + a.due_s for a in arrivals]
    open_recs: List[Record] = []
    i = 0
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while True:
            now = time.monotonic()
            while i < n and due[i] <= now:
                a = arrivals[i]
                req = engine.submit(prompts[i], max_new_tokens=a.max_new_tokens)
                rec = Record(due[i], time.monotonic(), prompts[i], req)
                w.records.append(rec)
                open_recs.append(rec)
                i += 1
            if i >= n and not open_recs and now >= deadline:
                break
            if now >= deadline + drain_s:
                break
            before = [len(r.req.tokens) for r in open_recs]
            cpu0 = time.thread_time()
            with jax.profiler.TraceAnnotation("chipbench.engine_step"):
                worked = engine.step()
            t = time.monotonic()
            step_s = t - now
            admitted = 0
            for rec, b in zip(open_recs, before):
                k = len(rec.req.tokens) - b
                if k <= 0:
                    continue
                if b == 0:
                    admitted += 1
                    rec.times.append(rec.req.first_token_t)
                    k -= 1
                rec.times.extend([t] * k)
            if step_s > w.longest_step:
                w.longest_step, w.longest_step_admitted = step_s, admitted
            if step_s > STALL_S:
                w.stalls.append(Stall(now, step_s, time.thread_time() - cpu0,
                                      admitted))
            open_recs = [r for r in open_recs if not r.req.done]
            if not worked:
                nxt = due[i] if i < n else deadline
                with jax.profiler.TraceAnnotation("chipbench.wait_arrival"):
                    time.sleep(min(max(nxt - time.monotonic(), 0.0), 0.002))
    w.end_drain = time.monotonic()
    w.compiles_in_window = compile_count() - c0
    return w
