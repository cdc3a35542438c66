"""From a profiler trace to busy time, per-program device time and idle gaps.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``, into plain lists: the ``XLA Modules`` line of
each device plane (``/device:TPU:<n>``; one event per program execution),
the host's ``chipbench.*`` spans (the benchmark's) and ``tally.*`` spans
(the engine's, ``ServingEngine.step``'s phases), the engine's also with
their stats as ``program_spans``. The per-op line (a million events in a
few seconds of decoding) is not read. ``reduce`` works on those lists
only, so a small recorded trace kept as JSON tests it.

- busy: the union of the program executions' intervals on each device,
  clipped to the window, averaged over the devices that ran anything (a
  program's own short stalls between its ops count as busy);
- programs: per program name (the jit name without its ``(id)``), the
  executions that started in the window and their summed device seconds;
- gaps: the stretches of the window with no program on the device, longest
  first, each labelled by the innermost span around its middle, the
  engine's where an engine span and a benchmark span are equally long.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]           # name, start_ns, duration_ns
ProgramSpan = Tuple[str, float, float, dict]   # ... and the span's stats
BENCH_PREFIX = "chipbench."
PROGRAM_PREFIX = "tally."
WINDOW_SPAN = BENCH_PREFIX + "window"
_ID = re.compile(r"\(\d+\)$")


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    program: List[ProgramSpan] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            lines = {}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    lines[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(BENCH_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append((e.name, e.start_ns, e.duration_ns,
                                        dict(e.stats)))
    program.sort(key=lambda s: s[1])
    return {"devices": devices,
            "spans": spans + [(n, s, d) for n, s, d, _ in program],
            "program_spans": program}


def program_name(event_name: str) -> str:
    return _ID.sub("", event_name.strip())


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def window_of(trace: dict) -> Tuple[float, float]:
    w = [(s, s + d) for n, s, d in trace["spans"] if n == WINDOW_SPAN]
    if not w:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return w[0]


def label(spans: List[Event], t: float) -> str:
    """The innermost (shortest) span holding ``t``; of two equally long,
    the engine's."""
    best: Optional[Tuple[float, bool, str]] = None
    for n, s, d in spans:
        if s <= t < s + d and n != WINDOW_SPAN:
            key = (d, not n.startswith(PROGRAM_PREFIX), n)
            if best is None or key[:2] < best[:2]:
                best = key
    return best[2] if best else "outside_spans"


def reduce(trace: dict, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> dict:
    lo, hi = window or window_of(trace)
    busy_s, gaps, programs = [], [], {}
    for lines in trace["devices"].values():
        mods = lines.get("XLA Modules", [])
        ivs = union(_clip([(s, s + d) for _, s, d in mods], lo, hi))
        if not ivs:
            continue
        busy_s.append(sum(e - s for s, e in ivs) / 1e9)
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for n, s, d in mods:
            if lo <= s < hi:
                p = programs.setdefault(program_name(n), [0, 0.0])
                p[0] += 1
                p[1] += d / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "devices": len(busy_s),
        "programs": {k: {"count": c, "seconds": s}
                     for k, (c, s) in programs.items()},
        "gaps": [[label(trace["spans"], (s + e) / 2), (e - s) / 1e9]
                 for s, e in gaps[:top]],
    }


def program(red: dict, prefix: str) -> Tuple[int, float]:
    """Executions and device seconds of the programs named ``prefix``."""
    n = sec = 0
    for name, p in red["programs"].items():
        if name == prefix or name.startswith(prefix + "."):
            n += p["count"]
            sec += p["seconds"]
    return n, sec


def breakdown(red: dict, top: int = 10) -> dict:
    progs = sorted(red["programs"].items(), key=lambda kv: -kv[1]["seconds"])
    return {"device_ops": [[k, v["seconds"]] for k, v in progs[:top]],
            "idle_gaps": red["gaps"][:top]}
