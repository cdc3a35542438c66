"""The knee of a cell's length mix: the highest Poisson rate it keeps up with.

    python3 chipbench/sweep.py --workload <cell> --rates 4,8,16 \
        [--seconds 15] [--seed 1]

One process and one set-up (both tenants, the BE trainer in the engine's
idle gaps, as in a run). For each rate, in the order given, Poisson
arrivals with the cell's prompt and answer lengths for ``--seconds``, then
a drain. Each rate prints one JSON line: requests due, how many were still
outstanding at a third, two thirds and the end of the window (a queue that
keeps up stays near the slots in use; one that does not grows), the drain
time, and TTFT and inter-token percentiles. A rate keeps up when the
outstanding count at the end is no more than at a third plus an eighth of
the slots (at least 4), and the drain is shorter than the longest answer
takes to decode.
The benchmark's own runs never run this; its output is recorded in PERF.md
and the rates chosen are written into the workload files.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def outstanding(recs, t: float) -> int:
    return sum(1 for r in recs if r.due <= t
               and (r.req.done_t is None or r.req.done_t > t))


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--root", default=None)
    args = ap.parse_args(argv)

    from spec import Bench
    bench = Bench(args.root)
    cell = run.cell_files(bench, args.workload)
    devices, compile_count = run.start_jax(bench, args.workload, require_tpu)
    if devices is None:
        return 2
    import driver
    import generator

    mix = dict(cell["mix"], arrivals="poisson")
    be, hp, _ = run.build(cell, args.seed)
    cap = cell["wl"]["capacity"]
    for r in args.rates.split(","):
        rate = float(r)
        arrivals = generator.generate(mix, rate, args.seconds)
        prompts = run.prompts_for(cell, arrivals, args.seed)
        n_be = len(be.spans)
        w = driver.run_window(hp.engine, arrivals, prompts, args.seconds,
                              run.DRAIN_S, compile_count)
        recs = w.due_in_window()
        t0, T = w.start, args.seconds
        q = [outstanding(recs, t0 + f * T) for f in (1 / 3, 2 / 3, 1.0)]
        ttft = [(r.req.first_token_t - r.due) * 1e3 for r in recs
                if r.req.first_token_t is not None]
        itl = [(b - a) * 1e3 for r in recs
               for a, b in zip(r.times, r.times[1:])]
        drain = w.end_drain - t0 - T
        longest = max((len(r.req.tokens) for r in recs), default=0)
        per_tok = np.median(itl) / 1e3 if itl else 0.0
        line = {"rate_rps": rate, "requests": len(recs),
                "finished": sum(1 for r in recs if r.req.done),
                "outstanding_at_thirds": q, "drain_s": drain,
                "ttft_p50_ms": float(np.percentile(ttft, 50)) if ttft else None,
                "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None,
                "itl_p95_ms": float(np.percentile(itl, 95)) if itl else None,
                "be_steps": len(be.spans) - n_be,
                "compiles": w.compiles_in_window,
                "keeps_up": bool(q[2] <= q[0] + max(4, cap // 8)
                                 and drain <= longest * per_tok + 1.0)}
        print("SWEEP " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
