"""Readings that the output check's limits are set from, in one process.

    python3 chipbench/limits.py --workload <cell> --seeds 1,2,3 \
        [--seconds 8] [--train 1]

For each seed: the set-up as a run makes it (both tenants, the BE
trainer's first three steps), a short window at the cell's own load, a
drain, and then, with the program's state freed, the reference. Each seed
prints one JSON line with the program's numbers (as a run compares them)
and, beside them, the readings the limits must separate them from:

- ``control.*``: the plain reference computed with fp8 (e4m3) operands in
  every matmul, put in the program's place (served: the gap of the token
  the fp8 reference puts first; training: its three steps against the
  float32 reference's);
- ``fault_token.*``: the served tokens with the last one of each request
  altered;
- ``fault_half_batch.*``: reference steps that leave out half of each
  batch and take the mean over the rest.

``--train 0`` leaves out the training readings (they depend on the BE job
alone, which the cells share). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--train", type=int, choices=(0, 1), default=1)
    ap.add_argument("--root", default=None)
    args = ap.parse_args(argv)

    from spec import Bench
    bench = Bench(args.root)
    cell = run.cell_files(bench, args.workload)
    devices, compile_count = run.start_jax(bench, args.workload, require_tpu)
    if devices is None:
        return 2
    import check
    import driver
    import generator

    for s in args.seeds.split(","):
        seed = int(s) % (1 << 64)
        arrivals = generator.generate(cell["mix"], cell["wl"]["rate_rps"],
                                      args.seconds)
        prompts = run.prompts_for(cell, arrivals, seed)
        be, hp, prog_train = run.build(cell, seed)
        w = driver.run_window(hp.engine, arrivals, prompts, args.seconds,
                              run.DRAIN_S, compile_count)
        recs = w.due_in_window()
        samples = check.sample_requests(recs, seed)
        del hp, be
        gc.collect()
        out = run.check_outputs(cell, seed, samples,
                                prog_train if args.train else None,
                                control=True)
        line = {"seed": seed, "requests": len(recs),
                "finished": sum(1 for r in recs if r.req.done),
                "compared_tokens": int(sum(len(t) for _, t in samples)),
                **out}
        print("LIMITS " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
