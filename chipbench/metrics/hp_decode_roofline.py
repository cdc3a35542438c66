"""The decode program's share of its roofline, in %.

Least time of a step = max(operations / peak, bytes / HBM bandwidth), from
``flops.decode_least`` over the stats of each ``tally.serve.decode`` span
in the traced window (the program's own count of the slots it decoded);
the mean least time over the mean device time of the decode program.
"""
import flops
import phases
import trace_reduce


def read(ctx):
    n, sec = trace_reduce.program(ctx["trace"], "jit__decode_impl")
    steps = phases.decode_stats(ctx["program_spans"])
    if not n or not steps:
        return None
    pk = ctx["peaks"]
    least = [max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
             for f, b in (flops.decode_least(ctx["cfg"], s) for s in steps)]
    return 100.0 * (sum(least) / len(least)) / (sec / n)
