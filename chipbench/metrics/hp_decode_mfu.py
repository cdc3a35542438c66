"""The whole HP decode step's share of the chip's bf16 peak, in %:
2 x matmul parameters x active slots per step (the ``active`` stat of each
``tally.serve.decode`` span in the traced window), over device time x
peak."""
import flops
import phases
import trace_reduce


def read(ctx):
    n, sec = trace_reduce.program(ctx["trace"], "jit__decode_impl")
    steps = phases.decode_stats(ctx["program_spans"])
    if not n or not steps:
        return None
    work = 2.0 * flops.matmul_params(ctx["cfg"]) * sum(
        s["active"] for s in steps) / len(steps)
    return 100.0 * work / ((sec / n) * ctx["peaks"]["flops_bf16"])
