"""The whole HP decode step's share of the chip's bf16 peak, in %:
2 x matmul parameters x active slots per step, over device time x peak."""
import flops
import trace_reduce


def read(ctx):
    n, sec = trace_reduce.program(ctx["trace"], "jit__decode_impl")
    calls = ctx["decodes"]
    if not n or not calls:
        return None
    work = 2.0 * flops.matmul_params(ctx["cfg"]) * sum(
        c.active for c in calls) / len(calls)
    return 100.0 * work / ((sec / n) * ctx["peaks"]["flops_bf16"])
