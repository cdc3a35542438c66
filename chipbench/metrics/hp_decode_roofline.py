"""The decode program's share of its roofline, in %.

Least time of a step = max(operations / peak, bytes / HBM bandwidth), from
``flops.decode_least`` over the slots each step served (host records);
the mean least time over the mean device time of the decode program.
"""
import flops
import trace_reduce


def read(ctx):
    n, sec = trace_reduce.program(ctx["trace"], "jit__decode_impl")
    calls = ctx["decodes"]
    if not n or not calls:
        return None
    pk = ctx["peaks"]
    least = [max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
             for f, b in (flops.decode_least(ctx["cfg"], c.active, c.kv_tokens)
                          for c in calls)]
    return 100.0 * (sum(least) / len(least)) / (sec / n)
