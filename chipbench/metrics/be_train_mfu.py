"""The whole BE step's share of the chip's bf16 peak, in %: 6 x matmul
parameters x tokens per step (recomputation not counted), over the step's
device time x peak."""
import flops
import trace_reduce


def read(ctx):
    n, sec = trace_reduce.program(ctx["trace"], "jit_train_step")
    if not n:
        return None
    job = ctx["job"]
    work = flops.train_flops(ctx["be_cfg"], job["batch"] * job["seq_len"])
    return 100.0 * work / ((sec / n) * ctx["peaks"]["flops_bf16"])
