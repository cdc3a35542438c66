"""Mean device time of one BE train step (the program's ``train_step``)."""
import trace_reduce


def read(ctx):
    n, sec = trace_reduce.program(ctx["trace"], "jit_train_step")
    return 1e3 * sec / n if n else None
