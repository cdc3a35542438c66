"""Mean device time of one HP prefill call (the engine's ``jit_prefill``)."""
import trace_reduce


def read(ctx):
    n, sec = trace_reduce.program(ctx["trace"], "jit_prefill")
    return 1e3 * sec / n if n else None
