"""Mean device time of one HP decode step (the engine's ``_decode_impl``)."""
import trace_reduce


def read(ctx):
    n, sec = trace_reduce.program(ctx["trace"], "jit__decode_impl")
    return 1e3 * sec / n if n else None
