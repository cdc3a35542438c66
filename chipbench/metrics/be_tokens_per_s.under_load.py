"""BE training tokens per second of the steps that finished in the window.

In a cell whose engine is rarely idle this counts the BE steps that slipped
in under load; each one holds back the requests that fall due meanwhile.
"""


def read(ctx):
    w = ctx["window"]
    lo, hi = w.start, w.start + w.seconds
    steps = sum(1 for s, e in ctx["be_spans"] if lo <= e <= hi)
    return steps * ctx["job"]["batch"] * ctx["job"]["seq_len"] / w.seconds
