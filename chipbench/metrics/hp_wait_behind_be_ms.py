"""Mean wait of a request behind the BE step in flight when it fell due.

Per request due in the window: the time from its due time to the end of
the BE step (a benchmark span around the engine's hook) that was running
then, or 0 if none was. Host clock.
"""


def read(ctx):
    recs = ctx["window"].due_in_window()
    if not recs:
        return None
    waits = []
    for r in recs:
        w = 0.0
        for s, e in ctx["be_spans"]:
            if s <= r.due < e:
                w = e - r.due
                break
        waits.append(w)
    return 1e3 * sum(waits) / len(waits)
