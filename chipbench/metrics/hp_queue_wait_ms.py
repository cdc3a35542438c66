"""Mean wait of a request in the engine's queue, in ms: from its submission
to the engine picking it for prefill (``Request.queue_wait``, the engine's
own stamps), over the requests due in the window that were admitted. A
program that does not stamp its requests gives nothing."""


def read(ctx):
    waits = [getattr(r.req, "queue_wait", None)
             for r in ctx["window"].due_in_window()]
    waits = [w for w in waits if w is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
