"""Mean host time of one admission, in ms: from the engine picking a request
for prefill to its first token on the host (``Request.admit_t`` to
``first_token_t``, the engine's own stamps: the prefill, the write into the
slot, the first token's argmax and its transfer), over the requests due in
the window. Beside ``hp_prefill_ms`` it gives what an admission costs
beyond the prefill program. A program that does not stamp its requests
gives nothing."""


def read(ctx):
    spans = [r.req.first_token_t - r.req.admit_t
             for r in ctx["window"].due_in_window()
             if getattr(r.req, "admit_t", None) is not None
             and r.req.first_token_t is not None]
    return 1e3 * sum(spans) / len(spans) if spans else None
