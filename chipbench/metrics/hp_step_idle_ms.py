"""Mean device-idle ms inside an engine step that decoded: the device's idle
time inside each ``tally.serve.step`` span of the traced window that holds
a ``tally.serve.decode`` span, averaged over those steps (``phases.py``).
It is the host's share of an inter-token gap: dispatch, the wait for the
tokens, the loop over slots. A program without the engine's spans gives
nothing."""
import phases
import trace_reduce


def read(ctx):
    events = ctx["events"]
    return phases.step_idle_ms(events, ctx["program_spans"],
                               trace_reduce.window_of(events))
