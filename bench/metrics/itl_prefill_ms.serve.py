"""Scheduler: milliseconds that admissions add to the mean gap between
output tokens.  From the program's spans of the whole window: each `admit`
span between two decode steps, weighted by the rows of the later step that
already had a token when it began, summed and divided by the rows of all
steps (`engine_loop.admission_stall_ms`)."""
from bench import engine_loop

NAME = "itl_prefill_ms.serve"


def read(ctx, out):
    events = engine_loop.span_events(NAME)
    if events is None:
        return None
    ms = engine_loop.admission_stall_ms(events)
    if ms is None:
        engine_loop.say(NAME, "the program recorded no decode_step span")
    return ms
