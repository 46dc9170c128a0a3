"""Model step, decode batch: share of the decode program's rows that held a
live request, `engine.decode_rows_live` / (`engine.decode_steps` x the
engine's slots), from the program's counters."""
from bench import engine_loop

NAME = "decode_row_share.serve"


def read(ctx, out):
    got = engine_loop.counters(
        NAME, ("engine.decode_rows_live", "engine.decode_steps"))
    if got is None:
        return None
    rows, steps = got
    if not steps:
        engine_loop.say(NAME, "no decode step ran")
        return None
    return 100.0 * rows / (steps * out["info"]["slots"])
