"""Model step, prefill buckets: share of the positions the prefill programs
ran that were padding, 1 - `engine.prefill_tokens` (the prompt suffixes
prefilled) / `engine.prefill_bucket_tokens` (the bucket widths they ran
at), from the program's counters."""
from bench import engine_loop

NAME = "prefill_pad_share.serve"


def read(ctx, out):
    got = engine_loop.counters(
        NAME, ("engine.prefill_tokens", "engine.prefill_bucket_tokens"))
    if got is None:
        return None
    ran, width = got
    if not width:
        engine_loop.say(NAME, "no prefill ran")
        return None
    return 100.0 * (1.0 - ran / width)
