"""Model step, host side: device-idle milliseconds per decode step inside
the engine loop's `schedule`, `decode_step` and `bookkeep` spans (input
transfers, dispatch, the sampler readback, per-row bookkeeping, admission
decisions), over the decode steps of the traced window.  Idle time inside
`admit` is the admission stall (`itl_prefill_ms.serve`) and inside
`idle_wait` no row waits; the split by span goes to standard error."""
from bench import engine_loop

NAME = "host_gap_ms.serve"


def read(ctx, out):
    tr = out["trace"]
    if not tr.ops:
        engine_loop.say(NAME, "the trace holds no device operation")
        return None
    names = {n for n, _, _ in tr.spans}
    missing = [n for n in engine_loop.HOST_GAP_SPANS if n not in names]
    steps = engine_loop.steps_in_window(tr)
    if missing or not steps:
        engine_loop.say(NAME, "the trace has no "
                        f"{', '.join(missing) or 'decode_step'} span")
        return None
    idle = engine_loop.idle_by_loop_span(tr)
    engine_loop.say(NAME, f"idle ms per decode step over {steps} steps: " +
                    ", ".join(f"{n} {1e3 * s / steps:.3f}"
                              for n, s in idle.items()))
    return 1e3 * sum(idle[n] for n in engine_loop.HOST_GAP_SPANS) / steps
