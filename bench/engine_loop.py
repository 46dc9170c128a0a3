"""The serving engine's loop as the program's own spans and counters show
it, for the chat cell's per-layer readers.

Three sources, all from a traced run:

* the profiler trace (`out["trace"]`): the loop's top-level spans
  (`LOOP_SPANS`) on the device's clock, so device-idle time can be laid
  against what the host was doing;
* the program's span buffer for the whole window (`repro.obs` tracer): one
  event per span with its args (`rid`, `step`, `live`, ...), in
  microseconds;
* the program's counters (`repro.obs` metrics), counted while obs is on.

`Engine.run`'s loop spends every moment inside one top-level span:
`schedule`, one `admit` per admission, then either `idle_wait` (no row is
live) or `decode_step` followed by its sibling `bookkeep`.  A program
without a span or counter a reader needs gives nothing to read: the helpers
return None and say why on standard error.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence

from bench import trace_reduce

# the loop spans whose device-idle time is the host's share of a decode
# step; `admit` is the admission stall, read whole by itl_prefill_ms.serve
HOST_GAP_SPANS = ("schedule", "decode_step", "bookkeep")
LOOP_SPANS = HOST_GAP_SPANS + ("admit", "idle_wait")
OUTSIDE = "(no loop span)"


def say(metric: str, why: str) -> None:
    print(f"bench: {metric}: {why}", file=sys.stderr, flush=True)


def span_events(metric: str) -> Optional[List[dict]]:
    """The program's buffered spans ("X" events), or None where the buffer
    dropped any (the window would be read in part)."""
    from repro import obs
    tracer = obs.get_tracer()
    if tracer.dropped:
        say(metric, f"the program's span buffer dropped {tracer.dropped} "
                    f"events")
        return None
    return [e for e in tracer.events() if e.get("ph") == "X"]


def counters(metric: str, names: Sequence[str]) -> Optional[List[float]]:
    """The values of the program's counters `names`, or None where the
    program has not counted one of them."""
    from repro import obs
    have = obs.get_metrics().snapshot()["counters"]
    missing = [n for n in names if n not in have]
    if missing:
        say(metric, f"the program counted no {', '.join(missing)}")
        return None
    return [have[n] for n in names]


def idle_by_loop_span(tr: trace_reduce.Trace) -> Dict[str, float]:
    """Device-idle seconds of the traced window inside each loop span, by
    name, and `OUTSIDE` for idle time that no loop span covers."""
    gaps = trace_reduce.idle_gaps(tr)
    out = {name: trace_reduce._overlap(gaps, trace_reduce.union(
        [(s, e) for n, s, e in tr.spans if n == name])) for name in LOOP_SPANS}
    out[OUTSIDE] = sum(e - s for s, e in gaps) - sum(out.values())
    return out


def steps_in_window(tr: trace_reduce.Trace) -> int:
    """Decode steps whose span starts inside the traced window."""
    lo, hi = tr.window
    return sum(1 for n, s, _ in tr.spans if n == "decode_step" and lo <= s < hi)


def admission_stall_ms(events: List[dict]) -> Optional[float]:
    """Milliseconds that admissions add to the mean gap between tokens.

    Admissions run between decode steps.  Each `admit` span before step k
    (and after step k-1) stretches the gap of every row of step k that
    already had a token when it began: the step's `live` rows less those
    admitted at or after it in the same interval.  The stretch is summed
    over rows and divided by the rows of all steps, which is the number of
    gaps.  None where no decode step was recorded."""
    pending: List[float] = []        # admit durations (us) since the last step
    stall_us = rows = 0.0
    for e in sorted(events, key=lambda e: e["ts"]):
        if e["name"] == "admit":
            pending.append(e["dur"])
        elif e["name"] == "decode_step":
            live, n = e["args"]["live"], len(pending)
            stall_us += sum(d * max(live - n + j, 0)
                            for j, d in enumerate(pending))
            rows += live
            pending = []
    return 1e-3 * stall_us / rows if rows else None
