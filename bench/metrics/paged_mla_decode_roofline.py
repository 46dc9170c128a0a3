"""Kernels: the Pallas paged MLA decode kernel's share of its roofline.
For each decode call inside the traced window, the least time one layer's
call could take (`bench.work_mla_moe.paged_mla_decode` over the live rows'
key counts, and the chip's peaks); their mean times the kernel executions
in the trace, over the kernel's summed device time."""
from bench import trace_reduce, work, work_mla_moe

KERNEL = r"^_paged_mla"


def read(ctx, out):
    ev = trace_reduce.kernel_events(out["trace"], KERNEL)
    lo, hi = ctx.trace_window or (0.0, 0.0)
    calls = [lens for t, lens in out["window"].decode_calls if lo <= t <= hi]
    if not ev or not calls:
        return None
    least = [work.min_time_s(*work_mla_moe.paged_mla_decode(lens, ctx.config),
                             ctx.peak) for lens in calls]
    spent = sum(o.end - o.start for o in ev)
    return 100.0 * (sum(least) / len(least)) * len(ev) / spent
