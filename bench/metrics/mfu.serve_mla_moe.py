"""Whole step: operations the window's served requests needed, as a share
of the chip's bf16 peak over the time from the first due time to the last
completion.  Per request, its uncached prompt in the decompressed form and
its decode steps in the absorbed form (`bench.work_mla_moe.served_flops`);
the held experts' work from the program's routed rows (`moe.routed_held`
and `moe.prefill_routed_held`, `routed_flops`)."""
from bench import engine_loop, work_mla_moe

NAME = "mfu.serve_mla_moe"


def read(ctx, out):
    win = out["window"]
    done = [x for x in win.completions if x.ok]
    got = engine_loop.counters(NAME, ("moe.routed_held",
                                      "moe.prefill_routed_held"))
    if not done or got is None:
        return None
    c = ctx.config
    flops = sum(work_mla_moe.served_flops(c, x.prompt_len, x.cached_tokens,
                                          len(x.tokens)) for x in done)
    flops += work_mla_moe.routed_flops(c, sum(got))
    span = max(x.done_s for x in win.completions) - \
        min(r.arrival_s for r in win.requests)
    return 100.0 * flops / span / (ctx.peak["bf16_flops"] * len(ctx.devices))
