"""Kernels: the grouped expert product's share of its roofline inside the
decode program (`jit_decode`).  A decode execution's least time is, for
each MoE layer, its three grouped products (`bench.work_mla_moe.
moe_gmm_layer`) at the mean routed held rows and the mean held experts that
got any row, per layer and step (the program's `moe.routed_held` and
`moe.held_active` over `engine.decode_steps` and the MoE layers: an expert
with no row needs no weight read); times the whole executions in the
trace, over the `_moe_gmm` kernels' device time inside them."""
from bench import engine_loop, metrics_common, work_mla_moe

NAME = "moe_gmm_roofline"


def read(ctx, out):
    got = engine_loop.counters(NAME, ("moe.routed_held", "moe.held_active",
                                      "engine.decode_steps"))
    if got is None or not got[2]:
        return None
    c = ctx.config
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    per_call = got[2] * layers
    least = layers * work_mla_moe.moe_gmm_layer(
        got[0] / per_call, got[1] / per_call, c, ctx.peak)
    return metrics_common.kernel_share(out["trace"], r"^_moe_gmm",
                                       "jit_decode", least)
