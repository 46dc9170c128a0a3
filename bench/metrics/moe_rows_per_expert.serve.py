"""MoE dispatch: mean rows each held expert got in a decode step, the
program's `moe.routed_held` over `engine.decode_steps` x MoE layers x held
experts.  The EP32 deployment gathers about 64 rows per expert from its 32
chips; one chip's own rows give it a few."""
from bench import engine_loop

NAME = "moe_rows_per_expert.serve"


def read(ctx, out):
    got = engine_loop.counters(NAME, ("moe.routed_held", "engine.decode_steps"))
    if got is None:
        return None
    held, steps = got
    if not steps:
        engine_loop.say(NAME, "no decode step ran")
        return None
    c = ctx.config
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return held / (steps * layers * c["n_routed_experts"])
