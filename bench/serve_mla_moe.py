"""Serving cells of an MLA + MoE model held as one chip's share: the
program's engine, as `bench/serve.py` runs it, on a configuration file that
states the model in the source's own keys (DeepSeek-V3's `config.json`).

What differs from `bench/serve.py`:

* the program's ModelConfig is built from the source's keys
  (`program_config`): latent attention, YaRN rope, the sigmoid
  group-limited router, and the expert share (`n_routed_experts` experts
  held here of `router_experts` routed over, from `first_held_expert`);
* the weights are made from the seed in bfloat16, leaf by leaf, in the
  program's own tree (`make_params`): a float32 copy would not fit;
* the check runs `bench/reference_mla_moe.py`, a plain float32 forward of
  the same share, layer by layer.

Everything else, the window, the sample of served requests, the gaps and
the numbers, is `bench/serve.py`'s.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict

from bench import reference_mla_moe, serve, traffic, weights

# the program this module measures: an engine without these refuses the
# cell here, before any weight is made
from repro.kernels.flash_attention.ops import paged_mla_decode  # noqa: F401
from repro.models.moe import apply_moe_dropless  # noqa: F401

# source key -> the program's ModelConfig field
SOURCE_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
    "router_experts": "num_experts", "n_routed_experts": "experts_held",
    "first_held_expert": "first_held_expert",
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "top_k", "moe_intermediate_size": "moe_d_ff",
    "first_k_dense_replace": "first_dense_layers",
    "scoring_func": "router_score", "n_group": "router_groups",
    "topk_group": "router_topk_groups",
    "routed_scaling_factor": "routed_scale",
}
YARN_KEYS = {"factor": "rope_yarn_factor",
             "original_max_position_embeddings": "rope_yarn_orig_len",
             "beta_fast": "rope_yarn_beta_fast",
             "beta_slow": "rope_yarn_beta_slow",
             "mscale": "rope_yarn_mscale",
             "mscale_all_dim": "rope_yarn_mscale_all_dim"}
# what the program's block computes, as the source states it
FIXED = {"hidden_act": "silu", "topk_method": "noaux_tc",
         "norm_topk_prob": True, "moe_layer_freq": 1}


def program_config(cfg: Dict, **overrides):
    """The program's ModelConfig for a configuration file in the source's
    keys; a key whose meaning the program does not implement is refused."""
    from repro.configs.registry import get_config
    for k, want in FIXED.items():
        if cfg[k] != want:
            raise ValueError(f"{k}={cfg[k]!r}: the program implements {want!r}")
    if cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"rope_scaling {cfg['rope_scaling']['type']!r}")
    fields = {f: cfg[k] for k, f in SOURCE_KEYS.items()}
    fields.update({f: cfg["rope_scaling"][k] for k, f in YARN_KEYS.items()})
    fields.update(head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                  attn_type="mla", mlp_type="swiglu", norm_type="rmsnorm",
                  pos_emb="rotary", dtype="bfloat16", mtp_depth=0)
    return dataclasses.replace(get_config(cfg["registry"]),
                               **{**fields, **overrides})


# --- weights ------------------------------------------------------------------

OUT_PROJ = ("wo", "w_down")         # residual outputs: 1/sqrt(2 layers) more
# The router's selection bias.  A trained bias exists to balance the
# experts' loads; a wide random one skews them by the seed instead (at 0.05
# this chip's share of the routed pairs read 2.1-3.5% over seven seeds on
# a TPU v5e, moving every end-to-end number with the seed).  At 0.005 it
# still moves near-tied choices, so the check sees it applied.
BIAS_STD = 0.005


def _std(path, shape, layers: int) -> float:
    """The usual initialisation: N(0, 0.02) for the embedding, N(0,
    1/fan_in) for a projection, the residual outputs scaled down further;
    the router's selection bias N(0, BIAS_STD)."""
    name = path[-1]
    if name == "embed":
        return 0.02
    if name == "router_bias":
        return BIAS_STD
    s = 1.0 / math.sqrt(shape[-2])
    return s / math.sqrt(2 * layers) if name in OUT_PROJ else s


def make_params(pcfg, seed: int) -> Dict:
    """The program's parameter tree for `pcfg` (its own initialiser gives
    the tree and shapes), every leaf made on the device in bfloat16 from
    its own key, fold_in(key(seed), leaf index); norm scales are ones."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_lm
    tree = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), pcfg))
    key = weights._seed_key(seed)
    flat = {}
    for i, path in enumerate(weights.flat_paths(tree)):
        shape = weights.get(tree, path).shape
        if path[-1] == "scale":
            flat[path] = jnp.ones(shape, jnp.bfloat16)
            continue
        std = _std(path, shape, pcfg.num_layers)
        flat[path] = jax.jit(
            lambda k, i=i, shape=shape, std=std: (jax.random.normal(
                jax.random.fold_in(k, i), shape, jnp.float32) * std
            ).astype(jnp.bfloat16))(key)
    return weights._nest(flat)


# --- one run ----------------------------------------------------------------

def run(ctx) -> Dict:
    """ctx: `bench.run.Context`.  Returns the cell's numbers, in the shape
    `bench/serve.py` gives them."""
    import jax
    from repro import obs
    from repro.serving.engine import Engine
    from repro.serving.engine.request import Request

    cfg, e = ctx.config, ctx.config["engine"]
    pcfg = program_config(cfg)
    params = make_params(pcfg, ctx.seed)
    eng = Engine(params, pcfg, max_batch=e["max_batch"],
                 max_prompt=e["max_prompt"], max_new=e["max_new"],
                 use_paged_kernel=e["paged_kernel"],
                 prefix_cache=e["prefix_cache"],
                 num_blocks=e.get("num_blocks"))
    if ctx.tamper is not None:
        ctx.tamper(eng)
    turns = traffic.serve_requests(ctx.mix, ctx.seed, ctx.seconds,
                                   cfg["vocab_size"])
    if not turns:
        raise RuntimeError("the traffic has no request due in the window")
    for batch in serve._warm_requests(eng, turns, Request):
        done, _ = eng.run(batch)
        bad = [c.finish_reason for c in done if not c.ok]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad}")
    jax.block_until_ready(eng.pool.caches)
    reqs = [Request(rid=t.rid, tokens=t.tokens, max_new_tokens=t.max_new,
                    arrival_s=t.arrival_s) for t in turns]

    win = serve.Window([], reqs, [], {})
    if ctx.trace:
        serve._instrument(eng, win, ctx)
    ctx.mark_setup_done()
    with ctx.compile_counter() as cc:
        if ctx.trace:
            ctx.schedule_trace()
        done, stats = eng.run(reqs)
    win.compiles = cc.count
    if ctx.trace:
        obs.disable()
        ctx.stop_trace()
        for ev in obs.get_tracer().events():
            if ev.get("name") == "admit" and not ev["args"].get("resume"):
                win.admit_s.setdefault(ev["args"]["rid"], ev["dur"] * 1e-6)
    win.completions = done
    memory_peak = ctx.memory_peak()

    out = {"numbers": serve.end_to_end(win), "window": win,
           "memory_peak_bytes": memory_peak, "moe_counts": eng.moe_counts}
    sample = serve._sample(done, ctx.seed, ctx.check["sample_tokens"])
    served = {c.rid: list(c.tokens) for c in done}
    by_rid = {r.rid: r for r in reqs}
    eng_shape = (eng.policy.num_slots, eng.policy.seq_max)
    eng.pool.caches = None          # the program's state goes first
    del eng
    t_ref = time.perf_counter()
    pairs = [(by_rid[r].tokens, served[r]) for r in sample]
    ref = reference_mla_moe.Reference(cfg, params)
    ref_len = ctx.check["ref_len"]
    gaps = serve.gap_numbers(ref.token_gaps(pairs, ref_len))
    unserved = sum(1 for c in done if not c.ok
                   or len(c.tokens) != by_rid[c.rid].max_new_tokens)
    limit = ctx.check["mean_logit_gap"]
    out["checks"] = {
        "mean_logit_gap": (gaps["mean"], limit),
        "unserved_requests": (unserved, 0),
    }
    if ctx.control:                 # the reference serves every request
        low = serve.gap_numbers(ref.token_gaps(pairs, ref_len,
                                               quant=ctx.control))
        out["control"] = {"mean_logit_gap": (low["mean"], limit),
                          "unserved_requests": (0, 0)}
    mem = ctx.devices[0].memory_stats() or {}
    out["info"] = {"decode_steps": stats.decode_steps,
                   "prefills": stats.prefills,
                   "preemptions": stats.preemptions,
                   "finish_reasons": stats.finish_reasons,
                   "slots": eng_shape[0], "seq_max": eng_shape[1],
                   "bytes_limit": mem.get("bytes_limit"),
                   "memory_peak_bytes": memory_peak,
                   "prompt_tokens": stats.prompt_tokens,
                   "cached_tokens": stats.cached_tokens,
                   "moe_counts": out["moe_counts"],
                   "ttft_p95_ms": serve.ttft_p95_ms(win),
                   "itl_p95_ms": serve.itl_p95_ms(win),
                   "last_done_s": max(c.done_s for c in done),
                   "widest_logit_gap": gaps["widest"],
                   "flip_share": gaps["flips"],
                   "check_s": time.perf_counter() - t_ref}
    if ctx.control:
        out["info"]["control_gaps"] = low
    out["compiles"] = win.compiles
    out["attempted"] = len(reqs)
    out["failed"] = sum(1 for c in done if not c.ok)
    out["sample"] = {"requests": len(sample),
                     "tokens": sum(len(served[r]) for r in sample)}
    return out
