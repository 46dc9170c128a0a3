"""A tiny version of the MLA + MoE cell for runs of the whole harness on the
CPU: the same configuration file, serving module, reference and checks, at
widths a test can hold (an expert share of 4 of 16 experts, from expert 4),
with the look for a chip skipped."""
import json

from bench import run as R

CELL = "reasoning.deepseek-v3-671b.ep32"


def config(c, mean_logit_gap=0.003):
    c = json.loads(json.dumps(c))
    c.update(num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
             num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
             router_experts=16, n_routed_experts=4, first_held_expert=4,
             n_group=4, topk_group=2, num_experts_per_tok=4, vocab_size=512)
    c["rope_scaling"]["original_max_position_embeddings"] = 64
    c["engine"].update(max_prompt=128, max_new=32, max_batch=8,
                       num_blocks=80)
    c["check"].update(ref_len=160, sample_tokens=64,
                      mean_logit_gap=mean_logit_gap)
    return c


def mix(m):
    m = json.loads(json.dumps(m))
    m["user"].update(min=8, max=64, median=32)
    m["output"].update(min=2, max=24, median=8)
    m["arrivals"]["rate"] = 4.0
    return m


def run(seed=7, seconds=2.0, **kw):
    args = R.parse(["--workload", CELL, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", "0"])
    kw.setdefault("config_override", config)
    return R.run(args, require_chip=False, mix_override=mix, **kw)
