"""deepseek-v3-671b — MLA + MoE (1 shared + 256 routed, top-8) + MTP
[arXiv:2412.19437; huggingface.co/deepseek-ai/DeepSeek-V3 config.json].

MLA: q_lora 1536, kv_lora 512, qk_nope 128, qk_rope 64, v_head 128, with
RMSNorms on the compressed q and on c_kv; YaRN rope (theta 1e4, factor 40,
mscale 1, beta_fast 32, beta_slow 1, original length 4096), so scores carry
the mscale^2 factor (0.1 ln 40 + 1)^2.  First 3 layers dense (d_ff 18432);
layers 4..61 MoE with expert d_ff 2048.  Router: sigmoid scores with a
selection-only bias (`noaux_tc`), 8 groups of 32 experts, the best 4 groups
kept, top-8 experts among them, weights normalised over the 8 and scaled
by 2.5.  RMSNorm eps 1e-6.

Expert parallelism: the V3 report's prefill unit spreads the 256 routed
experts over 32 chips (EP32), 8 per chip.  A one-chip share of that
deployment sets `experts_held=8` (and `first_held_expert`): the router
still scores all 256, and the layer computes its 8 experts' part of the
output plus the shared expert.  Expert GEMM n-dim 2048 is lane-aligned.
"""
from .base import ModelConfig
from .registry import register

FULL = ModelConfig(
    name="deepseek-v3-671b", family="moe",
    num_layers=61, d_model=7168, num_heads=128, num_kv_heads=128,
    d_ff=18432, vocab_size=129280,
    mlp_type="swiglu",
    rope_theta=10000.0, rope_yarn_factor=40.0, rope_yarn_orig_len=4096,
    rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
    rope_yarn_mscale=1.0, rope_yarn_mscale_all_dim=1.0, norm_eps=1e-6,
    attn_type="mla", q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, head_dim=192,
    num_experts=256, num_shared_experts=1, top_k=8, moe_d_ff=2048,
    first_dense_layers=3, moe_capacity_factor=1.25,
    router_score="sigmoid", router_groups=8, router_topk_groups=4,
    routed_scale=2.5,
    mtp_depth=1,
)

SMOKE = ModelConfig(
    name="deepseek-smoke", family="moe",
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
    d_ff=128, vocab_size=256,
    mlp_type="swiglu",
    rope_theta=10000.0, rope_yarn_factor=40.0, rope_yarn_orig_len=64,
    rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
    rope_yarn_mscale=1.0, rope_yarn_mscale_all_dim=1.0, norm_eps=1e-6,
    attn_type="mla", q_lora_rank=32, kv_lora_rank=16,
    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, head_dim=24,
    num_experts=8, num_shared_experts=1, top_k=2, moe_d_ff=32,
    first_dense_layers=1,
    router_score="sigmoid", router_groups=4, router_topk_groups=2,
    routed_scale=2.5,
    mtp_depth=1, dtype="float32",
)

register(FULL, SMOKE)
