"""Operations and bytes an MLA + MoE share needs, computed from shapes, in
the manner of `bench/work.py` (what the algorithm requires, not what a
kernel or compiler emits; a product of (m, k) by (k, n) is 2mkn
operations).  `c` is a configuration file in the source's keys
(`bench/configs/deepseek-v3-671b.ep32.json`).
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Tuple

BF16 = 2
PAGE = 16                   # tokens per page of the engine's latent pool


def _dims(c: Dict):
    return (c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_rope_head_dim"], c["qk_nope_head_dim"], c["v_head_dim"])


# --- kernels ----------------------------------------------------------------

def paged_mla_decode(lengths: Iterable[int], c: Dict,
                     itemsize: int = BF16) -> Tuple[float, float]:
    """One layer of absorbed one-token MLA decode over the live rows;
    `lengths` are each live row's key count, the new token included.  Per
    (head, key): the score over kv_lora + rope and p . c_kv over kv_lora,
    2 (kv_lora + rope + kv_lora) operations.  Bytes: every live page of
    kv_lora + rope values once, each row's queries and outputs once."""
    a, r, rd, _, _ = _dims(c)
    live = [int(n) for n in lengths if n > 0]
    keys = sum(live)
    pages = sum(math.ceil(n / PAGE) for n in live)
    flops = 2.0 * a * (r + rd + r) * keys
    nbytes = itemsize * (pages * PAGE * (r + rd) + len(live) * a * (r + rd + r))
    return flops, nbytes


def moe_gmm(rows: float, d_in: int, d_out: int, experts: int,
            itemsize: int = BF16) -> Tuple[float, float]:
    """One grouped product of the held experts: `rows` routed rows, each
    through its expert's (d_in, d_out) matrix; the matrix of each of
    `experts` experts that got a row read once, the rows read and written
    once."""
    flops = 2.0 * rows * d_in * d_out
    nbytes = itemsize * (experts * d_in * d_out + rows * (d_in + d_out))
    return flops, nbytes


def moe_gmm_layer(rows: float, active: float, c: Dict, peak: Dict) -> float:
    """Least time of one MoE layer's three grouped products (gate, up,
    down) with `rows` routed held rows over `active` held experts (those
    with a row: only their weights need reading)."""
    from bench.work import min_time_s
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return 2 * min_time_s(*moe_gmm(rows, d, f, active), peak) + \
        min_time_s(*moe_gmm(rows, f, d, active), peak)


# --- whole step ---------------------------------------------------------------

def token_flops(c: Dict) -> float:
    """Operations of one token outside attention over keys and outside the
    routed experts: the MLA projections of every layer, the dense layers'
    MLPs, the MoE layers' shared expert and router, the output head."""
    a, r, rd, nope, vd = _dims(c)
    d, L = c["hidden_size"], c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    mla = d * c["q_lora_rank"] + c["q_lora_rank"] * a * (nope + rd) \
        + d * (r + rd) + a * vd * d
    mlp = 3 * d * c["intermediate_size"]
    moe = 3 * d * c["moe_intermediate_size"] * c["n_shared_experts"] \
        + d * c["router_experts"]
    return 2.0 * (L * mla + dense * mlp + (L - dense) * moe
                  + d * c["vocab_size"])


def prefill_flops(c: Dict, prompt_len: int, cached: int) -> float:
    """Prompt positions cached..prompt_len-1 in the decompressed form:
    each new key's k_nope and v from c_kv once, then per (query, key)
    pair a score over nope + rope and a value of v_head; position p
    attends p + 1 keys."""
    a, r, rd, nope, vd = _dims(c)
    L, new = c["num_hidden_layers"], prompt_len - cached
    pairs = (prompt_len * (prompt_len + 1) - cached * (cached + 1)) / 2.0
    return new * token_flops(c) + 2.0 * L * (
        new * r * a * (nope + vd) + pairs * a * (nope + rd + vd))


def decode_flops(c: Dict, keys: int) -> float:
    """One decode token attending `keys` keys in the absorbed form: q_nope
    into the latent (W_uk), the scores and p . c_kv, out of it (W_uv)."""
    a, r, rd, nope, vd = _dims(c)
    L = c["num_hidden_layers"]
    return token_flops(c) + 2.0 * L * (a * nope * r + a * r * vd
                                       + keys * a * (r + rd + r))


def served_flops(c: Dict, prompt_len: int, cached: int,
                 generated: int) -> float:
    """A served request without its routed experts' work: the prefill of
    its uncached prompt, then a decode step for each generated token after
    the first."""
    return prefill_flops(c, prompt_len, cached) + sum(
        decode_flops(c, prompt_len + j + 1) for j in range(max(generated - 1, 0)))


def routed_flops(c: Dict, held_rows: float) -> float:
    """The held experts' work for `held_rows` routed (token, expert) rows."""
    return 6.0 * c["hidden_size"] * c["moe_intermediate_size"] * held_rows
