"""Plain reference of a DeepSeek-V3-style share (latent attention, YaRN
rope, sigmoid group-limited routing over all experts, the held experts'
part of the MoE output plus the shared expert), in float32.

Straightforward `jax.numpy`, importing nothing of the program under test;
every matrix product runs at `Precision.HIGHEST`.  It follows the
configuration file in the source's keys (`bench/configs/*.json`) and
arXiv:2412.19437 section 2.1:

    x = embed[tokens] * sqrt(d)
    per layer:  h = n1(x)
                c_q = n_q(h Wq_down);  q = c_q Wq_up      per head: nope ++ rope
                c_kv = n_kv(h Wkv_down[:, :r]);  k_rope = (h Wkv_down)[:, r:]
                k = c_kv Wk_up ++ k_rope (shared by the heads);  v = c_kv Wv_up
                x += Wo . softmax(mscale^2 q.k / sqrt(d_nope + d_rope)) v
                x += mlp(n2(x))                       the first dense layers
                x += sum over held experts e of g_e(x) mlp_e(n2(x))
                     + shared(n2(x))                  the MoE layers
    logits = n_f(x) . lm_head

with causal attention, rope on the two halves of the rope dims at YaRN's
frequencies (cos and sin times mscale / mscale_all_dim), RMSNorm at the
file's eps.  Routing: sigmoid scores s over all `router_experts`; a group's
score is the sum of its two best s + bias; the best `topk_group` groups
stay; the `num_experts_per_tok` best s + bias among them are chosen; their
weights are s normalised over the chosen and times `routed_scaling_factor`.
g_e is that weight where e was chosen, else 0; the experts not held here
add nothing.

It works one layer at a time (each layer's bfloat16 weights upcast inside
its own program), attention a chunk of queries at a time and the output
head a slice of the vocabulary at a time, so that it fits beside the
bfloat16 weights alone.  `quant="fp8"` (or `"int8"`) is the control of
`bench/reference.py`: every projection takes its activations per row and
its weights per output column rounded to the step below bf16.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, NEG, mm

Q_CHUNK = 128           # queries per attention chunk
V_CHUNK = 16384         # vocabulary columns per slice of the head


def rms(p, x, eps: float):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"]


def yarn(c: Dict):
    """(inverse frequencies, cos/sin magnitude, softmax factor) of the
    file's rope_scaling, as DeepSeek-V3's modelling code computes them."""
    dim, base = c["qk_rope_head_dim"], c["rope_theta"]
    y = c["rope_scaling"]

    def mscale(f, ms):
        return 1.0 if f <= 1 else 0.1 * ms * math.log(f) + 1.0

    def corr(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    inv = extra / y["factor"] * ramp + extra * (1 - ramp)
    mag = mscale(y["factor"], y["mscale"]) / mscale(y["factor"],
                                                    y["mscale_all_dim"])
    return inv, mag, mscale(y["factor"], y["mscale_all_dim"]) ** 2


def rope(x, inv, mag):
    """x: (s, heads, dims), positions 0..s-1, the two halves as pairs."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv, jnp.float32)
    sin, cos = jnp.sin(ang)[:, None] * mag, jnp.cos(ang)[:, None] * mag
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(c: Dict, p: Dict, h, quant: Optional[str]):
    """Latent attention of one layer over a causal row.  h: (s, d)."""
    s = h.shape[0]
    a, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rd = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    eps = c["rms_norm_eps"]
    inv, mag, factor = yarn(c)
    q = mm(rms(p["q_norm"], mm(h, p["wq_down"], quant), eps), p["wq_up"],
           quant).reshape(s, a, nope + rd)
    qn, qr = q[..., :nope], rope(q[..., nope:], inv, mag)
    lat = mm(h, p["wkv_down"], quant)
    ckv = rms(p["kv_norm"], lat[:, :r], eps)
    kr = rope(lat[:, None, r:], inv, mag)[:, 0]
    kn = mm(ckv, p["wk_up"], quant).reshape(s, a, nope)
    v = mm(ckv, p["wv_up"], quant).reshape(s, a, -1)
    scale = factor / math.sqrt(nope + rd)
    qc = math.gcd(s, Q_CHUNK)

    def chunk(args):
        qn_c, qr_c, pos = args
        sc = (jnp.einsum("qan,kan->aqk", qn_c, kn, precision=HIGHEST)
              + jnp.einsum("qar,kr->aqk", qr_c, kr, precision=HIGHEST)) * scale
        sc = jnp.where(jnp.arange(s)[None, :] <= pos[:, None], sc, NEG)
        return jnp.einsum("aqk,kav->qav", jax.nn.softmax(sc, -1), v,
                          precision=HIGHEST)

    o = jax.lax.map(chunk, (qn.reshape(s // qc, qc, a, nope),
                            qr.reshape(s // qc, qc, a, rd),
                            jnp.arange(s).reshape(s // qc, qc)))
    return mm(o.reshape(s, -1), p["wo"], quant)


def swiglu(p: Dict, h, quant: Optional[str]):
    return mm(jax.nn.silu(mm(h, p["w_gate"], quant)) * mm(h, p["w_up"], quant),
              p["w_down"], quant)


def route(c: Dict, p: Dict, h, quant: Optional[str]):
    """(t, router_experts) weights: the chosen experts' normalised, scaled
    sigmoid scores, 0 elsewhere."""
    e, k = c["router_experts"], c["num_experts_per_tok"]
    g, kg = c["n_group"], c["topk_group"]
    s = jax.nn.sigmoid(mm(h, p["router"], quant))
    sel = s + p["router_bias"]
    t = s.shape[0]
    best2 = jnp.sort(sel.reshape(t, g, e // g), -1)[..., -2:].sum(-1)
    gkeep = jnp.argsort(-best2, -1)[:, :kg]
    keep = (jnp.arange(g)[None, None, :] == gkeep[..., None]).any(1)
    sel = jnp.where(jnp.repeat(keep, e // g, axis=1), sel, -jnp.inf)
    chosen = jnp.argsort(-sel, -1)[:, :k]
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / w.sum(-1, keepdims=True) * c["routed_scaling_factor"]
    return jnp.zeros((t, e)).at[jnp.arange(t)[:, None], chosen].set(w)


def moe(c: Dict, p: Dict, h, quant: Optional[str]):
    gates = route(c, p, h, quant)
    first = c["first_held_expert"]
    y = swiglu(p["shared"], h, quant)
    for j in range(c["n_routed_experts"]):
        ex = {n: p[n][j] for n in ("w_gate", "w_up", "w_down")}
        y = y + gates[:, first + j:first + j + 1] * swiglu(ex, h, quant)
    return y


def layer(c: Dict, p: Dict, x, dense: bool, quant: Optional[str]):
    eps = c["rms_norm_eps"]
    x = x + mla(c, p["attn"], rms(p["norm1"], x, eps), quant)
    h = rms(p["norm2"], x, eps)
    return x + (swiglu(p["mlp"], h, quant) if dense else
                moe(c, p["moe"], h, quant))


class Reference:
    """The plain forward of the configuration `c` (the source's keys) over
    the program-layout weights `params` (bfloat16 in, float32 inside)."""

    def __init__(self, c: Dict, params: Dict):
        self.c, self.params = c, params
        self._jit: Dict = {}

    def _fn(self, name, fn):
        if name not in self._jit:
            self._jit[name] = jax.jit(fn)
        return self._jit[name]

    def hidden(self, row, quant: Optional[str] = None):
        """Final-normed hidden states (s, d) float32 of one token row."""
        c, p = self.c, self.params
        x = self._fn("embed", lambda e, t: e[t].astype(jnp.float32)
                     * math.sqrt(c["hidden_size"]))(p["embed"], row)
        for seg, dense in (("seg0", True), ("seg1", False)):
            n = jax.tree.leaves(p[seg])[0].shape[0]
            fn = self._fn((seg, quant), lambda st, i, x, dense=dense: layer(
                c, jax.tree.map(lambda a: a[i].astype(jnp.float32), st), x,
                dense, quant))
            for i in range(n):
                x = fn(p[seg], i, x)
        return self._fn("norm", lambda fn, x: rms(
            jax.tree.map(lambda a: a.astype(jnp.float32), fn), x,
            c["rms_norm_eps"]))(p["final_norm"], x)

    def scan_head(self, x, picks=None, quant: Optional[str] = None):
        """Over the vocabulary, a slice at a time: each row's best logit,
        its logit at `picks` (when given) and its argmax."""
        c, head = self.c, self.params["lm_head"]
        vp = head.shape[1]
        best = np.full(x.shape[0], -np.inf)
        arg = np.zeros(x.shape[0], np.int64)
        at = np.zeros(x.shape[0])
        for c0 in range(0, vp, V_CHUNK):
            w = min(V_CHUNK, vp - c0)
            lg = self._fn(("head", w, quant), lambda h, x, c0, w=w: jnp.where(
                c0 + jnp.arange(w) < c["vocab_size"],
                mm(x, jax.lax.dynamic_slice_in_dim(h, c0, w, 1), quant),
                NEG))(head, x, c0)
            lg = np.asarray(lg, np.float64)
            m = lg.max(-1)
            arg = np.where(m > best, c0 + lg.argmax(-1), arg)
            best = np.maximum(best, m)
            if picks is not None:
                inside = (picks >= c0) & (picks < c0 + w)
                at[inside] = lg[inside, picks[inside] - c0]
        return best, at, arg

    def token_gaps(self, pairs, ref_len: int,
                   quant: Optional[str] = None) -> List[np.ndarray]:
        """For each (prompt, served tokens): at each served position, the
        reference's best logit minus its logit at the token served; with
        `quant`, at the token the lower-precision reference puts first
        instead.  Rows are padded to `ref_len` (causal: the pad changes
        nothing before it) so one program serves all."""
        out = []
        for prompt, toks in pairs:
            p, g = len(prompt), len(toks)
            seq = np.concatenate([np.asarray(prompt, np.int32),
                                  np.asarray(toks[:-1], np.int32)])
            if len(seq) > ref_len:
                raise ValueError(f"request of {len(seq)} tokens > ref_len "
                                 f"{ref_len}")
            row = jnp.asarray(np.pad(seq, (0, ref_len - len(seq))))
            x = self.hidden(row)[p - 1:p - 1 + g]
            if quant:
                xl = self.hidden(row, quant)[p - 1:p - 1 + g]
                pick = self.scan_head(xl, quant=quant)[2]
            else:
                pick = np.asarray(toks)
            best, at, _ = self.scan_head(x, pick)
            out.append(best - at)
        return out
