"""Sharding rules: map every parameter / batch / cache leaf to a PartitionSpec.

Mesh axes:
  pod    — outer axis across pods (pure DP by default; PP optional)
  data   — within-pod data parallelism + FSDP (ZeRO-3 parameter sharding)
  model  — tensor parallelism (Megatron column/row pairs), expert parallelism,
           vocab sharding, and sequence sharding of decode KV caches

Rules implement the paper's parallel shape constraints: h/t, d_ff/t, a/t,
v/t, experts/t divisibility (checked by core.advisor.check_alignment before
lowering).  Parameters carry one dim sharded on `model` (TP) and one on
`data` (FSDP); XLA SPMD inserts the per-layer all-gathers inside the scan.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import MeshConfig, ModelConfig


# --- activation partitioning context -------------------------------------------------
# Models are mesh-agnostic; the launcher installs the axis names here and
# model code anchors activations via `constrain` (no-op when unset, e.g. in
# single-device CPU tests).  One anchor at the embedding output is what stops
# the SPMD partitioner from replicating the whole forward pass dp-fold.

_ACT_CTX: dict = {"dp": None, "tp": None, "mesh": None}


def set_activation_context(dp_axes, tp_axis="model", mesh=None):
    _ACT_CTX["dp"] = tuple(dp_axes) if dp_axes else None
    _ACT_CTX["tp"] = tp_axis
    _ACT_CTX["mesh"] = mesh


def clear_activation_context():
    _ACT_CTX["dp"] = None
    _ACT_CTX["tp"] = None
    _ACT_CTX["mesh"] = None


def activation_context():
    return dict(_ACT_CTX)


def constrain(x, kind: str):
    """Anchor an activation layout (no-op outside a mesh context).

    kinds:
      btd    (batch, seq, dim)           — residual stream
      btv    (batch, seq, vocab)         — logits, vocab TP-sharded
      bd     (batch, dim)
      bskh   (batch, seq, kv, hd)        — decode K/V: SEQUENCE over model
      bkgqs  (batch, kv, g, q, seq)      — decode scores: seq over model
                                           (distributed flash-decode softmax)
      bsr    (batch, seq, rank)          — MLA latent cache: seq over model
    """
    dp, tp = _ACT_CTX["dp"], _ACT_CTX["tp"]
    if dp is None:
        return x
    if tp in dp:  # pure-DP mode: the model axis is data-parallel
        tp = None
    spec = {"btd": P(dp, None, None),
            "btd_sp": P(dp, tp, None),  # sequence parallelism
            "btv": P(dp, None, tp),
            "bd": P(dp, None),
            "td": P(dp, None),          # flat token-major (MoE dispatch)
            "eh": P(tp, None),          # flat (expert*capacity, h) buffers
            "bskh": P(dp, tp, None, None),
            "bkgqs": P(dp, None, None, None, tp),
            "bsr": P(dp, tp, None)}[kind]
    # skip when the batch dim doesn't divide the dp axes (long_500k b=1)
    import numpy as _np
    mesh_size = 1
    try:
        from jax.sharding import get_abstract_mesh
        am = get_abstract_mesh()
        if am is not None and am.shape:
            mesh_size = int(_np.prod([am.shape.get(a, 1) for a in dp]))
    except Exception:
        pass
    if mesh_size > 1 and x.shape[0] % mesh_size:
        spec = P(*((None,) + tuple(spec)[1:]))
    return jax.lax.with_sharding_constraint(x, spec)


def auto_mesh(shape, axes) -> Mesh:
    """`jax.make_mesh` with every axis of type Auto: the partitioner
    propagates shardings from the parameter/batch specs and the `constrain`
    anchors, which is the model this module is written for (explicit axes
    would demand an out_sharding at every gather)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(mesh_cfg: MeshConfig) -> Mesh:
    if mesh_cfg.pod > 1:
        return auto_mesh((mesh_cfg.pod, mesh_cfg.data, mesh_cfg.model),
                         ("pod", "data", "model"))
    return auto_mesh((mesh_cfg.data, mesh_cfg.model), ("data", "model"))


def _axes(mesh: Mesh):
    return set(mesh.axis_names)


# Base (unstacked) PartitionSpecs by leaf name.  Leading stack dims (scan
# segments, vmapped sub-layers) are detected by ndim and padded with None.
# fsdp axis = "data"; tp/ep axis = "model".
def _base_spec(name: str, path: str, ndim_base: int, fsdp: str | None):
    col = P(fsdp, "model")       # (in, out) column-parallel
    row = P("model", fsdp)       # (in, out) row-parallel
    if name == "embed":
        return P("model", fsdp), 2          # (vocab, h)
    if name == "lm_head":
        return P(fsdp, "model"), 2          # (h, vocab)
    if name == "pos_embed":
        return P(None, fsdp), 2
    if name in ("wq", "wk", "wv", "wq_down", "wq_up", "wkv_down", "wk_up",
                "wv_up"):
        return col, 2
    if name in ("wo", "out_proj"):
        return row, 2
    if name in ("w_up", "w_gate"):
        if ndim_base == 3:                   # MoE expert stack (E, h, f)
            return P("model", fsdp, None), 3
        return col, 2
    if name == "w_down":
        if ndim_base == 3:                   # (E, f, h)
            return P("model", None, fsdp), 3
        return row, 2
    if name == "router":
        return P(fsdp, None), 2
    if name == "proj":                        # MTP projection (2h, h)
        return P(fsdp, None), 2
    if name in ("in_z", "in_x"):
        return col, 2
    if name in ("in_B", "in_C", "in_dt"):     # small n-dim: shard only fan-in
        return P(fsdp, None), 2
    if name == "conv_x":
        return P(None, "model"), 2
    if name in ("conv_B", "conv_C"):
        return P(None, None), 2
    if name == "conv_bx":
        return P("model"), 1
    if name in ("conv_bB", "conv_bC"):
        return P(None), 1
    if name in ("A_log", "D", "dt_bias"):
        return P("model"), 1                 # nh sharded with d_inner
    if name in ("bq", "bk", "bv"):
        return P("model"), 1
    if name in ("scale", "bias"):
        # the SSD gated-norm scale lives on the TP-sharded d_inner dim
        if ".ssm." in path or "/ssm/" in path:
            return P("model"), 1
        return P(None), 1
    return None, None


def _path_str(path) -> str:
    return "." + ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path) + "."


def param_specs(params: Any, cfg: ModelConfig, mesh: Mesh,
                fsdp: bool = True) -> Any:
    """PartitionSpec pytree matching `params` (works on ShapeDtypeStructs)."""
    fsdp_ax = "data" if (fsdp and "data" in _axes(mesh)) else None

    def spec(path, leaf):
        pstr = _path_str(path)
        name = pstr.rstrip(".").rsplit(".", 1)[-1]
        # MoE routed-expert stacks carry a leading expert dim (E, h, f)
        is_expert = (name in ("w_up", "w_gate", "w_down")
                     and ".moe." in pstr and ".shared." not in pstr)
        base, nd = _base_spec(name, pstr, 3 if is_expert else 2, fsdp_ax)
        if base is None:
            return P()  # replicated fallback (norm scales etc.)
        lead = leaf.ndim - nd
        if lead < 0:
            return P()
        return P(*([None] * lead), *base)

    return jax.tree_util.tree_map_with_path(spec, params)


def batch_specs(cfg: ModelConfig, mesh: Mesh) -> Any:
    """Input batch: global batch dim sharded over (pod, data)."""
    dp = ("pod", "data") if "pod" in _axes(mesh) else ("data",)
    return {
        "tokens": P(dp, None),
        "labels": P(dp, None),
        "loss_mask": P(dp, None),
        "patch_embeds": P(dp, None, None),
        "encoder_frames": P(dp, None, None),
    }


def cache_specs(cfg: ModelConfig, mesh: Mesh) -> Any:
    """Decode caches: batch over (pod,data); SEQUENCE over model.

    Sequence-sharding the KV cache turns decode attention into a
    flash-decode-style distributed softmax: XLA keeps the s dim sharded and
    all-reduces only the (b, a, hd)-sized stats — tiny collectives instead of
    gathering a 32k cache (DESIGN.md §5).  SSM states shard their head dim on
    `model` (d_inner is TP-sharded).
    """
    dp = ("pod", "data") if "pod" in _axes(mesh) else ("data",)

    def one(kind):
        kv = {"k": P(None, dp, "model", None, None),
              "v": P(None, dp, "model", None, None)}
        if cfg.attn_type == "mla":
            kv = {"latent": P(None, dp, "model", None)}
        ssm = {"state": P(None, dp, "model", None, None),
               "conv_x": P(None, dp, None, "model"),
               "conv_B": P(None, dp, None, None),
               "conv_C": P(None, dp, None, None)}
        if kind in ("dense", "moe"):
            return kv
        if kind == "pair":
            return {"moe_blk": kv, "dense_blk": kv}
        if kind == "ssm":
            return ssm
        if kind == "hybrid_super":
            ssm2 = jax.tree.map(lambda s: P(*s[:1], None, *s[1:]), ssm,
                                is_leaf=lambda x: isinstance(x, P))
            return {"ssm": ssm2, "shared_attn": kv}
        raise ValueError(kind)

    from ..models.blocks import stack_plan
    return [one(kind) for kind, _ in stack_plan(cfg)]


def strip_axis(spec_tree: Any, axis: str = "model") -> Any:
    """Remove one mesh axis from every spec (e.g. disable TP for models whose
    per-shard widths fall under the 128-lane tile — whisper-small at tp=16
    has h/t = 48; the advisor's hidden_shard_alignment rule)."""
    def fix(p):
        return P(*[None if e == axis else
                   (tuple(a for a in e if a != axis) if isinstance(e, tuple) else e)
                   for e in p])
    return jax.tree.map(fix, spec_tree, is_leaf=lambda x: isinstance(x, P))


def to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def validate_divisibility(cfg: ModelConfig, mesh_cfg: MeshConfig,
                          global_batch: int) -> list[str]:
    """Hard constraints that must hold before lowering (paper §VI-B rules)."""
    errs = []
    t, d = mesh_cfg.model, mesh_cfg.dp
    if global_batch % d:
        errs.append(f"global_batch {global_batch} % dp {d} != 0")
    if cfg.num_heads and cfg.num_heads % t:
        errs.append(f"num_heads {cfg.num_heads} % tp {t} != 0")
    if cfg.d_ff and cfg.d_ff % t:
        errs.append(f"d_ff {cfg.d_ff} % tp {t} != 0")
    if cfg.num_experts and cfg.num_experts % t:
        errs.append(f"experts {cfg.num_experts} % ep {t} != 0")
    if cfg.ssm_state and cfg.ssm_d_inner % t:
        errs.append(f"ssm_d_inner {cfg.ssm_d_inner} % tp {t} != 0")
    return errs
