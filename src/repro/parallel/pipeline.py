"""GPipe-style pipeline parallelism over a mesh axis (the `pod` axis of the
production mesh), via shard_map + collective_permute.

Design (DESIGN.md §5): each pipeline stage holds L/num_stages layers
(the paper's §VI-B rule "L divisible by the number of pipeline stages" is
asserted).  Microbatches stream through stages; activations hop stages with
`jax.lax.ppermute`.  The schedule is the classic GPipe loop of
(num_micro + num_stages - 1) ticks, bubble fraction
(S-1)/(M+S-1); each device computes every tick on its resident stage,
masking ticks outside its active window — SPMD-friendly (no per-device
control flow).

This module is self-contained on purpose: the 40-cell dry-run uses the pod
axis as outer data parallelism (the default, best for the assigned shapes
where DP is cheap); `pipeline_apply` is the drop-in for bandwidth-poor
cross-pod links, exercised by tests/test_pipeline.py on 8 host devices and
by the `--pp` dryrun treatment.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   mesh: Mesh, axis: str = "pod"):
    """Run a layer stack split across `axis` as a GPipe pipeline.

    stage_fn(params_for_stage, microbatch) -> microbatch  (one stage's layers)
    stage_params: pytree whose leaves have leading dim == num_stages
                  (sharded over `axis`).
    x: (num_micro, micro_batch, ...) microbatched input (replicated over
       `axis`; each stage consumes/produces as the schedule dictates).

    Returns (num_micro, micro_batch, ...) outputs (gathered on all devices).
    """
    num_stages = mesh.shape[axis]
    num_micro = x.shape[0]

    def per_stage(params, xs):
        # params: (1, ...) this stage's slice; xs: full (num_micro, ...)
        params = jax.tree.map(lambda t: t[0], params)
        stage = jax.lax.axis_index(axis)
        ticks = num_micro + num_stages - 1

        state = jnp.zeros_like(xs[0])  # activation resident on this stage
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (when valid)
            mb_idx = jnp.clip(t, 0, num_micro - 1)
            injected = jnp.where(stage == 0, xs[mb_idx], state)
            # compute only when this stage holds a live microbatch:
            # stage s is active for t in [s, s + num_micro)
            live = (t >= stage) & (t < stage + num_micro)
            out = stage_fn(params, injected)
            out = jnp.where(live, out, state)
            # last stage retires microbatch (t - (S-1))
            retire_idx = jnp.clip(t - (num_stages - 1), 0, num_micro - 1)
            retire = (stage == num_stages - 1) & (t >= num_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(retire, out, outputs[retire_idx]),
                retire_idx, 0)
            # hop activations forward one stage
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                           jnp.arange(ticks))
        # gather retired outputs from the last stage to all stages
        outputs = jax.lax.psum(
            jnp.where(stage == num_stages - 1, outputs, 0.0), axis)
        return outputs

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    return jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(stage_params, x)


def split_layers_into_stages(stacked_params: Any, num_stages: int) -> Any:
    """(L, ...) stacked layer params -> (num_stages, L/num_stages, ...).

    Asserts the paper's §VI-B rule: L % num_stages == 0.
    """
    def reshape(t):
        L = t.shape[0]
        assert L % num_stages == 0, (
            f"L={L} not divisible by pipeline stages={num_stages} "
            "(paper §VI-B)")
        return t.reshape((num_stages, L // num_stages) + t.shape[1:])
    return jax.tree.map(reshape, stacked_params)
