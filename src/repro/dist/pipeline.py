"""GPipe pipeline parallelism over one mesh axis (shard_map + ppermute).

``split_stages`` regroups stacked-layer parameters (leading layer axis)
into ``(n_stages, L / n_stages, ...)``; ``pipeline_forward`` runs the
classic GPipe schedule: microbatch ``j`` enters stage ``s`` at tick
``s + j``, activations hop one stage per tick via ``lax.ppermute``, and the
last stage's per-tick outputs are accumulated and ``psum``-ed back to a
replicated ``(n_micro, ...)`` result.  The whole schedule is one
``lax.scan`` over ``n_micro + n_stages - 1`` ticks, so forward AND backward
stay a single SPMD program — ppermute transposes to the reverse
permutation, which is exactly the backward hop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def split_stages(params, n_stages: int):
    """Reshape every leaf's leading (layer) axis L -> (n_stages, L // n)."""

    def split(a):
        L = a.shape[0]
        assert L % n_stages == 0, (a.shape, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])

    return jax.tree.map(split, params)


def pipeline_forward(stages, x, stage_body, *, mesh, axis: str = "pipe"):
    """Run ``stage_body`` over all stages for every microbatch.

    ``stages``: pytree with leading ``(n_stages, ...)`` axes (from
    ``split_stages``); ``x``: replicated ``(n_micro, ...)`` microbatches;
    ``stage_body(p_stage, x) -> y`` applies one stage's layers.  Returns
    ``(n_micro, ...)`` outputs equal to sequential execution.
    """
    n_stages = int(dict(mesh.shape)[axis])
    n_micro = x.shape[0]
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_device(p_stage, x_all):
        # shard_map hands each device a (1, L/n, ...) slice; drop the lead.
        p_stage = jax.tree.map(lambda a: a[0], p_stage)
        s = jax.lax.axis_index(axis)

        def tick(state, t):
            carry, out = state
            # stage 0 injects a fresh microbatch; later stages consume the
            # previous tick's ppermute hand-off.  Ticks outside a stage's
            # active window compute on stale data whose results are never
            # written (the take mask below), keeping the scan shape static.
            inject = jax.lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            x_in = jnp.where(s == 0, inject, carry)
            y = stage_body(p_stage, x_in)
            j = t - (n_stages - 1)  # microbatch finishing at this tick
            take = (s == n_stages - 1) & (j >= 0) & (j < n_micro)
            jc = jnp.clip(j, 0, n_micro - 1)
            prev = jax.lax.dynamic_index_in_dim(out, jc, 0, keepdims=False)
            upd = prev + jnp.where(take, y, jnp.zeros_like(y))
            out = jax.lax.dynamic_update_index_in_dim(out, upd, jc, 0)
            carry = jax.lax.ppermute(y, axis, fwd_perm)
            return (carry, out), ()

        init = (jnp.zeros_like(x_all[0]), jnp.zeros_like(x_all))
        (_, out), _ = jax.lax.scan(
            tick, init, jnp.arange(n_micro + n_stages - 1))
        # only the last stage wrote anything; psum replicates the result
        return jax.lax.psum(out, axis)

    fn = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stages), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stages, x)
