"""XLA mirror of the fused trie-replan kernel (`trie_plan.py`).

Same blocked algorithm — per-request running lexicographic minima carried
across node tiles, cumulative engine delay as a path-counts matmul, the
first-step gather fused into the tournament — expressed as a jnp fori-loop
instead of a Pallas grid.  This is the path CPU CI benchmarks and the
default `use_pallas=False` dispatch run; it executes the *same*
`_tile_lexmin_update` helper as the kernel body, so the two cannot drift.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.trie_plan import (
    BIG,
    BIG_IDX,
    DEFAULT_BLOCK_NODES,
    _tile_lexmin_update,
    finalize,
    lane_columns,
    node_rows,
    request_stats,
)


def fleet_plan_blocked(
    terminal, depth, acc, cost, lat, subtree_size, path_models,
    path_counts, engine_of_model, prefixes, elapsed_lat, elapsed_cost,
    engine_delays, acc_floor, cost_cap, lat_cap,
    *,
    kind: str,
    blocked_depth=None,
    block_nodes: int = DEFAULT_BLOCK_NODES,
):
    """Fused fleet replan: (targets, next_models), both (B,) int32.

    Same contract as `ref.fleet_plan` / `trie_plan.trie_plan_pallas`;
    ``blocked_depth`` (N,) is the engine-availability mask as a node
    column (see `_tile_lexmin_update`), ``None`` = every engine up.
    """
    del elapsed_cost
    if blocked_depth is None:
        blocked_depth = jnp.zeros_like(terminal)
    n = terminal.shape[0]
    bsz = prefixes.shape[0]
    # small tries fit one tile: skip the loop machinery entirely (the
    # running-minima pass degenerates to a single tile update)
    if n <= 4 * block_nodes:
        block_nodes = max((n + 7) // 8 * 8, 8)
    n_pad = -(-n // block_nodes) * block_nodes
    n_tiles = n_pad // block_nodes

    lo, hi, du, lat_u, cost_u, delay_u, thr, pmd, cap_eff, floor_eff = \
        request_stats(depth, cost, lat, subtree_size, path_counts,
                      engine_of_model, prefixes, elapsed_lat, engine_delays,
                      lat_cap, cost_cap, acc_floor)

    f32 = jnp.float32
    rows = node_rows(terminal, depth, acc, cost, lat, path_counts,
                     path_models, blocked_depth, n_pad)
    cols = lane_columns(lo, hi, du, lat_u, cost_u, delay_u, thr)

    carry0 = (
        jnp.full((bsz, 1), BIG, f32),
        jnp.full((bsz, 1), BIG, f32),
        jnp.full((bsz, 1), BIG, f32),
        jnp.full((bsz, 1), BIG_IDX, jnp.int32),
        jnp.full((bsz, 1), -1.0, f32),
    )

    def body(i, carry):
        s = i * block_nodes
        tiles = [jax.lax.dynamic_slice_in_dim(a, s, block_nodes, axis=1)
                 for a in rows]
        return _tile_lexmin_update(carry, s, *tiles, *cols, pmd, cap_eff,
                                   floor_eff, kind=kind)

    if n_tiles == 1:
        carry = body(0, carry0)
    else:
        carry = jax.lax.fori_loop(0, n_tiles, body, carry0)
    tgt, nxt = finalize(carry, cols[0])
    return tgt[:, 0], nxt[:, 0]
