"""XLA mirror of the fused trie-replan kernel (`trie_plan.py`).

Same fused algorithm — per-request lexicographic minima, cumulative engine
delay as a path-counts matmul, the first-step gather fused into the
tournament — expressed as one node tile over the whole trie instead of a
Pallas grid of node tiles.  This is the path CPU CI benchmarks and the
default `use_pallas=False` dispatch run; it executes the *same*
`_tile_lexmin_update` helper as the kernel body, so the two cannot drift.

The sweep takes no node-tile loop at any width: on a TPU v5e over the
5,461-node mathqa_4 trie one tile beat 512-node tiles from 1 lane (6.8x)
to 2,048 lanes, wider than any sweep the controllers issue (the table is
in PERF.md).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.trie_plan import (
    BIG,
    BIG_IDX,
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
):
    """Fused fleet replan: (targets, next_models), both (B,) int32.

    Same contract as `ref.fleet_plan` / `trie_plan.trie_plan_pallas`;
    ``blocked_depth`` (N,) is the engine-availability mask as a node
    column (see `_tile_lexmin_update`), ``None`` = every engine up.  The
    whole trie, padded to a multiple of 8 nodes, is one tile: its
    lexicographic narrowing keeps the lowest index on exact key ties, the
    node the Pallas grid's cross-tile merge picks.
    """
    del elapsed_cost
    if blocked_depth is None:
        blocked_depth = jnp.zeros_like(terminal)
    bsz = prefixes.shape[0]
    n_pad = max(-(-terminal.shape[0] // 8) * 8, 8)

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
    carry = _tile_lexmin_update(carry0, 0, *rows, *cols, pmd, cap_eff,
                                floor_eff, kind=kind)
    tgt, nxt = finalize(carry, cols[0])
    return tgt[:, 0], nxt[:, 0]
