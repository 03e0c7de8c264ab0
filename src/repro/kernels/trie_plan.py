"""Fused trie-replan as a Pallas kernel (the VineLM control-plane hot path).

One fleet replan re-solves the re-rooted constrained search for every
in-flight request.  The dense form (`ref.fleet_plan`) materializes an
(N, Dmax) cumulative-delay intermediate per request and runs one full
min-pass per lexicographic key; this kernel fuses cumulative engine-delay,
feasibility masking, the exact multi-pass lexicographic argmin, and the
first-step gather into a single tiled pass:

- grid = (node tiles, batch lanes), node tiles OUTER: each trie SoA tile
  (terminal/depth/acc/cost/lat/path_counts/path_models) is fetched into
  VMEM once per node tile and stays resident while every batch-lane block
  streams past it;
- cumulative engine delay is a (TILE_N, M) x (M, TILE_B) matmul against the
  per-request per-model delay rows (path-multiplicity counts replace the
  (N, Dmax) gather+sum — MXU work instead of HBM traffic);
- each request carries per-key running minima (k1, k2, k3, node index,
  first-step model) in VMEM scratch across node tiles, merged
  lexicographically tile-by-tile — no full-array min-pass ever exists;
- the winner's first step is gathered from the *resident* path_models tile
  via one-hot contractions the moment the winner is found, so the fused
  pass emits (target, next_model) directly.

Tie-breaking is exact: every comparison is on identical float32 key values
(no epsilon-weighted composite keys), so the kernel picks the *same* node
as the dense oracle and the host ``select_path`` — the property the fleet
equivalence suites pin.  `xla_trie.fleet_plan_blocked` runs the identical
tile math (same `_tile_lexmin_update` helper) as one tile over the whole
trie: the XLA mirror for CPU CI, bitwise-aligned with interpret-mode Pallas.

One caveat on the dense oracle: the counts matmul groups the delay sum by
model (count x delta) where the oracle sums by path position, so the two
float32 `d_lat` values can in principle differ in the last ulp.  A
candidate sitting exactly one ulp from the feasibility threshold (which
already carries a 1e-6 slack vs the float64 host) or an exact key tie
could then split fused-vs-dense.  The contract actually enforced — and the
one serving relies on — is agreement with the host `select_path`, pinned
by the preset sweeps in tests/test_trie_plan.py and end-to-end by
tests/test_golden.py; a boundary flip fails those loudly rather than
drifting silently.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30        # infeasible key sentinel (matches ref._PLAN_BIG)
BIG_CUT = 1e29    # "no feasible node survived" detection threshold
BIG_IDX = 2 ** 30  # infeasible node-index sentinel

DEFAULT_BLOCK_NODES = 512
DEFAULT_BLOCK_LANES = 128


def request_stats(depth, cost, lat, subtree_size, path_counts,
                  engine_of_model, prefixes, elapsed_lat, engine_delays,
                  lat_cap, cost_cap, acc_floor):
    """Per-request prefix statistics + effective budgets (tiny gathers; runs
    as an XLA prologue shared by the Pallas kernel and the XLA mirror).

    Returns (lo, hi, du, lat_u, cost_u, delay_u, thr, pmd, cap_eff,
    floor_eff): interval bounds and prefix annotations per request, the
    remaining-latency threshold ``(lat_cap - elapsed) + 1e-6``, the (B, M)
    per-model delay rows, and the slack-adjusted cost/accuracy scalars —
    identical arithmetic to the dense oracle's feasibility masks.
    """
    u = prefixes
    lo = u.astype(jnp.int32)
    hi = (u + subtree_size[u]).astype(jnp.int32)
    du = depth[u].astype(jnp.int32)
    pmd = engine_delays[:, engine_of_model].astype(jnp.float32)   # (B, M)
    delay_u = jnp.sum(path_counts[u] * pmd, axis=-1)              # (B,)
    lat_u = lat[u]
    cost_u = cost[u]
    thr = (lat_cap - elapsed_lat) + 1e-6
    cap_eff = cost_cap + 1e-6 * jnp.abs(cost_cap)
    floor_eff = acc_floor - 1e-6
    return lo, hi, du, lat_u, cost_u, delay_u, thr, pmd, cap_eff, floor_eff


def _tile_lexmin_update(carry, idx0, term_t, depth_t, acc_t, cost_t, lat_t,
                        counts_t, pm_t, bd_t, lo, hi, du, lat_u, cost_u,
                        delay_u, thr, pmd, cap_eff, floor_eff, *, kind):
    """Merge one node tile into the per-request running lexicographic minima.

    Everything is 2-D so that Mosaic can lay it out: nodes run along the
    lane (last) axis and requests along the sublane axis.  Node columns
    (``term_t`` ... ``lat_t``, ``bd_t``) are (1, T) rows, ``counts_t`` and
    ``pm_t`` are the node-minor (M, T) and (Dmax, T) transposes, request
    statistics (``lo`` ... ``thr``) are (B, 1) columns and ``pmd`` is
    (B, M).  ``carry`` = (bk1, bk2, bk3, bidx, bnxt), each (B, 1): the best
    key triple seen so far, its global node index, and the first-step
    model id gathered when that node became the incumbent.  Pure jnp —
    executed identically by the Pallas kernel body and the XLA mirror's
    single tile, so the two paths cannot drift.

    ``bd_t`` is the availability mask as a node row (``blocked_depth``:
    1 + deepest dead-engine stage position on the node's root path, 0 when
    clean); a candidate survives only if ``bd_t <= depth[u]`` — no *new*
    stage may sit on a down engine.  All-zeros means every engine is up.
    """
    bk1, bk2, bk3, bidx, bnxt = carry
    tile = term_t.shape[1]
    gidx = idx0 + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)  # (1, T)
    # HIGHEST: the TPU's default f32 matmul rounds operands to bf16, which
    # would move the delays off the float64 host search's values
    hi_prec = jax.lax.Precision.HIGHEST

    # cumulative engine delay for every (request, node) pair in the tile:
    # path-multiplicity counts x per-model delay rows — one MXU contraction
    delay_bt = jnp.dot(pmd, counts_t, precision=hi_prec,
                       preferred_element_type=jnp.float32)         # (B, T)
    d_lat = (lat_t - lat_u) + (delay_bt - delay_u)
    d_cost = cost_t - cost_u
    feas = (gidx >= lo) & (gidx < hi)
    feas &= term_t > 0.5
    feas &= bd_t <= du.astype(jnp.float32)
    feas &= d_lat <= thr
    feas &= cost_t <= cap_eff
    if kind == "min_cost":
        feas &= acc_t >= floor_eff
        k1v, k2v, k3v = d_cost, d_lat, jnp.broadcast_to(depth_t,
                                                        d_lat.shape)
    else:
        k1v = jnp.broadcast_to(-acc_t, d_lat.shape)
        k2v, k3v = d_cost, d_lat

    # tile-local exact lexicographic argmin (narrowing over the tile only)
    k1 = jnp.where(feas, k1v, BIG)
    m1 = k1.min(axis=1, keepdims=True)
    c2 = feas & (k1 <= m1)
    k2 = jnp.where(c2, k2v, BIG)
    m2 = k2.min(axis=1, keepdims=True)
    c3 = c2 & (k2 <= m2)
    k3 = jnp.where(c3, k3v, BIG)
    m3 = k3.min(axis=1, keepdims=True)
    c4 = c3 & (k3 <= m3)
    li = jnp.where(c4, gidx, BIG_IDX).min(axis=1, keepdims=True)     # (B, 1)

    # first step of the tile winner, gathered from the RESIDENT pm tile:
    # pm_du[b, t] = pm_t[du_b, t] via a one-hot depth contraction, then the
    # winner row via a one-hot index mask — no dynamic gather needed.
    dmax = pm_t.shape[0]
    dio = jax.lax.broadcasted_iota(jnp.int32, (1, dmax), 1)          # (1, D)
    onehot_du = (dio == du).astype(jnp.float32)                      # (B, D)
    pm_du = jnp.dot(onehot_du, pm_t, precision=hi_prec,
                    preferred_element_type=jnp.float32)              # (B, T)
    win = c4 & (gidx == li)
    nxt_t = jnp.sum(jnp.where(win, pm_du, 0.0), axis=1, keepdims=True)

    # cross-tile lexicographic merge (strict: earlier tiles win exact ties,
    # preserving the lowest-node-index tie-break)
    better = (m1 < bk1) | (
        (m1 == bk1) & ((m2 < bk2) | (
            (m2 == bk2) & ((m3 < bk3) | (
                (m3 == bk3) & (li < bidx))))))
    return (
        jnp.where(better, m1, bk1),
        jnp.where(better, m2, bk2),
        jnp.where(better, m3, bk3),
        jnp.where(better, li, bidx),
        jnp.where(better, nxt_t, bnxt),
    )


def finalize(carry, lo):
    """(targets, next_models) from the final running minima."""
    bk1, _, _, bidx, bnxt = carry
    tgt = jnp.where(bk1 >= BIG_CUT, jnp.int32(-1), bidx.astype(jnp.int32))
    nxt = jnp.where((tgt < 0) | (tgt == lo), jnp.int32(-1),
                    bnxt.astype(jnp.int32))
    return tgt, nxt


def _trie_plan_kernel(scal_ref, term_ref, depth_ref, acc_ref, cost_ref,
                      lat_ref, counts_ref, pm_ref, bd_ref, lo_ref, hi_ref,
                      du_ref, latu_ref, costu_ref, delayu_ref, thr_ref,
                      pmd_ref, tgt_ref, nxt_ref,
                      bk1_ref, bk2_ref, bk3_ref, bidx_ref, bnxt_ref,
                      *, kind, block_nodes):
    n = pl.program_id(0)
    b = pl.program_id(1)
    tb = lo_ref.shape[0]
    best = (bk1_ref, bk2_ref, bk3_ref, bidx_ref, bnxt_ref)

    # the running minima of lane block b live in row b of the
    # (lane blocks, tb, 1) scratch: a dynamic index on the untiled leading
    # axis, so no store needs a tile-aligned offset
    @pl.when(n == 0)
    def _():
        for ref, init in zip(best, (BIG, BIG, BIG, BIG_IDX, -1.0)):
            ref[b] = jnp.full((tb, 1), init, ref.dtype)

    carry = _tile_lexmin_update(
        tuple(ref[b] for ref in best), n * block_nodes,
        term_ref[...], depth_ref[...], acc_ref[...], cost_ref[...],
        lat_ref[...], counts_ref[...], pm_ref[...], bd_ref[...],
        lo_ref[...], hi_ref[...], du_ref[...], latu_ref[...],
        costu_ref[...], delayu_ref[...], thr_ref[...], pmd_ref[...],
        scal_ref[0], scal_ref[1], kind=kind)
    for ref, val in zip(best, carry):
        ref[b] = val
    # running best is written every visit; the last node tile's write is the
    # final answer (output blocks are indexed by the batch lane only)
    tgt_ref[...], nxt_ref[...] = finalize(carry, lo_ref[...])


def _pad_to(x, size, fill):
    pad = size - x.shape[0]
    if pad == 0:
        return x
    widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def node_rows(terminal, depth, acc, cost, lat, path_counts, path_models,
              blocked_depth, n_pad):
    """Trie SoA in the tile layout, padded to ``n_pad`` nodes: (1, n_pad)
    rows of terminal/depth/acc/cost/lat, the (M, n_pad) and (Dmax, n_pad)
    transposes of path_counts/path_models, and the blocked_depth row.
    Padded nodes are non-terminal (never feasible)."""
    f32 = jnp.float32

    def row(a, fill):
        return _pad_to(a.astype(f32), n_pad, fill)[None, :]

    return [
        row(terminal, 0.0), row(depth, 0.0), row(acc, 0.0), row(cost, 0.0),
        row(lat, 0.0),
        _pad_to(path_counts.astype(f32), n_pad, 0.0).T,
        _pad_to(path_models.astype(f32), n_pad, -1.0).T,
        row(blocked_depth, 0.0),
    ]


def lane_columns(lo, hi, du, lat_u, cost_u, delay_u, thr):
    """Per-request statistics as (B, 1) columns: int32 interval bounds and
    prefix depth, float32 prefix annotations and latency threshold."""
    i32, f32 = jnp.int32, jnp.float32
    return [lo.astype(i32)[:, None], hi.astype(i32)[:, None],
            du.astype(i32)[:, None], lat_u.astype(f32)[:, None],
            cost_u.astype(f32)[:, None], delay_u.astype(f32)[:, None],
            thr.astype(f32)[:, None]]


def trie_plan_pallas(
    terminal, depth, acc, cost, lat, subtree_size, path_models,
    path_counts, engine_of_model, prefixes, elapsed_lat, elapsed_cost,
    engine_delays, acc_floor, cost_cap, lat_cap,
    *,
    kind: str,
    blocked_depth=None,
    block_nodes: int = DEFAULT_BLOCK_NODES,
    block_lanes: int = DEFAULT_BLOCK_LANES,
    interpret: bool = True,
):
    """Fused fleet replan: (targets, next_models), both (B,) int32.

    Same contract as `ref.fleet_plan`; `elapsed_cost` is accepted for
    signature parity (cost budgets are expectation-based, see select_path).
    ``blocked_depth`` (N,) is the engine-availability mask as a node
    column (see `_tile_lexmin_update`); ``None`` means every engine up.
    """
    del elapsed_cost
    if blocked_depth is None:
        blocked_depth = jnp.zeros_like(terminal)
    n = terminal.shape[0]
    bsz = prefixes.shape[0]
    # node tiles run along the 128-wide lane axis; padded nodes are
    # non-terminal, so they are never feasible
    block_nodes = min(block_nodes, pl.cdiv(n, 128) * 128)
    n_pad = pl.cdiv(n, block_nodes) * block_nodes
    tb = min(block_lanes, max(pl.cdiv(bsz, 8) * 8, 8))
    b_pad = pl.cdiv(bsz, tb) * tb

    lo, hi, du, lat_u, cost_u, delay_u, thr, pmd, cap_eff, floor_eff = \
        request_stats(depth, cost, lat, subtree_size, path_counts,
                      engine_of_model, prefixes, elapsed_lat, engine_delays,
                      lat_cap, cost_cap, acc_floor)

    f32 = jnp.float32
    node_ops = node_rows(terminal, depth, acc, cost, lat, path_counts,
                         path_models, blocked_depth, n_pad)
    # padded lanes get hi=0 (empty interval -> infeasible -> tgt -1)
    lane_ops = [_pad_to(c, b_pad, 0) for c in lane_columns(
        lo, hi, du, lat_u, cost_u, delay_u, thr)]
    pmd_p = _pad_to(pmd, b_pad, 0.0)
    scal = jnp.stack([jnp.asarray(cap_eff, f32), jnp.asarray(floor_eff, f32)])

    grid = (n_pad // block_nodes, b_pad // tb)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    in_specs += [pl.BlockSpec((a.shape[0], block_nodes), lambda i, j: (0, i))
                 for a in node_ops]
    in_specs += [pl.BlockSpec((tb, 1), lambda i, j: (j, 0))
                 for _ in lane_ops]
    in_specs += [pl.BlockSpec((tb, pmd_p.shape[1]), lambda i, j: (j, 0))]
    n_lane_blocks = b_pad // tb
    scratch = [pltpu.VMEM((n_lane_blocks, tb, 1), dt)
               for dt in (f32, f32, f32, jnp.int32, f32)]

    tgt, nxt = pl.pallas_call(
        functools.partial(_trie_plan_kernel, kind=kind,
                          block_nodes=block_nodes),
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((tb, 1), lambda i, j: (j, 0)),
                   pl.BlockSpec((tb, 1), lambda i, j: (j, 0))),
        out_shape=(jax.ShapeDtypeStruct((b_pad, 1), jnp.int32),
                   jax.ShapeDtypeStruct((b_pad, 1), jnp.int32)),
        scratch_shapes=scratch,
        interpret=interpret,
    )(scal, *node_ops, *lane_ops, pmd_p)
    return tgt[:bsz, 0], nxt[:bsz, 0]
