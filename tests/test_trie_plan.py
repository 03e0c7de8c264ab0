"""Fused trie-replan dispatch: Pallas-interpret vs XLA mirror vs host.

The three dispatch variants ("dense" reference, "fused" XLA mirror,
"pallas" interpret-mode kernel) must pick the *identical* node and first
step as each other — and as the host float64 ``select_path`` — across the
three paper presets, both objective kinds, and live engine delays.  The
device-resident planner path must also hold the no-retrace invariant
across fluctuating update widths (the kernel-path extension of the
`fleet_planner_cache_size` guard).
"""
import numpy as np
import pytest

from repro.core import presets
from repro.core.controller import Objective, select_path
from repro.core.controller_jax import (
    TrieDevice,
    fleet_planner_cache_size,
    make_fleet_planner,
    make_resident_planner,
    next_model_for,
    trie_engines,
)
from repro.core.trie import Trie, TrieAnnotations
from repro.core.workload import generate_workload

_SIZES = {"nl2sql_8": 300, "nl2sql_2": 300, "mathqa_4": 120}
_VARIANTS = ("dense", "fused", "pallas")


def _setup(name):
    tpl = presets.PRESETS[name]()
    trie = Trie.build(tpl)
    wl = generate_workload(tpl, _SIZES[name], seed=0)
    ann = wl.exact_annotations(trie)
    return tpl, trie, ann


def _objectives(trie, ann):
    term = trie.terminal
    return [
        Objective("max_acc",
                  cost_cap=float(np.quantile(ann.cost[term], 0.5)),
                  lat_cap=float(np.quantile(ann.lat[term], 0.8))),
        Objective("min_cost",
                  acc_floor=float(np.quantile(ann.acc[term], 0.4)),
                  lat_cap=float(np.quantile(ann.lat[term], 0.9))),
    ]


@pytest.mark.parametrize("name", sorted(_SIZES))
def test_variants_match_host_select_path(name):
    """Equality sweep: every dispatch variant picks the host's node and
    first step under random prefixes, elapsed budgets, and live delays."""
    tpl, trie, ann = _setup(name)
    engines = trie_engines(tpl)
    td = TrieDevice.build(trie, ann)
    rng = np.random.default_rng(3)
    B = 24
    roots = rng.integers(0, trie.n_nodes, size=B).astype(np.int32)
    el = rng.uniform(0, 3, size=B).astype(np.float32)
    ec = np.zeros(B, np.float32)
    delays = rng.uniform(0, 0.5, size=(B, len(engines))).astype(np.float32)
    for obj in _objectives(trie, ann):
        outs = {}
        for v in _VARIANTS:
            step = make_fleet_planner(td, obj, variant=v)
            tgt, nxt = step(roots, el, ec, delays)
            outs[v] = (np.asarray(tgt), np.asarray(nxt))
        host_tgt = np.array([
            select_path(trie, ann, obj, root=int(roots[i]),
                        elapsed_lat=float(el[i]),
                        engine_delays={e: float(delays[i, j])
                                       for j, e in enumerate(engines)})
            for i in range(B)])
        host_nxt = np.array([
            next_model_for(trie, int(roots[i]), int(host_tgt[i]))
            for i in range(B)])
        for v in _VARIANTS:
            np.testing.assert_array_equal(outs[v][0], host_tgt,
                                          err_msg=f"{name}/{obj.kind}/{v}")
            np.testing.assert_array_equal(outs[v][1], host_nxt,
                                          err_msg=f"{name}/{obj.kind}/{v}")


def test_variants_agree_on_infeasible_and_stop():
    """-1 lanes (no feasible path) and stop-here lanes (target == prefix)
    agree across variants."""
    tpl, trie, ann = _setup("nl2sql_2")
    td = TrieDevice.build(trie, ann)
    obj = Objective("max_acc", cost_cap=0.0)  # nothing affordable
    roots = np.zeros(8, np.int32)
    zeros = np.zeros(8, np.float32)
    dl = np.zeros((8, len(trie_engines(tpl))), np.float32)
    for v in _VARIANTS:
        tgt, nxt = make_fleet_planner(td, obj, variant=v)(
            roots, zeros, zeros, dl)
        assert np.all(np.asarray(tgt) == -1), v
        assert np.all(np.asarray(nxt) == -1), v
    # terminal prefix with an exhausted latency budget: stop where you are
    term_nodes = np.nonzero(trie.terminal)[0][:8].astype(np.int32)
    obj2 = Objective("max_acc", lat_cap=1e-9)
    for v in _VARIANTS:
        tgt, nxt = make_fleet_planner(td, obj2, variant=v)(
            term_nodes, zeros, zeros, dl)
        np.testing.assert_array_equal(np.asarray(tgt), term_nodes, v)
        assert np.all(np.asarray(nxt) == -1), v


def test_trie_device_path_tables_match_path_walk():
    """The vectorized parent-pointer fill reproduces the per-node
    ``trie.path(u)`` walk (first-step table AND path-multiplicity counts)."""
    tpl, trie, ann = _setup("nl2sql_8")
    td = TrieDevice.build(trie, ann)
    pm = np.asarray(td.path_models)
    counts = np.asarray(td.path_counts)
    dmax = tpl.max_depth
    assert pm.shape == (trie.n_nodes, dmax)
    assert counts.shape == (trie.n_nodes, tpl.n_models)
    for u in range(trie.n_nodes):
        path = trie.path(u)
        expect = np.full(dmax, -1, np.int32)
        expect[: len(path)] = path
        np.testing.assert_array_equal(pm[u], expect, err_msg=f"node {u}")
        np.testing.assert_array_equal(
            counts[u], np.bincount(path, minlength=tpl.n_models),
            err_msg=f"node {u}")


def test_trie_device_n_engines_is_static():
    """n_engines is plain aux data computed once at build — no device
    array sync on access, and it survives pytree flatten/unflatten."""
    import jax

    tpl, trie, ann = _setup("nl2sql_2")
    td = TrieDevice.build(trie, ann)
    assert isinstance(td.n_engines, int)
    assert td.n_engines == len(trie_engines(tpl))
    leaves, treedef = jax.tree_util.tree_flatten(td)
    td2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert td2.n_engines == td.n_engines


@pytest.mark.parametrize("variant", ["fused", "pallas"])
def test_resident_planner_no_retrace_across_update_widths(variant):
    """The device-resident path compiles a fixed program set: scatters are
    fixed-width and the replan batch is pinned at capacity, so neither
    fluctuating update counts nor repeated replans add specializations."""
    tpl, trie, ann = _setup("nl2sql_2")
    td = TrieDevice.build(trie, ann)
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.7)))
    C = 12
    planner = make_resident_planner(td, obj, C, variant=variant)
    row = np.zeros(len(trie_engines(tpl)), np.float32)
    # warm: compile the scatter + resident-plan programs once
    planner.update([0], [0], [0.0], [0.0])
    planner.replan(row)
    c0 = fleet_planner_cache_size()
    if c0 < 0:
        pytest.skip("JAX runtime does not expose the jit cache counter")
    rng = np.random.default_rng(0)
    for k in (1, 3, 7, 12, 5, 9):
        slots = rng.choice(C, size=k, replace=False)
        planner.update(slots, np.zeros(k, np.int32),
                       rng.uniform(0, 1, k).astype(np.float32),
                       np.zeros(k, np.float32))
        tgt, nxt = planner.replan(row)
        assert tgt.shape == (C,) and nxt.shape == (C,)
    assert fleet_planner_cache_size() == c0


def test_resident_planner_matches_fleet_step():
    """Scattered device-resident state reaches the same answers as a
    one-shot fleet-step call with identical host arrays."""
    tpl, trie, ann = _setup("nl2sql_8")
    td = TrieDevice.build(trie, ann)
    engines = trie_engines(tpl)
    obj = Objective("max_acc",
                    cost_cap=float(np.quantile(ann.cost[trie.terminal], 0.6)),
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.8)))
    C = 16
    rng = np.random.default_rng(7)
    u = rng.integers(0, trie.n_nodes, size=C).astype(np.int32)
    el = rng.uniform(0, 2, size=C).astype(np.float32)
    ec = rng.uniform(0, 0.01, size=C).astype(np.float32)
    row = rng.uniform(0, 0.3, size=len(engines)).astype(np.float32)

    planner = make_resident_planner(td, obj, C)
    # scatter the state in three uneven waves, overwriting some lanes
    planner.update(np.arange(C), np.zeros(C, np.int32),
                   np.zeros(C, np.float32), np.zeros(C, np.float32))
    planner.update(np.arange(0, C, 2), u[0::2], el[0::2], ec[0::2])
    planner.update(np.arange(1, C, 2), u[1::2], el[1::2], ec[1::2])
    tgt_r, nxt_r = planner.replan(row)

    step = make_fleet_planner(td, obj)
    tgt_f, nxt_f = step(u, el, ec,
                        np.broadcast_to(row, (C, len(engines))).copy())
    np.testing.assert_array_equal(tgt_r, np.asarray(tgt_f))
    np.testing.assert_array_equal(nxt_r, np.asarray(nxt_f))


def test_resident_planner_detects_donated_buffer_invalidation():
    """A host-side failure that interrupts a donated update leaves the
    planner's resident buffers deleted; the next call must raise a
    descriptive RuntimeError (naming reset()) instead of the runtime's
    opaque deleted-array error, and reset() must let serving resume."""
    from repro.core.controller_jax import _apply_slot_updates

    tpl, trie, ann = _setup("nl2sql_2")
    td = TrieDevice.build(trie, ann)
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.7)))
    C = 8
    row = np.zeros(len(trie_engines(tpl)), np.float32)
    rng = np.random.default_rng(5)
    u = rng.integers(0, trie.n_nodes, size=C).astype(np.int32)
    el = rng.uniform(0, 1, size=C).astype(np.float32)
    ec = rng.uniform(0, 0.01, size=C).astype(np.float32)

    planner = make_resident_planner(td, obj, C)
    planner.update(np.arange(C), u, el, ec)
    tgt0, nxt0 = planner.replan(row)

    # inject the mid-run failure: donate the planner's buffers to an
    # update whose results are lost (exactly what an exception between
    # dispatch and reassignment leaves behind)
    _apply_slot_updates(planner._u, planner._el, planner._ec,
                        np.full(C, C, np.int32), np.zeros(C, np.int32),
                        np.zeros(C, np.float32), np.zeros(C, np.float32))
    if not planner._u.is_deleted():
        pytest.skip("backend did not donate (no invalidation to detect)")
    with pytest.raises(RuntimeError, match=r"reset\(\)"):
        planner.update([0], [0], [0.0], [0.0])
    with pytest.raises(RuntimeError, match=r"reset\(\)"):
        planner.replan(row)

    # resume: reset rematerializes zeroed buffers, the host re-mirrors
    # its authoritative lane state, and replans match the pre-failure run
    planner.reset()
    planner.update(np.arange(C), u, el, ec)
    tgt1, nxt1 = planner.replan(row)
    np.testing.assert_array_equal(tgt0, tgt1)
    np.testing.assert_array_equal(nxt0, nxt1)


def test_pallas_mode_follows_backend(monkeypatch):
    """Pallas runs compiled on TPU, interpreted on the CPU test backend,
    and refuses any other backend instead of silently interpreting."""
    import jax

    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret()


# ----------------------------------------------------------------------
# node tiling: the fused sweep's one tile against the Pallas grid's tiles
# ----------------------------------------------------------------------


def _on_grid(ann):
    """Annotations on a coarse dyadic grid, exact in float32 and float64,
    so that many candidates tie on every key and the lowest index
    decides."""
    return TrieAnnotations(acc=np.round(ann.acc * 4) / 4,
                           cost=np.round(ann.cost * 128) / 128,
                           lat=np.round(ann.lat / 2) * 2)


def _off_grid(obj):
    """The objective's budgets moved half a grid step off `_on_grid`'s
    values, so that no candidate sits on a feasibility boundary."""
    def mid(x, step):
        return None if x is None else (np.floor(x / step) + 0.5) * step
    return Objective(obj.kind, acc_floor=mid(obj.acc_floor, 1 / 4),
                     cost_cap=mid(obj.cost_cap, 1 / 128),
                     lat_cap=mid(obj.lat_cap, 2))


def _host_plan(trie, ann, obj, engines, roots, el, delays, bd):
    """Host `select_path` and first step per lane; candidates ``v`` with
    ``bd[v] > depth[root]`` (a new stage on a down engine) are masked by
    an infinite latency."""
    tgt, nxt = [], []
    for i, root in enumerate(roots):
        lat = np.where(bd > trie.depth[root], np.inf, ann.lat)
        t = select_path(trie, TrieAnnotations(ann.acc, ann.cost, lat), obj,
                        root=int(root), elapsed_lat=float(el[i]),
                        engine_delays={e: float(delays[i, j])
                                       for j, e in enumerate(engines)})
        tgt.append(t)
        nxt.append(next_model_for(trie, int(root), t))
    return np.array(tgt), np.array(nxt)


@pytest.mark.parametrize("kind", ["max_acc", "min_cost"])
@pytest.mark.parametrize("name", ["mathqa_4", "nl2sql_8"])
@pytest.mark.parametrize("lanes", [1, 2, 24, 256])
def test_fused_tilings_match_dense_oracle_and_host(lanes, name, kind):
    """The fused sweep, one node tile over the whole trie, and the Pallas
    grid of 512-node tiles pick the dense oracle's and the host's node and
    first step at every width: random prefixes, exact key ties, prefixes
    with no feasible plan, and an engine down.  On mathqa_4 (5,461 nodes,
    11 Pallas tiles) the ties cross tiles; nl2sql_8 (585 nodes) is small."""
    import jax

    from repro.core.controller_jax import _objective_scalars
    from repro.core.faults import blocked_depth_table
    from repro.kernels import ops, ref

    tpl, trie, ann = _setup(name)
    engines = trie_engines(tpl)
    n, n_eng = trie.n_nodes, len(engines)
    rng = np.random.default_rng(lanes)
    roots = rng.integers(0, n, size=lanes).astype(np.int32)
    zeros = np.zeros(lanes, np.float32)
    delays = rng.uniform(0, 0.5, (lanes, n_eng)).astype(np.float32)
    clean = np.zeros(n, np.float32)
    obj = {o.kind: o for o in _objectives(trie, ann)}[kind]
    tied_ann, tied_obj = _on_grid(ann), _off_grid(obj)
    down = np.zeros(n_eng, bool)
    down[0] = True
    td0 = TrieDevice.build(trie, ann)
    blocked = blocked_depth_table(np.asarray(td0.path_models),
                                  np.asarray(td0.engine_of_model), down)
    assert blocked.max() > 0
    el = rng.uniform(0, 3, lanes).astype(np.float32)
    over = np.where(np.arange(lanes) % 2 == 0,
                    np.float32(obj.lat_cap + 100.0), el)
    tied_roots = roots.copy()
    tied_roots[0] = 0
    # dyadic delays keep every key exact; the root lane's are zero
    tied_delays = rng.integers(0, 4, (lanes, n_eng)).astype(np.float32) / 8
    tied_delays[0] = 0.0
    cases = {
        "random": (ann, obj, roots, el, delays, clean),
        "ties": (tied_ann, tied_obj, tied_roots, zeros, tied_delays, clean),
        "infeasible": (ann, obj, roots, over, delays, clean),
        "blocked": (ann, obj, roots, el, delays, blocked),
    }
    for case, (a, o, r, e, d, bd) in cases.items():
        td = td0 if a is ann else TrieDevice.build(trie, a)
        lane_ops = (r, e, zeros, d, *_objective_scalars(o))
        cols = (td.terminal, td.depth, td.acc, td.cost, td.lat,
                td.subtree_size, td.path_models, td.path_counts,
                td.engine_of_model, *lane_ops)
        want = _host_plan(trie, a, o, engines, r, e, d, bd)
        dense = ref.fleet_plan(td.terminal, td.depth, td.acc, td.cost,
                               td.lat, td.subtree_size, td.path_models,
                               td.engine_of_model, *lane_ops, kind=kind,
                               blocked_depth=bd)
        for i, got in enumerate(dense):
            np.testing.assert_array_equal(got, want[i],
                                          err_msg=f"{case}/dense")
        for variant in ("fused", "pallas"):
            tiles = ops.trie_plan_tiles(n, variant)

            def plan(*c):
                return ops.trie_plan(*c, kind=kind, variant=variant,
                                     blocked_depth=bd)

            for i, got in enumerate(jax.jit(plan)(*cols)):
                np.testing.assert_array_equal(
                    got, want[i], err_msg=f"{case}/{variant} ({tiles} tiles)")
        if case == "infeasible":
            assert want[0][0] == -1
        if case == "ties":
            # the root lane's winner shares its whole key with another
            # feasible candidate, so the index tie-break decides it
            d_lat, d_cost = a.lat - a.lat[0], a.cost - a.cost[0]
            feas = trie.terminal & (d_lat <= o.lat_cap)
            if o.cost_cap is not None:
                feas &= a.cost <= o.cost_cap
            if kind == "min_cost":
                feas &= a.acc >= o.acc_floor
                key = np.stack([d_cost, d_lat, trie.depth])
            else:
                key = np.stack([-a.acc, d_cost, d_lat])
            w = want[0][0]
            assert w >= 0
            tied = np.nonzero(feas & np.all(key == key[:, [w]], axis=0))[0]
            assert len(tied) > 1
            if ops.trie_plan_tiles(n, "pallas") > 1:
                # ... and one of them lies in another tile of the Pallas
                # grid, so the cross-tile merge decides it there
                assert np.any(tied // 512 != w // 512)


@pytest.mark.parametrize("n_nodes,variant,want", [
    # the event engine's sweep over the 5,461-node trie
    (5461, "fused", 1),
    (5461, "dense", 1),
    # the Pallas grid: 512-node tiles, narrowed to a small trie
    (5461, "pallas", 11),
    (585, "pallas", 2),
    (31, "pallas", 1),
])
def test_plan_tiles_shapes(n_nodes, variant, want):
    from repro.kernels.ops import trie_plan_tiles
    assert trie_plan_tiles(n_nodes, variant) == want
