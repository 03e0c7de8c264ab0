"""Multi-device (lane-sharded) control plane tests.

The sharded engine partitions each replan round's needy-lane sweeps by
``lane % devices`` under `shard_map` and merges the plans with exactly
one `psum` — so every disposition, timestamp, and stream summary must be
BIT-IDENTICAL to the single-device run at any device count.  This module
pins that at 2/4/8 virtual CPU devices:

- the deterministic differential-oracle sweep re-run sharded;
- `test_events_compiled`-style bit-compat configs at every device count;
- the summary property (merged shard sketches == single-device sketch,
  exactly);
- exactly ONE cross-device collective per replan round, and zero
  retraces across device counts / epochs / traces;
- the lane-sharded `ResidentPlanner` (block scatter, lane-local replan,
  and the single-`psum` load-coupled delay row).

Most tests need >= 8 local devices and therefore only run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
``sharded`` job); `test_sharded_smoke_subprocess` always runs, carrying
the guarantee into the tier-1 suite via a subprocess (the
`test_dist.py` idiom, keeping the main process single-device).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from oracle_sim import (
    assert_scenario_matches,
    random_chaos_scenario,
    random_drift_scenario,
    random_scenario,
    run_subject,
)

from repro.core.controller import Objective
from repro.core.controller_jax import (
    TrieDevice,
    fleet_planner_cache_size,
    make_resident_planner,
    trie_engines,
)
from repro.core.events import run_events
from repro.core.events_compiled import (
    compiled_engine_cache_size,
    merge_stream_summaries,
    run_events_compiled,
)
from repro.dist.sharding import LANE_AXIS, lane_counts, lane_mesh
from test_events_compiled import _serving_setup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICE_COUNTS = (2, 4, 8)

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the CI sharded job sets it)")


# ----------------------------------------------------------------------
# helpers (single-device safe)
# ----------------------------------------------------------------------
def test_lane_counts_pads_to_device_multiple():
    class M:
        shape = {LANE_AXIS: 4}

    assert lane_counts(8, M()) == (8, 2)
    assert lane_counts(6, M()) == (8, 2)
    assert lane_counts(1, M()) == (4, 1)


def test_lane_mesh_error_names_cpu_recipe():
    want = len(jax.devices()) + 1
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        lane_mesh(want)
    with pytest.raises(ValueError, match=">= 1"):
        lane_mesh(0)


def test_unsharded_planner_rejects_load_coupling():
    from fleetlib import random_setup

    _, trie, _, ann = random_setup(0)
    td = TrieDevice.build(trie, ann, None)
    p = make_resident_planner(td, Objective("max_acc"), 4)
    with pytest.raises(RuntimeError, match="mesh"):
        p.update_loads([0], [0], [1.0])
    with pytest.raises(RuntimeError, match="mesh"):
        p.replan_coupled([2.0], [1.0], [True])


# ----------------------------------------------------------------------
# engine bit-compatibility at 2/4/8 devices
# ----------------------------------------------------------------------
def _run_pair(devices, seed=3, **overrides):
    trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(seed)
    obj = Objective("max_acc", cost_cap=np.inf, lat_cap=lat_q)
    kw = dict(arrivals=arrivals, capacity=6, policy="dynamic_load_aware",
              fleet_load=load, admission="predictive")
    kw.update(overrides)
    one = run_events_compiled(trie, ann, obj, reqs, execu, **kw)
    many = run_events_compiled(trie, ann, obj, reqs, execu,
                               devices=devices, **kw)
    return one, many


def _assert_bitwise(one, many):
    r1, s1 = one
    rd, sd = many
    assert s1.outcome == sd.outcome
    np.testing.assert_array_equal(s1.done_t, sd.done_t)
    np.testing.assert_array_equal(s1.admit_t, sd.admit_t)
    assert (s1.events, s1.replans, s1.preemptions, s1.rejected, s1.shed) \
        == (sd.events, sd.replans, sd.preemptions, sd.rejected, sd.shed)
    for a, b in zip(r1, rd):
        assert a == b


@multidevice
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
def test_sharded_engine_bitwise_identical(devices):
    _assert_bitwise(*_run_pair(devices))


@multidevice
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
def test_sharded_engine_bitwise_identical_priorities(devices):
    from repro.core.workload import SLOClass

    trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(7)
    obj = Objective("max_acc", lat_cap=lat_q)
    specs = (SLOClass("hi", deadline_s=lat_q * 0.75, weight=4.0),
             SLOClass("lo", deadline_s=None, weight=1.0))
    classes = np.arange(len(reqs)) % len(specs)
    kw = dict(arrivals=arrivals, capacity=5, admission="cost_aware",
              class_specs=specs, classes=classes, preempt=True)
    one = run_events_compiled(trie, ann, obj, reqs, execu, **kw)
    many = run_events_compiled(trie, ann, obj, reqs, execu,
                               devices=devices, **kw)
    _assert_bitwise(one, many)


@multidevice
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
@pytest.mark.parametrize("seed", range(0, 40, 5))
def test_sharded_oracle_sweep(seed, devices):
    """The deterministic differential-oracle sweep, re-run sharded."""
    assert_scenario_matches(random_scenario(seed), engine="compiled",
                            devices=devices)


@multidevice
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
@pytest.mark.parametrize("seed", range(0, 30, 6))
def test_sharded_chaos_sweep(seed, devices):
    """ISSUE 9: the chaos differential sweep (engine outages + forced
    stage failures) over the lane-sharded control plane — fault
    transitions and the blocked-depth planner operand must replicate
    identically on every shard, bit-compatible with the oracle at any
    device count."""
    assert_scenario_matches(random_chaos_scenario(seed), engine="compiled",
                            devices=devices)


@multidevice
@pytest.mark.parametrize("seed", range(0, 20, 4))
def test_sharded_drift_sweep(seed):
    """ISSUE 8: the drift differential sweep (forced annotation-version
    swaps mid-run) over the lane-sharded control plane at 2 virtual
    devices — a version swap must stay a pure buffer substitution on
    every shard, bit-compatible with the oracle."""
    assert_scenario_matches(random_drift_scenario(seed), engine="compiled",
                            devices=2)


@multidevice
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
def test_sharded_host_loop_matches_single_device(devices):
    """The host event loop over the lane-sharded ResidentPlanner."""
    trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(5)
    obj = Objective("max_acc", cost_cap=np.inf, lat_cap=lat_q)
    kw = dict(arrivals=arrivals, capacity=6, policy="dynamic_load_aware",
              fleet_load=load, admission="predictive")
    r1, s1 = run_events(trie, ann, obj, reqs, execu, **kw)
    rd, sd = run_events(trie, ann, obj, reqs, execu, devices=devices, **kw)
    assert s1.outcome == sd.outcome
    np.testing.assert_array_equal(s1.done_t, sd.done_t)
    for a, b in zip(r1, rd):
        # replan_overhead_s is wall-clock-measured on the host lane
        assert (a.success, a.total_cost, a.total_lat, a.models,
                a.outcome) == (b.success, b.total_cost, b.total_lat,
                               b.models, b.outcome)


# ----------------------------------------------------------------------
# summary property: shard count never changes the summary
# ----------------------------------------------------------------------
@multidevice
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
def test_sharded_stream_summary_exactly_single_device(devices):
    one, many = _run_pair(devices, seed=11, stream=True)
    s1, sd = one[0], many[0]
    assert s1 == sd  # includes the full sketch state, bin for bin


@multidevice
def test_merged_shard_sketches_equal_union_sketch():
    """Per-shard drains of a split trace merge EXACTLY into the whole-
    trace sketch: histogram addition loses nothing, and the sharded
    engine contributes identical per-request samples."""
    trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(
        9, n=32, rate=4.0)
    obj = Objective("max_acc", cost_cap=np.inf, lat_cap=lat_q)
    kw = dict(capacity=4, policy="dynamic_load_aware", fleet_load=load,
              admission="feasibility", stream=True)
    halves = []
    for part in (slice(0, 16), slice(16, 32)):
        arr = arrivals[part]
        s, _ = run_events_compiled(trie, ann, obj, reqs[part], execu,
                                   arrivals=arr - arr.min(),
                                   devices=4, **kw)
        halves.append(s)
    merged = merge_stream_summaries(*halves)
    assert merged["n_requests"] == 32
    total = np.array(merged["sketch"]["counts"])
    parts = [np.array(h["sketch"]["counts"]) for h in halves]
    np.testing.assert_array_equal(total, parts[0] + parts[1])
    assert merged["latency"]["count"] == sum(
        h["latency"]["count"] for h in halves)


# ----------------------------------------------------------------------
# the collective + retrace pins
# ----------------------------------------------------------------------
@multidevice
def test_exactly_one_psum_per_replan_round():
    """Trace-time pin: building the sharded step program calls `psum`
    exactly once (the replan-merge) — the only cross-device collective
    per replan round."""
    calls = []
    real = jax.lax.psum

    def counting(x, axis_name, **kw):
        calls.append(axis_name)
        return real(x, axis_name, **kw)

    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(jax.lax, "psum", counting)
        # capacity=7 is untouched by other tests -> a fresh trace
        _run_pair(3, seed=3, capacity=7)
    finally:
        mp.undo()
    assert calls.count(LANE_AXIS) == 1, calls


@multidevice
def test_zero_retrace_across_device_counts_and_traces():
    """One compiled program per device count; new traces, epochs, and
    arrival patterns must all reuse it."""
    trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(13)
    obj = Objective("max_acc", cost_cap=np.inf, lat_cap=lat_q)
    kw = dict(capacity=6, policy="dynamic_load_aware", fleet_load=load,
              admission="predictive")
    for d in DEVICE_COUNTS:
        run_events_compiled(trie, ann, obj, reqs, execu,
                            arrivals=arrivals, devices=d, **kw)
    c0 = compiled_engine_cache_size()
    if c0 < 0:
        pytest.skip("JAX runtime does not expose the jit cache counter")
    rng = np.random.default_rng(0)
    for d in DEVICE_COUNTS:
        for epoch in (64, 1024):
            run_events_compiled(
                trie, ann, obj, reqs, execu,
                arrivals=np.sort(rng.uniform(0, 8, len(reqs))),
                devices=d, epoch=epoch, **kw)
    assert compiled_engine_cache_size() == c0


# ----------------------------------------------------------------------
# lane-sharded ResidentPlanner
# ----------------------------------------------------------------------
def _planner_pair(devices, capacity=6, seed=1):
    from fleetlib import random_setup

    _, trie, _, ann = random_setup(seed)
    td = TrieDevice.build(trie, ann, None)
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.7)))
    E = len(trie_engines(trie.template))
    p1 = make_resident_planner(td, obj, capacity)
    pd = make_resident_planner(td, obj, capacity, mesh=lane_mesh(devices))
    return trie, E, p1, pd


@multidevice
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
def test_sharded_planner_replan_bitwise(devices):
    rng = np.random.default_rng(devices)
    trie, E, p1, pd = _planner_pair(devices)
    for _ in range(3):
        k = int(rng.integers(1, 7))
        slots = rng.choice(6, size=k, replace=False)
        u = rng.integers(0, trie.n_nodes, k).astype(np.int32)
        el = rng.random(k, dtype=np.float32)
        ec = rng.random(k, dtype=np.float32)
        p1.update(slots, u, el, ec)
        pd.update(slots, u, el, ec)
        row = rng.random(E).astype(np.float32)
        t1, n1 = p1.replan(row)
        td_, nd = pd.replan(row)
        np.testing.assert_array_equal(t1, td_)
        np.testing.assert_array_equal(n1, nd)


@multidevice
def test_sharded_planner_coupled_replan_single_psum():
    """`replan_coupled` derives the delay row from resident occupancy
    with exactly one psum, and matches the host-side row + plain replan."""
    rng = np.random.default_rng(2)
    trie, E, p1, pd = _planner_pair(4, capacity=6)
    slots = np.arange(6)
    u = rng.integers(0, trie.n_nodes, 6).astype(np.int32)
    el = rng.random(6, dtype=np.float32)
    ec = rng.random(6, dtype=np.float32)
    p1.update(slots, u, el, ec)
    pd.update(slots, u, el, ec)
    park = np.array([0, 1 % E, -1, 0, 1 % E, -1], np.int32)
    w = np.array([1, 1, 0, 2, 1, 0], np.float32)
    pd.update_loads(slots, park, w)

    conc = np.full(E, 2.0)
    ms = np.ones(E)
    hasm = np.ones(E, bool)
    calls = []
    real = jax.lax.psum

    def counting(x, axis_name, **kw):
        calls.append(axis_name)
        return real(x, axis_name, **kw)

    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(jax.lax, "psum", counting)
        tgt, nxt, row = pd.replan_coupled(conc, ms, hasm)
    finally:
        mp.undo()
    assert calls.count(LANE_AXIS) <= 1  # 0 when the program was cached
    # expected row, float32 like the traced computation
    occ = np.zeros(E, np.float32)
    for e, wv in zip(park, w):
        if e >= 0:
            occ[e] += wv
    exp = ((np.maximum(1.0, (occ + 1.0) / conc) - 1.0) * ms).astype(
        np.float32)
    np.testing.assert_array_equal(row, exp)
    t1, n1 = p1.replan(exp)
    np.testing.assert_array_equal(tgt, t1)
    np.testing.assert_array_equal(nxt, n1)


@multidevice
def test_sharded_planner_no_retrace_across_update_widths():
    rng = np.random.default_rng(0)
    trie, E, _, pd = _planner_pair(8, capacity=12)
    pd.update([0], [0], [0.0], [0.0])
    pd.replan(np.zeros(E, np.float32))
    c0 = fleet_planner_cache_size()
    if c0 < 0:
        pytest.skip("JAX runtime does not expose the jit cache counter")
    for k in (1, 3, 7, 12, 5):
        slots = rng.choice(12, size=k, replace=False)
        pd.update(slots, np.zeros(k, np.int32),
                  rng.random(k, dtype=np.float32), np.zeros(k, np.float32))
        tgt, nxt = pd.replan(np.zeros(E, np.float32))
        assert tgt.shape == (12,) and nxt.shape == (12,)
    assert fleet_planner_cache_size() == c0


# ----------------------------------------------------------------------
# tier-1 smoke: the sharded lane works even when THIS process is
# single-device (subprocess with virtual devices, test_dist.py idiom)
# ----------------------------------------------------------------------
def test_sharded_smoke_subprocess():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
sys.path.insert(0, "tests")
import numpy as np
from test_events_compiled import _serving_setup
from repro.core.controller import Objective
from repro.core.events_compiled import run_events_compiled

trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(3)
obj = Objective("max_acc", cost_cap=np.inf, lat_cap=lat_q)
kw = dict(arrivals=arrivals, capacity=6, policy="dynamic_load_aware",
          fleet_load=load, admission="predictive")
r1, s1 = run_events_compiled(trie, ann, obj, reqs, execu, **kw)
r4, s4 = run_events_compiled(trie, ann, obj, reqs, execu, devices=4, **kw)
assert s1.outcome == s4.outcome
assert np.array_equal(s1.done_t, s4.done_t)
assert all(a == b for a, b in zip(r1, r4))
o1, m1 = run_events_compiled(trie, ann, obj, reqs, execu, stream=True, **kw)
o4, m4 = run_events_compiled(trie, ann, obj, reqs, execu, stream=True,
                             devices=4, **kw)
# equal but for the host's wall time per phase
assert set(o1.pop("host_s")) == set(o4.pop("host_s"))
assert o1 == o4
print("SHARDED_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, timeout=560)
    assert "SHARDED_OK" in r.stdout, r.stderr[-3000:]
