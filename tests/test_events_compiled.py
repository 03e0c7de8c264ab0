"""Tier-1 suite for the jitted epoch-batched event engine (ISSUE 6).

The bit-compatibility bar is carried by the differential-oracle lanes in
`test_oracle_differential.py` (chain workflows, exact grids); this module
covers what the oracle cannot:

- host-vs-compiled identity on *branching* tries with the full workload
  generator (realistic annotations, load coupling, admission gates);
- the ``stream=True`` constant-memory path, on each calendar and with
  priority classes and faults: summary equal bit for bit to what the
  materialized run's state holds, and consistent with its per-request
  results (Welford moments, quantile-sketch resolution); the stream
  state without per-request columns, and no O(n) host lists;
- `merge_stream_summaries` exactness for sharded replays;
- dispatch plumbing: the ``compiled=`` switch in `run_events`, kwarg
  validation, and the NotImplementedError fence around host-only
  features (custom policies, ``load_probe``, duck-typed load models).
"""
import numpy as np
import pytest
from fleetlib import assert_results_identical, random_setup

from repro.core.admission import AdmissionPolicy
from repro.core.faults import FaultSchedule
from repro.core.controller import Objective
from repro.core.events import run_events
from repro.core.events_compiled import (
    merge_stream_summaries,
    run_events_compiled,
)
from repro.core.runtime import make_workload_executor
from repro.core.streaming import QuantileSketch, welford_finalize
from repro.core.workload import SLOClass, poisson_arrivals, sample_classes
from repro.serving.loadsim import (
    EngineLoadModel,
    EngineTokenModel,
    FleetLoadModel,
    TokenWorkModel,
)


def _serving_setup(seed, n=24, rate=3.0):
    """Branching workflow + open arrivals + a load-coupled fleet."""
    rng, trie, wl, ann = random_setup(seed)
    execu = make_workload_executor(wl)
    engines = sorted({m.engine for m in trie.template.models})
    load = FleetLoadModel(
        engines={e: EngineLoadModel(e, concurrency=2, jitter=0.0)
                 for e in engines},
        mean_service_s={e: 1.0 for e in engines})
    reqs = rng.choice(wl.n_requests, n, replace=False)
    arrivals = poisson_arrivals(n, rate=rate, seed=seed)
    lat_q = float(np.quantile(ann.lat[trie.terminal], 0.7))
    return trie, ann, execu, load, reqs, arrivals, lat_q


def _both_lanes(trie, ann, obj, reqs, execu, **kw):
    host = run_events(trie, ann, obj, reqs, execu, **kw)
    comp = run_events(trie, ann, obj, reqs, execu, compiled=True, **kw)
    return host, comp


def _assert_lanes_identical(host, comp):
    hres, hstats = host
    cres, cstats = comp
    assert_results_identical(hres, cres)
    for a, b in zip(hres, cres):
        assert a.total_lat == b.total_lat  # bitwise
        assert a.total_cost == b.total_cost
        assert a.outcome == b.outcome and a.n_stages == b.n_stages
    assert hstats.done_t.tolist() == cstats.done_t.tolist()
    assert hstats.admit_t.tolist() == cstats.admit_t.tolist()
    assert (hstats.admitted, hstats.rejected, hstats.shed) == \
        (cstats.admitted, cstats.rejected, cstats.shed)
    assert (hstats.preemptions, hstats.resumed) == \
        (cstats.preemptions, cstats.resumed)
    assert hstats.preempt_count.tolist() == cstats.preempt_count.tolist()
    assert hstats.peak_occupancy == cstats.peak_occupancy


@pytest.mark.parametrize("seed", [3, 11])
def test_compiled_matches_host_branching_load_aware(seed):
    """Branching trie + processor sharing + feasibility gate: the two
    lanes must agree bit-for-bit on every per-request field."""
    trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(seed)
    obj = Objective("max_acc", lat_cap=lat_q)
    host, comp = _both_lanes(
        trie, ann, obj, reqs, execu, arrivals=arrivals, capacity=4,
        policy="dynamic_load_aware", fleet_load=load,
        admission="feasibility")
    _assert_lanes_identical(host, comp)


def test_compiled_matches_host_priority_preempt():
    """Priority classes + preemption + predictive gating, load-aware."""
    trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(7)
    obj = Objective("max_acc", lat_cap=lat_q)
    specs = (SLOClass("hi", deadline_s=lat_q * 0.75, weight=4.0),
             SLOClass("lo", deadline_s=None, weight=1.0))
    cls = sample_classes(len(reqs), (0.4, 0.6), seed=7)
    host, comp = _both_lanes(
        trie, ann, obj, reqs, execu, arrivals=arrivals, capacity=3,
        policy="dynamic_load_aware", fleet_load=load,
        admission="predictive", classes=cls, class_specs=specs,
        preempt=True)
    _assert_lanes_identical(host, comp)


def test_compiled_matches_host_unit_calendar():
    """No load model (unit-rate calendar), plain dynamic policy."""
    trie, ann, execu, _, reqs, arrivals, lat_q = _serving_setup(19)
    obj = Objective("max_acc", lat_cap=lat_q)
    host, comp = _both_lanes(
        trie, ann, obj, reqs, execu, arrivals=arrivals, capacity=4,
        admission="feasibility")
    _assert_lanes_identical(host, comp)


# ----------------------------------------------------------------------
# streaming (constant-memory) path
# ----------------------------------------------------------------------
# the per-request result columns: only the materialized (stream=False)
# state carries them
RESULT_COLUMNS = {"roc", "rsc", "rct", "rdn", "rad", "ru", "rpc"}


@pytest.fixture
def final_state(monkeypatch):
    """The device state each compiled call's last epoch step returned,
    under ``"st"`` (the step is wrapped to record it)."""
    from repro.core import events_compiled

    build, seen = events_compiled._build_step, {}

    def recording(cfg):
        step = build(cfg)

        def recorded(st, cn, t_hi):
            seen["st"] = step(st, cn, t_hi)
            return seen["st"]
        return recorded

    monkeypatch.setattr(events_compiled, "_build_step", recording)
    # the wrapper cannot be lowered again by `engine_scope_maps`
    monkeypatch.setattr(events_compiled, "_ENGINE_CALLS", {})
    return seen


def _stream_scenario(name):
    """One serving scenario per calendar and fault lane: processor
    sharing, the token calendar, priority classes with preemption, and
    engine outages with stage failures."""
    trie, ann, execu, load, reqs, arrivals, lat_q = _serving_setup(
        7 if name == "priority" else 5)
    obj = Objective("max_acc", lat_cap=lat_q)
    kw = dict(arrivals=arrivals, capacity=4, policy="dynamic_load_aware",
              admission="feasibility", fleet_load=load)
    if name == "tokens":
        engines = sorted({m.engine for m in trie.template.models})
        kw.pop("fleet_load")
        kw["work_model"] = TokenWorkModel(
            engines={e: EngineTokenModel(e, t_weights_s=0.02, t_kv_s=0.002,
                                         t_flop_s=0.001, kv_capacity=2.0,
                                         prefill_tok_s=1e-4)
                     for e in engines},
            mean_service_s={e: 1.0 for e in engines},
            stage_tokens=lambda q, d, m: (float(100 + 37 * q % 400),
                                          float(20 + 13 * (q + d + m) % 40)))
    elif name == "priority":
        kw.update(capacity=3, admission="predictive", preempt=True,
                  classes=sample_classes(len(reqs), (0.4, 0.6), seed=7),
                  class_specs=(
                      SLOClass("hi", deadline_s=lat_q * 0.75, weight=4.0),
                      SLOClass("lo", deadline_s=None, weight=1.0)))
    elif name == "chaos":
        kw["faults"] = FaultSchedule(outages=((0, 1.0, 4.0),),
                                     stage_failure_rate=0.3, seed=3,
                                     max_retries=2)
    return trie, ann, obj, reqs, execu, kw


@pytest.mark.parametrize("scenario", ["ps", "tokens", "priority", "chaos"])
def test_stream_summary_matches_materialized_results(scenario, final_state):
    trie, ann, obj, reqs, execu, kw = _stream_scenario(scenario)
    res, stats = run_events_compiled(trie, ann, obj, reqs, execu, **kw)
    full = final_state["st"]
    summary, sstats = run_events_compiled(trie, ann, obj, reqs, execu,
                                          stream=True, **kw)
    # the stream state carries no per-request result columns
    assert RESULT_COLUMNS <= set(full)
    assert not RESULT_COLUMNS & set(final_state["st"])
    if scenario == "priority":
        assert stats.preemptions > 0
    if scenario == "chaos":
        assert stats.engine_outages == 1 and stats.stage_failures > 0
    # bit for bit what the materialized run's state holds
    assert summary["succeeded"] == int(full["nsc"]) == int(
        np.sum(full["rsc"])) == sum(r.success for r in res)
    assert summary["slo_violations"] == int(full["slo"]) == sum(
        r.slo_violated for r in res)
    assert summary["sketch"]["counts"] == np.asarray(full["hist"]).tolist()
    for key, acc in (("latency", "lw"), ("cost", "cw")):
        assert summary[key] == welford_finalize(
            tuple(float(x) for x in full[acc]))
    served = [r for r in res if r.outcome == "served"]
    assert served
    assert summary["n_requests"] == len(reqs)
    assert summary["served"] == len(served)
    assert summary["rejected"] == stats.rejected
    assert summary["shed"] == stats.shed
    assert summary["failed"] == stats.failed
    # Welford moments over the SERVED population, exact to rounding
    lats = np.array([r.total_lat for r in served])
    costs = np.array([r.total_cost for r in served])
    assert summary["latency"]["count"] == len(served)
    assert summary["latency"]["mean"] == pytest.approx(lats.mean(),
                                                       rel=1e-12)
    assert summary["latency"]["std"] == pytest.approx(lats.std(), rel=1e-9)
    assert summary["cost"]["mean"] == pytest.approx(costs.mean(), rel=1e-12)
    # sketch quantiles: the host sketch of the served latencies, which
    # gives the upper edge of the rank bin — at least the order statistic
    # min(floor(q n) + 1, n) it covers, at most one log-spaced bin (~3.3%)
    # above it
    sketch = QuantileSketch.log_spaced()
    sketch.add(lats)
    for q, key in ((0.5, "latency_p50"), (0.95, "latency_p95"),
                   (0.99, "latency_p99")):
        assert summary[key] == sketch.quantile(q)
        exact = float(np.sort(lats)[min(int(q * len(lats)), len(lats) - 1)])
        assert summary[key] >= exact - 1e-9
        assert summary[key] <= max(exact * 1.04, 1.1e-3)
    # constant-memory guarantee: no O(n) per-request host lists
    assert sstats.outcome == [] and sstats.preempt_count.size == 0
    # counters still drain
    assert (sstats.admitted, sstats.rejected, sstats.shed) == \
        (stats.admitted, stats.rejected, stats.shed)


def test_merge_stream_summaries_exact():
    trie, ann, execu, load, _, _, lat_q = _serving_setup(9)
    obj = Objective("max_acc", lat_cap=lat_q)
    rng = np.random.default_rng(9)
    shards = []
    all_res = []
    for shard_seed in (1, 2):
        n = 16
        reqs = rng.choice(100, n, replace=False)
        arrivals = poisson_arrivals(n, rate=3.0, seed=shard_seed)
        kw = dict(arrivals=arrivals, capacity=3,
                  policy="dynamic_load_aware", fleet_load=load,
                  admission="feasibility")
        s, _ = run_events_compiled(trie, ann, obj, reqs, execu,
                                   stream=True, **kw)
        shards.append(s)
        res, _ = run_events_compiled(trie, ann, obj, reqs, execu, **kw)
        all_res.extend(res)
    merged = merge_stream_summaries(shards[0], shards[1])
    served = [r for r in all_res if r.outcome == "served"]
    assert merged["n_requests"] == 32
    assert merged["served"] == len(served)
    assert merged["succeeded"] == sum(r.success for r in all_res)
    lats = np.array([r.total_lat for r in served])
    assert merged["latency"]["count"] == len(served)
    assert merged["latency"]["mean"] == pytest.approx(lats.mean(),
                                                      rel=1e-12)
    assert merged["latency"]["std"] == pytest.approx(lats.std(), rel=1e-9)


def test_empty_cohort_stream_summary():
    trie, ann, execu, _, _, _, _ = _serving_setup(13)
    summary, stats = run_events_compiled(
        trie, ann, Objective("max_acc"), np.zeros(0, dtype=np.int64),
        execu, arrivals=np.zeros(0), capacity=2, stream=True)
    assert summary["n_requests"] == 0 and summary["served"] == 0
    assert np.isnan(summary["latency_p99"])


# ----------------------------------------------------------------------
# dispatch plumbing and the host-only fence
# ----------------------------------------------------------------------
def test_run_events_rejects_compiled_kwargs_on_host_lane():
    trie, ann, execu, _, reqs, arrivals, _ = _serving_setup(3, n=4)
    with pytest.raises(TypeError, match="compiled=True"):
        run_events(trie, ann, Objective("max_acc"), reqs, execu,
                   arrivals=arrivals, epoch=64)


def test_compiled_rejects_host_only_features():
    trie, ann, execu, _, reqs, arrivals, _ = _serving_setup(3, n=4)
    obj = Objective("max_acc")

    class MyPolicy(AdmissionPolicy):
        """Custom subclass: host-only (cannot be distilled to a trace)."""
        name = "mine"

    with pytest.raises(NotImplementedError):
        run_events(trie, ann, obj, reqs, execu, arrivals=arrivals,
                   compiled=True, admission=MyPolicy())
    with pytest.raises(NotImplementedError):
        run_events(trie, ann, obj, reqs, execu, arrivals=arrivals,
                   compiled=True, load_probe=lambda t: {})

    class DuckLoad:
        """Duck-typed load model: host-only."""
        engines = {}

        def delays(self, inflight):
            return {}

        def slowdown(self, engine, n):
            return 1.0

    with pytest.raises(NotImplementedError):
        run_events(trie, ann, obj, reqs, execu, arrivals=arrivals,
                   compiled=True, policy="dynamic_load_aware",
                   fleet_load=DuckLoad())

    # the online estimator refresh loop needs per-completion host
    # observations — host lane only (a precomputed annotation_schedule
    # is the compiled-lane equivalent)
    from repro.core.estimators import OnlineEstimators, RefreshConfig
    D, M = trie.template.max_depth, trie.template.n_models
    est = OnlineEstimators.from_tables(
        np.full((D, M), 0.5), np.full((D, M), 0.01), np.ones((D, M)))
    with pytest.raises(NotImplementedError, match="refresh"):
        run_events(trie, ann, obj, reqs, execu, arrivals=arrivals,
                   compiled=True, refresh=RefreshConfig(est))
