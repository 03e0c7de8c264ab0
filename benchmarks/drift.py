"""Online estimator refresh under drift: frozen vs refreshed annotations.

ISSUE 8 acceptance benchmark.  Two drift schedules the offline
annotations cannot see:

- ``engine_slowdown`` — the hottest engine's stage latency steps up by
  ``SLOWDOWN`` at the half-way point (`loadsim.step_slowdown` through
  `make_workload_executor`).  Frozen annotations keep planning deep
  repair chains that now blow the latency cap; the refresh loop's
  latency posteriors absorb the inflated stage times, the
  `TrieAnnotator` republishes, and the planner falls back to shallow
  in-SLO plans.
- ``quality_regression`` — the most-dispatched model starts failing
  every invocation at the half-way point.  Frozen keeps routing through
  the dead model; the refresh loop's Beta posteriors collapse that
  cell's accuracy and the planner routes around it.

Both lanes start from the SAME posterior-derived annotation set (so the
only difference is whether the estimators keep learning), run the host
event loop (`run_events(refresh=...)` is host-only; posterior updates
need per-completion observations), and record goodput side by side.
The benchmark FAILS if online refresh does not strictly beat frozen
goodput under the engine-slowdown schedule — that margin is the point
of the subsystem — and records both margins in
``reports/bench/BENCH_drift.json``.  A zero-retrace guard pins that the
refresh loop's annotation-version swaps add no compiled programs.

    PYTHONPATH=src python -m benchmarks.drift [--tiny]
"""
from __future__ import annotations

import argparse
import collections
import time

import numpy as np

from benchmarks.common import (
    enable_compile_cache,
    profile,
    save_report,
    workload,
)
from benchmarks.open_arrival import make_fleet_load
from repro.core.controller import Objective
from repro.core.controller_jax import fleet_planner_cache_size
from repro.core.estimators import (
    OnlineEstimators,
    RefreshConfig,
    TrieAnnotator,
)
from repro.core.events import run_events
from repro.core.runtime import make_workload_executor, summarize
from repro.core.workload import poisson_arrivals
from repro.serving.loadsim import step_slowdown

SLOWDOWN = 4.0
COVERAGE = 0.2          # offline profiling coverage seeding the priors


def _seed_estimators(wf: str):
    trie, wl = workload(wf)
    # count_weight=0: trust the offline profile's MEANS but not its bulk
    # (a production profile's thousands of telemetry rows would otherwise
    # pin the posteriors and average the drift away)
    return OnlineEstimators.from_profile(trie, profile(wf, COVERAGE),
                                         prior_strength=8.0,
                                         count_weight=0.0)


def _hot_choices(wf: str, obj, reqs, arrivals, capacity, load):
    """(engine, model) the drift targets: whatever the frozen planner
    leans on hardest in a drift-free replay."""
    trie, wl = workload(wf)
    ann0 = TrieAnnotator(trie, _seed_estimators(wf)).annotations()
    res, _ = run_events(trie, ann0, obj, reqs,
                        make_workload_executor(wl),
                        arrivals=arrivals, capacity=capacity,
                        policy="dynamic_load_aware", fleet_load=load,
                        admission="feasibility")
    used = collections.Counter(m for r in res for m in r.models)
    hot_model = used.most_common(1)[0][0]
    return trie.template.models[hot_model].engine, hot_model


def _lane(wf, obj, reqs, arrivals, capacity, load, executor, refresh):
    """One serving replay; returns (summary, stats)."""
    trie, wl = workload(wf)
    est = _seed_estimators(wf)
    ann0 = TrieAnnotator(trie, est).annotations()
    kw = dict(arrivals=arrivals, capacity=capacity,
              policy="dynamic_load_aware", fleet_load=load,
              admission="feasibility")
    if refresh is not None:
        kw["refresh"] = RefreshConfig(est, interval=refresh["interval"],
                                      decay=refresh["decay"])
    res, stats = run_events(trie, ann0, obj, reqs, executor, **kw)
    return summarize(res), stats


def run(wf: str = "nl2sql_8", n_requests: int = 160, rate: float = 2.0,
        capacity: int = 24, interval: float = 2.0, decay: float = 0.8):
    trie, wl = workload(wf)
    ann0 = TrieAnnotator(trie, _seed_estimators(wf)).annotations()
    # cap at the 0.9 quantile of frozen terminal latency: tight enough
    # that the slowdown pushes deep plans out of SLO, loose enough that
    # honest (refreshed) annotations leave shallow in-SLO alternatives
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann0.lat[trie.terminal], 0.9)))
    load = make_fleet_load(trie, wl)
    reqs = np.random.default_rng(0).choice(wl.n_requests, n_requests,
                                           replace=True)
    arrivals = poisson_arrivals(n_requests, rate, seed=1)
    t_half = float(arrivals[n_requests // 2])
    hot_engine, hot_model = _hot_choices(wf, obj, reqs, arrivals, capacity,
                                         load)
    refresh = {"interval": interval, "decay": decay}

    def quality_executor():
        """Hot model fails every invocation from t_half on."""
        base = make_workload_executor(wl)

        def ex(q, d, m, t):
            s, c, lat = base(q, d, m, t)
            if m == hot_model and t >= t_half:
                s = False
            return s, c, lat

        return ex

    scenarios = {
        "engine_slowdown": lambda: make_workload_executor(
            wl, step_slowdown(t_half, SLOWDOWN, engine=hot_engine)),
        "quality_regression": quality_executor,
    }
    rows = []
    t_total = time.perf_counter()
    for name, mk in scenarios.items():
        frozen, _ = _lane(wf, obj, reqs, arrivals, capacity, load,
                          mk(), None)
        cache0 = fleet_planner_cache_size()
        live, lstats = _lane(wf, obj, reqs, arrivals, capacity, load,
                             mk(), refresh)
        cache1 = fleet_planner_cache_size()
        retraces = (cache1 - cache0) if cache0 >= 0 and cache1 >= 0 else -1
        if retraces > 0:
            raise RuntimeError(
                f"refresh republish re-traced the planner {retraces} "
                "times — annotation swaps must be pure buffer "
                "substitutions")
        if lstats.refreshes == 0:
            raise RuntimeError(
                f"{name}: the refresh loop never republished — the drift "
                "harness is not exercising the estimators")
        margin = live["goodput"] - frozen["goodput"]
        rows.append({
            "scenario": name,
            "workflow": wf,
            "drift_t": round(t_half, 3),
            "hot_engine": hot_engine,
            "hot_model": hot_model,
            "frozen_goodput": round(frozen["goodput"], 4),
            "refresh_goodput": round(live["goodput"], 4),
            "goodput_margin": round(margin, 4),
            "frozen_accuracy": round(frozen["accuracy"], 4),
            "refresh_accuracy": round(live["accuracy"], 4),
            "frozen_slo_violation_rate": round(
                frozen["slo_violation_rate"], 4),
            "refresh_slo_violation_rate": round(
                live["slo_violation_rate"], 4),
            "refreshes": lstats.refreshes,
            "planner_retraces": retraces,
        })
    slow = next(r for r in rows if r["scenario"] == "engine_slowdown")
    if slow["goodput_margin"] <= 0:
        raise RuntimeError(
            "online refresh did not beat frozen annotations under engine "
            f"slowdown (margin {slow['goodput_margin']:+.4f}) — the "
            "estimator refresh subsystem is not earning its keep")
    elapsed = time.perf_counter() - t_total
    save_report("BENCH_drift", {
        "schema": "bench_drift/v1",
        "slowdown_factor": SLOWDOWN,
        "refresh": refresh,
        "rows": rows,
    })
    return {
        "name": "drift",
        "us_per_call": elapsed * 1e6 / max(len(rows), 1),
        "derived": " ".join(
            f"{r['scenario']}: frozen={r['frozen_goodput']:.3f} "
            f"refresh={r['refresh_goodput']:.3f} "
            f"margin={r['goodput_margin']:+.3f}" for r in rows),
        "rows": rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small trie, small cohort")
    ap.add_argument("--workflow", default=None)
    args = ap.parse_args()
    wf = args.workflow or ("nl2sql_2" if args.tiny else "nl2sql_8")
    out = run(wf=wf,
              n_requests=48 if args.tiny else 160,
              rate=2.0, capacity=16 if args.tiny else 24,
              interval=1.0 if args.tiny else 2.0)
    for r in out["rows"]:
        print(f"{r['scenario']:20s} frozen={r['frozen_goodput']:.3f} "
              f"refresh={r['refresh_goodput']:.3f} "
              f"margin={r['goodput_margin']:+.3f} "
              f"refreshes={r['refreshes']} "
              f"(drift at t={r['drift_t']:.1f}s, "
              f"hot={r['hot_engine']}/m{r['hot_model']})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
