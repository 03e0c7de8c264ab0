"""Fault-tolerant serving: checkpointed recovery vs restart-from-root.

ISSUE 9 acceptance benchmark.  One pinned `FaultSchedule` — four short
engine outages spread across the arrival window on the engine serving
the most DEEP (position >= 1) stages, plus seeded transient stage
failures — is replayed over the SAME open-arrival cohort three times:

- ``restart`` (host loop) — ``recovery="restart"``: outage victims
  requeue from the trie root, keeping only their spent cost.  The naive
  baseline every serving stack without stage checkpoints degrades to.
- ``checkpoint`` (host loop) — ``recovery="checkpoint"``: victims are
  checkpointed at their realized trie node with elapsed latency/cost
  budgets intact and resume from there once the engine returns.
- ``checkpoint`` (compiled) — the same schedule through the jitted
  epoch-batched engine; must match the host lane bitwise
  (outcome-for-outcome, timestamp-for-timestamp), and the outage
  transitions must add ZERO compiled programs — engine availability is
  a traced planner operand (the blocked-depth column), never a shape.

The outage targets deep stages deliberately: a victim on its FIRST
stage has realized node == root, so both recoveries are trivially
identical — the differential only bites when restart throws away real
progress.  The stage-failure draws are identical across lanes (same
seed), so retry/backoff churn cancels and the margin isolates the
recovery policy.

The benchmark FAILS if checkpointed recovery does not strictly beat
restart goodput — preserving realized progress across outages is the
point of the subsystem — or if any fault transition re-traces the
planner or the event engine.  Margins and fault-accounting stats land
in ``reports/bench/BENCH_chaos.json``.

    PYTHONPATH=src python -m benchmarks.chaos [--tiny]
"""
from __future__ import annotations

import argparse
import collections
import time

import numpy as np

from benchmarks.common import (
    enable_compile_cache,
    exact_ann,
    save_report,
    workload,
)
from benchmarks.open_arrival import make_fleet_load
from repro.core.controller import Objective
from repro.core.controller_jax import fleet_planner_cache_size
from repro.core.events import run_events
from repro.core.events_compiled import compiled_engine_cache_size
from repro.core.faults import FaultSchedule
from repro.core.runtime import make_workload_executor, summarize

STAGE_FAILURE_RATE = 0.03
MAX_RETRIES = 2
OUTAGE_S = 1.25            # per-outage duration (dyadic: 10/8)
OUTAGE_QS = (0.2, 0.4, 0.6, 0.8)   # arrival quantiles the downs land on


def _deep_hot_engine(wf, obj, reqs, arrivals, capacity, load):
    """Engine the outages target: whatever a fault-free replay leans on
    hardest for stages PAST the first.  Depth-0 victims checkpoint at
    the root, where restart and checkpoint coincide — deep stages are
    where the recovery policy actually differs."""
    trie, wl = workload(wf)
    res, _ = run_events(trie, exact_ann(wf), obj, reqs,
                        make_workload_executor(wl),
                        arrivals=arrivals, capacity=capacity,
                        policy="dynamic_load_aware", fleet_load=load,
                        admission="feasibility")
    used = collections.Counter(
        trie.template.models[m].engine for r in res for m in r.models[1:])
    return used.most_common(1)[0][0]


def _schedule(hot, arrivals, recovery):
    """Four short outages spread across the arrival window, plus seeded
    transient stage failures.  Down-times snap to the 1/8 grid so every
    lane shares one dyadic clock."""
    outages = tuple(
        (hot, float(np.floor(np.quantile(arrivals, q) * 8) / 8),
         float(np.floor(np.quantile(arrivals, q) * 8) / 8) + OUTAGE_S)
        for q in OUTAGE_QS)
    return FaultSchedule(outages=outages,
                         stage_failure_rate=STAGE_FAILURE_RATE,
                         seed=7, max_retries=MAX_RETRIES,
                         recovery=recovery)


def _lane(wf, obj, reqs, arrivals, capacity, load, faults, compiled=False):
    trie, wl = workload(wf)
    res, stats = run_events(trie, exact_ann(wf), obj, reqs,
                            make_workload_executor(wl),
                            arrivals=arrivals, capacity=capacity,
                            policy="dynamic_load_aware", fleet_load=load,
                            admission="feasibility", faults=faults,
                            compiled=compiled)
    return res, stats, summarize(res)


def run(wf: str = "nl2sql_8", n_requests: int = 160, rate: float = 2.0,
        capacity: int = 24):
    trie, wl = workload(wf)
    ann = exact_ann(wf)
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.9)))
    load = make_fleet_load(trie, wl)
    reqs = np.random.default_rng(0).choice(wl.n_requests, n_requests,
                                           replace=True)
    # dyadic arrivals keep every lane on the oracle's exact clock
    rng = np.random.default_rng(100)
    arrivals = np.cumsum(
        np.maximum(np.round(rng.exponential(1.0 / rate, n_requests) * 8),
                   1) / 8)
    hot = _deep_hot_engine(wf, obj, reqs, arrivals, capacity, load)

    t_total = time.perf_counter()
    _, rstats, restart = _lane(wf, obj, reqs, arrivals, capacity, load,
                               _schedule(hot, arrivals, "restart"))
    ckpt_fs = _schedule(hot, arrivals, "checkpoint")
    hres, cstats, ckpt = _lane(wf, obj, reqs, arrivals, capacity, load,
                               ckpt_fs)
    if cstats.engine_outages == 0 or cstats.checkpointed == 0:
        raise RuntimeError(
            "the outage windows never caught an in-flight stage — the "
            "chaos schedule is not exercising checkpointed recovery")

    # compiled lane: warm once, then re-run and pin zero retraces across
    # the outage transitions (mask is a traced operand, never a shape)
    _lane(wf, obj, reqs, arrivals, capacity, load, ckpt_fs, compiled=True)
    p0, e0 = fleet_planner_cache_size(), compiled_engine_cache_size()
    jres, jstats, jsum = _lane(wf, obj, reqs, arrivals, capacity, load,
                               ckpt_fs, compiled=True)
    retraces = (fleet_planner_cache_size() - p0,
                compiled_engine_cache_size() - e0)
    if any(r > 0 for r in retraces if r >= 0):
        raise RuntimeError(
            f"fault transitions re-traced (planner, engine) = {retraces} "
            "compiled programs — engine availability must stay a traced "
            "operand")
    if ([r.outcome for r in jres] != [r.outcome for r in hres]
            or jstats.done_t.tolist() != cstats.done_t.tolist()):
        raise RuntimeError(
            "compiled chaos lane diverged from the host loop — the "
            "differential guarantee is broken")

    margin = ckpt["goodput"] - restart["goodput"]
    if margin <= 0:
        raise RuntimeError(
            "checkpointed recovery did not beat restart-from-root "
            f"(margin {margin:+.4f}) — resuming from the realized trie "
            "node is the point of the subsystem")
    elapsed = time.perf_counter() - t_total

    rows = []
    for name, stats, summ in (("restart", rstats, restart),
                              ("checkpoint", cstats, ckpt),
                              ("checkpoint_compiled", jstats, jsum)):
        rows.append({
            "lane": name,
            "workflow": wf,
            "goodput": round(summ["goodput"], 4),
            "failed_rate": round(summ["failed_rate"], 4),
            "shed_rate": round(summ["shed_rate"], 4),
            "slo_violation_rate": round(summ["slo_violation_rate"], 4),
            "engine_outages": stats.engine_outages,
            "checkpointed": stats.checkpointed,
            "stage_failures": stats.stage_failures,
            "fault_retries": stats.fault_retries,
        })
    save_report("BENCH_chaos", {
        "schema": "bench_chaos/v1",
        "hot_engine": hot,
        "outages": [list(o) for o in ckpt_fs.outages],
        "stage_failure_rate": STAGE_FAILURE_RATE,
        "max_retries": MAX_RETRIES,
        "goodput_margin": round(margin, 4),
        "planner_retraces": retraces[0],
        "engine_retraces": retraces[1],
        "rows": rows,
    })
    return {
        "name": "chaos",
        "us_per_call": elapsed * 1e6 / max(len(rows), 1),
        "derived": (f"restart={restart['goodput']:.3f} "
                    f"checkpoint={ckpt['goodput']:.3f} "
                    f"margin={margin:+.3f} retraces={retraces}"),
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
              rate=3.0 if args.tiny else 2.0,
              capacity=10 if args.tiny else 24)
    for r in out["rows"]:
        print(f"{r['lane']:20s} goodput={r['goodput']:.3f} "
              f"failed={r['failed_rate']:.3f} "
              f"ckpt={r['checkpointed']} sfail={r['stage_failures']} "
              f"retries={r['fault_retries']}")
    print(out["derived"])


if __name__ == "__main__":
    enable_compile_cache()
    main()
