"""Priority-class preemptive serving: per-class goodput/p99 vs load.

Sweeps a Poisson arrival rate over the event-driven open-arrival runtime
(`repro.core.events.run_events`) with a 25/75 interactive/batch mix
(`repro.core.workload.interactive_batch_classes`: the interactive class
carries a tight deadline and 4x weighted-processor-sharing share), under
the feasibility gate, with slot **preemption** toggled off and on.  With
preemption, a queued interactive request may pause the lowest-value
in-flight batch stage — checkpointed at its realized trie node and
resumed later with its remaining work intact — so interactive tail
latency stops being hostage to batch residency times.

The sweep locates the **knee** of the preemption-off overall goodput
curve and asserts the ISSUE-5 acceptance criterion in the overload region
(>= 2x that knee): at some swept overload rate, preemption strictly
improves interactive-class p99 while batch-class goodput stays within 10%
of the no-preemption run.  Work-conserving weighted PS already gives the
interactive class full service rate while engines have spare capacity, so
the win typically appears a step past 2x the knee, once slots — not
engine share — are the binding constraint; and far past it the trade
turns against batch (preemption is a priority mechanism, not free
capacity).  The per-rate rows keep both edges honest.

The whole sweep — classes, weights, per-class deadlines, preemption —
must reuse the capacity-shaped resident planner program set: per-class
deadlines ride per-lane elapsed shifts against one traced cap scalar, so
the benchmark extends the zero-retrace guard to the priority path and
fails loudly on growth.

    PYTHONPATH=src python -m benchmarks.priority [--tiny]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.admission import find_knee
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
from repro.core.runtime import (
    make_workload_executor,
    summarize,
    summarize_by_class,
)
from repro.core.workload import (
    interactive_batch_classes,
    poisson_arrivals,
    sample_classes,
)

FULL_RATES = (0.5, 1.0, 2.0, 4.0, 8.0, 16.0)   # requests/second
TINY_RATES = (1.0, 4.0, 16.0)
INTERACTIVE_FRACTION = 0.25
DEADLINE_QUANTILE = 0.6   # interactive SLO: 0.6 quantile of plan latency


def run(wf: str = "nl2sql_2", rates=FULL_RATES, n_requests: int = 192,
        capacity: int = 8, concurrency: int = 2):
    trie, wl = workload(wf)
    ann = exact_ann(wf)
    execu = make_workload_executor(wl)
    term = trie.terminal
    obj = Objective(
        "max_acc",
        cost_cap=float(np.quantile(ann.cost[term], 0.5)),
        lat_cap=float(np.quantile(ann.lat[term], 0.8)),
    )
    load = make_fleet_load(trie, wl, concurrency=concurrency)
    reqs = np.random.default_rng(0).choice(wl.n_requests, n_requests,
                                           replace=True)
    specs = interactive_batch_classes(
        float(np.quantile(ann.lat[term], DEADLINE_QUANTILE)))
    cls = sample_classes(n_requests, (INTERACTIVE_FRACTION,
                                      1.0 - INTERACTIVE_FRACTION), seed=3)

    cache0 = None
    rows = []
    by_rate: dict[bool, dict[float, dict]] = {False: {}, True: {}}
    t_total = time.perf_counter()
    for rate in rates:
        arr = poisson_arrivals(n_requests, rate, seed=1)
        for pre in (False, True):
            res, stats = run_events(
                trie, ann, obj, reqs, execu,
                arrivals=arr, capacity=capacity,
                policy="dynamic_load_aware", fleet_load=load,
                admission="feasibility", classes=cls, class_specs=specs,
                preempt=pre,
            )
            if cache0 is None:
                # the first run compiles the device-resident program set
                # once; every later (rate, preempt) combination — classes,
                # weights, per-class deadlines included — must reuse it
                cache0 = fleet_planner_cache_size()
            s = summarize(res)
            by = summarize_by_class(res, stats.class_of, specs)
            by_rate[pre][rate] = {"overall": s, "by_class": by,
                                  "stats": stats}
            rows.append({
                "workflow": wf,
                "rate_rps": rate,
                "preempt": pre,
                "goodput": round(s["goodput"], 4),
                "interactive_goodput": round(by["interactive"]["goodput"], 4),
                "interactive_p99_s": round(by["interactive"]["p99_lat"], 3),
                "batch_goodput": round(by["batch"]["goodput"], 4),
                "batch_p99_s": round(by["batch"]["p99_lat"], 3),
                "shed_rate": round(s["shed_rate"], 4),
                "reject_rate": round(s["reject_rate"], 4),
                "preemptions": stats.preemptions,
                "resumed": stats.resumed,
                "preempt_rate": round(
                    stats.preemptions / max(stats.admitted, 1), 4),
                "events": stats.events,
                "replans": stats.replans,
            })

    cache1 = fleet_planner_cache_size()
    retraces = (cache1 - cache0) if cache0 >= 0 and cache1 >= 0 else -1
    if retraces > 0:
        raise RuntimeError(
            f"fleet planner re-traced {retraces} times across the priority "
            "sweep — per-class deadlines/weights must ride the existing "
            "capacity-shaped lanes, not add compiled specializations")

    # acceptance: at >= 2x the (preemption-off) knee, preemption improves
    # interactive p99 with batch goodput within 10%.  Weighted PS alone
    # already protects the interactive class at moderate overload (its
    # work-conserving share gives interactive full rate while the engine
    # has spare capacity), so the first rate past 2x the knee may show no
    # preemption headroom; the claim is that SOME overload rate >= 2x the
    # knee does — scan the overload region and fail only if none qualify.
    off_goodput = {r: by_rate[False][r]["overall"]["goodput"] for r in rates}
    knee = find_knee(rates, off_goodput)
    overload = [r for r in rates if r >= 2.0 * knee]
    if not overload:
        raise RuntimeError(
            f"rate sweep {rates} never reaches 2x the knee ({knee} rps) — "
            "extend the sweep so the preemption claim is actually tested")
    probe = None
    for r in overload:
        p99_off = by_rate[False][r]["by_class"]["interactive"]["p99_lat"]
        p99_on = by_rate[True][r]["by_class"]["interactive"]["p99_lat"]
        b_off = by_rate[False][r]["by_class"]["batch"]["goodput"]
        b_on = by_rate[True][r]["by_class"]["batch"]["goodput"]
        if (by_rate[True][r]["stats"].preemptions > 0
                and p99_on < p99_off and b_on >= 0.9 * b_off):
            probe = r
            break
    if probe is None:
        raise RuntimeError(
            f"no overload rate >= 2x the knee ({knee} rps) shows preemption "
            "improving interactive p99 with batch goodput within 10% — "
            "the preemption path stopped paying for itself: "
            + "; ".join(
                f"{r}rps p99 "
                f"{by_rate[True][r]['by_class']['interactive']['p99_lat']:.2f}"
                f"/{by_rate[False][r]['by_class']['interactive']['p99_lat']:.2f}"
                f" batch "
                f"{by_rate[True][r]['by_class']['batch']['goodput']:.3f}"
                f"/{by_rate[False][r]['by_class']['batch']['goodput']:.3f}"
                for r in overload))
    p99_off = by_rate[False][probe]["by_class"]["interactive"]["p99_lat"]
    p99_on = by_rate[True][probe]["by_class"]["interactive"]["p99_lat"]
    b_off = by_rate[False][probe]["by_class"]["batch"]["goodput"]
    b_on = by_rate[True][probe]["by_class"]["batch"]["goodput"]

    elapsed = time.perf_counter() - t_total
    save_report("priority", rows)
    return {
        "name": "priority",
        "us_per_call": elapsed * 1e6 / max(len(rows), 1),
        "derived": (f"planner_compiles={retraces} knee={knee}rps "
                    f"interactive_p99@{probe}rps={p99_on:.2f}/{p99_off:.2f}s "
                    f"batch_goodput={b_on:.3f}/{b_off:.3f}"),
        "rows": rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: 3 rates, small cohort")
    ap.add_argument("--workflow", default=None)
    args = ap.parse_args()
    out = run(wf=args.workflow or "nl2sql_2",
              rates=TINY_RATES if args.tiny else FULL_RATES,
              n_requests=48 if args.tiny else 192)
    print(out["derived"])
    for r in out["rows"]:
        print(f"{r['workflow']:9s} rate={r['rate_rps']:5.1f}/s "
              f"preempt={str(r['preempt']):5s} "
              f"goodput={r['goodput']:.3f} "
              f"int(gp={r['interactive_goodput']:.3f} "
              f"p99={r['interactive_p99_s']:6.2f}s) "
              f"batch(gp={r['batch_goodput']:.3f}) "
              f"pre={r['preemptions']:3d} res={r['resumed']:3d} "
              f"shed={r['shed_rate']:.3f} rej={r['reject_rate']:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
