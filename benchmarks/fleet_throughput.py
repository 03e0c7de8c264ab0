"""Fleet runtime throughput: batched lockstep replanning vs the host loop.

For each batch size (== slot capacity of the serving fleet), serves the
same cohort with sequential per-request host replanning
(`run_cohort(engine="scalar")`, the paper's Table-3 setting) and with the
fleet runtime (`run_fleet`, one jitted planner call per lockstep round)
under each planner dispatch variant — the pre-fusion ``dense`` program,
the ``fused`` XLA mirror (default serving path), and the ``pallas`` kernel
(interpret mode on CPU) — and reports per-request replanning latency plus
end-to-end control-plane wall time.  The fleet planner is warmed once per
(shape, variant) so compile time is reported separately and excluded from
the steady-state comparison (a serving fleet compiles once per cohort
shape, then replans millions of times).  Both paths report the MIN over
repeats: the container has no isolated cores and XLA dispatch has a heavy
scheduling tail, so the minimum is the comparable noise-floor statistic.

    PYTHONPATH=src python benchmarks/fleet_throughput.py [--tiny]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import (
    enable_compile_cache,
    exact_ann,
    save_report,
    workload,
)
from repro.core.controller import Objective
from repro.core.fleet import run_fleet
from repro.core.runtime import make_workload_executor, run_cohort

FULL_BATCHES = (8, 32, 128, 256)
TINY_BATCHES = (8, 32)
VARIANTS = ("dense", "fused", "pallas")


def run(wf: str = "nl2sql_8", batches=FULL_BATCHES, repeats: int = 7,
        variants=VARIANTS):
    trie, wl = workload(wf)
    ann = exact_ann(wf)
    execu = make_workload_executor(wl)
    obj = Objective(
        "max_acc",
        cost_cap=float(np.quantile(ann.cost[trie.terminal], 0.5)),
        lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.8)),
    )
    rng = np.random.default_rng(0)
    rows = []
    t_total = time.perf_counter()
    for B in batches:
        reqs = rng.choice(wl.n_requests, B, replace=True)

        host_walls, host_replans = [], []
        for _ in range(repeats):
            t0 = time.perf_counter()
            host = run_cohort(trie, ann, obj, reqs, execu, engine="scalar")
            host_walls.append(time.perf_counter() - t0)
            host_replans.append(
                float(np.mean([r.replan_overhead_s for r in host]) * 1e6))
        host_replan_us = float(np.min(host_replans))
        host_wall_s = float(np.min(host_walls))

        for variant in variants:
            t0 = time.perf_counter()
            run_fleet(trie, ann, obj, reqs, execu,
                      plan_variant=variant)  # warm: jit compile
            warm_wall = time.perf_counter() - t0
            fleet_walls, fleet_replans = [], []
            stats = None
            for _ in range(repeats):
                t0 = time.perf_counter()
                flt, stats = run_fleet(trie, ann, obj, reqs, execu,
                                       plan_variant=variant)
                fleet_walls.append(time.perf_counter() - t0)
                fleet_replans.append(
                    float(np.mean([r.replan_overhead_s for r in flt]) * 1e6))
            fleet_replan_us = float(np.min(fleet_replans))
            rows.append({
                "workflow": wf,
                "batch": B,
                "variant": variant,
                "rounds": stats.rounds,
                "host_replan_us_per_request": round(host_replan_us, 1),
                "fleet_replan_us_per_request": round(fleet_replan_us, 1),
                "replan_speedup": round(
                    host_replan_us / max(fleet_replan_us, 1e-9), 1),
                "fleet_compile_s": round(warm_wall, 3),
                "host_wall_s": round(host_wall_s, 4),
                "fleet_wall_s": round(float(np.min(fleet_walls)), 4),
            })
    elapsed = time.perf_counter() - t_total
    save_report("fleet_throughput", rows)
    best = max(r["replan_speedup"] for r in rows)
    return {
        "name": "fleet_throughput",
        "us_per_call": elapsed * 1e6 / max(len(rows), 1),
        "derived": f"max_replan_speedup={best:.1f}x",
        "rows": rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small trie, two batch sizes, 1 repeat")
    ap.add_argument("--workflow", default=None)
    args = ap.parse_args()
    wf = args.workflow or ("nl2sql_2" if args.tiny else "nl2sql_8")
    out = run(wf=wf,
              batches=TINY_BATCHES if args.tiny else FULL_BATCHES,
              repeats=1 if args.tiny else 3)
    for r in out["rows"]:
        print(f"{r['workflow']:9s} batch={r['batch']:4d} "
              f"{r['variant']:7s} rounds={r['rounds']:2d} "
              f"host={r['host_replan_us_per_request']:9.1f}us/req "
              f"fleet={r['fleet_replan_us_per_request']:7.1f}us/req "
              f"({r['replan_speedup']:6.1f}x)  "
              f"wall host={r['host_wall_s']:.4f}s "
              f"fleet={r['fleet_wall_s']:.4f}s "
              f"(compile {r['fleet_compile_s']:.2f}s)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
