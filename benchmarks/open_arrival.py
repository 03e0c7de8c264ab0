"""Open-arrival serving: goodput/p99 vs arrival rate (event-driven runtime).

Sweeps a Poisson arrival rate over the event-driven open-arrival runtime
(`repro.core.events.run_events`) with self-induced load coupling: requests
arrive mid-flight, join the batched replan, queue for admission when every
slot is busy, and share engine capacity with whatever overlaps them in
wall-clock time.  SLO latency is measured from each request's arrival, so
the curves show the classic serving knee — goodput collapses and p99
explodes once the offered load crosses what the engines absorb.

The planner batch is pinned at the slot capacity and the device-resident
slot-state scatters at a fixed width, so the whole sweep must compile the
planner program set exactly once (during the first rate); the benchmark
asserts zero growth afterwards via
`controller_jax.fleet_planner_cache_size` and fails loudly on re-tracing
(that is the regression it exists to catch).

Every rate also replays through the jitted epoch-batched engine
(`run_events(compiled=True)`, see docs/EVENT_ENGINE.md) with an
outcome-level consistency check, recording per-rate host-vs-compiled
event throughput; `benchmarks/trace_replay.py` carries the hard >=10x
floor at trace scale.

With ``--devices N`` (N > 1) the highest rate additionally replays
through the lane-sharded compiled engine on N virtual CPU devices
(provisioned below before jax loads), with the same outcome-equality
bar and a zero-retrace guard; the row gains ``sharded_events_per_s``.

    PYTHONPATH=src python -m benchmarks.open_arrival [--tiny] \\
        [--devices N]
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _devices_arg(argv) -> int | None:
    """Peek ``--devices`` out of argv (pre-argparse: the XLA device count
    must be pinned BEFORE anything imports jax)."""
    for i, a in enumerate(argv):
        val = None
        if a == "--devices" and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith("--devices="):
            val = a.split("=", 1)[1]
        if val is not None:
            return int(val)
    return None


# only peek argv when running AS this benchmark — other modules import
# make_fleet_load from here and own their own --devices conventions
_DEVICES = _devices_arg(sys.argv[1:]) if __name__ == "__main__" else None
if _DEVICES and _DEVICES > 1 and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={_DEVICES}").strip()

import numpy as np  # noqa: E402

from benchmarks.common import (  # noqa: E402
    enable_compile_cache,
    exact_ann,
    save_report,
    workload,
)
from repro.core.controller import Objective  # noqa: E402
from repro.core.controller_jax import fleet_planner_cache_size  # noqa: E402
from repro.core.events import run_events  # noqa: E402
from repro.core.events_compiled import (  # noqa: E402
    compiled_engine_cache_size,
)
from repro.core.runtime import make_workload_executor, summarize  # noqa: E402
from repro.core.workload import poisson_arrivals  # noqa: E402
from repro.serving.loadsim import EngineLoadModel, FleetLoadModel  # noqa: E402

FULL_RATES = (0.5, 1.0, 2.0, 4.0, 8.0, 16.0)   # requests/second
TINY_RATES = (1.0, 4.0, 16.0)


def make_fleet_load(trie, wl, concurrency: int = 4) -> FleetLoadModel:
    """Self-induced load model for a preset: per-engine processor sharing
    with mean service times measured from the workload's own stage tables."""
    engines = sorted({m.engine for m in trie.template.models})
    mean_service = {}
    for e in engines:
        ms = [j for j, m in enumerate(trie.template.models) if m.engine == e]
        mean_service[e] = float(np.mean(wl.lat[:, :, ms]))
    return FleetLoadModel(
        engines={e: EngineLoadModel(e, concurrency=concurrency, jitter=0.0)
                 for e in engines},
        mean_service_s=mean_service,
    )


def run(wf: str = "nl2sql_8", rates=FULL_RATES, n_requests: int = 192,
        capacity: int = 32, devices: int | None = None):
    trie, wl = workload(wf)
    ann = exact_ann(wf)
    execu = make_workload_executor(wl)
    obj = Objective(
        "max_acc",
        cost_cap=float(np.quantile(ann.cost[trie.terminal], 0.5)),
        lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.8)),
    )
    load = make_fleet_load(trie, wl)
    reqs = np.random.default_rng(0).choice(wl.n_requests, n_requests,
                                           replace=True)
    cache0 = None
    rows = []
    # warm the compiled engine once (same cohort shape for every rate ->
    # one XLA program) so per-rate compiled timings are steady-state
    run_events(trie, ann, obj, reqs, execu,
               arrivals=poisson_arrivals(n_requests, rates[0], seed=1),
               capacity=capacity, policy="dynamic_load_aware",
               fleet_load=load, compiled=True)
    t_total = time.perf_counter()
    for rate in rates:
        arr = poisson_arrivals(n_requests, rate, seed=1)
        t0 = time.perf_counter()
        res, stats = run_events(
            trie, ann, obj, reqs, execu,
            arrivals=arr, capacity=capacity,
            policy="dynamic_load_aware", fleet_load=load,
        )
        host_wall = time.perf_counter() - t0
        if cache0 is None:
            # the first rate compiles the device-resident program set once
            # (fixed-width slot scatter + capacity-shaped replan); nothing
            # later in the sweep may add to it
            cache0 = fleet_planner_cache_size()
        # compiled lane: same rate through the epoch-batched engine, with
        # an outcome-level consistency check against the host loop
        t0 = time.perf_counter()
        cres, cstats = run_events(
            trie, ann, obj, reqs, execu,
            arrivals=arr, capacity=capacity,
            policy="dynamic_load_aware", fleet_load=load, compiled=True,
        )
        comp_wall = time.perf_counter() - t0
        if any(a.outcome != b.outcome or a.models != b.models
               for a, b in zip(res, cres)):
            raise RuntimeError(
                f"compiled engine disagrees with the host loop at "
                f"rate={rate}/s — run the differential oracle suite")
        sharded = None
        if devices and devices > 1 and rate == rates[-1]:
            # lane-sharded replay of the hottest rate: same dispositions,
            # one compiled program, recorded throughput
            run_events(trie, ann, obj, reqs, execu, arrivals=arr,
                       capacity=capacity, policy="dynamic_load_aware",
                       fleet_load=load, compiled=True, devices=devices)
            sc0 = compiled_engine_cache_size()
            t0 = time.perf_counter()
            sres, sstats = run_events(
                trie, ann, obj, reqs, execu, arrivals=arr,
                capacity=capacity, policy="dynamic_load_aware",
                fleet_load=load, compiled=True, devices=devices)
            sh_wall = time.perf_counter() - t0
            if sc0 >= 0 and compiled_engine_cache_size() != sc0:
                raise RuntimeError(
                    f"sharded engine re-traced on a replay at "
                    f"devices={devices} — device count must be the only "
                    "static axis")
            if any(a.outcome != b.outcome or a.models != b.models
                   for a, b in zip(cres, sres)):
                raise RuntimeError(
                    f"sharded engine (devices={devices}) disagrees with "
                    f"the single-device run at rate={rate}/s")
            sharded = round(sstats.events / sh_wall, 1)
        s = summarize(res)
        rows.append({
            "workflow": wf,
            "rate_rps": rate,
            "goodput": round(s["goodput"], 4),
            "accuracy": round(s["accuracy"], 4),
            "p99_lat_s": round(s["p99_lat"], 3),
            "mean_lat_s": round(s["mean_lat"], 3),
            "slo_violation_rate": round(s["slo_violation_rate"], 4),
            "mean_queue_wait_s": round(stats.mean_queue_wait_s, 3),
            "peak_occupancy": max(stats.peak_occupancy.values()),
            "events": stats.events,
            "replans": stats.replans,
            "replan_us_per_planned_request": round(
                stats.replan_s_per_planned_request * 1e6, 1),
            "host_events_per_s": round(stats.events / host_wall, 1),
            "compiled_events_per_s": round(cstats.events / comp_wall, 1),
            "compiled_speedup": round(
                (cstats.events / comp_wall) / (stats.events / host_wall), 2),
            **({"sharded_devices": devices,
                "sharded_events_per_s": sharded}
               if sharded is not None else {}),
        })
    cache1 = fleet_planner_cache_size()
    retraces = (cache1 - cache0) if cache0 >= 0 and cache1 >= 0 else -1
    if retraces > 0:
        raise RuntimeError(
            f"fleet planner re-traced {retraces} times across the sweep — "
            "the events runtime must pin its replan batch at slot capacity "
            "and its state scatters at the fixed update width")
    elapsed = time.perf_counter() - t_total
    save_report("open_arrival", rows)
    return {
        "name": "open_arrival",
        "us_per_call": elapsed * 1e6 / max(len(rows), 1),
        "derived": (f"planner_compiles={retraces} "
                    f"goodput@{rates[0]}rps={rows[0]['goodput']:.2f} "
                    f"goodput@{rates[-1]}rps={rows[-1]['goodput']:.2f} "
                    f"compiled_speedup={max(r['compiled_speedup'] for r in rows):.1f}x"),
        "rows": rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small trie, 3 rates, small cohort")
    ap.add_argument("--workflow", default=None)
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the compiled lane of the highest rate "
                         "over N virtual CPU devices")
    args = ap.parse_args()
    wf = args.workflow or ("nl2sql_2" if args.tiny else "nl2sql_8")
    out = run(wf=wf,
              rates=TINY_RATES if args.tiny else FULL_RATES,
              n_requests=48 if args.tiny else 192,
              capacity=16 if args.tiny else 32,
              devices=_DEVICES)
    print(out["derived"])
    for r in out["rows"]:
        sh = (f" sharded@{r['sharded_devices']}dev="
              f"{r['sharded_events_per_s']:.0f}ev/s"
              if "sharded_events_per_s" in r else "")
        print(f"{r['workflow']:9s} rate={r['rate_rps']:5.1f}/s "
              f"goodput={r['goodput']:.3f} p99={r['p99_lat_s']:7.2f}s "
              f"wait={r['mean_queue_wait_s']:7.2f}s "
              f"peak_occ={r['peak_occupancy']:3d} "
              f"events={r['events']:4d} replans={r['replans']:4d} "
              f"({r['replan_us_per_planned_request']:.0f}us/req) "
              f"compiled={r['compiled_speedup']:.1f}x{sh}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
