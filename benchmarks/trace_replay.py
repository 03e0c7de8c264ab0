"""Trace replay at 1M requests: compiled event engine vs the host loop.

Replays a recorded-arrival trace (bootstrap-extended to the target cohort
size by `repro.core.workload.trace_arrivals`) through BOTH open-arrival
lanes at the MathQA preset:

- the PR 5 host event loop (`repro.core.events.run_events`), timed on a
  prefix of the trace — the per-event Python dispatch makes the full 1M
  cohort impractical, which is exactly the point of this benchmark;
- the jitted epoch-batched engine (`repro.core.events_compiled`) in
  ``stream=True`` mode on the full trace, where per-request columns stay
  on device and the host only drains O(1) scalars + a fixed-size
  quantile histogram per run.

Before timing, the two lanes are differentially checked on the host
prefix (bit-identical outcomes/completion times — the same bar as the
oracle sweep in `tests/test_oracle_differential.py`).  The headline
metric is event throughput (events/s); the run FAILS unless the compiled
engine clears ``MIN_SPEEDUP``x the host loop, and unless the streaming
stats are constant-memory (no O(n) host-side lists).  Results land in
``reports/bench/BENCH_replay.json``.

With ``--devices 1,2,4,8`` the run adds a lane-sharded sweep: a prefix of
the trace replays through the sharded engine at each device count, the
streaming summary is checked for EXACT equality against the single-device
run (shard count must never change a disposition or a sketch bin), a
zero-retrace guard pins one compiled program per device count, and the
per-device-count throughput lands in the report under ``"sharded"``.  The
virtual CPU devices are provisioned automatically (``XLA_FLAGS=
--xla_force_host_platform_device_count``, set below before jax loads).

    PYTHONPATH=src python -m benchmarks.trace_replay [--tiny] \\
        [--devices 1,2,4,8]
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _devices_arg(argv) -> tuple[int, ...]:
    """Peek ``--devices`` out of argv (pre-argparse: the XLA device count
    must be pinned BEFORE anything imports jax, which the repro imports
    below do transitively)."""
    for i, a in enumerate(argv):
        val = None
        if a == "--devices" and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith("--devices="):
            val = a.split("=", 1)[1]
        if val is not None:
            return tuple(int(x) for x in val.split(",") if x.strip())
    return ()


_DEVICES = _devices_arg(sys.argv[1:])
if _DEVICES and max(_DEVICES) > 1 and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={max(_DEVICES)}").strip()

import numpy as np  # noqa: E402

from benchmarks.common import (  # noqa: E402
    enable_compile_cache,
    exact_ann,
    save_report,
    workload,
)
from benchmarks.open_arrival import make_fleet_load  # noqa: E402
from repro.core.controller import Objective  # noqa: E402
from repro.core.events import run_events  # noqa: E402
from repro.core.events_compiled import (  # noqa: E402
    compiled_engine_cache_size,
    run_events_compiled,
)
from repro.core.runtime import make_workload_executor  # noqa: E402
from repro.core.workload import poisson_arrivals, trace_arrivals  # noqa: E402

MIN_SPEEDUP = 10.0      # ISSUE 6 acceptance: compiled >= 10x host events/s
TRACE_SEED_LEN = 512    # length of the "recorded" arrival trace stub
SHARDED_N = 4_000       # sharded-sweep prefix length (replicated compute
                        # on virtual CPU devices multiplies real work)


def _check_constant_memory(summary: dict, stats) -> None:
    """The streaming contract: nothing O(n_requests) on the host."""
    if stats.outcome != [] or stats.preempt_count.size != 0:
        raise RuntimeError(
            "stream=True replay materialized per-request host lists — the "
            "constant-memory streaming contract is broken")
    for key in ("latency", "cost"):
        if set(summary[key]) != {"count", "mean", "var", "std"}:
            raise RuntimeError(f"summary[{key!r}] is not a finalized "
                               "Welford moment dict")


def _sharded_sweep(trie, ann, obj, reqs, arr, execu, kw, ckw,
                   devices: tuple[int, ...]) -> dict:
    """Per-device-count replay of a trace prefix: exact summary equality
    vs single-device, zero retraces, recorded throughput."""
    sn = min(len(reqs), SHARDED_N)
    sreqs, sarr = reqs[:sn], arr[:sn]

    def one(d, **extra):
        return run_events_compiled(trie, ann, obj, sreqs, execu,
                                   arrivals=sarr, stream=True,
                                   devices=d, **kw, **ckw, **extra)

    base, _ = one(None)
    per = []
    for d in devices:
        one(d)  # warm: compile this device count's program
        c0 = compiled_engine_cache_size()
        t0 = time.perf_counter()
        summary, sstats = one(d)
        wall = time.perf_counter() - t0
        if c0 >= 0 and compiled_engine_cache_size() != c0:
            raise RuntimeError(
                f"sharded engine re-traced on a replay at devices={d} — "
                "device count must be the only static axis")
        # equal but for the host's wall time per phase
        if {**summary, "host_s": None} != {**base, "host_s": None}:
            raise RuntimeError(
                f"sharded replay summary diverged from single-device at "
                f"devices={d} — dispositions/sketches must be exact")
        _check_constant_memory(summary, sstats)
        per.append({"devices": d, "wall_s": round(wall, 3),
                    "events_per_s": round(summary["events"] / wall, 1)})
    return {"n_requests": sn, "summary_identical": True, "per_devices": per}


def deployment(wf: str = "mathqa_4", n: int = 1_000_000, rate: float = 8.0,
               capacity: int = 32, seed: int = 0):
    """The replayed deployment: ``wf``'s trie with exact annotations, a
    max-accuracy objective at the 0.8 latency quantile, a trace-extended
    Poisson stream of ``n`` requests at ``rate`` req/s, and the
    load-aware policy with feasibility admission over ``capacity`` slots.

    Returns (trie, ann, obj, requests, arrivals, executor, run_events
    keyword arguments); ``seed`` shifts every draw."""
    trie, wl = workload(wf)
    ann = exact_ann(wf)
    execu = make_workload_executor(wl)
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.8)))
    load = make_fleet_load(trie, wl)

    # bootstrap-extend a short recorded trace to the cohort size (the
    # PR 6 trace_arrivals fix: gaps resampled from the empirical gaps)
    base = poisson_arrivals(min(n, TRACE_SEED_LEN), rate, seed=seed + 1)
    arr = trace_arrivals(base, n=n, seed=seed + 2)
    reqs = np.random.default_rng(seed).choice(wl.n_requests, n, replace=True)
    kw = dict(capacity=capacity, policy="dynamic_load_aware",
              fleet_load=load, admission="feasibility")
    return trie, ann, obj, reqs, arr, execu, kw


def replay(wf: str = "mathqa_4", n: int = 1_000_000, host_n: int = 20_000,
           rate: float = 8.0, capacity: int = 32, epoch: int | None = None,
           warm: bool = False, devices: tuple[int, ...] = ()):
    """Run both lanes, differential-check the prefix, return the report.

    ``warm=True`` (the --tiny CI mode) times a SECOND run of each lane so
    XLA/planner compiles are excluded; the full 1M run amortizes its
    one-off compile into the measured wall instead of doubling the cost.
    """
    trie, ann, obj, reqs, arr, execu, kw = deployment(wf, n, rate, capacity)
    ckw = {} if epoch is None else {"epoch": epoch}
    host_n = min(host_n, n)

    # --- differential check + host timing on the prefix ----------------
    hp = (reqs[:host_n], arr[:host_n])
    if warm:
        run_events(trie, ann, obj, hp[0], execu, arrivals=hp[1], **kw)
    t0 = time.perf_counter()
    hres, hstats = run_events(trie, ann, obj, hp[0], execu,
                              arrivals=hp[1], **kw)
    host_wall = time.perf_counter() - t0
    if warm:
        run_events(trie, ann, obj, hp[0], execu, arrivals=hp[1],
                   compiled=True, **kw, **ckw)
    cres, cstats = run_events(trie, ann, obj, hp[0], execu, arrivals=hp[1],
                              compiled=True, **kw, **ckw)
    # same equivalence bar as the differential oracle sweep: discrete
    # fields exact, timestamps within 1e-9 (XLA FMA contraction shifts
    # completion times by a few ulps on messy float workloads), costs
    # within 1e-12
    mismatch = sum(a.outcome != b.outcome or a.n_stages != b.n_stages
                   or a.models != b.models
                   or abs(a.total_cost - b.total_cost) > 1e-12
                   for a, b in zip(hres, cres))
    if mismatch or np.abs(hstats.done_t - cstats.done_t).max() > 1e-9:
        raise RuntimeError(
            f"compiled engine diverged from the host loop on the replay "
            f"prefix ({mismatch} of {host_n} requests differ)")

    # --- compiled streaming replay of the full trace --------------------
    if warm:
        run_events_compiled(trie, ann, obj, reqs, execu, arrivals=arr,
                            stream=True, **kw, **ckw)
    t0 = time.perf_counter()
    summary, sstats = run_events_compiled(trie, ann, obj, reqs, execu,
                                          arrivals=arr, stream=True,
                                          **kw, **ckw)
    comp_wall = time.perf_counter() - t0
    _check_constant_memory(summary, sstats)

    sharded = _sharded_sweep(trie, ann, obj, reqs, arr, execu, kw, ckw,
                             devices) if devices else None

    host_eps = hstats.events / host_wall
    comp_eps = summary["events"] / comp_wall
    speedup = comp_eps / host_eps
    report = {
        "schema": "bench_replay/v2",
        "workflow": wf,
        "n_requests": n,
        "rate_rps": rate,
        "capacity": capacity,
        "epoch": epoch,
        "prefix_differential": {"n": host_n, "mismatches": 0},
        "host": {"n_requests": host_n, "events": hstats.events,
                 "wall_s": round(host_wall, 3),
                 "events_per_s": round(host_eps, 1)},
        "compiled": {"n_requests": n, "events": summary["events"],
                     "wall_s": round(comp_wall, 3),
                     "events_per_s": round(comp_eps, 1),
                     "served": summary["served"],
                     "goodput": round(summary["succeeded"]
                                      / max(summary["n_requests"], 1), 4),
                     "shed": summary["shed"],
                     "rejected": summary["rejected"],
                     "mean_lat_s": round(summary["latency"]["mean"], 4),
                     "p99_lat_s": round(summary["latency_p99"], 4)},
        "speedup": round(speedup, 2),
        "min_speedup": MIN_SPEEDUP,
        "sharded": sharded,
    }
    save_report("BENCH_replay", report)
    if speedup < MIN_SPEEDUP:
        raise RuntimeError(
            f"compiled event throughput is only {speedup:.1f}x the host "
            f"loop ({comp_eps:.0f} vs {host_eps:.0f} events/s) — the "
            f"acceptance floor is {MIN_SPEEDUP:.0f}x")
    return report


def run(n: int = 10_000, host_n: int = 2_000):
    """Registry entry for `benchmarks.run`: a --tiny-equivalent replay
    (10k requests, warmed timing) in the harness's standard row shape —
    the full 1M sweep stays behind the standalone entrypoint."""
    t0 = time.perf_counter()
    rep = replay(n=n, host_n=host_n, warm=True)
    elapsed = time.perf_counter() - t0
    return {
        "name": "trace_replay",
        "us_per_call": elapsed * 1e6 / max(rep["compiled"]["events"], 1),
        "derived": (
            f"speedup={rep['speedup']:.1f}x "
            f"compiled_ev_per_s={rep['compiled']['events_per_s']:.0f} "
            f"goodput={rep['compiled']['goodput']:.3f}"),
        "rows": [rep],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: 10k-request replay, warmed timing")
    ap.add_argument("--n", type=int, default=None,
                    help="replay size (default 1M, or 10k with --tiny)")
    ap.add_argument("--epoch", type=int, default=None,
                    help="epoch width override (default: engine default)")
    ap.add_argument("--devices", type=str, default=None,
                    help="comma list of device counts for the sharded "
                         "sweep, e.g. 1,2,4,8 (virtual CPU devices are "
                         "provisioned automatically)")
    args = ap.parse_args()
    n = args.n or (10_000 if args.tiny else 1_000_000)
    rep = replay(n=n, host_n=2_000 if args.tiny else 20_000,
                 epoch=args.epoch, warm=args.tiny, devices=_DEVICES)
    h, c = rep["host"], rep["compiled"]
    print(f"host     {h['events']:>9d} events in {h['wall_s']:8.2f}s  "
          f"({h['events_per_s']:>10.0f} ev/s, {h['n_requests']} reqs)")
    print(f"compiled {c['events']:>9d} events in {c['wall_s']:8.2f}s  "
          f"({c['events_per_s']:>10.0f} ev/s, {c['n_requests']} reqs)")
    print(f"speedup  {rep['speedup']:.1f}x (floor {MIN_SPEEDUP:.0f}x)  "
          f"goodput={c['goodput']:.3f} p99={c['p99_lat_s']:.2f}s")
    if rep["sharded"]:
        for row in rep["sharded"]["per_devices"]:
            print(f"sharded  devices={row['devices']} "
                  f"{row['events_per_s']:>10.0f} ev/s "
                  f"({rep['sharded']['n_requests']} reqs, summary exact)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
