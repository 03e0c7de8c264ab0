"""Smoke run of VineLM's served path on one TPU chip.

Drives the control plane through the entry points a user calls, at the
size of a deployment, and checks every answer against the repo's own
references:

- Phase A, the replan kernel at real width: the mathqa_4 trie (5,461
  nodes, 4 models) with 256 lanes of seeded prefixes, elapsed budgets and
  engine delays.  The ``dense``, ``fused`` and compiled ``pallas``
  planner variants must pick identical (target, next model) pairs, and
  agree with the host ``select_path`` on a seeded subset of lanes.  The
  Pallas planner must lower to a Mosaic ``tpu_custom_call``.
- Phase B, the served path: the trace-replay deployment (mathqa_4,
  capacity 32, ``dynamic_load_aware``, ``feasibility`` admission, a
  trace-extended Poisson stream at 8 req/s) through
  ``run_events(..., compiled=True)``.  A request prefix is replayed
  through the host event loop as a differential reference (discrete
  fields exact, completion times within `DONE_T_TOL` and total costs
  within `COST_RTOL`); the full stream runs with ``stream=True`` twice,
  and the second replay must compile nothing.

With ``--chips 4`` only Phase B's stream runs, at ``devices=4`` against
``devices=1`` on the same host, and the two streaming summaries must be
equal.

The script exits nonzero, printing no result, when JAX finds no TPU.  The
last line of a passing run is one JSON object naming the device.

    python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

MODEL_WF = "mathqa_4"
PLAN_LANES = 256       # Phase A batch width (two 128-lane kernel blocks)
HOST_LANES = 32        # Phase A lanes also solved by the host select_path
STREAM_N = 20_000      # Phase B streamed requests
PREFIX_N = 2_000       # Phase B host-loop differential prefix
# Float64 on the TPU is emulated with pairs of float32 (about 48
# significand bits, not IEEE binary64), so the compiled engine's float64
# fields carry rounding the host loop's numpy does not.  Completion times
# against the host loop, seconds (the CPU lane's bound):
DONE_T_TOL = 1e-9
# total request cost against the host loop, relative: a few units of the
# emulated format's precision (2**-48) over a path of at most 6 stages
COST_RTOL = 1e-13


def _log(msg: str) -> None:
    print(msg, flush=True)


def _rel_diff(ref: float, x: float) -> float:
    """|x - ref| relative to ``ref``; infinite when ``ref`` is 0 and ``x``
    is not."""
    if ref:
        return abs(x - ref) / abs(ref)
    return 0.0 if x == 0 else float("inf")


def _timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def check_mosaic_lowering(td, roots, el, ec, delays) -> None:
    """The pallas planner variant must lower to a compiled Mosaic kernel
    (``tpu_custom_call``), not the Pallas interpreter."""
    import functools

    import jax
    import numpy as np

    from repro.kernels import ops as kernel_ops

    lowered = jax.jit(functools.partial(
        kernel_ops.trie_plan, kind="max_acc", variant="pallas")).lower(
        td.terminal, td.depth, td.acc, td.cost, td.lat, td.subtree_size,
        td.path_models, td.path_counts, td.engine_of_model, roots, el, ec,
        delays, np.float32(-1.0), np.float32(1e30), np.float32(1e30))
    if "tpu_custom_call" not in lowered.as_text():
        raise RuntimeError("the pallas planner did not lower to a Mosaic "
                           "tpu_custom_call")
    _log("phase A: pallas planner lowers to tpu_custom_call")


def replan_kernel(seed: int, lanes: int = PLAN_LANES,
                  host_lanes: int = HOST_LANES) -> None:
    """Phase A: the three planner variants agree with each other and with
    the host search at the mathqa_4 trie's full width."""
    import jax
    import numpy as np

    from benchmarks.common import exact_ann, workload
    from repro.core.controller import Objective, select_path
    from repro.core.controller_jax import (
        TrieDevice,
        make_fleet_planner,
        next_model_for,
        trie_engines,
    )
    from repro.kernels import ops as kernel_ops

    trie, _ = workload(MODEL_WF)
    ann = exact_ann(MODEL_WF)
    engines = trie_engines(trie.template)
    td = TrieDevice.build(trie, ann)
    rng = np.random.default_rng(seed)
    roots = rng.integers(0, trie.n_nodes, size=lanes).astype(np.int32)
    el = rng.uniform(0, 3, size=lanes).astype(np.float32)
    ec = np.zeros(lanes, np.float32)
    delays = rng.uniform(0, 0.5, size=(lanes, len(engines))).astype(
        np.float32)
    term = trie.terminal
    objectives = [
        Objective("max_acc",
                  cost_cap=float(np.quantile(ann.cost[term], 0.5)),
                  lat_cap=float(np.quantile(ann.lat[term], 0.8))),
        Objective("min_cost",
                  acc_floor=float(np.quantile(ann.acc[term], 0.4)),
                  lat_cap=float(np.quantile(ann.lat[term], 0.9))),
    ]
    _log(f"phase A: {MODEL_WF} trie {trie.n_nodes} nodes, "
         f"{len(trie.template.models)} models, {lanes} lanes")

    check_mosaic_lowering(td, roots, el, ec, delays)

    for obj in objectives:
        outs = {}
        for variant in kernel_ops.TRIE_PLAN_VARIANTS:
            step = make_fleet_planner(td, obj, variant=variant)
            (tgt, nxt), first_s = _timed(
                lambda: jax.block_until_ready(step(roots, el, ec, delays)))
            (tgt, nxt), warm_s = _timed(
                lambda: jax.block_until_ready(step(roots, el, ec, delays)))
            outs[variant] = (np.asarray(tgt), np.asarray(nxt))
            _log(f"phase A: {obj.kind} {variant}: first call {first_s:.3f}s "
                 f"(compile included), second {warm_s * 1e3:.3f}ms")
        ref_tgt, ref_nxt = outs["dense"]
        for variant, (tgt, nxt) in outs.items():
            if not (np.array_equal(tgt, ref_tgt)
                    and np.array_equal(nxt, ref_nxt)):
                bad = int(np.sum((tgt != ref_tgt) | (nxt != ref_nxt)))
                raise RuntimeError(f"{obj.kind}: {variant} differs from "
                                   f"dense on {bad} of {lanes} lanes")
        host_tgt = np.array([
            select_path(trie, ann, obj, root=int(roots[i]),
                        elapsed_lat=float(el[i]),
                        engine_delays={e: float(delays[i, j])
                                       for j, e in enumerate(engines)})
            for i in range(host_lanes)])
        host_nxt = np.array([next_model_for(trie, int(roots[i]),
                                            int(host_tgt[i]))
                             for i in range(host_lanes)])
        if not (np.array_equal(ref_tgt[:host_lanes], host_tgt)
                and np.array_equal(ref_nxt[:host_lanes], host_nxt)):
            raise RuntimeError(f"{obj.kind}: device planners disagree with "
                               f"the host select_path")
        _log(f"phase A: {obj.kind}: dense == fused == pallas on {lanes} "
             f"lanes ({int(np.sum(ref_tgt >= 0))} feasible), host "
             f"select_path agrees on {host_lanes}")


def _stream(dep, devices=None):
    """One streamed replay of the whole deployment through the compiled
    engine: (summary, wall seconds)."""
    from repro.core.events import run_events

    trie, ann, obj, reqs, arr, execu, kw = dep
    (summary, _), wall = _timed(run_events, trie, ann, obj, reqs, execu,
                                arrivals=arr, compiled=True, stream=True,
                                devices=devices, **kw)
    if summary["n_requests"] != len(reqs):
        raise RuntimeError("streamed summary lost requests")
    return summary, wall


def served_path(seed: int, n: int = STREAM_N, prefix: int = PREFIX_N) -> None:
    """Phase B: the compiled served path against the host event loop on a
    prefix, then the full stream twice with no new compilation."""
    import numpy as np

    from benchmarks.trace_replay import deployment
    from repro.core.events import run_events
    from repro.core.events_compiled import compiled_engine_cache_size

    dep = deployment(MODEL_WF, n=n, seed=seed)
    trie, ann, obj, reqs, arr, execu, kw = dep
    _log(f"phase B: {MODEL_WF}, capacity {kw['capacity']}, {n} requests, "
         f"host differential on {prefix}")

    p_reqs, p_arr = reqs[:prefix], arr[:prefix]
    (hres, hstats), host_s = _timed(run_events, trie, ann, obj, p_reqs,
                                    execu, arrivals=p_arr, **kw)
    (cres, cstats), comp_s = _timed(run_events, trie, ann, obj, p_reqs,
                                    execu, arrivals=p_arr, compiled=True,
                                    **kw)
    mismatch = {f: sum(getattr(a, f) != getattr(b, f)
                       for a, b in zip(hres, cres))
                for f in ("outcome", "n_stages", "models")}
    cost_rel = max(_rel_diff(a.total_cost, b.total_cost)
                   for a, b in zip(hres, cres))
    cost_ne = sum(a.total_cost != b.total_cost for a, b in zip(hres, cres))
    done_dt = float(np.abs(hstats.done_t - cstats.done_t).max())
    _log(f"phase B: prefix host loop {host_s:.2f}s ({hstats.events} events), "
         f"compiled {comp_s:.2f}s (compile included); mismatches {mismatch}, "
         f"total_cost differs on {cost_ne}, max relative {cost_rel!r}; "
         f"max |done_t diff| {done_dt!r} s")
    if len(hres) != prefix or len(cres) != prefix or any(mismatch.values()):
        raise RuntimeError(f"compiled engine diverged from the host loop on "
                           f"the {prefix}-request prefix: {mismatch}")
    if not cost_rel <= COST_RTOL:
        raise RuntimeError(f"total costs differ by {cost_rel!r} relative, "
                           f"over the {COST_RTOL} tolerance")
    if not done_dt <= DONE_T_TOL:
        raise RuntimeError(f"completion times differ by {done_dt!r} s, over "
                           f"the {DONE_T_TOL} s tolerance")

    summary, first_s = _stream(dep)
    programs = compiled_engine_cache_size()
    again, second_s = _stream(dep)
    if compiled_engine_cache_size() != programs:
        raise RuntimeError("the second replay compiled a new engine program")
    # equal but for the host's wall time per phase
    if {**again, "host_s": None} != {**summary, "host_s": None}:
        raise RuntimeError("the second replay changed the streamed summary")
    _log(f"phase B: stream {summary['events']} events, first replay "
         f"{first_s:.2f}s (compile included), second {second_s:.2f}s; "
         f"served {summary['served']}, rejected {summary['rejected']}, "
         f"shed {summary['shed']}, p99 latency "
         f"{summary['latency_p99']!r} s; {programs} engine programs")


def sharded_stream(seed: int, chips: int, n: int = STREAM_N) -> None:
    """The ``--chips`` path: the streamed replay lane-sharded over
    ``chips`` devices must equal the single-device replay exactly."""
    from benchmarks.trace_replay import deployment

    dep = deployment(MODEL_WF, n=n, seed=seed)
    single, single_s = _stream(dep)
    sharded, sharded_s = _stream(dep, devices=chips)
    _log(f"sharded: devices=1 {single_s:.2f}s, devices={chips} "
         f"{sharded_s:.2f}s (compile included), {single['events']} events")
    if sharded != single:
        diff = sorted(k for k in single if sharded.get(k) != single[k])
        raise RuntimeError(f"devices={chips} summary differs from "
                           f"devices=1 in {diff}")
    _log(f"sharded: devices={chips} summary equals devices=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded stream against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.common import enable_compile_cache

    kind = devices[0].device_kind
    _log(f"device_kind {kind!r}, {len(devices)} devices, compile cache "
         f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips > 1:
        sharded_stream(args.seed, args.chips)
    else:
        replan_kernel(args.seed)
        served_path(args.seed)
    _log(f"wall {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
