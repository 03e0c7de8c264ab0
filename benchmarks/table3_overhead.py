"""Paper Table 3: per-replan controller overhead.

Measures (a) the host (numpy) re-rooted search per replanning step, matching
the paper's measurement, and (b) the batched fleet-step replanner across its
dispatch variants (DESIGN.md §2.1), amortized per request — the form that
scales to fleets:

- ``dense``  — the pre-fusion masked-reduction program (one full min-pass
  per lexicographic key, (N, Dmax) delay intermediate materialized);
- ``fused``  — the XLA mirror of the Pallas kernel (lexicographic minima
  in one tile over the whole trie, path-counts delay matmul, first-step
  gather fused into the pass) — the default serving path;
- ``pallas`` — the fused Pallas kernel itself (interpret mode on CPU;
  compiled on TPU the tile pass maps 1:1 onto VMEM-resident trie tiles).

At the largest preset trie the fused planner must beat the dense program —
the benchmark asserts it (min-over-iters, full mode only).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import (
    enable_compile_cache,
    exact_ann,
    save_report,
    workload,
)
from repro.core.controller import Objective, select_path
from repro.core.controller_jax import TrieDevice, make_fleet_planner

WORKFLOWS = ("mathqa_4", "nl2sql_2", "nl2sql_8")
VARIANTS = ("dense", "fused", "pallas")


def run(batch: int = 256, iters: int = 50, workflows=WORKFLOWS,
        host_iters: int = 200, variants=VARIANTS):
    rows = []
    total_t0 = time.perf_counter()
    for wf in workflows:
        trie, _ = workload(wf)
        ann = exact_ann(wf)
        obj = Objective("max_acc",
                        lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.7)))
        rng = np.random.default_rng(0)
        roots = rng.integers(0, trie.n_nodes, size=batch).astype(np.int32)
        lat = rng.uniform(0, 3, size=batch).astype(np.float32)
        ec = np.zeros(batch, np.float32)

        # host path (per-request, paper's setting)
        t0 = time.perf_counter()
        n = host_iters
        for i in range(n):
            select_path(trie, ann, obj, root=int(roots[i % batch]),
                        elapsed_lat=float(lat[i % batch]))
        host_us = (time.perf_counter() - t0) / n * 1e6
        rows.append({
            "workflow": wf, "n_nodes": trie.n_nodes, "batch": batch,
            "variant": "host", "us_per_replan": round(host_us, 1),
        })

        # batched fleet step, one row per dispatch variant
        td = TrieDevice.build(trie, ann)
        delays = np.zeros((batch, td.n_engines), np.float32)
        for variant in variants:
            step = make_fleet_planner(td, obj, variant=variant)
            t0 = time.perf_counter()
            np.asarray(step(roots, lat, ec, delays)[1])  # compile + run
            compile_s = time.perf_counter() - t0
            # interpret-mode Pallas is a correctness path on CPU; keep its
            # sample count small so the sweep stays cheap
            it = max(iters // 5, 3) if variant == "pallas" else iters
            times = []
            for _ in range(it):
                t0 = time.perf_counter()
                np.asarray(step(roots, lat, ec, delays)[1])
                times.append(time.perf_counter() - t0)
            us_batch = float(np.min(times)) * 1e6
            rows.append({
                "workflow": wf, "n_nodes": trie.n_nodes, "batch": batch,
                "variant": variant,
                "us_per_batch": round(us_batch, 1),
                "us_per_request": round(us_batch / batch, 2),
                "compile_s": round(compile_s, 3),
                "iters": it,
            })
    elapsed = time.perf_counter() - total_t0
    save_report("table3_overhead", rows)

    # the fused planner must beat the pre-fusion program where it matters:
    # the largest preset trie (full runs; --tiny sweeps one small preset)
    by_key = {(r["workflow"], r["variant"]): r for r in rows}
    largest = max(workflows, key=lambda w: by_key[(w, "host")]["n_nodes"])
    speedup = None
    if (largest, "dense") in by_key and (largest, "fused") in by_key:
        speedup = (by_key[(largest, "dense")]["us_per_batch"]
                   / by_key[(largest, "fused")]["us_per_batch"])
        if len(workflows) > 1 and speedup < 1.0:
            raise RuntimeError(
                f"fused planner is {1 / speedup:.2f}x SLOWER than the dense "
                f"program at the largest trie ({largest}, "
                f"{by_key[(largest, 'host')]['n_nodes']} nodes) — the fusion "
                "regressed")
    worst = max(r["us_per_replan"] for r in rows if r["variant"] == "host")
    derived = f"max_host_replan={worst:.0f}us"
    if speedup is not None:
        derived += f" fused_vs_dense@{largest}={speedup:.2f}x"
    return {
        "name": "table3_overhead",
        "us_per_call": elapsed * 1e6 / max(len(rows), 1),
        "derived": derived,
        "rows": rows,
    }


if __name__ == "__main__":
    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small trie, few iterations")
    args = ap.parse_args()
    out = (run(batch=32, iters=5, workflows=("nl2sql_2",), host_iters=20)
           if args.tiny else run())
    print(out["derived"])
    for r in out["rows"]:
        if r["variant"] == "host":
            print(f"{r['workflow']:10s} nodes={r['n_nodes']:5d} "
                  f"host    {r['us_per_replan']:9.1f}us/replan")
        else:
            print(f"{r['workflow']:10s} nodes={r['n_nodes']:5d} "
                  f"{r['variant']:7s} {r['us_per_batch']:9.1f}us/batch"
                  f"{r['batch']:4d} ({r['us_per_request']:6.2f}us/req, "
                  f"compile {r['compile_s']:.2f}s)")
