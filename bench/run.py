"""Benchmark of VineLM's served control plane on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``)
and a traffic mix (``bench/traffic/<mix>.json``); its per-layer metrics are
readers in ``bench/metrics/<name>.py``.  All three are found by name.

A run builds the deployment of the configuration (its question table,
drawn from the configuration's ``questions_seed``, and the program's
trie, annotations and load model), warms the cell's one compiled engine
program, then calls the served entry
``run_events(..., compiled=True, stream=True)`` back to back for
``--seconds``, each call on the next segment of the mix drawn from
``--seed``.  After
the window every call is replayed by the plain reference and compared
(`check.py`).  ``--trace 1`` runs the same window with its first call
under the profiler and reports the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object.  The run exits 1,
printing no result, when JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()
# when each phase of set-up ended, from the process's start
MARKS = [("start", T_START)]

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import gen  # noqa: E402
import xplane  # noqa: E402
from reference import Reference  # noqa: E402

TRACE_DIR = ".bench_trace"    # in the checkout; the newest trace only
# a traced run profiles the window's first calls only: a call replays
# thousands of events, each of a hundred-odd device operations
TRACE_CALLS = 1
BREAKDOWN_TOP = 10


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything the benchmark knows about cell ``name``, by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return {
        "cell": cell,
        "config": config,
        "mix": mix,
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def load_reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory in the checkout; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader gets: the trace, the device planes of the
    chips the cell uses, and the counts of the traced calls."""
    trace: xplane.Trace
    devices: list
    events: int
    calls: int


def _breakdown(trace: xplane.Trace, devices: list) -> dict:
    w = trace.window()
    if w is None or not devices:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi = w
    tot: dict = {}
    for d in devices:
        for n, s, e in trace.ops.get(d, []) or trace.modules.get(d, []):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                n = n.split(" = ")[0]   # an op's name, not its HLO text
                tot[n] = tot.get(n, 0) + (e - s)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:BREAKDOWN_TOP]
    gaps = xplane.idle_gaps(xplane.busy_intervals(trace, devices[0]), lo, hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:BREAKDOWN_TOP]
    spans = sorted(trace.spans, key=lambda s: s[2] - s[1])

    def named(s, e):
        # the innermost harness span that holds the gap's midpoint
        mid = (s + e) // 2
        for n, a, b in spans:
            if a <= mid <= b:
                return n
        return "outside bench spans"

    return {
        "device_ops": [[n, v / len(devices) / 1e9] for n, v in ops],
        "idle_gaps": [[named(s, e), (e - s) / 1e9] for s, e in gaps],
    }


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, mix_overrides: dict | None = None,
             run_events=None) -> dict:
    """One run of cell ``name``; returns the result object.

    The platform check is the caller's: `main` refuses to run without the
    chips the cell asks for, while the tests call this on the CPU with
    short calls (``mix_overrides``) and, to plant faults, a substitute
    ``run_events``."""
    import jax
    import numpy as np

    import deploy
    from repro.core.events_compiled import compiled_engine_cache_size

    spec = load_cell(name, root)
    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    mix = {**mix, **(mix_overrides or {})}
    chips = int(cell["chips"])
    devices = jax.devices()[:chips]
    wf = config["workflow"]
    nq = int(config["questions"])
    epoch = int(mix["arrivals_per_step"])

    phases = MARKS + [("harness", time.perf_counter())]
    # the deployment's question table is part of its configuration: the
    # program bakes numbers derived from it into its compiled step, so a
    # table drawn per seed would compile a program in every run's set-up
    tables = gen.config_tables(config)
    phases.append(("tables", time.perf_counter()))
    dep = deploy.build(config, tables)
    phases.append(("deployment", time.perf_counter()))
    log(f"{name}: {wf['name']}, {dep.trie.n_nodes} trie nodes, capacity "
        f"{config['capacity']}, {mix['requests_per_call']} requests per call,"
        f" {epoch} arrivals per step, {chips} chip(s)")

    # warm-up: the window's one engine program, on the first segment's
    # requests all arriving at once (the feasibility gate then rejects most
    # of the queue within one latency budget, so few events run)
    reqs0, _ = gen.call_inputs(mix, nq, seed, 0)
    try:
        dep.call(reqs0, np.zeros(reqs0.size), epoch=epoch,
                 run_events=run_events)
    except Exception as e:  # the window's calls will fail and count
        log(f"warm-up raised {type(e).__name__}: {e}")
    phases.append(("warm-up", time.perf_counter()))

    compiles = []

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    programs0 = compiled_engine_cache_size()
    trace_dir = os.path.join(root, TRACE_DIR)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - T_START
    log("set-up " + ", ".join(f"{n} {b - a:.3f} s" for (_, a), (n, b)
                               in zip(phases, phases[1:])))

    calls, events, wall, attempted, failed = [], 0, 0.0, 0, 0
    traced_events = 0
    n_window = len(compiles)
    t_window = time.perf_counter()
    k = 0
    while time.perf_counter() - t_window < seconds:
        if trace and k == TRACE_CALLS:
            jax.profiler.stop_trace()
            traced_events = events
        with jax.profiler.TraceAnnotation("bench.inputs"):
            reqs, arr = gen.call_inputs(mix, nq, seed, k)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.call"):
                summary = dep.call(reqs, arr, epoch=epoch,
                                   run_events=run_events)
        except Exception as e:  # a failed call counts; the window goes on
            log(f"call {k} raised {type(e).__name__}: {e}")
            summary = None
        wall += time.perf_counter() - t0
        attempted += reqs.size
        if summary is None or summary["n_requests"] != reqs.size:
            failed += reqs.size
        else:
            events += int(summary["events"])
        calls.append((k, summary))
        k += 1
    if trace and k <= TRACE_CALLS:
        jax.profiler.stop_trace()
        traced_events = events
    window_compiles = len(compiles) - n_window
    programs = compiled_engine_cache_size() - programs0
    log(f"window: {len(calls)} calls, {attempted} requests, {events} events "
        f"in {wall:.3f} s")
    print(f"compiles_in_window: {programs} engine programs, "
          f"{window_compiles} backend compiles", flush=True)

    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    del dep

    # the comparison: every call of the window against the reference,
    # built now, once the program's state is freed
    t0 = time.perf_counter()
    ref = Reference(config, tables)
    per_call = []
    for k, summary in calls:
        if summary is None:
            continue
        reqs, arr = gen.call_inputs(mix, nq, seed, k)
        per_call.append(check.gaps(check.program_summary(summary),
                                   ref.simulate(reqs, arr)))
    worst = check.worst(per_call)
    correct = failed == 0 and bool(calls) and check.passes(worst)
    log(f"reference built and replayed {len(per_call)} calls in "
        f"{time.perf_counter() - t0:.3f} s")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        values = {"events_per_s": events / wall if wall > 0 else 0.0,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in spec["end_to_end"]}
    else:
        tr = xplane.load(xplane.find(trace_dir))
        planes = sorted(set(tr.ops) | set(tr.modules))
        # the planes of the chips the run used, by their device ids
        ids = {f"/device:{d.platform.upper()}:{d.id}" for d in devices}
        used = [p for p in planes if p in ids] or planes[:chips]
        ctx = ReadContext(trace=tr, devices=used, events=traced_events,
                          calls=min(len(calls), TRACE_CALLS))
        metrics = {}
        for m in spec["per_layer"]:
            v = load_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        w = tr.window()
        busy = [xplane.union_ns(xplane.busy_intervals(tr, d), *w)
                for d in used] if w else []
        device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        device["window_s"] = (w[1] - w[0]) / 1e9 if w else 0.0
        result["breakdown"] = _breakdown(tr, used)
    result["device"] = device
    result["checks"] = {k: {"value": worst[k], "limit": check.LIMITS[k]}
                        for k in check.LIMITS}
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    chips = int(spec["cell"]["chips"])
    import jax

    MARKS.append(("import", time.perf_counter()))
    found = jax.devices()
    MARKS.append(("devices", time.perf_counter()))
    if found[0].platform != "tpu" or len(found) < chips:
        log(f"{args.workload} needs {chips} TPU chip(s); JAX found "
            f"{len(found)} {found[0].platform} device(s)")
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    log(f"compile cache {enable_compile_cache()}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
