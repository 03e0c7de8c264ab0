"""The compiled engine's spans, scopes and counters.

- six host spans ``vinelm.build`` ... ``vinelm.drain`` per call, in order
  and inside the caller's span, carrying the call's shape, the planner's
  node tiles and the counters;
- the always-on counters: ``sweeps`` (width-1 planner sweeps) against
  the host loop's planned lanes, ``epochs``, and ``host_s`` per phase;
- `merge_stream_summaries` adds them;
- the scope map of a compiled step names the four ``vinelm/`` scopes.
"""
import glob

import jax
import numpy as np
import pytest
from fleetlib import random_setup

from repro.core.controller import Objective
from repro.core.events import run_events
from repro.core.events_compiled import (
    SCOPES,
    compiled_engine_cache_size,
    engine_scope_maps,
    hlo_scope_map,
    merge_stream_summaries,
    run_events_compiled,
)
from repro.core.runtime import make_workload_executor
from repro.core.workload import poisson_arrivals
from repro.kernels.ops import trie_plan_tiles
from repro.serving.loadsim import EngineLoadModel, FleetLoadModel

PHASES = ("build", "tabulate", "upload", "enqueue", "wait", "drain")


def _deployment(seed=5, n=24, epoch=8):
    _, trie, wl, ann = random_setup(seed)
    engines = sorted({m.engine for m in trie.template.models})
    load = FleetLoadModel(
        engines={e: EngineLoadModel(e, concurrency=2, jitter=0.0)
                 for e in engines},
        mean_service_s={e: 1.0 for e in engines})
    reqs = np.random.default_rng(seed).choice(wl.n_requests, n,
                                              replace=False)
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.7)))
    kw = dict(arrivals=poisson_arrivals(n, rate=3.0, seed=seed), capacity=4,
              policy="dynamic_load_aware", fleet_load=load,
              admission="feasibility")
    args = (trie, ann, obj, reqs, make_workload_executor(wl))
    return args, kw, epoch


def _stream(args, kw, epoch):
    return run_events_compiled(*args, stream=True, epoch=epoch, **kw)


def test_sweeps_equal_the_host_loops_planned_lanes():
    args, kw, epoch = _deployment()
    summary, stats = _stream(args, kw, epoch)
    _, hstats = run_events(*args, **kw)
    assert hstats.planned_per_replan
    assert summary["sweeps"] == stats.sweeps == sum(hstats.planned_per_replan)
    assert summary["replans"] == hstats.replans
    # the same inputs sweep the same lanes every time
    again, _ = _stream(args, kw, epoch)
    assert again["sweeps"] == summary["sweeps"]


def test_epochs_and_host_phases_are_counted():
    args, kw, epoch = _deployment(n=24, epoch=8)
    summary, stats = _stream(args, kw, epoch)
    # 24 arrivals in steps of 8: three steps, the last one unbounded
    assert summary["epochs"] == stats.epochs == 3
    assert tuple(summary["host_s"]) == PHASES
    assert summary["host_s"] is stats.host_s
    assert all(v >= 0.0 for v in summary["host_s"].values())
    assert summary["host_s"]["tabulate"] > 0.0


def test_merge_adds_the_tracing_counters():
    args, kw, epoch = _deployment()
    a, _ = _stream(args, kw, epoch)
    b, _ = _stream(args, kw, 4)
    m = merge_stream_summaries(a, b)
    assert m["sweeps"] == a["sweeps"] + b["sweeps"]
    assert m["epochs"] == a["epochs"] + b["epochs"]
    assert set(m["host_s"]) == set(PHASES)
    for k in PHASES:
        assert m["host_s"][k] == pytest.approx(a["host_s"][k]
                                               + b["host_s"][k])


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("vinelm.", "caller")):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_a_traced_call_writes_six_spans_in_order_inside_the_callers(
        tmp_path):
    args, kw, epoch = _deployment()
    _stream(args, kw, epoch)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller"):
            summary, _ = _stream(args, kw, epoch)
    evs = _host_events(tmp_path)
    (caller,) = [e for e in evs if e[0] == "caller"]
    spans = [e for e in evs if e[0].startswith("vinelm.")]
    assert [n for n, *_ in spans] == [f"vinelm.{p}" for p in PHASES]
    for _, s, e, stats in spans:
        assert caller[1] <= s <= e <= caller[2]
        assert stats["requests"] == 24
    for (_, _, e, _), (_, s, _, _) in zip(spans, spans[1:]):
        assert e <= s
    trie = args[0]
    build, drain = spans[0][3], spans[-1][3]
    assert build["nodes"] == trie.n_nodes
    assert build["models"] == trie.template.n_models
    assert build["plan_tiles"] == trie_plan_tiles(trie.n_nodes, "fused") == 1
    assert (drain["events"], drain["sweeps"], drain["epochs"]) == (
        summary["events"], summary["sweeps"], summary["epochs"])


def test_the_scope_map_names_the_four_scopes(monkeypatch):
    from repro.core import events_compiled

    # only this test's program: others this process ran are not rebuilt
    monkeypatch.setattr(events_compiled, "_ENGINE_CALLS", {})
    args, kw, epoch = _deployment()
    _stream(args, kw, epoch)
    _stream(args, kw, 4)  # another epoch width runs the same program
    programs = compiled_engine_cache_size()
    (smap,) = engine_scope_maps()
    assert compiled_engine_cache_size() == programs
    names = set(smap.values())
    for scope in SCOPES:
        assert any(scope + "/" in n for n in names), scope
    # the sweeps run inside the dispatch round
    assert any("vinelm/dispatch/" in n and "/vinelm/plan/" in n
               for n in names)


HLO = """HloModule jit_f, is_scheduled=true

%body.1 (arg.1: (f32[4], f32[])) -> (f32[4], f32[]) {
  %arg.1 = (f32[4]{0}, f32[]) parameter(0)
  %gte.2 = f32[4]{0} get-tuple-element(%arg.1), index=0
  %fusion.3 = f32[4]{0} fusion(%gte.2), kind=kLoop, calls=%fused.4, metadata={op_name="jit(f)/vinelm/dispatch/while/body/vinelm/plan/add" stack_frame_id=3}
  ROOT %tuple.5 = (f32[4]{0}, f32[]) tuple(%fusion.3, %gte.2)
}

%branch.6 (p.7: f32[]) -> f32[] {
  ROOT %p.7 = f32[] parameter(0)
}

ENTRY %main.8 (x.9: f32[4], t.10: f32[]) -> f32[4] {
  %x.9 = f32[4]{0} parameter(0)
  %copy.11 = f32[4]{0} copy(%x.9)
  %while.12 = (f32[4]{0}, f32[]) while(%tuple.13), condition=%cond.14, body=%body.1, metadata={op_name="jit(f)/vinelm/dispatch/while"}
  %conditional.15 = f32[] conditional(%p.16, %t.10, %t.10), branch_computations={%branch.6, %branch.17}, metadata={op_name="jit(f)/vinelm/clock/cond"}
}
"""  # noqa: E501


def test_hlo_scope_map_inherits_the_caller_scope():
    m = hlo_scope_map(HLO)
    assert m["fusion.3"] == "jit(f)/vinelm/dispatch/while/body/vinelm/plan/add"
    # metadata-less loop plumbing runs inside the while that calls it
    assert m["gte.2"] == m["tuple.5"] == "jit(f)/vinelm/dispatch/while"
    assert m["p.7"] == "jit(f)/vinelm/clock/cond"
    assert "copy.11" not in m and "x.9" not in m
