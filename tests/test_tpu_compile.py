"""Compile-only checks for TPU v5e: the served path's kernels at real widths.

Nothing runs on a chip here.  The TPU compiler, which is installed with
JAX, compiles for a described ``v5e:2x2`` topology and raises what the
chip's compiler would raise: unaligned Mosaic stores, VMEM overflow,
programs that do not fit.  Interpret-mode tests cannot see any of these.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  All such compiles stay in this one file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import events_compiled, presets
from repro.core.controller import Objective
from repro.core.controller_jax import TrieDevice
from repro.core.events import run_events
from repro.core.trie import Trie
from repro.core.workload import generate_workload, poisson_arrivals
from repro.kernels.trie_plan import trie_plan_pallas
from repro.kernels.xla_trie import fleet_plan_blocked
from repro.serving.loadsim import (EngineLoadModel, EngineTokenModel,
                                   FleetLoadModel, TokenWorkModel)

LANES = (1, 32, 256)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mathqa_td():
    tpl = presets.PRESETS["mathqa_4"]()
    trie = Trie.build(tpl)
    wl = generate_workload(tpl, 20, seed=0)
    return TrieDevice.build(trie, wl.exact_annotations(trie))


def _planner_shapes(td, lanes, sharding):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    trie_cols = [sds(a.shape, a.dtype) for a in (
        td.terminal, td.depth, td.acc, td.cost, td.lat, td.subtree_size,
        td.path_models, td.path_counts, td.engine_of_model)]
    lane_cols = [sds((lanes,), jnp.int32), sds((lanes,), jnp.float32),
                 sds((lanes,), jnp.float32),
                 sds((lanes, td.n_engines), jnp.float32)]
    scalars = [sds((), jnp.float32)] * 3
    return trie_cols + lane_cols + scalars, sds(td.terminal.shape,
                                                jnp.float32)


@pytest.mark.parametrize("kind", ["max_acc", "min_cost"])
@pytest.mark.parametrize("lanes", LANES)
def test_pallas_planner_compiles_for_v5e(one_chip, mathqa_td, lanes, kind):
    """The compiled Mosaic replan kernel at 5,461 nodes."""
    assert mathqa_td.terminal.shape[0] == 5461
    args, bd = _planner_shapes(mathqa_td, lanes, one_chip)
    plan = functools.partial(trie_plan_pallas, kind=kind, interpret=False)
    compiled = jax.jit(
        lambda *a, bd: plan(*a, blocked_depth=bd)).lower(*args,
                                                         bd=bd).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes", LANES)
def test_fused_planner_compiles_for_v5e(one_chip, mathqa_td, lanes):
    """The blocked XLA planner, the default variant, at 5,461 nodes."""
    args, bd = _planner_shapes(mathqa_td, lanes, one_chip)
    plan = functools.partial(fleet_plan_blocked, kind="max_acc")
    compiled = jax.jit(
        lambda *a, bd: plan(*a, blocked_depth=bd)).lower(*args,
                                                         bd=bd).compile()
    assert compiled.memory_analysis() is not None


# 4,096 lanes: wider than any sweep the controllers issue
@pytest.mark.parametrize("lanes", LANES + (4096,))
def test_fused_planner_tiles_follow_the_working_set_for_v5e(one_chip,
                                                            mathqa_td,
                                                            lanes):
    """A fused sweep over the 5,461-node trie compiles as one node tile
    at every width: no ``while`` in its optimized v5e HLO."""
    args, bd = _planner_shapes(mathqa_td, lanes, one_chip)
    plan = functools.partial(fleet_plan_blocked, kind="max_acc")
    hlo = jax.jit(
        lambda *a, bd: plan(*a, blocked_depth=bd)).lower(
            *args, bd=bd).compile().as_text()
    assert " while(" not in hlo


class _Captured(Exception):
    pass


def _step_for_v5e(one_chip, serve):
    """Capture the engine config and operand shapes of ``serve``'s first
    epoch step (a CPU run stopped at that call), then compile the real
    step for the chip."""
    seen = {}

    def capture(cfg):
        def step(st, cn, t_hi):
            seen["cfg"] = cfg
            seen["shapes"] = jax.tree.map(
                lambda a: (np.shape(a), jnp.result_type(a)), (st, cn, t_hi))
            raise _Captured
        return step

    with pytest.MonkeyPatch.context() as monkeypatch:
        monkeypatch.setattr(events_compiled, "_build_step", capture)
        with pytest.raises(_Captured):
            serve()

    step = events_compiled._build_step(seen["cfg"])
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1], sharding=one_chip),
        seen["shapes"], is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[0], tuple))
    with jax.enable_x64(True):
        return step.lower(*args).compile()


@pytest.fixture(scope="module")
def v5e_step(one_chip):
    """One epoch step of the compiled engine (float64 virtual clock) at
    nl2sql_2 size."""
    tpl = presets.PRESETS["nl2sql_2"]()
    trie = Trie.build(tpl)
    wl = generate_workload(tpl, 64, seed=0)
    ann = wl.exact_annotations(trie)
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.8)))

    from repro.core.runtime import make_workload_executor
    return _step_for_v5e(one_chip, lambda: run_events(
        trie, ann, obj, np.arange(64), make_workload_executor(wl),
        arrivals=poisson_arrivals(64, 4.0, seed=1), capacity=16,
        policy="dynamic_load_aware", admission="feasibility",
        compiled=True))


def test_epoch_step_compiles_for_v5e(v5e_step):
    assert v5e_step.memory_analysis().temp_size_in_bytes > 0


def test_epoch_step_for_v5e_keeps_its_named_scopes(v5e_step):
    """The chip's compiler keeps the step's scopes in its metadata: every
    loop and branch of the step is named by one of the four."""
    smap = events_compiled.hlo_scope_map(v5e_step.as_text())
    names = set(smap.values())
    for scope in events_compiled.SCOPES:
        assert any(scope + "/" in n for n in names), scope
    loops = [line for line in v5e_step.as_text().splitlines()
             if " while(" in line or " conditional(" in line]
    assert loops
    for line in loops:
        name = line.split(" = ")[0].split()[-1].lstrip("%")
        assert "vinelm/" in smap.get(name, ""), line[:120]


# the served step: the engine program the benchmark runs (stream mode)
_HLO_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?(\S+) \(.*\{\s*$")
_HLO_CALLEE = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation"
                         r"|false_computation)=%?([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _event_loop(text):
    """Every instruction line the event loop runs: its ``while`` (the
    entry's outermost loop in the clock's scope) and each computation
    reachable from it (fusions, nested loops, branches)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif cur is not None and " = " in line:
            cur.append(line)

    def callees(line):
        out = _HLO_CALLEE.findall(line)
        for branches in _HLO_BRANCHES.findall(line):
            out += [c.strip().lstrip("%") for c in branches.split(",")]
        return out

    seen, queue, loop = set(), [entry], None
    while queue and loop is None:
        for line in comps[queue.pop(0)]:
            if " while(" in line and "vinelm/clock" in line:
                loop = line
                break
            for c in callees(line):
                if c not in seen:
                    seen.add(c)
                    queue.append(c)
    assert loop is not None, "no event loop in the entry computation"
    lines, queue, seen = [loop], callees(loop), set(callees(loop))
    while queue:
        for line in comps[queue.pop()]:
            lines.append(line)
            for c in callees(line):
                if c not in seen:
                    seen.add(c)
                    queue.append(c)
    return lines


def _served_step(one_chip, capacity, *, tokens, stream=True):
    """mathqa_4's epoch step at ``capacity`` slots as the benchmark serves
    it (load-aware, feasibility admission), on the processor-sharing or
    the token calendar, compiled for the chip."""
    tpl = presets.PRESETS["mathqa_4"]()
    trie = Trie.build(tpl)
    wl = generate_workload(tpl, 64, seed=0)
    ann = wl.exact_annotations(trie)
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.8)))
    engines = sorted({m.engine for m in tpl.models})
    if tokens:
        load = dict(work_model=TokenWorkModel(
            engines={e: EngineTokenModel(e, t_weights_s=0.02, t_kv_s=1e-4,
                                         t_flop_s=1e-4, kv_capacity=64.0,
                                         prefill_tok_s=1e-5)
                     for e in engines},
            mean_service_s={e: 1.0 for e in engines},
            stage_tokens=lambda q, d, m: (1200.0, 600.0)))
    else:
        load = dict(fleet_load=FleetLoadModel(
            engines={e: EngineLoadModel(e, concurrency=4, jitter=0.0)
                     for e in engines},
            mean_service_s={e: 1.0 for e in engines}))
    n = 2 * capacity

    from repro.core.runtime import make_workload_executor
    return _step_for_v5e(one_chip, lambda: run_events(
        trie, ann, obj, np.arange(n) % 64, make_workload_executor(wl),
        arrivals=poisson_arrivals(n, 8.0, seed=1), capacity=capacity,
        policy="dynamic_load_aware", admission="feasibility",
        compiled=True, stream=stream, **load))


@pytest.mark.parametrize("capacity,tokens", [(32, False), (256, True)],
                         ids=["ps32", "tokens256"])
def test_served_step_loop_has_no_scatter_or_binary_search_for_v5e(
        one_chip, capacity, tokens):
    """XLA:TPU runs a scatter one update at a time, dropped updates too,
    so a slot-wide scatter costs in proportion to the slots on every
    event.  The served step's event loop holds none, and no
    binary-search loop: its sums over slots are one-hot reductions."""
    hlo = _served_step(one_chip, capacity, tokens=tokens).as_text()
    lines = _event_loop(hlo)
    assert any(" while(" in line for line in lines[1:])  # the loop's body
    scatters = [line[:160] for line in lines if " scatter(" in line]
    assert not scatters, scatters
    searches = [line[:160] for line in lines
                if " while(" in line and "searchsorted" in line]
    assert not searches, searches


def test_materialized_step_compiles_for_v5e(one_chip):
    """The ``stream=False`` step, which keeps the per-request columns and
    their scatters, still compiles for the chip."""
    step = _served_step(one_chip, 32, tokens=False, stream=False)
    assert step.memory_analysis().temp_size_in_bytes > 0
    assert " scatter(" in step.as_text()
