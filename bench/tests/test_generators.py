"""The benchmark's copies reproduce the program's draws and results.

The generators and the plain reference live under ``bench/`` so that no
change to the program can move them; these tests pin that, at today's
program, they still agree with it draw for draw and answer for answer.
"""
import json
import os

import numpy as np
import pytest

import gen
from reference import Reference

from repro.core import presets
from repro.core import workload as wl_mod
from repro.core.trie import Trie

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_arrival_generators_match_the_program(seed):
    ours = gen.poisson_arrivals(300, 8.0, seed)
    assert np.array_equal(ours, wl_mod.poisson_arrivals(300, 8.0, seed=seed))
    stub = gen.poisson_arrivals(64, 8.0, seed)
    assert np.array_equal(gen.trace_arrivals(stub, 500, seed + 1),
                          wl_mod.trace_arrivals(stub, n=500, seed=seed + 1))
    assert np.array_equal(gen.trace_arrivals(stub, 10, seed),
                          wl_mod.trace_arrivals(stub, n=10, seed=seed))


@pytest.mark.parametrize("name,preset", [("mathqa_4.c32", "mathqa_4"),
                                         ("nl2sql_2.c64", "nl2sql_2")])
def test_question_tables_and_workflow_match_the_program(name, preset):
    cfg = _config(name)
    tpl = presets.PRESETS[preset]()
    models = cfg["workflow"]["models"]
    assert [m["name"] for m in models] == [m.name for m in tpl.models]
    for m, spec in zip(models, tpl.models):
        assert (m["price"], m["base_latency"], m["per_token_latency"],
                m["power"], m["engine"]) == (
            spec.price, spec.base_latency, spec.per_token_latency,
            spec.power, spec.engine)
    stages = cfg["workflow"]["stages"]
    assert len(stages) == tpl.max_depth
    for d, st in enumerate(stages):
        assert tuple(st["models"]) == tpl.admissible(d)
        assert (st["tool_cost"], st["tool_latency"]) == \
            tpl.tool_cost_latency(d)
    S, cost, lat = gen.question_tables(models, len(stages), 50, 11)
    ref = wl_mod.generate_workload(tpl, 50, seed=11)
    assert np.array_equal(S, ref.S)
    assert np.array_equal(cost, ref.cost)
    assert np.array_equal(lat, ref.lat)


@pytest.mark.parametrize("name,preset", [("mathqa_4.c32", "mathqa_4"),
                                         ("nl2sql_2.c64", "nl2sql_2")])
def test_reference_trie_and_annotations_match_the_program(name, preset):
    cfg = _config(name)
    wf = cfg["workflow"]
    tables = gen.question_tables(wf["models"], len(wf["stages"]), 40, 3)
    ref = Reference(cfg, tables)
    tpl = presets.PRESETS[preset]()
    trie = Trie.build(tpl)
    assert np.array_equal(ref.parent, trie.parent)
    assert np.array_equal(ref.model, trie.model)
    assert np.array_equal(ref.size, trie.subtree_size)
    assert np.array_equal(ref.child, trie.child)
    wl = wl_mod.Workload(template=tpl, S=tables[0], cost=tables[1],
                         lat=tables[2], difficulty=np.zeros(40))
    ann = wl.exact_annotations(trie)
    assert np.array_equal(ref.acc, ann.acc)
    assert np.array_equal(ref.cost, ann.cost)
    assert np.array_equal(ref.lat, ann.lat)


def test_call_inputs_depend_on_seed_and_call_alone():
    mix = {"requests_per_call": 200, "arrivals_per_step": 64,
           "arrivals": {"kind": "trace", "rate": 8.0, "stub": 32}}
    a = gen.call_inputs(mix, 400, 2**31 + 9, 3)
    b = gen.call_inputs(mix, 400, 2**31 + 9, 3)
    c = gen.call_inputs(mix, 400, 2**31 + 9, 4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert a[0].min() >= 0 and a[0].max() < 400
    assert np.all(np.diff(a[1]) >= 0)


def test_gamma_arrivals_have_the_stated_rate_and_burstiness():
    arr = gen.gamma_arrivals(200_000, 6.0, 3.0, 1)
    gaps = np.diff(arr, prepend=0.0)
    assert abs(gaps.mean() - 1 / 6.0) < 0.01 / 6.0
    assert abs(gaps.std() / gaps.mean() - 3.0) < 0.1
