"""The controls: the reference computed in the precision below the one
that the configuration states, put in the program's place, has to fail
the comparison that the program passes.  There are two, one for each
stated precision: the planner in bfloat16 (below float32), and the clock
and engine calendar in float32 (below float64).  On one call of each
cell's own mix, at its own size: the float32 clock departs from float64
only once the virtual clock has run for some hundreds of seconds (on
1,500 requests it still passes).  The chip readings that set the limits
are in PERF.md."""
import json
import os

import pytest

import check
import deploy
import gen
from reference import Reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    CELLS = [(w["config"], w["traffic"])
             for w in json.load(f)["workloads"]]
CONTROLS = [{"planner": "bfloat16"}, {"clock": "float32"}]


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("control", CONTROLS, ids=lambda c: "-".join(
    f"{k}_{v}" for k, v in c.items()))
@pytest.mark.parametrize("config,traffic", CELLS)
def test_lower_precision_fails_where_the_program_passes(config, traffic,
                                                         control):
    cfg, mix = _load("configs", config), _load("traffic", traffic)
    seed = 2**31 + 31
    wf = cfg["workflow"]
    tables = gen.question_tables(wf["models"], len(wf["stages"]),
                                 cfg["questions"], cfg["questions_seed"])
    ref = Reference(cfg, tables)
    assert ref.precision == {"clock": "float64", "planner": "float32"}
    dep = deploy.build(cfg, tables)
    reqs, arr = gen.call_inputs(mix, cfg["questions"], seed, 0)
    prog = check.program_summary(
        dep.call(reqs, arr, epoch=int(mix["arrivals_per_step"])))
    stated = ref.simulate(reqs, arr)
    sound = check.gaps(prog, stated)
    lower = check.gaps(ref.simulate(reqs, arr, **control), stated)
    assert check.passes(sound), sound
    assert not check.passes(lower), lower
