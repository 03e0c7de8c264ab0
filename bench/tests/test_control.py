"""The controls: the reference computed in the precision below the one
that the configuration states, put in the program's place, has to fail
the comparison that the program passes.  There are two, one for each
stated precision: the planner in bfloat16 (below float32), and the clock
and engine calendar in float32 (below float64).  On one call of each
cell's own mix, at its own size: the float32 clock departs from float64
only once the virtual clock has run for some hundreds of seconds (on
1,500 requests it still passes).  The test fleet configuration, whose
stages take seconds, is held at 5,000 requests a call: at 2,000 (20,000 s
of virtual time) the float32 clock still passes on most seeds.  The chip
readings that set the limits are in PERF.md."""
import json
import os

import pytest

import check
import deploy
import gen
from reference import Reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    CELLS = [pytest.param(
        os.path.join(BENCH, "configs", w["config"] + ".json"),
        os.path.join(BENCH, "traffic", w["traffic"] + ".json"), {},
        id=f"{w['config']}-{w['traffic']}")
        for w in json.load(f)["workloads"]]
CELLS.append(pytest.param(os.path.join(DATA, "nemo_reflect.c16.json"),
                          os.path.join(DATA, "poisson01.json"),
                          {"requests_per_call": 5000},
                          id="nemo_reflect.c16-poisson01"))
CONTROLS = [{"planner": "bfloat16"}, {"clock": "float32"}]


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("control", CONTROLS, ids=lambda c: "-".join(
    f"{k}_{v}" for k, v in c.items()))
@pytest.mark.parametrize("config,traffic,size", CELLS)
def test_lower_precision_fails_where_the_program_passes(config, traffic,
                                                         size, control):
    cfg, mix = _load(config), {**_load(traffic), **size}
    seed = 2**31 + 31
    tables = gen.config_tables(cfg)
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
