"""Compile-only checks for TPU v5e: each cell's epoch step at its size.

Nothing runs on a chip.  Each cell's deployment, and each that waits
for a cell, is built as a run builds it; the first call of the served
entry is stopped at its first epoch step, and that step's program is
compiled for a described chip (v5e, and a v5e:2x2 mesh for the four-chip
deployment) with the operands' shapes.  The compiler refuses here what
the chip's compiler would refuse.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.  All such compiles of the
benchmark stay in this one file.
"""
import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

import deploy
import gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
# (configuration, traffic mix): the cells, and the pairs whose cells wait
# for a measurement on the chip (PERF.md, Open questions)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    PAIRS = [(w["config"], w["traffic"]) for w in json.load(f)["workloads"]]
PAIRS += [p for p in (("nl2sql_2.c64", "burst6"),
                      ("mathqa_4.c32", "steady8.live"),
                      ("mathqa_4.c32.x4", "steady8")) if p not in PAIRS]
# and the test fleet configuration: the token calendar's step
PAIRS += [(os.path.join(DATA, "nemo_reflect.c16.json"),
           os.path.join(DATA, "poisson01.json"))]


def _load(kind, name):
    path = name if os.path.isabs(name) else os.path.join(BENCH, kind,
                                                        name + ".json")
    with open(path) as f:
        return json.load(f)


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


class _Captured(Exception):
    pass


@pytest.mark.parametrize("config,traffic", PAIRS, ids=lambda p: (
    os.path.basename(p)[:-len(".json")] if os.path.isabs(p) else p))
def test_cell_step_compiles_for_v5e(topo, config, traffic, monkeypatch):
    from repro.core import events_compiled
    from repro.dist import sharding

    config, mix = _load("configs", config), _load("traffic", traffic)
    chips = int(config["devices"])
    nq = int(config["questions"])
    dep = deploy.build(config, gen.config_tables(config))
    mesh = Mesh(np.array(topo.devices[:chips]), (sharding.LANE_AXIS,))
    seen = {}

    def capture(cfg):
        def step(st, cn, t_hi):
            seen["cfg"] = cfg
            seen["shapes"] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                               jax.numpy.result_type(a)),
                (st, cn, t_hi))
            raise _Captured
        return step

    monkeypatch.setattr(sharding, "lane_mesh", lambda n=None: mesh)
    monkeypatch.setattr(events_compiled, "_ENGINE_CACHE", {})
    build_step = events_compiled._build_step
    monkeypatch.setattr(events_compiled, "_build_step", capture)
    reqs, arr = gen.call_inputs(mix, nq, 5, 0)
    assert reqs.size == int(mix["requests_per_call"])
    with pytest.raises(_Captured):
        dep.call(reqs, arr, epoch=int(mix["arrivals_per_step"]))
    assert seen["cfg"].n_shards == chips

    where = (SingleDeviceSharding(topo.devices[0]) if chips == 1
             else NamedSharding(mesh, PartitionSpec()))
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=where),
        seen["shapes"])
    with jax.enable_x64(True):
        compiled = build_step(seen["cfg"]).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    if chips > 1:
        assert "all-reduce" in compiled.as_text()
