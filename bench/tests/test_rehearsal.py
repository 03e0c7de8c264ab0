"""The whole run, rehearsed on the CPU at a tiny size.

`run.run_cell` is what the chip command runs after its platform check;
here it runs each cell's window, comparison and (traced) reduction with
short calls.
"""
import json
import os
import subprocess
import sys

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = {"requests_per_call": 120}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct_and_complete(cell):
    r = run.run_cell(cell, 2**31 + 17, 0.5, False, mix_overrides=TINY)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] >= 120
    assert set(r["metrics"]) == {"events_per_s", "setup_s"}
    assert r["metrics"]["events_per_s"]["value"] > 0
    assert r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"count_gap", "latency_gap", "cost_gap"}


def test_a_new_seed_compiles_no_new_engine_program():
    from repro.core.events_compiled import compiled_engine_cache_size

    run.run_cell("mathqa4.replay", 2**31 + 41, 0.2, False,
                 mix_overrides=TINY)
    programs = compiled_engine_cache_size()
    r = run.run_cell("mathqa4.replay", 2**31 + 42, 0.2, False,
                     mix_overrides=TINY)
    assert r["correct"] is True
    assert compiled_engine_cache_size() == programs


def test_tiny_traced_run_reports_what_the_cpu_trace_holds():
    r = run.run_cell("mathqa4.replay", 5, 0.5, True, mix_overrides=TINY)
    assert r["correct"] is True
    # the CPU trace has no device plane: the device readers find nothing
    assert r["metrics"] == {}
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "mathqa4.replay", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
