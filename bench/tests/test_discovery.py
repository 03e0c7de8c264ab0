"""A configuration, a traffic mix and a per-layer metric are added as new
files plus ``BENCHMARK.json`` entries, and the harness finds each by
name: no file the benchmark already has is edited."""
import filecmp
import json
import os
import shutil

import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

READER = '''"""traced_calls: how many calls the traced window held."""


def read(ctx):
    return float(ctx.calls) if ctx.calls else None
'''


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = os.path.join(root, "bench")

    with open(os.path.join(bench, "configs", "nl2sql_2.c64.json")) as f:
        cfg = json.load(f)
    cfg["name"], cfg["capacity"] = "nl2sql_2.c16", 16
    with open(os.path.join(bench, "configs", "nl2sql_2.c16.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "poisson4.json"), "w") as f:
        json.dump({"requests_per_call": 150, "arrivals_per_step": 64,
                   "arrivals": {"kind": "gamma", "rate": 4.0, "cv": 1.0}},
                  f)
    with open(os.path.join(bench, "metrics", "traced_calls.py"), "w") as f:
        f.write(READER)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "nl2sql_2.c16", "source": "https://arxiv.org/abs/2605.23914",
        "file": "bench/configs/nl2sql_2.c16.json", "reduced": ["questions"],
        "why": "a smaller slot pool"})
    spec["workloads"].append({
        "name": "nl2sql2.p4", "config": "nl2sql_2.c16", "traffic": "poisson4",
        "chips": 1, "why": "steady Poisson arrivals"})
    spec["per_layer"].append({
        "name": "traced_calls", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "entry and drain",
        "moves": "events_per_s", "workloads": ["nl2sql2.p4"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    r = run.run_cell("nl2sql2.p4", 8, 0.3, False, root=root)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"events_per_s", "setup_s"}
    r = run.run_cell("nl2sql2.p4", 8, 0.3, True, root=root)
    assert r["correct"] is True
    assert r["metrics"]["traced_calls"]["value"] >= 1

    # every file the benchmark already had is unchanged
    for dirpath, _, files in os.walk(os.path.join(ROOT, "bench")):
        if "__pycache__" in dirpath or os.sep + "data" in dirpath:
            continue
        for name in files:
            src = os.path.join(dirpath, name)
            dst = os.path.join(bench, os.path.relpath(src, os.path.join(
                ROOT, "bench")))
            assert filecmp.cmp(src, dst, shallow=False), src
