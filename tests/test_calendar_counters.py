"""The token calendar's scope and counters in the compiled engine.

On a small fleet of two engines that serve a latent-attention (MLA)
architecture at its published widths, with KV caps of 4 and 2 sequences
and 16 slots, the engine-seconds the compiled calendar spends batching
under a cap (``batched_s``) and above one (``over_cap_s``) equal the
benchmark's plain reference (`bench/reference.py`, which imports nothing
of the program), and the ``vinelm/calendar`` scope names the calendar's
operations in token mode only.  The benchmark's readers of the two
(``calendar_us_per_event``, ``over_cap_share``) read them from a made
trace, and find nothing where the program has neither.
"""
import copy
import importlib.util
import os
import sys
import types

import numpy as np
import pytest

from repro.core import events_compiled

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(name, sub=""):
    """A module of ``bench/``, registered under its plain name: the
    benchmark's modules import each other so."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "bench", sub, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


fleet, gen, deploy, check, reference, xplane, scopes = (
    _bench(n) for n in ("fleet", "gen", "deploy", "check", "reference",
                        "xplane", "scopes"))

# https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json
# (the keys that shape it): MLA without a query latent, 64 routed experts,
# top-6, 2 shared, one leading dense layer
V2_LITE = {
    "hidden_size": 2048, "num_hidden_layers": 27, "num_attention_heads": 16,
    "num_key_value_heads": 16, "intermediate_size": 10944,
    "vocab_size": 102400, "tie_word_embeddings": False, "q_lora_rank": None,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "n_routed_experts": 64, "n_shared_experts": 2,
    "num_experts_per_tok": 6, "moe_intermediate_size": 1408,
    "first_k_dense_replace": 1, "moe_layer_freq": 1}

# 31,104 B a token x 8,192 tokens = 254.8 MB a sequence: caps of 4 and 2
ENGINE = {"arch": "DeepSeek-V2-Lite", "published": V2_LITE,
          "replica_chips": 2, "context_len": 8192}
CONFIG = {
    "workflow": {
        "name": "Lite-Reflect-2", "min_depth": 1,
        "models": [
            {"name": "lite-a", "price": 0.00015, "power": 0.5,
             "engine": "engine-a"},
            {"name": "lite-b", "price": 0.0003, "power": 0.6,
             "engine": "engine-b"}],
        "stages": [{"models": [0, 1], "tool_cost": 0.0,
                    "tool_latency": 0.0}] * 3},
    "questions": 100, "questions_seed": 0, "capacity": 16,
    "policy": "dynamic_load_aware", "admission": "feasibility",
    "objective": {"kind": "max_acc", "lat_cap_quantile": 0.8},
    "devices": 1, "precision": {"clock": "float64", "planner": "float32"},
    "fleet": {
        "chip": {"hbm_bw": 819e9, "peak_flops": 197e12,
                 "hbm_bytes": 17179869184},
        "bytes_per_param": 2,
        "engines": {"engine-a": dict(ENGINE, kv_budget_bytes=1.1e9),
                    "engine-b": dict(ENGINE, kv_budget_bytes=0.6e9)},
        "prompt_tokens": [1200, 1900, 2600],
        "decode_tokens": {"median": 600, "sigma": 0.45}},
}
N_REQUESTS, RATE, EPOCH = 240, 1.5, 64


@pytest.fixture(scope="module")
def fleet_call():
    """One call of the compiled token calendar and the reference's replay
    of it, with the program's scope map of the call."""
    cfg = copy.deepcopy(CONFIG)
    caps = {e: c.kv_cap for e, c in fleet.engines(cfg["fleet"]).items()}
    assert caps == {"engine-a": 4.0, "engine-b": 2.0}
    tables = gen.config_tables(cfg)
    dep = deploy.build(cfg, tables)
    rng = np.random.default_rng(7)
    reqs = rng.integers(0, cfg["questions"], N_REQUESTS)
    arr = gen.poisson_arrivals(N_REQUESTS, RATE, 11)
    saved = events_compiled._ENGINE_CALLS
    events_compiled._ENGINE_CALLS = {}
    try:
        summary = dep.call(reqs, arr, epoch=EPOCH)
        (smap,) = events_compiled.engine_scope_maps()
    finally:
        events_compiled._ENGINE_CALLS = saved
    ref = reference.Reference(cfg, tables).simulate(reqs, arr)
    return summary, ref, smap


def test_counters_equal_the_reference(fleet_call):
    summary, ref, _ = fleet_call
    g = check.gaps(check.program_summary(summary), ref)
    assert g["count_gap"] == 0 and check.passes(g), g
    for key in ("batched_s", "over_cap_s"):
        assert ref[key] > 0.0, key
        assert summary[key] == pytest.approx(ref[key], rel=1e-12, abs=0.0)


def test_calendar_scope_is_named_in_token_mode(fleet_call):
    _, _, smap = fleet_call
    names = set(smap.values())
    cal = events_compiled.CALENDAR_SCOPE + "/"
    # the advance and the rates under the clock, the delay row under a
    # replan round
    assert any(n.startswith("jit(step)/vinelm/clock/while/body/" + cal)
               for n in names)
    assert any("/vinelm/dispatch/" in n and cal in n for n in names)


def test_calendar_scope_is_absent_in_processor_sharing_mode(monkeypatch):
    from fleetlib import random_setup

    from repro.core.controller import Objective
    from repro.core.runtime import make_workload_executor
    from repro.core.workload import poisson_arrivals
    from repro.serving.loadsim import EngineLoadModel, FleetLoadModel

    monkeypatch.setattr(events_compiled, "_ENGINE_CALLS", {})
    _, trie, wl, ann = random_setup(5)
    engines = sorted({m.engine for m in trie.template.models})
    load = FleetLoadModel(
        engines={e: EngineLoadModel(e, concurrency=2, jitter=0.0)
                 for e in engines},
        mean_service_s={e: 1.0 for e in engines})
    obj = Objective("max_acc",
                    lat_cap=float(np.quantile(ann.lat[trie.terminal], 0.7)))
    summary, _ = events_compiled.run_events_compiled(
        trie, ann, obj, np.arange(24), make_workload_executor(wl),
        arrivals=poisson_arrivals(24, rate=3.0, seed=5), capacity=4,
        policy="dynamic_load_aware", fleet_load=load,
        admission="feasibility", stream=True, epoch=8)
    assert "batched_s" not in summary and "over_cap_s" not in summary
    (smap,) = events_compiled.engine_scope_maps()
    names = set(smap.values())
    assert any("vinelm/clock/" in n for n in names)
    assert not any(events_compiled.CALENDAR_SCOPE in n for n in names)


DEV = "/device:TPU:0"
BODY = "jit(step)/vinelm/clock/while/body/"
SMAP = {
    "fusion.1": BODY + "vinelm/calendar/add",
    "fusion.2": BODY + "vinelm/calendar/jit(_where)/select_n",
    "fusion.3": BODY + "vinelm/admit/while/body/vinelm/dispatch/cond/"
                "branch_1_fun/vinelm/calendar/div",
    "fusion.4": BODY + "lt",
}


def _made_trace():
    def op(name, a, b):
        return (f"%{name} = f64[4]{{0}} op(f64[4]{{0}} %p)", a, b)
    return xplane.Trace(
        spans=[("bench.call", 0, 1000)],
        ops={DEV: [op("fusion.1", 100, 150), op("fusion.2", 140, 200),
                   op("fusion.4", 200, 300), op("fusion.3", 400, 450),
                   # another program's operation, outside the step
                   op("fusion.1", 950, 990)]},
        modules={DEV: [("jit_step(3)", 100, 900),
                       ("jit_convert(4)", 940, 995)]})


@pytest.mark.parametrize("program_has_them", [True, False],
                         ids=["program", "parent"])
def test_benchmark_readers_of_the_calendar(monkeypatch, program_has_them):
    cal = _bench("calendar_us_per_event", "metrics")
    share = _bench("over_cap_share", "metrics")
    drain = {"requests": 8, "events": 10, "sweeps": 7, "epochs": 1}
    smap = {k: v.replace("vinelm/calendar/", "") for k, v in SMAP.items()}
    if program_has_them:
        drain.update(batched_s=30.0, over_cap_s=10.0)
        smap = SMAP
    monkeypatch.setattr(scopes, "scope_map", lambda: smap)
    monkeypatch.setattr(scopes, "program_spans", lambda: [
        ("vinelm.drain", 920, 930, drain)])
    ctx = types.SimpleNamespace(trace=_made_trace(), devices=[DEV],
                                events=10, calls=1)
    if program_has_them:
        # the union of [100, 200) and [400, 450), over 10 events
        assert cal.read(ctx) == pytest.approx(150 / 1e3 / 10)
        assert share.read(ctx) == pytest.approx(25.0)
    else:
        assert cal.read(ctx) is None and share.read(ctx) is None
