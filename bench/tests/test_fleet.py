"""Fleet configurations: engines that serve published architectures.

The benchmark's own count (`fleet.py`) reproduces published parameter
and KV-cache counts; on a test configuration whose two engines serve
Mistral-Nemo-Base-2407, the program's token calendar, handed the
benchmark's curve, agrees with the plain reference's while the calendar
reaches both of its regimes (batching under a KV cap, queueing above
it); and two faults planted in that curve read `correct` false.
Configurations without ``fleet`` read as before.
"""
import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

import check
import deploy
import fleet
import gen
import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = "nemo_reflect.c16"
TRAFFIC = "poisson01"
CELL = "nemo.reflect"

# published widths, read from each model's config.json:
# https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json
DEEPSEEK_V3 = {
    "hidden_size": 7168, "num_hidden_layers": 61, "num_attention_heads": 128,
    "num_key_value_heads": 128, "intermediate_size": 18432,
    "vocab_size": 129280, "tie_word_embeddings": False, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "n_routed_experts": 256, "n_shared_experts": 1,
    "num_experts_per_tok": 8, "moe_intermediate_size": 2048,
    "first_k_dense_replace": 3, "moe_layer_freq": 1,
    "num_nextn_predict_layers": 1,
    # keys of the published config that change no count
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "max_position_embeddings": 163840,
    "model_type": "deepseek_v3", "n_group": 8, "topk_group": 4,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "ep_size": 1,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
}
# https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json
KIMI_K2 = {
    "hidden_size": 7168, "num_hidden_layers": 61, "num_attention_heads": 64,
    "num_key_value_heads": 64, "intermediate_size": 18432,
    "vocab_size": 163840, "tie_word_embeddings": False, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "n_routed_experts": 384, "n_shared_experts": 1,
    "num_experts_per_tok": 8, "moe_intermediate_size": 2048,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_nextn_predict_layers": 0,
}
# https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
MOONLIGHT = {
    "hidden_size": 2048, "num_hidden_layers": 27, "num_attention_heads": 16,
    "num_key_value_heads": 16, "intermediate_size": 11264,
    "vocab_size": 163840, "tie_word_embeddings": False, "q_lora_rank": None,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "n_routed_experts": 64, "n_shared_experts": 2,
    "num_experts_per_tok": 6, "moe_intermediate_size": 1408,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_nextn_predict_layers": 0,
}
# https://huggingface.co/mistralai/Mistral-Nemo-Base-2407/blob/main/config.json
MISTRAL_NEMO = {
    "hidden_size": 5120, "num_hidden_layers": 40, "num_attention_heads": 32,
    "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 14336,
    "vocab_size": 131072, "tie_word_embeddings": False,
}


def _data(name):
    with open(os.path.join(HERE, "data", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("published,params,active,kv", [
    (DEEPSEEK_V3, 671_026_404_352, 37_552_282_624, 70_272),
    (KIMI_K2, 1_026_408_209_408, 32_861_477_888, 70_272),
    (MOONLIGHT, 15_960_108_544, 2_914_774_528, 31_104),
    (MISTRAL_NEMO, 12_247_782_400, 12_247_782_400, 163_840),
], ids=["DeepSeek-V3", "Kimi-K2-Instruct", "Moonlight-16B-A3B",
        "Mistral-Nemo-Base-2407"])
def test_counts_at_published_widths(published, params, active, kv):
    assert fleet.counts(published, 2) == fleet.Counts(params, active, kv)


def test_count_names_every_key_it_does_not_model():
    with pytest.raises(NotImplementedError) as err:
        fleet.counts(dict(MISTRAL_NEMO, sliding_window=4096,
                          layer_types=["full"] * 40), 2)
    assert "layer_types" in str(err.value)
    assert "sliding_window=4096" in str(err.value)
    cfg = _data(CONFIG)
    cfg["fleet"]["engines"]["engine-b"]["kv_budget_bytes"] = 1.0e10
    with pytest.raises(ValueError, match="engine-b"):
        fleet.engines(cfg["fleet"])


def test_program_entry_names_deepseek_v3_keys_it_does_not_model():
    cfg = _data(CONFIG)
    e = cfg["fleet"]["engines"]["engine-a"]
    e["arch"], e["published"] = "DeepSeek-V3", DEEPSEEK_V3
    with pytest.raises(NotImplementedError) as err:
        deploy.engine_model("engine-a", cfg["fleet"])
    for key in ("kv_lora_rank", "q_lora_rank", "n_routed_experts",
                "n_shared_experts", "first_k_dense_replace",
                "moe_intermediate_size"):
        assert key in str(err.value)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose one cell is the test configuration."""
    root = tmp_path_factory.mktemp("fleet")
    for sub, name in (("configs", CONFIG), ("traffic", TRAFFIC)):
        os.makedirs(root / "bench" / sub)
        with open(root / "bench" / sub / (name + ".json"), "w") as f:
            json.dump(_data(name), f)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{
        "name": CONFIG, "source": _data(CONFIG)["source"],
        "file": f"bench/configs/{CONFIG}.json", "reduced": [],
        "why": "two engines serving Mistral-Nemo-Base-2407"}]
    spec["workloads"] = [{"name": CELL, "config": CONFIG,
                          "traffic": TRAFFIC, "chips": 1,
                          "why": "the token calendar"}]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return str(root)


def _curve_engine(name, fl):
    """The program's engine model, handed the benchmark's own curve."""
    from repro.serving.loadsim import EngineTokenModel

    c = fleet.engines(fl)[name]
    return EngineTokenModel(name, t_weights_s=c.t_w, t_kv_s=c.t_kv,
                            t_flop_s=c.t_f, kv_capacity=c.kv_cap,
                            prefill_tok_s=c.prefill_tok_s)


# (seed, call) of calls on which the program's own count of Mistral-Nemo,
# which leaves out the final norm (4.2e-7 of its weight stream), shifts a
# deadline or plan decision (PERF.md, Open questions)
SHIFTED = [(2**31 + 6354, 4), (2**31 + 6355, 1), (2**31 + 6356, 5)]


def test_token_calendar_agrees_with_the_reference_in_both_regimes(
        monkeypatch):
    from reference import Reference

    monkeypatch.setattr(deploy, "engine_model", _curve_engine)
    cfg, mix = _data(CONFIG), _data(TRAFFIC)
    tables = gen.config_tables(cfg)
    dep = deploy.build(cfg, tables)
    ref = Reference(cfg, tables)
    for seed, k in SHIFTED:
        reqs, arr = gen.call_inputs(mix, cfg["questions"], seed, k)
        prog = check.program_summary(dep.call(
            reqs, arr, epoch=int(mix["arrivals_per_step"])))
        s = ref.simulate(reqs, arr)
        g = check.gaps(prog, s)
        assert g["count_gap"] == 0 and check.passes(g), (seed, k, g)
        # the engines batched under their KV caps and queued above them
        assert s["batched_s"] > 0 and s["over_cap_s"] > 0
        assert s["rejected"] == 0


def test_fleet_run_goes_end_to_end(root):
    r = run.run_cell(CELL, 2**31 + 57, 0.3, False, root=root)
    assert r["failed"] == 0 and r["attempted"] >= 2000
    assert set(r["metrics"]) == {"events_per_s", "setup_s"}
    assert set(r["checks"]) == {"count_gap", "latency_gap", "cost_gap"}


@pytest.mark.parametrize("fault,correct", [
    (lambda m: m, True),
    (lambda m: dataclasses.replace(m, kv_capacity=m.kv_capacity + 1), False),
    (lambda m: dataclasses.replace(m, t_kv_s=0.0), False),
], ids=["none", "kv_cap_one_higher", "curve_without_t_kv"])
def test_planted_engine_fault_reads_incorrect(root, fault, correct,
                                              monkeypatch):
    monkeypatch.setattr(deploy, "engine_model",
                        lambda name, fl: fault(_curve_engine(name, fl)))
    r = run.run_cell(CELL, 2**31 + 58, 0.3, False, root=root)
    assert r["failed"] == 0
    assert r["correct"] is correct


def test_configuration_without_fleet_reads_as_before():
    with open(os.path.join(BENCH, "configs", "mathqa_4.c32.json")) as f:
        cfg = json.load(f)
    tables = gen.config_tables(cfg)
    h = hashlib.sha256()
    for a in tables:
        h.update(np.ascontiguousarray(a).tobytes())
    # the tables and deployment of the parent of the fleet format
    assert len(tables) == 3
    assert h.hexdigest() == ("466fb554d43c07ed56585032a259aad1"
                             "82314f04bf244c5b505258048eb27212")
    dep = deploy.build(cfg, tables)
    assert dep.decode is None and dep.prompt_tokens is None
    load = dep.kwargs["fleet_load"]
    assert dep.kwargs == {"capacity": 32, "policy": "dynamic_load_aware",
                          "fleet_load": load, "admission": "feasibility",
                          "devices": None}
    assert {e: (m.concurrency, m.jitter) for e, m in load.engines.items()} \
        == {e: (4, 0.0) for e in ("engine-a", "engine-b", "engine-c")}
    assert {e: v.hex() for e, v in load.mean_service_s.items()} == {
        "engine-a": "0x1.bca636b967b05p-1",
        "engine-b": "0x1.d9e08923d4360p+0",
        "engine-c": "0x1.560422311ad70p+0"}
    assert dep.obj.lat_cap.hex() == "0x1.079a92101c66cp+3"
