"""The program's count of published architectures (`ArchConfig`) and its
engine entry `EngineTokenModel.from_published`, against the benchmark's
own count (`bench/fleet.py`, written from the published equations and
importing nothing of the program): parameters, active parameters and KV
bytes a token, and the decode-step curve bit for bit."""
import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from repro.configs import get_config
from repro.models.config import ArchConfig, MLAConfig, MoEConfig
from repro.serving.loadsim import EngineTokenModel, published_arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench_fleet", os.path.join(ROOT, "bench", "fleet.py"))
fleet = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = fleet   # its dataclasses look their module up
_spec.loader.exec_module(fleet)

CHIP = {"hbm_bw": 819e9, "peak_flops": 197e12, "hbm_bytes": 17179869184}


def _fleet_config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "dsv3_reflect.c256.json")) as f:
        return json.load(f)


# the four engines' published configs, verbatim from each model's
# config.json, as the fleet configuration holds them
PUBLISHED = {e["arch"]: e["published"]
             for e in _fleet_config()["fleet"]["engines"].values()}
# https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
PUBLISHED["Moonlight-16B-A3B"] = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
# https://huggingface.co/mistralai/Mistral-Nemo-Base-2407/blob/main/config.json
PUBLISHED["Mistral-Nemo-Base-2407"] = {
    "hidden_size": 5120, "num_hidden_layers": 40, "num_attention_heads": 32,
    "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 14336,
    "vocab_size": 131072, "tie_word_embeddings": False}


def _both(published, replica_chips=8, kv_budget_bytes=40e9,
          context_len=32768):
    kw = dict(chip=CHIP, replica_chips=replica_chips,
              kv_budget_bytes=kv_budget_bytes, context_len=context_len)
    prog = EngineTokenModel.from_published("e", published, **kw,
                                           bytes_per_param=2.0)
    ref = fleet.curve(published, **kw, bytes_per_param=2)
    return prog, ref


def _assert_same_curve(prog, ref):
    got = (prog.t_weights_s, prog.t_kv_s, prog.t_flop_s, prog.kv_capacity,
           prog.prefill_tok_s)
    want = (ref.t_w, ref.t_kv, ref.t_f, ref.kv_cap, ref.prefill_tok_s)
    assert [float(x).hex() for x in got] == [float(x).hex() for x in want]


@pytest.mark.parametrize("arch", sorted(PUBLISHED))
def test_curve_equals_the_benchmarks_bit_for_bit(arch):
    _assert_same_curve(*_both(PUBLISHED[arch]))


def test_deepseek_v3_counts_at_published_widths():
    e = _fleet_config()["fleet"]["engines"]["engine-c"]
    arch = published_arch("engine-c", e["published"])
    assert arch.param_count() == 671_026_404_352
    assert arch.active_param_count() == 37_552_282_624
    assert arch.kv_bytes_per_token(2) == 70_272
    prog, _ = _both(e["published"], e["replica_chips"],
                    e["kv_budget_bytes"], e["context_len"])
    # 37,552,282,624 active parameters at 2 bytes over 128 chips' bandwidth
    assert prog.t_weights_s == 37_552_282_624 * 2 / (128 * 819e9)
    # 70,272 B a token (61 layers x (512 + 64) x 2), not 2·L·kv·hd's 1.75 MB
    assert prog.t_kv_s == 70_272 * 32768 / (128 * 819e9)
    assert prog.kv_capacity == 130.0


def _random_published(rng):
    H = int(rng.choice([4, 8, 16, 32]))
    p = {"hidden_size": int(rng.choice([256, 512, 1024, 2048, 3072])),
         "num_hidden_layers": int(rng.integers(4, 40)),
         "num_attention_heads": H,
         "intermediate_size": int(rng.integers(1, 64)) * 128,
         "vocab_size": int(rng.integers(1000, 200000)),
         "tie_word_embeddings": bool(rng.integers(2))}
    if rng.integers(2):
        p["kv_lora_rank"] = int(rng.choice([64, 128, 256, 512]))
        p["qk_nope_head_dim"] = int(rng.choice([32, 64, 128]))
        p["qk_rope_head_dim"] = int(rng.choice([16, 32, 64]))
        p["v_head_dim"] = int(rng.choice([32, 64, 128]))
        p["num_key_value_heads"] = H
        p["q_lora_rank"] = [None, 0, int(rng.choice([96, 384, 1536]))][
            int(rng.integers(3))]
    else:
        p["num_key_value_heads"] = int(rng.choice([k for k in (1, 2, 4, 8, 16)
                                                   if H % k == 0]))
        if rng.integers(2):
            p["head_dim"] = int(rng.choice([64, 128]))
    if rng.integers(2):
        routed = int(rng.integers(4, 400))
        p.update(n_routed_experts=routed,
                 num_experts_per_tok=int(rng.integers(1, min(routed, 9))),
                 moe_intermediate_size=int(rng.integers(1, 32)) * 64,
                 n_shared_experts=int(rng.integers(0, 3)),
                 first_k_dense_replace=int(rng.integers(0, 4)))
    return p


@pytest.mark.parametrize("seed", range(50))
def test_curve_equals_the_benchmarks_on_random_published_configs(seed):
    rng = np.random.default_rng(seed)
    p = _random_published(rng)
    prog, ref = _both(p, replica_chips=int(rng.integers(1, 9)),
                      kv_budget_bytes=float(rng.uniform(1e8, 5e10)),
                      context_len=int(rng.choice([2048, 8192, 32768])))
    _assert_same_curve(prog, ref)


@pytest.mark.parametrize("extra,named", [
    ({"sliding_window": 4096}, "sliding_window=4096"),
    ({"moe_layer_freq": 2}, "moe_layer_freq=2"),
    ({"linear_attn_config": {"kda_layers": [1, 2]}}, "linear_attn_config"),
], ids=["sliding_window", "moe_layer_freq", "linear_attention"])
def test_each_key_it_does_not_model_is_named(extra, named):
    with pytest.raises(NotImplementedError, match=named):
        _both(dict(PUBLISHED["DeepSeek-V3"], **extra))


def test_unmodelled_keys_are_named_together():
    p = dict(PUBLISHED["Mistral-Nemo-Base-2407"], sliding_window=4096,
             layer_types=["full"] * 40)
    with pytest.raises(NotImplementedError) as err:
        _both(p)
    assert "layer_types" in str(err.value)
    assert "sliding_window=4096" in str(err.value)


# counts of every ArchConfig of src/repro/configs/ before the final norm
# and the latent norms were counted: (params, active)
BEFORE = {
    "arctic-480b": (476850268160, 15584307200),
    "arctic-smoke": (169216, 95488),
    "granite-moe-1b-a400m": (1384962048, 478992384),
    "granite-smoke": (107264, 82688),
    "llava-next-34b": (34397167616, 34397167616),
    "llava-smoke": (108800, 108800),
    "mamba2-1.3b": (1446712320, 1446712320),
    "mamba2-smoke": (117224, 117224),
    "minicpm3-4b": (4261836800, 4261836800),
    "minicpm3-smoke": (107776, 107776),
    "mistral-nemo-12b": (12247777280, 12247777280),
    "nemo-smoke": (106752, 106752),
    "qwen2-72b": (72705376256, 72705376256),
    "qwen2-smoke": (106752, 106752),
    "whisper-base": (109745152, 109745152),
    "whisper-smoke": (229888, 229888),
    "yi-9b": (8829403136, 8829403136),
    "yi-smoke": (102656, 102656),
    "zamba2-2.7b": (2422667680, 2422667680),
    "zamba2-smoke": (186464, 186464),
}


def _repo_archs():
    import importlib
    import pkgutil

    import repro.configs as pkg
    out = []
    for m in pkgutil.iter_modules(pkg.__path__):
        mod = importlib.import_module(f"repro.configs.{m.name}")
        out += [getattr(mod, k) for k in ("CONFIG", "SMOKE")
                if isinstance(getattr(mod, k, None), ArchConfig)]
    return out


@pytest.mark.parametrize("arch", _repo_archs(), ids=lambda a: a.name)
def test_repo_configs_keep_their_count(arch):
    norms = 0
    if arch.attn_kind == "mla":
        norms = arch.n_layers * (arch.mla.q_lora_rank + arch.mla.kv_lora_rank)
    params, active = BEFORE[arch.name]
    assert arch.param_count() == params + arch.d_model + norms
    assert arch.active_param_count() == active + arch.d_model + norms


def test_moe_fields_default_to_the_old_count():
    base = get_config("arctic-480b")
    same = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_shared=0, d_expert=base.d_ff, first_k_dense=0))
    assert same.param_count() == base.param_count()
    assert same.active_param_count() == base.active_param_count()


def test_query_without_latent_is_projected_directly():
    d, H = 2048, 16
    mla = MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                    qk_rope_head_dim=64, v_head_dim=128)
    a = ArchConfig(name="a", family="moe", n_layers=1, d_model=d, n_heads=H,
                   n_kv_heads=H, d_ff=1024, vocab=10, attn_kind="mla",
                   mla=mla, moe=MoEConfig(n_experts=4, top_k=2))
    b = dataclasses.replace(a, mla=dataclasses.replace(mla, q_lora_rank=None))
    qk = 128 + 64
    assert a.param_count() - b.param_count() == (
        d * 1536 + 1536 + 1536 * H * qk - d * H * qk)
    assert b.kv_bytes_per_token(2) == (512 + 64) * 2
