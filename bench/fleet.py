"""The benchmark's own count of the engines a fleet configuration serves.

A configuration may carry a ``fleet`` section: each engine serves one
published architecture, on a replica of some chips, and the decode-step
curve of its token calendar follows from the architecture's widths and
the chip's peaks.  This module is the yardstick for that curve, written
out plainly from the published equations and importing nothing of the
program:

- `counts`: parameters, parameters active per token, and KV-cache bytes a
  token, for dense GQA/MHA blocks and for latent attention (MLA) with
  routed and shared experts;
- `curve`: the decode-step curve of one replica,
  ``step(b) = max(t_w + t_kv * b, t_f * b)``, its KV cap and its prefill
  seconds a token;
- `engines`: every engine of a ``fleet`` section, each checked to fit its
  replica's memory.

A key of ``published`` that the count does not model raises, naming it:
none is ignored.
"""
from __future__ import annotations

import dataclasses
import math

# keys the count reads
COUNTED = frozenset({
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
    "tie_word_embeddings",
    # latent attention
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim",
    # experts
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "moe_intermediate_size", "first_k_dense_replace", "moe_layer_freq",
    # multi-token prediction modules: not served, not counted
    "num_nextn_predict_layers",
})
# keys the count holds for at these values only
ASSUMED = {"attention_bias": False, "hidden_act": "silu",
           "sliding_window": None}
# keys of a published config that change no parameter count and no cache
NO_SHAPE = frozenset({
    "rms_norm_eps", "rope_theta", "rope_scaling", "max_position_embeddings",
    "model_type", "n_group", "topk_group", "topk_method", "scoring_func",
    "routed_scaling_factor", "norm_topk_prob", "seq_aux", "ep_size",
})
MLA_KEYS = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim")
MOE_KEYS = ("n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size")


@dataclasses.dataclass(frozen=True)
class Counts:
    params: int
    active: int
    kv_bytes_per_token: int


@dataclasses.dataclass(frozen=True)
class Curve:
    """One replica's decode-step curve (seconds), KV cap (sequences) and
    prefill seconds a prompt token."""
    t_w: float
    t_kv: float
    t_f: float
    kv_cap: float
    prefill_tok_s: float

    def step(self, b: float) -> float:
        return max(self.t_w + self.t_kv * b, self.t_f * b)


def counts(published: dict, bytes_per_param: int) -> Counts:
    """Parameters (embedding, output head unless tied, final norm, and per
    layer two RMSNorm weights, attention and MLP), those active per token
    (the routed experts a token is sent to, not all), and KV-cache bytes a
    token."""
    unknown = sorted(set(published) - COUNTED - NO_SHAPE - set(ASSUMED))
    unknown += sorted(f"{k}={published[k]!r}" for k, v in ASSUMED.items()
                      if k in published and published[k] != v)
    if unknown:
        raise NotImplementedError(
            f"the benchmark's count does not model {', '.join(unknown)}")
    p = published
    if p.get("moe_layer_freq", 1) != 1:
        raise NotImplementedError(
            f"moe_layer_freq {p['moe_layer_freq']}: only every layer after "
            f"the leading dense ones is counted as an expert layer")
    d = int(p["hidden_size"])
    L = int(p["num_hidden_layers"])
    H = int(p["num_attention_heads"])
    V = int(p["vocab_size"])
    inter = int(p["intermediate_size"])
    mla = any(k in p for k in MLA_KEYS)
    moe = any(k in p for k in MOE_KEYS)

    if mla:
        nope, rope = int(p["qk_nope_head_dim"]), int(p["qk_rope_head_dim"])
        v, kv_lora = int(p["v_head_dim"]), int(p["kv_lora_rank"])
        q_lora = p.get("q_lora_rank")
        if q_lora:
            attn = d * q_lora + q_lora + q_lora * H * (nope + rope)
        else:
            attn = d * H * (nope + rope)
        attn += (d * (kv_lora + rope) + kv_lora + kv_lora * H * (nope + v)
                 + H * v * d)
        kv_tok = L * (kv_lora + rope) * bytes_per_param
    else:
        kv = int(p.get("num_key_value_heads", H))
        hd = int(p.get("head_dim") or d // H)
        attn = d * hd * (H + 2 * kv) + H * hd * d
        kv_tok = L * 2 * kv * hd * bytes_per_param

    dense_mlp = 3 * d * inter
    layer = 2 * d + attn
    if moe:
        n_dense = int(p.get("first_k_dense_replace", 0))
        expert = 3 * d * int(p["moe_intermediate_size"])
        routed, top = int(p["n_routed_experts"]), int(p["num_experts_per_tok"])
        outside = expert * int(p.get("n_shared_experts", 0)) + d * routed
        n_moe = L - n_dense
        params_layers = (n_dense * (layer + dense_mlp)
                         + n_moe * (layer + outside + routed * expert))
        active_layers = (n_dense * (layer + dense_mlp)
                         + n_moe * (layer + outside + top * expert))
    else:
        params_layers = active_layers = L * (layer + dense_mlp)
    emb = V * d * (1 if p.get("tie_word_embeddings", False) else 2) + d
    return Counts(params=emb + params_layers, active=emb + active_layers,
                  kv_bytes_per_token=kv_tok)


def curve(published: dict, *, chip: dict, replica_chips: int,
          kv_budget_bytes: float, context_len: int,
          bytes_per_param: int) -> Curve:
    """The decode-step curve of one replica of ``replica_chips`` chips:
    weights and each sequence's KV cache of ``context_len`` tokens
    streamed from HBM, two operations a parameter a token of compute, and
    as many sequences KV-resident as the budget holds (at least one)."""
    c = counts(published, bytes_per_param)
    bw = replica_chips * float(chip["hbm_bw"])
    flops = replica_chips * float(chip["peak_flops"])
    kv_seq = c.kv_bytes_per_token * context_len
    t_f = 2.0 * c.active / flops
    return Curve(t_w=c.active * bytes_per_param / bw,
                 t_kv=kv_seq / bw,
                 t_f=t_f,
                 kv_cap=float(max(1, math.floor(kv_budget_bytes / kv_seq))),
                 prefill_tok_s=t_f)


def engines(fleet: dict) -> dict:
    """Each engine's `Curve`, by name; raises where a replica cannot hold
    its weights and KV budget."""
    out = {}
    bpp = int(fleet["bytes_per_param"])
    for name, e in fleet["engines"].items():
        n = int(e["replica_chips"])
        need = counts(e["published"], bpp).params * bpp + float(
            e["kv_budget_bytes"])
        room = n * float(fleet["chip"]["hbm_bytes"])
        if need > room:
            raise ValueError(
                f"engine {name} ({e['arch']}): weights and KV budget need "
                f"{need:.4g} bytes, its {n} chip(s) hold {room:.4g}")
        out[name] = curve(e["published"], chip=fleet["chip"],
                          replica_chips=n,
                          kv_budget_bytes=float(e["kv_budget_bytes"]),
                          context_len=int(e["context_len"]),
                          bytes_per_param=bpp)
    return out
