"""Unified architecture configuration for the assigned model families.

One ``ArchConfig`` describes any of the 10 assigned architectures; family-
specific extensions live in optional sub-configs.  ``reduced()`` produces
the CPU-smoke-test variant of the same family (same code paths, tiny dims).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2 style; minicpm3).

    ``q_lora_rank`` 0 or None projects the query directly from the
    hidden state (DeepSeek-V2-Lite), with no query latent or its norm."""

    q_lora_rank: int | None = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 32
    top_k: int = 8
    capacity_factor: float = 1.25
    expert_group: int = 512      # tokens per dispatch group (memory knob)
    dense_residual: bool = False  # arctic: dense MLP in parallel with MoE
    dense_d_ff: int = 0           # width of the dense residual MLP
    n_shared: int = 0             # shared experts, every token takes them
    d_expert: int = 0             # one expert's width; 0 means d_ff
    first_k_dense: int = 0        # leading layers with a dense d_ff MLP


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD (state-space duality) block parameters."""

    state_dim: int = 64
    head_dim: int = 64
    expand: int = 2
    chunk: int = 256
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style hybrid: SSM backbone + one weight-shared attention
    block applied every ``attn_every`` layers."""

    attn_every: int = 6
    window: int = 4096  # sliding window for the shared attention at long ctx


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    n_encoder_layers: int = 6
    encoder_len: int = 1500  # precomputed audio-frame embeddings (stub)


@dataclasses.dataclass(frozen=True)
class VLMConfig:
    n_patches: int = 2880     # anyres patch embeddings (stub frontend)
    patch_dim: int = 1152     # frontend embedding dim before projection


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    qkv_bias: bool = False
    attn_kind: str = "gqa"    # gqa | mla
    rope_theta: float = 1e4
    tie_embeddings: bool = False
    mla: MLAConfig | None = None
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    hybrid: HybridConfig | None = None
    encdec: EncDecConfig | None = None
    vlm: VLMConfig | None = None
    # execution knobs
    dtype: str = "bfloat16"
    remat: str = "full"       # none | full
    scan_layers: bool = True
    use_pallas: bool = False  # TPU kernel path (validated via interpret=True)
    logit_chunk_vocab: int = 0  # >0: chunked xent to avoid full-logit buffer
    vocab_pad_to: int = 0     # >0: pad embedding tables to a multiple (TP)

    def __post_init__(self):
        if self.head_dim is None and self.n_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        if self.vocab_pad_to <= 0:
            return self.vocab
        m = self.vocab_pad_to
        return ((self.vocab + m - 1) // m) * m

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch serve 500k-token contexts? (ssm / hybrid-window)."""
        return self.family in ("ssm", "hybrid")

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs are (or contain) decoders

    def param_count(self) -> int:
        """Analytic parameter count (embeddings, blocks, final norm), for
        roofline."""
        d, f, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        emb = V * d * (1 if self.tie_embeddings else 2) + d
        per_layer = 0
        if self.family in ("ssm", "hybrid"):
            s = self.ssm
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            N = s.state_dim
            conv_ch = d_in + 2 * N
            # mirrors layers.init_mamba: in_proj (z,x,B,C,dt), depthwise
            # conv, A_log/D/dt_bias, gated norm, out_proj (+ layer norm)
            per_layer = (
                d * (2 * d_in + 2 * N + nheads)
                + (s.conv_width + 1) * conv_ch
                + 3 * nheads + d_in + d_in * d + d
            )
            ssm_total = L * per_layer
            attn_total = 0
            if self.family == "hybrid":
                hd = self.head_dim
                # one weight-shared attention + MLP block
                attn_total = (
                    d * hd * (self.n_heads + 2 * self.n_kv_heads)
                    + self.n_heads * hd * d + 3 * d * f + 2 * d
                )
            return emb + ssm_total + attn_total
        if self.attn_kind == "mla":
            m = self.mla
            H = self.n_heads
            qk = m.qk_nope_head_dim + m.qk_rope_head_dim
            if m.q_lora_rank:
                # query latent, its RMSNorm, and the up-projection
                attn = d * m.q_lora_rank + m.q_lora_rank + m.q_lora_rank * H * qk
            else:
                attn = d * H * qk
            attn += (
                d * (m.kv_lora_rank + m.qk_rope_head_dim) + m.kv_lora_rank
                + m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
                + H * m.v_head_dim * d
            )
        else:
            hd = self.head_dim
            attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        dense_mlp = 3 * d * f
        mlp_total = L * dense_mlp
        if self.moe is not None:
            e = self.moe
            mlp = (e.n_experts + e.n_shared) * 3 * d * self._expert_width
            if e.dense_residual:
                mlp += 3 * d * e.dense_d_ff
            mlp += d * e.n_experts  # router
            mlp_total = e.first_k_dense * dense_mlp + (L - e.first_k_dense) * mlp
        total = emb + L * (attn + 2 * d) + mlp_total
        if self.encdec is not None:
            total += self.encdec.n_encoder_layers * (attn + 3 * d * f + 2 * d)
            total += L * attn  # decoder cross-attention blocks
        if self.vlm is not None:
            total += self.vlm.patch_dim * d  # frontend projection
        return int(total)

    @property
    def _expert_width(self) -> int:
        return self.moe.d_expert or self.d_ff

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: the top-k routed experts of
        each expert layer, not all of them)."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        inactive = ((self.n_layers - e.first_k_dense) * (e.n_experts - e.top_k)
                    * 3 * self.d_model * self._expert_width)
        return int(self.param_count() - inactive)

    def kv_bytes_per_token(self, bytes_per_param: float) -> float:
        """KV-cache bytes a token over all layers: keys and values of every
        KV head (GQA/MHA), or the latent and the shared rope key (MLA)."""
        if self.attn_kind == "mla":
            m = self.mla
            return self.n_layers * (m.kv_lora_rank + m.qk_rope_head_dim) * bytes_per_param
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim * bytes_per_param


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    """One (input-shape) cell from the assignment."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}
