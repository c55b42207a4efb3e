"""Unified model configuration covering every assigned architecture family.

One dataclass describes dense / MoE / SSM / hybrid / pattern / encoder-only
models; the factory in ``models/model.py`` reads the fields that apply to
the family and ignores the rest.  Every field corresponds to a published
hyper-parameter of one of the assigned architectures (see
``repro/configs/``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-style Multi-head Latent Attention dimensions."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed-expert configuration (Mixtral / DeepSeek-V2 style)."""

    num_experts: int = 8
    top_k: int = 2
    d_expert: int = 14336           # per-expert FFN hidden dim
    num_shared_experts: int = 0     # DeepSeek shared experts (always-on)
    d_shared_expert: int = 0        # hidden dim of the shared expert block
    capacity_factor: float = 1.25   # dispatch buffer slack
    router_aux_weight: float = 0.01  # load-balancing aux loss weight
    first_dense_layers: int = 0     # leading dense layers (DeepSeek-V2 has 1)
    first_dense_d_ff: int = 0       # FFN dim of those dense layers
    dispatch_quant: str = "none"    # none | int8 — EP all-to-all payload


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) configuration."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256
    n_groups: int = 1               # B/C groups (Mamba2 uses 1)
    dt_min: float = 0.001
    dt_max: float = 0.1
    a_init_range: Tuple[float, float] = (1.0, 16.0)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  ``family`` picks the block layout."""

    name: str = "model"
    # dense | moe | ssm | hybrid | pattern | encoder
    family: str = "dense"
    # none | vq_tokens (chameleon) | audio_frames (hubert) — modality frontend
    # stubs: input_specs() provides precomputed embeddings / token ids.
    frontend: str = "none"

    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0               # 0 → d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 32000

    # --- attention ---
    attn_type: str = "full"         # full | swa | mla | none
    sliding_window: int = 0         # >0 → sliding-window attention width
    rope_theta: float = 10000.0
    use_rope: bool = True
    qk_norm: bool = False           # chameleon-style qk layernorm
    attn_bias: bool = False
    attn_logit_softcap: float = 0.0
    mla: Optional[MLAConfig] = None

    # --- ffn ---
    activation: str = "swiglu"      # swiglu | geglu | relu2 | gelu
    mlp_bias: bool = False

    # --- block layout ---
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    norm_eps: float = 1e-5
    parallel_block: bool = False    # command-r style parallel attn+FFN
    tie_embeddings: bool = False
    embed_scale: bool = False       # gemma-style sqrt(d_model) embed scaling
    final_logit_softcap: float = 0.0

    # --- moe / ssm / hybrid ---
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2): one *shared* attention+MLP block applied every
    # `hybrid_attn_every` SSM layers (weights shared across applications).
    hybrid_attn_every: int = 6
    # pattern (nemotron-h): one character per layer, each layer one
    # pre-norm mixer with its own weights added to the residual —
    # "M" mamba2, "-" MLP, "*" attention
    layer_pattern: str = ""

    # --- encoder-only (hubert) ---
    encoder_only: bool = False
    frontend_dim: int = 0           # dim of precomputed frontend features

    # --- dtypes ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    # --- remat / scan ---
    remat_policy: str = "minimal"   # none | minimal | full
    scan_layers: bool = True

    # ------------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim > 0 else self.d_model // self.num_heads

    @property
    def q_groups(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def d_inner(self) -> int:
        assert self.ssm is not None
        return self.ssm.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        assert self.ssm is not None
        return self.d_inner // self.ssm.head_dim

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pattern_runs(self) -> Tuple[Tuple[str, int], ...]:
        """``layer_pattern`` as runs of one kind: ``(kind, length)``."""
        runs = []
        for ch in self.layer_pattern:
            if ch not in "M-*":
                raise ValueError(f"unknown layer kind {ch!r} in "
                                 f"{self.layer_pattern!r}")
            if runs and runs[-1][0] == ch:
                runs[-1][1] += 1
            else:
                runs.append([ch, 1])
        return tuple((k, n) for k, n in runs)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    # ------------------------------------------------------------------
    def num_params(self) -> int:
        """Exact parameter count of the constructed model (analytic)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        hd = self.head_dim_
        n = V * d  # embed
        if not self.tie_embeddings and not self.encoder_only:
            n += V * d  # lm head
        if self.encoder_only:
            n += V * d  # prediction head
        if self.frontend == "audio_frames" and self.frontend_dim:
            n += self.frontend_dim * d + d   # projection + mask embedding

        def attn_params() -> int:
            if self.attn_type == "mla":
                m = self.mla
                qk = m.qk_nope_head_dim + m.qk_rope_head_dim
                p = d * m.q_lora_rank + m.q_lora_rank * self.num_heads * qk
                p += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                p += m.kv_lora_rank * self.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
                p += self.num_heads * m.v_head_dim * d
                p += m.q_lora_rank + m.kv_lora_rank  # the two lora norms
                return p
            p = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd
            p += self.num_heads * hd * d
            if self.attn_bias:
                p += (self.num_heads + 2 * self.num_kv_heads) * hd + d
            if self.qk_norm:
                p += 2 * hd
            return p

        def mlp_params(dff: int) -> int:
            if self.activation in ("swiglu", "geglu"):
                return 3 * d * dff
            return 2 * d * dff

        def ssm_params() -> int:
            s = self.ssm
            di = self.d_inner
            H = self.ssm_heads
            N = s.d_state
            conv_ch = di + 2 * s.n_groups * N
            p = d * (2 * di + 2 * s.n_groups * N + H)   # in_proj (x,z,B,C,dt)
            p += conv_ch * s.d_conv + conv_ch            # depthwise conv + bias
            p += H + H + H                               # A_log, D, dt_bias
            p += di                                      # pre-out norm
            p += di * d                                  # out_proj
            return p

        norm_p = d  # rmsnorm weight (layernorm adds bias)
        if self.norm == "layernorm":
            norm_p = 2 * d

        if self.family in ("dense", "encoder"):
            per_layer = attn_params() + mlp_params(self.d_ff) + 2 * norm_p
            if self.parallel_block:
                per_layer = attn_params() + mlp_params(self.d_ff) + norm_p
            n += L * per_layer + norm_p
        elif self.family == "moe":
            m = self.moe
            moe_layer = attn_params() + 2 * norm_p
            moe_layer += d * m.num_experts  # router
            moe_layer += m.num_experts * mlp_params(m.d_expert) // 1
            if m.num_shared_experts:
                moe_layer += mlp_params(m.d_shared_expert)
            dense_layer = attn_params() + mlp_params(m.first_dense_d_ff) + 2 * norm_p
            n += (L - m.first_dense_layers) * moe_layer
            n += m.first_dense_layers * dense_layer + norm_p
        elif self.family == "ssm":
            n += L * (ssm_params() + norm_p) + norm_p
        elif self.family == "hybrid":
            n += L * (ssm_params() + norm_p) + norm_p
            # one shared attention+MLP block
            n += attn_params() + mlp_params(self.d_ff) + 2 * norm_p
        elif self.family == "pattern":
            per_kind = {"M": ssm_params(), "-": mlp_params(self.d_ff),
                        "*": attn_params()}
            n += sum(per_kind[k] + norm_p for k in self.layer_pattern)
            n += norm_p
        else:
            raise ValueError(self.family)
        return n

    def active_params(self) -> int:
        """Activated parameters per token (= num_params for non-MoE)."""
        if self.family != "moe":
            return self.num_params()
        m = self.moe
        full = self.num_params()
        # remove the routed experts' inactive share
        def mlp_params(dff: int) -> int:
            d = self.d_model
            if self.activation in ("swiglu", "geglu"):
                return 3 * d * dff
            return 2 * d * dff
        routed_layers = self.num_layers - m.first_dense_layers
        inactive = routed_layers * (m.num_experts - m.top_k) * mlp_params(m.d_expert)
        return full - inactive

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        """KV-cache bytes per token per layer-application (serving planner)."""
        if self.attn_type == "mla":
            return (self.mla.kv_lora_rank + self.mla.qk_rope_head_dim) * dtype_bytes
        if self.attn_type == "none":
            return 0
        return 2 * self.num_kv_heads * self.head_dim_ * dtype_bytes

    # ------------------------------------------------------------------
    def to_dict(self) -> dict:
        """JSON-safe dict (tuples become lists; ``from_dict`` restores)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ModelConfig":
        d = dict(d)
        if d.get("mla") is not None:
            d["mla"] = MLAConfig(**d["mla"])
        if d.get("moe") is not None:
            d["moe"] = MoEConfig(**d["moe"])
        if d.get("ssm") is not None:
            ssm = dict(d["ssm"])
            ssm["a_init_range"] = tuple(ssm["a_init_range"])
            d["ssm"] = SSMConfig(**ssm)
        return cls(**d)


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Shrink a config to smoke-test size, preserving family structure."""
    small = dict(
        num_layers=min(cfg.num_layers, 2),
        d_model=128,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 4) if cfg.num_kv_heads > 1 else 1,
        head_dim=32,
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=256,
        frontend_dim=64 if cfg.frontend_dim else 0,
        sliding_window=min(cfg.sliding_window, 32) if cfg.sliding_window else 0,
    )
    if cfg.mla is not None:
        small["mla"] = MLAConfig(q_lora_rank=64, kv_lora_rank=32,
                                 qk_nope_head_dim=32, qk_rope_head_dim=16,
                                 v_head_dim=32)
    if cfg.moe is not None:
        small["moe"] = dataclasses.replace(
            cfg.moe, num_experts=min(cfg.moe.num_experts, 4),
            top_k=min(cfg.moe.top_k, 2), d_expert=128,
            d_shared_expert=128 if cfg.moe.num_shared_experts else 0,
            first_dense_d_ff=256 if cfg.moe.first_dense_layers else 0)
    if cfg.ssm is not None:
        small["ssm"] = dataclasses.replace(
            cfg.ssm, d_state=16, head_dim=16, chunk_size=16)
        small["head_dim"] = 0
    if cfg.family == "hybrid":
        small["hybrid_attn_every"] = 2
        small["num_layers"] = 4
    small.update(overrides)
    return dataclasses.replace(cfg, **small)
