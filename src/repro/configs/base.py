"""Model/shape configuration system.

Every assigned architecture is a `ModelConfig` (exact published numbers) plus a
`smoke()` reduction of the same family for CPU tests.  Input shapes are the four
assigned (seq_len, global_batch, kind) cells; `input_specs()` produces
ShapeDtypeStruct stand-ins (no allocation) for the dry-run.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  Families:

    dense    -- GQA transformer (llama/qwen/granite/nemotron)
    moe      -- fine-grained MoE w/ shared experts (deepseek-moe)
    mla_moe  -- MLA attention + MoE + MTP (deepseek-v3)
    ssm      -- Mamba2 / SSD, attention-free
    hybrid   -- Mamba2 backbone + periodic shared attention (zamba2)
    encdec   -- encoder-decoder (whisper; conv frontend stubbed)
    vlm      -- dense backbone + patch-embedding stub frontend (phi-3-vision)
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25  # the sharded (GShard) path only
    first_dense_layers: int = 0  # deepseek: leading dense MLP layers
    # router: "softmax" or "sigmoid" scores over all experts; with
    # router_bias a per-expert correction bias (not trained by the
    # gradient) is added to the scores for choosing the top-k only
    router_score: str = "softmax"
    router_bias: bool = False
    routed_scaling: float = 1.0     # the renormalised weights times this
    balance_alpha: float = 0.0      # sequence-wise balance loss weight
    bias_rate: float = 0.0          # bias step after each train step
    # expert parallelism: this chip holds experts
    # [ep_rank * E / ep_size, (ep_rank + 1) * E / ep_size) of num_experts
    ep_size: int = 1
    ep_rank: int = 0

    # --- MLA (deepseek-v3) ---
    q_lora_rank: int = 0  # 0: queries by one direct wq (no q-LoRA)
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0
    mtp_depth: int = 0  # multi-token-prediction blocks

    # --- SSM (mamba2 / zamba2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_ngroups: int = 1
    ssm_chunk: int = 256
    conv_width: int = 4
    attn_every: int = 0  # hybrid: shared attention block every N layers

    # --- enc-dec (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 1500  # whisper 30s audio -> 1500 frames (stub frontend)

    # --- vlm (phi-3-vision) ---
    num_image_tokens: int = 0

    # --- misc ---
    qk_norm: bool = False
    activation: str = "silu"  # silu | gelu | relu2
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    # remat policy: "nothing" | "dots" | "none"
    remat: str = "nothing"

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # ---- derived ----
    @property
    def d_inner(self) -> int:  # SSM inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def experts_held(self) -> int:
        """Experts of each MoE layer this chip holds."""
        return self.num_experts // self.ep_size

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """True if long_500k is runnable (SSM/hybrid: O(1)-state decode)."""
        return self.family in ("ssm", "hybrid")

    def supports_shape(self, shape: ShapeSpec) -> bool:
        if shape.name == "long_500k" and not self.sub_quadratic:
            return False  # pure full-attention archs skip long-context decode
        return True

    def smoke(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        kw = dict(
            num_layers=2,
            d_model=64,
            num_heads=4,
            num_kv_heads=max(1, min(self.num_kv_heads, 2)) if self.num_kv_heads else 0,
            head_dim=16,
            d_ff=128,
            vocab_size=256,
        )
        if self.num_experts:
            kw.update(num_experts=4 * self.ep_size, top_k=2, d_ff_expert=32,
                      num_shared_experts=min(self.num_shared_experts, 1),
                      first_dense_layers=min(self.first_dense_layers, 1))
        if self.q_lora_rank or self.kv_lora_rank:
            kw.update(q_lora_rank=32 if self.q_lora_rank else 0,
                      kv_lora_rank=16, qk_rope_dim=8,
                      qk_nope_dim=8, v_head_dim=16, head_dim=16)
        if self.mtp_depth:
            kw.update(mtp_depth=1)
        if self.ssm_state:
            kw.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=8)
        if self.attn_every:
            kw.update(attn_every=2, num_layers=4)
        if self.encoder_layers:
            kw.update(encoder_layers=2, encoder_seq=16)
        if self.num_image_tokens:
            kw.update(num_image_tokens=4)
        return replace(self, name=self.name + "-smoke", **kw)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if not _REGISTRY:
        _load_all()
    return _REGISTRY[name]


def list_configs() -> list[str]:
    if not _REGISTRY:
        _load_all()
    return sorted(_REGISTRY)


def _load_all() -> None:
    # import side-effect registers each arch
    from repro.configs import (  # noqa: F401
        deepseek_moe_16b, deepseek_v3_671b, qwen3_4b, nemotron_4_340b,
        granite_3_2b, llama3_2_3b, whisper_small, phi_3_vision_4_2b,
        mamba2_780m, zamba2_7b, moonlight_16b_a3b,
    )


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of one (arch, shape) cell.

    train/prefill : tokens + labels (+ frontend stubs)
    decode        : one new token per sequence + the KV/SSM caches at seq_len
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = jnp.dtype(cfg.dtype)

    def sds(shp, dt=f):
        return jax.ShapeDtypeStruct(shp, dt)

    if shape.kind in ("train", "prefill"):
        # VLM: image patches occupy the first num_image_tokens positions of the
        # assigned seq_len, so total sequence length stays exactly S.
        S_txt = S - cfg.num_image_tokens if cfg.family == "vlm" else S
        specs = {"tokens": sds((B, S_txt), i32)}
        if shape.kind == "train":
            specs["labels"] = sds((B, S), i32)
        if cfg.family == "vlm":
            # modality frontend is a STUB: precomputed patch embeddings
            specs["patch_embeds"] = sds((B, cfg.num_image_tokens, cfg.d_model))
        if cfg.family == "encdec":
            # conv frontend stub: precomputed mel-frame embeddings
            specs["frame_embeds"] = sds((B, cfg.encoder_seq, cfg.d_model))
        return specs

    # ---- decode: one new token against caches of length S ----
    specs = {"tokens": sds((B, 1), i32), "cache_index": sds((), i32)}
    specs.update(cache_specs(cfg, B, S, f))
    if cfg.family == "encdec":
        specs["encoder_out"] = sds((B, cfg.encoder_seq, cfg.d_model))
    return specs


def cache_specs(cfg: ModelConfig, B: int, S: int, dt) -> dict:
    """Decode-cache ShapeDtypeStructs (stacked over layers)."""
    def sds(shp, d=dt):
        return jax.ShapeDtypeStruct(shp, d)

    L = cfg.num_layers
    specs: dict = {}
    if cfg.family in ("dense", "moe", "mla_moe", "vlm", "encdec", "hybrid"):
        if cfg.family == "mla_moe":
            # MLA compressed cache: latent c_kv + decoupled rope key
            specs["kv_cache"] = sds((L, B, S, cfg.kv_lora_rank + cfg.qk_rope_dim))
        elif cfg.family == "hybrid":
            n_attn = len([i for i in range(L) if i % cfg.attn_every == 0])
            specs["k_cache"] = sds((n_attn, B, S, cfg.num_kv_heads, cfg.head_dim))
            specs["v_cache"] = sds((n_attn, B, S, cfg.num_kv_heads, cfg.head_dim))
        else:
            nl = L if cfg.family != "encdec" else cfg.num_layers
            specs["k_cache"] = sds((nl, B, S, cfg.num_kv_heads, cfg.head_dim))
            specs["v_cache"] = sds((nl, B, S, cfg.num_kv_heads, cfg.head_dim))
    if cfg.family in ("ssm", "hybrid"):
        specs["ssm_state"] = sds((L, B, cfg.ssm_nheads, cfg.ssm_head_dim,
                                  cfg.ssm_state), jnp.float32)
        specs["conv_state"] = sds(
            (L, B, cfg.conv_width - 1,
             cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state))
    return specs


def make_inputs(cfg: ModelConfig, shape: ShapeSpec, seed: int = 0) -> dict:
    """Materialized inputs for smoke tests / examples (small shapes only)."""
    specs = input_specs(cfg, shape)
    rng = np.random.default_rng(seed)
    out = {}
    for k, s in specs.items():
        if np.issubdtype(s.dtype, np.integer):
            if k == "cache_index":
                out[k] = jnp.asarray(min(shape.seq_len - 1, 7), s.dtype)
            else:
                out[k] = jnp.asarray(
                    rng.integers(0, cfg.vocab_size, s.shape), s.dtype)
        else:
            out[k] = jnp.asarray(rng.standard_normal(s.shape) * 0.02, s.dtype)
    return out
