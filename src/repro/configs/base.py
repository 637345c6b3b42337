"""Configuration system for the repro framework.

A single `ModelConfig` dataclass describes every architecture in the pool
(dense / MoE / SSM / hybrid / VLM / audio / LCSM).  Architectures are
registered by id in `REGISTRY` and retrieved with `get_config(arch)`.

Input shapes are registered in `SHAPES`; each (arch x shape) pair defines a
dry-run cell.  `input_specs(cfg, shape)` (in launch/specs.py) materializes
jax.ShapeDtypeStruct stand-ins for every model input.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Layer block kinds
# ---------------------------------------------------------------------------
# Block kinds understood by models/transformer.py. A model is a repeating
# `pattern` of blocks, scanned (n_layers // len(pattern)) times.
ATTN = "attn"              # global causal GQA attention
LOCAL_ATTN = "local_attn"  # sliding-window causal attention
RGLRU = "rglru"            # RecurrentGemma RG-LRU recurrent block
MAMBA2 = "mamba2"          # Mamba-2 SSD block (attention-free)
HYENA = "hyena"            # multi-head Hyena long-convolution block (LCSM)

MLP_DENSE = "dense"        # gated or plain MLP (per `act`)
MLP_MOE = "moe"            # mixture-of-experts MLP


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    # router jitter / z-loss co-efficients used during training
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD configuration."""
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 256           # SSD chunk length
    n_groups: int = 1
    conv_bias: bool = False    # a bias on the depthwise conv (Granite-4.0-H)


@dataclass(frozen=True)
class RGLRUConfig:
    """RecurrentGemma RG-LRU configuration."""
    d_conv: int = 4
    expand: int = 1            # lru width = expand * d_model (RG uses 1x w/ block width)
    window: int = 2048         # local attention window used by LOCAL_ATTN blocks


@dataclass(frozen=True)
class HyenaConfig:
    """Multi-head Hyena (paper, Sec. 4). heads == d_model -> vanilla Hyena.

    filter_param selects the long-filter parametrization:
      "mlp" — Hyena implicit sine MLP;
      "ssm" — H3-style diagonal SSM (modal form, ssm_state modes): the
              paper's other LCSM family, where distillation reduces to
              model-order reduction (App. E.3).
    """
    n_filter_heads: int = 8        # M: number of tied long filters
    filter_order: int = 64         # width of the implicit filter MLP
    filter_emb: int = 33           # positional-embedding dim fed to filter MLP
    short_conv: int = 3            # explicit short conv width for q,k,v
    sine_freq: float = 4.0         # omega_0 for the siren filter MLP (paper D.1)
    modulate: bool = True          # exponential decay window modulation
    filter_param: str = "mlp"      # mlp | ssm (H3)
    ssm_state: int = 64            # modes of the H3 diagonal-SSM filter
    # distillation deployment
    distill_order: int = 16        # d: SSM state dim after distillation


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio | lcsm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None   # default d_model // n_heads
    act: str = "swiglu"              # swiglu | geglu | gelu
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    rope_theta: float = 10000.0
    m_rope: bool = False             # Qwen2-VL multimodal RoPE (3 sections)
    m_rope_sections: Tuple[int, ...] = (16, 24, 24)
    tie_embeddings: bool = False
    pattern: Tuple[str, ...] = (ATTN,)       # block kinds, tiled to n_layers
    mlp_kind: str = MLP_DENSE
    window: int = 0                  # sliding window for LOCAL_ATTN
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    hyena: Optional[HyenaConfig] = None
    enc_dec: bool = False            # whisper-style encoder-decoder
    n_enc_layers: int = 0
    frontend: str = "none"           # none | audio_stub | vision_stub
    frontend_len: int = 1500         # number of frontend embeddings (stub)
    logit_softcap: float = 0.0       # gemma-style final logit soft-capping
    # Granite-4.0-H: no positions at all (attention without RoPE or a
    # learned table), and four multipliers; every default leaves a model
    # without them unchanged
    nope: bool = False
    embedding_multiplier: float = 1.0    # token embeddings times this
    residual_multiplier: float = 1.0     # each mixer and MLP output times this
    attention_multiplier: Optional[float] = None   # softmax scale; None: 1/sqrt(hd)
    logits_scaling: float = 1.0          # logits divided by this
    norm_eps: float = 1e-6               # every RMS/layer norm, gated norm too
    dtype: str = "bfloat16"
    max_seq: int = 131072

    # ------------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def learned_pos(self) -> bool:
        """A learned absolute position table (rope_theta <= 0, not NoPE)."""
        return self.rope_theta <= 0.0 and not self.nope

    @property
    def blocks(self) -> Tuple[str, ...]:
        """Full per-layer block list (pattern tiled to n_layers)."""
        reps = -(-self.n_layers // len(self.pattern))
        return tuple((self.pattern * reps)[: self.n_layers])

    @property
    def attention_free(self) -> bool:
        return all(b in (MAMBA2, HYENA, RGLRU) for b in self.blocks)

    @property
    def subquadratic(self) -> bool:
        """True if the arch supports O(1)-state decode at 500k context.

        Pure full-attention archs are quadratic and their KV cache is O(L);
        SSM / hybrid(local-attn) / LCSM-with-distillation archs qualify.
        """
        return all(b in (MAMBA2, HYENA, RGLRU, LOCAL_ATTN) for b in self.blocks)

    def n_params(self) -> int:
        """Analytic parameter count (approximate; embeddings included once)."""
        d, f, V = self.d_model, self.d_ff, self.vocab
        hd = self.hd
        total = V * d                      # embedding
        if not self.tie_embeddings:
            total += V * d                 # unembedding
        per_kind: Dict[str, int] = {}
        for b in self.blocks:
            if b in (ATTN, LOCAL_ATTN):
                p = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
                    + (self.n_heads * hd) * d
            elif b == MAMBA2:
                s = self.ssm or SSMConfig()
                di = s.expand * d
                p = d * (2 * di + 2 * s.n_groups * s.d_state) + di * d + di
            elif b == RGLRU:
                r = self.rglru or RGLRUConfig()
                di = r.expand * d
                p = 2 * d * di + di * d + 2 * di
            elif b == HYENA:
                h = self.hyena or HyenaConfig()
                p = 3 * d * d + d * d + h.n_filter_heads * (
                    h.filter_emb * h.filter_order + h.filter_order * h.filter_order
                    + h.filter_order)
            else:
                raise ValueError(b)
            # mlp
            if self.mlp_kind == MLP_MOE:
                assert self.moe is not None
                mlp = self.moe.n_experts * 3 * d * f + d * self.moe.n_experts
            elif self.act in ("swiglu", "geglu"):
                mlp = 3 * d * f
            else:
                mlp = 2 * d * f
            total += p + mlp + 2 * d       # norms
            per_kind[b] = p
        if self.enc_dec:
            # encoder layers: attn + mlp (cross-attn counted in decoder blocks above
            # is omitted from this estimate for simplicity)
            enc = self.n_enc_layers * (4 * d * d + 2 * d * f + 2 * d)
            total += enc
        return total

    def n_active_params(self) -> int:
        """Active (per-token) parameter count — differs for MoE."""
        if self.mlp_kind != MLP_MOE:
            return self.n_params()
        assert self.moe is not None
        d, f = self.d_model, self.d_ff
        dense_total = self.n_params()
        inactive = (self.moe.n_experts - self.moe.top_k) * 3 * d * f * self.n_layers
        return dense_total - inactive

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned to the paper; see system prompt)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def cell_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether a (arch x shape) dry-run cell is well-defined.

    long_500k needs sub-quadratic attention; pure full-attention archs skip it
    (recorded in DESIGN.md). Encoder-only archs would skip decode, but every
    arch in our pool has a decoder.
    """
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "full-attention arch: 500k KV cache is O(L); skipped per assignment"
    return True, ""


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}


def register(fn: Callable[[], ModelConfig]) -> Callable[[], ModelConfig]:
    cfg = fn()
    REGISTRY[cfg.name] = fn
    return fn


def get_config(name: str) -> ModelConfig:
    if name not in REGISTRY:
        # import side-effect registration
        import repro.configs  # noqa
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[name]()


def list_archs() -> List[str]:
    import repro.configs  # noqa
    return sorted(REGISTRY)


# ---------------------------------------------------------------------------
# Smoke-test reduction: same family, tiny dims.
# ---------------------------------------------------------------------------
def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduce a config to a CPU-runnable size preserving its family/topology."""
    kw: Dict[str, object] = dict(
        n_layers=max(2, len(cfg.pattern)),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2),
        head_dim=16,
        d_ff=128,
        vocab=257,
        max_seq=512,
    )
    if cfg.moe is not None:
        kw["moe"] = MoEConfig(n_experts=4, top_k=2)
    if cfg.ssm is not None:
        kw["ssm"] = SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, chunk=32)
    if cfg.rglru is not None:
        kw["rglru"] = RGLRUConfig(d_conv=4, expand=1, window=64)
        kw["window"] = 64
    if cfg.hyena is not None:
        kw["hyena"] = dataclasses.replace(
            cfg.hyena, n_filter_heads=2, filter_order=16, filter_emb=9,
            ssm_state=8, distill_order=8)
    if cfg.enc_dec:
        kw["n_enc_layers"] = 2
    if cfg.frontend != "none":
        kw["frontend_len"] = 16
    if cfg.window:
        kw["window"] = 64
    return cfg.replace(**kw)
