"""Config schema + shape registry + arch registry.

Every assigned architecture lives in its own ``configs/<id>.py`` defining
``CONFIG`` (exact published figures) and ``SMOKE`` (reduced same-family
variant for CPU tests). This module holds the shared dataclass, the
assigned input-shape set, and the (arch x shape) cell enumeration with the
skip rules from DESIGN.md §Arch-applicability.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

import numpy as np

__all__ = ["ArchConfig", "ShapeSpec", "SHAPES", "ARCH_IDS", "get_config",
           "smoke_config", "list_archs", "cells"]


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One architecture. Exact figures from the assignment; padding derived.

    ``tp`` is the tensor-parallel degree the padded dims target (16 on the
    production mesh, 1 for smoke configs so tests stay small).
    """

    name: str
    family: str                   # dense | moe | ssm | hybrid | swin | pde | pairformer
    n_layers: int
    d_model: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    vocab: int = 0
    head_dim: int = 0             # 0 -> d_model // n_heads

    # --- attention bias / positional (the paper's technique) ---
    bias_kind: str = "alibi"      # "alibi" | "none"
    bias_mode: str = "flashbias"  # "flashbias" (factored) | "dense" (baseline)
    rope: bool = False
    window: int = 0               # sliding-window size; 0 = full attention

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25

    # --- SSM (Mamba2 SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    conv_bias: bool = False       # bias on the depthwise conv over x, B, C

    # --- layer kinds (interleaved hybrids) ---
    # one mixer kind per layer in published order ("attention" | "mamba");
    # empty: every layer is the family's. Each kind holds its own
    # parameter stack and cache leaves (models/blocks.py).
    layer_kinds: Tuple[str, ...] = ()
    # muP-style multipliers (Granite): embedding output, each residual
    # branch, and the divisor of the logits; 0 / 1 leave a model as is
    embedding_multiplier: float = 0.0   # 0 -> sqrt(d_model)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float = 0.0   # 0 -> 1 / sqrt(head_dim)
    norm_eps: float = 1e-6

    # --- frontends (audio/vision stubs: precomputed embeddings) ---
    frontend: str = "none"        # "none" | "audio" | "vision"
    frontend_len: int = 0

    # --- paper-model extras ---
    coord_dim: int = 3            # pde: spatial dimension of mesh points
    d_pair: int = 0               # pairformer: pair-representation channels
    bias_rank: int = 0            # svd/neural decomposition rank R

    # --- parallelism / numerics ---
    pad_heads: int = 0            # explicit override of heads_padded
    pad_kv_heads: int = 0         # explicit override of kv_heads_padded
    tp: int = 16
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: str = "dots"           # "none" | "dots" | "full"
    attn_chunk: int = 512         # kv chunk of the XLA flash path
    attn_impl: str = "auto"       # "auto" | "xla" | "pallas" | "pallas_interpret"
    # kept only because bench/families pass cache_layout="kernel"; no code
    # reads it, and any other value is refused in __post_init__
    cache_layout: str = "kernel"
    ssd_chunk: int = 256          # SSD intra-chunk quadratic block
    grad_accum: int = 1           # microbatches per train step (activation fit)
    grad_rs: bool = False         # pin grads to param shardings (forces the
                                  # DP reduction to reduce-scatter, ZeRO-2)

    notes: str = ""

    def __post_init__(self):
        if self.cache_layout != "kernel":
            raise ValueError(
                f"cache_layout={self.cache_layout!r}: the kv-head-major "
                "\"kernel\" layout is the only KV-cache layout")

    # ---- derived (TP padding; zero-padded weights keep math exact) ----
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def attn_scale(self) -> float:
        """Softmax scale of the attention logits."""
        if self.attention_multiplier:
            return self.attention_multiplier
        return 1.0 / float(np.sqrt(self.resolved_head_dim))

    @property
    def embed_scale(self) -> float:
        return self.embedding_multiplier or float(np.sqrt(self.d_model))

    def layers_of(self, kind: str) -> int:
        """Layers of one mixer kind (interleaved models)."""
        return sum(k == kind for k in self.layer_kinds)

    @property
    def kv_groups(self) -> int:
        """Padded q-heads per padded kv head."""
        if self.n_kv_heads == 0:
            return 1
        return self.heads_padded // self.kv_heads_padded

    @property
    def kv_heads_padded(self) -> int:
        if self.pad_kv_heads:
            return self.pad_kv_heads
        if self.n_kv_heads == 0:
            return 0
        if self.n_kv_heads == self.n_heads:     # MHA: pad kv with q
            return self.heads_padded
        return self.n_kv_heads                  # GQA kv stays (replicated)

    @property
    def heads_padded(self) -> int:
        if self.pad_heads:
            return self.pad_heads
        if self.n_heads == 0:
            return 0
        if self.n_kv_heads and self.n_kv_heads != self.n_heads:
            # keep the (kv, group) structure: pad groups so kv*g % tp == 0
            kv = self.pad_kv_heads or self.n_kv_heads
            g = _ceil_to(self.n_heads, kv) // kv
            while (kv * g) % self.tp:
                g += 1
            return kv * g
        return _ceil_to(self.n_heads, self.tp)

    @property
    def vocab_padded(self) -> int:
        return _ceil_to(self.vocab, self.tp) if self.vocab else 0

    @property
    def experts_padded(self) -> int:
        return _ceil_to(self.n_experts, self.tp) if self.n_experts else 0

    @property
    def ssm_heads(self) -> int:
        if not self.ssm_state:
            return 0
        d_inner = self.ssm_expand * self.d_model
        return d_inner // self.ssm_head_dim

    @property
    def ssm_heads_padded(self) -> int:
        return _ceil_to(self.ssm_heads, self.tp) if self.ssm_state else 0

    @property
    def d_inner_padded(self) -> int:
        return self.ssm_heads_padded * self.ssm_head_dim

    def param_count(self) -> int:
        """Approximate *logical* (unpadded) parameter count."""
        d, v, l = self.d_model, self.vocab, self.n_layers
        hd = self.resolved_head_dim
        n = v * d                                     # embedding (+ tied head)
        attn = d * hd * (self.n_heads * 2) + d * hd * (self.n_kv_heads or self.n_heads) * 2
        if self.family == "moe":
            ffn = self.n_experts * 3 * d * self.d_ff + d * self.n_experts
        elif self.family == "ssm":
            d_in = self.ssm_expand * d
            ffn = 0
            attn = d * (2 * d_in + 2 * self.ssm_state + self.ssm_heads) + d_in * d
        else:
            ffn = 3 * d * self.d_ff
        if self.layer_kinds:
            d_in = self.ssm_expand * d
            mamba = (d * (2 * d_in + 2 * self.ssm_state + self.ssm_heads)
                     + d_in * d)
            n_m = self.layers_of("mamba")
            return n + n_m * (mamba + ffn + 2 * d) + (l - n_m) * (
                attn + ffn + 2 * d)
        if self.family == "hybrid":
            d_in = self.ssm_expand * d
            attn += d * (2 * d_in + 2 * self.ssm_state + self.ssm_heads) + d_in * d
        return n + l * (attn + ffn + 2 * d)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str      # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS = [
    "musicgen_medium",
    "command_r_plus_104b",
    "minicpm_2b",
    "stablelm_12b",
    "codeqwen15_7b",
    "phi3_vision_42b",
    "llama4_scout_17b_a16e",
    "granite_moe_3b_a800m",
    "hymba_15b",
    "mamba2_130m",
]

PAPER_IDS = ["gpt2_alibi_15b", "swinv2_b", "pde_solver", "pairformer_lite"]


def _module(arch_id: str):
    return importlib.import_module(f"repro.configs.{arch_id}")


def get_config(arch_id: str) -> ArchConfig:
    return _module(arch_id).CONFIG


def smoke_config(arch_id: str) -> ArchConfig:
    return _module(arch_id).SMOKE


def list_archs(include_paper: bool = False):
    return list(ARCH_IDS) + (list(PAPER_IDS) if include_paper else [])


def cells():
    """All (arch_id, shape_name) dry-run cells, with the documented skips.

    ``long_500k`` needs sub-quadratic attention: it runs only for the SSM
    (mamba2) and hybrid (hymba, sliding-window + constant state) archs —
    pure full-attention archs skip it (DESIGN.md §Arch-applicability).
    """
    out = []
    for a in ARCH_IDS:
        cfg = get_config(a)
        for s in SHAPES.values():
            if s.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
                continue
            out.append((a, s.name))
    return out
