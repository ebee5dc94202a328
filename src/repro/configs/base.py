"""Architecture configuration schema + registry.

Every assigned architecture is one ``ArchConfig`` in ``configs/<id>.py``.
Heterogeneous layer stacks are expressed as ``blocks``: a list of
``(unit, repeat)`` pairs, where ``unit`` is a tuple of layer kinds scanned
``repeat`` times (e.g. gemma-2's local:global alternation is
``(("local", "global"), 23)``).  This is what lets ``lax.scan`` compile one
layer body per kind instead of 88 copies — compile time and HLO size stay
bounded for the dry-run.

Layer kinds:
  dense        — full attention + dense MLP
  local        — sliding-window attention + dense MLP (gemma2)
  global       — full attention + dense MLP (gemma2 pairing)
  moe          — full attention + MoE FFN
  mla_moe      — MLA attention + MoE FFN (deepseek-v3)
  mla_dense    — MLA attention + dense MLP (deepseek-v3 first layers)
  mamba        — Mamba-2 SSD block (attention-free)
  hybrid<k>    — zamba2's hybrid layer: shared transformer block ``k`` (one
                 of ``num_mem_blocks``, whose weights every occurrence
                 reuses — the paper's "one bitstream, many tiles" case) on
                 ``concat(x, embedding)``, its output through a per-
                 occurrence linear into the input of the layer's own Mamba-2
                 block; ``hybrid0``, ``hybrid1``, … name the block
  enc / dec    — encoder (bidirectional) / decoder (causal + cross-attn)
"""

from __future__ import annotations

import dataclasses
from typing import Callable

_REGISTRY: dict[str, Callable[[], "ArchConfig"]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> "ArchConfig":
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}") from None


def list_archs() -> list[str]:
    return sorted(_REGISTRY)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | audio | vlm
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    blocks: tuple[tuple[tuple[str, ...], int], ...]
    head_dim: int = 0                 # 0 -> d_model // num_heads
    # --- attention options ---
    rope_theta: float = 10_000.0
    sliding_window: int | None = None          # for "local" layers
    attn_softcap: float | None = None          # gemma2
    final_softcap: float | None = None         # gemma2
    query_pre_attn_scalar: float | None = None # gemma2 scaling
    # --- MLA (deepseek-v3) ---
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    router_scoring: str = "softmax"            # softmax | sigmoid (deepseek)
    # --- SSM (mamba2) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 64
    ssm_groups: int = 1                        # B/C groups; heads split evenly
    # --- hybrid (zamba2) ---
    num_mem_blocks: int = 0                    # shared transformer blocks
    adapter_rank: int = 0                      # per-occurrence MLP adapter
    # --- enc-dec ---
    encoder_blocks: tuple[tuple[tuple[str, ...], int], ...] = ()
    # --- misc ---
    act: str = "silu"                          # silu | gelu (tanh) | gelu_exact (erf)
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    embed_scale: float = 1.0                   # gemma: sqrt(d); minicpm: 12
    residual_scale: float = 1.0                # minicpm depth scaling
    post_norms: bool = False                   # gemma2 post-sublayer norms
    mtp_depth: int = 0                         # deepseek multi-token prediction
    frontend: str | None = None                # "audio" | "vision" stub
    frontend_dim: int = 0                      # stub embedding feature size
    dtype: str = "bfloat16"
    # training-step options (hillclimb knobs — overridable per run)
    remat: str = "full"                        # full | none | dots
    scan_layers: bool = True

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def num_layers(self) -> int:
        return sum(len(u) * r for u, r in self.blocks) + \
            sum(len(u) * r for u, r in self.encoder_blocks)

    @property
    def is_encdec(self) -> bool:
        return bool(self.encoder_blocks)

    @property
    def attention_free(self) -> bool:
        kinds = {k for u, _ in self.blocks for k in u}
        return kinds <= {"mamba"}

    @property
    def subquadratic(self) -> bool:
        """True if long-context decode is viable (SSM/hybrid)."""
        kinds = {k for u, _ in self.blocks for k in u}
        return "mamba" in kinds

    @property
    def ssm_layers(self) -> bool:
        """True if any layer carries Mamba-2 state (``mamba``, ``hybrid<k>``)."""
        return any(k == "mamba" or hybrid_block(k) is not None
                   for u, _ in self.blocks for k in u)

    @property
    def hybrid_layers(self) -> bool:
        """True if any layer runs a shared transformer block (zamba2)."""
        return any(hybrid_block(k) is not None
                   for u, _ in self.blocks for k in u)

    def param_count(self) -> int:
        """Analytic parameter count (total, incl. all experts)."""
        return _count_params(self)

    def active_param_count(self) -> int:
        """Params active per token (MoE: only routed-in experts)."""
        return _count_params(self, active_only=True)

    def scaled(self, **overrides) -> "ArchConfig":
        """Reduced config of the same family for CPU smoke tests."""
        return dataclasses.replace(self, **overrides)


def hybrid_block(kind: str) -> int | None:
    """The shared block a ``hybrid<k>`` layer calls, or None for any other
    kind."""
    if kind.startswith("hybrid") and kind[6:].isdigit():
        return int(kind[6:])
    return None


def _ffn_params(cfg: ArchConfig, d_ff: int) -> int:
    return 3 * cfg.d_model * d_ff  # SwiGLU w1/w3/w2


def _attn_params(cfg: ArchConfig, d_in: int | None = None) -> int:
    hd = cfg.resolved_head_dim
    d_in = d_in or cfg.d_model
    if cfg.kv_lora_rank:  # MLA
        q = cfg.d_model * cfg.q_lora_rank + \
            cfg.q_lora_rank * cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        kv = cfg.d_model * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) + \
            cfg.kv_lora_rank * cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        o = cfg.num_heads * cfg.v_head_dim * cfg.d_model
        return q + kv + o
    q = d_in * cfg.num_heads * hd
    kv = 2 * d_in * cfg.num_kv_heads * hd
    o = cfg.num_heads * hd * cfg.d_model
    return q + kv + o


def _mamba_params(cfg: ArchConfig) -> int:
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_head_dim
    bc = 2 * cfg.ssm_groups * cfg.ssm_state
    in_proj = cfg.d_model * (2 * d_inner + bc + nheads)
    conv = (cfg.ssm_conv_width + 1) * (d_inner + bc)      # weights + bias
    out = d_inner * cfg.d_model
    # + A_log, D, dt_bias and the gated norm's scale
    return in_proj + conv + out + 3 * nheads + d_inner


def shared_block_params(cfg: ArchConfig) -> int:
    """One shared transformer block of a hybrid model: attention on
    ``concat(x, embedding)`` (width ``2 d_model``), the MLP, two norms."""
    d = cfg.d_model
    return _attn_params(cfg, 2 * d) + _ffn_params(cfg, cfg.d_ff) + 3 * d


def _layer_params(cfg: ArchConfig, kind: str) -> int:
    norms = 2 * cfg.d_model
    if kind == "mamba":
        return _mamba_params(cfg) + cfg.d_model
    if hybrid_block(kind) is not None:   # own mamba + adapter + linear
        adapter = cfg.adapter_rank * (cfg.d_model + 2 * cfg.d_ff)
        return _mamba_params(cfg) + cfg.d_model + adapter + cfg.d_model ** 2
    if kind in ("dense", "local", "global", "enc"):
        return _attn_params(cfg) + _ffn_params(cfg, cfg.d_ff) + norms
    if kind == "dec":
        return 2 * _attn_params(cfg) + _ffn_params(cfg, cfg.d_ff) + 3 * cfg.d_model
    if kind in ("moe", "mla_moe"):
        att = _attn_params(cfg)
        router = cfg.d_model * cfg.num_experts
        experts = cfg.num_experts * _ffn_params(cfg, cfg.moe_d_ff)
        shared = cfg.num_shared_experts * _ffn_params(cfg, cfg.moe_d_ff)
        return att + router + experts + shared + norms
    if kind == "mla_dense":
        return _attn_params(cfg) + _ffn_params(cfg, cfg.d_ff) + norms
    raise ValueError(f"unknown layer kind {kind!r}")


def _count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    total = cfg.vocab_size * cfg.d_model            # embedding
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * cfg.d_model       # lm head
    total += cfg.d_model                            # final norm
    for unit, rep in (*cfg.blocks, *cfg.encoder_blocks):
        for kind in unit:
            n = _layer_params(cfg, kind)
            if active_only and kind in ("moe", "mla_moe"):
                att = _attn_params(cfg)
                router = cfg.d_model * cfg.num_experts
                act_e = (cfg.experts_per_token + cfg.num_shared_experts) * \
                    _ffn_params(cfg, cfg.moe_d_ff)
                n = att + router + act_e + 2 * cfg.d_model
            total += n * rep
    # shared blocks: held once, whichever layers call them
    total += cfg.num_mem_blocks * shared_block_params(cfg)
    return total
