"""Import-side-effect registration of all assigned architectures + the
paper's own VMUL&Reduce workload config, and the smoke-test reduction
helper used by per-arch CPU tests.
"""

from __future__ import annotations

import dataclasses

# one module per assigned arch (registration happens at import)
from repro.configs import (  # noqa: F401
    deepseek_v3_671b, gemma2_27b, granite_moe_1b, mamba2_130m,
    minicpm_2b, mistral_large_123b, phi3_mini_3_8b, pixtral_12b,
    seamless_m4t_medium, zamba2_7b)
from repro.configs.base import ArchConfig, get_config, register


# ---------------------------------------------------------------------------
# The paper's own workload (vmul+reduce) as a "config" for the benchmarks
# ---------------------------------------------------------------------------
PAPER_DATA_BYTES = 16 * 1024          # §III: "data size was set to 16 KBytes"
PAPER_VECTOR_LEN = PAPER_DATA_BYTES // 4   # f32 elements per input vector
PAPER_PR_OVERHEAD_MS = 1.250          # §III measured PR download cost


# ---------------------------------------------------------------------------
# Reduced configs for CPU smoke tests — same family, tiny dims
# ---------------------------------------------------------------------------
def _shrink_blocks(blocks, max_rep=2):
    return tuple((unit, min(rep, max_rep)) for unit, rep in blocks)


def smoke_config(name: str) -> ArchConfig:
    """A tiny same-family config: every layer kind of the original appears."""
    cfg = get_config(name)
    heads = min(cfg.num_heads, 4)
    kv = min(cfg.num_kv_heads, heads)
    d_model = 64
    over = dict(
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=16,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=256,
        blocks=_shrink_blocks(cfg.blocks),
        encoder_blocks=_shrink_blocks(cfg.encoder_blocks),
        embed_scale=min(cfg.embed_scale, 8.0),
    )
    if cfg.query_pre_attn_scalar is not None:
        over["query_pre_attn_scalar"] = d_model / heads
    if cfg.num_experts:
        # generous capacity: smoke tests assert exact semantics (prefill ==
        # decode), which only holds drop-free; drop behaviour is covered by
        # the property tests
        over.update(num_experts=4, experts_per_token=2, moe_d_ff=32,
                    capacity_factor=4.0)
    if cfg.kv_lora_rank:
        over.update(q_lora_rank=32, kv_lora_rank=16,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    if cfg.ssm_state:
        over.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=8)
    if cfg.adapter_rank:
        over["adapter_rank"] = 8
    if cfg.frontend_dim:
        over["frontend_dim"] = 32
    return cfg.scaled(**over)
