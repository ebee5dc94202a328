"""zamba2-7b — hybrid Mamba2 + two alternating shared transformer blocks
[arXiv:2411.15242; huggingface.co/Zyphra/Zamba2-7B-Instruct config.json].

81 Mamba2 layers (2 B/C groups, exact GELU in the shared MLP).  Before 13
of them (published ids 6, 11, 17, …, 77) a *hybrid* layer runs one of 2
shared transformer blocks, A and B in turn by occurrence, on
``concat(x, embedding)``; the block's output passes a per-occurrence linear
into the input of that layer's own Mamba2 block.  The shared blocks' weights
are held once (one "bitstream", 13 tile placements — the paper's operator
reuse case); each occurrence keeps its own KV cache, MLP adapter and linear.

Layer ids 0-11 are ``6 mamba, hybrid-A, 4 mamba, hybrid-B``; ids 12-71 are
five periods of ``5 mamba, hybrid-A, 5 mamba, hybrid-B``; ids 72-80 are
``5 mamba, hybrid-A, 3 mamba``.
"""

from repro.configs.base import ArchConfig, register

M = ("mamba",)
# 12 published layers: two periods of (5 mamba + hybrid), blocks A then B
PERIOD = M * 5 + ("hybrid0",) + M * 5 + ("hybrid1",)


@register("zamba2-7b")
def zamba2_7b() -> ArchConfig:
    return ArchConfig(
        name="zamba2-7b",
        family="hybrid",
        d_model=3584,
        num_heads=32,
        num_kv_heads=32,
        head_dim=224,                    # attention_head_dim
        d_ff=14336,
        vocab_size=32000,
        blocks=(
            (M * 6 + ("hybrid0",) + M * 4 + ("hybrid1",), 1),
            (PERIOD, 5),
            (M * 5 + ("hybrid0",) + M * 3, 1),
        ),
        query_pre_attn_scalar=112.0,     # scores scaled by (224 / 2) ** -0.5
        ssm_state=64,
        ssm_head_dim=64,
        ssm_expand=2,
        ssm_groups=2,
        ssm_conv_width=4,
        ssm_chunk=256,
        num_mem_blocks=2,
        adapter_rank=128,
        act="gelu_exact",
        rope_theta=10_000.0,
        norm_eps=1e-5,
        tie_embeddings=True,
    )
