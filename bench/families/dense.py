"""The dense decoder family: the weights of ``bench/weights.py``, the
reference of ``bench/reference.py``, the counts of ``bench/flops.py``, and
the program's config at the published widths."""

from bench.flops import (decode_step, kv_bytes_per_token,  # noqa: F401
                         prefill_chunk, rmsnorm_bytes, token_flops,
                         weight_bytes)
from bench.reference import served_gaps  # noqa: F401
from bench.weights import Dims, program_params  # noqa: F401


def program_config(conf: dict, dims: Dims):
    """The program's registered config of the arch, run at the benchmark's
    widths and equations: it has to be a dense decoder with the published
    activation, and every number the benchmark states replaces the
    program's own."""
    from bench.harness import BenchError
    from repro.configs import get_config

    cfg = get_config(conf["arch"])
    act = conf["published"]["hidden_act"]
    if {k for u, _ in cfg.blocks for k in u} != {"dense"} or cfg.act != act:
        raise BenchError(f"program config {cfg.name} is not a dense "
                         f"decoder with {act}")
    return cfg.scaled(
        d_model=dims.d_model, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab, blocks=((("dense",), dims.layers),),
        tie_embeddings=dims.tied, embed_scale=dims.embed_scale,
        residual_scale=dims.residual_scale, norm_eps=dims.norm_eps,
        rope_theta=dims.rope_theta, dtype=dims.dtype)
