"""Operations and bytes that the algorithm needs, from shapes alone.

These count what serving a token requires, not what this implementation
does: a decode step reads every matrix once (the output head too), only
the embedding rows of the tokens it embeds where the head is not tied to
the embedding, the keys and values of each live row's filled positions,
and writes the new ones; a prompt token needs logits only at the prompt's
last position.  A later program that reads less
(live positions only, no rewrite of the whole cache) is credited, and no
share computed from these counts can pass 100% of the chip's peak.
"""

from __future__ import annotations

from bench.weights import Dims

WEIGHT_BYTES = 2        # bfloat16 matrices and embedding
NORM_BYTES = 4          # float32 norm scales
KV_BYTES = 2            # bfloat16 cache


def matmul_params_per_layer(d: Dims) -> int:
    hd = d.head_dim
    attn = d.d_model * hd * (2 * d.heads + 2 * d.kv_heads)
    return attn + 3 * d.d_model * d.d_ff


def weight_bytes(d: Dims) -> int:
    """Every weight once: matrices and embedding in bfloat16, norm scales
    in float32 (the program's parameter layout)."""
    return decode_weight_bytes(d, d.vocab if not d.tied else 0)


def decode_weight_bytes(d: Dims, rows: int) -> int:
    """The weights a step needs that embeds ``rows`` tokens: every layer
    matrix, the norm scales and the output head once; of an untied
    embedding only the ``rows`` rows it gathers (a tied one is read whole,
    as the head)."""
    mats = d.layers * matmul_params_per_layer(d) + d.vocab * d.d_model
    if not d.tied:
        mats += rows * d.d_model
    norms = (2 * d.layers + 1) * d.d_model
    return mats * WEIGHT_BYTES + norms * NORM_BYTES


def kv_bytes_per_token(d: Dims) -> int:
    """Keys and values of one position, all layers."""
    return 2 * d.layers * d.kv_heads * d.head_dim * KV_BYTES


def token_flops(d: Dims, ctx: int, logits: bool) -> float:
    """Multiply-adds x 2 for one token that attends to ``ctx`` positions
    (itself included), with the output head only where its logits are
    needed."""
    f = 2.0 * d.layers * matmul_params_per_layer(d)
    f += 4.0 * d.layers * d.heads * d.head_dim * ctx      # QK^T and PV
    if logits:
        f += 2.0 * d.d_model * d.vocab
    return f


def decode_step(d: Dims, ctxs: list[int]) -> tuple[float, float]:
    """(flops, bytes) of one batched decode step whose live rows attend to
    ``ctxs`` positions each (the new one included)."""
    flops = sum(token_flops(d, c, True) for c in ctxs)
    kv = kv_bytes_per_token(d)
    byts = decode_weight_bytes(d, len(ctxs)) + kv * sum(c - 1 for c in ctxs) \
        + kv * len(ctxs)
    return flops, float(byts)


def prefill_chunk(d: Dims, off: int, n: int, last: bool) -> float:
    """Flops of prefilling prompt positions ``off .. off+n-1``; ``last``:
    the chunk holds the prompt's final token, whose logits are needed."""
    return sum(token_flops(d, off + i + 1, last and i == n - 1)
               for i in range(n))


def rmsnorm_bytes(d: Dims, rows: int, act_bytes: int = 2) -> int:
    """One RMSNorm call over ``rows`` rows of ``d_model``: read the rows and
    the float32 scale, write the rows."""
    return 2 * rows * d.d_model * act_bytes + d.d_model * NORM_BYTES
