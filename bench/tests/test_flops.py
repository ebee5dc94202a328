"""Operation and byte counts against hand counts for phi3-mini-3.8b."""

import dataclasses
import json

from bench import flops
from bench.weights import Dims
from conftest import ROOT

PHI3 = Dims.from_config(json.loads(
    (ROOT / "bench/configs/phi3-mini-3.8b.json").read_text()))


def test_phi3_weight_bytes():
    # per layer: q,k,v,o 4 x 3072 x 3072 + gate,up,down 3 x 3072 x 8192
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert per_layer == 113_246_208
    mats = 32 * per_layer + 2 * 32064 * 3072          # embed + lm_head
    norms = (2 * 32 + 1) * 3072
    assert flops.weight_bytes(PHI3) == mats * 2 + norms * 4 == 7_642_558_464


def test_phi3_kv_bytes():
    # K and V, 32 layers, 32 heads of 96, bf16
    assert flops.kv_bytes_per_token(PHI3) == 2 * 32 * 32 * 96 * 2 == 393_216


def test_phi3_decode_step_batch4():
    ctxs = [100, 400, 700, 960]
    f, b = flops.decode_step(PHI3, ctxs)
    kv = 393_216
    # the untied embedding: only the 4 rows gathered, not 32064 x 3072
    weights = 7_642_558_464 - 32064 * 3072 * 2 + 4 * 3072 * 2
    assert weights == 7_445_581_824
    assert b == weights + kv * sum(c - 1 for c in ctxs) + 4 * kv
    per_token = 2 * 32 * 113_246_208 + 2 * 3072 * 32064
    attn = sum(4 * 32 * 32 * 96 * c for c in ctxs)
    assert f == 4 * per_token + attn


def test_prefill_needs_logits_once():
    with_head = flops.prefill_chunk(PHI3, 0, 128, True)
    without = flops.prefill_chunk(PHI3, 0, 128, False)
    assert with_head - without == 2 * 3072 * 32064


def test_rmsnorm_bytes():
    assert flops.rmsnorm_bytes(PHI3, 4) == 2 * 4 * 3072 * 2 + 3072 * 4


def test_tied_head_reads_the_whole_embedding():
    tied = dataclasses.replace(PHI3, tied=True)
    # no lm_head; the embedding is the head, read whole whatever the rows
    assert flops.decode_weight_bytes(tied, 4) == \
        flops.decode_weight_bytes(tied, 0) == \
        7_642_558_464 - 32064 * 3072 * 2
