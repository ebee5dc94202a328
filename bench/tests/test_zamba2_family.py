"""The zamba2 family through the harness: a tiny Zamba2 stage of the
benchmark's configuration is served end to end on the CPU and reads
correct, a decode that leaves the cache (the SSM state with it) as it
was reads not correct, and the SSD kernel's roofline reader reads its
share from a synthetic trace."""

import json
import shutil
import time
import types
from types import SimpleNamespace

import pytest

from bench import faults, families, harness, trace_reduce
from bench.families import zamba2 as z2
from conftest import DATA, ROOT

CELL = "tiny-zamba2"


def _zamba2_conf() -> dict:
    return json.loads((ROOT / "bench" / "configs" /
                       "zamba2-7b.json").read_text())


def _tiny_data(tmp_path):
    """bench/tests/data with a tiny cut of the zamba2-7b configuration
    (two periods of mamba, mamba, hybrid; blocks A, B) and a closed cell."""
    data = tmp_path / "data"
    shutil.copytree(DATA, data)
    types_ = ["mamba", "mamba", "hybrid"] * 2
    conf = _zamba2_conf()
    conf.update(
        hidden_size=64, attention_hidden_size=128, n_mamba_heads=8,
        mamba_headdim=16, mamba_d_state=16, num_attention_heads=4,
        num_key_value_heads=4, num_query_groups=4, attention_head_dim=32,
        kv_channels=16, intermediate_size=128, ffn_hidden_size=128,
        adapter_rank=8, vocab_size=512, num_hidden_layers=len(types_),
        layers_block_type=types_, hybrid_layer_ids=[2, 5],
        max_position_embeddings=128,
        stage={"first_layer": 0, "first_occurrence": 0},
        engine={"batch": 4, "max_len": 128, "chunk": 16},
        check={"logit_gap_limit": 0.15})     # as the tiny dense config
    (data / "configs" / "tiny-zamba2.json").write_text(json.dumps(conf))
    bm = json.loads((data / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": CELL, "config": "tiny-zamba2",
                            "traffic": "tiny-closed", "chips": 1,
                            "why": "tests"})
    for m in bm["end_to_end"]:
        if m["name"] == "out_tokens_per_s":
            m["workloads"].append(CELL)
    (data / "BENCHMARK.json").write_text(json.dumps(bm))
    return data


@pytest.mark.parametrize("fault", [None, "state_unchanged"])
def test_tiny_zamba2_is_served_and_checked(tmp_path, fault):
    data = _tiny_data(tmp_path)
    res = harness.run(harness.RunArgs(CELL, 2**31 + 16, 1.5, False),
                      t_start=time.monotonic(), require_chip=False,
                      root=data, data=data,
                      fault=faults.FAULTS[fault] if fault else None)
    assert res["correct"] == (fault is None), res["checks"]
    assert res["metrics"]["out_tokens_per_s"]["value"] > 0


def _ctx(family, dims, ops_per_tick):
    """Two traced ticks: one decode-only, one that ran a 128-token chunk
    and a 64-token chunk."""
    tick = harness.Tick
    return SimpleNamespace(
        family=family, dims=dims, config={"engine": {"batch": 16}},
        peaks=trace_reduce.peaks_for("TPU v5 lite"), obs=None,
        trace=SimpleNamespace(window_s=0.1, tick_busy_s=[0.03, 0.07],
                              tick_ops=ops_per_tick),
        traced_ticks=[tick(0.0, 0.03, [], [100] * 16),
                      tick(0.03, 0.1, [(0, 128, False), (128, 64, True)],
                           [101] * 15)])


def test_ssd_roofline_reads_the_kernel_in_prefill_ticks():
    conf = _zamba2_conf()
    dims = z2.Dims.from_config(conf)
    read = harness.metric_reader("ssd_roofline.zamba2")
    calls = 2 * dims.layers                  # two chunks, every layer
    dur = 40e-6
    ops = [{"jit_decode/fusion.1": [0.02]},
           {"jit_prefill_chunk/ssd_chunk.7": [dur] * calls,
            "jit_prefill_chunk/fusion.2": [0.01]}]
    need = calls * (z2.ssd_bytes(dims, 128) + z2.ssd_bytes(dims, 64)) / 2
    want = 100.0 * need / 819e9 / (calls * dur)
    ctx = _ctx(families.of(conf), dims, ops)
    assert read(ctx) == pytest.approx(want)
    assert 0 < read(ctx) < 100
    # no kernel in the trace, or a family without the count: nothing
    ctx_none = _ctx(families.of(conf), dims,
                    [ops[0], {"jit_prefill_chunk/fusion.2": [0.01]}])
    assert read(ctx_none) is None
    bare = types.ModuleType("bench.families.bare")
    ctx.family = bare
    assert read(ctx) is None


def test_ssd_bytes_counts_the_kernels_operands():
    """x (bf16), the log-decay (f32), B and C per group (bf16) in; y and
    the chunk's state (f32) out, at the published widths."""
    dims = z2.Dims.from_config(_zamba2_conf())
    length, h, p, n = 128, 112, 64, 64
    want = length * (h * p * 2 + h * 4 + 2 * 2 * n * 2) \
        + length * h * p * 4 + h * n * p * 4
    assert z2.ssd_bytes(dims, length) == want
