"""The family seam: the dense family gives the very counts, weights and
readings that the benchmark gave before it had families, a family module
that lacks a name is refused, and a configuration of a family that only
new files define is served end to end."""

import json
import shutil
import sys
import time
import types
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import families, harness, trace_reduce, weights
from conftest import DATA, ROOT


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


# (arguments, value) of each count, as bench/flops.py gave them before the
# family seam existed
COUNTS = {
    "phi3-mini-3.8b": {
        "token_flops": [((100, True), 7484080128.0),
                        ((960, False), 7625244672.0)],
        "prefill_chunk": [((128, 128, True), 937598779392.0),
                          ((0, 77, False), 559258140672.0)],
        "decode_step": [(([100, 400, 700, 960],),
                         (30628380672.0, 8294928384.0)),
                        (([1, 33, 257, 500, 768, 1024],),
                         (45684228096.0, 8461271040.0))],
        "weight_bytes": [((), 7642558464)],
        "kv_bytes_per_token": [((), 393216)],
        "rmsnorm_bytes": [((4,), 61440), ((6,), 86016)],
    },
    "minicpm-2b": {
        "token_flops": [((100, True), 5486252544.0),
                        ((960, False), 5237637120.0)],
        "prefill_chunk": [((128, 128, True), 634768003584.0),
                          ((0, 77, False), 377155215360.0)],
        "decode_step": [(([100, 400, 700, 960],),
                         (22593816576.0, 6246397440.0)),
                        (([1, 33, 257, 500, 768, 1024],),
                         (33648528384.0, 6402332160.0))],
        "weight_bytes": [((), 5450135040)],
        "kv_bytes_per_token": [((), 368640)],
        "rmsnorm_bytes": [((4,), 46080), ((6,), 64512)],
    },
}


@pytest.mark.parametrize("config", sorted(COUNTS))
def test_dense_counts_are_unchanged(config):
    conf = _conf(config)
    fam = families.of(conf)
    assert fam.__name__ == "bench.families.dense"
    dims = fam.Dims.from_config(conf)
    assert set(COUNTS[config]) == set(families.COUNTS)
    for name, cases in COUNTS[config].items():
        for args, want in cases:
            assert getattr(fam, name)(dims, *args) == want, (name, args)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_dense_weights_are_bit_identical(seed):
    conf = json.loads((DATA / "configs" / "tiny.json").read_text())
    fam = families.of(conf)
    dims = fam.Dims.from_config(conf)
    got = fam.program_params(dims, families.root_key(seed))
    want = weights.program_params(weights.Dims.from_config(conf),
                                  weights.root_key(seed))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = np.asarray(a), np.asarray(b)
        assert a.tobytes() == b.tobytes()


def test_family_missing_a_name_is_refused(monkeypatch):
    from bench.families import dense

    mod = types.ModuleType("bench.families.lacks_reference")
    for name in ("Dims", "program_config", "program_params"):
        setattr(mod, name, getattr(dense, name))
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    with pytest.raises(harness.BenchError, match="served_gaps"):
        families.of({"family": "lacks_reference"})
    with pytest.raises(harness.BenchError, match="no family module"):
        families.of({"family": "no_such_family"})


def _synthetic_ctx(family, dims):
    """Three traced ticks: two decode-only (with RMSNorm calls), one with
    two prefill chunks."""
    tick = harness.Tick
    return SimpleNamespace(
        family=family, dims=dims, config={"engine": {"batch": 4}},
        peaks=trace_reduce.peaks_for("TPU v5 lite"), obs=None,
        trace=SimpleNamespace(
            window_s=0.25, tick_busy_s=[0.0201, 0.0203, 0.05],
            tick_ops=[{"jit_p.decode/rmsnorm.15": [1.0e-5, 1.1e-5],
                       "jit_p.decode/fusion.1": [0.01]},
                      {"jit_p.decode/rmsnorm.3": [1.2e-5]},
                      {"jit_p.prefill_chunk/rmsnorm.1": [9e-5]}]),
        traced_ticks=[tick(0.0, 0.0201, [], [100, 400, 700, 960]),
                      tick(0.0201, 0.0404, [], [101, 401, 701, 961]),
                      tick(0.0404, 0.0904, [(0, 128, False), (128, 64, True)],
                           [102])])


# readings of the count readers on _synthetic_ctx at phi3 widths, as they
# were read before the family seam existed
READINGS = {"step_mfu": 2.980294643524873,
            "decode_hbm_roofline": 50.14395009610852,
            "rmsnorm_roofline": 0.6819846819846819}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_count_readers_read_through_the_family(metric):
    conf = _conf("phi3-mini-3.8b")
    fam = families.of(conf)
    ctx = _synthetic_ctx(fam, fam.Dims.from_config(conf))
    read = harness.metric_reader(metric)
    assert read(ctx) == READINGS[metric]
    # a family without the counts: the reader reads nothing
    bare = types.ModuleType("bench.families.bare")
    for name in families.REQUIRED:
        setattr(bare, name, getattr(fam, name))
    ctx.family = bare
    assert read(ctx) is None


def _recording_family(calls: list):
    """A family that is the dense one, recording each call by name."""
    from bench.families import dense

    mod = types.ModuleType("bench.families.recorded")

    def wrap(name):
        fn = getattr(dense, name)

        def recorded(*args, **kwargs):
            calls.append(name)
            return fn(*args, **kwargs)
        return recorded

    class Dims(dense.Dims):
        @classmethod
        def from_config(cls, conf):
            calls.append("Dims")
            return dense.Dims.from_config(conf)

    mod.Dims = Dims
    for name in families.REQUIRED[1:] + families.COUNTS:
        setattr(mod, name, wrap(name))
    return mod


def test_new_family_is_served_from_new_files_only(tmp_path, monkeypatch):
    calls = []
    mod = _recording_family(calls)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    data = tmp_path / "data"
    shutil.copytree(DATA, data)
    conf_path = data / "configs" / "tiny.json"
    conf = json.loads(conf_path.read_text())
    conf["family"] = "recorded"
    conf_path.write_text(json.dumps(conf))

    res = harness.run(harness.RunArgs("tiny-closed", 2**31 + 29, 1.5, False),
                      t_start=time.monotonic(), require_chip=False,
                      root=data, data=data)
    assert res["correct"], res["checks"]
    assert set(families.REQUIRED) <= set(calls)

    # the count readers reach the family's counts through their context
    ctx = _synthetic_ctx(mod, mod.Dims.from_config(conf))
    for metric in READINGS:
        assert harness.metric_reader(metric)(ctx) is not None
    assert {"token_flops", "prefill_chunk", "decode_step",
            "rmsnorm_bytes"} <= set(calls)
