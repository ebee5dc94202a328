"""BENCHMARK.json against the benchmark's own files: every cell finds its
configuration, its mix and its arrival process by name, every per-layer
metric finds its reader, and names and units keep to their characters."""

import importlib
import json
import re

import pytest

from bench import families, harness, traffic
from conftest import ROOT

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_and_units():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BM[kind]]
        assert len(set(names)) == len(names)
        for e in BM[kind]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_cell_resolves(cell):
    c = harness.load_cell(cell)
    fam = families.of(c.config)
    assert fam.Dims.from_config(c.config).vocab > 0
    importlib.import_module(f"bench.arrivals.{c.mix['arrival']}")
    assert c.workload["chips"] == 1
    e2e = {m["name"] for m in c.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.metrics("per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in BM["per_layer"]])
def test_metric_has_reader(metric):
    m = next(m for m in BM["per_layer"] if m["name"] == metric)
    assert callable(harness.metric_reader(metric))
    e2e = {e["name"]: e for e in BM["end_to_end"]}
    assert m["moves"] in e2e
    # the harness reports a per-layer metric in the cells it lists
    assert m["workloads"]
    for cell in m["workloads"]:
        assert cell in e2e[m["moves"]].get("workloads", [cell])


def test_mix_files_name_known_arrivals():
    for path in (ROOT / "bench" / "traffic").glob("*.json"):
        mix = traffic.load_mix(path.stem, ROOT / "bench")
        mod = traffic.arrival_module(mix)
        assert isinstance(mod.CLOSED, bool)
