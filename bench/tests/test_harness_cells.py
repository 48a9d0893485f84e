"""Every cell of BENCHMARK.json resolves by name: configuration file,
entry kind, mix and generator, limits, and a reader for every metric."""
import json
import os

import pytest

from bench.harness import core

BENCH = json.load(open(os.path.join(core.ROOT, "BENCHMARK.json")))
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_resolves(name):
    c = core.cell(name, BENCH)
    assert hasattr(core.entry_module(c["config"]), "Entry")
    assert callable(core.traffic_module(c["mix"]).drive)
    e2e = [m["name"] for m in c["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2 and c["per_layer"]
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(core.metric_reader(m["name"]))
    assert c["limits"]
    for lim in c["limits"].values():
        assert lim["lower"] < lim["limit"] < lim["upper"]
        assert lim["control"]


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_exists(name):
    assert callable(core.metric_reader(name))


def test_paths_hold_the_command():
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert os.path.isfile(os.path.join(core.ROOT, c["file"]))
