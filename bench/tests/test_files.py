"""A cell whose files are missing or malformed is refused; the committed
benchmark's own cells load."""

import json
import pathlib

import pytest

from bench import harness
from bench.tests import tiny


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


def test_committed_cells_load():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.limits and cell.per_layer


def test_tiny_cell_loads(root):
    assert harness.load_cell("gpt2.offload", root).traffic["os_host_fraction"] == 1.0


@pytest.mark.parametrize("breakage", [
    "no_workload", "no_config_file", "config_not_json", "config_lacks_key",
    "no_traffic_file", "traffic_lacks_key", "no_limits", "limits_not_numbers",
])
def test_broken_files_are_refused(root, breakage):
    cfg = root / "bench" / "configs" / "gpt2.json"
    traffic = root / "bench" / "traffic" / "offload.json"
    limits = root / "bench" / "limits" / "gpt2.offload.json"
    name = "gpt2.offload"
    if breakage == "no_workload":
        name = "gpt2.nowhere"
    elif breakage == "no_config_file":
        cfg.unlink()
    elif breakage == "config_not_json":
        cfg.write_text("{hidden_size: 128")
    elif breakage == "config_lacks_key":
        d = json.loads(cfg.read_text())
        del d["hidden_size"]
        cfg.write_text(json.dumps(d))
    elif breakage == "no_traffic_file":
        traffic.unlink()
    elif breakage == "traffic_lacks_key":
        d = json.loads(traffic.read_text())
        del d["rows"]
        traffic.write_text(json.dumps(d))
    elif breakage == "no_limits":
        limits.unlink()
    elif breakage == "limits_not_numbers":
        limits.write_text(json.dumps({"loss_gap": "small"}))
    with pytest.raises(harness.SpecError):
        harness.load_cell(name, root)
