"""BENCHMARK.json resolves: every cell finds its files, its block family
and every metric its reader; the peaks table refuses an unknown device."""
import json
import os

import _paths  # noqa: F401
import pytest
from harness import spec

with open(os.path.join(os.path.dirname(_paths.BENCH), "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
#: what the harness reads from a block family
FAMILY_API = ("program_config", "forward_logits", "step_flops", "step_bytes",
              "kernel_calls")


def _config(name):
    entry, = [c for c in BENCHMARK["configs"] if c["name"] == name]
    with open(os.path.join(os.path.dirname(_paths.BENCH),
                           entry["file"])) as f:
        return json.load(f)


DENSE = [c["name"] for c in BENCHMARK["configs"]
         if _config(c["name"])["family"] == "dense"]


def test_peaks_of_v5e_and_unknown_device():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = spec.load_cell(name)
    moved = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in moved and len(moved) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in moved
        assert callable(spec.metric_reader(m["name"]))
    assert cell.limits["rank_mean"] > 0
    assert cell.family is spec.family(cell.config["family"])
    for name in FAMILY_API:
        assert callable(getattr(cell.family, name))
    a = cell.config["as_run"]
    assert a["layers"] == cell.config["num_hidden_layers"]
    assert a["d_model"] == cell.config["hidden_size"]
    assert a["vocab"] == cell.config["vocab_size"]


@pytest.mark.parametrize("name", DENSE)
def test_dense_config_keeps_published_widths(name):
    """A dense configuration's run keeps its published attention and FFN
    widths."""
    c = _config(name)
    a = c["as_run"]
    assert a["heads"] * a["head_dim"] == a["d_model"]
    assert a["d_ff"] == c["intermediate_size"]
    assert a["kv_heads"] == c["num_key_value_heads"]
