"""Everything a run reads about its cell, found by name.

`BENCHMARK.json` names the cell's configuration and traffic mix; the
configuration's file, its block family `families/<family>.py` (named by
the file's `"family"` key), `traffic/<traffic>.json`, `limits/<cell>.json`
and `metrics/<metric>.py` are looked up from those names, so a cell, a
mix, a metric or a model's architecture is added by adding files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    family: object          # the configuration's families/<family>.py
    traffic: dict
    limits: dict
    end_to_end: list        # metric entries this cell reports untraced
    per_layer: list         # metric entries this cell reports traced


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = _load(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in moved and _reports(m, name)]
    config = _load(root / conf["file"])
    bench = root / BENCH.name
    return Cell(name=name, chips=w["chips"], config=config,
                family=family(config.get("family"), root),
                traffic=_load(bench / "traffic" / f"{w['traffic']}.json"),
                limits=_load(bench / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


_FAMILIES: dict = {}


def family(name, root: pathlib.Path = ROOT):
    """The module families/<name>.py under `root`'s benchmark directory: a
    block family's program config, reference and counts. Loaded once per
    path, so its jitted functions keep their compiled programs."""
    where = root / BENCH.name / "families"
    path = where / f"{name}.py"
    if not isinstance(name, str) or not path.is_file():
        have = sorted(p.stem for p in where.glob("*.py"))
        raise KeyError(f"no block family {name!r} in {where} (have {have})")
    path = path.resolve()
    if path not in _FAMILIES:
        mod_spec = importlib.util.spec_from_file_location(
            "bench_family_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return _FAMILIES[path]


def peaks(device_kind: str) -> dict:
    table = _load(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def metric_reader(name: str):
    """The `read(ctx)` function of metrics/<name>.py."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
