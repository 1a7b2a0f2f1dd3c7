"""The harness's data: cells, their files, metric readers, peaks.

Everything that belongs to one configuration, traffic mix, per-layer
metric or entry sits in a file of its own under this directory, found by
the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``    the configuration as it is run
- ``traffic/<traffic>.json``   the traffic mix's parameters
- ``limits/<workload>.json``   the limits of the cell's correctness check
- ``metrics/<metric>.py``      ``read(ctx) -> float | None``
- ``entries/<entry>.py``       the driver a traffic mix names
- ``references/<ref>.py``, ``masters/<algorithm>.py``   plain references
- ``peaks.json``               published peaks by ``device_kind``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (file names may hold ``-`` and ``.``)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_file_" + re.sub(r"\W", "_", str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # metric entries this cell reports, trace 0
    per_layer: list           # metric entries this cell reports, trace 1

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def resolve(bench: dict, workload: str, root: Path = HERE) -> Cell:
    """The cell ``workload`` of ``bench`` with every file it names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    if w["config"] not in {c["name"] for c in bench["configs"]}:
        raise KeyError(f"workload {workload!r} names config "
                       f"{w['config']!r}, which BENCHMARK.json lacks")
    config = load_json(root / "configs" / f"{w['config']}.json")
    traffic = load_json(root / "traffic" / f"{w['traffic']}.json")
    limits = load_json(root / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    for m in e2e + per_layer:
        if not (root / "metrics" / f"{m['name']}.py").is_file():
            raise FileNotFoundError(f"metric {m['name']!r} has no reader "
                                    f"metrics/{m['name']}.py")
    return Cell(w, config, traffic, limits, e2e, per_layer)


def read_metrics(entries: list, ctx: dict, root: Path = HERE) -> dict:
    """``{name: {"value", "unit"}}`` for every reader that found
    something to read; a reader that returns None is left out."""
    out = {}
    for m in entries:
        value = load_module(root / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peaks_for(kind: str, root: Path = HERE) -> dict:
    table = load_json(root / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device_kind {kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[kind]
