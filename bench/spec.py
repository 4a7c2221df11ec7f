"""Finds a cell's pieces by the names `BENCHMARK.json` gives them.

A cell (`workloads` entry) names a configuration and a traffic mix:

  bench/configs/<config>.json   the model as it is run: the published
                                config.json keys, plus a `bench` group
                                naming its family and nonlinear mode
  bench/models/<family>.py      that family's mapping onto the program,
                                its seeded weights and its plain float32
                                reference
  bench/traffic/<traffic>.json  one traffic mix: loop, lengths, rate or
                                clients, the engine settings it is
                                served with, and the correctness sample
  bench/metrics/<metric>.py     one per-layer metric's reader

Adding any of these is adding a file and an entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SpecError(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _load_json(path: str) -> dict:
    if not os.path.exists(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=_load_json(os.path.join(ROOT, cfg_entry["file"])),
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(BENCH, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _module(path: str, label: str) -> ModuleType:
    if not os.path.exists(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cell: Cell) -> ModuleType:
    fam = cell.config["bench"]["family"]
    return _module(os.path.join(BENCH, "models", fam + ".py"),
                   f"bench_family_{fam}")


def metric_reader(name: str) -> ModuleType:
    return _module(os.path.join(BENCH, "metrics", name + ".py"),
                   f"bench_metric_{name.replace('.', '_')}")


def program_config(cell: Cell, impl: str | None = None):
    """(ModelConfig, SalPimEngine) of the program, as the cell runs it."""
    from repro.core.salpim import SalPimConfig, SalPimEngine
    cfg = family(cell).program_config(cell.config)
    engine = SalPimEngine.create(SalPimConfig(
        nonlinear_mode=cell.config["bench"]["nonlinear_mode"], impl=impl))
    return cfg, engine


def chunk_shapes(cell: Cell) -> list[int]:
    """Every prefill-chunk length the cell's prompts produce."""
    from bench import traffic
    chunk = cell.traffic["engine"]["prefill_chunk_tokens"]
    shapes = set()
    for n in traffic.prompt_lengths(cell.traffic):
        if n >= chunk:
            shapes.add(chunk)
        if n % chunk:
            shapes.add(n % chunk)
    return sorted(shapes)
