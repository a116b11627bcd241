"""Finds a cell's parts by name: the benchmark is data, the harness general.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
each metric. Every part lives in a file of its own, found by that name
under one of the search directories (the benchmark's own first, then any
a caller adds):

* ``configs/<config>.json`` — the deployment: source, cuts, graph family,
  ``ServiceConfig`` fields, chips, kernels;
* ``traffic/<traffic>.json`` — the arrival process;
* ``metrics/<metric>.py`` — a reader with ``read(run) -> float | None``;
* ``kernels/<kernel>.py`` — how to find a kernel in the device trace and
  the bytes and element operations it needs for a unit;
* ``peaks.json`` — the chip's published peaks, keyed by ``device_kind``.

A new cell therefore needs new files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Dict, List, Sequence

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, metric or part named in the spec cannot be found or used."""


def _find(dirs: Sequence[pathlib.Path], rel: str) -> pathlib.Path:
    for d in dirs:
        p = pathlib.Path(d) / rel
        if p.is_file():
            return p
    raise SpecError(f"{rel} not found under {[str(d) for d in dirs]}")


def load_json(dirs, rel: str) -> dict:
    return json.loads(_find(dirs, rel).read_text())


def load_module(dirs, rel: str, name: str):
    path = _find(dirs, rel)
    spec = importlib.util.spec_from_file_location(
        f"bench_part_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every part it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    #: {metric name: (entry in BENCHMARK.json, reader function)}
    end_to_end: Dict[str, tuple]
    per_layer: Dict[str, tuple]
    kernels: Dict[str, object]
    dirs: List[pathlib.Path]

    def metrics(self, trace: bool) -> Dict[str, tuple]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(bench: dict, name: str,
              extra_dirs: Sequence[pathlib.Path] = ()) -> Cell:
    """Resolve workload ``name`` of the parsed ``bench`` spec."""
    dirs = [BENCH_DIR, *map(pathlib.Path, extra_dirs)]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    config = load_json(dirs, f"configs/{w['config']}.json")
    traffic = load_json(dirs, f"traffic/{w['traffic']}.json")

    def readers(entries) -> Dict[str, tuple]:
        out = {}
        for m in entries:
            if _applies(m, name):
                mod = load_module(dirs, f"metrics/{m['name']}.py", m["name"])
                out[m["name"]] = (m, mod.read)
        return out

    kernels = {k: load_module(dirs, f"kernels/{k}.py", k)
               for k in config.get("kernels", ())}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=readers(bench["end_to_end"]),
                per_layer=readers(bench["per_layer"]),
                kernels=kernels, dirs=dirs)


def load_bench(root: pathlib.Path = ROOT) -> dict:
    path = pathlib.Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def peaks_for(device_kind: str, dirs=(BENCH_DIR,)) -> dict:
    """The published peaks of ``device_kind``; an unknown chip is an error."""
    table = load_json(dirs, "peaks.json")
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json; have {sorted(table)}")
    return table[device_kind]

