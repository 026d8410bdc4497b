"""Find every part of a cell by name: ``BENCHMARK.json`` names the cell, its
configuration and traffic mix; each lives in a file of its own.

    bench/configs/<config>.json        sizes, serving knobs, driver, reference
    bench/traffic/<mix>.json           parameters of the one generator
    bench/checks/<cell>.json           limits of the comparison with the reference
    bench/drivers/<driver>.py          how to serve the configuration
    bench/references/<reference>.py    the plain float32 forward
    bench/metrics/<metric>.py          one reader per metric
    bench/counts/<kernel>.py           FLOPs and bytes of one kernel call
    bench/peaks.json                   peaks by device_kind
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(path: Path, name: str):
    """Import a file by path (metric files carry dots in their names)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    bdir = root / "bench"
    config = json.loads((bdir / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((bdir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bdir / "checks" / f"{name}.json").read_text())
    return Cell(name, int(w["chips"]), config, mix, limits,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def driver(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "drivers" / f"{name}.py",
                       f"bench_driver_{name}")


def reference(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "references" / f"{name}.py",
                       f"bench_reference_{name}")


def reader(metric: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_"))


def counts(kernel: str, root: Path = ROOT):
    return load_module(root / "bench" / "counts" / f"{kernel}.py",
                       f"bench_counts_{kernel}")


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in bench/peaks.json"
                       f" (has {sorted(table)})")
    return table[device_kind]
