"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A workload names a configuration (the ``file`` of its entry in ``configs``,
with its plain reference, weight layout and program fields in
``bench/references/<config>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``); each metric is a reader in
``bench/metrics/<metric>.py`` with a ``compute(record)`` function (see
``metric_reader`` for metrics split by cells). Everything is found under one
root, the checkout's by default. Adding a configuration, a cell or a metric
adds files and entries, and edits none.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple
    config_name: str  # the configuration's name in BENCHMARK.json
    root: Path  # where BENCHMARK.json and the files it names are


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name,
        config=config,
        mix=mix,
        chips=int(w["chips"]),
        end_to_end=tuple(m for m in bench["end_to_end"] if applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if applies(m, name)),
        config_name=w["config"],
        root=root,
    )


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The Python file at ``path`` as a module, loaded once per process (so
    its jitted functions keep their compiled programs across runs)."""
    name = f"bench_{path.parent.name}_{path.stem}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cell: Cell):
    """The configuration's module ``bench/references/<config>.py``: its
    ``layout(cfg)`` of the weights (``bench/weights.py``),
    ``program_sizes(cfg)`` and ``program_fields(cfg)`` of the program's
    registry entry, the plain forward ``hidden(cfg, quant, params, tokens)``
    with the output ``head(params, hidden, quant)``, and ``CONTROL``, the
    ``quant`` of its lower-precision control."""
    path = cell.root / "bench" / "references" / f"{cell.config_name}.py"
    if not path.exists():
        raise SystemExit(f"configuration {cell.config_name!r} has no reference module {path}")
    return load_module(path)


def metric_reader(name: str, root: Path = ROOT):
    """``compute(record)`` of ``bench/metrics/<name>.py``, or, for a metric
    split by cells (``<base>.<part>``) with no file of its own, of
    ``bench/metrics/<base>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = path.with_name(name.rsplit(".", 1)[0] + ".py")
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} under {root / 'bench' / 'metrics'}")
    return load_module(path).compute


def peaks(kind: str, root: Path = ROOT) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json ({sorted(table)})")
    return table[kind]
