"""Find a cell's configuration, traffic mix and per-layer metric readers
by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own:

  bench/configs/<config>.json        sizes, build arguments, reference, limits
  bench/reference/<reference>.py     the plain reference the configuration
                                     names: its forward pass, the weight
                                     layout it reads and its op counts
  bench/traffic/<traffic>.json       parameters for the one traffic generator
  bench/layer_metrics/<metric>.py    ``read(ctx) -> float | None``; a metric
                                     split by cell, ``<metric>.<cell>``, may
                                     share ``<metric>.py``

so a later change adds a cell by adding files and entries, never by
editing one that exists.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    root: str
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: list      # BENCHMARK.json metric entries reported by this cell
    per_layer: list


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration and traffic
    files loaded. Raises KeyError for an unknown name."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(root=root, name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def reader_path(name: str, root: str = ROOT) -> str:
    """The reader file of per-layer metric ``name``: ``<name>.py``, or for
    a name split by cell (``tick_ms.api``) the reader of the quantity
    before the first dot (``tick_ms.py``), so one file serves every cell."""
    d = os.path.join(root, "bench", "layer_metrics")
    own = os.path.join(d, f"{name}.py")
    return own if os.path.isfile(own) else os.path.join(d, f"{name.split('.')[0]}.py")


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    path = reader_path(name, root)
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(name: str, root: str = ROOT):
    """The plain reference module ``bench/reference/<name>.py`` of the
    checkout at ``root``: ``weight_spec(m)``, ``decode_flops(m, kv_len)``,
    ``prefill_flops(m, start, n)``, and for the check ``final_hidden``
    and ``head_weights``. Loaded once per file and process, so its jitted
    functions compile once."""
    path = os.path.abspath(os.path.join(root, "bench", "reference", f"{name}.py"))
    key = "bench_reference_" + hashlib.sha1(path.encode()).hexdigest()[:12]
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]
