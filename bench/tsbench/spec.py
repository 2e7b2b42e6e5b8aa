"""What a cell is made of, found by name.

``BENCHMARK.json`` at the root names the cells, their configuration and
traffic mix, and the metrics.  Everything else is a file of its own under
``<root>/bench``, found by the name that ``BENCHMARK.json`` or the
configuration gives it, so a new cell, mix, metric, generator, reference
or work count is a new file and no edit:

- ``configs/<config>.json``  (the file that ``BENCHMARK.json`` names)
- ``traffic/<traffic>.json``
- ``metrics/<metric>.py``    a ``read(run)`` returning a number or None
- ``work/<kernel>.py``       operations and bytes a roofline divides by
- ``data/<generator>.py``    a ``series(key, n, T, **args)`` on the device
- ``reference/<name>.py``    the plain reference of a configuration
- ``peaks.json``             peaks per ``device_kind``
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass


class SpecError(Exception):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # the metric entries that the cell reports
    per_layer: list


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from None


def load_cell(root: str, name: str) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in confs:
        raise SpecError(f"workload {name} names unknown config "
                        f"{w['config']!r}")
    config = load_json(os.path.join(root, confs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench.get("end_to_end", [])
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench.get("per_layer", [])
                 if name in m.get("workloads", [name])]
    return Cell(name=name, chips=int(w.get("chips", 1)), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def plugin(root: str, kind: str, name: str):
    """Import ``<root>/bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"tsbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(root: str, device_kind: str) -> dict:
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json (have "
                        f"{sorted(k for k in table if k != 'source')})")
    return table[device_kind]
