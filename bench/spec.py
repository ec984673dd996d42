"""The benchmark's description and the files it names.

``BENCHMARK.json`` at the root of the checkout lists configurations,
cells (``workloads``) and metrics.  Each is found by its name:

* a configuration in ``bench/configs/<name>.json`` (the ``file`` its
  ``configs`` entry names);
* a traffic mix in ``bench/traffic/<name>.json``;
* the correctness limits of a configuration in ``bench/limits/<name>.json``;
* a metric's reader in ``bench/metrics/<name>.py``;
* a model family's operation counts in ``bench/counts/<family>.py`` and its
  plain reference in ``bench/reference/<family>.py``.

A later cell adds files and entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str, entry: dict | None = None) -> dict:
    """Everything one cell needs, resolved by name: from BENCHMARK.json,
    or from ``entry`` (a ``workloads`` item) for a cell not listed yet."""
    b = benchmark()
    cells = {w["name"]: w for w in b["workloads"]}
    if entry is None and name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = entry or cells[name]
    metrics = {
        "end_to_end": [m for m in b["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in b["per_layer"]
                      if name in m.get("workloads", [name])],
    }
    return {
        "name": name, "chips": w["chips"], "entry": w,
        "config": load_json(BENCH / "configs" / f"{w['config']}.json"),
        "config_name": w["config"],
        "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": limits(w["config"]),
        "metrics": metrics,
    }


def limits(config: str) -> dict:
    """The correctness limits of ``config``, set from readings on the chip.
    A configuration without them has no cell that can be checked."""
    path = BENCH / "limits" / f"{config}.json"
    if not path.exists():
        raise SystemExit(f"no correctness limits for configuration "
                         f"{config!r} ({path.name} is missing): its cells "
                         f"cannot be checked")
    return load_json(path)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` (a metric reader, a family's counts
    or reference) by file name."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plain(obj):
    """A configuration dataclass as the plain JSON it is written in:
    nested dataclasses as objects, tuples as lists, dtypes by name."""
    if dataclasses.is_dataclass(obj):
        return {f.name: plain(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, (list, tuple)):
        return [plain(x) for x in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    import numpy as np

    return np.dtype(obj).name


def differences(want, got, path="config") -> list:
    """Where the configuration file and the configuration as run differ."""
    if isinstance(want, dict) and isinstance(got, dict):
        out = [f"{path}.{k}: missing from the program's config"
               for k in want if k not in got]
        out += [f"{path}.{k}: not in the file" for k in got if k not in want]
        for k in want:
            if k in got:
                out += differences(want[k], got[k], f"{path}.{k}")
        return out
    return [] if want == got else [f"{path}: file {want!r}, program {got!r}"]
