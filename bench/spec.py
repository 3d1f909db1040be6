"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each workload's configuration and traffic mix; the
files live at fixed places under ``bench/``:

* ``configs/<config>.json`` — the model as it is run (published keys, what
  was reduced, the pinned profile and KV precision);
* ``traffic/<mix>.json`` — the generator kind and its parameters;
* ``cells/<workload>.json`` — the serving sizes of one cell and the limit of
  its correctness comparison;
* ``metrics/<metric>.py`` — one reader per per-layer metric;
* ``arch/<arch>.py`` — one module per model architecture, named by a
  configuration file's ``"arch"``: its sizes, the check of the program's
  configuration, its seeded weights, its plain reference and the work it
  needs (``arch/dense_decoder.py`` says what a module supplies).

Adding a cell, a mix, a configuration, a metric or an architecture adds
files; no file here needs an edit.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load(kind: str, name: str, bench: Path = BENCH) -> dict:
    path = bench / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def workload(name: str, root: Path = ROOT, bench: Path = BENCH) -> dict:
    """Everything one run of workload ``name`` needs, found by name."""
    bj = benchmark(root)
    cells = {w["name"]: w for w in bj["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bj["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bj["per_layer"]
                 if name in m.get("workloads", [name])
                 and any(m["moves"] == e["name"] for e in e2e)]
    config = _load("configs", w["config"], bench)
    return {"workload": w, "config": config,
            "arch": arch(config.get("arch"), bench),
            "traffic": _load("traffic", w["traffic"], bench),
            "cell": _load("cells", name, bench),
            "end_to_end": e2e, "per_layer": per_layer,
            "run_seconds": bj["run_seconds"]}


def _module(kind: str, name: str, bench: Path):
    path = bench / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH):
    """The module ``metrics/<name>.py``: ``UNIT``, ``LAYER``, ``MOVES`` and
    ``read(record) -> float | None``."""
    return _module("metrics", name, bench)


@functools.cache
def arch(name: str, bench: Path = BENCH):
    """The module ``arch/<name>.py`` (loaded once per tree). A name with no
    module there is an error that lists the known ones, never a default."""
    known = sorted(p.stem for p in (bench / "arch").glob("*.py"))
    if name not in known:
        raise KeyError(f"unknown architecture {name!r} in bench/arch/ "
                       f"(known: {known})")
    return _module("arch", name, bench)


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    """Published peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    with open(bench / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
