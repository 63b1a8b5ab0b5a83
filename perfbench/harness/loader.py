"""``BENCHMARK.json`` -> one cell, every name resolved to a file.

A cell names a configuration and a traffic mix; the traffic file names
its runner, the configuration file its family; a per-layer metric is
``layer_metrics/<name>.py``.  A later PR adds files and entries and
edits nothing here: nothing in this module knows a name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = "perfbench"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(Exception):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchmarkError(
            f"{what} {name!r}: a name starts with a letter, digit or _ "
            "and holds at most 64 of A-Za-z0-9_.-")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise BenchmarkError(
            f"{what}: unit {unit!r} must be 1 to 16 of A-Za-z0-9_/%.-")
    return unit


def read_json(path) -> dict:
    path = Path(path)
    if not path.is_file():
        raise BenchmarkError(f"missing file {path}")
    with open(path) as fh:
        return json.load(fh)


_MODULES: dict = {}


def load_module(root: Path, kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module, loaded once a path;
    the file name may hold dots and dashes, so it is loaded by path."""
    check_name(name, kind)
    path = (Path(root) / BENCH_DIR / kind / f"{name}.py").resolve()
    if path in _MODULES:
        return _MODULES[path]
    if not path.is_file():
        raise BenchmarkError(f"{kind} {name!r}: missing file {path}")
    mod_name = "perfbench_%s_%s" % (kind, re.sub(r"[^A-Za-z0-9_]", "_", name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    _MODULES[path] = module
    return module


def twin_of(file: str, name: str):
    """The ``reduce`` of ``layer_metrics/<name>.py`` beside the reader
    ``file``: a per-layer metric has ONE ``moves``, so a quantity read in
    cells that report different end-to-end metrics has a name for each,
    and the second name's file is this one line."""
    return load_module(Path(file).resolve().parents[2], "layer_metrics",
                       name).reduce


def load_benchmark(root: Path = ROOT) -> dict:
    """The parsed file, with every name, unit and cross-reference
    checked (the driver checks them again; a fault should show here
    first, on the CPU)."""
    bench = read_json(Path(root) / "BENCHMARK.json")
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in bench:
            raise BenchmarkError(f"BENCHMARK.json lacks {key!r}")
    configs = {}
    for c in bench["configs"]:
        check_name(c.get("name"), "config")
        if c["name"] in configs:
            raise BenchmarkError(f"config {c['name']!r} appears twice")
        for k in c.get("reduced", []):
            check_name(k, f"config {c['name']} reduced key")
        configs[c["name"]] = c
    cells = {}
    for w in bench["workloads"]:
        check_name(w.get("name"), "workload")
        check_name(w.get("traffic"), f"workload {w['name']} traffic")
        if w["name"] in cells:
            raise BenchmarkError(f"workload {w['name']!r} appears twice")
        if w.get("config") not in configs:
            raise BenchmarkError(
                f"workload {w['name']!r} names config {w.get('config')!r}, "
                "which BENCHMARK.json does not define")
        if w.get("chips") not in (1, 4):
            raise BenchmarkError(f"workload {w['name']!r}: chips 1 or 4")
        cells[w["name"]] = w
    e2e = {}
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_name(m.get("name"), "metric")
        check_unit(m.get("unit"), f"metric {m['name']}")
        if m["name"] in e2e:
            raise BenchmarkError(f"metric {m['name']!r} appears twice")
        if m.get("better") not in ("lower", "higher"):
            raise BenchmarkError(f"metric {m['name']!r}: better lower|higher")
        if m.get("source") not in SOURCES:
            raise BenchmarkError(f"metric {m['name']!r}: source "
                                 f"{m.get('source')!r} not in {SOURCES}")
        for w in m.get("workloads", []):
            if w not in cells:
                raise BenchmarkError(
                    f"metric {m['name']!r} lists unknown workload {w!r}")
        e2e[m["name"]] = m
    names = {m["name"] for m in bench["end_to_end"]}
    if "setup_s" not in names:
        raise BenchmarkError("end_to_end lacks setup_s")
    for m in bench["per_layer"]:
        if m.get("moves") not in names:
            raise BenchmarkError(
                f"per-layer metric {m['name']!r} moves {m.get('moves')!r}, "
                "which is no end-to-end metric")
    return bench


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""
    name: str
    chips: int
    why: str
    run_seconds: int
    config_name: str
    config: dict            # the configuration file, as run
    traffic_name: str
    traffic: dict           # the traffic file
    end_to_end: list        # metric entries this cell reports
    per_layer: list         # metric entries this cell reports
    root: Path

    def module(self, kind: str, name: str):
        return load_module(self.root, kind, name)

    def runner(self):
        return self.module("runners", self.traffic["runner"])

    def family(self):
        return self.module("families", self.config["family"])

    def reference(self):
        return self.module("references", self.config["family"])

    def layer_metric(self, name: str):
        return self.module("layer_metrics", name)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json; "
                             f"it has {sorted(cells)}")
    w = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = read_json(root / entry["file"])
    traffic = read_json(root / BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    for key, holder, what in (("family", config, entry["file"]),
                              ("runner", traffic, w["traffic"])):
        if key not in holder:
            raise BenchmarkError(f"{what} lacks {key!r}")
    cell = Cell(
        name=name, chips=w["chips"], why=w["why"],
        run_seconds=bench["run_seconds"], config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)
    # fail loudly now, not after a compile
    cell.runner(), cell.family(), cell.reference()
    for m in cell.per_layer:
        cell.layer_metric(m["name"])
    return cell
