"""``BENCHMARK.json`` and the files it names: loading and the checks on them.

A cell is found by name: its workload entry names a configuration (whose
``file`` is under ``configs/``) and a traffic mix (``traffic/<mix>.json``);
``cells/<cell>.json`` holds what belongs to the cell alone (the optimizer
arm, the backends its sketched tables must resolve to, the correctness
limits).  The configuration names its runner (``runners/<runner>.py``) and
its plain reference (``reference/<reference>.py``); each per-layer metric
is read by ``metrics/<metric>.py``.  Nothing here changes when a cell is
added: a later cell brings files and entries only."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import List

HERE = pathlib.Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict                     # cells/<name>.json
    end_to_end: List[dict]         # the end-to-end metrics it reports
    per_layer: List[dict]          # the per-layer metrics it reports


def load_module(path: pathlib.Path):
    """Import a file of this benchmark by path (its name may hold dots)."""
    mod_name = "chipbench_file_" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """The manifest at ``root/BENCHMARK.json`` with the files it names."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.bench_dir = self.root / "chipbench"
        with open(self.root / "BENCHMARK.json") as f:
            self.manifest = json.load(f)

    def _json(self, rel: str) -> dict:
        with open(self.root / rel) as f:
            return json.load(f)

    def cell_names(self) -> List[str]:
        return [w["name"] for w in self.manifest["workloads"]]

    def reports(self, metric: dict, cell: str) -> bool:
        """Whether ``cell`` reports ``metric`` (its ``workloads``, or for a
        per-layer metric without one, every cell reporting its ``moves``)."""
        if "workloads" in metric:
            return cell in metric["workloads"]
        if "moves" in metric:
            moved = next(m for m in self.manifest["end_to_end"]
                         if m["name"] == metric["moves"])
            return self.reports(moved, cell)
        return True

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(cells: {', '.join(self.cell_names())})")
        conf = next(c for c in self.manifest["configs"]
                    if c["name"] == entry["config"])
        return Cell(
            name=name, chips=int(entry["chips"]),
            config=self._json(conf["file"]),
            traffic=self._json(f"chipbench/traffic/{entry['traffic']}.json"),
            spec=self._json(f"chipbench/cells/{name}.json"),
            end_to_end=[m for m in self.manifest["end_to_end"]
                        if self.reports(m, name)],
            per_layer=[m for m in self.manifest["per_layer"]
                       if self.reports(m, name)])

    def runner(self, cell: Cell):
        return load_module(self.bench_dir / "runners"
                           / f"{cell.config['runner']}.py")

    def reference(self, cell: Cell):
        return load_module(self.bench_dir / "reference"
                           / f"{cell.config['reference']}.py")

    def metric_reader(self, metric: dict):
        return load_module(self.bench_dir / "metrics" / f"{metric['name']}.py")


def problems(bench: Benchmark) -> List[str]:
    """What in the manifest or its files breaks the benchmark's rules (an
    empty list when nothing does)."""
    m = bench.manifest
    out = []
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m.get(group, []):
            if not NAME.match(e["name"]):
                out.append(f"{group}: illegal name {e['name']!r}")
            if e["name"] in names:
                out.append(f"duplicate name {e['name']!r}")
            names.add(e["name"])
    for e in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(e["unit"]):
            out.append(f"{e['name']}: illegal unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            out.append(f"{e['name']}: better must be lower or higher")
        if e["source"] not in SOURCES:
            out.append(f"{e['name']}: unknown source {e['source']!r}")
    for e in m["end_to_end"]:
        if e["source"] not in ("host_clock", "device_trace"):
            out.append(f"{e['name']}: end-to-end source must be host_clock "
                       f"or device_trace")
        if not 0 < e["bound"] <= 0.25:
            out.append(f"{e['name']}: bound out of (0, 0.25]")
    e2e = {e["name"] for e in m["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("no setup_s end-to-end metric")
    configs = {c["name"]: c for c in m["configs"]}
    used = set()
    for w in m["workloads"]:
        if w["config"] not in configs:
            out.append(f"{w['name']}: unknown config {w['config']!r}")
            continue
        used.add(w["config"])
        if w["chips"] not in (1, 4):
            out.append(f"{w['name']}: chips must be 1 or 4")
        for rel in (configs[w["config"]]["file"],
                    f"chipbench/traffic/{w['traffic']}.json",
                    f"chipbench/cells/{w['name']}.json"):
            if not (bench.root / rel).is_file():
                out.append(f"{w['name']}: missing {rel}")
        if out:
            continue
        cell = bench.cell(w["name"])
        for kind, folder in (("runner", "runners"), ("reference", "reference")):
            path = bench.bench_dir / folder / f"{cell.config[kind]}.py"
            if not path.is_file():
                out.append(f"{w['name']}: missing "
                           f"{path.relative_to(bench.root)}")
        reported = {e["name"] for e in cell.end_to_end}
        if "setup_s" not in reported or len(reported) < 2:
            out.append(f"{w['name']}: reports setup_s and no other "
                       f"end-to-end metric")
        if not cell.per_layer:
            out.append(f"{w['name']}: reports no per-layer metric")
    for c in m["configs"]:
        if c["name"] not in used:
            out.append(f"config {c['name']!r} is used by no cell")
        if not (bench.root / c["file"]).is_file():
            out.append(f"config {c['name']!r}: missing {c['file']}")
    cells = {w["name"] for w in m["workloads"]}
    for p in m["per_layer"]:
        if p["moves"] not in e2e:
            out.append(f"{p['name']}: moves unknown metric {p['moves']!r}")
            continue
        moved = next(e for e in m["end_to_end"] if e["name"] == p["moves"])
        for c in p.get("workloads", ()):
            if c not in cells:
                out.append(f"{p['name']}: unknown cell {c!r}")
            elif not bench.reports(moved, c):
                out.append(f"{p['name']}: cell {c} does not report "
                           f"{p['moves']}")
        if not (bench.bench_dir / "metrics" / f"{p['name']}.py").is_file():
            out.append(f"{p['name']}: missing chipbench/metrics/"
                       f"{p['name']}.py")
    return out
