"""Tiny cells for the CPU tests: a copy of the benchmark in a temporary
checkout, with configurations small enough for a test run, found by the
harness exactly as the real ones are (files and manifest entries only)."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY_ROWS, TINY_IDS = 1 << 20, 256


def _dump(path: pathlib.Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def copy_benchmark(root: pathlib.Path) -> dict:
    """The repository's benchmark (manifest and ``chipbench/``) under
    ``root``, with ``src`` linked in; returns the manifest."""
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(REPO / "src")
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def add_cell(root: pathlib.Path, manifest: dict, *, name: str, config: dict,
             traffic: str, mix: dict, cell: dict, rate_metric: str) -> None:
    """Add one cell as files and manifest entries alone."""
    cb = root / "chipbench"
    _dump(cb / "configs" / f"{config['name']}.json", config)
    _dump(cb / "traffic" / f"{traffic}.json", mix)
    _dump(cb / "cells" / f"{name}.json", cell)
    if all(c["name"] != config["name"] for c in manifest["configs"]):
        manifest["configs"].append({
            "name": config["name"], "source": config["source"],
            "file": f"chipbench/configs/{config['name']}.json",
            "reduced": config.get("reduced", []), "why": "a test cell"})
    manifest["workloads"].append({"name": name, "config": config["name"],
                                  "traffic": traffic, "chips": 1,
                                  "why": "a test cell"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m and (m["name"] == rate_metric
                                 or m.get("moves") == rate_metric):
            m["workloads"].append(name)
    _dump(root / "BENCHMARK.json", manifest)


def tiny_sparse(root: pathlib.Path, manifest: dict,
                name: str = "tiny-emb.cs_adam.zipf") -> str:
    with open(BENCH / "configs" / "criteo-tb-emb-d128.json") as f:
        cfg = json.load(f)
    with open(BENCH / "cells" / "criteo-emb.cs_adam.cat0.json") as f:
        cell = json.load(f)
    # few ids on a wide sketch: buckets seldom collide, so the CPU's
    # batched ``xla`` update and the per-row reference agree as the chip's
    # ``tiled`` path and the reference do
    cfg.update(name="tiny-emb", num_rows=TINY_ROWS, embedding_dim=8,
               sketch={"depth": 3, "width": 70144,
                       "seed": cfg["sketch"]["seed"]})
    cell["backends"] = [["pair", "adam_rows", "xla"]]
    add_cell(root, manifest, name=name, config=cfg, traffic="tiny-zipf",
             mix={"batch": 1, "seq": TINY_IDS, "alpha": 1.1, "pool": 4},
             cell=cell, rate_metric="ids_per_s")
    return name
