"""The manifest and the files it names keep the benchmark's rules, and a
cell added as files and entries alone is found by the harness."""
import json

import pytest

from chipbench import bench
from chipbench.tests import fixtures


def test_repository_manifest_has_no_problems():
    assert bench.problems(bench.Benchmark(fixtures.REPO)) == []


def test_manifest_keys_and_limits():
    with open(fixtures.REPO / "BENCHMARK.json") as f:
        m = json.load(f)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["chipbench"]
    assert m["command"][1].startswith("chipbench/")
    assert 1 <= m["run_seconds"] <= 51
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
    for p in m["per_layer"]:
        if p["unit"] == "%" and "roofline" in p["name"]:
            assert p["name"].endswith("_roofline")
    texts = [e["why"] for e in m["configs"] + m["workloads"]]
    texts += [c["source"] for c in m["configs"]]
    texts += [p["layer"] for p in m["per_layer"]] + m["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    assert (fixtures.REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for c in m["configs"]:
        assert len(c["reduced"]) <= 16
        with open(fixtures.REPO / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))


@pytest.mark.parametrize("name", ["a b", "x/y", "", "é"])
def test_illegal_names_are_problems(tmp_path, name):
    m = fixtures.copy_benchmark(tmp_path)
    m["workloads"][0]["name"] = name
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    assert any("illegal name" in p
               for p in bench.problems(bench.Benchmark(tmp_path)))


def test_cell_added_as_files_alone_is_found(tmp_path):
    m = fixtures.copy_benchmark(tmp_path)
    name = fixtures.tiny_sparse(tmp_path, m)
    b = bench.Benchmark(tmp_path)
    assert bench.problems(b) == []
    cell = b.cell(name)
    assert cell.config["num_rows"] == fixtures.TINY_ROWS
    assert cell.traffic["seq"] == fixtures.TINY_IDS
    assert {e["name"] for e in cell.end_to_end} == {
        "ids_per_s", "peak_hbm_gib", "setup_s"}
    assert cell.per_layer
    assert hasattr(b.runner(cell), "build")
    assert hasattr(b.reference(cell), "numbers")
    for metric in cell.per_layer:
        assert hasattr(b.metric_reader(metric), "read")


def test_missing_cell_file_is_a_problem(tmp_path):
    m = fixtures.copy_benchmark(tmp_path)
    name = fixtures.tiny_sparse(tmp_path, m)
    (tmp_path / "chipbench" / "cells" / f"{name}.json").unlink()
    assert any("missing" in p
               for p in bench.problems(bench.Benchmark(tmp_path)))


@pytest.mark.parametrize("mix", sorted(
    p.stem for p in (fixtures.BENCH / "traffic").glob("*.json")))
def test_traffic_is_its_features_share_of_the_batch(mix):
    """A mix that names a feature of the source carries the ids a step
    that the feature sends to one row shard: global batch × lookups a
    sample ÷ row shards."""
    with open(fixtures.BENCH / "traffic" / f"{mix}.json") as f:
        t = json.load(f)
    want = t["global_batch"] * t["lookups_per_sample"] // t["row_shards"]
    assert t["batch"] * t["seq"] == want
    assert str(want) in t["about"].replace(",", "")
