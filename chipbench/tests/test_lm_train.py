"""The language-model cell at a test size: a copy of the benchmark with a
tiny Moonlight-shaped cell (latent attention, sigmoid routing over 2 of 8
experts, the embedding through the sparse-rows step), run through
``harness.run_cell`` on the CPU; its per-layer readers on hand-made
traces; the step's cost against hand counts."""
import json
import pathlib
import time
import types

import pytest

from chipbench import compare, generate, harness, spans, trace
from chipbench.bench import Benchmark, load_module
from chipbench.tests import fixtures

CELL = "tiny-lm.cs_adam.seq16"
CONFIG = "moonlight-16b-a3b-ep8"
# the program's reduced sibling of the cut, as the configuration states it
SMOKE = {"vocab_size": 262144, "n_experts_held": 2}
SIZES = {"hidden_size": 128, "num_attention_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "moe_intermediate_size": 32, "intermediate_size": 256,
         "n_routed_experts": 8, "num_experts_per_tok": 2, "n_experts_held": 2,
         "first_k_dense_replace": 1, "n_layers": 3, "vocab_size": 262144,
         "n_shared_experts": 2, "compute_dtype": "float32"}
WIDTH = 17664                 # 262,144 / (5 · 3), rounded up to 256


def _dump(path: pathlib.Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def add_tiny_lm(root: pathlib.Path, manifest: dict) -> str:
    """The tiny cell as files and manifest entries alone."""
    cb = root / "chipbench"
    with open(fixtures.BENCH / "configs" / f"{CONFIG}.json") as f:
        cfg = json.load(f)
    with open(fixtures.BENCH / "cells" /
              "moonlight-ep8.cs_adam.seq8k.json") as f:
        cell = json.load(f)
    cfg.update(SIZES, name="tiny-lm", smoke=SMOKE)
    for sk in cfg["sketch"].values():
        sk["width"] = WIDTH
    # few ids on a wide sketch: the CPU's batched ``xla`` update and the
    # reference's per-id order agree as the chip's ``tiled`` path does
    cell.update(backends=[["pair", "adam_rows", "xla"]],
                limits={"loss": 1e-5, "grad": 1e-4, "change": 1e-3})
    _dump(cb / "configs" / "tiny-lm.json", cfg)
    _dump(cb / "traffic" / "tiny-seq16.json",
          {"batch": 2, "seq": 16, "alpha": 1.0, "pool": 4})
    _dump(cb / "cells" / f"{CELL}.json", cell)
    manifest["configs"].append({"name": "tiny-lm", "source": cfg["source"],
                                "file": "chipbench/configs/tiny-lm.json",
                                "reduced": cfg["reduced"], "why": "a test"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-lm",
                                  "traffic": "tiny-seq16", "chips": 1,
                                  "why": "a test cell"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "moonlight-ep8.cs_adam.seq8k" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    _dump(root / "BENCHMARK.json", manifest)
    return CELL


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    r = tmp_path_factory.mktemp("lm")
    add_tiny_lm(r, fixtures.copy_benchmark(r))
    return Benchmark(r)


def _run(bench, fault=None):
    return harness.run_cell(bench, bench.cell(CELL), 2**31 + 77, 0.2, False,
                            time.perf_counter(), fault=fault)


def test_tiny_cell_is_found(bench):
    from chipbench.bench import problems
    assert problems(bench) == []
    cell = bench.cell(CELL)
    assert {e["name"] for e in cell.end_to_end} == {
        "ids_per_s", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= {
        "mfu.lm", "attn_ms.lm", "router_ms.lm", "experts_roofline",
        "opt_ms.lm", "kernel_ms.cs_adam_tiled", "cs_adam_tiled_roofline"}


def test_sound_run_is_correct(bench):
    line = _run(bench)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"ids_per_s", "peak_hbm_gib", "setup_s"}
    assert line["backends"] == [["pair", "adam_rows", "xla"]]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(bench, fault):
    line = _run(bench, fault)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_control_fails_the_limits(bench):
    cell = bench.cell(CELL)
    runner, reference = bench.runner(cell), bench.reference(cell)
    pool = generate.batches(runner.n_ids(cell), cell.traffic, 5)
    ref = reference.numbers(cell, 5, pool[:3])
    ctl = reference.numbers(cell, 5, pool[:3], control=cell.spec["control"])
    gaps = compare.gaps(ctl, ref)
    assert not compare.judge(gaps, cell.spec["limits"]), gaps


# -- the cost of a step -------------------------------------------------------

TINY = {"d": 4, "h": 1, "r": 2, "dn": 1, "dr": 2, "dv": 1, "ff_dense": 3,
        "ff": 2, "shared": 4, "E": 2, "K": 1, "held": 1, "rank": 0,
        "n_dense": 1, "n_moe": 1, "V": 5}


def _cost():
    return load_module(fixtures.BENCH / "cost" / "lm_step.py")


def test_lm_step_flops_by_hand():
    c = _cost()
    # attention 4·3 + 4·4 + 2·2 + 1·4 = 36 a layer, 2 layers; dense FFN
    # 3·4·3; router 4·2 and shared 3·4·4; head 5·4
    assert c.token_params(TINY) == 72 + 36 + 56 + 20
    got = c.cost([{"ids": 6, "unique": 3}], TINY, [4.0], batch=2, seq=3)
    # 6 FLOPs a parameter a token; causal attention 3·2·b·h·s(s+1)/2·4 a
    # layer; 4 routed pairs through 3·4·2 expert weights
    assert got["flops"] == 6 * 184 * 6 + 3 * 2 * 2 * 1 * 6 * 4 * 2 \
        + 6 * 4 * 3 * 4 * 2


def test_experts_cost_by_hand():
    e = _cost().experts_cost(TINY, [2.0, 6.0])
    assert e["flops"] == 6 * 4 * 3 * 4 * 2
    # weights 3·4·2 read three times in bf16 and their gradient written
    # in f32; per pair 2·(4 + 3·2 + 4) B forward and as many backward
    assert e["bytes"] == 24 * 10 + 2 * 4 * 28


def test_the_moonlight_share_needs_four_gflop_a_token():
    with open(fixtures.BENCH / "configs" / f"{CONFIG}.json") as f:
        cfg = json.load(f)
    a = load_module(fixtures.BENCH / "reference" / "lm_train.py").sizes(cfg)
    c = _cost()
    assert c.token_params(a) == 543_293_440
    got = c.cost([{"ids": 16384, "unique": 5000}], a, [49152.0], batch=2,
                 seq=8192)
    assert 4.0e9 < got["flops"] / 16384 < 4.1e9


# -- the per-layer readers on a hand-made trace -------------------------------

def _ctx(monkeypatch, scopes, steps=2):
    """Two steps of ops on one device, each op 1 µs per unit of its
    length, with the scope map ``scopes`` ({op: scope})."""
    ops = [trace.Op("fusion.1", 0, 4000), trace.Op("gmm.2", 4000, 10000),
           trace.Op("sort.3", 10000, 12000),
           trace.Op("while.4", 12000, 20000),
           trace.Op("cs_adam_tiled.5", 13000, 18000),
           trace.Op("scatter.6", 20000, 21000),
           trace.Op("fusion.7", 21000, 22000)]
    red = trace.Reduced(devices={"/device:TPU:0": ops}, host=[],
                        window=(0, 25000))
    s = spans.Spans(reduced=red, program=[], modules={}, hlo={})
    s.__dict__["scopes"] = scopes
    monkeypatch.setattr(spans, "of", lambda ctx: s)
    cost = {"step": {"flops": 197e12 * 25e-6 / steps * 0.4},
            "kernels": {"experts": {"flops": 197e12 * 6e-6 / steps * 0.5,
                                    "bytes": 1.0}}}
    return harness.MetricContext(
        trace=red, steps=steps, cell=types.SimpleNamespace(chips=1),
        cost=cost, peaks={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9})


SCOPES = {"fusion.1": "obs.attn", "gmm.2": "obs.experts",
          "sort.3": "obs.router", "while.4": "obs.kernel",
          "cs_adam_tiled.5": "obs.kernel", "scatter.6": "obs.apply",
          "fusion.7": "obs.dedup"}


def _reader(name):
    return Benchmark(fixtures.REPO).metric_reader({"name": name})


@pytest.mark.parametrize("name,want", [
    ("attn_ms.lm", 4e-3 / 2), ("router_ms.lm", 2e-3 / 2),
    # the while's own 3 µs and the kernel's 5, the scatter, the dedup
    ("opt_ms.lm", (3 + 5 + 1 + 1) * 1e-3 / 2),
    ("experts_roofline", 50.0), ("mfu.lm", 40.0)])
def test_reader_on_a_hand_made_trace(monkeypatch, name, want):
    assert _reader(name).read(_ctx(monkeypatch, SCOPES)) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", ["attn_ms.lm", "router_ms.lm", "opt_ms.lm",
                                  "experts_roofline"])
def test_reader_without_the_programs_scopes_reads_none(monkeypatch, name):
    """The parent program has no such scope: nothing to read, no error."""
    assert _reader(name).read(_ctx(monkeypatch, None)) is None
    other = {k: "obs.grad" for k in SCOPES}
    assert _reader(name).read(_ctx(monkeypatch, other)) is None
