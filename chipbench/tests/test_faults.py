"""A run with the timed path broken underneath comes out not correct.

The harness's look for a chip is skipped (``harness.run_cell`` is driven
directly on the CPU) and the rest of a run is made at a test size: set-up,
the first three steps through ``Trainer.fit``, the window, the reference.
Each fault a training cell can have is planted in the step the window
drives: a step that returns its state unchanged, and half of the batch
left out with the mean taken over the rest.  (No cell here exchanges
between chips or produces answers one by one.)"""
import time

import pytest

from chipbench import harness
from chipbench.bench import Benchmark
from chipbench.tests import fixtures


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("faults")
    m = fixtures.copy_benchmark(r)
    fixtures.tiny_sparse(r, m)
    return r


def _run(root, name, fault):
    b = Benchmark(root)
    return harness.run_cell(b, b.cell(name), 2**31 + 5, 0.2, False,
                            time.perf_counter(), fault=fault)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(root, fault):
    line = _run(root, "tiny-emb.cs_adam.zipf", fault)
    assert line["correct"] is False
    assert list(line)[-1] == "checks"
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_sound_run_result_line(root):
    line = _run(root, "tiny-emb.cs_adam.zipf", None)
    assert line["correct"] is True
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(line)[-1] == "checks"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"ids_per_s", "peak_hbm_gib", "setup_s"}
    assert set(line["peak_bytes_by_phase"]) == {"build", "checked_steps",
                                                "window"}
