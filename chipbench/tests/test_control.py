"""The control — the reference put in the program's place, computed in
the precision below the configuration's — fails the cell's limits, here at
a test size (on the chip it is read at the cell's own size by
``calibrate.py``)."""
import pytest

from chipbench import compare, generate
from chipbench.bench import Benchmark
from chipbench.tests import fixtures


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    r = tmp_path_factory.mktemp("control")
    m = fixtures.copy_benchmark(r)
    fixtures.tiny_sparse(r, m)
    return Benchmark(r)


def test_control_fails_the_limits(bench):
    cell = bench.cell("tiny-emb.cs_adam.zipf")
    runner, reference = bench.runner(cell), bench.reference(cell)
    pool = generate.batches(runner.n_ids(cell), cell.traffic, 77)
    ref = reference.numbers(cell, 77, pool[:3])
    ctl = reference.numbers(cell, 77, pool[:3], control=cell.spec["control"])
    gaps = compare.gaps(ctl, ref)
    assert not compare.judge(gaps, cell.spec["limits"]), gaps
