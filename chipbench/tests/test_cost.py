"""The operation and byte counts of ``cost/`` against hand counts."""
import pathlib

from chipbench.bench import load_module

COST = pathlib.Path(__file__).resolve().parents[1] / "cost"


def _mod(name):
    return load_module(COST / f"{name}.py")


def test_cs_adam_tiled_bytes_and_ops():
    c = _mod("cs_adam_tiled").cost([{"ids": 10, "unique": 4},
                                    {"ids": 10, "unique": 6}],
                                   dim=128, depth=3)
    # 5 unique rows: grad read + update write, and m, v: 3 rows read and
    # written each, 512 B a row
    assert c["bytes"] == 5 * 512 * 2 + 2 * 3 * 5 * 512 * 2
    assert c["flops"] == 5 * 128 * (4 * 3 + 12)


def test_sparse_step_bytes():
    c = _mod("sparse_step").cost([{"ids": 8, "unique": 2}], dim=4, depth=3)
    # ids 8·4 B; table, target read and table written for 2 rows of 16 B;
    # sketches: 2 moments · 3 rows · 2 uniques · 16 B, read and written
    assert c["bytes"] == 32 + 2 * 16 * 3 + 2 * 3 * 2 * 16 * 2
    assert c["flops"] == 8 * 4 * 3 + 2 * 4 * (4 * 3 + 12)
