"""The control at a size a test run can hold: the reference computed in
float8 (bench/reference/dense.py, ``quant="fp8"``) put in the program's
place, through a whole run of the harness (bench/control.py), reads a far
wider logit gap than the program does on every seed, and the harness
judges it not correct against the cell's limit."""

import pytest

import tinycell
from bench import control

isolated_cache = pytest.fixture(scope="module", autouse=True)(tinycell.isolated_cache_fixture)
SEEDS = [5, 6, 7, 2**34 + 1]
LIMIT = 0.05  # as in test_bench_faults.py
CELLS = ["tiny-qwen.tiny_decode", "tiny-chatglm.tiny_decode"]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    root = tinycell.make_root(tmp_path_factory.mktemp("bench"), limit=LIMIT, sample=8)
    return {cell: (list(control.readings(cell, SEEDS, root=root, need_chip=False)),
                   list(control.control_runs(cell, SEEDS, root=root, need_chip=False)))
            for cell in CELLS}


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_three_times_the_program(readings, cell):
    rows, ctl = readings[cell]
    assert [r["requests"] for r in rows] == [8] * len(SEEDS)
    lower = max(r["logit_gap"] for r in rows)
    upper = min(r["logit_gap"] for r in ctl)
    assert upper >= 3 * lower, (rows, ctl)


@pytest.mark.parametrize("cell", CELLS)
def test_control_run_is_not_correct(readings, cell):
    _, ctl = readings[cell]
    assert [r["seed"] for r in ctl] == SEEDS
    for r in ctl:
        assert not r["correct"] and r["limits"] == {"logit_gap": LIMIT}, r
