"""The control at a size a test run can hold: the reference computed in
float8 (the precision below the bfloat16 a configuration states) in the
program's place has to fail the limit that sound runs of the program
meet, on every seed."""

import pytest

from bench import harness, traffic
from conftest import DATA


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(harness.load_cell("tiny-closed", DATA, DATA),
                         require_chip=False)


@pytest.mark.parametrize("seed", [1, 2**31 + 1, 12345])
def test_control_fails_where_the_program_passes(bench, seed):
    eng = bench.serve(seed)
    tr = traffic.generate(bench.cell.mix, seed=seed, seconds=1.5,
                          vocab=bench.dims.vocab, batch=bench.batch)
    win = bench.window(eng, tr, 1.5)
    bench.free(eng)
    checks, correct, gaps = harness.check(bench, win, seed, control=True)
    limit = checks["logit_gap"]["limit"]
    assert correct and gaps["program"].max() <= limit
    assert gaps["control"].max() > limit
