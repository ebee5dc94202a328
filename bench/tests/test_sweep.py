"""The knee of a sweep: every seed, at that rate and every lower one."""

from bench.sweep import knee


def _rows(table):
    return [{"rate_rps": rate, "seed": i, "keeps_pace": ok}
            for rate, oks in table for i, ok in enumerate(oks)]


def test_one_lucky_seed_does_not_set_the_knee():
    rows = _rows([(2.0, [True, True]), (2.5, [True, False]),
                  (3.0, [True, True])])
    assert knee(rows) == 2.0


def test_no_rate_keeps_pace():
    assert knee(_rows([(1.0, [False, True])])) is None


def test_every_rate_keeps_pace():
    assert knee(_rows([(0.5, [True]), (0.65, [True, True])])) == 0.65
