"""The controls read as not correct: the reference computed from float8
inputs, in the program's place, fails the comparison that sound runs
pass."""
import time

from bench import harness
from bench.tests import small


def test_dit_float8_reference_fails_the_limit():
    cell = small.dit_cell(limit=1e-4)
    limit = cell.settings["limits"]["dit_step_excess_err"]
    kept = {}
    ok = harness.run(cell, 11, 2.0, False, time.perf_counter(), small.CPU,
                     after_setup=lambda r: kept.update(runner=r))
    assert ok["correct"]
    ctl = kept["runner"].check(control=True)
    assert ctl["dit_step_excess_err"] > limit
    prog = ok["readings"]["dit_step_rel_err"]
    assert ctl["dit_step_rel_err"] > 1e4 * prog


def test_lm_float8_reference_fails_the_limit():
    cell = small.lm_cell(limit=1e-4)
    kept = {}
    ok = harness.run(cell, 12, 2.0, False, time.perf_counter(), small.CPU,
                     after_setup=lambda d: kept.update(runner=d))
    assert ok["correct"]
    gap = kept["runner"].check(control=True)["lm_logit_gap"]
    assert gap > cell.settings["limits"]["lm_logit_gap"]
