"""The references agree with the program where the program computes in
float32 (small widths, CPU), so a reading above the limit on the chip is
the program's precision or a fault, not a reference that drifted."""
import time

from bench import harness
from bench.tests import small


def _run(cell, seed=2**33 + 5, seconds=2.0, **kw):
    return harness.run(cell, seed, seconds, False, time.perf_counter(),
                       small.CPU, **kw)


def test_dit_reference_matches_the_program():
    res = _run(small.dit_cell(limit=1e-4))
    err = res["readings"]["dit_step_rel_err"]
    assert res["correct"], res
    assert 0.0 <= err < 1e-4
    assert res["compared"]["dit_step_excess_err"]["value"] <= err
    assert res["metrics"]["dit_tokens_per_s"]["value"] > 0
    assert set(res["metrics"]) == {"dit_tokens_per_s", "setup_s"}


def test_lm_reference_matches_the_program():
    res = _run(small.lm_cell(limit=1e-4))
    assert res["correct"], res
    assert res["compared"]["lm_logit_gap"]["value"] < 1e-4
    assert res["metrics"]["lm_tokens_per_s"]["value"] > 0


def test_lm_open_loop_drains_every_request_of_the_window():
    res = _run(small.lm_cell(limit=1e-4, loop="open"), seconds=3.0)
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["ttft_p90_ms"]["value"] > 0
    assert res["metrics"]["itl_p95_ms"]["value"] > 0
