"""A run whose timed path is broken underneath reports correct: false.
The chip check is skipped; everything else of the run is driven as on
the chip, at small widths: a step that returns its state unchanged, and
an answer (a latent, a token) altered where it is produced. The DiT
faults are held to the committed cell's own limit."""
import json
import time

from bench import BENCH, faults, harness
from bench.tests import small

DIT_LIMIT = json.loads((BENCH / "cells" / "wan-t2v-32k.json").read_text())[
    "limits"]["dit_step_excess_err"]


def _run(cell, patch):
    return harness.run(cell, 7, 2.0, False, time.perf_counter(), small.CPU,
                       after_setup=patch)


def test_dit_step_returning_its_state_unchanged_is_not_correct():
    res = _run(small.dit_cell(limit=DIT_LIMIT), faults.dit_unchanged)
    assert not res["correct"]


def test_dit_answer_altered_is_not_correct():
    res = _run(small.dit_cell(limit=DIT_LIMIT), faults.dit_altered)
    assert not res["correct"]


def test_lm_step_returning_its_state_unchanged_is_not_correct():
    res = _run(small.lm_cell(limit=1e-4), faults.lm_unchanged)
    assert not res["correct"]


def test_lm_token_altered_is_not_correct():
    res = _run(small.lm_cell(limit=1e-4), faults.lm_altered)
    assert not res["correct"]
