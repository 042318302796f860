"""trace_reduce on a small trace in the layout a TPU v5e profile has
(`data/make_tiny_xplane.py` writes it): a device plane whose "XLA Ops"
line holds a while loop around two fusions and a kernel custom call,
plus a copy after it, and whose "XLA Modules" line holds three calls of
one program; a host plane with `bench.step` and `bench.idle` spans
inside `bench.window`. Names and nesting follow the chip traces of
PERF.md's first runs."""
import pathlib

import pytest

from bench import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_file(DATA / "tiny.xplane.pb")


def test_window_and_busy_time(red):
    assert 0.0 < red.window_s < 10.0
    assert 0.0 < red.busy_s < red.window_s
    assert len(red.busy_ns_per_device) >= 1


def test_ops_fall_inside_the_window_and_name_their_program(red):
    lo, hi = red.window
    assert red.ops
    assert all(op.start + op.dur > lo and op.start < hi for op in red.ops)
    assert any("jit" in op.module for op in red.ops)
    summary = red.module_summary()
    assert summary and summary[0][2] == 3      # three calls of the program
    assert sum(red.op_seconds().values()) >= red.busy_s * 0.999


def test_idle_gaps_are_labelled_by_the_covering_span(red):
    gaps = red.idle_gaps()
    labels = {g[0] for g in gaps}
    assert "bench.idle" in labels
    idle = sum(s for name, s in gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][1] >= b["idle_gaps"][-1][1]


def test_kernel_and_module_lookups(red):
    name = red.ops[0].name
    assert red.kernel_seconds(name) > 0
    assert red.kernel_seconds("sla_fwd") == pytest.approx(600e-6)
    assert red.kernel_seconds("no-such-kernel") == 0
    mod = red.module_summary()[0][0]
    assert len(red.module_durations(mod)) == 3
    assert 0 < red.module_busy_seconds(mod) <= red.busy_s * 1.000001
