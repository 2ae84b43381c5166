"""The trace reduction on a small trace recorded on a TPU v5e: two
programs both named ``jit_step`` (one run twice a round, one once), over
two rounds of harness spans."""
from pathlib import Path

import pytest

from benchmarks.chip import trace_reduce as T

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "two_steps.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return T.reduce_trace(str(TRACE))


def test_programs_of_one_name_stay_apart(red):
    steps = [p for p in red.programs.values() if p.name == "jit_step"]
    assert len(steps) == 2
    assert steps[0].program_id != steps[1].program_id
    assert sorted(p.count for p in steps) == [2, 4]
    assert all(p.device_ns > 0 for p in steps)


def test_roles_by_executions_per_round(red):
    assert red.rounds == 2
    roles = T.assign_roles(red, {"inner": ("jit_step", 2),
                                 "outer": ("jit_step", 1),
                                 "stats": ("jit_absent", 1)})
    assert roles["inner"].count == 4 and roles["outer"].count == 2
    assert roles["inner"].program_id != roles["outer"].program_id
    assert "stats" not in roles


def test_roles_that_cannot_be_told_apart_are_left_out(red):
    roles = T.assign_roles(red, {"inner": ("jit_step", 1),
                                 "outer": ("jit_step", 1)})
    assert roles == {}


def test_busy_idle_and_gaps(red):
    assert red.chips == 1
    assert 0 < red.busy_ns < red.window_ns
    assert 0 < red.idle_share < 1
    gap_ns = sum(ns for _, ns in red.gaps)
    assert gap_ns == pytest.approx(red.window_ns - red.busy_ns, rel=1e-9)
    assert {name for name, _ in red.gaps} <= {
        "bench.round", "bench.inner", "bench.outer", "outside"}
    assert T.top_gaps(red, 3)[0][1] == pytest.approx(red.gaps[0][1] / 1e9)


def test_union_of_nested_and_overlapping_intervals():
    assert T._union([(0, 10), (2, 3), (5, 12), (20, 21)]) == [(0, 12),
                                                              (20, 21)]
    assert T._clip([(0, 12), (20, 21)], 5, 20.5) == [(5, 12), (20, 20.5)]
