"""Device time by scope on ``scopes.xplane.pb``: two rounds of the plain
cell cut to ``tiny.py``'s size, recorded on a TPU v5e by
``record_spans_trace.py --scopes``, which keeps each op's program and
name stack."""
from pathlib import Path

import pytest

from benchmarks.chip import harness
from benchmarks.chip import trace_reduce as T

DATA = Path(__file__).resolve().parents[1] / "testdata"
TRACE = str(DATA / "scopes.xplane.pb")
#: the inner step's device ns under each scope over the trace's twelve
#: executions (three rounds of H=2 steps for M=2 workers)
INNER_SCOPES = {"grad": 338379.068, "update": 56316.092}
#: the readers with the roles of the plain cell
READ = {"update_ms": 0.004693007666666667}


@pytest.fixture(scope="module")
def red():
    return T.reduce_trace(TRACE)


@pytest.fixture(scope="module")
def ops():
    return T.leaf_ops(TRACE)


def inner(red):
    return harness_roles(red)["inner"]


def harness_roles(red):
    return T.assign_roles(red, {
        "inner": ("jit_inner_step", 4),
        "stats": ("jit_stats_from_microbatch_grads", 1),
        "outer": ("jit_outer_step", 1)})


def test_inner_step_scopes_are_pinned(red):
    p = inner(red)
    assert p.count == 12
    got = {s: ns for (name, pid, s), ns in red.scopes.items()
           if (name, pid) == (p.name, p.program_id)}
    assert got == pytest.approx(INNER_SCOPES, rel=1e-12)


@pytest.mark.parametrize("name", sorted(READ))
def test_scope_readers_are_pinned(red, name):
    run = {"roles": harness_roles(red)}
    assert harness.metric_reader(name)(red, run) == pytest.approx(
        READ[name], rel=1e-12)


def test_top_level_scopes_partition_each_program(red, ops):
    """Per program, the leaf ops under no named scope and those under
    each outermost named scope add up to all its leaf ops, and those lie
    inside the program's executions."""
    programs = {p.program_id: p for p in red.programs.values()}
    leaf, bare, tops = {}, {}, {}
    for op in ops:
        leaf[op.program_id] = leaf.get(op.program_id, 0.0) + op.ns
        if op.scopes:
            tops.setdefault(op.program_id, set()).add(op.scopes[0])
        else:
            bare[op.program_id] = bare.get(op.program_id, 0.0) + op.ns
    assert "jit_inner_step" in {programs[pid].name for pid in leaf}
    for pid, total in leaf.items():
        p = programs[pid]
        scoped = sum(red.scopes[(p.name, pid, s)]
                     for s in tops.get(pid, ()))
        assert bare.get(pid, 0.0) + scoped == pytest.approx(total, rel=0.01)
        assert 0 < total <= p.device_ns
    assert tops[inner(red).program_id] == {"grad", "update"}


def test_device_ops_name_program_and_scope(red):
    top = T.top_scopes(red, 10)
    assert len(top) == 10
    assert [s for s, _ in top] == sorted(
        (s for s, _ in top), key=lambda s: -dict(top)[s])
    p = inner(red)
    assert f"{p.name}({p.program_id}) grad" in dict(top)
    assert sum(ns for ns in red.innermost.values()) == pytest.approx(
        sum(op.ns for op in T.leaf_ops(TRACE)), rel=1e-12)


@pytest.mark.parametrize("op_name, scopes", [
    ("jit(inner_step)/update/add:", ("update",)),
    ("jit(inner_step)/grad/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/bqkgh,bskh->bkgqs/dot_general:",
     ("grad",)),
    ("jit(f)/grad/transpose(jvp(attention))/while/body/dot_general:",
     ("grad", "attention")),
    ("jit(f)/grad/jvp(attention/rope)/mul:", ("grad", "attention", "rope")),
    ("jit(f)/grad/jvp(jit(take_along_axis))/gather:", ("grad",)),
    ("jit(f)/mlp/cond/branch_1_fun/mlp/add:", ("mlp",)),
    ("jit(f)/vmap(update)/add:", ("update",)),
    ("jit(f)/checkpoint/remat(grad)/pjit/mul:", ("grad",)),
    ("jit(f)/while/cond/lt:", ()),
    ("jit(f)/add:", ()), ("add", ()), ("", ())])
def test_named_scopes_of_a_name_stack(op_name, scopes):
    assert T.named_scopes(op_name) == scopes


@pytest.mark.parametrize("roles, scope", [
    ({}, "update"), (None, "attention")])
def test_a_reader_with_nothing_to_read_returns_none(red, roles, scope):
    """No inner program in the trace, or no op under the scope in it:
    the reader returns None, and the harness leaves the metric out."""
    roles = harness_roles(red) if roles is None else roles
    assert T.scope_ms(red, roles.get("inner"), scope) is None


def test_only_ops_holding_no_other_op_count():
    evs = [(0, 100, 1), (0, 40, 2), (50, 60, 3), (60, 100, 4),
           (120, 130, 5), (120, 130, 6)]
    assert [m for _, _, m in T._leaves(evs)] == [2, 3, 4, 5, 6]

