"""The trace reduction on ``round_spans.xplane.pb``: two rounds of the
plain cell cut to ``tiny.py``'s size, recorded on a TPU v5e by
``record_spans_trace.py``, where each program has a name of its own and
the program's ``adloco.*`` host spans lie on the device's clock; and the
readers' values on ``two_steps.xplane.pb``, pinned for any change to the
reduction."""
from collections import Counter
from pathlib import Path

import pytest

from benchmarks.chip import harness
from benchmarks.chip import trace_reduce as T

DATA = Path(__file__).resolve().parents[1] / "testdata"
#: the readers on ``two_steps`` with ``two_step_run``'s work counts
READ_BEFORE = {"inner_step_ms": 0.00320175,
               "inner_step_roofline": 38.13543284145299,
               "stats_ms": None, "outer_ms": 0.003558,
               "device_idle_share": 99.53850127841407,
               "train_mfu": 0.0888960479839532}
#: each program span in the two traced rounds (H=2 steps, M=2 workers),
#: and the harness span it lies in
SPANS = {"adloco.inner": (2, "bench.inner"), "adloco.data": (8, "bench.inner"),
         "adloco.step": (8, "bench.inner"),
         "adloco.sync.loss": (4, "bench.inner"),
         "adloco.stats": (2, "bench.inner"),
         "adloco.sync.batch": (2, "bench.inner"),
         "adloco.outer": (2, "bench.outer"),
         "adloco.outer.stack": (2, "bench.outer")}


def two_step_run(red):
    return {"roles": T.assign_roles(red, {"inner": ("jit_step", 2),
                                          "outer": ("jit_step", 1),
                                          "stats": ("jit_absent", 1)}),
            "flops_per_step": 1e8, "bytes_per_step": 1e6,
            "steps_traced": 4, "peak_flops": 197e12,
            "peak_bytes_per_s": 819e9}


@pytest.mark.parametrize("name", sorted(READ_BEFORE))
def test_readers_on_two_steps_are_pinned(name):
    red = T.reduce_trace(str(DATA / "two_steps.xplane.pb"))
    assert (red.window_ns, red.busy_ns) == (2284080.0, 10541.0)
    got = harness.metric_reader(name)(red, two_step_run(red))
    want = READ_BEFORE[name]
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


def test_programs_found_by_their_names():
    red = T.reduce_trace(str(DATA / "round_spans.xplane.pb"))
    assert red.rounds == 2 and red.program_rounds == 3
    roles = T.assign_roles(red, {
        "inner": ("jit_inner_step", 4),
        "stats": ("jit_stats_from_microbatch_grads", 1),
        "outer": ("jit_outer_step", 1)})
    assert {r: p.count for r, p in roles.items()} == {
        "inner": 12, "stats": 3, "outer": 3}
    assert not any(p.name == "jit_step" for p in red.programs.values())


def test_program_spans_lie_in_the_harness_spans():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "round_spans.xplane.pb"))
    host, = [p for p in pd.planes if p.name == "/host:CPU"]
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for line in host.lines for ev in line.events]
    rounds = [(s, e) for n, s, e in spans if n == "bench.round"]
    lo, hi = min(s for s, _ in rounds), max(e for _, e in rounds)
    inside = [(n, s, e) for n, s, e in spans
              if n.startswith("adloco.") and lo <= s and e <= hi]
    assert Counter(n for n, _, _ in inside) == {
        n: c for n, (c, _) in SPANS.items()}
    for name, s, e in inside:
        assert any(p == SPANS[name][1] and ps <= s and e <= pe
                   for p, ps, pe in spans), name
