"""The host spans ``TrainerRound`` writes into a ``jax.profiler`` trace,
read back from the profile on the CPU: their vocabulary and nesting,
how many each round enters, and the names of the jitted programs they
dispatch."""
import glob
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import AdLoCoConfig
from repro.core import train_adloco
from repro.core.adloco import TrainerRound
from repro.data import QuadraticProblem

H, M, DIM = 2, 2, 8

#: child span -> the span it must lie inside
PARENT = {"adloco.data": "adloco.inner", "adloco.step": "adloco.inner",
          "adloco.sync.loss": "adloco.inner",
          "adloco.stats": "adloco.inner",
          "adloco.sync.batch": "adloco.stats",
          "adloco.outer.stack": "adloco.outer",
          "PjitFunction(inner_step)": "adloco.step",
          "PjitFunction(inner_step_accum)": "adloco.step",
          "PjitFunction(outer_step)": "adloco.outer"}


class QuadStream:
    def __init__(self, prob, shard):
        self.prob = prob
        self.rng = np.random.default_rng(np.random.SeedSequence([0, shard]))

    def next_batch(self, b):
        A, y = self.prob.sample(b, self.rng)
        return {"A": A, "y": y}


def quad_loss(params, batch):
    r = batch["A"] @ params["x"] - batch["y"]
    return 0.5 * jnp.mean(jnp.square(r)), {}


def setup(**overrides):
    acfg = AdLoCoConfig(num_outer_steps=1, num_inner_steps=H,
                        nodes_per_gpu=M, num_init_trainers=1,
                        inner_optimizer="sgd", lr_inner=0.05,
                        stats_estimator="microbatch", enable_merge=False,
                        **overrides)
    prob = QuadraticProblem(dim=DIM, noise=1.0, seed=0)
    inits = [{"x": jnp.zeros(DIM)}]
    streams = [QuadStream(prob, m) for m in range(M)]
    return acfg, inits, streams


def rounds(rounds_i):
    """Drive ``TrainerRound`` as the chip benchmark does: inner, then
    outer, for each of ``rounds_i``; the first round runs before the
    trace, so the trace holds no compiles."""
    def run(acfg, inits, streams, trace):
        rnd = TrainerRound(quad_loss, acfg)
        tr = rnd.init_pool(inits, streams).trainers[0]

        def one(r):
            out = rnd.inner(tr, round_i=r)
            rnd.outer(tr, out.worker_params)
            jax.block_until_ready(tr.params)

        one(1)
        with trace():
            for r in rounds_i:
                one(r)
    return run


def train(acfg, inits, streams, trace):
    with trace():
        train_adloco(quad_loss, inits, streams, acfg, num_outer_steps=1)


#: name -> (config overrides, how the rounds run, the inner program,
#: spans expected)
CASES = {
    "plain": ({"initial_batch_size": 2, "max_batch": 16}, rounds([2]),
              "inner_step",
              {"adloco.inner": 1, "adloco.data": H * M,
               "adloco.step": H * M, "adloco.sync.loss": M,
               "adloco.stats": 1, "adloco.sync.batch": 1,
               "adloco.outer": 1, "adloco.outer.stack": 1}),
    "accum": ({"initial_batch_size": 8, "max_batch": 2,
               "switch_multiplier": 1, "max_global_batch": 8},
              rounds([2]), "inner_step_accum",
              {"adloco.inner": 1, "adloco.data": H * M,
               "adloco.step": H * M, "adloco.sync.loss": M,
               "adloco.stats": 1, "adloco.sync.batch": 1,
               "adloco.outer": 1, "adloco.outer.stack": 1}),
    # round 2 of k_correct 2 is predicted: no batch read; round 3 is exact
    "predicted": ({"initial_batch_size": 2, "max_batch": 16,
                   "k_correct": 2}, rounds([2, 3]), "inner_step",
                  {"adloco.inner": 2, "adloco.data": 2 * H * M,
                   "adloco.step": 2 * H * M, "adloco.sync.loss": 2 * M,
                   "adloco.stats": 2, "adloco.sync.batch": 1,
                   "adloco.outer": 2, "adloco.outer.stack": 2}),
    "train_adloco": ({"initial_batch_size": 2, "max_batch": 16}, train,
                     "inner_step",
                     {"adloco.round": 1, "adloco.inner": 1,
                      "adloco.data": H * M, "adloco.step": H * M,
                      "adloco.sync.loss": M, "adloco.stats": 1,
                      "adloco.sync.batch": 1, "adloco.outer": 1,
                      "adloco.outer.stack": 1}),
}


def host_spans(log_dir):
    """(name, start, end) of every host event in the profile."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for line in plane.lines for ev in line.events]
    return spans


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_spans(case, tmp_path):
    overrides, drive, inner_program, want = CASES[case]

    def trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        return jax.profiler.trace(str(tmp_path), profiler_options=opts)

    drive(*setup(**overrides), trace)
    spans = host_spans(tmp_path)
    counts = Counter(n for n, _, _ in spans if n.startswith("adloco."))
    assert dict(counts) == want

    programs = {n for n, _, _ in spans if n.startswith("PjitFunction(")}
    assert {f"PjitFunction({inner_program})",
            "PjitFunction(outer_step)"} <= programs
    assert "PjitFunction(step)" not in programs

    def inside(s, e, parent):
        return any(p == parent and ps <= s and e <= pe
                   for p, ps, pe in spans)

    # the outer step takes the workers' params as they are: inside
    # adloco.outer the host dispatches that one program and no eager
    # stack (broadcast_in_dim, concatenate) of them; programs nested in
    # another (trace-time constants of a first call) are not dispatches
    pjit = [(n, s, e) for n, s, e in spans if n.startswith("PjitFunction(")]
    outer_programs = Counter(
        n for n, s, e in pjit if inside(s, e, "adloco.outer")
        and not any((ps, pe) != (s, e) and ps <= s and e <= pe
                    for _, ps, pe in pjit))
    assert outer_programs == {"PjitFunction(outer_step)":
                              want["adloco.outer"]}, outer_programs

    parent = dict(PARENT)
    if "adloco.round" in want:
        parent.update({"adloco.inner": "adloco.round",
                       "adloco.outer": "adloco.round"})
    for name, s, e in spans:
        if name in parent:
            assert inside(s, e, parent[name]), (name, parent[name])
