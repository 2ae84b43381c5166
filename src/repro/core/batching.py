"""Adaptive batch-size tests (AdAdaGrad family — paper §3.3 / eqs 10,12,13).

All three tests reduce to three statistics over per-sample gradients g_i
(i = 1..b) with mean ḡ:

  s_i = ||g_i||²,   d_i = <g_i, ḡ>,   n2 = ||ḡ||²

  norm test       σ² = (Σ s_i − b·n2) / (b−1)
                  b⁺ = ceil( σ² / (η² n2) )                       (eq 10)
  inner-product   v  = Σ (d_i − n2)² / (b−1)
                  b⁺ = ceil( v / (ϑ² n2²) )                       (eq 12)
  augmented       o  = Σ (s_i − d_i²/n2) / (b−1)
                  b⁺ = max(ipt, ceil( o / (ν² n2) ))              (eq 13)

(The orthogonal residuals have mean 0 because mean(g_i) = ḡ, so the
augmented variance is the mean squared residual norm.)

Two estimator paths for the statistics:
  * exact per-sample grads (vmap-of-grad) — small models, tests;
  * distributed microbatch estimator: with per-replica microbatch-mean
    grads G_j over m samples each, Var(G_j) = σ²/m, so σ² = m·Var(G_j) —
    statistics data parallelism already materializes for free.

The fused single-pass reduction over the (B, D) gradient matrix is the
``gradstats`` Pallas kernel; ``repro.kernels.gradstats.ref`` is the
pure-jnp oracle used here by default.  The microbatch estimator's J
worker pytrees never form that matrix: :func:`stats_from_microbatch_grads`
reduces them to their J x J Gram matrix, leaf by leaf.

Distributed composition (the shape-agreement protocol)
------------------------------------------------------
When the per-sample (or per-microbatch-mean) gradient rows live on
different processes, the statistics still compose *exactly*: given the
global mean direction ḡ, every test above is a function of five
additive reductions over the rows —

  (b,  Σ‖g_i‖²,  Σ<g_i, ḡ>,  Σ<g_i, ḡ>²,  b·‖ḡ‖²)

— and sums and counts all-reduce trivially.  :func:`distributed_stats`
runs the two-phase protocol: (1) all-reduce the column sum and row
count to obtain ḡ, (2) compute the local :func:`shard_moments` against
ḡ and all-reduce the five scalars.  The result equals
:func:`stats_from_matrix` on the row-concatenation of every shard (to
float-associativity tolerance), including the degenerate one-row-per-
shard case the distributed microbatch estimator produces — which is
what lets every rank derive the identical batch decision from the
identical reduced statistics (see ``repro.core.adloco.
BatchPlanProtocol``).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


class GradStats(NamedTuple):
    """Sufficient statistics for all batching tests (f32 scalars)."""
    mean_norm2: jnp.ndarray     # ||ḡ||²
    sigma2: jnp.ndarray         # trace-variance of per-sample grads
    ip_var: jnp.ndarray         # Var(<g_i, ḡ>)
    orth_var: jnp.ndarray       # Var of orthogonal residuals
    b: jnp.ndarray              # number of samples the stats came from


def stats_from_matrix(G: jnp.ndarray, *, use_kernel: bool = False) -> GradStats:
    """G: (B, D) per-sample (or per-microbatch-mean) flattened gradients."""
    if use_kernel:
        from repro.kernels.gradstats.ops import gradstats_reduce
        s, d, gbar_n2, b = gradstats_reduce(G)
    else:
        from repro.kernels.gradstats.ref import gradstats_reduce_ref
        s, d, gbar_n2, b = gradstats_reduce_ref(G)
    return _stats_from_reductions(s, d, gbar_n2, b)


def _stats_from_reductions(s, d, gbar_n2, b) -> GradStats:
    """GradStats from per-row ``s_i = ||g_i||²``, ``d_i = <g_i, ḡ>``,
    ``||ḡ||²`` and the f32 row count ``b``."""
    bm1 = jnp.maximum(b - 1.0, 1.0)
    sigma2 = (jnp.sum(s) - b * gbar_n2) / bm1
    ip_var = jnp.sum(jnp.square(d - gbar_n2)) / bm1
    orth_var = (jnp.sum(s) - jnp.sum(jnp.square(d)) /
                jnp.maximum(gbar_n2, 1e-30)) / bm1
    return GradStats(gbar_n2, jnp.maximum(sigma2, 0.0),
                     jnp.maximum(ip_var, 0.0), jnp.maximum(orth_var, 0.0), b)


@partial(jax.jit, static_argnames=("micro_size",))
def stats_from_microbatch_grads(grads, micro_size: int) -> GradStats:
    """grads: J pytrees of per-microbatch mean grads (each over
    ``micro_size`` samples).  One program reads each gradient once and
    makes no gradient-sized copy: every statistic is a function of the
    J x J Gram matrix C[j, k] = <g_j, g_k>, accumulated leaf by leaf in
    f32 (s_j = C[j, j], d_j = mean_k C[j, k], ||ḡ||² = mean C).  Each
    leaf's J² products and sums fuse into one pass over its J copies.
    They are elementwise f32 products, not a matmul, so no default
    matmul precision rounds them.  Rescales the variance estimates to
    per-sample units: Var(G_j) = σ²/m  =>  σ² = m·Var."""
    J = len(grads)
    C = jnp.zeros((J, J), jnp.float32)
    for leaves in zip(*(jax.tree.leaves(g) for g in grads)):
        g = [leaf.astype(jnp.float32) for leaf in leaves]
        C = C + jnp.stack([jnp.stack([jnp.sum(a * b) for b in g])
                           for a in g])
    st = _stats_from_reductions(jnp.diagonal(C), jnp.mean(C, axis=1),
                                jnp.mean(C), jnp.float32(J))
    return rescale_microbatch(st, micro_size)


def rescale_microbatch(st: GradStats, micro_size: int) -> GradStats:
    """Microbatch-mean rows to per-sample units (σ² = m·Var(G_j))."""
    m = jnp.float32(micro_size)
    return GradStats(st.mean_norm2, st.sigma2 * m, st.ip_var * m,
                     st.orth_var * m, st.b)


# ------------------------------------------------------------------
# distributed composition: additive sufficient statistics
# ------------------------------------------------------------------

def shard_moments(G: jnp.ndarray, gbar: jnp.ndarray) -> jnp.ndarray:
    """The five additive sufficient statistics of shard ``G`` against
    the *global* mean direction ``gbar``, packed as an f32 ``(5,)``
    vector ``[b, Σ‖g_i‖², Σ<g_i,ḡ>, Σ<g_i,ḡ>², b·‖ḡ‖²]``.

    Summing these vectors over disjoint shards yields the exact global
    reductions (every entry is a sum over rows, or the row count times
    the shared ``‖ḡ‖²``), so :func:`stats_from_moments` of the sum
    equals :func:`stats_from_matrix` of the row concatenation.
    """
    G = G.astype(jnp.float32)
    gbar = gbar.astype(jnp.float32)
    b = jnp.float32(G.shape[0])
    s = jnp.sum(jnp.square(G), axis=1)
    d = G @ gbar
    n2 = jnp.sum(jnp.square(gbar))
    return jnp.stack([b, jnp.sum(s), jnp.sum(d),
                      jnp.sum(jnp.square(d)), b * n2])


def stats_from_moments(m: jnp.ndarray) -> GradStats:
    """GradStats from summed :func:`shard_moments` (the inverse of the
    additive encoding; same guards as :func:`stats_from_matrix`)."""
    b, sum_s, sum_d, sum_d2, b_n2 = m[0], m[1], m[2], m[3], m[4]
    n2 = b_n2 / jnp.maximum(b, 1.0)
    bm1 = jnp.maximum(b - 1.0, 1.0)
    sigma2 = (sum_s - b * n2) / bm1
    ip_var = (sum_d2 - 2.0 * n2 * sum_d + b * jnp.square(n2)) / bm1
    orth_var = (sum_s - sum_d2 / jnp.maximum(n2, 1e-30)) / bm1
    return GradStats(n2, jnp.maximum(sigma2, 0.0),
                     jnp.maximum(ip_var, 0.0),
                     jnp.maximum(orth_var, 0.0), b)


def stats_phase1(G_local: jnp.ndarray) -> jnp.ndarray:
    """Phase-1 payload of the two-phase composition: the ``[colsum, b]``
    f32 vector whose SUM all-reduce yields the global mean direction.
    Split out of :func:`distributed_stats` so the runtime can dispatch
    the reduction nonblocking (piggybacked on the outer sync) and finish
    the statistics later with :func:`stats_finish`."""
    G_local = G_local.astype(jnp.float32)
    b_local = jnp.full((1,), G_local.shape[0], jnp.float32)
    return jnp.concatenate([jnp.sum(G_local, axis=0), b_local])


def stats_finish(tot: jnp.ndarray, G_local: jnp.ndarray,
                 sum_reduce: Callable, *, micro_size: int = 0) -> GradStats:
    """Finish the two-phase composition given the already-reduced
    phase-1 total ``tot`` (= sum of every shard's :func:`stats_phase1`):
    derive ḡ, reduce the five :func:`shard_moments` (phase 2), and
    rescale.  Bit-identical to the inline :func:`distributed_stats`."""
    G_local = G_local.astype(jnp.float32)
    gbar = tot[:-1] / jnp.maximum(tot[-1], 1.0)
    st = stats_from_moments(sum_reduce(shard_moments(G_local, gbar)))
    return rescale_microbatch(st, micro_size) if micro_size else st


def stats_finish_total(moments_total: jnp.ndarray, *,
                       micro_size: int = 0) -> GradStats:
    """Finish from an already-reduced phase-2 moments total (= sum of
    every shard's :func:`shard_moments`), for backends that fuse the
    phase-2 reduction onto another in-flight collective and hand the
    runtime the summed vector directly.  Bit-identical to
    :func:`stats_finish` fed the same reduction."""
    st = stats_from_moments(jnp.asarray(moments_total, jnp.float32))
    return rescale_microbatch(st, micro_size) if micro_size else st


def distributed_stats(G_local: jnp.ndarray, sum_reduce: Callable, *,
                      micro_size: int = 0) -> GradStats:
    """Two-phase exact composition of :class:`GradStats` across shards.

    ``G_local`` is this process's ``(b_local, D)`` shard of gradient
    rows; ``sum_reduce`` is an elementwise SUM all-reduce of a small
    1-D f32 vector over every participating process (identity on a
    single process).  Phase 1 reduces ``[colsum, b]`` so every rank
    holds the global mean ḡ; phase 2 reduces the five
    :func:`shard_moments`.  Both phases are deterministic collectives,
    so every rank returns bit-identical statistics — the agreement the
    batch-plan protocol builds on.  ``micro_size`` > 0 applies the
    microbatch-estimator rescale to per-sample units.
    """
    return stats_finish(sum_reduce(stats_phase1(G_local)), G_local,
                        sum_reduce, micro_size=micro_size)


def compose_shards(shards: Sequence[jnp.ndarray], *,
                   micro_size: int = 0) -> GradStats:
    """In-process reference of the distributed protocol: run the exact
    two-phase composition over a list of shards (as if each lived on
    its own process).  Property-tested against
    ``stats_from_matrix(concat(shards))``."""
    phase1s = [jnp.concatenate([jnp.sum(G.astype(jnp.float32), axis=0),
                                jnp.full((1,), G.shape[0], jnp.float32)])
               for G in shards]
    tot = sum(phase1s[1:], start=phase1s[0])
    gbar = tot[:-1] / jnp.maximum(tot[-1], 1.0)
    moments = [shard_moments(G, gbar) for G in shards]
    st = stats_from_moments(sum(moments[1:], start=moments[0]))
    return rescale_microbatch(st, micro_size) if micro_size else st


def stats_payload_bytes(n_params: int) -> float:
    """Wire payload of one stats reduction: the phase-1 ``[colsum, b]``
    f32 vector plus the five phase-2 moments — what the cluster runtime
    prices the collective at.  Note the phase-1 vector is one f32 per
    parameter, i.e. the same order as a gradient all-reduce: the
    protocol is exact, not cheap.  Under the async policy the runtime
    therefore piggybacks this payload onto the outer sync (one fused
    ``"piggyback"`` collective priced at params + stats bytes) instead
    of paying a second gradient-order all-reduce; sync keeps the
    standalone reduction so it stays bit-identical to the host loop."""
    return 4.0 * (n_params + 1 + 5)


def flatten_grads(tree) -> jnp.ndarray:
    """Pytree with leading axis B -> (B, D) f32 matrix."""
    leaves = jax.tree.leaves(tree)
    B = leaves[0].shape[0]
    return jnp.concatenate(
        [l.reshape(B, -1).astype(jnp.float32) for l in leaves], axis=1)


def per_sample_stats(loss_fn, params, batch, *, use_kernel: bool = False
                     ) -> GradStats:
    """Exact path: vmap of grad over the batch's sample axis."""
    def one(sample):
        sb = jax.tree.map(lambda x: x[None], sample)
        return jax.grad(lambda p: loss_fn(p, sb)[0])(params)

    per = jax.vmap(one)(batch)
    return stats_from_matrix(flatten_grads(per), use_kernel=use_kernel)


# ------------------------------------------------------------------
# the batch-size tests
# ------------------------------------------------------------------

def _ceil_robust(x: jnp.ndarray) -> jnp.ndarray:
    """``ceil`` with a 1e-6 relative guard band below each integer.

    The batch decision must agree across numerically different routes
    to the same statistics (in-process ``stats_from_matrix`` vs the
    two-phase ``distributed_stats`` composition differ by f32
    re-association, ~1e-7 relative).  A bare ceil flips by one whenever
    the exact ratio lands on an integer and the routes straddle it —
    which deterministic fixtures actually do — so the sim/real parity
    gates would be flaky by construction.  Shrinking x by 1e-6 relative
    before the ceil absorbs ulp-scale noise around integer ratios
    (exactly-integer x keeps its value; the flip set moves to the
    measure-1e-6 band above each integer, which noisy statistics hit
    with negligible probability)."""
    return jnp.ceil(x * (1.0 - 1e-6))


def norm_test(st: GradStats, eta: float) -> jnp.ndarray:
    """eq 10.  Returns requested batch (f32, >= 1)."""
    return _ceil_robust(
        st.sigma2 / (eta ** 2 * jnp.maximum(st.mean_norm2, 1e-30)))


def inner_product_test(st: GradStats, theta: float) -> jnp.ndarray:
    """eq 12."""
    return _ceil_robust(
        st.ip_var / (theta ** 2 * jnp.maximum(st.mean_norm2, 1e-30) ** 2))


def augmented_test(st: GradStats, theta: float, nu: float) -> jnp.ndarray:
    """eq 13: max of the inner-product test and the orthogonality test."""
    b_ipt = inner_product_test(st, theta)
    b_orth = _ceil_robust(st.orth_var /
                          (nu ** 2 * jnp.maximum(st.mean_norm2, 1e-30)))
    return jnp.maximum(b_ipt, b_orth)


def requested_batch(st: GradStats, acfg, current_b: int) -> int:
    """Apply the configured test; enforce monotone growth (paper Lemma 1:
    b_{k+1} >= b_k) and the global cap."""
    if acfg.batch_test == "norm":
        b = norm_test(st, acfg.eta)
    elif acfg.batch_test == "inner_product":
        b = inner_product_test(st, acfg.theta)
    elif acfg.batch_test == "augmented":
        b = augmented_test(st, acfg.theta, acfg.nu)
    else:
        raise ValueError(acfg.batch_test)
    with TraceAnnotation("adloco.sync.batch"):
        b = int(jax.device_get(b))      # the host waits for the test
    b = max(b, int(current_b))          # monotone non-decreasing
    return int(min(b, acfg.max_global_batch))


# ------------------------------------------------------------------
# predicted batch growth (PadaDamp; Lau et al., arXiv 2406.13936)
# ------------------------------------------------------------------

class BatchGrowthPredictor:
    """Fit the observed batch-growth trajectory and predict between
    exact estimates.

    The adaptive tests above make the requested batch track the falling
    gradient signal-to-noise ratio, which under geometric loss decay is
    (close to) exponential in the round index — so ``ln b`` is fit by
    least squares against the round number over the *exact* decisions
    observed so far, and skipped rounds read the fitted line instead of
    paying a gradient-order stats reduction (``acfg.k_correct``).

    Determinism contract: the fit is pure Python float arithmetic over
    observations that are identical on every rank by the shape-agreement
    protocol (exact decisions are reduced collectively), so every rank
    derives the identical predicted batch with **zero** collectives on
    non-correction rounds.  Predictions are conservative — the slope is
    clamped non-negative, the fitted value floored to an int, growth
    kept monotone and capped — so an over-eager fit cannot lock in a
    runaway batch between corrections (the cap and the monotone floor
    are the same policy the exact path applies).
    """

    def __init__(self, max_global_batch: int):
        self.max_global_batch = int(max_global_batch)
        self._rounds: list = []
        self._batches: list = []

    def observe(self, round_i: int, b: int) -> None:
        """Record an exact decision (correction round)."""
        round_i, b = int(round_i), int(b)
        if b < 1:
            return
        if self._rounds and round_i <= self._rounds[-1]:
            return                      # stale/duplicate fold (async)
        self._rounds.append(round_i)
        self._batches.append(b)

    @property
    def num_observations(self) -> int:
        return len(self._rounds)

    def predict(self, round_i: int, current_b: int) -> int:
        """Predicted batch for ``round_i``; falls back to ``current_b``
        until two exact observations anchor the fit."""
        if len(self._rounds) < 2:
            return int(current_b)
        xs, ys = self._rounds, [math.log(b) for b in self._batches]
        n = float(len(xs))
        mx = sum(xs) / n
        my = sum(ys) / n
        sxx = sum((x - mx) ** 2 for x in xs)
        sxy = sum((x - mx) * (y - my) for x, y in zip(xs, ys))
        slope = max(0.0, sxy / sxx) if sxx > 0.0 else 0.0
        b = int(math.floor(math.exp(my + slope * (round_i - mx)) + 1e-9))
        b = max(b, int(current_b))      # monotone non-decreasing
        return int(min(b, self.max_global_batch))
