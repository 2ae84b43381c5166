"""Deterministic (no-hypothesis) tests for the distributed stats
composition protocol — kept out of test_batching.py so its module-level
``pytest.importorskip("hypothesis")`` cannot silently skip the core
composition-law coverage on environments without hypothesis.  The
randomized property tests over the same law live in test_batching.py
and ride along wherever hypothesis is installed (CI pins it)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batching


def _split_shards(G, cuts):
    """Split the row axis at the (sorted, deduped) cut points."""
    edges = sorted({c % (G.shape[0] - 1) + 1 for c in cuts})
    return jnp.split(G, edges, axis=0)


def _assert_stats_close(a, b, rel=5e-3):
    # per-field relative tolerance plus an absolute floor scaled to the
    # largest statistic: the variance fields subtract near-equal f32
    # sums (catastrophic cancellation), so a near-zero orth_var carries
    # error proportional to Σ‖g‖², not to itself
    scale = max(abs(float(v)) for v in a)
    for name, x, y in zip(batching.GradStats._fields, a, b):
        tol = rel * max(abs(float(x)), abs(float(y))) + 1e-5 * scale
        assert abs(float(x) - float(y)) <= tol, (name, float(x), float(y))


def test_sharded_stats_compose_to_concatenated_matrix():
    """The composition law on fixed fixtures: uneven shards and the
    one-row-per-shard (microbatch) edge both reproduce
    stats_from_matrix on the row concatenation."""
    rng = np.random.default_rng(0)
    G = jnp.asarray(rng.standard_normal((13, 23)) * 3 + 1, jnp.float32)
    full = batching.stats_from_matrix(G)
    _assert_stats_close(full, batching.compose_shards(
        [G[:4], G[4:5], G[5:]]))
    _assert_stats_close(full, batching.compose_shards(
        [G[i:i + 1] for i in range(G.shape[0])]))


def test_distributed_stats_identity_reduce_is_single_shard():
    """With the identity SUM reduce (single process) the protocol must
    reproduce stats_from_matrix on the local shard."""
    rng = np.random.default_rng(7)
    G = jnp.asarray(rng.standard_normal((12, 20)), jnp.float32)
    st_ = batching.distributed_stats(G, lambda v: v)
    _assert_stats_close(batching.stats_from_matrix(G), st_, rel=1e-4)


def test_distributed_stats_microbatch_rescale_matches_estimator():
    """micro_size rescale through the protocol == the in-process
    microbatch estimator on the stacked rows."""
    rng = np.random.default_rng(8)
    rows = [jnp.asarray(rng.standard_normal(24), jnp.float32)
            for _ in range(4)]
    st_in = batching.stats_from_microbatch_grads([{"g": r} for r in rows],
                                                 micro_size=8)
    # emulate 4 processes: each contributes one row, reduce = in-process
    # sums over the shard list
    shards = [r[None] for r in rows]
    st_comp = batching.compose_shards(shards, micro_size=8)
    _assert_stats_close(st_in, st_comp, rel=1e-4)


def test_stats_payload_bytes_prices_both_phases():
    """The priced payload is the phase-1 [colsum, count] vector plus
    the five phase-2 scalars: one f32 per parameter plus six — the
    same order as a gradient all-reduce (the runtime must not price
    the stats agreement as free)."""
    assert batching.stats_payload_bytes(16) == 4.0 * (16 + 6)
    assert batching.stats_payload_bytes(0) == 24.0


def test_growth_predictor_warmup_and_exact_exponential_fit():
    """Fewer than two exact observations cannot anchor a fit — the
    predictor must fall back to the current batch — and once the
    observations lie on an exponential the extrapolation is exact."""
    pred = batching.BatchGrowthPredictor(max_global_batch=512)
    assert pred.predict(3, 7) == 7
    pred.observe(1, 4)
    assert pred.predict(3, 7) == 7          # one point is still warmup
    pred.observe(2, 8)
    pred.observe(3, 16)
    # ln b is exactly linear in the round, so the fitted line passes
    # through every future doubling (the 1e-9 guard absorbs float fuzz)
    assert pred.predict(5, 16) == 64
    assert pred.predict(6, 16) == 128


def test_growth_predictor_monotone_capped_and_slope_clamped():
    """Conservatism contract: predictions never shrink the batch, never
    exceed the global cap, and a decreasing observation sequence clamps
    the slope to zero (round-independent prediction) instead of
    extrapolating the batch downward."""
    pred = batching.BatchGrowthPredictor(max_global_batch=64)
    pred.observe(1, 4)
    pred.observe(2, 8)
    assert pred.predict(20, 8) == 64        # capped, not 2 ** 21
    assert pred.predict(3, 60) >= 60        # monotone vs current batch
    down = batching.BatchGrowthPredictor(max_global_batch=64)
    down.observe(1, 16)
    down.observe(2, 8)
    # clamped slope: the fit is flat, so prediction cannot depend on
    # how far ahead the skipped round is
    assert down.predict(3, 8) == down.predict(30, 8)
    assert down.predict(3, 8) >= 8


def test_growth_predictor_ignores_stale_async_observations():
    """Async folds can replay an older round's decision after a newer
    one; the predictor must drop stale/duplicate observations so every
    rank fits the same ordered series."""
    pred = batching.BatchGrowthPredictor(max_global_batch=512)
    pred.observe(4, 32)
    pred.observe(4, 48)                     # duplicate round: dropped
    pred.observe(2, 8)                      # stale round: dropped
    assert pred.num_observations == 1
    pred.observe(7, 64)
    ref = batching.BatchGrowthPredictor(max_global_batch=512)
    ref.observe(4, 32)
    ref.observe(7, 64)
    assert pred.predict(9, 64) == ref.predict(9, 64)


def test_decision_agreement_under_prediction():
    """The k_correct protocol across simulated ranks: correction rounds
    decide once from the composed (all-reduced) shard statistics, and
    the skipped rounds read each rank's *local* predictor — yet every
    rank must derive the identical batch trajectory, with the stats
    composition running only on the corrections."""
    rng = np.random.default_rng(5)
    ranks, T, k_correct, cap = 4, 10, 3, 512
    preds = [batching.BatchGrowthPredictor(cap) for _ in range(ranks)]
    b = [4] * ranks
    traj = [[] for _ in range(ranks)]
    compositions = 0
    for r in range(1, T + 1):
        if (r - 1) % k_correct == 0:
            # exact: one shard per rank, one composition standing in for
            # the all-reduce (its result is identical on every rank)
            shards = [jnp.asarray(rng.standard_normal((3, 16)) * 2.0,
                                  jnp.float32) for _ in range(ranks)]
            st_ = batching.compose_shards(shards)
            compositions += 1
            req = int(batching.norm_test(st_, 0.5))
            for k in range(ranks):
                b[k] = min(max(b[k], req), cap)
                preds[k].observe(r, b[k])
        else:
            for k in range(ranks):
                b[k] = preds[k].predict(r, b[k])
        for k in range(ranks):
            traj[k].append(b[k])
    assert all(t == traj[0] for t in traj)
    corrections = [r for r in range(1, T + 1) if (r - 1) % k_correct == 0]
    assert compositions == len(corrections) < T


def test_periodic_correction_pins_predicted_arm_to_exact():
    """Exact-every-round vs k_correct=3 over the same stats schedule
    (requested batch doubles per round): after the second correction
    anchors the fit, the predicted arm reproduces the exact trajectory
    on every round — including the capped tail — while paying stats
    evaluations only on corrections."""
    eta, cap, T, k_correct = 0.5, 512, 9, 3

    def stats_at(r):
        # eq-10 ratio = sigma2 / (eta^2 * mean_norm2) = 9 * 2^(r-1)
        return batching.GradStats(
            mean_norm2=jnp.float32(4.0 / 2 ** (r - 1)),
            sigma2=jnp.float32(9.0), ip_var=jnp.float32(0.0),
            orth_var=jnp.float32(0.0), b=jnp.float32(8))

    exact, pred_arm = 4, 4
    pred = batching.BatchGrowthPredictor(cap)
    evals = 0
    exact_traj, pred_traj = [], []
    for r in range(1, T + 1):
        exact = min(max(exact, int(batching.norm_test(stats_at(r), eta))),
                    cap)
        if (r - 1) % k_correct == 0:
            evals += 1
            pred_arm = min(max(pred_arm,
                               int(batching.norm_test(stats_at(r), eta))),
                           cap)
            pred.observe(r, pred_arm)
        else:
            pred_arm = pred.predict(r, pred_arm)
        exact_traj.append(exact)
        pred_traj.append(pred_arm)
    corrections = [r for r in range(1, T + 1) if (r - 1) % k_correct == 0]
    for r in corrections:
        assert pred_traj[r - 1] == exact_traj[r - 1]
    # once two corrections anchor the fit, parity is per-round exact
    second = corrections[1]
    assert pred_traj[second - 1:] == exact_traj[second - 1:]
    assert exact_traj[-1] == cap            # the schedule reaches the cap
    assert evals == len(corrections) < T


def test_batch_tests_stable_at_integer_ratios():
    """The epsilon-guarded ceil: statistics whose test ratio lands
    exactly on an integer must request exactly that integer, and a
    sub-ulp perturbation (the in-process vs two-phase route noise)
    must not flip the decision."""
    st_ = batching.GradStats(
        mean_norm2=jnp.float32(4.0), sigma2=jnp.float32(9.0),
        ip_var=jnp.float32(0.0), orth_var=jnp.float32(0.0),
        b=jnp.float32(8))
    # eq 10 with eta=0.5: the exact ratio is 9.0
    assert int(batching.norm_test(st_, 0.5)) == 9
    bumped = st_._replace(sigma2=jnp.float32(np.nextafter(
        np.float32(9.0), np.float32(10.0))))
    assert int(batching.norm_test(bumped, 0.5)) == 9
