"""Unit + property tests for the adaptive batching tests (paper eqs
10/12/13) and their statistics estimators."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# property tests ride along whenever hypothesis is installed (CI pins
# it); without it the whole module is skipped rather than erroring at
# collection
pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import AdLoCoConfig
from repro.core import batching


def _manual_stats(G):
    """Straight-from-the-paper reference (numpy, explicit loops in math)."""
    G = np.asarray(G, np.float64)
    b, D = G.shape
    gbar = G.mean(0)
    n2 = float(gbar @ gbar)
    sigma2 = float(np.sum((G - gbar) ** 2) / max(b - 1, 1))
    d = G @ gbar
    ip_var = float(np.sum((d - n2) ** 2) / max(b - 1, 1))
    orth = G - np.outer(d / max(n2, 1e-30), gbar)
    orth_var = float(np.sum(orth ** 2) / max(b - 1, 1))
    return n2, sigma2, ip_var, orth_var


def test_stats_match_manual():
    rng = np.random.default_rng(0)
    G = rng.standard_normal((24, 64)) + 0.5
    st_ = batching.stats_from_matrix(jnp.asarray(G, jnp.float32))
    n2, sigma2, ip_var, orth_var = _manual_stats(G)
    assert np.isclose(float(st_.mean_norm2), n2, rtol=1e-4)
    assert np.isclose(float(st_.sigma2), sigma2, rtol=1e-4)
    assert np.isclose(float(st_.ip_var), ip_var, rtol=1e-3)
    assert np.isclose(float(st_.orth_var), orth_var, rtol=1e-3)


def test_norm_test_closed_form():
    """σ² and ‖ḡ‖² chosen exactly -> b⁺ = ceil(σ²/(η²‖ḡ‖²))."""
    st_ = batching.GradStats(
        mean_norm2=jnp.float32(4.0), sigma2=jnp.float32(9.0),
        ip_var=jnp.float32(0.0), orth_var=jnp.float32(0.0),
        b=jnp.float32(8))
    # eq 10 with eta=0.5: ceil(9 / (0.25*4)) = 9
    assert int(batching.norm_test(st_, 0.5)) == 9


def test_inner_product_test_closed_form():
    st_ = batching.GradStats(
        mean_norm2=jnp.float32(2.0), sigma2=jnp.float32(0.0),
        ip_var=jnp.float32(32.0), orth_var=jnp.float32(0.0),
        b=jnp.float32(8))
    # eq 12 with theta=1: ceil(32 / (1*4)) = 8
    assert int(batching.inner_product_test(st_, 1.0)) == 8


def test_augmented_is_max_of_tests():
    st_ = batching.GradStats(
        mean_norm2=jnp.float32(1.0), sigma2=jnp.float32(0.0),
        ip_var=jnp.float32(10.0), orth_var=jnp.float32(100.0),
        b=jnp.float32(8))
    b_ipt = batching.inner_product_test(st_, 0.5)
    b_aug = batching.augmented_test(st_, 0.5, 0.5)
    assert float(b_aug) >= float(b_ipt)
    # orth part: ceil(100 / (0.25 * 1)) = 400 dominates
    assert int(b_aug) == 400


def test_zero_variance_requests_batch_one():
    """Identical per-sample gradients -> sigma2 = 0 -> b+ = 0-ceil -> 1."""
    G = jnp.ones((16, 32))
    st_ = batching.stats_from_matrix(G)
    assert float(st_.sigma2) < 1e-6
    assert int(batching.norm_test(st_, 0.8)) <= 1


def test_monotone_growth_enforced():
    acfg = AdLoCoConfig(eta=0.8)
    st_ = batching.GradStats(jnp.float32(100.0), jnp.float32(1.0),
                             jnp.float32(0.0), jnp.float32(0.0),
                             jnp.float32(4))
    # tiny request, but current_b=32 -> stays 32
    assert batching.requested_batch(st_, acfg, 32) == 32


def test_cap_enforced():
    acfg = AdLoCoConfig(eta=0.01, max_global_batch=128)
    st_ = batching.GradStats(jnp.float32(1e-6), jnp.float32(1e3),
                             jnp.float32(0.0), jnp.float32(0.0),
                             jnp.float32(4))
    assert batching.requested_batch(st_, acfg, 1) == 128


def test_per_sample_stats_match_matrix_path():
    """vmap-of-grad path == hand-built per-sample gradient matrix."""
    def loss_fn(params, batch):
        r = batch["A"] @ params["x"] - batch["y"]
        return 0.5 * jnp.mean(jnp.square(r)), {}

    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.standard_normal((12, 8)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(12), jnp.float32)
    params = {"x": jnp.asarray(rng.standard_normal(8), jnp.float32)}
    st_ = batching.per_sample_stats(loss_fn, params, {"A": A, "y": y})
    # manual per-sample grads: g_i = a_i (a_i.x - y_i)
    G = np.asarray(A) * (np.asarray(A @ params["x"] - y))[:, None]
    n2, sigma2, _, _ = _manual_stats(G)
    assert np.isclose(float(st_.mean_norm2), n2, rtol=1e-4)
    assert np.isclose(float(st_.sigma2), sigma2, rtol=1e-4)


def test_microbatch_estimator_scaling():
    """Var of microbatch means ~ sigma^2 / m: estimator must rescale."""
    rng = np.random.default_rng(2)
    D, m, J = 16, 8, 64
    per_sample = rng.standard_normal((J * m, D)) * 3.0 + 1.0
    micro_means = per_sample.reshape(J, m, D).mean(1)
    st_micro = batching.stats_from_microbatch_grads(
        [{"g": jnp.asarray(row, jnp.float32)} for row in micro_means],
        micro_size=m)
    st_full = batching.stats_from_matrix(
        jnp.asarray(per_sample, jnp.float32))
    # rescaled micro sigma2 estimates the per-sample sigma2 (within 25%)
    assert float(st_micro.sigma2) == pytest.approx(
        float(st_full.sigma2), rel=0.25)


def _worker_grads(seed, J, dtype):
    """J gradient pytrees that share a mean direction, as workers' do:
    an embedding, a stacked (L, ...) layer leaf with its norms, a bias."""
    rng = np.random.default_rng(seed)
    shapes = {"embed": (40, 16), "layers": {"w": (3, 8, 12), "norm": (3, 8)},
              "bias": (7,)}
    noise = rng.uniform(0.2, 3.0)
    mean = jax.tree.map(lambda s: rng.standard_normal(s), shapes,
                        is_leaf=lambda s: isinstance(s, tuple))
    return [jax.tree.map(
        lambda m: jnp.asarray(m + noise * rng.standard_normal(m.shape),
                              dtype), mean) for _ in range(J)]


@jax.jit
def _matrix_route(grads, micro_size):
    G = batching.flatten_grads(jax.tree.map(lambda *g: jnp.stack(g), *grads))
    return batching.rescale_microbatch(batching.stats_from_matrix(G),
                                       micro_size)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("J", [2, 3, 5])
def test_microbatch_gram_route_matches_matrix_route(J, dtype):
    """The Gram-matrix statistics agree with the (J, D) matrix route on
    pytrees of several leaves, a stacked layer leaf among them."""
    grads = _worker_grads(J, J, dtype)
    got = batching.stats_from_microbatch_grads(grads, 4)
    want = _matrix_route(grads, 4)
    for name, rel in [("mean_norm2", 1e-5), ("sigma2", 1e-5),
                      ("orth_var", 1e-5), ("ip_var", 1e-4)]:
        assert float(getattr(got, name)) == pytest.approx(
            float(getattr(want, name)), rel=rel), name
    assert float(got.b) == J


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("test", ["norm", "inner_product", "augmented"])
@pytest.mark.parametrize("J", [2, 3, 5])
def test_microbatch_gram_route_requests_the_same_batch(J, test, dtype):
    """Both routes give the same requested batch on 50 seeds."""
    acfg = AdLoCoConfig(batch_test=test, eta=0.5, theta=0.01, nu=0.5,
                        max_global_batch=1 << 30)
    for seed in range(50):
        grads = _worker_grads(seed, J, dtype)
        got = batching.stats_from_microbatch_grads(grads, 4)
        want = _matrix_route(grads, 4)
        assert batching.requested_batch(got, acfg, 1) == \
            batching.requested_batch(want, acfg, 1), seed


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 40), st.integers(1, 96), st.integers(0, 2 ** 31 - 1))
def test_property_stats_nonnegative_any_matrix(b, dim, seed):
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.standard_normal((b, dim)) * 10, jnp.float32)
    s = batching.stats_from_matrix(G)
    assert float(s.sigma2) >= 0
    assert float(s.ip_var) >= 0
    assert float(s.orth_var) >= -1e-3
    assert float(s.mean_norm2) >= 0


@settings(max_examples=20, deadline=None)
@given(st.floats(0.1, 2.0), st.integers(0, 2 ** 31 - 1))
def test_property_norm_test_monotone_in_eta(eta, seed):
    """Smaller η (stricter test) must never request a smaller batch."""
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    s = batching.stats_from_matrix(G)
    b1 = float(batching.norm_test(s, eta))
    b2 = float(batching.norm_test(s, eta / 2))
    assert b2 >= b1


# ------------------------------------------------------------------
# distributed composition (the stats all-reduce law) — randomized
# properties; the deterministic fixtures (which must run even without
# hypothesis) live in tests/test_batching_dist.py along with the
# shared helpers
# ------------------------------------------------------------------

from tests.test_batching_dist import (_assert_stats_close,  # noqa: E402
                                      _split_shards)


@settings(max_examples=30, deadline=None)
@given(st.integers(3, 40), st.integers(1, 96),
       st.lists(st.integers(0, 10 ** 6), min_size=1, max_size=6),
       st.integers(0, 2 ** 31 - 1))
def test_property_sharded_stats_compose_exactly(b, dim, cuts, seed):
    """The composition law behind the distributed protocol: GradStats
    all-reduced across k disjoint shards == stats_from_matrix on the
    row-concatenation (to f32 tolerance), for every shard split —
    the five sufficient statistics are additive."""
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.standard_normal((b, dim)) * 3 + 0.7, jnp.float32)
    full = batching.stats_from_matrix(G)
    comp = batching.compose_shards(_split_shards(G, cuts))
    _assert_stats_close(full, comp)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 24), st.integers(1, 64),
       st.integers(0, 2 ** 31 - 1))
def test_property_one_row_per_shard_composes(b, dim, seed):
    """The b=1-per-shard edge (each worker contributes exactly its
    microbatch-mean grad — the distributed microbatch estimator): the
    per-shard statistics are degenerate but the additive moments still
    compose to the full-matrix GradStats."""
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.standard_normal((b, dim)) * 2 - 0.5, jnp.float32)
    full = batching.stats_from_matrix(G)
    comp = batching.compose_shards([G[i:i + 1] for i in range(b)])
    _assert_stats_close(full, comp)


@settings(max_examples=15, deadline=None)
@given(st.integers(4, 32), st.integers(2, 48),
       st.lists(st.integers(0, 10 ** 6), min_size=1, max_size=4),
       st.floats(0.2, 1.5), st.integers(0, 2 ** 31 - 1))
def test_property_all_three_tests_agree_on_composed_stats(
        b, dim, cuts, eta, seed):
    """All three batch tests (norm / inner-product / augmented) must
    request the same batch from the composed statistics as from the
    concatenated matrix — the decision, not just the moments, is what
    every rank must agree on."""
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.standard_normal((b, dim)) * 3 + 1.0, jnp.float32)
    full = batching.stats_from_matrix(G)
    comp = batching.compose_shards(_split_shards(G, cuts))
    for test in (lambda s: batching.norm_test(s, eta),
                 lambda s: batching.inner_product_test(s, eta),
                 lambda s: batching.augmented_test(s, eta, eta)):
        bf, bc = float(test(full)), float(test(comp))
        # ceil() can disagree by one count right at an integer boundary
        assert abs(bf - bc) <= 1.0 + 1e-2 * max(bf, bc), (bf, bc)


