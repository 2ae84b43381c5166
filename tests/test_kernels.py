"""Pallas kernel validation vs pure-jnp oracles (interpret mode on CPU):
shape/dtype sweeps + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# property tests ride along whenever hypothesis is installed (CI pins
# it); without it the whole module is skipped rather than erroring at
# collection
pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.gradstats.ops import gradstats_reduce
from repro.kernels.gradstats.ref import gradstats_reduce_ref
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mamba_scan.ref import mamba_scan_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------
# flash attention
# ------------------------------------------------------------------

FLASH_CASES = [
    # (B, S, H, Hk, hd, window, causal, dtype)
    (2, 256, 4, 2, 64, None, True, jnp.float32),
    (1, 128, 8, 8, 32, None, True, jnp.float32),
    (2, 256, 4, 1, 64, 100, True, jnp.float32),
    (1, 384, 6, 3, 128, 64, True, jnp.float32),
    (1, 256, 2, 2, 64, None, False, jnp.float32),     # bidirectional
    (2, 192, 4, 2, 64, None, True, jnp.bfloat16),     # bf16 + pad (192)
    (1, 96, 4, 4, 80, None, True, jnp.float32),       # odd hd, pad
]


@pytest.mark.parametrize("B,S,H,Hk,hd,window,causal,dtype", FLASH_CASES)
def test_flash_attention_allclose(B, S, H, Hk, hd, window, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hk, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hk, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


def test_flash_attention_dynamic_window_traced():
    """Window passed as a traced scalar (gemma's local/global scan)."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))

    @jax.jit
    def run(w):
        return flash_attention(q, k, v, causal=True, window=w)

    for w in (16, 64, 1 << 20):
        out = run(jnp.int32(w))
        ref = flash_attention_ref(q, k, v, causal=True, window=int(w))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 2), st.sampled_from([64, 128, 160]),
       st.sampled_from([(2, 1), (4, 2), (4, 4)]),
       st.sampled_from([32, 64]), st.integers(0, 2 ** 31 - 1))
def test_property_flash_matches_ref(B, S, heads, hd, seed):
    H, Hk = heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, Hk, hd))
    v = jax.random.normal(ks[2], (B, S, Hk, hd))
    out = flash_attention(q, k, v, causal=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------------
# mamba selective scan
# ------------------------------------------------------------------

MAMBA_CASES = [
    (2, 256, 128, 16, jnp.float32),
    (1, 200, 96, 8, jnp.float32),       # padding both axes
    (2, 64, 256, 16, jnp.float32),
    (1, 128, 128, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,di,n,dtype", MAMBA_CASES)
def test_mamba_scan_allclose(B, S, di, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    u = jax.random.normal(ks[0], (B, S, di), dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))) * 0.1
          ).astype(dtype)
    A_log = jnp.log(jnp.abs(jax.random.normal(ks[2], (di, n))) + 0.5)
    Bm = jax.random.normal(ks[3], (B, S, n), dtype)
    Cm = jax.random.normal(ks[4], (B, S, n), dtype)
    y, h = mamba_scan(u, dt, A_log, Bm, Cm)
    yr, hr = mamba_scan_ref(u, dt, A_log, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32), **_tol(dtype))


def test_mamba_scan_matches_naive_recurrence():
    """Kernel vs an explicit python-loop recurrence (ground truth)."""
    B, S, di, n = 1, 16, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    u = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))) * 0.2
    A_log = jnp.log(jnp.abs(jax.random.normal(ks[2], (di, n))) + 0.5)
    Bm = jax.random.normal(ks[3], (B, S, n))
    Cm = jax.random.normal(ks[4], (B, S, n))
    A = -np.exp(np.asarray(A_log))
    h = np.zeros((B, di, n))
    ys = []
    for t in range(S):
        a = np.exp(np.asarray(dt)[:, t, :, None] * A[None])
        h = a * h + (np.asarray(dt)[:, t] * np.asarray(u)[:, t])[..., None] \
            * np.asarray(Bm)[:, t, None, :]
        ys.append(np.einsum("bdn,bn->bd", h, np.asarray(Cm)[:, t]))
    y_ref = np.stack(ys, 1)
    y, h_last = mamba_scan(u, dt, A_log, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), h, rtol=1e-4, atol=1e-4)


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 2), st.sampled_from([32, 96, 128]),
       st.sampled_from([64, 160]), st.sampled_from([8, 16]),
       st.integers(0, 2 ** 31 - 1))
def test_property_mamba_matches_ref(B, S, di, n, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))) * 0.1
    A_log = jnp.log(jnp.abs(jax.random.normal(ks[2], (di, n))) + 0.5)
    Bm = jax.random.normal(ks[3], (B, S, n))
    Cm = jax.random.normal(ks[4], (B, S, n))
    y, h = mamba_scan(u, dt, A_log, Bm, Cm)
    yr, hr = mamba_scan_ref(u, dt, A_log, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------
# gradstats
# ------------------------------------------------------------------

@pytest.mark.parametrize("B,D,dtype", [
    (16, 1024, jnp.float32), (7, 300, jnp.float32), (64, 4096, jnp.float32),
    (3, 130, jnp.float32), (32, 2048, jnp.bfloat16),
])
def test_gradstats_allclose(B, D, dtype):
    G = jax.random.normal(jax.random.PRNGKey(2), (B, D), dtype)
    s, d, n2, b = gradstats_reduce(G)
    sr, dr, n2r, br = gradstats_reduce_ref(G)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), **tol)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), **tol)
    np.testing.assert_allclose(float(n2), float(n2r), **tol)
    assert float(b) == B


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 48), st.integers(16, 700),
       st.integers(0, 2 ** 31 - 1))
def test_property_gradstats_matches_ref(B, D, seed):
    G = jax.random.normal(jax.random.PRNGKey(seed), (B, D)) * 3
    s, d, n2, b = gradstats_reduce(G)
    sr, dr, n2r, _ = gradstats_reduce_ref(G)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(n2), float(n2r), rtol=1e-4, atol=1e-5)


#: the shapes the distributed estimator actually feeds the kernel:
#: B = worker/probe counts (rarely a power of two, B=1 when a single
#: microbatch-mean row is the local shard), D = flattened param dims
#: (never a multiple of the 512-lane tile for toy models)
GRADSTATS_EDGE_CASES = [
    (1, 16, jnp.float32),     # single-row shard (microbatch estimator)
    (1, 513, jnp.float32),
    (2, 16, jnp.float32),     # the 2-worker fixture, tiny D
    (5, 193, jnp.float32),
    (9, 515, jnp.float32),
    (13, 1027, jnp.float32),
    (3, 130, jnp.bfloat16),   # bf16 on non-pow2 both axes
    (5, 193, jnp.bfloat16),
    (17, 700, jnp.bfloat16),
    (31, 1000, jnp.bfloat16),
]


@pytest.mark.parametrize("B,D,dtype", GRADSTATS_EDGE_CASES)
def test_gradstats_kernel_nonpow2_and_dtypes(B, D, dtype):
    """Kernel == oracle on exactly the ragged shapes and dtypes the
    distributed estimator produces (zero-padding must stay exact)."""
    G = jax.random.normal(jax.random.PRNGKey(B * 1000 + D), (B, D),
                          dtype) * 2 + jnp.asarray(0.3, dtype)
    s, d, n2, b = gradstats_reduce(G)
    sr, dr, n2r, br = gradstats_reduce_ref(G)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), **tol)
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), **tol)
    np.testing.assert_allclose(float(n2), float(n2r), **tol)
    assert float(b) == float(br) == B
    assert s.shape == d.shape == (B,)
    # outputs are f32 accumulators regardless of the input dtype
    assert s.dtype == d.dtype == jnp.float32


@pytest.mark.parametrize("B,D,dtype", GRADSTATS_EDGE_CASES)
def test_stats_from_matrix_kernel_path_matches_ref_path(B, D, dtype):
    """use_kernel=True must be a drop-in for the derived GradStats —
    the contract that lets TrainerRound route the adaptive estimators
    through the fused kernel (acfg.stats_use_kernel)."""
    from repro.core import batching

    G = jax.random.normal(jax.random.PRNGKey(B + 7 * D), (B, D),
                          dtype) * 3
    a = batching.stats_from_matrix(G, use_kernel=False)
    k = batching.stats_from_matrix(G, use_kernel=True)
    scale = max(abs(float(v)) for v in a) + 1e-6
    rel = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    for name, x, y in zip(batching.GradStats._fields, a, k):
        assert abs(float(x) - float(y)) <= \
            rel * max(abs(float(x)), abs(float(y))) + rel * scale, \
            (name, float(x), float(y))


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False),
                                                ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, platform, interpret):
    """Interpret mode on the CPU, compiled kernels on the TPU, and an
    error anywhere else: no platform silently falls back."""
    from repro import kernels
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            kernels.interpret_mode()
    else:
        assert kernels.interpret_mode() is interpret
