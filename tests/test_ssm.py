"""Mamba-2 SSD: chunked dual form vs naive recurrence oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.ssm import segsum, ssd_chunked


def naive_ssd(x, dt, a, b_mat, c_mat):
    """Token-by-token linear recurrence (the definitionally-correct form).
    x: [B,S,H,P], dt: [B,S,H], a: [H], b/c: [B,S,G,N] (G divides H)."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    bm = np.repeat(np.asarray(b_mat), rep, axis=2)
    cm = np.repeat(np.asarray(c_mat), rep, axis=2)
    xs, dts = np.asarray(x), np.asarray(dt)
    an = np.asarray(a)
    state = np.zeros((bsz, h, p, n))
    ys = np.zeros((bsz, s, h, p))
    for t in range(s):
        da = np.exp(dts[:, t] * an)                      # [B,H]
        state = state * da[..., None, None] + np.einsum(
            "bh,bhp,bhn->bhpn", dts[:, t], xs[:, t], bm[:, t])
        ys[:, t] = np.einsum("bhpn,bhn->bhp", state, cm[:, t])
    return ys, state


def _inputs(bsz, s, h, p, g, n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(bsz, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(bsz, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(bsz, s, g, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(bsz, s, g, n)), jnp.float32)
    return x, dt, a, b, c


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_matches_naive(chunk):
    x, dt, a, b, c = _inputs(2, 16, 4, 8, 2, 6, seed=0)
    y, final = ssd_chunked(x, dt, a, b, c, chunk=chunk)
    y_ref, final_ref = naive_ssd(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), final_ref, rtol=1e-4,
                               atol=1e-4)


def test_chunk_size_invariance():
    x, dt, a, b, c = _inputs(1, 32, 2, 4, 1, 4, seed=1)
    y8, f8 = ssd_chunked(x, dt, a, b, c, chunk=8)
    y32, f32_ = ssd_chunked(x, dt, a, b, c, chunk=32)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(f8), np.asarray(f32_), rtol=1e-4,
                               atol=1e-4)


def test_initial_state_continuation():
    """SSD(x₁∥x₂) == SSD(x₂ | state=SSD(x₁))."""
    x, dt, a, b, c = _inputs(1, 16, 2, 4, 1, 4, seed=2)
    y_full, f_full = ssd_chunked(x, dt, a, b, c, chunk=4)
    y1, f1 = ssd_chunked(x[:, :8], dt[:, :8], a, b[:, :8], c[:, :8], chunk=4)
    y2, f2 = ssd_chunked(x[:, 8:], dt[:, 8:], a, b[:, 8:], c[:, 8:],
                         chunk=4, initial_state=f1)
    np.testing.assert_allclose(np.asarray(y_full[:, 8:]), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(f_full), np.asarray(f2),
                               rtol=1e-4, atol=1e-4)


def test_segsum():
    x = jnp.asarray([1.0, 2.0, 3.0])
    out = np.asarray(segsum(x))
    assert out[0, 0] == 0.0
    assert out[1, 0] == 2.0
    assert out[2, 0] == 5.0
    assert out[2, 1] == 3.0
    assert np.isneginf(out[0, 1])


@settings(max_examples=10, deadline=None)
@given(s=st.integers(2, 24), chunk=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 99))
def test_hypothesis_chunked_vs_naive(s, chunk, seed):
    s = (s // chunk) * chunk or chunk
    x, dt, a, b, c = _inputs(1, s, 2, 4, 1, 4, seed=seed)
    y, f = ssd_chunked(x, dt, a, b, c, chunk=chunk)
    y_ref, f_ref = naive_ssd(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(f), f_ref, rtol=2e-4, atol=2e-4)
