"""Per-kernel allclose sweeps (interpret mode) against the ref.py oracles,
over shapes and dtypes, plus hypothesis property checks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import synthetic
from repro.kernels.attention import ops as attn_ops
from repro.kernels.decode import ops as dec_ops
from repro.kernels.igd_fused import kernel as igd_kernel
from repro.kernels.igd_fused import ops as igd_ops
from repro.kernels.igd_fused import ref as igd_ref

RNG = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# igd_fused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss", ["lr", "svm", "lsq"])
@pytest.mark.parametrize("n,d", [(256, 128), (512, 200), (256, 64)])
def test_igd_fold_matches_ref(loss, n, d):
    x = jax.random.normal(RNG, (n, d), jnp.float32) / jnp.sqrt(d)
    y = jnp.sign(jax.random.normal(jax.random.fold_in(RNG, 1), (n,)))
    alpha = 0.1 / (1.0 + jnp.arange(n, dtype=jnp.float32) / n)
    w0 = 0.01 * jax.random.normal(jax.random.fold_in(RNG, 2), (d,))
    wk = igd_ops.igd_fold(x, y, alpha, w0, loss=loss)
    wr = igd_ref.igd_fold_ref(x, y, alpha, w0, loss=loss)
    np.testing.assert_allclose(np.asarray(wk), np.asarray(wr),
                               rtol=2e-4, atol=2e-5)


def _igd_inputs(n, d, seed=3):
    rng = jax.random.PRNGKey(seed)
    x = jax.random.normal(rng, (n, d), jnp.float32) / jnp.sqrt(d)
    y = jnp.sign(jax.random.normal(jax.random.fold_in(rng, 1), (n,)))
    alpha = 0.1 / (1.0 + jnp.arange(n, dtype=jnp.float32) / n)
    w0 = 0.01 * jax.random.normal(jax.random.fold_in(rng, 2), (d,))
    return x, y, alpha, w0


# the padding matrix: every ragged combination the tiler must absorb
# (N % TILE != 0, D % 128 != 0, and both at once)
_PAD_SHAPES = [(300, 72), (513, 200), (256, 130), (512, 128)]


@pytest.mark.parametrize("loss", ["lr", "svm", "lsq"])
@pytest.mark.parametrize("n,d", _PAD_SHAPES)
def test_igd_fold_padding_matrix(loss, n, d):
    """Parity matrix vs the jnp oracle across losses × padding shapes."""
    x, y, alpha, w0 = _igd_inputs(n, d)
    wk = igd_ops.igd_fold(x, y, alpha, w0, loss=loss)
    wr = igd_ref.igd_fold_ref(x, y, alpha, w0, loss=loss)
    assert wk.shape == (d,)
    np.testing.assert_allclose(np.asarray(wk), np.asarray(wr),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("loss", ["lr", "svm", "lsq"])
@pytest.mark.parametrize("n,d", _PAD_SHAPES)
def test_igd_pad_rows_are_bitwise_noops(loss, n, d):
    """The regression the ragged tail relies on: _pad's rows carry
    alpha=0, so the transition w - alpha*c*x leaves w untouched EXACTLY
    (0.0 * anything-finite = 0.0; w - 0 = w bitwise). For lsq in
    particular the pad's margin is w·x with y=0 — nonzero! — and only
    the zero alpha kills the step. Row padding is pinned bit-equal, not
    allclose: a future pad scheme that merely approximates the no-op
    must fail. Column padding appends zero features: they never move off
    their zero init (exact), but the longer margin reduction adds in a
    different order, so the real columns agree to fp32 tolerance."""
    x, y, alpha, w0 = _igd_inputs(n, d)
    xp, yp, ap, wp, d_out = igd_ops._pad(x, y, alpha, w0)
    assert d_out == d
    assert xp.shape[0] % igd_kernel.TILE == 0 and xp.shape[1] % 128 == 0
    ref_raw = igd_ref.igd_fold_ref(x, y, alpha, w0, loss=loss)
    # rows padded, columns as stored: bitwise the unpadded fold
    ref_rows = igd_ref.igd_fold_ref(xp[:, :d], yp, ap, wp[:d], loss=loss)
    assert np.array_equal(np.asarray(ref_rows), np.asarray(ref_raw))
    # rows and columns padded: fp32 fold tolerance on the real columns
    ref_padded = igd_ref.igd_fold_ref(xp, yp, ap, wp, loss=loss)
    np.testing.assert_allclose(np.asarray(ref_padded[:d]),
                               np.asarray(ref_raw), rtol=1e-5, atol=1e-6)
    # and the padded tail of the model never moves off its zero init
    assert np.array_equal(
        np.asarray(ref_padded[d:]), np.zeros(xp.shape[1] - d, np.float32)
    )


@pytest.mark.parametrize("op", [igd_ops.igd_fold])
def test_igd_escape_hatch_matches_kernel(op):
    """use_kernel=False is the oracle path: it must accept the same
    ragged shapes the kernel accepts and agree with the kernel within
    fold tolerance."""
    x, y, alpha, w0 = _igd_inputs(300, 72)
    wk = op(x, y, alpha, w0, loss="lsq", use_kernel=True)
    wh = op(x, y, alpha, w0, loss="lsq", use_kernel=False)
    assert wh.shape == (72,)
    np.testing.assert_allclose(np.asarray(wk), np.asarray(wh),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("loss", ["svm", "lr"])
def test_igd_fold_forest_shaped_parity(loss):
    """The serial kernel takes its margins from a block's Gram matrix but
    still updates the model row by row, in row order, as the sequential
    fold does. On a Forest-shaped table (54 features, a planted separator
    with overlapping classes, shuffled, alpha0 = 0.2 diminishing) the
    margins differ from the fold's only by rounding, which moves no
    hinge: the svm model must come out bit for bit the fold's. A model
    accumulated any other way (once per block, say) flips hinges near
    margin 1 and fails here. logreg's smooth loss carries the margins'
    rounding into the model: 1e-5 of each entry, or of the model's
    largest entry for entries near zero (where a row-at-a-time fold in
    the kernel's lane order differs from the reference by 2.7e-5 of the
    entry)."""
    n, d = 4096, 54
    rng = jax.random.PRNGKey(7)
    data = synthetic.dense_classification(rng, n, d, margin=0.05, noise=0.5)
    perm = jax.random.permutation(jax.random.fold_in(rng, 3), n)
    x, y = data["x"][perm], data["y"][perm]
    alpha = 0.2 / (1.0 + jnp.arange(n, dtype=jnp.float32) / n)
    w0 = jnp.zeros((d,), jnp.float32)
    wk = np.asarray(igd_ops.igd_fold(x, y, alpha, w0, loss=loss))
    wr = np.asarray(igd_ref.igd_fold_ref(x, y, alpha, w0, loss=loss))
    if loss == "svm":
        assert np.array_equal(wk, wr)
    else:
        np.testing.assert_allclose(wk, wr, rtol=1e-5,
                                   atol=1e-5 * np.max(np.abs(wr)))


def test_igd_svm_hinge_follows_the_sequential_margin():
    """Where a block margin's rounding and the sequential fold's margin
    fall on different sides of the hinge, the fold's decision holds. Two
    entries of w, 2**23 and -2**23, cancel in every row's margin, and
    each step adds 0.5 to both: the first is absorbed (the spacing of
    floats there is 1), the second is not. So after one update the fold
    reads a margin of 0.5 and steps again, where the block's Gram
    correction reads 1.0 and would not. Every margin here is exact in
    any order of addition, so the fold's answer is unambiguous, and the
    kernel's must equal it bit for bit."""
    n, d = 32, 54
    x = jnp.zeros((n, d), jnp.float32).at[:, :2].set(1.0)
    y = jnp.ones((n,), jnp.float32)
    alpha = jnp.full((n,), 0.5, jnp.float32)
    w0 = jnp.zeros((d,), jnp.float32).at[0].set(2.0**23).at[1].set(-2.0**23)
    wk = igd_ops.igd_fold(x, y, alpha, w0, loss="svm")
    wr = igd_ref.igd_fold_ref(x, y, alpha, w0, loss="svm")
    assert float(wr[1]) == -2.0**23 + 1.0  # two steps, then margin 1.0
    assert np.array_equal(np.asarray(wk), np.asarray(wr))


@pytest.mark.parametrize("loss", ["lr", "svm", "lsq"])
@pytest.mark.parametrize("n", [300, 520])
def test_igd_zero_alpha_rows_inside_a_block_are_noops(loss, n):
    """Rows with alpha = 0 anywhere in a block, not only in the tail pad,
    are exact no-ops of the block recurrence: their c is 0, so neither
    the model nor the block's later margins move. Their contents
    therefore cannot reach the result, bit for bit; and the kernel
    agrees with the sequential fold over the remaining rows alone."""
    d = 72
    x, y, alpha, w0 = _igd_inputs(n, d)
    idx = np.arange(n)
    # runs of zero steps inside blocks and across block boundaries
    dead = (idx % 7 == 3) | ((idx >= 40) & (idx < 75)) | (idx == n - 1)
    alpha = jnp.where(jnp.asarray(dead), 0.0, alpha)
    wk = igd_ops.igd_fold(x, y, alpha, w0, loss=loss)
    x_other = jnp.where(jnp.asarray(dead)[:, None], 3.0 * x[::-1], x)
    y_other = jnp.where(jnp.asarray(dead), -y, y)
    wk_other = igd_ops.igd_fold(x_other, y_other, alpha, w0, loss=loss)
    assert np.array_equal(np.asarray(wk), np.asarray(wk_other))
    live = ~dead
    wr = igd_ref.igd_fold_ref(x[live], y[live], alpha[live], w0, loss=loss)
    np.testing.assert_allclose(np.asarray(wk), np.asarray(wr),
                               rtol=2e-4, atol=2e-5)


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_igd_fold_property_random_seeds(seed):
    rng = jax.random.PRNGKey(seed)
    n, d = 256, 128
    x = jax.random.normal(rng, (n, d)) / jnp.sqrt(d)
    y = jnp.sign(jax.random.normal(jax.random.fold_in(rng, 1), (n,)))
    alpha = 0.05 * jnp.ones((n,))
    w0 = jnp.zeros((d,))
    wk = igd_ops.igd_fold(x, y, alpha, w0, loss="lr")
    wr = igd_ref.igd_fold_ref(x, y, alpha, w0, loss="lr")
    np.testing.assert_allclose(np.asarray(wk), np.asarray(wr),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,hd", [
    (2, 256, 4, 2, 64),
    (1, 128, 4, 4, 128),
    (2, 384, 6, 2, 32),
])
def test_flash_attention_matches_ref(b, s, h, kv, hd, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd)).astype(dtype)
    out_k = attn_ops.mha(q, k, v, use_kernel=True, interpret=True)
    out_r = attn_ops.mha(q, k, v, use_kernel=False)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_is_causal():
    """Perturbing future tokens must not change earlier outputs."""
    b, s, h, kv, hd = 1, 256, 2, 2, 64
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kv, hd))
    v = jax.random.normal(ks[2], (b, s, kv, hd))
    out1 = attn_ops.mha(q, k, v, use_kernel=True, interpret=True)
    k2 = k.at[:, s // 2 :].set(0.0)
    v2 = v.at[:, s // 2 :].set(0.0)
    out2 = attn_ops.mha(q, k2, v2, use_kernel=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, : s // 2]), np.asarray(out2[:, : s // 2]),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,hd,s,length", [
    (2, 4, 2, 64, 1024, 700),
    (1, 8, 8, 128, 512, 512),
    (4, 4, 1, 32, 2048, 1),
])
def test_flash_decode_matches_ref(b, h, kv, hd, s, length, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, h, hd)).astype(dtype)
    kc = jax.random.normal(ks[1], (b, s, kv, hd)).astype(dtype)
    vc = jax.random.normal(ks[2], (b, s, kv, hd)).astype(dtype)
    out_k = dec_ops.decode_attention(q, kc, vc, length, use_kernel=True,
                                     interpret=True)
    out_r = dec_ops.decode_attention(q, kc, vc, length, use_kernel=False)
    tol = 5e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_decode_ignores_cache_tail():
    b, h, kv, hd, s = 1, 2, 2, 64, 1024
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, h, hd))
    kc = jax.random.normal(ks[1], (b, s, kv, hd))
    vc = jax.random.normal(ks[2], (b, s, kv, hd))
    out1 = dec_ops.decode_attention(q, kc, vc, 300, use_kernel=True)
    kc2 = kc.at[:, 300:].set(99.0)
    vc2 = vc.at[:, 300:].set(-99.0)
    out2 = dec_ops.decode_attention(q, kc2, vc2, 300, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-7)
