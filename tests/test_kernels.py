"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps
+ hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.greedy_update.ops import greedy_update
from repro.kernels.greedy_update.ref import greedy_update_ref
from repro.kernels.imgs_panel.ops import imgs_panel
from repro.kernels.imgs_panel.ref import imgs_panel_ref
from repro.kernels.imgs_project.ops import imgs_project
from repro.kernels.imgs_project.ref import imgs_project_ref
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _mk(rng, shape, dtype):
    if np.issubdtype(dtype, np.complexfloating):
        return (rng.standard_normal(shape)
                + 1j * rng.standard_normal(shape)).astype(dtype)
    return rng.standard_normal(shape).astype(dtype)


# ------------------------------------------------------------- greedy_update
@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("shape", [(64, 96), (300, 700), (1024, 256),
                                   (17, 33)])
def test_greedy_update_sweep(rng, dtype, shape):
    N, M = shape
    S = _mk(rng, (N, M), dtype)
    q = _mk(rng, (N,), dtype)
    q = q / np.linalg.norm(q)
    acc = np.abs(rng.standard_normal(M)).astype(np.float32)
    norms = np.sum(np.abs(S) ** 2, axis=0).astype(np.float32)

    c, a, mx, am = greedy_update(
        jnp.asarray(q), jnp.asarray(S), jnp.asarray(acc), jnp.asarray(norms)
    )
    cr, ar, mxr, amr = greedy_update_ref(
        jnp.asarray(q), jnp.asarray(S), jnp.asarray(acc), jnp.asarray(norms)
    )
    scale = float(jnp.max(jnp.abs(cr))) + 1e-6
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr),
                               rtol=1e-4, atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(a), np.asarray(ar),
                               rtol=1e-4, atol=1e-3 * scale ** 2)
    assert float(mx) == pytest.approx(float(mxr), rel=1e-3, abs=1e-2)
    assert int(am) == int(amr)


# S is swept at its own shape: the interpreter fills the part of a partial
# block beyond the array with NaN, so a missing row or column mask shows
# as NaN or a wrong argmax.  N = 256 j + 16 is 10,000's remainder at the
# default row tile.
@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("N,M,tiles,last_wins", [
    (272, 1029, {}, False),                      # one full row tile + 16
    (528, 1029, {}, True),                       # two + 16; argmax = M - 1
    (400, 385, {"nt": 128, "mt": 128}, False),   # M = 3 mt + 1
    (400, 385, {"nt": 128, "mt": 128}, True),
    (272, 2048, {"mt": 1024}, True),             # ragged rows only
], ids=["n272-m1029", "n528-m1029-last", "n400-m385", "n400-m385-last",
        "n272-m2048-last"])
def test_greedy_update_ragged_tiles(rng, dtype, N, M, tiles, last_wins):
    S = _mk(rng, (N, M), dtype)
    q = _mk(rng, (N,), dtype)
    q = q / np.linalg.norm(q)
    acc = np.abs(rng.standard_normal(M)).astype(np.float32)
    norms = np.sum(np.abs(S) ** 2, axis=0).astype(np.float32)
    if last_wins:
        norms[-1] += 1e4
    args = [jnp.asarray(a) for a in (q, S, acc, norms)]

    c, a, mx, am = greedy_update(*args, **tiles)
    cr, ar, mxr, amr = greedy_update_ref(*args)
    assert c.shape == (M,) and a.shape == (M,)
    assert np.all(np.isfinite(np.asarray(c)))
    assert np.all(np.isfinite(np.asarray(a)))
    scale = float(jnp.max(jnp.abs(cr)))
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr),
                               rtol=1e-4, atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(a), np.asarray(ar),
                               rtol=1e-4, atol=1e-3 * scale ** 2)
    assert float(mx) == pytest.approx(float(mxr), rel=1e-5)
    assert int(am) == int(amr)
    if last_wins:
        assert int(am) == M - 1


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 9999), n=st.integers(8, 200),
       m=st.integers(8, 300),
       cplx=st.booleans())
def test_greedy_update_property(seed, n, m, cplx):
    rng = np.random.default_rng(seed)
    dtype = np.complex64 if cplx else np.float32
    S = _mk(rng, (n, m), dtype)
    q = _mk(rng, (n,), dtype)
    q /= np.linalg.norm(q)
    acc = np.zeros(m, np.float32)
    norms = np.sum(np.abs(S) ** 2, 0).astype(np.float32)
    c, a, mx, am = greedy_update(jnp.asarray(q), jnp.asarray(S),
                                 jnp.asarray(acc), jnp.asarray(norms))
    cr, ar, mxr, amr = greedy_update_ref(jnp.asarray(q), jnp.asarray(S),
                                         jnp.asarray(acc),
                                         jnp.asarray(norms))
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr), rtol=2e-3,
                               atol=2e-3 * (float(np.abs(cr).max()) + 1))
    # residual values agree; index may differ only on numerical ties
    res_k = norms - np.asarray(a)
    res_r = norms - np.asarray(ar)
    assert abs(res_k[int(am)] - res_r[int(amr)]) <= 1e-2 * (
        abs(float(mxr)) + 1.0
    )


# -------------------------------------------------------------- imgs_project
@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("shape", [(128, 16), (513, 37), (1000, 100)])
def test_imgs_project_sweep(rng, dtype, shape):
    N, K = shape
    Q = _mk(rng, (N, K), dtype)
    Qo, _ = np.linalg.qr(Q)
    Qo = Qo.astype(dtype)
    v = _mk(rng, (N,), dtype)
    vo, co = imgs_project(jnp.asarray(v), jnp.asarray(Qo))
    vr, cr = imgs_project_ref(jnp.asarray(v), jnp.asarray(Qo))
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(co), np.asarray(cr),
                               rtol=1e-4, atol=1e-4)


def test_imgs_project_orthogonalizes(rng):
    N, K = 256, 32
    Q, _ = np.linalg.qr(rng.standard_normal((N, K)))
    v = rng.standard_normal(N).astype(np.float32)
    vo, _ = imgs_project(jnp.asarray(v), jnp.asarray(Q.astype(np.float32)))
    # after one pass, residual is orthogonal to span(Q) to ~f32 eps
    overlap = np.abs(Q.T @ np.asarray(vo)).max()
    assert overlap < 1e-4 * np.linalg.norm(v)


# ---------------------------------------------------------------- imgs_panel
@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("shape", [(128, 16, 4), (513, 37, 5),
                                   (1000, 100, 8), (64, 7, 3)])
def test_imgs_panel_sweep(rng, dtype, shape):
    """The fused panel-projection kernel (interpret mode) matches the
    literal reference on padded and non-sublane-multiple panel widths."""
    N, K, p = shape
    Q = _mk(rng, (N, K), dtype)
    Qo, _ = np.linalg.qr(Q)
    Qo = Qo.astype(dtype)
    V = _mk(rng, (N, p), dtype)
    vo, co = imgs_panel(jnp.asarray(V), jnp.asarray(Qo))
    vr, cr = imgs_panel_ref(jnp.asarray(V), jnp.asarray(Qo))
    assert vo.shape == (N, p) and co.shape == (K, p)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(co), np.asarray(cr),
                               rtol=1e-4, atol=1e-4)


def test_imgs_panel_matches_columnwise_project(rng):
    """One panel pass == p independent single-vector passes (the BLAS-3
    form changes the execution, not the math)."""
    N, K, p = 256, 32, 6
    Q, _ = np.linalg.qr(rng.standard_normal((N, K)))
    Q = Q.astype(np.float32)
    V = rng.standard_normal((N, p)).astype(np.float32)
    vo, co = imgs_panel(jnp.asarray(V), jnp.asarray(Q))
    for i in range(p):
        vi, ci = imgs_project_ref(jnp.asarray(V[:, i]), jnp.asarray(Q))
        np.testing.assert_allclose(np.asarray(vo[:, i]), np.asarray(vi),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(co[:, i]), np.asarray(ci),
                                   rtol=1e-4, atol=1e-4)
    # and the pass orthogonalizes: residual panel ⟂ span(Q) to ~f32 eps
    overlap = np.abs(Q.T @ np.asarray(vo)).max()
    assert overlap < 1e-4 * float(np.max(np.linalg.norm(V, axis=0)))


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (4, 1)])
def test_flash_attention_sweep(rng, causal, window, hq, hkv):
    B, S, D = 2, 256, 64
    q = (rng.standard_normal((B, hq, S, D)) * 0.3).astype(np.float32)
    k = (rng.standard_normal((B, hkv, S, D)) * 0.3).astype(np.float32)
    v = rng.standard_normal((B, hkv, S, D)).astype(np.float32)
    o = flash_attention_kernel(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=causal, window=window,
                               interpret=True)
    r = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_ragged_padding(rng):
    """Non-tile-multiple lengths route through padding, still exact."""
    B, H, S, D = 1, 2, 200, 64
    q = (rng.standard_normal((B, H, S, D)) * 0.3).astype(np.float32)
    k = (rng.standard_normal((B, H, S, D)) * 0.3).astype(np.float32)
    v = rng.standard_normal((B, H, S, D)).astype(np.float32)
    o = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=True, use_kernel=True, interpret=True)
    r = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16(rng):
    B, H, S, D = 1, 2, 128, 128
    q = (rng.standard_normal((B, H, S, D)) * 0.3)
    k = (rng.standard_normal((B, H, S, D)) * 0.3)
    v = rng.standard_normal((B, H, S, D))
    args = [jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)]
    o = flash_attention_kernel(*args, causal=True, interpret=True)
    r = attention_ref(*args, causal=True)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32),
        rtol=5e-2, atol=5e-2,
    )


# ------------------------------------- kernels inside the chunked JAX path
def test_chunked_attention_matches_flash(rng):
    """The pure-JAX online-softmax path (dry-run default) is the same math."""
    from repro.models.attention import _chunked_attn, _einsum_attn

    B, S, H, K, D = 2, 192, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, D)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    for causal, window in [(True, None), (True, 48), (False, None)]:
        a = _chunked_attn(q, k, v, causal, window, chunk=64)
        b = _einsum_attn(q, k, v, causal, window)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# --------------------------------------------- int8 KV quantization property
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 9999), n=st.integers(1, 64),
       hd=st.sampled_from([16, 64, 128]))
def test_kv_quantization_roundtrip(seed, n, hd):
    """|dequant(quant(x)) - x| <= absmax(x)/127 per row (symmetric int8)."""
    from repro.models.attention import dequantize_kv, quantize_kv
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, hd)) * 3.0, jnp.float32)
    q, s = quantize_kv(x)
    back = dequantize_kv(q, s, jnp.float32)
    bound = np.asarray(jnp.max(jnp.abs(x), axis=-1, keepdims=True)) / 127.0
    assert np.all(np.abs(np.asarray(back - x)) <= bound + 1e-6)


# -------------------------------------------------- greedy projector property
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 9999))
def test_greedy_projection_idempotent_and_monotone(seed):
    """Q Q^H is a projector; adding bases never increases any column error."""
    from repro.core import rb_greedy
    from repro.core.errors import per_column_errors
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((60, 10)) @ rng.standard_normal((10, 30))
    S = jnp.asarray(A + 1e-6 * rng.standard_normal((60, 30)))
    res = rb_greedy(S, tau=1e-8)
    k = int(res.k)
    Q = res.Q[:, :k]
    P1 = Q @ (Q.conj().T @ S)
    P2 = Q @ (Q.conj().T @ P1)
    np.testing.assert_allclose(np.asarray(P1), np.asarray(P2),
                               rtol=1e-6, atol=1e-9)
    prev = None
    for j in range(1, k + 1):
        errs = np.asarray(per_column_errors(S, res.Q[:, :j]))
        if prev is not None:
            assert np.all(errs <= prev + 1e-8)
        prev = errs
