"""Pallas kernel numerics, validated on CPU via interpret mode.

Reference analog: the FMHA correctness tests around
operators/fused/fused_attention_op.cu — here against the composite
`sdpa_reference` (kernels/attention.py) which is itself parity-tested through
the model suites.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.kernels.attention import sdpa_reference  # noqa: E402
from paddle_tpu.kernels.flash_attention import _splash  # noqa: E402


def _qkv(b, h, s_q, s_k, d, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))  # noqa: E731
    return mk(s_q), jnp.asarray(rng.randn(b, h, s_k, d).astype(np.float32)), \
        jnp.asarray(rng.randn(b, h, s_k, d).astype(np.float32))


def test_splash_causal_matches_reference_square():
    b, h, s, d = 1, 2, 256, 128
    q, k, v = _qkv(b, h, s, s, d)
    scale = 1.0 / d ** 0.5
    out = _splash(q, k, v, scale, interpret=True)
    ref = sdpa_reference(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_splash_causal_rectangular_bottom_right_aligned():
    """s_q < s_k: the causal diagonal must align bottom-right (query i sees
    keys up to i + s_k - s_q), matching sdpa_reference's tril(k=s_k-s_q)."""
    b, h, s_q, s_k, d = 1, 2, 128, 256, 128
    q, k, v = _qkv(b, h, s_q, s_k, d, seed=1)
    scale = 1.0 / d ** 0.5
    out = _splash(q, k, v, scale, interpret=True)
    ref = sdpa_reference(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_splash_custom_vjp_grad_fast():
    """Fast-tier coverage of the hand-written _splash custom_vjp backward
    (round 5: the library kernel's internal vjp lowered under global x64 and
    failed Mosaic; _splash_fwd/_splash_bwd re-trace under x64-off). Small
    shape so the interpret-mode backward stays cheap."""
    b, h, s, d = 1, 2, 128, 64
    q, k, v = _qkv(b, h, s, s, d, seed=5)
    scale = 1.0 / d ** 0.5

    def f_splash(q, k, v):
        return jnp.sum(_splash(q, k, v, scale, True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, is_causal=True) ** 2)

    g_s = jax.grad(f_splash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gs, gr in zip(g_s, g_r):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.slow
def test_splash_grad_matches_reference():
    b, h, s, d = 1, 1, 256, 128
    q, k, v = _qkv(b, h, s, s, d, seed=2)
    scale = 1.0 / d ** 0.5

    def f_splash(q, k, v):
        return jnp.sum(_splash(q, k, v, scale, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, is_causal=True) ** 2)

    g_s = jax.grad(f_splash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gs, gr in zip(g_s, g_r):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3)


# ------------------------------------------- in-tree flash forward/backward
from paddle_tpu.kernels import flash_attention as fa  # noqa: E402

#: largest error allowed, as a share of the reference's largest entry: a
#: bf16 run rounds p, dS and each result once to 8 bits (eps 2**-7); an
#: fp32 run only accumulates fp32 rounding over sums of up to 1024 terms
_FLASH_TOL = {jnp.bfloat16: 2.0 ** -6, jnp.float32: 2.0 ** -17}

FLASH_PARITY = [
    pytest.param(s, d, causal, dtype,
                 id=f"s{s}-d{d}-{'causal' if causal else 'full'}-"
                    f"{jnp.dtype(dtype).name}")
    for s in (256, 1024) for d in (64, 128) for causal in (True, False)
    for dtype in (jnp.bfloat16, jnp.float32)
] + [
    # the pad route: 640 is causal attention padded to 1024 by the dispatch
    pytest.param(640, d, True, dtype, id=f"pad640-d{d}-{jnp.dtype(dtype).name}")
    for d in (64, 128) for dtype in (jnp.bfloat16, jnp.float32)
]


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("s,d,causal,dtype", FLASH_PARITY)
def test_flash_kernels_match_reference(monkeypatch, s, d, causal, dtype):
    """The in-tree forward and the fused backward, through the Pallas
    interpreter, against the composite: the output and dQ, dK, dV. s 256
    is one block (the mask inside it), s 1024 is two by two at the shipped
    edge of 512 (a dead step, a full one, two on the diagonal), 640 goes
    through ``sdpa``'s pad route."""
    from paddle_tpu.kernels import _common, attention

    scale = 1.0 / d ** 0.5
    q, k, v = (x.astype(dtype) for x in _qkv(1, 2, s, s, d, seed=s + d))
    w = _qkv(1, 2, s, s, d, seed=7)[0]

    if fa.flash_route(q.shape, k.shape, causal) == "direct":
        def attn(q, k, v):
            return fa._flash(q, k, v, causal, scale, True)
    else:
        assert fa.flash_route(q.shape, k.shape, causal) == "pad"
        monkeypatch.setattr(_common, "on_tpu_backend", lambda: True)
        real = fa._flash
        monkeypatch.setattr(
            fa, "_flash", lambda q, k, v, c, sc: real(q, k, v, c, sc, True))

        def attn(q, k, v):
            return attention.sdpa(q, k, v, is_causal=True)

    def ref(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        return sdpa_reference(q, k, v, is_causal=causal, scale=scale)

    out, vjp = jax.vjp(attn, q, k, v)
    want, ref_vjp = jax.vjp(ref, q, k, v)
    assert out.dtype == dtype and out.shape == q.shape
    tol = _FLASH_TOL[dtype]
    _close(out, want, tol)
    for got, exp in zip(vjp(w.astype(dtype)), ref_vjp(w)):
        assert got.dtype == dtype
        _close(got, exp, tol)


def test_flash_causal_rectangular_bottom_right_aligned():
    """s_q < s_k, as the splash path: query i sees keys up to i + s_k - s_q."""
    q, k, v = _qkv(1, 2, 256, 1024, 64, seed=3)
    out, vjp = jax.vjp(lambda q, k, v: fa._flash(q, k, v, True, 0.125, True),
                       q, k, v)
    want, ref_vjp = jax.vjp(
        lambda q, k, v: sdpa_reference(q, k, v, is_causal=True), q, k, v)
    _close(out, want, _FLASH_TOL[jnp.float32])
    for got, exp in zip(vjp(q), ref_vjp(q)):
        _close(got, exp, _FLASH_TOL[jnp.float32])
    # the rows above every key have nothing to attend: no kernel route
    assert fa.flash_route((1, 2, 1024, 64), (1, 2, 256, 64), True) == ""


def test_flash_dq_leaves_as_partials_when_a_head_does_not_fit(monkeypatch):
    """Above ``_DQ_RESIDENT_BYTES`` the backward writes each kv block's
    share of dQ as an fp32 partial and XLA sums them: the same numbers."""
    q, k, v = _qkv(1, 2, 1024, 1024, 64, seed=4)

    def grads():
        _, vjp = jax.vjp(
            lambda q, k, v: fa._flash(q, k, v, True, 0.125, True), q, k, v)
        return vjp(v)

    resident = grads()
    monkeypatch.setattr(fa, "_DQ_RESIDENT_BYTES", 0)
    partial = grads()
    for a, b in zip(resident, partial):
        _close(a, b, _FLASH_TOL[jnp.float32])


def test_flash_residual_statistic_is_one_number_a_row():
    """What the forward keeps for the backward: q, k, v, o and ONE fp32
    statistic, ``lse`` as ``[b, h, 1, s]`` — never a lane-broadcast
    ``[b, h, s, 128]`` (the library kept two, and a third in its
    backward)."""
    b, h, s, d = 2, 2, 256, 64
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(b, h, s, s, d))
    _, vjp = jax.vjp(lambda q, k, v: fa._flash(q, k, v, True, 0.125, True),
                     q, k, v)
    shapes = sorted((x.shape, x.dtype.name)
                    for x in jax.tree_util.tree_leaves(vjp))
    assert shapes == sorted([((b, h, s, d), "bfloat16")] * 4
                            + [((b, h, 1, s), "float32")])
