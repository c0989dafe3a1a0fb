"""The grouped flash forward of a serving prefill
(``kernels/flash_attention.py:flash_fwd_grouped``) through the Pallas
interpreter on the CPU, against the plain formula: grouped KV heads (query
head ``h`` reads KV head ``h // g``), the window's lower edge in the mask,
and the blocks wholly behind a window skipped, counted against the kernel's
own grid. Blocks of 128 (the statistics are 128 equal lanes a row), a
sequence of 512, heads of 16."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — x64 on, as in production
from paddle_tpu.kernels import flash_attention as fa

B, H, KV, S, D, BLOCK = 1, 4, 2, 512, 16, 128
SCALE = 0.25


@pytest.fixture(autouse=True)
def _block(monkeypatch):
    monkeypatch.setattr(fa, "_GROUPED_BLOCK", BLOCK)


def operands(dtype, seed=0, s=S):
    rng = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(rng.normal(size=(B, h, s, D)), dtype)  # noqa: E731
    return mk(H), mk(KV), mk(KV)


def plain(q, k, v, window):
    g = H // KV
    kk, vv = (jnp.repeat(t.astype(jnp.float32), g, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk,
                    precision="highest") * SCALE
    s = q.shape[2]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vv, precision="highest")


@pytest.mark.parametrize("window", [None, 1, 100, 128, 129, 200, 384, 600],
                         ids=lambda w: f"w{w}")
def test_the_grouped_forward_is_the_plain_formula(window):
    """No window, a window of the token alone, under, at and one over a
    block, across blocks, and wider than the sequence: float32 to
    round-off."""
    q, k, v = operands(jnp.float32)
    got = fa.flash_fwd_grouped(q, k, v, SCALE, window, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    assert float(jnp.max(jnp.abs(got - plain(q, k, v, window)))) < 2e-6


def test_bfloat16_agrees_to_the_rounding_of_the_probabilities():
    q, k, v = operands(jnp.bfloat16, seed=1)
    got = fa.flash_fwd_grouped(q, k, v, SCALE, 200, interpret=True)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - plain(q, k, v, 200)))) < 3e-2


def test_a_query_head_reads_its_own_kv_head():
    """Change KV head 1 (every other key every other dimension, all its
    values): the heads of group 0 (0, 1) do not move, every row of those
    of group 1 does."""
    q, k, v = operands(jnp.float32)
    a = fa.flash_fwd_grouped(q, k, v, SCALE, 200, interpret=True)
    b = fa.flash_fwd_grouped(q, k.at[:, 1, ::2].mul(-1.0),
                             v.at[:, 1].add(1.0), SCALE, 200,
                             interpret=True)
    assert jnp.array_equal(a[:, :2], b[:, :2])
    assert float(jnp.min(jnp.abs(a[:, 2:] - b[:, 2:]).max(-1))) > 1e-2


@pytest.mark.parametrize("window, live", [
    (None, 10), (600, 10), (384, 10), (257, 9), (200, 9), (129, 7), (128, 7),
    (1, 4)])
def test_blocks_behind_the_window_are_skipped_not_masked(window, live):
    """``grouped_live_steps`` is the kernel's own count: the grid steps
    in which ``_visible_steps`` runs a step, counted here by running the
    predicate over the 4 x 4 grid of blocks. A window of one block and one
    token leaves three blocks a row where the first query's window still
    reaches two back; a window of a block leaves two."""
    from jax.experimental import pallas as pl

    assert fa.grouped_live_steps(S, window) == live
    ran = []

    def when(cond):
        return lambda fn: ran.append(bool(cond))

    real = pl.when
    pl.when = when
    try:
        n = S // BLOCK
        for i in range(n):
            for j in range(n):
                before = len(ran)
                fa._visible_steps(lambda masked: None, True, i, j, BLOCK,
                                  BLOCK, 0, window)
                # full and masked are exclusive: at most one of them runs
                assert sum(ran[before:]) <= 1
    finally:
        pl.when = real
    assert sum(ran) == live
    # what a skipped block would have added is an exact zero: blocks of
    # huge keys and values behind the window change nothing
    if window is not None and window <= 128:
        q, k, v = operands(jnp.float32, seed=3)
        clean = fa.flash_fwd_grouped(q, k, v, SCALE, window, interpret=True)
        # queries of the last block alone; the first block lies wholly
        # behind their windows
        dirty_k = k.at[:, :, :BLOCK].set(1e4)
        dirty_v = v.at[:, :, :BLOCK].set(-1e30)
        got = fa.flash_fwd_grouped(q, dirty_k, dirty_v, SCALE, window,
                                   interpret=True)
        assert jnp.array_equal(got[:, :, 3 * BLOCK:], clean[:, :, 3 * BLOCK:])


def test_the_gate_and_a_sequence_of_one_block():
    assert fa.grouped_supported(512, 16, interpret=True)
    assert not fa.grouped_supported(512, 16)         # the chip wants d % 64
    assert fa.grouped_supported(4096, 128) and fa.grouped_edge(4096) == 128
    assert not fa.grouped_supported(64, 128)         # under a lane row
    assert not fa.grouped_supported(640 + 64, 128)   # no whole blocks
    q, k, v = operands(jnp.float32, s=128)
    got = fa.flash_fwd_grouped(q, k, v, SCALE, 40, interpret=True)
    assert float(jnp.max(jnp.abs(got - plain(q, k, v, 40)))) < 2e-6
    assert fa.grouped_live_steps(128, 40) == 1


def test_the_training_forward_is_the_kernel_it_was():
    """``_flash_fwd_kernel`` without a window is cell 1's kernel: the
    custom-vjp forward's jaxpr names ``flash_fwd`` and no grouped
    kernel."""
    q = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)
    text = str(jax.make_jaxpr(
        lambda q: fa._flash(q, q, q, True, 0.125, True))(q))
    assert "name=flash_fwd\n" in text or "name=flash_fwd " in text \
        or "flash_fwd" in text
    assert "flash_fwd_grouped" not in text
