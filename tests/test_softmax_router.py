"""The softmax router beside the sigmoid one, and a dropless expert layer
that holds EVERY expert (``incubate/distributed/models/dropless_moe.py``):
``route_softmax_topk`` against its formula written out, and
``dropless_experts(held=(0, n))`` against the dense sum, every expert over
every token weighed by the router. float32 on the CPU (``ragged_dot``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — x64 on, as in production
from paddle_tpu.incubate.distributed.models import dropless_moe as dm

TOKENS, HIDDEN, WIDTH = 37, 24, 16


def operands(n, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(0, 0.5, s), dtype)  # noqa: E731
    return (mk(TOKENS, HIDDEN), mk(HIDDEN, n), mk(n, HIDDEN, WIDTH),
            mk(n, HIDDEN, WIDTH), mk(n, WIDTH, HIDDEN))


@pytest.mark.parametrize("n, k", [(8, 2), (64, 8), (5, 5)])
@pytest.mark.parametrize("normalize", [True, False])
def test_route_softmax_topk_is_its_formula(n, k, normalize):
    """float32 softmax over ALL experts, its top k, divided by their sum
    when ``norm_topk_prob``: against numpy in float64."""
    y, w_r, *_ = operands(n)
    w, idx = dm.route_softmax_topk(y, w_r, k, normalize)
    assert w.shape == idx.shape == (TOKENS, k)
    assert w.dtype == jnp.float32 and idx.dtype == jnp.int32
    logits = np.asarray(y, np.float64) @ np.asarray(w_r, np.float64)
    g = np.exp(logits - logits.max(-1, keepdims=True))
    g /= g.sum(-1, keepdims=True)
    order = np.argsort(-g, axis=-1)[:, :k]
    top = np.take_along_axis(g, order, -1)
    if normalize:
        top /= top.sum(-1, keepdims=True)
    assert np.array_equal(np.asarray(idx), order)
    assert np.abs(np.asarray(w) - top).max() < 1e-6
    if normalize:
        assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    else:
        assert (np.asarray(w).sum(-1) < 1.0 + 1e-6).all()
        assert (n == k) == bool(np.allclose(np.asarray(w).sum(-1), 1.0))


def test_the_router_is_float32_whatever_the_activations_are():
    """bfloat16 activations and router weights are multiplied in float32:
    the choice is that of the same numbers held in float32."""
    y, w_r, *_ = operands(16, seed=3, dtype=jnp.bfloat16)
    w, idx = dm.route_softmax_topk(y, w_r, 4)
    w32, idx32 = dm.route_softmax_topk(y.astype(jnp.float32),
                                       w_r.astype(jnp.float32), 4)
    assert w.dtype == jnp.float32
    assert jnp.array_equal(idx, idx32) and jnp.array_equal(w, w32)


@pytest.mark.parametrize("n, k", [(8, 2), (16, 8)])
def test_the_whole_layer_held_is_the_dense_sum(n, k):
    """``held = (0, n)``: every assignment is local, no token is dropped
    however unevenly they fall (expert 0 is made everyone's first choice),
    padding tokens are routed nowhere, and the result is ``sum_k g_k
    E_k(y)`` with every expert computed over every token."""
    y, w_r, gate, up, down = operands(n, seed=1)
    w_r = w_r.at[:, 0].add(jnp.sign(jnp.sum(y, 0)) * 2.0)
    w, idx = dm.route_softmax_topk(y, w_r, k)
    valid = jnp.arange(TOKENS) < TOKENS - 5
    out, counters = jax.jit(lambda *a: dm.dropless_experts(
        *a, (0, n), valid))(y, w, idx, gate, up, down)
    dense_w = jnp.zeros((TOKENS, n)).at[
        jnp.arange(TOKENS)[:, None], idx].set(w)
    each = jnp.einsum(
        "etf,efh->eth",
        jax.nn.silu(jnp.einsum("th,ehf->etf", y, gate, precision="highest"))
        * jnp.einsum("th,ehf->etf", y, up, precision="highest"),
        down, precision="highest")
    want = jnp.einsum("te,eth->th", dense_w, each, precision="highest")
    assert float(jnp.max(jnp.abs(out[:TOKENS - 5] - want[:TOKENS - 5]))) \
        < 2e-6
    assert float(jnp.max(jnp.abs(out[TOKENS - 5:]))) == 0.0
    assigned, local, slots, hits = (int(c) for c in counters)
    assert assigned == local == (TOKENS - 5) * k
    assert slots == n and 1 <= hits <= n
    # expert 0 got far more than its share, and nothing was dropped
    first = int(jnp.sum((idx == 0) & valid[:, None]))
    assert first > 1.4 * (TOKENS - 5) * k / n
    assert dm.COUNTERS == ("assignments", "local_assignments",
                           "expert_slots", "expert_hits")


@pytest.mark.parametrize("k, n, tile", [
    (7168, 2048, (128, 1792, 1024)),     # cell K's gate and up: as before
    (2048, 7168, (128, 2048, 1024)),     # and its down
    (2304, 896, (128, 2304, 896)),       # an expert of 2304 x 896: one tile
    (896, 2304, (128, 896, 2304)),
    (4096, 1024, (128, 2048, 1024)),
    (256, 128, (128, 256, 128)),
])
def test_the_grouped_product_s_tiles(k, n, tile):
    """The widest listed tiles that divide the product, a weight tile of
    at most 2048 x 1024 elements: the shapes the benchmark had keep their
    tiles, and an expert as small as 2304 x 896 is one tile (not 9 x 7
    turns of the grid for 64 KB each)."""
    assert dm._tiling(k, n) == tile
    assert tile[1] * tile[2] <= 2048 * 1024 and k % tile[1] == 0 == n % tile[2]
