"""A DROPLESS expert layer for serving that is told which experts it holds
(the DeepSeek-V3 layer, arXiv:2412.19437 section 2.1.2; beside ``moe.py``,
whose GShard dispatch drops tokens over capacity 1.25).

One chip of an expert-parallel deployment holds ``count`` of the router's
``n_routed_experts`` experts, ``held = (first, count)``. The router keeps
its published width and its top-k over ALL experts; this layer computes
the part of the sum that its own experts give, for the tokens routed to
them, and leaves out the rest. ``count == n_routed_experts`` is the whole
layer. Nothing stands in for the absent chips or their exchange.

No token routed to a held expert is dropped, whatever the imbalance, and
every shape is static: the ``tokens * top_k`` assignments are sorted by
held expert (assignments to experts held elsewhere, and those of padding
tokens, sort behind the last group), the sorted rows go through one
grouped product over the held experts' ``[count, in, out]`` weights, and
the results return to their tokens by the inverse permutation.

The grouped product is ``jax.lax.ragged_dot``, or on a TPU the Pallas
grouped matmul of ``jax.experimental.pallas.ops.tpu.megablox`` (a grid over
the row tiles that hold assignments only, so the work follows the local
assignments and not the worst case; the library's kernel, no
``pallas_call`` of this module's own).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route_sigmoid_topk", "route_softmax_topk", "grouped_matmul",
           "dropless_experts", "COUNTERS"]

#: what :func:`dropless_experts` counts, in this order (int32 [4])
COUNTERS = ("assignments", "local_assignments", "expert_slots",
            "expert_hits")

#: row tile of the Pallas grouped matmul: rows are padded to it
GMM_ROWS = 128


def route_sigmoid_topk(y, w_router, bias, top_k: int, scaling: float,
                       normalize: bool = True):
    """The ``noaux_tc`` router with ``n_group = topk_group = 1``: float32
    logits, sigmoid scores, the top-k of the scores PLUS the correction
    bias, and as weights the scores themselves (without the bias),
    divided by their sum and scaled. y [tokens, hidden] -> (weights
    [tokens, k] float32, experts [tokens, k] int32)."""
    logits = jnp.dot(y.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    sig = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(sig + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(sig, idx, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scaling, idx.astype(jnp.int32)


def route_softmax_topk(y, w_router, top_k: int, normalize: bool = True):
    """The softmax router (Mixtral's and Qwen-MoE's; ``norm_topk_prob``):
    float32 logits, a float32 softmax over ALL experts, its top-k, and as
    weights those k probabilities, divided by their sum when
    ``normalize``. No bias, no scaling, no group. y [tokens, hidden] ->
    (weights [tokens, k] float32, experts [tokens, k] int32)."""
    logits = jnp.dot(y.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx.astype(jnp.int32)


def _use_pallas(rows: int, k: int, n: int) -> bool:
    from ....kernels._common import on_tpu_backend
    from ....utils.flags import flag

    return (on_tpu_backend() and bool(flag("FLAGS_use_pallas_kernels", True))
            and rows % GMM_ROWS == 0 and k % 128 == 0 and n % 128 == 0)


#: the most elements of a weight tile (a grid step copies one: 4 MiB in
#: bfloat16, two of them in flight)
_GMM_TILE = 2048 * 1024


def _tiling(k: int, n: int) -> tuple:
    """(rows, contraction, columns) of one grid step: the widest tiles of
    these that divide the product, a weight tile of at most 2048 x 1024
    elements. 2304 and 896 are there for experts as small as 2304 x 896
    (one tile an expert): without them such a product falls to tiles of
    256 x 128, and a decode step that gives an expert six tokens then
    turns its grid nine times seven times as often, each turn for 64 KB
    (my chip runs, PR 35: a grouped product of 1.4 ms where the weights'
    stream is 0.32)."""
    tk = next(t for t in (2304, 2048, 1792, 1024, 896, 512, 256, 128)
              if k % t == 0)
    tn = next(t for t in (2304, 1024, 896, 512, 256, 128)
              if n % t == 0 and tk * t <= _GMM_TILE)
    return GMM_ROWS, tk, tn


def grouped_matmul(x, w, sizes):
    """``x`` [rows, k] sorted by group, ``w`` [groups, k, n], ``sizes``
    [groups] int32 -> [rows, n] in x's dtype. Rows behind the last group
    hold nothing a caller may read."""
    if _use_pallas(x.shape[0], w.shape[1], w.shape[2]):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        from ....kernels._common import i32_index_scope

        with i32_index_scope():
            return gmm(x, w, sizes, preferred_element_type=x.dtype,
                       tiling=_tiling(w.shape[1], w.shape[2]))
    return jax.lax.ragged_dot(x, w, sizes)


def dropless_experts(y, weights, experts, w_gate, w_up, w_down,
                     held: tuple, valid=None):
    """The held experts' part of ``sum_k w_k E_k(y)``. y [tokens, hidden];
    ``weights`` / ``experts`` [tokens, k] from the router; ``w_gate``,
    ``w_up`` [count, hidden, width], ``w_down`` [count, width, hidden];
    ``valid`` [tokens] bool: padding tokens are routed nowhere and counted
    nowhere. Returns ([tokens, hidden] in y's dtype, counters int32 [4] in
    the order of ``COUNTERS``)."""
    first, count = held
    tokens, k = experts.shape
    if valid is None:
        valid = jnp.ones((tokens,), bool)
    with jax.named_scope("dispatch"):
        local = experts - jnp.int32(first)
        is_local = (local >= 0) & (local < count) & valid[:, None]
        group = jnp.where(is_local, local, count).reshape(-1)
        rows = tokens * k
        pad = -rows % GMM_ROWS
        if pad:
            group = jnp.concatenate(
                [group, jnp.full((pad,), count, jnp.int32)])
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
        # a padding row reads the last token: it is in no group
        x = y[jnp.minimum(order // k, tokens - 1)]
    with jax.named_scope("experts"):
        mid = jax.nn.silu(grouped_matmul(x, w_gate, sizes)) \
            * grouped_matmul(x, w_up, sizes)
        out = grouped_matmul(mid, w_down, sizes)
    with jax.named_scope("combine"):
        inverse = jnp.argsort(order).astype(jnp.int32)[:rows]
        back = out[inverse].reshape(tokens, k, -1)
        w_local = jnp.where(is_local, weights, 0.0)
        # where, not a product with 0: a row of no group is not defined
        back = jnp.where(is_local[..., None], back, 0)
        summed = jnp.einsum("tk,tkh->th", w_local,
                            back.astype(jnp.float32)).astype(y.dtype)
    counters = jnp.stack([
        jnp.sum(valid, dtype=jnp.int32) * k,
        jnp.sum(is_local, dtype=jnp.int32),
        jnp.int32(count),
        jnp.sum(sizes > 0, dtype=jnp.int32)])
    return summed, counters
