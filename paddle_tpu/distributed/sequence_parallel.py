"""Sequence / context parallelism: ring attention + Ulysses (all-to-all).

The reference (survey §5.7) has NO sequence parallelism — its long-sequence
story stops at Megatron head-sharding (`meta_parallel/parallel_layers/mp_layers.py`),
recompute (`fleet/utils/recompute.py:209`) and pipeline micro-batching. On TPU,
sequence parallelism is first-class: activations are sharded over a mesh axis
`sp` on the *sequence* dimension, and attention runs as either

- **ring attention** (`ring_attention`): K/V shards rotate around the `sp` ring
  via `lax.ppermute` (ICI-neighbour traffic only) while each device keeps its
  Q shard; softmax is merged online (running max/sum, flash-attention style).
  Communication overlaps compute step-by-step; memory per device is
  O((S/n)^2) logits, O(S/n) activations. Backward is a second ring pass
  (custom VJP — dK/dV accumulators travel with their K/V blocks).
- **Ulysses attention** (`ulysses_attention`): two `all_to_all`s re-shard
  [B, H, S/n, D] -> [B, H/n, S, D], run dense (flash) attention on full
  sequence with a head shard, and shard back. One collective round-trip,
  requires heads % sp_size == 0.

Both are legal inside `shard_map`/`pjit` over a mesh with an `sp` axis and
compose with the dp/mp/pp axes used by fleet hybrid training.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "ring_attention",
    "ulysses_attention",
    "split_sequence",
    "gather_sequence",
    "sequence_parallel_scope",
    "active_sp_axis",
    "sp_local_offset",
    "build_context_parallel_step",
]

_sp_tls = threading.local()


@contextlib.contextmanager
def sequence_parallel_scope(axis_name: str):
    """Inside this scope, framework attention dispatches to ring attention over
    `axis_name`, and models offset their position ids by the shard offset.
    Only meaningful while tracing inside `shard_map` over a mesh with that axis."""
    prev = getattr(_sp_tls, "axis", None)
    _sp_tls.axis = axis_name
    try:
        yield
    finally:
        _sp_tls.axis = prev


def active_sp_axis():
    return getattr(_sp_tls, "axis", None)


def sp_local_offset(seq_local: int):
    """Global sequence offset of this device's shard (0 when SP inactive)."""
    ax = active_sp_axis()
    if ax is None:
        return 0
    return lax.axis_index(ax) * seq_local

_NEG_INF = -1e30


def _pvary(x, axis_name):
    """Mark x as device-varying over axis_name (shard_map carry typing)."""
    try:
        return lax.pcast(x, (axis_name,), to="varying")
    except (AttributeError, TypeError):
        try:
            return lax.pvary(x, (axis_name,))
        except (AttributeError, TypeError):
            return x


def _shift_perm(n):
    # each device hands its block to the previous device: after j steps,
    # device i holds the block that originated on device (i + j) % n
    return [(p, (p - 1) % n) for p in range(n)]


def _block_attn(q, k, v, sm_scale, causal, q_off, k_off):
    """One Q-shard x K-shard attention block with global-position causal mask.

    Returns (unnormalized out [B,H,Sq,D], row sum l [B,H,Sq], row max m [B,H,Sq]).
    All f32.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        qpos = q_off + jnp.arange(q.shape[2])
        kpos = k_off + jnp.arange(k.shape[2])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    if causal:
        p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, l, m


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention(q, k, v, axis_name, causal=True, scale=None):
    """Ring flash attention over mesh axis `axis_name`.

    q, k, v: [batch, heads, seq_local, head_dim] — sequence-sharded over
    `axis_name`. Returns [batch, heads, seq_local, head_dim] in q.dtype.
    """
    out, _ = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return out


def _ring_fwd_impl(q, k, v, axis_name, causal, scale):
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    sl = q.shape[2]
    sm_scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    qf = q.astype(jnp.float32)
    perm = _shift_perm(n)

    o0 = _pvary(jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32), axis_name)
    l0 = _pvary(jnp.zeros(q.shape[:3], jnp.float32), axis_name)
    m0 = _pvary(jnp.full(q.shape[:3], _NEG_INF, jnp.float32), axis_name)

    def step(carry, j):
        o, l, m, k_blk, v_blk = carry
        src = (idx + j) % n
        bo, bl, bm = _block_attn(qf, k_blk.astype(jnp.float32), v_blk, sm_scale,
                                 causal, idx * sl, src * sl)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)       # rescale old accumulator
        beta = jnp.exp(bm - m_new)       # rescale new block
        o = o * alpha[..., None] + bo * beta[..., None]
        l = l * alpha + bl * beta
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o, l, m_new, k_blk, v_blk), None

    (o, l, m, _, _), _ = lax.scan(step, (o0, l0, m0, k, v), jnp.arange(n))
    l_safe = jnp.where(l > 0, l, 1.0)
    out = (o / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


def _ring_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, scale, res, g):
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    sl = q.shape[2]
    sm_scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    perm = _shift_perm(n)

    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # [B,H,Sq]

    dq0 = jnp.zeros_like(qf)
    dk0 = _pvary(jnp.zeros(k.shape, jnp.float32), axis_name)
    dv0 = _pvary(jnp.zeros(v.shape, jnp.float32), axis_name)

    def step(carry, j):
        dq, k_blk, v_blk, dk_blk, dv_blk = carry
        src = (idx + j) % n
        kf = k_blk.astype(jnp.float32)
        vf = v_blk.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf,
                       preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = idx * sl + jnp.arange(sl)
            kpos = src * sl + jnp.arange(k.shape[2])
            mask = (qpos[:, None] >= kpos[None, :])[None, None]
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        dv_blk = dv_blk + jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
        dk_blk = dk_blk + jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        dk_blk = lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = lax.ppermute(dv_blk, axis_name, perm)
        return (dq, k_blk, v_blk, dk_blk, dv_blk), None

    (dq, _, _, dk, dv), _ = lax.scan(step, (dq0, k, v, dk0, dv0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ulysses_attention(q, k, v, axis_name, causal=True, scale=None,
                      attn_fn=None):
    """DeepSpeed-Ulysses style sequence parallelism over `axis_name`.

    q, k, v: [batch, heads, seq_local, head_dim], heads % axis_size == 0.
    all_to_all to [batch, heads_local, seq_full, head_dim], dense attention on
    the full sequence, all_to_all back.
    """
    n = lax.axis_size(axis_name)
    if q.shape[1] % n:
        raise ValueError(f"heads {q.shape[1]} not divisible by sp size {n}")

    def to_seq(x):   # [B, H, S/n, D] -> [B, H/n, S, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_heads(x):  # [B, H/n, S, D] -> [B, H, S/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_seq(q), to_seq(k), to_seq(v)
    if attn_fn is None:
        sm_scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                       kh.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * sm_scale
        if causal:
            sq = qh.shape[2]
            mask = jnp.tril(jnp.ones((sq, sq), bool))
            s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        oh = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32)).astype(q.dtype)
    else:
        oh = attn_fn(qh, kh, vh, causal=causal, scale=scale)
    return to_heads(oh)


def split_sequence(x, axis_name, seq_dim=1):
    """Take this device's sequence shard of a replicated tensor (in-graph)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    if x.shape[seq_dim] % n != 0:
        raise ValueError(
            f"sequence length {x.shape[seq_dim]} not divisible by "
            f"{axis_name!r} axis size {n}")
    sl = x.shape[seq_dim] // n
    return lax.dynamic_slice_in_dim(x, idx * sl, sl, axis=seq_dim)


def gather_sequence(x, axis_name, seq_dim=1):
    """All-gather sequence shards back to the full sequence (in-graph)."""
    return lax.all_gather(x, axis_name, axis=seq_dim, tiled=True)


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _default_loss_weight(labels):
    """Per-shard loss weight for the cross-shard weighted mean: the count of
    non-ignored target tokens (ignore_index=-100, matching
    nn.functional.cross_entropy's default) when the last labels tensor is
    integer-typed; otherwise the shard's token count (equal across shards, so
    it degenerates to a plain pmean)."""
    import jax.numpy as jnp

    if labels and jnp.issubdtype(jnp.asarray(labels[-1]).dtype, jnp.integer):
        return jnp.sum(jnp.asarray(labels[-1]) != -100).astype(jnp.float32)
    return jnp.float32(1.0)


def build_context_parallel_step(model, optimizer, loss_fn, mesh,
                                sp_axis: str = "sp", dp_axis: str = "dp",
                                donate: bool = True, loss_weight_fn=None):
    """Build (init_fn, step_fn, shard_batch) for dp x sp (context-parallel)
    training: batch dim sharded over `dp_axis`, sequence dim over `sp_axis`,
    parameters replicated. The whole step runs inside one `shard_map`; attention
    inside the model dispatches to `ring_attention` via `sequence_parallel_scope`.

    `loss_weight_fn(*labels) -> scalar` sets each shard's weight in the
    cross-shard loss/grad mean (default: valid-token count, see
    `_default_loss_weight`) so uneven ignore_index padding across shards still
    reproduces the global mean exactly.

    Mirrors `fleet.hybrid_train.build_hybrid_step`'s contract:
    step_fn(state, key, lr, inputs, labels) -> (loss, new_state).
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..core import rng as rng_mod, tape as tape_mod
    from ..core.tensor import Tensor

    params, buffers = model.functional_state()
    train_p = {k: v for k, v in params.items()
               if v is not None and not v.stop_gradient}
    frozen_p = {k: v for k, v in params.items()
                if v is not None and v.stop_gradient}
    opt_template = optimizer.functional_init(
        {k: v._value for k, v in train_p.items()})

    rep = NamedSharding(mesh, P())
    axes = set(mesh.axis_names)
    grad_axes = tuple(a for a in (dp_axis, sp_axis) if a in axes)

    def _batch_spec(ndim):
        # dim0 = batch over dp, dim1 = sequence over sp
        spec = [None] * ndim
        if ndim >= 1 and dp_axis in axes:
            spec[0] = dp_axis
        if ndim >= 2 and sp_axis in axes:
            spec[1] = sp_axis
        return P(*spec)

    def init_fn():
        return {
            "p": {k: jax.device_put(v._value, rep) for k, v in train_p.items()},
            "frozen": {k: jax.device_put(v._value, rep)
                       for k, v in frozen_p.items()},
            "b": {k: jax.device_put(v._value, rep)
                  for k, v in buffers.items() if v is not None},
            "opt": jax.tree_util.tree_map(
                lambda a: jax.device_put(a, rep), opt_template),
        }

    def local_step(state, key, lr, inputs, labels):
        # decorrelate dropout/rng across shards
        for a in grad_axes:
            key = jax.random.fold_in(key, lax.axis_index(a))

        def forward(pvals):
            with tape_mod.no_grad(), rng_mod.trace_rng_scope(key), \
                    sequence_parallel_scope(sp_axis):
                allp = {**pvals, **state["frozen"]}
                out, new_b = model.functional_call(
                    allp, state["b"], *[Tensor(x) for x in inputs])
            outs = out if isinstance(out, (tuple, list)) else [out]
            lv = loss_fn(*(list(outs) + [Tensor(x) for x in labels]))
            loss = lv._value if isinstance(lv, Tensor) else lv
            if loss.ndim > 0:
                loss = jnp.mean(loss)
            loss = loss.astype(jnp.float32)
            # Weight each shard's mean by its valid-token count INSIDE the
            # differentiated function: cross-shard activation flow (ring
            # permutes) mixes shards' contributions into every device's grad,
            # so the weight must scale the cotangent seed, not the result.
            # psum of these scaled losses == the global token-weighted mean.
            if grad_axes:
                if loss_weight_fn is not None:
                    w = loss_weight_fn(*[Tensor(x) for x in labels])
                    w = jnp.asarray(w._value if isinstance(w, Tensor) else w,
                                    dtype=jnp.float32)
                else:
                    w = _default_loss_weight(labels)
                # clamp: a batch with zero valid tokens everywhere must give
                # loss 0, not 0/0 NaN (which would poison params via the grads)
                loss = loss * w / jnp.maximum(lax.psum(w, grad_axes), 1e-8)
            return loss, new_b

        (loss, new_b), grads = jax.value_and_grad(
            forward, has_aux=True)(state["p"])
        if grad_axes:
            loss = lax.psum(loss, grad_axes)
            grads = jax.tree_util.tree_map(
                lambda g: lax.psum(g, grad_axes), grads)
        new_p, new_opt = optimizer.functional_update(
            state["p"], grads, state["opt"], lr)
        return loss, {"p": new_p, "frozen": state["frozen"], "b": new_b,
                      "opt": new_opt}

    def step(state, key, lr, inputs, labels):
        in_specs = (P(), P(), P(),
                    tuple(_batch_spec(np.ndim(x)) for x in inputs),
                    tuple(_batch_spec(np.ndim(x)) for x in labels))
        f = _shard_map(local_step, mesh, in_specs, (P(), P()))
        return f(state, key, lr, tuple(inputs), tuple(labels))

    step_jit = jax.jit(step, donate_argnums=(0,) if donate else ())

    from ._sharding_utils import make_shard_batch

    return init_fn, step_jit, make_shard_batch(mesh, _batch_spec)
