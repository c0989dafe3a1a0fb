"""What every family's plain reference shares: the precisions a *control*
runs in, the comparison of served tokens with the reference's logits,
AdamW as Loshchilov & Hutter state it (decoupled decay applied to every
leaf, as the program's default does) and the following of a training run.
The model itself — its forward pass, its loss, the parts of its fused
leaves — is the family's copy (``benchmark/families/<family>.py``), in
straightforward ``jax.numpy`` and float32, its matrix products at
``highest`` precision: no kernel, no cache, no batching tricks, and no
import of the program.

``policy`` selects the precision the *control* runs in:

- ``"f32"``: the reference itself.
- ``"bf16"``: every weight and activation rounded to bfloat16 (the step
  below a serving configuration's float32).
- ``"fp8"``: the operands of every matrix product rounded to float8 e4m3
  with a per-tensor scale, forward only, gradients straight through (the
  step below a training configuration's bfloat16 arithmetic).
- ``"bf16_master"``: float32 arithmetic, but the optimizer keeps the
  parameters and both moments in bfloat16 between steps (the step below
  a training configuration's float32 master weights and moments).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: float8 e4m3 as ``reduce_precision`` has it: 4 exponent bits, 3 of
#: mantissa, largest finite value 240
F8_BITS, F8_MAX = (4, 3), 240.0
BF16_BITS = (8, 7)


def round_to(x, bits):
    """Round float32 values to a narrower format and keep them in float32.
    ``reduce_precision`` and not a pair of casts: the TPU compiler may drop
    a cast down and up again as excess precision, and did."""
    return jax.lax.reduce_precision(x, *bits)


def round_f8(x):
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = round_to(x * scale, F8_BITS) / scale
    return x + jax.lax.stop_gradient(q - x)


def mm(policy: str):
    """The matrix product of a policy: (a [.., k], b [k, n]) -> [.., n]."""
    if policy in ("f32", "bf16_master"):
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if policy == "bf16":
        return lambda a, b: jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if policy == "fp8":
        return lambda a, b: jnp.matmul(round_f8(a), round_f8(b),
                                       precision=HIGHEST)
    raise ValueError(f"unknown policy {policy!r}")


@jax.jit
def below_best(logits, tokens):
    """How far each of ``tokens`` [b, n] lies below the best of ``logits``
    [b, n, vocab] at its position: 0 where it is the best."""
    mine = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - mine


def first_tokens(logits_at, leaves_of, ids, positions, model: dict,
                 policy: str):
    """The token that ``policy`` puts first at each position, under a
    family's ``logits_at``."""
    return jnp.argmax(logits_at(leaves_of, ids, positions, model, policy),
                      axis=-1)


def freeze(model: dict) -> tuple:
    """A configuration's ``model`` group as a static argument of a jitted
    function."""
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _block_grad(mean_loss, model_items: tuple, policy: str):
    model = dict(model_items)
    return jax.jit(jax.value_and_grad(
        lambda p, ids, labels: mean_loss(p, ids, labels, model, policy)))


def loss_and_grads_by_blocks(mean_loss, p: dict, ids, labels, model: dict,
                             policy: str = "f32", rows: int = 2):
    """Loss and gradient of the whole batch under a family's ``mean_loss``,
    accumulated over blocks of ``rows`` rows so that the activations of one
    block fit beside the weights. Every block weighs by its share of the
    rows."""
    fn = _block_grad(mean_loss, freeze(model), policy)
    n = ids.shape[0]
    loss, grads = None, None
    for at in range(0, n, rows):
        l, g = fn(p, ids[at:at + rows], labels[at:at + rows])
        share = min(rows, n - at) / n
        loss = l * share if loss is None else loss + l * share
        grads = _scaled(g, share) if grads is None \
            else _accumulate(grads, g, share)
    return loss, grads


@jax.jit
def _scaled(g, share):
    return jax.tree_util.tree_map(lambda a: a * share, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, g, share):
    return jax.tree_util.tree_map(lambda a, b: a + b * share, acc, g)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3),
                   static_argnames=("beta1", "beta2", "eps", "decay"))
def adamw_step(p, g, m, v, step, lr, *, beta1, beta2, eps, decay):
    """One AdamW update of every leaf, in float32."""
    def leaf(p, g, m, v):
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        m_hat = m / (1 - beta1 ** step)
        v_hat = v / (1 - beta2 ** step)
        p = p * (1.0 - lr * decay) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
        return p, m, v
    out = {n: leaf(p[n], g[n], m[n], v[n]) for n in p}
    return ({n: o[0] for n, o in out.items()},
            {n: o[1] for n, o in out.items()},
            {n: o[2] for n, o in out.items()})


def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


@functools.partial(jax.jit, static_argnames="parts")
def leaf_norms(tree, parts):
    """The norm of every leaf, a fused one as the ``parts`` that its
    family's ``parts`` splits it into."""
    return {n: _norm(a) for n, a in parts(tree).items()}


@functools.partial(jax.jit, static_argnames="parts")
def delta_norms(new, old, parts):
    new, old = parts(new), parts(old)
    return {n: _norm(new[n].astype(jnp.float32) - old[n].astype(jnp.float32))
            for n in new}


@functools.partial(jax.jit, donate_argnums=(0,))
def _through_bf16(tree):
    return jax.tree_util.tree_map(lambda a: round_to(a, BF16_BITS), tree)


def follow_training(family, p0: dict, batches, model: dict, opt: dict,
                    policy: str = "f32", rows: int = 2,
                    keep_rows: slice | None = None) -> dict:
    """Follow ``len(batches)`` optimizer steps from ``p0`` (float32 leaves;
    consumed) under ``family``'s ``loss_and_grads`` and ``parts``. Returns
    each step's loss, the per-leaf norm of the first gradient and the
    per-leaf norm of the parameters' change after the last step.
    ``keep_rows`` plants the fault "part of the batch left out, the mean
    taken over the rest"."""
    start = jax.tree_util.tree_map(jnp.copy, p0)
    p = p0
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norm = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        if keep_rows is not None:
            ids, labels = ids[keep_rows], labels[keep_rows]
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        loss, g = family.loss_and_grads(p, ids, labels, model, policy, rows)
        losses.append(float(loss))
        if grad_norm is None:
            grad_norm = {n: float(x) for n, x in
                         leaf_norms(g, family.parts).items()}
        p, m, v = adamw_step(
            p, g, m, v, jnp.float32(t), jnp.float32(opt["learning_rate"]),
            beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["epsilon"],
            decay=opt["weight_decay"])
        del g
        if policy == "bf16_master":
            p, m, v = _through_bf16((p, m, v))
    change = {n: float(x) for n, x in
              delta_norms(p, start, family.parts).items()}
    return {"losses": losses, "grad_norm": grad_norm, "change_norm": change}
