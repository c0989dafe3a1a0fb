"""The plain reference: GPT-3 as published (Brown et al. 2020; the GPT-2
block: pre-LayerNorm, fused qkv projection, causal softmax attention,
GELU (tanh form) MLP of 4x width, learned positions, tied embedding) in
straightforward ``jax.numpy`` and float32, its matrix products at
``highest`` precision. No kernel, no cache, no batching tricks, and no
import of the program. AdamW as Loshchilov & Hutter state it (decoupled
decay applied to every leaf, as the program's default does).

``policy`` selects the precision the *control* runs in:

- ``"f32"``: the reference itself.
- ``"bf16"``: every weight and activation rounded to bfloat16 (the step
  below the serving configuration's float32).
- ``"fp8"``: the operands of every matrix product rounded to float8 e4m3
  with a per-tensor scale, forward only, gradients straight through (the
  step below the training configuration's bfloat16 arithmetic).
- ``"bf16_master"``: float32 arithmetic, but the optimizer keeps the
  parameters and both moments in bfloat16 between steps (the step below
  the training configuration's float32 master weights and moments).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST
#: float8 e4m3 as ``reduce_precision`` has it: 4 exponent bits, 3 of
#: mantissa, largest finite value 240
F8_BITS, F8_MAX = (4, 3), 240.0
BF16_BITS = (8, 7)


def _round(x, bits):
    """Round float32 values to a narrower format and keep them in float32.
    ``reduce_precision`` and not a pair of casts: the TPU compiler may drop
    a cast down and up again as excess precision, and did."""
    return jax.lax.reduce_precision(x, *bits)


def _round_f8(x):
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = _round(x * scale, F8_BITS) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(policy: str):
    """The matrix product of a policy: (a [.., k], b [k, n]) -> [.., n]."""
    if policy in ("f32", "bf16_master"):
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if policy == "bf16":
        return lambda a, b: jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if policy == "fp8":
        return lambda a, b: jnp.matmul(_round_f8(a), _round_f8(b),
                                       precision=HIGHEST)
    raise ValueError(f"unknown policy {policy!r}")


def _layer_norm(x, w, b):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + LN_EPS)
    return (y * w + b).astype(x.dtype)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden_states(p: dict, ids, model: dict, policy: str = "f32"):
    """[b, s] token ids -> [b, s, h] after the final LayerNorm."""
    mm = _mm(policy)
    act = jnp.bfloat16 if policy == "bf16" else jnp.float32
    nh = model["num_heads"]
    b, s = ids.shape
    hd = model["hidden_size"] // nh
    g = lambda name: p[name].astype(act)  # noqa: E731
    x = g("gpt.wte.weight")[ids] + g("gpt.wpe.weight")[jnp.arange(s)][None]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(model["num_layers"]):
        pre = f"gpt.blocks.{i}."
        y = _layer_norm(x, g(pre + "ln1.weight"), g(pre + "ln1.bias"))
        qkv = mm(y, g(pre + "attn.qkv_proj.weight")) \
            + g(pre + "attn.qkv_proj.bias")
        qkv = qkv.reshape(b, s, 3, nh, hd)
        q, k, v = (jnp.transpose(qkv[:, :, j], (0, 2, 1, 3))
                   for j in range(3))                      # [b, nh, s, hd]
        if policy == "fp8":
            q, k, v = _round_f8(q), _round_f8(k), _round_f8(v)
        prec = None if policy == "bf16" else HIGHEST
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(act)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, v, precision=prec,
                       preferred_element_type=jnp.float32).astype(act)
        o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, nh * hd)
        x = x + (mm(o, g(pre + "attn.out_proj.weight"))
                 + g(pre + "attn.out_proj.bias")).astype(act)
        y = _layer_norm(x, g(pre + "ln2.weight"), g(pre + "ln2.bias"))
        y = _gelu((mm(y, g(pre + "mlp.fc1.weight"))
                   + g(pre + "mlp.fc1.bias")).astype(act))
        x = x + (mm(y, g(pre + "mlp.fc2.weight"))
                 + g(pre + "mlp.fc2.bias")).astype(act)
    return _layer_norm(x, g("gpt.ln_f.weight"), g("gpt.ln_f.bias"))


def logits_at(p: dict, ids, positions, model: dict, policy: str = "f32"):
    """float32 logits [b, n, vocab] of the sequences ``ids`` [b, s] at the
    given ``positions`` [b, n] (the logit at position t scores token
    t + 1)."""
    h = hidden_states(p, ids, model, policy)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    wte = p["gpt.wte.weight"]
    if policy == "bf16":
        wte = wte.astype(jnp.bfloat16)
    return _mm(policy)(h, wte.T).astype(jnp.float32)


def below_best(logits, tokens):
    """How far each of ``tokens`` [b, n] lies below the best of ``logits``
    [b, n, vocab] at its position: 0 where it is the best."""
    mine = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - mine


def token_gaps(p: dict, ids, positions, tokens, model: dict):
    """``below_best`` under the reference's own logits."""
    return below_best(logits_at(p, ids, positions, model), tokens)


def first_tokens(p: dict, ids, positions, model: dict, policy: str):
    """The token that ``policy`` puts first at each position."""
    return jnp.argmax(logits_at(p, ids, positions, model, policy), axis=-1)


def mean_loss(p: dict, ids, labels, model: dict, policy: str = "f32"):
    """Mean cross-entropy of ``labels`` [b, s] under the model, over every
    position."""
    h = hidden_states(p, ids, model, policy)
    logits = _mm(policy)(h, p["gpt.wte.weight"].T).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def loss_and_grads(p: dict, ids, labels, model: dict, policy: str = "f32",
                   rows: int = 2):
    """Loss and gradient of the whole batch, accumulated over blocks of
    ``rows`` rows so that the activations of one block fit beside the
    weights. Every block weighs by its share of the rows."""
    fn = _block_grad(_freeze(model), policy)
    n = ids.shape[0]
    loss, grads = None, None
    for at in range(0, n, rows):
        l, g = fn(p, ids[at:at + rows], labels[at:at + rows])
        share = min(rows, n - at) / n
        loss = l * share if loss is None else loss + l * share
        grads = _scaled(g, share) if grads is None \
            else _accumulate(grads, g, share)
    return loss, grads


def _freeze(model: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _block_grad(model_items: tuple, policy: str):
    model = dict(model_items)
    return jax.jit(jax.value_and_grad(
        lambda p, ids, labels: mean_loss(p, ids, labels, model, policy)))


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


def _parts(tree: dict) -> dict:
    """The leaves as the published model has them: a fused ``qkv_proj``
    leaf is the query, key and value projections side by side, and each is
    a leaf of its own here (the key's bias has no gradient under softmax;
    fused, it would hide in a leaf that has)."""
    out = {}
    for n, a in tree.items():
        if "qkv_proj" in n:
            for tag, part in zip("qkv", jnp.split(a, 3, axis=-1)):
                out[f"{n}[{tag}]"] = part
        else:
            out[n] = a
    return out


def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


@jax.jit
def leaf_norms(tree):
    return {n: _norm(a) for n, a in _parts(tree).items()}


@jax.jit
def delta_norms(new, old):
    new, old = _parts(new), _parts(old)
    return {n: _norm(new[n].astype(jnp.float32) - old[n].astype(jnp.float32))
            for n in new}


@functools.partial(jax.jit, donate_argnums=(0,))
def _through_bf16(tree):
    return jax.tree_util.tree_map(lambda a: _round(a, BF16_BITS), tree)


def follow_training(p0: dict, batches, model: dict, opt: dict,
                    policy: str = "f32", rows: int = 2,
                    keep_rows: slice | None = None) -> dict:
    """Follow ``len(batches)`` optimizer steps from ``p0`` (float32 leaves;
    consumed). Returns each step's loss, the per-leaf norm of the first
    gradient and the per-leaf norm of the parameters' change after the last
    step. ``keep_rows`` plants the fault "part of the batch left out, the
    mean taken over the rest"."""
    start = jax.tree_util.tree_map(jnp.copy, p0)
    p = p0
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norm = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        if keep_rows is not None:
            ids, labels = ids[keep_rows], labels[keep_rows]
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        loss, g = loss_and_grads(p, ids, labels, model, policy, rows)
        losses.append(float(loss))
        if grad_norm is None:
            grad_norm = {n: float(x) for n, x in leaf_norms(g).items()}
        p, m, v = adamw_step(
            p, g, m, v, jnp.float32(t), jnp.float32(opt["learning_rate"]),
            beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["epsilon"],
            decay=opt["weight_decay"])
        del g
        if policy == "bf16_master":
            p, m, v = _through_bf16((p, m, v))
    change = {n: float(x) for n, x in delta_norms(p, start).items()}
    return {"losses": losses, "grad_norm": grad_norm, "change_norm": change}
