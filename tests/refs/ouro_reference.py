"""The plain reference of Ouro (``model_type`` ``ouro``; the configuration of
``ByteDance/Ouro-2.6B``, the LoopLM family of "Scaling Latent Reasoning via
Looped Language Models", 2025): the whole forward pass in ``jax.numpy`` and
float32 with every matrix product at ``highest`` precision. No cache, no
kernel, no batching trick: attention is masked attention over the whole
sequence, and every loop runs the whole stack again.

Inputs ``x0 = E[ids]`` ``[T, hidden]`` at positions ``0 .. T - 1``, the same
in every loop. For loop ``t = 1 .. total_ut_steps``, with ``h = h_{t-1}``
(``h_0 = x0``), layers ``l = 0 .. L - 1`` in order:

1. ``a = RMSNorm(h; w_in1)``; ``q = a Wq``, ``k = a Wk``, ``v = a Wv``, each
   ``[T, heads, d]``, no bias. Rotate-half rotary over all ``d`` dimensions
   of ``q`` and ``k``, ``inv_freq_i = theta^(-2i/d)``, no scaling. ``s_ij =
   q_i . k_j / sqrt(d)``, visible where ``j <= i``; float32 softmax; ``o =
   P v``; ``h = h + RMSNorm(concat(o) Wo; w_in2)``.
2. ``m = RMSNorm(h; w_post1)``; ``h = h + RMSNorm((SiLU(m Wgate) * (m Wup))
   Wdown; w_post2)``.
3. After the last layer: ``h_t = RMSNorm(h; w_final)``, loop ``t``'s output
   and loop ``t + 1``'s input.
4. The exit gate: ``lambda_t = sigmoid(h_t . w_gate + b_gate)`` a position;
   ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < n`` and ``p_n =
   prod_{j<n} (1 - lambda_j)``.
5. The logits: ``h_n Whead`` in float32, untied. With
   ``early_exit_threshold`` 1 the cumulative exit probability first reaches
   1 at the last loop, so every token runs all loops and its logits are
   the last loop's.

Every norm uses ``rms_norm_eps``. Loop ``t``'s attention at position ``i``
sees the keys and values that loop ``t`` computed at ``j <= i``: a cache a
(loop, layer).

What ``config.json`` has no key for, from the published modeling code and
the paper, the same in the program (``paddle_tpu/text/ouro.py``) and in the
benchmark's copy (``benchmark/families/ouro.py``):

- the norm after each sub-layer (``input_layernorm_2``,
  ``post_attention_layernorm_2``) beside the one before it;
- the final norm applied at the end of EVERY loop and fed into the next;
- a cache for each (loop, layer);
- the exit gate as one linear map ``hidden -> 1`` with a bias;
- no QK-norm and no attention bias (either would change no shape, cache
  or byte count).

``max_window_layers``, ``use_sliding_window`` false and ``sliding_window``
null say that no layer has a window.

``params`` is the program's ``OuroForCausalLM.functional_state()`` by name
(weights ``[in, out]``). ``cfg`` is anything with the ``config.json`` key
names as attributes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary_table(positions, dim: int, theta: float):
    """``(cos, sin)`` ``[T, dim]``: rotate-half pairs ``i`` and ``i + dim /
    2`` at ``inv_freq_i = theta^(-2i/dim)``."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """x [T, heads, d]; cos, sin [T, d]."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def attention(p, pre, a, cfg, keys_of=None):
    """Causal attention of one sequence, a [T, hidden]. ``keys_of``: the
    (k, v) that the positions BEFORE each query read, where they are not
    this call's own (a fault the tests plant: every loop reading loop 1's
    keys); a query still reads its own position's."""
    t = a.shape[0]
    h, d = cfg.num_attention_heads, cfg.head_dim
    q = mm(a, p[pre + "q_proj.weight"]).reshape(t, h, d)
    k = mm(a, p[pre + "k_proj.weight"]).reshape(t, -1, d)
    v = mm(a, p[pre + "v_proj.weight"]).reshape(t, -1, d)
    cos, sin = rotary_table(jnp.arange(t), d, float(cfg.rope_theta))
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    rep = h // k.shape[1]
    kr, vr = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kr, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(d))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    if keys_of is not None:
        k0, v0 = (jnp.repeat(x, rep, axis=1) for x in keys_of)
        s0 = jnp.einsum("qhd,khd->hqk", q, k0, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(d))
        s = jnp.where(j < i, s0, s)
    w = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    if keys_of is None:
        o = jnp.einsum("hqk,khd->qhd", w, vr, precision=HIGHEST)
    else:
        o = jnp.einsum("hqk,khd->qhd", jnp.where(j < i, w, 0.0), v0,
                       precision=HIGHEST) \
            + jnp.einsum("hqk,khd->qhd", jnp.where(j == i, w, 0.0), vr,
                         precision=HIGHEST)
    return mm(o.reshape(t, h * d), p[pre + "o_proj.weight"]), (k, v)


def layer(p, i, h, cfg, keys_of=None):
    """Layer ``i`` over one sequence's state ``h`` [T, hidden]; returns the
    new state and the layer's (k, v)."""
    pre = f"model.layers.{i}."
    eps = cfg.rms_norm_eps
    a = rms_norm(h, p[pre + "input_layernorm.weight"], eps)
    o, kv = attention(p, pre + "self_attn.", a, cfg, keys_of)
    h = h + rms_norm(o, p[pre + "input_layernorm_2.weight"], eps)
    m = rms_norm(h, p[pre + "post_attention_layernorm.weight"], eps)
    f = mm(jax.nn.silu(mm(m, p[pre + "mlp.gate_proj.weight"]))
           * mm(m, p[pre + "mlp.up_proj.weight"]),
           p[pre + "mlp.down_proj.weight"])
    return h + rms_norm(f, p[pre + "post_attention_layernorm_2.weight"],
                        eps), kv


def exit_probabilities(lambdas):
    """``[n, ...]`` gate values to the exit distribution over the loops:
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the last loop the rest."""
    n = lambdas.shape[0]
    stay = jnp.cumprod(1.0 - lambdas, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    probs = lambdas * before
    return probs.at[n - 1].set(before[n - 1])


def forward_one(p, ids, cfg, with_exits: bool = False,
                loop_zero_keys: bool = False):
    """float32 logits [T, vocab] of one sequence (and, ``with_exits``, the
    exit probabilities [total_ut_steps, T]). ``loop_zero_keys``: the fault
    in which every loop after the first reads the FIRST loop's keys and
    values at earlier positions (cache sharing across loops): not the
    model."""
    h = p["model.embed_tokens.weight"][ids].astype(jnp.float32)
    lambdas, first = [], []
    for t in range(cfg.total_ut_steps):
        for i in range(cfg.num_hidden_layers):
            shared = first[i] if (loop_zero_keys and t > 0) else None
            h, kv = layer(p, i, h, cfg, shared)
            if t == 0:
                first.append(kv)
        h = rms_norm(h, p["model.norm.weight"], cfg.rms_norm_eps)
        lambdas.append(jax.nn.sigmoid(
            mm(h, p["model.early_exit_gate.weight"])[:, 0]
            + p["model.early_exit_gate.bias"][0]))
    logits = mm(h, p["lm_head.weight"])
    if with_exits:
        return logits, exit_probabilities(jnp.stack(lambdas))
    return logits


def forward(p, ids, cfg, with_exits: bool = False):
    """float32 logits [b, T, vocab] of a batch, a sequence at a time (and
    the exit probabilities [b, total_ut_steps, T])."""
    outs = [forward_one(p, jnp.asarray(row), cfg, with_exits)
            for row in jnp.asarray(ids)]
    if with_exits:
        return (jnp.stack([o[0] for o in outs]),
                jnp.stack([o[1] for o in outs]))
    return jnp.stack(outs)
