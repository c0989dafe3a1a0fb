"""The plain reference of Brumby (``model_type`` ``brumby``; the
configuration of ``manifestai/Brumby-14B-Base``): the whole forward pass in
``jax.numpy`` and float32 with every matrix product at ``highest``
precision, and the retention layer in its ATTENTION form: a quadratic sum
over the earlier positions, with no state, no chunk and no cache. The
program (``paddle_tpu/text/brumby.py``) computes the same layer in two
other forms, a state carried token by token and a chunked one; this one is
independent of both.

Inputs ``x = E[ids]`` ``[T, hidden]`` at positions ``0 .. T - 1``. Layers
``l = 0 .. L - 1`` in order, each the Qwen3 block with its attention
replaced by gated power retention of degree 2 (Gelada, Buckman, Zhang et
al., *Scaling Context Requires Rethinking Attention*, arXiv:2507.04239):

1. ``y = RMSNorm(x; w_in)``; ``q = y Wq`` ``[T, heads, d]``, ``k = y Wk``,
   ``v = y Wv`` ``[T, kv_heads, d]``, no bias. ``q`` and ``k`` a head each
   through an RMSNorm over ``d`` (``q_norm``, ``k_norm``), then rotate-half
   rotary over all ``d`` at ``theta^(-2i/d)``, no scaling.
2. The gate a KV head: ``log gamma_t = logsigmoid(y_t . w_g + b_g)``.
3. For query head ``h`` of KV group ``g`` at position ``t``::

       w_ts = exp(sum_{l = s+1 .. t} log gamma_l) * (q_t . k_s)^2   (s <= t)
       o_t = sum_s w_ts v_s / (sum_s w_ts + eps)

   then ``x = x + concat(o) Wo``.
4. ``m = RMSNorm(x; w_post)``; ``x = x + (SiLU(m Wgate) * (m Wup)) Wdown``.

After the last layer ``logits = RMSNorm(x; w_final) Whead`` in float32,
untied. Every norm uses ``rms_norm_eps``.

What ``config.json`` has no key for, assumed the same in the program and
in the benchmark's copy (``benchmark/families/brumby.py``); each is marked
``assumed (n)`` where it is used below:

1. the degree is 2 (the paper's and the ``retention`` library's default):
   the weight of a key is ``(q . k)^2``;
2. one gate a KV head, a linear map of the normed input through
   ``logsigmoid`` (the library's ``log_G``): a group's query heads share
   the key's decay as they share its key and value;
3. the base block's QK-norm and rotary positions are kept (the config
   keeps ``rope_theta`` and ``max_position_embeddings``);
4. the normaliser is the gated sum of the same weights (the library's
   ``sum_of_keys``), with ``eps`` = 1e-6. A scale on ``q . k`` would cancel
   in the ratio but for ``eps``: there is none.

``params`` is the program's ``BrumbyForCausalLM.functional_state()`` by
name (weights ``[in, out]``). ``cfg`` is anything with the ``config.json``
key names as attributes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: assumed (4): the normaliser's floor
EPS = 1e-6


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


def retention(p, pre, y, cfg):
    """Gated power retention of one sequence, y [T, hidden], in attention
    form."""
    t = y.shape[0]
    h, d, eps = cfg.num_attention_heads, cfg.head_dim, cfg.rms_norm_eps
    q = mm(y, p[pre + "q_proj.weight"]).reshape(t, h, d)
    k = mm(y, p[pre + "k_proj.weight"]).reshape(t, -1, d)
    v = mm(y, p[pre + "v_proj.weight"]).reshape(t, -1, d)
    # assumed (3): QK-norm and rotary, as the Qwen3 block has them
    q = rms_norm(q, p[pre + "q_norm.weight"], eps)
    k = rms_norm(k, p[pre + "k_norm.weight"], eps)
    cos, sin = rotary_table(jnp.arange(t), d, float(cfg.rope_theta))
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    # assumed (2): a gate a KV head, repeated over its group as k and v are
    log_g = jax.nn.log_sigmoid(mm(y, p[pre + "gate.weight"])
                               + p[pre + "gate.bias"])      # [T, kv]
    rep = h // k.shape[1]
    kr, vr = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    cum = jnp.repeat(jnp.cumsum(log_g, axis=0), rep, axis=1)  # [T, h]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    # the decay from position s to t: gates s+1 .. t
    decay = jnp.exp(jnp.where(seen[None], cum.T[:, :, None]
                              - cum.T[:, None, :], -jnp.inf))  # [h, t, s]
    # assumed (1): degree 2
    w = decay * jnp.square(jnp.einsum("qhd,khd->hqk", q, kr,
                                      precision=HIGHEST))
    # assumed (4): the gated sum of the same weights normalises
    o = jnp.einsum("hqk,khd->qhd", w, vr, precision=HIGHEST) \
        / (jnp.sum(w, axis=-1).T[..., None] + EPS)
    return mm(o.reshape(t, h * d), p[pre + "o_proj.weight"])


def layer(p, i, x, cfg):
    pre = f"model.layers.{i}."
    eps = cfg.rms_norm_eps
    y = rms_norm(x, p[pre + "input_layernorm.weight"], eps)
    x = x + retention(p, pre + "self_attn.", y, cfg)
    m = rms_norm(x, p[pre + "post_attention_layernorm.weight"], eps)
    return x + mm(jax.nn.silu(mm(m, p[pre + "mlp.gate_proj.weight"]))
                  * mm(m, p[pre + "mlp.up_proj.weight"]),
                  p[pre + "mlp.down_proj.weight"])


def forward_one(p, ids, cfg):
    """float32 logits [T, vocab] of one sequence."""
    p = {n: jnp.asarray(a, jnp.float32) for n, a in p.items()}
    with jax.default_matmul_precision("highest"):
        x = p["model.embed_tokens.weight"][ids]
        for i in range(cfg.num_hidden_layers):
            x = layer(p, i, x, cfg)
        x = rms_norm(x, p["model.norm.weight"], cfg.rms_norm_eps)
        return mm(x, p["lm_head.weight"])


def forward(p, ids, cfg):
    """float32 logits [b, T, vocab] of a batch, a sequence at a time."""
    return jnp.stack([forward_one(p, jnp.asarray(row), cfg)
                      for row in jnp.asarray(ids)])
