"""The plain reference of Granite 4.0-H (``model_type``
``granitemoehybrid``; Mamba-2: Dao & Gu 2024, arXiv:2405.21060 section 3):
the whole forward pass in ``jax.numpy`` and float32 with every matrix
product at ``highest`` precision. No cache, no kernel, no chunks, no
batching trick: the state-space recurrence is a ``lax.scan`` over tokens
from an empty state, attention is full causal attention over the whole
sequence with the KV heads repeated.

``params`` is the program's ``GraniteHybridForCausalLM.functional_state()``
by name (weights are ``[in, out]``; the convolution's taps ``[d_conv,
width]``, tap ``j`` multiplying the token ``d_conv - 1 - j`` back).
``cfg`` is anything with the ``config.json`` key names as attributes.
Departures from the published description are noted at their line.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mamba2(p, pre, y, cfg):
    """One sequence, y [s, hidden] -> [s, hidden]."""
    nh, hd, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    taps = cfg.mamba_d_conv
    d_inner = nh * hd
    width = d_inner + 2 * cfg.mamba_n_groups * n   # one group: B and C once
    s = y.shape[0]
    zxbcdt = mm(y, p[pre + "in_proj.weight"])
    z, xbc, dt = (zxbcdt[:, :d_inner], zxbcdt[:, d_inner:d_inner + width],
                  zxbcdt[:, d_inner + width:])
    # depthwise causal convolution: zeros stand before the sequence
    seq = jnp.concatenate([jnp.zeros((taps - 1, width)), xbc], axis=0)
    w = p[pre + "conv1d.weight"]
    conv = p[pre + "conv1d.bias"] + sum(w[j] * seq[j:j + s]
                                        for j in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(s, nh, hd)
    b_in, c_out = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    step = jax.nn.softplus(dt + p[pre + "dt_bias"])          # [s, heads]
    a = -jnp.exp(p[pre + "A_log"])                            # [heads]

    def token(state, inp):
        x_t, d_t, b_t, c_t = inp
        # S <- exp(D A) S + D x (x) B;   y = S C
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    _, ys = jax.lax.scan(token, jnp.zeros((nh, hd, n)),
                         (x, step, b_in, c_out))
    ys = ys + p[pre + "D"][None, :, None] * x
    # the gated norm: the gate first, then ONE RMS group over d_inner
    g = rms_norm(ys.reshape(s, d_inner) * jax.nn.silu(z),
                 p[pre + "norm.weight"], cfg.rms_norm_eps)
    return mm(g, p[pre + "out_proj.weight"])


def attention(p, pre, y, cfg):
    """One sequence: causal attention, no positional encoding ("nope"),
    the score scaled by ``attention_multiplier`` (1/64 at head size 64,
    NOT ``head_dim ** -0.5``), every KV head repeated over its group."""
    s = y.shape[0]
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    d = cfg.hidden_size // nq   # head_dim is not in the config
    q = mm(y, p[pre + "q_proj.weight"]).reshape(s, nq, d)
    k = mm(y, p[pre + "k_proj.weight"]).reshape(s, nkv, d)
    v = mm(y, p[pre + "v_proj.weight"]).reshape(s, nkv, d)
    k, v = (jnp.repeat(t, nq // nkv, axis=1) for t in (k, v))
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * cfg.attention_multiplier
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)
    return mm(o.reshape(s, nq * d), p[pre + "o_proj.weight"])


def mlp(p, pre, y, cfg):
    gu = mm(y, p[pre + "input_linear.weight"])
    f = cfg.shared_intermediate_size
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:],
              p[pre + "output_linear.weight"])


def forward(params, ids, cfg):
    """float32 logits [b, s, vocab] of ids [b, s]."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    table = p["model.embed_tokens.weight"]
    eps, res = cfg.rms_norm_eps, cfg.residual_multiplier

    def one(row):
        x = cfg.embedding_multiplier * table[row]
        for i, kind in enumerate(cfg.layer_types):
            pre = f"model.layers.{i}."
            y = rms_norm(x, p[pre + "input_layernorm.weight"], eps)
            m = mamba2(p, pre + "mamba.", y, cfg) if kind == "mamba" \
                else attention(p, pre + "self_attn.", y, cfg)
            x = x + res * m
            y = rms_norm(x, p[pre + "post_attention_layernorm.weight"], eps)
            x = x + res * mlp(p, pre + "shared_mlp.", y, cfg)
        x = rms_norm(x, p["model.norm.weight"], eps)
        # the tied head
        return mm(x, table.T) / cfg.logits_scaling

    return jnp.stack([one(row) for row in jnp.asarray(ids)])
