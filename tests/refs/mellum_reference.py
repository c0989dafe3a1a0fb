"""The plain reference of Mellum 2 (``model_type`` ``mellum``; the
configuration of ``JetBrains/Mellum2-12B-A2.5B-Instruct``): the whole
forward pass in ``jax.numpy`` and float32 with every matrix product at
``highest`` precision. No cache, no kernel, no batching trick: attention is
masked attention over the whole sequence with the KV heads repeated, and
the expert layer multiplies every token by every expert and weighs the
results.

For layer ``l`` of kind ``layer_types[l]``, input ``x [T, hidden]`` at
positions ``0 .. T - 1``:

1. ``y = RMSNorm(x; w_in)``; ``q = y Wq`` as ``[T, heads, d]``, ``k = y
   Wk``, ``v = y Wv`` as ``[T, kv_heads, d]``; no bias.
2. Rotary over all ``d`` dimensions of ``q`` and ``k`` (rotate-half) with
   the parameters of the layer's kind (``rope_parameters``):
   ``sliding_attention`` plain, ``inv_freq_i = theta^(-2i/d)``;
   ``full_attention`` YaRN: ``inv_freq`` blended with ``inv_freq / factor``
   over the linear ramp between the correction dimensions of ``beta_fast``
   and ``beta_slow``, and cos and sin MULTIPLIED by ``attention_factor``
   (a full layer's scores carry its square). The blend is static: it
   applies at every length, below ``original_max_position_embeddings`` too.
3. Query head ``h`` attends KV head ``h // (heads / kv_heads)``. ``s_ij =
   q_i . k_j / sqrt(d)``, visible where ``j <= i`` and, in a
   ``sliding_attention`` layer, ``i - j < sliding_window`` (the token
   itself and the ``sliding_window - 1`` before it); float32 softmax; ``x
   = x + concat(P v) Wo``.
4. ``y = RMSNorm(x; w_post)``; ``g = softmax(y Wr)`` over ALL experts in
   float32; the top ``num_experts_per_tok`` of ``g``, each divided by the
   sum of those (``norm_topk_prob``); ``x = x + sum_k g_k (SiLU(y Wgate_k)
   * (y Wup_k)) Wdown_k``.
5. After the last layer ``RMSNorm(x; w_f)`` and the untied head.

``params`` is the program's ``MellumForCausalLM.functional_state()`` by
name (weights are ``[in, out]``, the experts' stacked ``[experts, in,
out]``). ``cfg`` is anything with the ``config.json`` key names as
attributes.

Departures from what ``config.json`` leaves open, the same in the program
(``paddle_tpu/text/mellum.py``) and in the benchmark's copy
(``benchmark/families/mellum.py``):

- no QK-norm: the config has no key for one (a per-head RMSNorm of ``q``
  and ``k`` would add ``2 d`` parameters a layer and change no shape, no
  cache and no byte count);
- no multi-token-prediction head (the model card mentions one; the config
  has no key for it);
- ``intermediate_size`` is unused: every layer of ``mlp_layer_types`` is
  ``sparse``, none dense;
- ``max_window_layers`` and ``use_sliding_window`` say nothing that
  ``layer_types`` does not.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary_table(positions, dim: int, rp: dict):
    """``(cos, sin)`` ``[T, dim]`` of a layer kind's ``rope_parameters``
    entry ``rp``, its ``attention_factor`` multiplied in."""
    theta = rp["rope_theta"] ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    factor = 1.0
    if rp["rope_type"] == "yarn":
        def correction_dim(rotations):
            return dim * math.log(rp["original_max_position_embeddings"]
                                  / (rotations * 2 * math.pi)) \
                / (2 * math.log(rp["rope_theta"]))

        low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        theta = theta / rp["factor"] * ramp + theta * (1.0 - ramp)
        factor = rp["attention_factor"]
    elif rp["rope_type"] != "default":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    ang = positions.astype(jnp.float32)[:, None] * theta[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rotate(x, cos, sin):
    """Rotate-half: x [T, heads, d]."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def attention(p, pre, y, kind: str, cfg):
    """One sequence, y [T, hidden] -> [T, hidden]."""
    t = y.shape[0]
    nq, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = mm(y, p[pre + "q_proj.weight"]).reshape(t, nq, d)
    k = mm(y, p[pre + "k_proj.weight"]).reshape(t, nkv, d)
    v = mm(y, p[pre + "v_proj.weight"]).reshape(t, nkv, d)
    cos, sin = rotary_table(jnp.arange(t), d, cfg.rope_parameters[kind])
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    k, v = (jnp.repeat(a, nq // nkv, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / math.sqrt(d)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if kind == "sliding_attention":
        seen &= i - j < cfg.sliding_window
    w = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)
    return mm(o.reshape(t, nq * d), p[pre + "o_proj.weight"])


def experts(p, pre, y, cfg):
    """The expert layer, y [T, hidden] -> [T, hidden]: every expert over
    every token, weighed by the router."""
    g = jax.nn.softmax(mm(y, p[pre + "gate.weight"]), axis=-1)
    top, idx = jax.lax.top_k(g, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    # [T, experts]: a chosen expert's weight, 0 elsewhere
    w = jnp.zeros_like(g).at[jnp.arange(y.shape[0])[:, None], idx].set(top)
    gate = jnp.einsum("th,ehf->etf", y, p[pre + "experts.gate_proj"],
                      precision=HIGHEST)
    up = jnp.einsum("th,ehf->etf", y, p[pre + "experts.up_proj"],
                    precision=HIGHEST)
    out = jnp.einsum("etf,efh->eth", jax.nn.silu(gate) * up,
                     p[pre + "experts.down_proj"], precision=HIGHEST)
    return jnp.einsum("te,eth->th", w, out, precision=HIGHEST)


def forward_one(p, ids, cfg):
    """float32 logits [T, vocab] of one sequence ``ids`` [T]."""
    p = {n: jnp.asarray(a, jnp.float32) for n, a in p.items()}
    x = p["model.embed_tokens.weight"][ids]
    for i, kind in enumerate(cfg.layer_types):
        pre = f"model.layers.{i}."
        y = rms_norm(x, p[pre + "input_layernorm.weight"], cfg.rms_norm_eps)
        x = x + attention(p, pre + "self_attn.", y, kind, cfg)
        y = rms_norm(x, p[pre + "post_attention_layernorm.weight"],
                     cfg.rms_norm_eps)
        x = x + experts(p, pre + "mlp.", y, cfg)
    x = rms_norm(x, p["model.norm.weight"], cfg.rms_norm_eps)
    return mm(x, p["lm_head.weight"])


def forward(p, ids, cfg):
    """float32 logits [b, T, vocab] of the sequences ``ids`` [b, T]."""
    return jnp.stack([forward_one(p, row, cfg) for row in jnp.asarray(ids)])
