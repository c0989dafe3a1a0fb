"""The plain reference of the ``kimi_k2`` layer (the DeepSeek-V3 layer,
arXiv:2412.19437 section 2.1; MLA from DeepSeek-V2, arXiv:2405.04434
section 2.1): the whole forward pass in ``jax.numpy`` and float32 with
every matrix product at ``highest`` precision. No cache, no kernel, no
batching trick: attention is the expanded form over the whole sequence,
and the expert layer is a loop over all ``n_routed_experts``.

``params`` is the program's ``KimiK2ForCausalLM.functional_state()`` by
name (weights are ``[in, out]``). ``cfg`` is anything with the
``config.json`` key names as attributes. Departures from the published
description are noted at their line.
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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, rs: dict):
    """The ``dim / 2`` rotary frequencies under YaRN: each ``theta_i`` is
    blended between itself and ``theta_i / factor`` by the linear ramp
    between the correction dimensions of ``beta_fast`` and ``beta_slow``
    at the original context length."""
    theta = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001  # as published: no division by zero
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return theta / rs["factor"] * ramp + theta * (1.0 - ramp)


def softmax_scale(cfg) -> float:
    rs = cfg.rope_scaling
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def rope(x, positions, cfg):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of the last axis
    by ``position * theta_i``. x: [b, s, ..., d]; positions: [b, s]. The
    published code moves the pairs apart first (even entries, then odd) and
    rotates halves; queries and keys are permuted alike, so every score is
    the same, and the pairs stay where they are here."""
    rs = cfg.rope_scaling
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] \
        * yarn_inv_freq(d, cfg.rope_theta, rs)          # [b, s, d/2]
    # the tables' own scale, mscale / mscale_all_dim: 1 as published
    t = yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * t, jnp.sin(ang) * t
    while cos.ndim < x.ndim:
        cos, sin = cos[:, :, None], sin[:, :, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape)


def mla(p, pre, y, positions, cfg):
    """Expanded multi-head latent attention over the whole sequence."""
    b, s, _ = y.shape
    nh, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    r = cfg.kv_lora_rank
    c_q = rms_norm(mm(y, p[pre + "q_a_proj.weight"]),
                   p[pre + "q_a_layernorm.weight"], cfg.rms_norm_eps)
    q = mm(c_q, p[pre + "q_b_proj.weight"]).reshape(b, s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], positions, cfg)
    kv_a = mm(y, p[pre + "kv_a_proj_with_mqa.weight"])
    c_kv = rms_norm(kv_a[..., :r], p[pre + "kv_a_layernorm.weight"],
                    cfg.rms_norm_eps)
    k_rope = rope(kv_a[..., r:], positions, cfg)      # one for all heads
    kv = mm(c_kv, p[pre + "kv_b_proj.weight"]).reshape(b, s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    sc = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, precision=HIGHEST)
          + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope, precision=HIGHEST)
          ) * softmax_scale(cfg)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
    return mm(o.reshape(b, s, nh * dv), p[pre + "o_proj.weight"])


def gated_mlp(y, gate, up, down):
    return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)


def route(p, pre, y, cfg):
    """(weights [..., k], experts [..., k]) of the router: sigmoid scores,
    top-k of the scores plus the correction bias, the weights the scores
    themselves, normalised and scaled. ``n_group`` = ``topk_group`` = 1 as
    published, so no group limits the choice."""
    sig = jax.nn.sigmoid(mm(y, p[pre + "gate.weight"]))
    _, idx = jax.lax.top_k(sig + p[pre + "gate.e_score_correction_bias"],
                           cfg.num_experts_per_tok)
    w = jnp.take_along_axis(sig, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, idx


def moe(p, pre, y, cfg, held=None, shared=True):
    """The expert layer. ``held = (first, count)``: the rows of the expert
    stacks in ``p`` are experts ``first .. first + count - 1`` of the
    router's ``n_routed_experts``; what the other experts would add is
    left out (a chip's share, with no exchange). None: the stacks hold
    every expert. ``shared=False`` leaves the shared expert out (it is
    counted once when shares are added up)."""
    first, count = held or (0, cfg.n_routed_experts)
    w, idx = route(p, pre, y, cfg)
    out = jnp.zeros_like(y)
    for e in range(cfg.n_routed_experts):
        if not first <= e < first + count:
            continue
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        out = out + w_e * gated_mlp(
            y, p[pre + "experts.gate_proj"][e - first],
            p[pre + "experts.up_proj"][e - first],
            p[pre + "experts.down_proj"][e - first])
    if shared:
        sh = pre + "shared_experts."
        out = out + gated_mlp(y, p[sh + "gate_proj.weight"],
                              p[sh + "up_proj.weight"],
                              p[sh + "down_proj.weight"])
    return out


def layer(p, i, x, positions, cfg, held=None):
    pre = f"model.layers.{i}."
    y = rms_norm(x, p[pre + "input_layernorm.weight"], cfg.rms_norm_eps)
    x = x + mla(p, pre + "self_attn.", y, positions, cfg)
    y = rms_norm(x, p[pre + "post_attention_layernorm.weight"],
                 cfg.rms_norm_eps)
    if i < cfg.first_k_dense_replace:
        return x + gated_mlp(y, p[pre + "mlp.gate_proj.weight"],
                             p[pre + "mlp.up_proj.weight"],
                             p[pre + "mlp.down_proj.weight"])
    return x + moe(p, pre + "mlp.", y, cfg, held)


def forward(params, ids, cfg, held=None):
    """[b, s] token ids -> float32 logits [b, s, vocab_size]. Left out, as
    the configuration's file says: the vision tower (text only), the
    training-only keys, multi-token prediction
    (``num_nextn_predict_layers`` 0)."""
    p = {n: jnp.asarray(a, jnp.float32) for n, a in params.items()}
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = p["model.embed_tokens.weight"][ids]
    for i in range(cfg.num_hidden_layers):
        x = layer(p, i, x, positions, cfg, held)
    x = rms_norm(x, p["model.norm.weight"], cfg.rms_norm_eps)
    return mm(x, p["lm_head.weight"])
