"""The ``kimi_k2`` family: everything the benchmark knows of Kimi-K2
(``model_type`` ``kimi_k2``), whose layer is the DeepSeek-V3 layer
(arXiv:2412.19437 section 2.1; MLA from DeepSeek-V2, arXiv:2405.04434
section 2.1): pre-RMSNorm blocks of multi-head latent attention with a
decoupled rotary key under YaRN frequencies, a gated SiLU MLP in the
leading dense layer and a sigmoid-routed expert layer with a shared expert
in every later one, a final RMSNorm and an untied head. Serving only.

A configuration's ``model`` group has the ``config.json`` key names. One
chip of an expert-parallel deployment holds ``n_routed_experts`` of the
router's ``router_width`` experts (``held_experts_first`` is the first):
the router keeps its published width and top-k, the chip computes its own
experts' part of the sum, and the reference is given the same share.

The parts, in the order ``families/gpt3.py`` has them: ``check`` and
``leaf_table``; ``build_serving``; the plain reference ``logits_at``,
which imports nothing of the program and draws one layer's float32 leaves
at a time (the whole model in float32 is 16.7 GB); ``WORK``.
"""
from __future__ import annotations

import functools
import math
import weakref

import jax
import jax.numpy as jnp

from benchmark.lib import reference
from benchmark.lib.reference import HIGHEST, round_f8
from benchmark.lib.weights import num_params

#: bytes a weight and a cached value take: the configuration's precision
#: (``check`` holds the file to it)
ITEM = 2
#: a pool row as the program lays it out: whole 128-lane rows
LANES = 128

#: The router's selection bias (``e_score_correction_bias``) is USED at this
#: share of what the harness draws for a leaf of kind ``bias`` (0.01 N), by
#: the program and the reference alike (``as_used``). The published bias is
#: what balances the experts' load. Drawn whole, 0.01 on sigmoid scores is
#: a fifth of the spread of the scores around the top-8 threshold: expert
#: popularity then runs from 0.1 to 2 times the mean by seed, the share of
#: held experts hit a step from 93 to 98%, and the step's time with it by
#: 1.5% (my chip runs, PR 29: PERF.md section 6). At a tenth the bias still
#: decides choices (a path that dropped it would show in ``correct``) and
#: popularity stays within a tenth of uniform, as the cell's ``why`` says.
SELECTION_BIAS_SHARE = 0.1

#: top-level keys of a configuration's file that its ``model`` group
#: repeats: the two have to agree
_SHARED = ("vocab_size", "hidden_size", "intermediate_size",
           "moe_intermediate_size", "num_hidden_layers",
           "num_attention_heads", "n_routed_experts", "n_shared_experts",
           "num_experts_per_tok", "first_k_dense_replace",
           "routed_scaling_factor", "norm_topk_prob", "kv_lora_rank",
           "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
           "v_head_dim", "max_position_embeddings", "rms_norm_eps",
           "rope_theta", "rope_scaling")


# ------------------------------------------------------------------ leaves
def check(config: dict) -> None:
    """What a Kimi-K2 configuration's file must agree on."""
    m, name = config["model"], config["name"]
    for key in _SHARED:
        if m[key] != config[key]:
            raise ValueError(f"{name}: model.{key} {m[key]!r} is not the "
                             f"file's {config[key]!r}")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError(f"{name}: only n_group = topk_group = 1 is here")
    if m["held_experts_first"] + m["n_routed_experts"] > m["router_width"]:
        raise ValueError(f"{name}: the held experts pass the router's "
                         "width")
    if m["router_width"] != config["published"]["n_routed_experts"]:
        raise ValueError(f"{name}: router_width is not the published "
                         "n_routed_experts")
    p = config["precision"]
    if (p["parameters"], p["kv_cache"]) != ("bfloat16", "bfloat16"):
        raise ValueError(f"{name}: the work functions count 2 bytes a "
                         "weight and a cached value")


def _attention_leaves(m: dict) -> dict:
    h, nh = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    r, rq = m["kv_lora_rank"], m["q_lora_rank"]
    return {
        "q_a_proj.weight": ((h, rq), "matrix"),
        "q_a_layernorm.weight": ((rq,), "scale"),
        "q_b_proj.weight": ((rq, nh * (dn + dr)), "matrix"),
        "kv_a_proj_with_mqa.weight": ((h, r + dr), "matrix"),
        "kv_a_layernorm.weight": ((r,), "scale"),
        "kv_b_proj.weight": ((r, nh * (dn + dv)), "matrix"),
        "o_proj.weight": ((nh * dv, h), "matrix"),
    }


def _mlp_leaves(h: int, f: int) -> dict:
    return {"gate_proj.weight": ((h, f), "matrix"),
            "up_proj.weight": ((h, f), "matrix"),
            "down_proj.weight": ((f, h), "matrix")}


def leaf_table(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind) under the names of the program's
    ``KimiK2ForCausalLM.functional_state()``; weights are ``[in, out]``,
    the held experts' stacked ``[held, in, out]``."""
    m = model
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    held = m["n_routed_experts"]
    table = {"model.embed_tokens.weight": ((m["vocab_size"], h), "matrix")}
    for i in range(m["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        table[pre + "input_layernorm.weight"] = ((h,), "scale")
        for n, t in _attention_leaves(m).items():
            table[pre + "self_attn." + n] = t
        table[pre + "post_attention_layernorm.weight"] = ((h,), "scale")
        if i < m["first_k_dense_replace"]:
            for n, t in _mlp_leaves(h, m["intermediate_size"]).items():
                table[pre + "mlp." + n] = t
            continue
        table[pre + "mlp.gate.weight"] = ((h, m["router_width"]), "matrix")
        table[pre + "mlp.gate.e_score_correction_bias"] = (
            (m["router_width"],), "bias")
        table[pre + "mlp.experts.gate_proj"] = ((held, h, f), "matrix")
        table[pre + "mlp.experts.up_proj"] = ((held, h, f), "matrix")
        table[pre + "mlp.experts.down_proj"] = ((held, f, h), "matrix")
        for n, t in _mlp_leaves(h, f * m["n_shared_experts"]).items():
            table[pre + "mlp.shared_experts." + n] = t
    table["model.norm.weight"] = ((h,), "scale")
    table["lm_head.weight"] = ((h, m["vocab_size"]), "matrix")
    return table


@jax.jit
def _at_share(a):
    # reduce_precision, not a pair of casts (the TPU compiler may drop a
    # cast down and up again): the same bfloat16 value on both sides
    used = jax.lax.reduce_precision(
        a.astype(jnp.float32) * SELECTION_BIAS_SHARE, 8, 7)
    return used.astype(a.dtype)


def as_used(leaves: dict) -> dict:
    """The harness's leaves as this family's model takes them: every leaf
    as drawn, a selection bias at ``SELECTION_BIAS_SHARE`` of its draw."""
    return {n: _at_share(a) if n.endswith(".e_score_correction_bias") else a
            for n, a in leaves.items()}


# ------------------------------------------------------ the program's side
def program_config(model: dict):
    """The program's config of a configuration's ``model`` group."""
    from paddle_tpu.text.kimi_k2 import KimiK2Config

    m = model
    keys = [k for k in _SHARED if k != "n_routed_experts"]
    return KimiK2Config(
        n_routed_experts=m["router_width"],
        held_experts=(m["held_experts_first"], m["n_routed_experts"]),
        **{k: m[k] for k in keys})


def build_serving(run, leaves: dict):
    """``ServingEngine`` over the model holding ``leaves``."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.kimi_k2 import KimiK2ForCausalLM

    from benchmark.lib.common import install_weights
    from benchmark.lib.serve import pool_pages

    m, sv = run.config["model"], run.config["serve"]
    # shapes only (LazyGuard): the program's own initializers never run
    with paddle.LazyGuard():
        model = KimiK2ForCausalLM(program_config(m))
    install_weights(model, as_used(leaves))
    model.eval()
    # a page holds one padded latent row a token of every layer
    page_bytes = m["num_hidden_layers"] * sv["page_size"] * ITEM \
        * _padded(m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return ServingEngine(model, ServingConfig(
        max_batch=sv["max_batch"], num_pages=pool_pages(sv, page_bytes),
        page_size=sv["page_size"], max_prompt_len=sv["max_prompt_len"],
        enable_prefix_caching=sv["enable_prefix_caching"],
        do_sample=sv["do_sample"], tensor_parallel=sv["tensor_parallel"],
        chunk_size=sv["chunk_size"]))


def _padded(width: int) -> int:
    return -(-width // LANES) * LANES


# ----------------------------------------------------- the plain reference
# A copy of tests/refs/kimi_k2_reference.py (tests/test_benchmark_families
# .py holds the two to the same logits), with the control's policies, one
# layer's leaves at a time, attention one row and one block of queries at
# a time, and each held expert over the tokens routed to it alone.
def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(dim: int, base: float, rs: dict):
    theta = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return theta / rs["factor"] * ramp + theta * (1.0 - ramp)


def _rope(x, m: dict):
    """x [s, ..., d] at positions 0 .. s-1: the interleaved pairs
    ``(x[2i], x[2i+1])`` rotated by ``position * theta_i``."""
    rs = m["rope_scaling"]
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * _inv_freq(d, m["rope_theta"], rs)
    t = _yarn_mscale(rs["factor"], rs["mscale"]) \
        / _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * t, jnp.sin(ang) * t
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x32 = x.astype(jnp.float32)
    x0, x1 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _softmax_scale(m: dict) -> float:
    rs = m["rope_scaling"]
    ms = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * ms * ms


def _mla_row(p, pre, y, m: dict, policy: str):
    """Expanded causal attention of one sequence, y [s, hidden]."""
    mm = reference.mm(policy)
    act = y.dtype
    g = lambda n: p[pre + n].astype(act)  # noqa: E731
    s = y.shape[0]
    nh, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"])
    r, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    c_q = _rms_norm(mm(y, g("q_a_proj.weight")).astype(act),
                    g("q_a_layernorm.weight"), eps)
    q = mm(c_q, g("q_b_proj.weight")).astype(act).reshape(s, nh, dn + dr)
    kv_a = mm(y, g("kv_a_proj_with_mqa.weight")).astype(act)
    c_kv = _rms_norm(kv_a[:, :r], g("kv_a_layernorm.weight"), eps)
    k_rope = _rope(kv_a[:, r:], m)                    # one for all heads
    kv = mm(c_kv, g("kv_b_proj.weight")).astype(act).reshape(s, nh, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, None], (s, nh, dr))],
        axis=-1)
    v = kv[..., dn:]
    if policy == "fp8":
        q, k, v = round_f8(q), round_f8(k), round_f8(v)
    prec = None if policy == "bf16" else HIGHEST
    scale = _softmax_scale(m)

    def attend(block):
        """Queries ``block`` [t, heads, d] at positions ``at ..``: a block
        at a time, so that a long row's scores never stand whole."""
        qb, at = block
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=prec,
                        preferred_element_type=jnp.float32) * scale
        seen = jnp.arange(s)[None, :] <= at + jnp.arange(qb.shape[0])[:, None]
        w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w.astype(act), v, precision=prec,
                          preferred_element_type=jnp.float32).astype(act)

    t = next(t for t in (640, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
             if s % t == 0)
    o = jax.lax.map(attend, (q.reshape(s // t, t, nh, dn + dr),
                             jnp.arange(0, s, t)))
    return mm(o.reshape(s, nh * dv), g("o_proj.weight")).astype(act)


def _gated_mlp(y, gate, up, down, mm):
    act = y.dtype
    return mm((jax.nn.silu(mm(y, gate).astype(act))
               * mm(y, up).astype(act)), down).astype(act)


def _moe(p, pre, y, live, m: dict, policy: str):
    """The held experts' part of the expert layer, and the shared expert;
    the router in float32 whatever the policy rounds elsewhere (its
    products are rounded as the policy's are). ``live`` [tokens]: the
    tokens whose result is read; the rest (the padding behind a sequence's
    last position asked for, which all route alike) go to no expert."""
    mm = reference.mm(policy)
    act = y.dtype
    g = lambda n: p[pre + n].astype(act)  # noqa: E731
    first, held = m["held_experts_first"], m["n_routed_experts"]
    sig = jax.nn.sigmoid(reference.mm("fp8" if policy == "fp8" else "f32")(
        y.astype(jnp.float32), p[pre + "gate.weight"]))
    _, idx = jax.lax.top_k(sig + p[pre + "gate.e_score_correction_bias"],
                           m["num_experts_per_tok"])
    w = jnp.take_along_axis(sig, idx, axis=-1)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = jnp.where(live[:, None], w * m["routed_scaling_factor"], 0.0)
    # An expert is computed over the tokens routed to it and no others:
    # the ``room`` tokens of largest weight, which are all of them while
    # no expert is routed more than ``room`` (``most`` says, and
    # ``logits_at`` raises if one was: nothing is dropped in silence).
    # The repo's reference multiplies every token by every held expert;
    # at 20,480 tokens a block that is 48 times the work.
    tokens = y.shape[0]
    room = tokens if tokens <= 4096 else tokens // 8
    out = jnp.zeros(y.shape, jnp.float32)
    most = jnp.int32(0)
    for e in range(m["router_width"]):      # the experts held elsewhere
        if not first <= e < first + held:   # add nothing here
            continue
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        most = jnp.maximum(most, jnp.sum(w_e > 0, dtype=jnp.int32))
        w_top, rows = jax.lax.top_k(w_e, room)
        part = _gated_mlp(
            y[rows], g("experts.gate_proj")[e - first],
            g("experts.up_proj")[e - first],
            g("experts.down_proj")[e - first], mm).astype(jnp.float32)
        out = out.at[rows].add(w_top[:, None] * part)
    sh = "shared_experts."
    return out.astype(act) + _gated_mlp(
        y, g(sh + "gate_proj.weight"), g(sh + "up_proj.weight"),
        g(sh + "down_proj.weight"), mm), most, room


def _freeze(model: dict) -> tuple:
    """``model`` as a static argument, its nested group too."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in model.items()))


def _thaw(items: tuple) -> dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in items}


@functools.partial(jax.jit, static_argnames=("i", "model_items", "policy"),
                   donate_argnums=(1,))
def _layer(p: dict, x, live, i: int, model_items: tuple, policy: str):
    """One block over x [b, s, hidden]; ``p`` holds this layer's leaves.
    Also the most tokens any held expert was routed, and the room it
    had."""
    m = _thaw(model_items)
    pre, eps = f"model.layers.{i}.", m["rms_norm_eps"]
    act = x.dtype
    g = lambda n: p[pre + n].astype(act)  # noqa: E731

    def attend(row):
        y = _rms_norm(row, g("input_layernorm.weight"), eps)
        return row + _mla_row(p, pre + "self_attn.", y, m, policy)

    x = jax.lax.map(attend, x)
    b, s, h = x.shape
    y = _rms_norm(x, g("post_attention_layernorm.weight"), eps)
    y = y.reshape(b * s, h)
    most = room = 0
    if i < m["first_k_dense_replace"]:
        out = _gated_mlp(y, g("mlp.gate_proj.weight"),
                         g("mlp.up_proj.weight"),
                         g("mlp.down_proj.weight"), reference.mm(policy))
    else:
        out, most, room = _moe(p, pre + "mlp.", y, live.reshape(b * s), m,
                               policy)
    return x + out.reshape(b, s, h), most, room


@functools.partial(jax.jit, static_argnames=("eps", "policy"))
def _head(p: dict, x, positions, eps: float, policy: str):
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    x = _rms_norm(x, p["model.norm.weight"].astype(x.dtype), eps)
    return reference.mm(policy)(
        x, p["lm_head.weight"].astype(x.dtype)).astype(jnp.float32)


#: leaves_of -> {prefix: its leaves as the program got them}: a block of
#: requests needs every layer again, and a draw costs a compile
_DRAWN = weakref.WeakKeyDictionary()


@jax.jit
def _as_stored(tree: dict) -> dict:
    return {n: a.astype(jnp.bfloat16) for n, a in tree.items()}


def _leaves(leaves_of, prefix: str) -> dict:
    """The float32 leaves under ``prefix``, drawn once a run: between the
    blocks of a comparison they are kept in the configuration's own
    bfloat16, which holds them exactly (``check``; a drawn leaf that it
    would round is an error), and every product still takes them in
    float32 (``_layer`` casts at use)."""
    kept = _DRAWN.setdefault(leaves_of, {})
    if prefix not in kept:
        drawn = as_used(leaves_of(only=prefix))
        stored = _as_stored(drawn)
        name = min(drawn, key=lambda n: drawn[n].size)
        if not bool(jnp.all(stored[name].astype(jnp.float32)
                            == drawn[name])):
            raise ValueError(f"{name} is not held exactly in bfloat16")
        kept[prefix] = stored
    return kept[prefix]


def logits_at(leaves_of, ids, positions, model: dict, policy: str = "f32"):
    """float32 logits [b, n, vocab] of the sequences ``ids`` [b, s] at the
    given ``positions`` [b, n] (the logit at position t scores token
    t + 1). ``leaves_of(only=...)`` draws float32 leaves, a layer's at a
    time (the whole model in float32 is 16.7 GB)."""
    act = jnp.bfloat16 if policy == "bf16" else jnp.float32
    items = _freeze(model)
    x = _leaves(leaves_of, "model.embed_tokens.")[
        "model.embed_tokens.weight"][ids].astype(act)
    # causal: what lies behind a row's last position asked for is read by
    # nothing (the driver pads every row to the longest request)
    live = jnp.arange(ids.shape[1])[None, :] \
        <= jnp.max(positions, axis=1)[:, None]
    crowded = []
    for i in range(model["num_hidden_layers"]):
        x, most, room = _layer(_leaves(leaves_of, f"model.layers.{i}."), x,
                               live, i, items, policy)
        crowded.append((i, most, room))
    tail = dict(_leaves(leaves_of, "model.norm."),
                **_leaves(leaves_of, "lm_head."))
    out = _head(tail, x, positions, model["rms_norm_eps"], policy)
    for i, most, room in crowded:
        if int(most) > int(room):
            raise ValueError(f"layer {i}: an expert was routed {int(most)} "
                             f"tokens and the reference gave it {int(room)}")
    return out


# -------------------------------------------------------------------- work
def _counts(m: dict) -> dict:
    """Parameters by part, from the sizes alone."""
    h = m["hidden_size"]
    attn = num_params(_attention_leaves(m))
    expert = 3 * h * m["moe_intermediate_size"]
    layers = m["num_hidden_layers"]
    dense = min(m["first_k_dense_replace"], layers)
    return {"layers": layers, "dense_layers": dense,
            "moe_layers": layers - dense, "attention": attn,
            "dense_mlp": 3 * h * m["intermediate_size"],
            "expert": expert, "shared": expert * m["n_shared_experts"],
            "router": h * m["router_width"],
            "head": h * m["vocab_size"],
            # of the experts a token is routed to, the share held here
            "routed_per_token": m["num_experts_per_tok"]
            * m["n_routed_experts"] / m["router_width"]}


def _flops_per_token(m: dict) -> float:
    """2 FLOPs a parameter a token over what a token passes through:
    attention, the dense layer, the shared expert, the router, the
    ``top_k x held / published`` routed experts that are here, the head."""
    c = _counts(m)
    return 2.0 * (c["layers"] * c["attention"]
                  + c["dense_layers"] * c["dense_mlp"]
                  + c["moe_layers"] * (c["shared"] + c["router"]
                                       + c["routed_per_token"] * c["expert"])
                  + c["head"])


def _attend_flops(m: dict, absorbed: bool) -> float:
    """FLOPs a token of context attended, a layer: scores and values over
    64 heads, expanded (192 + 128) or absorbed (576 + 512)."""
    nh = m["num_attention_heads"]
    if absorbed:
        latent = m["kv_lora_rank"] + m["qk_rope_head_dim"]
        return 2.0 * nh * (latent + m["kv_lora_rank"])
    return 2.0 * nh * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                       + m["v_head_dim"])


def _row_bytes(m: dict) -> float:
    """A token's latent row a layer, without the pad of the layout."""
    return float(m["kv_lora_rank"] + m["qk_rope_head_dim"]) * ITEM


def _held_weight_bytes(m: dict) -> float:
    """Every held weight but the embedding table (a step reads rows of it,
    not the table)."""
    return (num_params(leaf_table(m))
            - m["vocab_size"] * m["hidden_size"]) * float(ITEM)


def serve_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of every token computed in the traced window: prompt
    tokens not served from the cache (expanded attention) and decoded
    tokens (absorbed)."""
    layers = model["num_hidden_layers"]
    tokens = traced.get("prefill_tokens", 0) + traced.get("decode_tokens", 0)
    return {"flops": _flops_per_token(model) * tokens
            + layers * (_attend_flops(model, False)
                        * traced.get("prefill_ctx_tokens", 0)
                        + _attend_flops(model, True)
                        * traced.get("decode_ctx_tokens", 0)),
            "bytes": 0.0}


def decode_steps(model: dict, traced: dict) -> dict:
    """What the traced decode steps must do: read every held weight but
    the embedding table once a step, and the latent rows of the live
    contexts."""
    layers = model["num_hidden_layers"]
    ctx = traced.get("decode_ctx_tokens", 0)
    return {"flops": _flops_per_token(model) * traced.get("decode_tokens", 0)
            + layers * _attend_flops(model, True) * ctx,
            "bytes": _held_weight_bytes(model) * traced.get("decode_steps", 0)
            + layers * _row_bytes(model) * ctx}


def mla_attention(model: dict, traced: dict) -> dict:
    """Absorbed attention of every layer of every traced decode step: the
    latent rows of the live contexts once, the queries in (576 a head) and
    the outputs back (512 a head)."""
    m = model
    layers, nh = m["num_hidden_layers"], m["num_attention_heads"]
    ctx = traced.get("decode_ctx_tokens", 0)
    tokens = traced.get("decode_tokens", 0)
    q_and_o = nh * (2.0 * m["kv_lora_rank"] + m["qk_rope_head_dim"]) * ITEM
    return {"flops": layers * _attend_flops(m, True) * ctx,
            "bytes": layers * (_row_bytes(m) * ctx + q_and_o * tokens)}


def expert_matmul(model: dict, traced: dict) -> dict:
    """The held experts' three products of every expert layer of every
    traced launch (decode steps and prefills): the held weights once a
    launch, the routed tokens in and out; ``2 x 3 x hidden x width`` a
    local assignment, of which a token has ``top_k x held / published``
    on average."""
    c = _counts(model)
    launches = traced.get("decode_steps", 0) + traced.get("prefills", 0)
    tokens = traced.get("decode_tokens", 0) + traced.get("prefill_tokens", 0)
    local = tokens * c["routed_per_token"] * c["moe_layers"]
    return {"flops": 2.0 * c["expert"] * local,
            "bytes": float(ITEM) * (
                launches * c["moe_layers"] * model["n_routed_experts"]
                * c["expert"] + 2.0 * model["hidden_size"] * local)}


WORK = {
    "serve_model": serve_model,
    "decode_steps": decode_steps,
    "mla_attention": mla_attention,
    "expert_matmul": expert_matmul,
}
