"""The ``mellum`` family: everything the benchmark knows of Mellum 2
(``model_type`` ``mellum``; ``JetBrains/Mellum2-12B-A2.5B-Instruct``):
pre-RMSNorm blocks of grouped-head attention whose mask is causal in a
``full_attention`` layer and causal-and-within-``sliding_window`` in a
``sliding_attention`` layer, rotary positions whose parameters differ by
layer kind (plain in window layers; YaRN with its ``attention_factor`` on
cos and sin in full ones), a softmax-routed mixture of small gated-SiLU
experts (top-k of the softmax over all, renormalised, no shared expert) in
every layer, a final RMSNorm and an untied head. Serving only.

A configuration's ``model`` group has the ``config.json`` key names, cut
as the file's ``reduced`` says (``layer_types`` to the layers that are
here).

The parts, in the order ``families/gpt3.py`` has them: ``check`` and
``leaf_table``; ``build_serving``; the plain reference ``logits_at``, which
imports nothing of the program and draws one layer's float32 leaves at a
time (a layer's experts are 1.6 GB in float32); ``WORK``.
"""
from __future__ import annotations

import functools
import math
import weakref

import jax
import jax.numpy as jnp

from benchmark.lib import reference
from benchmark.lib.reference import HIGHEST, round_f8

#: bytes a weight and a cached key or value take: the configuration's
#: precision (``check`` holds the file to it)
ITEM = 2
WINDOW, FULL = "sliding_attention", "full_attention"

#: top-level keys of a configuration's file that its ``model`` group
#: repeats: the two have to agree
_SHARED = ("vocab_size", "hidden_size", "moe_intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "head_dim", "num_experts", "num_experts_per_tok", "norm_topk_prob",
           "sliding_window", "max_position_embeddings", "rms_norm_eps",
           "rope_parameters", "tie_word_embeddings")


# ------------------------------------------------------------------ leaves
def check(config: dict) -> None:
    """What a Mellum configuration's file must agree on."""
    m, name = config["model"], config["name"]
    for key in _SHARED:
        if m[key] != config[key]:
            raise ValueError(f"{name}: model.{key} {m[key]!r} is not the "
                             f"file's {config[key]!r}")
    n = m["num_hidden_layers"]
    # the lists stand whole in the file, as published; the model has the
    # layers that are here: the first n
    if m["layer_types"] != config["layer_types"][:n] or len(
            config["layer_types"]) != config["published"][
                "num_hidden_layers"]:
        raise ValueError(f"{name}: model.layer_types is not the first "
                         f"{n} of the file's published list")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError(f"{name}: only mlp_layer_types 'sparse' is here")
    if config["tie_word_embeddings"] or config["attention_bias"] \
            or config["hidden_act"] != "silu":
        raise ValueError(f"{name}: only the untied head, no attention bias "
                         "and silu are here")
    p = config["precision"]
    if (p["parameters"], p["kv_cache"]) != ("bfloat16", "bfloat16"):
        raise ValueError(f"{name}: the work functions count 2 bytes a "
                         "weight and a cached value")


def _layer_leaves(m: dict) -> dict:
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    n, f = m["num_experts"], m["moe_intermediate_size"]
    return {"input_layernorm.weight": ((h,), "scale"),
            "self_attn.q_proj.weight": ((h, q), "matrix"),
            "self_attn.k_proj.weight": ((h, kv), "matrix"),
            "self_attn.v_proj.weight": ((h, kv), "matrix"),
            "self_attn.o_proj.weight": ((q, h), "matrix"),
            "post_attention_layernorm.weight": ((h,), "scale"),
            "mlp.gate.weight": ((h, n), "matrix"),
            "mlp.experts.gate_proj": ((n, h, f), "matrix"),
            "mlp.experts.up_proj": ((n, h, f), "matrix"),
            "mlp.experts.down_proj": ((n, f, h), "matrix")}


def leaf_table(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind) under the names of the program's
    ``MellumForCausalLM.functional_state()``; weights are ``[in, out]``,
    the experts' stacked ``[experts, in, out]``."""
    m = model
    h = m["hidden_size"]
    table = {"model.embed_tokens.weight": ((m["vocab_size"], h), "matrix")}
    for i in range(m["num_hidden_layers"]):
        for n, t in _layer_leaves(m).items():
            table[f"model.layers.{i}.{n}"] = t
    table["model.norm.weight"] = ((h,), "scale")
    table["lm_head.weight"] = ((h, m["vocab_size"]), "matrix")
    return table


def as_used(leaves: dict) -> dict:
    """The harness's leaves as this family's model takes them: as drawn
    (0.02 N a matrix: scores of standard deviation 0.9, 1.5 in a full
    layer, and an attention output of twice the embedding's size, so the
    window has its say in every logit: ``limits/`` has the readings)."""
    return leaves


# ------------------------------------------------------ the program's side
def program_config(model: dict):
    """The program's config of a configuration's ``model`` group."""
    from paddle_tpu.text.mellum import MellumConfig

    return MellumConfig(layer_types=model["layer_types"],
                        **{k: model[k] for k in _SHARED})


def page_bytes(model: dict, page_size: int, kind: str) -> int:
    """A page's bytes in the group of ``kind`` layers: keys and values of
    ``page_size`` tokens in each of them."""
    return model["layer_types"].count(kind) * page_size * ITEM \
        * 2 * model["num_key_value_heads"] * model["head_dim"]


def build_serving(run, leaves: dict):
    """``ServingEngine`` over the model holding ``leaves``."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.mellum import MellumForCausalLM

    from benchmark.lib.common import install_weights

    m, sv = run.config["model"], run.config["serve"]
    # shapes only (LazyGuard): the program's own initializers never run
    with paddle.LazyGuard():
        model = MellumForCausalLM(program_config(m))
    install_weights(model, as_used(leaves))
    model.eval()
    # two page groups by layer kind, each with its own count: the full
    # layers' pool holds every slot's whole context, the window layers' a
    # window a slot and one prompt in flight
    return ServingEngine(model, ServingConfig(
        max_batch=sv["max_batch"], num_pages=sv["num_pages"],
        group_pages=dict(sv["group_pages"]),
        page_size=sv["page_size"], max_prompt_len=sv["max_prompt_len"],
        enable_prefix_caching=sv["enable_prefix_caching"],
        do_sample=sv["do_sample"], tensor_parallel=sv["tensor_parallel"],
        chunk_size=sv["chunk_size"]))


# ----------------------------------------------------- the plain reference
# A copy of tests/refs/mellum_reference.py (tests/test_benchmark_families
# .py holds the two to the same logits), with the control's policy, one
# layer's leaves at a time, attention one row and one block of queries at a
# time (8 rows x 32 heads x 5,120 x 5,120 float32 scores are 26.8 GB), the
# experts one after another (every expert over every token, weighed by the
# router: nothing is gathered, sorted or dropped) and the head at the
# positions asked for alone.
#
# The control, ``"fp8"``: one step under what the file states. The operands
# of every matrix product (the router's too) and the attention's q, k, v
# rounded to float8 e4m3, as ``families/kimi_k2.py``.
def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _rotary_table(s: int, dim: int, rp: dict):
    """``(cos, sin)`` ``[s, dim]`` of a layer kind's ``rope_parameters``
    entry, its ``attention_factor`` multiplied in."""
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
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * theta[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _window_of(m: dict, kind: str) -> int | None:
    """How far back a layer of ``kind`` sees, itself included; None: to
    the start."""
    return m["sliding_window"] if kind == WINDOW else None


def _attention_row(p, pre, y, kind: str, m: dict, policy: str):
    """Attention of one sequence, y [s, hidden]: rotary by the layer's
    kind, a KV head repeated over its group, causal and, in a window
    layer, within the window."""
    mm = reference.mm(policy)
    s = y.shape[0]
    nq, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q = mm(y, p[pre + "q_proj.weight"]).reshape(s, nq, d)
    k = mm(y, p[pre + "k_proj.weight"]).reshape(s, nkv, d)
    v = mm(y, p[pre + "v_proj.weight"]).reshape(s, nkv, d)
    cos, sin = _rotary_table(s, d, m["rope_parameters"][kind])
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    if policy == "fp8":
        q, k, v = round_f8(q), round_f8(k), round_f8(v)
    k, v = (jnp.repeat(t, nq // nkv, axis=1) for t in (k, v))
    scale = 1.0 / math.sqrt(d)
    window = _window_of(m, kind)

    def attend(block):
        """Queries ``block`` [t, heads, d] at positions ``at ..``: a block
        at a time, so that a long row's scores never stand whole."""
        qb, at = block
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        i = at + jnp.arange(qb.shape[0])[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)

    t = next(t for t in (256, 128, 64, 32, 16, 8, 4, 2, 1) if s % t == 0)
    o = jax.lax.map(attend, (q.reshape(s // t, t, nq, d),
                             jnp.arange(0, s, t)))
    return mm(o.reshape(s, nq * d), p[pre + "o_proj.weight"])


def _experts(p, pre, y, m: dict, policy: str):
    """The expert layer over y [tokens, hidden]: every expert over every
    token, one expert after another, weighed by the router (0 for an
    expert not among a token's top k)."""
    mm = reference.mm(policy)
    g = jax.nn.softmax(mm(y, p[pre + "gate.weight"]), axis=-1)
    top, idx = jax.lax.top_k(g, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)

    def one(out, e):
        w_e = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)
        take = lambda n: jax.lax.dynamic_index_in_dim(  # noqa: E731
            p[pre + n], e, axis=0, keepdims=False)
        part = mm(jax.nn.silu(mm(y, take("experts.gate_proj")))
                  * mm(y, take("experts.up_proj")),
                  take("experts.down_proj"))
        return out + w_e[:, None] * part, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          jnp.arange(m["num_experts"]))
    return out


def _freeze(v):
    """``model`` as a static argument, its lists and nested groups too."""
    if isinstance(v, dict):
        return ("dict",) + tuple(sorted((k, _freeze(x))
                                        for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return ("list",) + tuple(_freeze(x) for x in v)
    return v


def _thaw(v):
    if isinstance(v, tuple) and v and v[0] == "dict":
        return {k: _thaw(x) for k, x in v[1:]}
    if isinstance(v, tuple) and v and v[0] == "list":
        return [_thaw(x) for x in v[1:]]
    return v


@functools.partial(jax.jit,
                   static_argnames=("kind", "model_items", "policy"),
                   donate_argnums=(1,))
def _layer(p: dict, x, kind: str, model_items: tuple, policy: str):
    """One block of ``kind`` over x [b, s, hidden]; ``p`` holds the
    layer's leaves under their names within the layer (kept in bfloat16
    between blocks, float32 here): one program a kind, not one a layer."""
    m = _thaw(model_items)
    p = {n: a.astype(jnp.float32) for n, a in p.items()}
    eps = m["rms_norm_eps"]
    b, s, h = x.shape
    y = _rms_norm(x, p["input_layernorm.weight"], eps)
    x = x + jax.lax.map(
        lambda row: _attention_row(p, "self_attn.", row, kind, m, policy), y)
    y = _rms_norm(x, p["post_attention_layernorm.weight"], eps)
    return x + _experts(p, "mlp.", y.reshape(b * s, h), m,
                        policy).reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("eps", "policy"))
def _head(p: dict, x, positions, eps: float, policy: str):
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    x = _rms_norm(x, p["model.norm.weight"].astype(jnp.float32), eps)
    return reference.mm(policy)(x, p["lm_head.weight"].astype(jnp.float32))


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


#: leaves_of -> {prefix: its leaves as the program got them}: a block of
#: requests needs every layer again, and a draw costs a compile
_DRAWN = weakref.WeakKeyDictionary()
#: of those, the bytes that wait on the device; the rest wait on the host.
#: A block's logits are 3.2 GB (8 rows x 1,024 positions x 98,304) and a
#: control holds two of them beside a layer's float32 leaves (1.7 GB) and
#: activations: all 10.9 GB of leaves beside that would not fit
KEEP_ON_DEVICE = 3 << 30


@jax.jit
def _as_stored(tree: dict) -> dict:
    return {n: a.astype(jnp.bfloat16) for n, a in tree.items()}


def _leaves(leaves_of, prefix: str) -> dict:
    """The float32 leaves under ``prefix``, drawn once a run: between the
    blocks of a comparison they are kept in the configuration's own
    bfloat16, which holds them exactly (a drawn leaf that it would round is
    an error), and every product still takes them in float32."""
    kept = _DRAWN.setdefault(leaves_of, {})
    if prefix not in kept:
        drawn = as_used(leaves_of(only=prefix))
        stored = _as_stored(drawn)
        name = min(drawn, key=lambda n: drawn[n].size)
        if not bool(jnp.all(stored[name].astype(jnp.float32)
                            == drawn[name])):
            raise ValueError(f"{name} is not held exactly in bfloat16")
        on_device = sum(a.nbytes for tree in kept.values()
                        for a in tree.values() if isinstance(a, jax.Array))
        if on_device + sum(a.nbytes for a in stored.values()) \
                > KEEP_ON_DEVICE:
            stored = jax.device_get(stored)
        kept[prefix] = stored
    return kept[prefix]


def logits_at(leaves_of, ids, positions, model: dict, policy: str = "f32"):
    """float32 logits [b, n, vocab] of the sequences ``ids`` [b, s] at the
    given ``positions`` [b, n] (the logit at position t scores token
    t + 1). ``leaves_of(only=...)`` draws float32 leaves, a layer's at a
    time."""
    if policy not in ("f32", "fp8"):
        raise ValueError(f"unknown policy {policy!r}")
    items = _freeze(model)
    table = _leaves(leaves_of, "model.embed_tokens.")
    x = _embed(table["model.embed_tokens.weight"], ids)
    for i, kind in enumerate(model["layer_types"]):
        pre = f"model.layers.{i}."
        layer = {n[len(pre):]: a
                 for n, a in _leaves(leaves_of, pre).items()}
        x = _layer(layer, x, kind, items, policy)
    tail = dict(_leaves(leaves_of, "model.norm."),
                **_leaves(leaves_of, "lm_head."))
    return _head(tail, x, positions, model["rms_norm_eps"], policy)


# -------------------------------------------------------------------- work
def _counts(m: dict) -> dict:
    """Parameters by part, and the layers by kind, from the sizes alone."""
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    kinds = m["layer_types"]
    return {"layers": len(kinds), "full": kinds.count(FULL),
            "window": kinds.count(WINDOW),
            "attention": 2 * h * q + 2 * h * kv,
            "router": h * m["num_experts"],
            "expert": 3 * h * m["moe_intermediate_size"],
            "norms": 2 * h, "head": h * m["vocab_size"]}


def _token_flops(m: dict) -> float:
    """2 FLOPs a parameter a token over what every token passes through:
    the projections, the router and its top-k experts, every layer."""
    c = _counts(m)
    return 2.0 * c["layers"] * (c["attention"] + c["router"]
                                + m["num_experts_per_tok"] * c["expert"])


def _pair_flops(m: dict) -> float:
    """A (query, key) pair a layer: scores and values, 2 FLOPs each a
    head a head-size."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"]


def _kv_row_bytes(m: dict) -> float:
    """A token's keys and values a layer."""
    return 2.0 * m["num_key_value_heads"] * m["head_dim"] * ITEM


def _pairs(m: dict, traced: dict) -> dict:
    """(query, key) pairs a layer of each kind, from what the traffic did:
    a full layer attends every position up to the query's, a window layer
    the last ``sliding_window`` of them. Exact where every prompt is
    longer than the window and nothing is cached (this family's cell): a
    prefill of ``T > W`` tokens is ``W T - W (W - 1) / 2`` pairs a window
    layer, a decode token ``W``; never more than the full layer's."""
    w = m["sliding_window"]
    d_full = traced.get("decode_ctx_tokens", 0)
    p_full = traced.get("prefill_ctx_tokens", 0)
    d_win = min(w * traced.get("decode_tokens", 0), d_full)
    p_win = min(w * traced.get("prefill_tokens", 0)
                - traced.get("prefills", 0) * w * (w - 1) // 2, p_full)
    return {"decode": (d_full, d_win), "prefill": (p_full, max(p_win, 0))}


def _over_kinds(m: dict, full_and_window: tuple) -> float:
    c = _counts(m)
    return c["full"] * full_and_window[0] + c["window"] * full_and_window[1]


def _weight_bytes(m: dict) -> float:
    """Every weight a decode step reads: all but the embedding table (a
    step reads rows of it): every expert too, since 48 rows x 8 of 64 hit
    every one of them."""
    c = _counts(m)
    return float(ITEM) * (c["layers"] * (
        c["attention"] + c["router"] + c["norms"]
        + m["num_experts"] * c["expert"]) + c["head"] + m["hidden_size"])


def _scaled(traced: dict, per: str) -> float:
    """The share of the driver's record that the kernel's own calls hold:
    where a metric names its module, ``calls`` is the executions that lie
    whole inside the window and ``per`` the driver's count of the same."""
    calls, mine = traced.get("calls"), traced.get(per, 0)
    return calls / mine if calls is not None and mine else 1.0


def serve_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of every token computed in the traced window: prompt
    and decoded tokens through every layer, the context each attends by
    layer kind, and the head where a token's logits are read (every
    decoded token, the last of a prompt)."""
    c = _counts(model)
    pairs = _pairs(model, traced)
    tokens = traced.get("prefill_tokens", 0) + traced.get("decode_tokens", 0)
    heads = traced.get("prefills", 0) + traced.get("decode_tokens", 0)
    return {"flops": _token_flops(model) * tokens + 2.0 * c["head"] * heads
            + _pair_flops(model) * (_over_kinds(model, pairs["prefill"])
                                    + _over_kinds(model, pairs["decode"])),
            "bytes": 0.0}


def decode_steps(model: dict, traced: dict) -> dict:
    """What the traced decode steps must do: read every weight but the
    embedding table once a step, and the live contexts' keys and values: a
    full layer's whole, a window layer's last ``sliding_window``."""
    c = _counts(model)
    pairs = _pairs(model, traced)["decode"]
    tokens = traced.get("decode_tokens", 0)
    return {"flops": (_token_flops(model) + 2.0 * c["head"]) * tokens
            + _pair_flops(model) * _over_kinds(model, pairs),
            "bytes": _weight_bytes(model) * traced.get("decode_steps", 0)
            + _kv_row_bytes(model) * _over_kinds(model, pairs)}


def gqa_decode_attention(model: dict, traced: dict) -> dict:
    """The decode attention of every layer of the traced decode steps: by
    layer kind the live rows once (a window layer's inside the window),
    the queries in and the outputs back. HBM-bound."""
    share = _scaled(traced, "decode_steps")
    pairs = _pairs(model, traced)["decode"]
    c = _counts(model)
    q_and_o = 2.0 * model["num_attention_heads"] * model["head_dim"] * ITEM
    return {"flops": share * _pair_flops(model) * _over_kinds(model, pairs),
            "bytes": share * (
                _kv_row_bytes(model) * _over_kinds(model, pairs)
                + c["layers"] * q_and_o * traced.get("decode_tokens", 0))}


def prefill_attention(model: dict, traced: dict) -> dict:
    """The attention of every layer of the traced prefills: the visible
    pairs' FLOPs by layer kind (a window layer's inside the window), q, k,
    v in and o out once. Compute-bound."""
    share = _scaled(traced, "prefills")
    pairs = _pairs(model, traced)["prefill"]
    c = _counts(model)
    a_token = 2.0 * (model["num_attention_heads"]
                     + model["num_key_value_heads"]) * model["head_dim"] \
        * ITEM
    return {"flops": share * _pair_flops(model) * _over_kinds(model, pairs),
            "bytes": share * c["layers"] * a_token
            * traced.get("prefill_tokens", 0)}


def expert_matmul(model: dict, traced: dict) -> dict:
    """The experts' three products of every layer of every traced launch
    (decode steps and prefills): every expert's weights once a launch, the
    routed tokens in and out; ``2 x 3 x hidden x width`` an assignment,
    ``top_k`` a token."""
    c = _counts(model)
    launches = traced.get("decode_steps", 0) + traced.get("prefills", 0)
    tokens = traced.get("decode_tokens", 0) + traced.get("prefill_tokens", 0)
    routed = tokens * model["num_experts_per_tok"] * c["layers"]
    return {"flops": 2.0 * c["expert"] * routed,
            "bytes": float(ITEM) * (
                launches * c["layers"] * model["num_experts"] * c["expert"]
                + 2.0 * model["hidden_size"] * routed)}


WORK = {
    "serve_model": serve_model,
    "decode_steps": decode_steps,
    "gqa_decode_attention": gqa_decode_attention,
    "prefill_attention": prefill_attention,
    "expert_matmul": expert_matmul,
}
