"""The ``ouro`` family: everything the benchmark knows of Ouro (``model_type``
``ouro``; ``ByteDance/Ouro-2.6B``, the LoopLM family): a decoder whose whole
stack of layers runs ``total_ut_steps`` times for every token with the same
weights. A layer is full multi-head attention with rotary positions and a
gated SiLU MLP, each sub-layer with an RMSNorm before and after it; a final
RMSNorm closes every loop and feeds the next; an exit gate (a linear map
with a bias) gives each loop a probability of stopping; the head is untied.
Serving only.

A configuration's ``model`` group has the ``config.json`` key names.

The parts, in the order ``families/gpt3.py`` has them: ``check`` and
``leaf_table``; ``build_serving``; the plain reference ``logits_at``,
which imports nothing of the program and works loop-major, drawing each
layer's leaves once and keeping them between loops; ``WORK``.
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

#: top-level keys of a configuration's file that its ``model`` group
#: repeats: the two have to agree
_SHARED = ("vocab_size", "hidden_size", "intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "head_dim", "max_position_embeddings", "rope_theta",
           "rms_norm_eps", "total_ut_steps", "early_exit_threshold",
           "tie_word_embeddings")


# ------------------------------------------------------------------ leaves
def check(config: dict) -> None:
    """What an Ouro configuration's file must agree on."""
    m, name = config["model"], config["name"]
    for key in _SHARED:
        if m[key] != config[key]:
            raise ValueError(f"{name}: model.{key} {m[key]!r} is not the "
                             f"file's {config[key]!r}")
    if set(config["layer_types"]) != {"full_attention"} or len(
            config["layer_types"]) != m["num_hidden_layers"]:
        raise ValueError(f"{name}: every layer is full_attention here")
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu" \
            or config["rope_scaling"] is not None \
            or config["use_sliding_window"] \
            or config["sliding_window"] is not None:
        raise ValueError(f"{name}: only the untied head, silu, unscaled "
                         "rotary and no window are here")
    if m["early_exit_threshold"] != 1:
        raise ValueError(f"{name}: every token runs every loop here "
                         "(early_exit_threshold 1)")
    p = config["precision"]
    if (p["parameters"], p["kv_cache"]) != ("bfloat16", "bfloat16"):
        raise ValueError(f"{name}: the work functions count 2 bytes a "
                         "weight and a cached value")


def _layer_leaves(m: dict) -> dict:
    h, f = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return {"input_layernorm.weight": ((h,), "scale"),
            "self_attn.q_proj.weight": ((h, q), "matrix"),
            "self_attn.k_proj.weight": ((h, kv), "matrix"),
            "self_attn.v_proj.weight": ((h, kv), "matrix"),
            "self_attn.o_proj.weight": ((q, h), "matrix"),
            "input_layernorm_2.weight": ((h,), "scale"),
            "post_attention_layernorm.weight": ((h,), "scale"),
            "mlp.gate_proj.weight": ((h, f), "matrix"),
            "mlp.up_proj.weight": ((h, f), "matrix"),
            "mlp.down_proj.weight": ((f, h), "matrix"),
            "post_attention_layernorm_2.weight": ((h,), "scale")}


def leaf_table(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind) under the names of the program's
    ``OuroForCausalLM.functional_state()``; weights are ``[in, out]``."""
    m = model
    h = m["hidden_size"]
    table = {"model.embed_tokens.weight": ((m["vocab_size"], h), "matrix")}
    for i in range(m["num_hidden_layers"]):
        for n, t in _layer_leaves(m).items():
            table[f"model.layers.{i}.{n}"] = t
    table["model.norm.weight"] = ((h,), "scale")
    table["model.early_exit_gate.weight"] = ((h, 1), "matrix")
    table["model.early_exit_gate.bias"] = ((1,), "bias")
    table["lm_head.weight"] = ((h, m["vocab_size"]), "matrix")
    return table


# ------------------------------------------------------ the program's side
def program_config(model: dict):
    """The program's config of a configuration's ``model`` group."""
    from paddle_tpu.text.ouro import OuroConfig

    return OuroConfig(**{k: model[k] for k in _SHARED})


def page_bytes(model: dict, page_size: int) -> int:
    """A page's bytes: keys and values of ``page_size`` tokens in every
    (loop, layer)."""
    return model["total_ut_steps"] * model["num_hidden_layers"] \
        * page_size * _kv_row_bytes(model)


def build_serving(run, leaves: dict):
    """``ServingEngine`` over the model holding ``leaves``."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.ouro import OuroForCausalLM

    from benchmark.lib.common import install_weights

    m, sv = run.config["model"], run.config["serve"]
    # shapes only (LazyGuard): the program's own initializers never run
    with paddle.LazyGuard():
        model = OuroForCausalLM(program_config(m))
    install_weights(model, leaves)
    model.eval()
    return ServingEngine(model, ServingConfig(
        max_batch=sv["max_batch"], num_pages=sv["num_pages"],
        page_size=sv["page_size"], max_prompt_len=sv["max_prompt_len"],
        enable_prefix_caching=sv["enable_prefix_caching"],
        do_sample=sv["do_sample"], tensor_parallel=sv["tensor_parallel"],
        chunk_size=sv["chunk_size"]))


# ----------------------------------------------------- the plain reference
# A copy of tests/refs/ouro_reference.py (tests/test_benchmark_families.py
# holds the two to the same logits), with the control's policy, loop-major:
# each loop runs every layer over the whole block of rows, a layer's leaves
# drawn once a run and kept between loops and blocks, attention one row at
# a time, and the head at the positions asked for alone. The serving path
# computes no exit gate (at early_exit_threshold 1 the last loop's logits
# are every token's whatever the gate says), so neither does this.
#
# The control, ``"fp8"``: one step under what the file states. The operands
# of every matrix product and the attention's q, k, v rounded to float8 e4m3,
# as ``families/mellum.py``.
def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary_table(s: int, dim: int, theta: float):
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _qkv(p, pre, y, m: dict, policy: str):
    """A row's rotated q, k, v [s, heads, d] (the control's rounding
    applied)."""
    mm = reference.mm(policy)
    s, d = y.shape[0], m["head_dim"]
    q = mm(y, p[pre + "q_proj.weight"]).reshape(s, -1, d)
    k = mm(y, p[pre + "k_proj.weight"]).reshape(s, -1, d)
    v = mm(y, p[pre + "v_proj.weight"]).reshape(s, -1, d)
    cos, sin = _rotary_table(s, d, float(m["rope_theta"]))
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    if policy == "fp8":
        q, k, v = round_f8(q), round_f8(k), round_f8(v)
    rep = m["num_attention_heads"] // m["num_key_value_heads"]
    return q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)


def _attention_row(p, pre, y, m: dict, policy: str):
    """Causal attention of one sequence, y [s, hidden]."""
    q, k, v = _qkv(p, pre, y, m, policy)
    s = y.shape[0]
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / math.sqrt(m["head_dim"])
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)
    return reference.mm(policy)(o.reshape(s, -1), p[pre + "o_proj.weight"])


def _freeze(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("model_items", "policy"),
                   donate_argnums=(1,))
def _layer(p: dict, x, model_items: tuple, policy: str):
    """One layer over x [b, s, hidden]; ``p`` holds the layer's leaves
    under their names within the layer (kept in bfloat16, float32 here):
    one program for every layer and loop."""
    m = dict(model_items)
    mm = reference.mm(policy)
    p = {n: a.astype(jnp.float32) for n, a in p.items()}
    eps = m["rms_norm_eps"]
    a = _rms_norm(x, p["input_layernorm.weight"], eps)
    o = jax.lax.map(lambda row: _attention_row(p, "self_attn.", row, m,
                                               policy), a)
    x = x + _rms_norm(o, p["input_layernorm_2.weight"], eps)
    y = _rms_norm(x, p["post_attention_layernorm.weight"], eps)
    f = mm(jax.nn.silu(mm(y, p["mlp.gate_proj.weight"]))
           * mm(y, p["mlp.up_proj.weight"]), p["mlp.down_proj.weight"])
    return x + _rms_norm(f, p["post_attention_layernorm_2.weight"], eps)


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(1,))
def _loop_norm(w, x, eps: float):
    return _rms_norm(x, w.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("policy",))
def _head(w, x, positions, policy: str):
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    return reference.mm(policy)(x, w.astype(jnp.float32))


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


#: leaves_of -> {prefix: its leaves as the program got them}: every loop
#: and every block of requests needs every layer again, and a draw costs a
#: compile
_DRAWN = weakref.WeakKeyDictionary()
#: of those, the bytes that wait on the device; the rest wait on the host.
#: The served state is gone when the reference runs: every layer's leaves
#: in bfloat16 (4.93 GB) wait on the device beside a block's activations
#: (8 rows x 640 positions, 42 MB a copy) and a control's two blocks of
#: logits (0.6 GB each)
KEEP_ON_DEVICE = 6 << 30


@jax.jit
def _as_stored(tree: dict) -> dict:
    return {n: a.astype(jnp.bfloat16) for n, a in tree.items()}


def _leaves(leaves_of, prefix: str) -> dict:
    """The float32 leaves under ``prefix``, drawn once a run: between loops
    and blocks they are kept in the configuration's own bfloat16, which
    holds them exactly (a drawn leaf that it would round is an error), and
    every product still takes them in float32."""
    kept = _DRAWN.setdefault(leaves_of, {})
    if prefix not in kept:
        drawn = leaves_of(only=prefix)
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
    t + 1): every loop of every layer, loop-major. ``leaves_of(only=...)``
    draws float32 leaves, a layer's at a time."""
    if policy not in ("f32", "fp8"):
        raise ValueError(f"unknown policy {policy!r}")
    items = _freeze(model)
    x = _embed(_leaves(leaves_of, "model.embed_tokens.")[
        "model.embed_tokens.weight"], ids)
    norm = _leaves(leaves_of, "model.norm.")["model.norm.weight"]
    for _ in range(model["total_ut_steps"]):
        for i in range(model["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            layer = {n[len(pre):]: a
                     for n, a in _leaves(leaves_of, pre).items()}
            x = _layer(layer, x, items, policy)
        x = _loop_norm(norm, x, model["rms_norm_eps"])
    return _head(_leaves(leaves_of, "lm_head.")["lm_head.weight"], x,
                 positions, policy)


# -------------------------------------------------------------------- work
def _counts(m: dict) -> dict:
    """Parameters by part, and the passes (loop, layer), from the sizes
    alone."""
    h = m["hidden_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return {"layers": m["num_hidden_layers"], "loops": m["total_ut_steps"],
            "passes": m["total_ut_steps"] * m["num_hidden_layers"],
            "attention": 2 * h * q + 2 * h * kv,
            "mlp": 3 * h * m["intermediate_size"],
            "norms": 4 * h, "head": h * m["vocab_size"]}


def _token_flops(m: dict) -> float:
    """2 FLOPs a parameter a token a layer application: every layer of
    every loop."""
    c = _counts(m)
    return 2.0 * c["passes"] * (c["attention"] + c["mlp"])


def _pair_flops(m: dict) -> float:
    """A (query, key) pair a (loop, layer): scores and values, 2 FLOPs
    each a head a head-size."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"]


def _kv_row_bytes(m: dict) -> float:
    """A token's keys and values a (loop, layer)."""
    return 2.0 * m["num_key_value_heads"] * m["head_dim"] * ITEM


def _weight_bytes(m: dict) -> float:
    """What a decode step reads of the weights: every layer's once a loop
    (the loop runs the stack again; 5 GB stays in no cache), the final
    norm once a loop, the head once; the embedding table a row a token."""
    c = _counts(m)
    return float(ITEM) * (
        c["passes"] * (c["attention"] + c["mlp"] + c["norms"])
        + c["loops"] * m["hidden_size"] + c["head"])


def _scaled(traced: dict, per: str) -> float:
    """The share of the driver's record that the kernel's own calls hold:
    where a metric names its module, ``calls`` is the executions that lie
    whole inside the window and ``per`` the driver's count of the same."""
    calls, mine = traced.get("calls"), traced.get(per, 0)
    return calls / mine if calls is not None and mine else 1.0


def serve_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of every token computed in the traced window: prompt
    and decoded tokens through every layer of every loop, the context each
    attends in every (loop, layer), and the head where a token's logits
    are read (every decoded token, the last of a prompt)."""
    c = _counts(model)
    tokens = traced.get("prefill_tokens", 0) + traced.get("decode_tokens", 0)
    heads = traced.get("prefills", 0) + traced.get("decode_tokens", 0)
    pairs = traced.get("prefill_ctx_tokens", 0) \
        + traced.get("decode_ctx_tokens", 0)
    return {"flops": _token_flops(model) * tokens + 2.0 * c["head"] * heads
            + _pair_flops(model) * c["passes"] * pairs,
            "bytes": 0.0}


def decode_steps(model: dict, traced: dict) -> dict:
    """What the traced decode steps must do: read every layer's weights
    once a loop and the head once a step, and the live contexts' keys and
    values once a (loop, layer)."""
    c = _counts(model)
    ctx = traced.get("decode_ctx_tokens", 0)
    tokens = traced.get("decode_tokens", 0)
    return {"flops": (_token_flops(model) + 2.0 * c["head"]) * tokens
            + _pair_flops(model) * c["passes"] * ctx,
            "bytes": _weight_bytes(model) * traced.get("decode_steps", 0)
            + _kv_row_bytes(model) * c["passes"] * ctx}


def gqa_decode_attention(model: dict, traced: dict) -> dict:
    """The decode attention of every (loop, layer) of the traced decode
    steps: the live rows once, the queries in and the outputs back.
    HBM-bound."""
    share = _scaled(traced, "decode_steps")
    c = _counts(model)
    ctx = traced.get("decode_ctx_tokens", 0)
    q_and_o = 2.0 * model["num_attention_heads"] * model["head_dim"] * ITEM
    return {"flops": share * _pair_flops(model) * c["passes"] * ctx,
            "bytes": share * c["passes"] * (
                _kv_row_bytes(model) * ctx
                + q_and_o * traced.get("decode_tokens", 0))}


WORK = {
    "serve_model": serve_model,
    "decode_steps": decode_steps,
    "gqa_decode_attention": gqa_decode_attention,
}
