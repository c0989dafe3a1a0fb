"""The ``granite_hybrid`` family: everything the benchmark knows of Granite
4.0-H (``model_type`` ``granitemoehybrid``): pre-RMSNorm blocks whose mixer
is a Mamba-2 state-space layer (Dao & Gu 2024, arXiv:2405.21060) or, in the
layers that ``layer_types`` names, causal attention with grouped KV heads
and no positional encoding; a gated SiLU MLP behind every mixer; Granite's
four multipliers; a final RMSNorm and a head tied to the embedding.
Serving only.

A configuration's ``model`` group has the ``config.json`` key names.

The parts, in the order ``families/gpt3.py`` has them: ``check`` and
``leaf_table`` (with ``as_used``: the initialisation the family adds to the
harness's draw); ``build_serving``; the plain reference ``logits_at``, which
imports nothing of the program and draws one layer's float32 leaves at a
time; ``WORK``.
"""
from __future__ import annotations

import functools
import math
import weakref

import jax
import jax.numpy as jnp

from benchmark.lib import reference
from benchmark.lib.reference import BF16_BITS, HIGHEST, round_f8, round_to
from benchmark.lib.weights import INIT_STD

#: bytes a weight and a cached key or value take, and a number of the
#: recurrent state: the configuration's precision (``check`` holds the file
#: to it)
ITEM, STATE_ITEM = 2, 4

#: The state-space leaves are USED with Mamba-2's published initialisation
#: added to what the harness draws (``as_used``; program and reference
#: alike). The harness draws ``A_log`` and ``dt_bias`` as a ``bias``
#: (0.01 N): ``A`` = -1 and a step of softplus(0) = 0.69 in every head, so a
#: state forgets half of itself every token, and a fault in carrying it
#: across a chunk, a swap or a thousand decode steps would fade before the
#: comparison saw it. Published (``mamba_ssm`` ``Mamba2.__init__``): ``A``
#: spread over [1, 16] and the step's bias the inverse softplus of a step
#: spread log-uniformly over [0.001, 0.1]; here both run evenly over the
#: heads, head ``i`` of ``n``: memories from one token to a thousand.
A_RANGE = (1.0, 16.0)
STEP_RANGE = (1e-3, 1e-1)
#: The convolution's taps are USED at the published scale too: a depthwise
#: ``Conv1d`` of 4 taps starts uniform over +-1/sqrt(4) (standard deviation
#: 0.29), where the harness's ``matrix`` draw (0.02 N) would leave ``x``,
#: ``B`` and ``C`` at a twentieth of the skip term ``D x`` and the state
#: without a say in any logit.
CONV_STD = 0.5 / math.sqrt(3.0)

#: top-level keys of a configuration's file that its ``model`` group
#: repeats: the two have to agree
_SHARED = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
           "num_attention_heads", "num_key_value_heads",
           "shared_intermediate_size", "mamba_n_heads", "mamba_d_head",
           "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "mamba_expand",
           "mamba_chunk_size", "embedding_multiplier", "residual_multiplier",
           "attention_multiplier", "logits_scaling", "rms_norm_eps",
           "max_position_embeddings", "position_embedding_type",
           "tie_word_embeddings", "num_local_experts")


# ------------------------------------------------------------------ leaves
def check(config: dict) -> None:
    """What a Granite 4.0-H configuration's file must agree on."""
    m, name = config["model"], config["name"]
    for key in _SHARED:
        if m[key] != config[key]:
            raise ValueError(f"{name}: model.{key} {m[key]!r} is not the "
                             f"file's {config[key]!r}")
    if config["num_local_experts"] or config["num_experts_per_tok"]:
        raise ValueError(f"{name}: routed experts are not here")
    if config["mamba_n_groups"] != 1:
        raise ValueError(f"{name}: only mamba_n_groups = 1 is here")
    if config["intermediate_size"] != config["shared_intermediate_size"]:
        raise ValueError(f"{name}: the MLP's width is stated twice and "
                         "differs")
    p = config["precision"]
    if (p["parameters"], p["kv_cache"], p["ssm_state"]) != (
            "bfloat16", "bfloat16", "float32"):
        raise ValueError(f"{name}: the work functions count 2 bytes a "
                         "weight and a cached value and 4 a state number")


def _sizes(m: dict) -> dict:
    nh, hd, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    d_inner = nh * hd
    return {"d_inner": d_inner, "heads": nh, "head": hd, "state": n,
            "conv": d_inner + 2 * m["mamba_n_groups"] * n,
            "qk_head": m["hidden_size"] // m["num_attention_heads"]}


def _mixer_leaves(m: dict, kind: str) -> dict:
    h, z = m["hidden_size"], _sizes(m)
    if kind == "attention":
        q, kv = (m["num_attention_heads"] * z["qk_head"],
                 m["num_key_value_heads"] * z["qk_head"])
        return {"self_attn.q_proj.weight": ((h, q), "matrix"),
                "self_attn.k_proj.weight": ((h, kv), "matrix"),
                "self_attn.v_proj.weight": ((h, kv), "matrix"),
                "self_attn.o_proj.weight": ((q, h), "matrix")}
    return {
        "mamba.in_proj.weight": (
            (h, z["d_inner"] + z["conv"] + z["heads"]), "matrix"),
        "mamba.conv1d.weight": ((m["mamba_d_conv"], z["conv"]), "matrix"),
        "mamba.conv1d.bias": ((z["conv"],), "bias"),
        "mamba.A_log": ((z["heads"],), "bias"),
        "mamba.D": ((z["heads"],), "scale"),
        "mamba.dt_bias": ((z["heads"],), "bias"),
        "mamba.norm.weight": ((z["d_inner"],), "scale"),
        "mamba.out_proj.weight": ((z["d_inner"], h), "matrix")}


def leaf_table(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind) under the names of the program's
    ``GraniteHybridForCausalLM.functional_state()``; weights are ``[in,
    out]``, the convolution's taps ``[d_conv, width]``. The head is the
    embedding table: one leaf."""
    m = model
    h, f = m["hidden_size"], m["shared_intermediate_size"]
    table = {"model.embed_tokens.weight": ((m["vocab_size"], h), "matrix")}
    for i, kind in enumerate(m["layer_types"]):
        pre = f"model.layers.{i}."
        table[pre + "input_layernorm.weight"] = ((h,), "scale")
        for n, t in _mixer_leaves(m, kind).items():
            table[pre + n] = t
        table[pre + "post_attention_layernorm.weight"] = ((h,), "scale")
        table[pre + "shared_mlp.input_linear.weight"] = ((h, 2 * f),
                                                         "matrix")
        table[pre + "shared_mlp.output_linear.weight"] = ((f, h), "matrix")
    table["model.norm.weight"] = ((h,), "scale")
    return table


def _stored(a, like):
    """A float32 value as the leaf holds it: rounded to bfloat16 by
    ``reduce_precision`` (not a pair of casts, which the TPU compiler may
    drop), so the program's bfloat16 leaf and the reference's float32 one
    hold the same number."""
    return round_to(a, BF16_BITS).astype(like.dtype)


@jax.jit
def _a_log(a):
    n = a.shape[0]
    spread = A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) \
        * jnp.arange(n, dtype=jnp.float32) / max(n - 1, 1)
    return _stored(a.astype(jnp.float32) + jnp.log(spread), a)


@jax.jit
def _dt_bias(a):
    n = a.shape[0]
    lo, hi = (math.log10(v) for v in STEP_RANGE)
    step = 10.0 ** (lo + (hi - lo) * jnp.arange(n, dtype=jnp.float32)
                    / max(n - 1, 1))
    # the inverse of softplus: step + log(1 - exp(-step))
    return _stored(a.astype(jnp.float32) + step
                   + jnp.log(-jnp.expm1(-step)), a)


@jax.jit
def _conv_taps(a):
    return _stored(a.astype(jnp.float32) * (CONV_STD / INIT_STD), a)


_USED = {".mamba.A_log": _a_log, ".mamba.dt_bias": _dt_bias,
         ".mamba.conv1d.weight": _conv_taps}


def as_used(leaves: dict) -> dict:
    """The harness's leaves as this family's model takes them: every leaf
    as drawn, the state-space leaves with the published initialisation
    added (``A_RANGE``, ``STEP_RANGE``, ``CONV_STD``)."""
    def used(name, a):
        for end, fn in _USED.items():
            if name.endswith(end):
                return fn(a)
        return a
    return {n: used(n, a) for n, a in leaves.items()}


# ------------------------------------------------------ the program's side
def program_config(model: dict):
    """The program's config of a configuration's ``model`` group."""
    from paddle_tpu.text.granite_hybrid import GraniteHybridConfig

    return GraniteHybridConfig(**{k: model[k] for k in _SHARED})


def page_bytes(model: dict, page_size: int) -> int:
    """A page's bytes: keys and values of ``page_size`` tokens in the
    layers that page, the attention layers. What a slot keeps is no part of
    a page."""
    z = _sizes(model)
    return model["layer_types"].count("attention") * page_size * ITEM \
        * 2 * model["num_key_value_heads"] * z["qk_head"]


def build_serving(run, leaves: dict):
    """``ServingEngine`` over the model holding ``leaves``."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.granite_hybrid import GraniteHybridForCausalLM

    from benchmark.lib.common import install_weights
    from benchmark.lib.serve import pool_pages

    m, sv = run.config["model"], run.config["serve"]
    # shapes only (LazyGuard): the program's own initializers never run
    with paddle.LazyGuard():
        model = GraniteHybridForCausalLM(program_config(m))
    install_weights(model, as_used(leaves))
    model.eval()
    # the pool's share of the device sizes what is paged: the four
    # attention layers' keys and values, 128 KB a page
    pages = pool_pages(sv, page_bytes(m, sv["page_size"]))
    return ServingEngine(model, ServingConfig(
        max_batch=sv["max_batch"], num_pages=pages,
        page_size=sv["page_size"], max_prompt_len=sv["max_prompt_len"],
        enable_prefix_caching=sv["enable_prefix_caching"],
        do_sample=sv["do_sample"], tensor_parallel=sv["tensor_parallel"],
        chunk_size=sv["chunk_size"]))


# ----------------------------------------------------- the plain reference
# A copy of tests/refs/granite_hybrid_reference.py (tests/test_benchmark_
# families.py holds the two to the same logits), with the control's policy,
# one layer's leaves at a time, the recurrence run no further than the
# block's last position asked for, and attention one row and one block of
# queries at a time.
#
# The control, ``"fp8"``: one step under what the file states. The operands
# of every matrix product (and the attention's q, k, v) rounded to float8
# e4m3, as ``families/kimi_k2.py``; and the recurrent state, which the file
# states in float32, rounded to bfloat16 after every token.
def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _mamba(p, pre, y, live, m: dict, policy: str):
    """The Mamba-2 mixer over y [b, s, hidden]. ``live`` [b, s]: the
    positions whose result is read; behind a row's last one the state is
    left alone, and the recurrence stops at the block's last."""
    mm = reference.mm(policy)
    z_ = _sizes(m)
    d_inner, width, nh, hd, n = (z_["d_inner"], z_["conv"], z_["heads"],
                                 z_["head"], z_["state"])
    taps, (b, s, _) = m["mamba_d_conv"], y.shape
    zxbcdt = mm(y, p[pre + "in_proj.weight"]).astype(jnp.float32)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + width]
    dt = zxbcdt[..., d_inner + width:]
    # depthwise causal convolution: zeros stand before the sequence
    seq = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    w = p[pre + "conv1d.weight"]
    conv = p[pre + "conv1d.bias"] + sum(w[j] * seq[:, j:j + s]
                                        for j in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_inner].reshape(b, s, nh, hd)
    b_in, c_out = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
    step = jnp.where(live[..., None],
                     jax.nn.softplus(dt + p[pre + "dt_bias"]), 0.0)
    a = -jnp.exp(p[pre + "A_log"])
    last = jnp.max(jnp.sum(live, axis=1))       # the block's last position

    def token(t, carry):
        state, ys = carry
        take = lambda v: jax.lax.dynamic_index_in_dim(  # noqa: E731
            v, t, axis=1, keepdims=False)
        x_t, d_t, b_t, c_t = take(x), take(step), take(b_in), take(c_out)
        # S <- exp(D A) S + D x (x) B;   y = S C
        state = jnp.exp(d_t * a)[..., None, None] * state \
            + (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        if policy == "fp8":
            state = round_to(state, BF16_BITS)
        y_t = jnp.sum(state * c_t[:, None, None, :], axis=-1)
        return state, jax.lax.dynamic_update_index_in_dim(ys, y_t, t, 1)

    _, ys = jax.lax.fori_loop(
        0, last, token, (jnp.zeros((b, nh, hd, n), jnp.float32),
                         jnp.zeros((b, s, nh, hd), jnp.float32)))
    ys = ys + p[pre + "D"][:, None] * x
    # the gated norm: the gate first, then one RMS group over d_inner
    g = _rms_norm(ys.reshape(b, s, d_inner) * jax.nn.silu(z),
                  p[pre + "norm.weight"], m["rms_norm_eps"])
    return mm(g, p[pre + "out_proj.weight"]).astype(y.dtype)


def _attention_row(p, pre, y, m: dict, policy: str):
    """Causal attention of one sequence, y [s, hidden]: no positional
    encoding, the score scaled by ``attention_multiplier``, a KV head
    repeated over its group."""
    mm = reference.mm(policy)
    s = y.shape[0]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // nq
    q = mm(y, p[pre + "q_proj.weight"]).reshape(s, nq, d)
    k = mm(y, p[pre + "k_proj.weight"]).reshape(s, nkv, d)
    v = mm(y, p[pre + "v_proj.weight"]).reshape(s, nkv, d)
    if policy == "fp8":
        q, k, v = round_f8(q), round_f8(k), round_f8(v)
    k, v = (jnp.repeat(t, nq // nkv, axis=1) for t in (k, v))
    scale = m["attention_multiplier"]

    def attend(block):
        """Queries ``block`` [t, heads, d] at positions ``at ..``: a block
        at a time, so that a long row's scores never stand whole."""
        qb, at = block
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        seen = jnp.arange(s)[None, :] <= at + jnp.arange(qb.shape[0])[:, None]
        w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)

    t = next(t for t in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
             if s % t == 0)
    o = jax.lax.map(attend, (q.reshape(s // t, t, nq, d),
                             jnp.arange(0, s, t)))
    return mm(o.reshape(s, nq * d), p[pre + "o_proj.weight"])


def _mlp(p, pre, y, m: dict, policy: str):
    mm = reference.mm(policy)
    gu = mm(y, p[pre + "input_linear.weight"])
    f = m["shared_intermediate_size"]
    return mm(jax.nn.silu(gu[..., :f]) * gu[..., f:],
              p[pre + "output_linear.weight"])


def _freeze(model: dict) -> tuple:
    """``model`` as a static argument, its list too."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def _thaw(items: tuple) -> dict:
    return {k: list(v) if isinstance(v, tuple) else v for k, v in items}


@functools.partial(jax.jit,
                   static_argnames=("kind", "model_items", "policy"),
                   donate_argnums=(1,))
def _layer(p: dict, x, live, kind: str, model_items: tuple, policy: str):
    """One block of ``kind`` over x [b, s, hidden]; ``p`` holds the
    layer's leaves under their names within the layer (kept in bfloat16
    between blocks, float32 here): one program a kind, not one a layer."""
    m = _thaw(model_items)
    p = {n: a.astype(jnp.float32) for n, a in p.items()}
    eps, res = m["rms_norm_eps"], m["residual_multiplier"]
    y = _rms_norm(x, p["input_layernorm.weight"], eps)
    if kind == "mamba":
        mixed = _mamba(p, "mamba.", y, live, m, policy)
    else:
        mixed = jax.lax.map(
            lambda row: _attention_row(p, "self_attn.", row, m, policy), y)
    x = x + res * mixed
    y = _rms_norm(x, p["post_attention_layernorm.weight"], eps)
    return x + res * _mlp(p, "shared_mlp.", y, m, policy)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "policy"))
def _head(p: dict, x, positions, eps: float, scaling: float, policy: str):
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    x = _rms_norm(x, p["model.norm.weight"].astype(jnp.float32), eps)
    # the tied head: the embedding table, transposed
    table = p["model.embed_tokens.weight"].astype(jnp.float32)
    return reference.mm(policy)(x, table.T) / scaling


@functools.partial(jax.jit, static_argnames="multiplier")
def _embed(table, ids, multiplier: float):
    return multiplier * table[ids].astype(jnp.float32)


#: leaves_of -> {prefix: its leaves as the program got them}: a block of
#: requests needs every layer again, and a draw costs a compile
_DRAWN = weakref.WeakKeyDictionary()
#: of those, the bytes that wait on the device; the rest wait on the host.
#: A block's logits are 3.3 GB (8 rows x 1,024 positions x 100,352) and a
#: control holds two of them beside a layer's activations: all 6.4 GB of
#: leaves beside that would not fit
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
        for name in sorted(drawn, key=lambda n: drawn[n].size)[:4]:
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
    x = _embed(table["model.embed_tokens.weight"], ids,
               float(model["embedding_multiplier"]))
    # causal: what lies behind a row's last position asked for is read by
    # nothing (the driver pads every row to the longest request), and the
    # recurrence is not run over it
    live = jnp.arange(ids.shape[1])[None, :] \
        <= jnp.max(positions, axis=1)[:, None]
    for i, kind in enumerate(model["layer_types"]):
        pre = f"model.layers.{i}."
        layer = {n[len(pre):]: a
                 for n, a in _leaves(leaves_of, pre).items()}
        x = _layer(layer, x, live, kind, items, policy)
    tail = dict(_leaves(leaves_of, "model.norm."), **table)
    return _head(tail, x, positions, model["rms_norm_eps"],
                 float(model["logits_scaling"]), policy)


# -------------------------------------------------------------------- work
def _matmul_params(m: dict) -> float:
    """The parameters a token is multiplied by: every projection and the
    tied head (the table's rows gathered for the embedding are no
    product)."""
    table = leaf_table(m)
    return float(sum(math.prod(shape) for name, (shape, kind)
                     in table.items()
                     if kind == "matrix" and "conv1d" not in name))


def _state_flops(m: dict) -> float:
    """A token's recurrence a Mamba layer: decay, outer product and add
    for the update, multiply and add for the readout, over ``d_inner x
    d_state``."""
    z = _sizes(m)
    return 5.0 * z["d_inner"] * z["state"]


def _attend_flops(m: dict) -> float:
    """A token of context attended, over the attention layers: scores and
    values, 2 FLOPs each a head a head-size."""
    return m["layer_types"].count("attention") * 4.0 \
        * m["num_attention_heads"] * _sizes(m)["qk_head"]


def _flops(m: dict, tokens: float, ctx_tokens: float) -> float:
    return (2.0 * _matmul_params(m) + m["layer_types"].count("mamba")
            * _state_flops(m)) * tokens + _attend_flops(m) * ctx_tokens


def _weight_bytes(m: dict) -> float:
    """Every weight once (the tied table too: the head reads all of it)."""
    return float(ITEM) * sum(math.prod(shape)
                             for shape, _ in leaf_table(m).values())


def _state_bytes(m: dict) -> float:
    """What a slot keeps a Mamba layer: the float32 state and the rows of
    the convolution."""
    z = _sizes(m)
    return float(STATE_ITEM) * z["d_inner"] * z["state"] \
        + float(ITEM) * (m["mamba_d_conv"] - 1) * z["conv"]


def _kv_bytes(m: dict) -> float:
    """A token's keys and values over the attention layers."""
    return float(page_bytes(m, 1))


def serve_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of every token computed in the traced window (prompt and
    decoded): 2 a matmul parameter, the head included; the recurrence of
    the Mamba layers; the context attended in the attention layers."""
    tokens = traced.get("prefill_tokens", 0) + traced.get("decode_tokens", 0)
    ctx = traced.get("prefill_ctx_tokens", 0) \
        + traced.get("decode_ctx_tokens", 0)
    return {"flops": _flops(model, tokens, ctx), "bytes": 0.0}


def decode_steps(model: dict, traced: dict) -> dict:
    """What the traced decode steps must do: read every weight once a
    step, read and write the live slots' state once a Mamba layer, read the
    live contexts' keys and values."""
    mamba = model["layer_types"].count("mamba")
    tokens = traced.get("decode_tokens", 0)
    ctx = traced.get("decode_ctx_tokens", 0)
    return {"flops": _flops(model, tokens, ctx),
            "bytes": _weight_bytes(model) * traced.get("decode_steps", 0)
            + 2.0 * mamba * _state_bytes(model) * tokens
            + _kv_bytes(model) * ctx}


def ssm_state_update(model: dict, traced: dict) -> dict:
    """The decode update of every Mamba layer of every traced decode step:
    the live slots' float32 state read and written once, ``x``, ``B``,
    ``C`` and the step in and ``y`` out (float32). HBM-bound."""
    z = _sizes(model)
    mamba = model["layer_types"].count("mamba")
    tokens = traced.get("decode_tokens", 0)
    small = 2.0 * z["d_inner"] + 2.0 * z["state"] + z["heads"]
    return {"flops": mamba * _state_flops(model) * tokens,
            "bytes": mamba * float(STATE_ITEM) * tokens
            * (2.0 * z["d_inner"] * z["state"] + small)}


WORK = {
    "serve_model": serve_model,
    "decode_steps": decode_steps,
    "ssm_state_update": ssm_state_update,
}
