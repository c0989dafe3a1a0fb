"""The ``brumby`` family: everything the benchmark knows of Brumby
(``model_type`` ``brumby``; ``manifestai/Brumby-14B-Base``): the Qwen3 block
with its attention replaced in every layer by gated power retention of
degree 2 (arXiv:2507.04239): grouped KV heads, each with a gate, a key
weighing ``(q . k)^2`` under the gates' decay and a normaliser of the same
weights; QK-norm and rotary positions; a gated SiLU MLP; a final RMSNorm
and an untied head. Serving only.

A configuration's ``model`` group has the ``config.json`` key names, and
``power`` and ``chunk_size``, which the config has no key for.

The parts, in the order ``families/gpt3.py`` has them: ``check`` and
``leaf_table`` (with ``as_used``: the gate's bias the family adds to the
harness's draw); ``build_serving``; the plain reference ``logits_at``,
which imports nothing of the program and computes the retention in its
attention form, a row and a block of query rows at a time, the weights
waiting on the host between uses; ``WORK``.
"""
from __future__ import annotations

import functools
import math
import weakref

import jax
import jax.numpy as jnp

from benchmark.lib import reference
from benchmark.lib.reference import BF16_BITS, HIGHEST, round_f8, round_to

#: bytes a weight takes and a number of the retention state: the
#: configuration's precision (``check`` holds the file to it)
ITEM, STATE_ITEM = 2, 4

#: The gate's bias is USED with a decay spread over the KV heads added to
#: what the harness draws (``as_used``; program and reference alike). The
#: harness draws a ``bias`` as 0.01 N: every gate near 0.5, a state that
#: forgets a token within a few steps, and a fault in carrying it across a
#: thousand decode steps that would never show in the comparison. Here KV
#: head ``g`` of ``n`` adds ``log(gamma / (1 - gamma))`` with ``1 - gamma``
#: spread log-evenly from ``1 - GAMMA_RANGE[0]`` to ``1 - GAMMA_RANGE[1]``:
#: memories from a hundred to ten thousand tokens.
GAMMA_RANGE = (0.99, 0.9999)

#: the normaliser's floor, as the program's (the config states none)
EPS = 1e-6

#: query rows of one block of the reference's attention form: a block's
#: weights ``[heads, rows, positions]`` stand whole
QUERY_ROWS = 256

#: top-level keys of a configuration's file that its ``model`` group
#: repeats: the two have to agree
_SHARED = ("vocab_size", "hidden_size", "intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "head_dim", "max_position_embeddings", "rope_theta",
           "rms_norm_eps", "tie_word_embeddings")
#: the model's keys that the config has none for
_ASSUMED = ("power", "chunk_size")


# ------------------------------------------------------------------ leaves
def check(config: dict) -> None:
    """What a Brumby configuration's file must agree on."""
    m, name = config["model"], config["name"]
    for key in _SHARED:
        if m[key] != config[key]:
            raise ValueError(f"{name}: model.{key} {m[key]!r} is not the "
                             f"file's {config[key]!r}")
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu" \
            or config["rope_scaling"] is not None \
            or config["attention_bias"] or config["use_sliding_window"] \
            or config["sliding_window"] is not None:
        raise ValueError(f"{name}: only the untied head, silu, unscaled "
                         "rotary, no bias and no window are here")
    if m["power"] != 2:
        raise ValueError(f"{name}: only degree 2 is here")
    p = config["precision"]
    if (p["parameters"], p["state"]) != ("bfloat16", "float32"):
        raise ValueError(f"{name}: the work functions count 2 bytes a "
                         "weight and 4 a state number")


def _layer_leaves(m: dict) -> dict:
    h, f, d = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    q = m["num_attention_heads"] * d
    kv = m["num_key_value_heads"] * d
    g = m["num_key_value_heads"]
    return {"input_layernorm.weight": ((h,), "scale"),
            "self_attn.q_proj.weight": ((h, q), "matrix"),
            "self_attn.k_proj.weight": ((h, kv), "matrix"),
            "self_attn.v_proj.weight": ((h, kv), "matrix"),
            "self_attn.o_proj.weight": ((q, h), "matrix"),
            "self_attn.q_norm.weight": ((d,), "scale"),
            "self_attn.k_norm.weight": ((d,), "scale"),
            "self_attn.gate.weight": ((h, g), "matrix"),
            "self_attn.gate.bias": ((g,), "bias"),
            "post_attention_layernorm.weight": ((h,), "scale"),
            "mlp.gate_proj.weight": ((h, f), "matrix"),
            "mlp.up_proj.weight": ((h, f), "matrix"),
            "mlp.down_proj.weight": ((f, h), "matrix")}


def leaf_table(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind) under the names of the program's
    ``BrumbyForCausalLM.functional_state()``; weights are ``[in, out]``."""
    m = model
    h = m["hidden_size"]
    table = {"model.embed_tokens.weight": ((m["vocab_size"], h), "matrix")}
    for i in range(m["num_hidden_layers"]):
        for n, t in _layer_leaves(m).items():
            table[f"model.layers.{i}.{n}"] = t
    table["model.norm.weight"] = ((h,), "scale")
    table["lm_head.weight"] = ((h, m["vocab_size"]), "matrix")
    return table


@jax.jit
def _gate_bias(a):
    n = a.shape[0]
    lo, hi = (math.log10(1.0 - v) for v in GAMMA_RANGE)
    forget = 10.0 ** (lo + (hi - lo) * jnp.arange(n, dtype=jnp.float32)
                      / max(n - 1, 1))
    used = a.astype(jnp.float32) + jnp.log1p(-forget) - jnp.log(forget)
    # as the leaf holds it: rounded to bfloat16 by reduce_precision (not
    # a pair of casts, which the TPU compiler may drop)
    return round_to(used, BF16_BITS).astype(a.dtype)


def as_used(leaves: dict) -> dict:
    """The harness's leaves as this family's model takes them: every leaf
    as drawn, the gates' biases with the decay spread added
    (``GAMMA_RANGE``)."""
    return {n: _gate_bias(a) if n.endswith(".self_attn.gate.bias") else a
            for n, a in leaves.items()}


# ------------------------------------------------------ the program's side
def program_config(model: dict):
    """The program's config of a configuration's ``model`` group."""
    from paddle_tpu.text.brumby import BrumbyConfig

    return BrumbyConfig(**{k: model[k] for k in _SHARED + _ASSUMED})


def build_serving(run, leaves: dict):
    """``ServingEngine`` over the model holding ``leaves``. The model keeps
    no page: the engine allocates its slots' state and nothing else."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.brumby import BrumbyForCausalLM

    from benchmark.lib.common import install_weights

    m, sv = run.config["model"], run.config["serve"]
    # shapes only (LazyGuard): the program's own initializers never run
    with paddle.LazyGuard():
        model = BrumbyForCausalLM(program_config(m))
    install_weights(model, as_used(leaves))
    model.eval()
    return ServingEngine(model, ServingConfig(
        max_batch=sv["max_batch"], page_size=sv["page_size"],
        max_prompt_len=sv["max_prompt_len"],
        enable_prefix_caching=sv["enable_prefix_caching"],
        do_sample=sv["do_sample"], tensor_parallel=sv["tensor_parallel"],
        chunk_size=sv["chunk_size"]))


# ----------------------------------------------------- the plain reference
# A copy of tests/refs/brumby_reference.py (tests/test_benchmark_families.py
# holds the two to the same logits), with the controls' policies: the
# retention in attention form (a quadratic sum over earlier positions, no
# state, no chunk, no cache), one row and one block of QUERY_ROWS query
# rows at a time, the MLP a row at a time, a layer's leaves drawn once a
# run and waiting on the host between uses, and the head at the positions
# asked for alone, a block of the vocabulary at a time.
#
# The controls:
# - ``"fp8"``: one step under what the file states. The operands of every
#   matrix product and the retention's q, k, v rounded to float8 e4m3, as
#   ``families/ouro.py``.
# - ``"state_reset"``: float32, but a position after the prompt sees no key
#   before the prompt's end: a decode that started from a zero state after
#   the prefill. The prompt's length is the first position asked for + 1
#   (``serve.compare`` asks from the prompt's last token on).
def _mm(policy: str):
    """The matrix product of a policy (state_reset's is float32's)."""
    return reference.mm("fp8" if policy == "fp8" else "f32")


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


def _retention_row(p, y, start, m: dict, policy: str):
    """Gated power retention of one sequence, y [s, hidden], in attention
    form. ``start``: where the decode began (state_reset: no position at or
    past it sees a key before it)."""
    mm = _mm(policy)
    s, d, eps = y.shape[0], m["head_dim"], m["rms_norm_eps"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    q = mm(y, p["self_attn.q_proj.weight"]).reshape(s, nq, d)
    k = mm(y, p["self_attn.k_proj.weight"]).reshape(s, nkv, d)
    v = mm(y, p["self_attn.v_proj.weight"]).reshape(s, nkv, d)
    q = _rms_norm(q, p["self_attn.q_norm.weight"], eps)
    k = _rms_norm(k, p["self_attn.k_norm.weight"], eps)
    cos, sin = _rotary_table(s, d, float(m["rope_theta"]))
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    if policy == "fp8":
        q, k, v = round_f8(q), round_f8(k), round_f8(v)
    log_g = jax.nn.log_sigmoid(mm(y, p["self_attn.gate.weight"])
                               + p["self_attn.gate.bias"])
    rep = nq // nkv
    k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
    cum = jnp.repeat(jnp.cumsum(log_g, axis=0), rep, axis=1).T   # [h, s]
    keys = jnp.arange(s)

    def attend(block):
        """Queries ``block`` [t, heads, d] at positions ``at ..``."""
        qb, at = block
        rows = at + jnp.arange(qb.shape[0])
        seen = keys[None, :] <= rows[:, None]
        if policy == "state_reset":
            seen &= (keys[None, :] >= start) | (rows[:, None] < start)
        gap = jax.lax.dynamic_slice_in_dim(cum, at, qb.shape[0], axis=1)
        decay = jnp.exp(jnp.where(seen[None], gap[:, :, None]
                                  - cum[:, None, :], -jnp.inf))
        w = decay * jnp.square(jnp.einsum("qhd,khd->hqk", qb, k,
                                          precision=HIGHEST))
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST) \
            / (jnp.sum(w, axis=-1).T[..., None] + EPS)

    t = math.gcd(s, QUERY_ROWS)
    o = jax.lax.map(attend, (q.reshape(s // t, t, nq, d),
                             jnp.arange(0, s, t)))
    return mm(o.reshape(s, nq * d), p["self_attn.o_proj.weight"])


def _freeze(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("model_items", "policy"),
                   donate_argnums=(1,))
def _layer(p: dict, x, starts, model_items: tuple, policy: str):
    """One layer over x [b, s, hidden], a row at a time; ``p`` holds the
    layer's leaves under their names within the layer (bfloat16, float32
    here): one program for every layer."""
    m = dict(model_items)
    mm = _mm(policy)
    p = {n: a.astype(jnp.float32) for n, a in p.items()}
    eps = m["rms_norm_eps"]

    def row(inp):
        xr, start = inp
        y = _rms_norm(xr, p["input_layernorm.weight"], eps)
        xr = xr + _retention_row(p, y, start, m, policy)
        y = _rms_norm(xr, p["post_attention_layernorm.weight"], eps)
        return xr + mm(jax.nn.silu(mm(y, p["mlp.gate_proj.weight"]))
                       * mm(y, p["mlp.up_proj.weight"]),
                       p["mlp.down_proj.weight"])

    return jax.lax.map(row, (x, starts))


#: vocabulary blocks of the head, at most: one block's float32 columns
#: stand beside the logits, which each block's call writes in place
HEAD_BLOCKS = 8


@functools.partial(jax.jit, static_argnames=("eps", "policy"))
def _head_in(norm, x, positions, eps: float, policy: str):
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    x = _rms_norm(x, norm.astype(jnp.float32), eps)
    return round_f8(x) if policy == "fp8" else x


@functools.partial(jax.jit, static_argnames=("at",), donate_argnums=(0,))
def _head_block(out, x, wb, scale, at: int):
    """Columns ``at ..`` of the logits, written into ``out`` (donated):
    a program whose result is the whole logits would hold them twice."""
    wb = wb.astype(jnp.float32)
    if scale is not None:
        wb = round_to(wb * scale, reference.F8_BITS) / scale
    return jax.lax.dynamic_update_slice_in_dim(
        out, jnp.matmul(x, wb, precision=HIGHEST), at, axis=2)


def _head(norm, w, x, positions, eps: float, policy: str):
    """float32 logits ``[b, n, vocab]`` at ``positions``, a block of the
    vocabulary at a time (``w`` waits on the host)."""
    x = _head_in(norm, x, positions, eps, policy)
    vocab = w.shape[1]
    nb = next(k for k in range(HEAD_BLOCKS, 0, -1) if vocab % k == 0)
    cols = vocab // nb
    # fp8: the whole head's one per-tensor scale, as round_f8 takes it
    scale = None if policy != "fp8" else jnp.float32(
        reference.F8_MAX / max(float(abs(w.astype("float32")).max()),
                               1e-30))
    out = jnp.zeros(x.shape[:2] + (vocab,), jnp.float32)
    for i in range(nb):
        out = _head_block(out, x, w[:, i * cols:(i + 1) * cols], scale,
                          i * cols)
    return out


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


#: leaves_of -> {prefix: its leaves as the program got them, on the host}:
#: every block of requests needs every layer again, and a draw costs a
#: compile. They wait on the host: a block's logits (8 rows x 2,048
#: positions x 151,936, float32) are 9.96 GB of the device's memory
_DRAWN = weakref.WeakKeyDictionary()


@jax.jit
def _as_stored(tree: dict) -> dict:
    return {n: a.astype(jnp.bfloat16) for n, a in tree.items()}


def _leaves(leaves_of, prefix: str) -> dict:
    """The leaves under ``prefix`` as the program got them, drawn once a
    run and kept on the host in the configuration's own bfloat16, which
    holds them exactly (a drawn leaf that it would round is an error);
    every product takes them in float32."""
    kept = _DRAWN.setdefault(leaves_of, {})
    if prefix not in kept:
        drawn = as_used(leaves_of(only=prefix))
        stored = _as_stored(drawn)
        for name in sorted(drawn, key=lambda n: drawn[n].size)[:4]:
            if not bool(jnp.all(stored[name].astype(jnp.float32)
                                == drawn[name])):
                raise ValueError(f"{name} is not held exactly in bfloat16")
        kept[prefix] = jax.device_get(stored)
        del drawn, stored
    return kept[prefix]


def logits_at(leaves_of, ids, positions, model: dict, policy: str = "f32"):
    """float32 logits [b, n, vocab] of the sequences ``ids`` [b, s] at the
    given ``positions`` [b, n] (the logit at position t scores token
    t + 1). ``leaves_of(only=...)`` draws float32 leaves, a layer's at a
    time."""
    if policy not in ("f32", "fp8", "state_reset"):
        raise ValueError(f"unknown policy {policy!r}")
    items = _freeze(model)
    x = _embed(_leaves(leaves_of, "model.embed_tokens.")[
        "model.embed_tokens.weight"], ids)
    # the decode's first position: one past the prompt's last token
    starts = jnp.asarray(positions)[:, 0] + 1
    for i in range(model["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        layer = {n[len(pre):]: a for n, a in _leaves(leaves_of, pre).items()}
        # one layer's leaves on the device at a time: calls that queued
        # ahead would each hold theirs (660 MB at the cell's widths) until
        # they ran, beside the head's 9.96 GB of logits
        x = jax.block_until_ready(_layer(layer, x, starts, items, policy))
    norm = _leaves(leaves_of, "model.norm.")["model.norm.weight"]
    w = _leaves(leaves_of, "lm_head.")["lm_head.weight"]
    return _head(norm, w, x, jnp.asarray(positions), model["rms_norm_eps"],
                 policy)


# -------------------------------------------------------------------- work
def _counts(m: dict) -> dict:
    """Parameters by part, and the retention's sizes, from the sizes
    alone."""
    h, d = m["hidden_size"], m["head_dim"]
    q = m["num_attention_heads"] * d
    kv = m["num_key_value_heads"] * d
    dd = d * (d + 1) // 2
    return {"layers": m["num_hidden_layers"], "D": dd,
            "matmul": 2 * h * q + 2 * h * kv + h * m["num_key_value_heads"]
            + 3 * h * m["intermediate_size"],
            "small": 2 * h + 2 * d + m["num_key_value_heads"],
            "head": h * m["vocab_size"]}


def _state_flops(m: dict) -> float:
    """A token's retention a layer in state form: the update of every
    group's ``S`` (2 a number) and the readout of every query head (2 a
    number of its group's ``S``)."""
    c = _counts(m)
    return 2.0 * c["D"] * m["head_dim"] * (m["num_key_value_heads"]
                                           + m["num_attention_heads"])


def _chunk_flops(m: dict) -> float:
    """A prefill token's pairs inside its chunk, a layer: ``(chunk + 1) /
    2`` keys on average (exact where a tail is whole chunks), scores and
    values, 2 FLOPs each a head a head-size."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] \
        * (m["chunk_size"] + 1) / 2.0


def _state_bytes(m: dict) -> float:
    """What a slot keeps a layer: every group's float32 ``S`` and ``z``
    (34,080,768 B at Brumby-14B's widths)."""
    c = _counts(m)
    return float(STATE_ITEM) * m["num_key_value_heads"] * c["D"] \
        * (m["head_dim"] + 1)


def _weight_bytes(m: dict) -> float:
    """What a decode step reads of the weights: every layer's once and the
    head once (the embedding a row a token)."""
    c = _counts(m)
    return float(ITEM) * (c["layers"] * (c["matmul"] + c["small"])
                          + m["hidden_size"] + c["head"])


def serve_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of every token computed in the traced window: prompt
    and decoded tokens through every layer (2 a matmul parameter, the
    retention in state form, a prefill token's pairs inside its chunk), and
    the head where a token's logits are read (every decoded token, the last
    of a prompt)."""
    c = _counts(model)
    pre = traced.get("prefill_tokens", 0)
    dec = traced.get("decode_tokens", 0)
    heads = traced.get("prefills", 0) + dec
    per_token = 2.0 * c["matmul"] + _state_flops(model)
    return {"flops": c["layers"] * (per_token * (pre + dec)
                                    + _chunk_flops(model) * pre)
            + 2.0 * c["head"] * heads,
            "bytes": 0.0}


def decode_steps(model: dict, traced: dict) -> dict:
    """What the traced decode steps must do: read every weight once a step
    and the live slots' state, read and written, once a layer."""
    c = _counts(model)
    tokens = traced.get("decode_tokens", 0)
    return {"flops": (c["layers"] * (2.0 * c["matmul"] + _state_flops(model))
                      + 2.0 * c["head"]) * tokens,
            "bytes": _weight_bytes(model) * traced.get("decode_steps", 0)
            + 2.0 * c["layers"] * _state_bytes(model) * tokens}


def power_retention_decode(model: dict, traced: dict) -> dict:
    """The decode retention kernel of every layer of every traced decode
    step: the live slots' float32 ``S`` and ``z`` read and written once,
    ``q``, ``k``, ``v`` and the gate in and the output out (float32).
    HBM-bound."""
    c = _counts(model)
    tokens = traced.get("decode_tokens", 0)
    d, nq, nkv = model["head_dim"], model["num_attention_heads"], \
        model["num_key_value_heads"]
    small = float(STATE_ITEM) * (2 * nq * d + 2 * nkv * d + nkv)
    return {"flops": c["layers"] * _state_flops(model) * tokens,
            "bytes": c["layers"] * tokens
            * (2.0 * _state_bytes(model) + small)}


WORK = {
    "serve_model": serve_model,
    "decode_steps": decode_steps,
    "power_retention_decode": power_retention_decode,
}
