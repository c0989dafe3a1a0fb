"""The ``gpt3`` family: everything the benchmark knows of GPT-3 (Brown et
al. 2020; the GPT-2 block: pre-LayerNorm, fused qkv projection, causal
softmax attention, GELU (tanh form) MLP of 4x width, learned positions,
tied embedding). A configuration names its family (``"family"`` in its
file) and the drivers take from the family's module, found by that name
alone, the five parts that this file has in this order:

1. ``check(config)`` and ``leaf_table(model)``: what the sizes must
   satisfy, and ``{name: (shape, kind)}`` of every leaf under the names
   the program's ``GPTForCausalLM.functional_state()`` uses, because that
   dict is how weights are handed to it (``lib/weights.py`` draws them);
2. the program's side, ``build_serving(run, leaves)`` -> the engine and
   ``build_training(run, leaves)`` -> ``(step, state, feed)``: the only
   code of the benchmark that imports the model's class;
3. the plain reference, which imports nothing of the program:
   ``logits_at`` for serving, ``loss_and_grads`` and ``parts`` for
   training;
4. ``WORK``: the operations and bytes that the algorithm needs, from the
   configuration's sizes and from what the traffic did in the traced
   window (``traced``: counts of steps, tokens and context lengths that
   the drivers record) - never from the implementation, so a kernel's
   roofline reads the same work whatever implements it. Every function
   returns ``{"flops": ..., "bytes": ...}`` for all the calls of the
   traced window together. A training step's ``calls`` are counted by the
   reader, from the trace: steps are dispatched ahead of the device, so
   the host's count of dispatches is of other steps than the window holds.

(The fifth, counters, needs no code here: a metric file names the engine's
counter and ``lib/serve.py`` snapshots it.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.lib import reference
from benchmark.lib.reference import HIGHEST, round_f8
from benchmark.lib.weights import num_params

LN_EPS = 1e-5


# ------------------------------------------------------------------ leaves
#: per-block leaves: name -> (shape as a function of (h, f), kind)
_BLOCK = {
    "ln1.weight": (lambda h, f: (h,), "scale"),
    "ln1.bias": (lambda h, f: (h,), "bias"),
    "attn.qkv_proj.weight": (lambda h, f: (h, 3 * h), "matrix"),
    "attn.qkv_proj.bias": (lambda h, f: (3 * h,), "bias"),
    "attn.out_proj.weight": (lambda h, f: (h, h), "matrix"),
    "attn.out_proj.bias": (lambda h, f: (h,), "bias"),
    "ln2.weight": (lambda h, f: (h,), "scale"),
    "ln2.bias": (lambda h, f: (h,), "bias"),
    "mlp.fc1.weight": (lambda h, f: (h, f), "matrix"),
    "mlp.fc1.bias": (lambda h, f: (f,), "bias"),
    "mlp.fc2.weight": (lambda h, f: (f, h), "matrix"),
    "mlp.fc2.bias": (lambda h, f: (h,), "bias"),
}


def check(config: dict) -> None:
    """The sizes a GPT-3 configuration's file must agree on."""
    m = config["model"]
    if m["head_size"] * m["num_heads"] != m["hidden_size"]:
        raise ValueError(f"{config['name']}: head_size x num_heads is not "
                         "hidden_size")
    if m["ffn_hidden"] != 4 * m["hidden_size"]:
        raise ValueError(f"{config['name']}: ffn_hidden is not 4 x "
                         "hidden_size")


def leaf_table(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, kind) for every leaf of a configuration's ``model``
    group (tied embedding: no separate head)."""
    h, f = model["hidden_size"], model["ffn_hidden"]
    table = {"gpt.wte.weight": ((model["vocab_size"], h), "matrix"),
             "gpt.wpe.weight": ((model["max_seq_len"], h), "matrix")}
    for i in range(model["num_layers"]):
        for name, (shape, kind) in _BLOCK.items():
            table[f"gpt.blocks.{i}.{name}"] = (shape(h, f), kind)
    table["gpt.ln_f.weight"] = ((h,), "scale")
    table["gpt.ln_f.bias"] = ((h,), "bias")
    return table


# ------------------------------------------------------ the program's side
def _model(run, leaves: dict, **config):
    """The program's model holding the benchmark's weights."""
    import paddle_tpu as paddle
    from paddle_tpu.text.gpt import GPTForCausalLM, gpt_config

    from benchmark.lib.common import install_weights

    cfg, m = run.config, run.config["model"]
    gcfg = gpt_config(cfg["program_preset"], max_seq_len=m["max_seq_len"],
                      dropout=m["dropout"], **config)
    # the configuration's sizes are the program preset's, or nothing runs
    for key in ("vocab_size", "hidden_size", "num_layers", "num_heads",
                "ffn_hidden"):
        if m[key] != getattr(gcfg, key):
            raise SystemExit(f"{cfg['name']}: {key} {m[key]} is not the "
                             f"program preset's {getattr(gcfg, key)}")
    # shapes only (LazyGuard): the program's own initializers never run
    with paddle.LazyGuard():
        model = GPTForCausalLM(gcfg)
    install_weights(model, leaves)
    return model


def build_serving(run, leaves: dict):
    """``ServingEngine`` (a copy of ``chip_smoke.build_serve_model`` /
    ``serve_config`` as PR 21 ran them) over ``leaves``."""
    from paddle_tpu.serving import ServingConfig, ServingEngine

    from benchmark.lib.serve import pool_pages

    m, sv = run.config["model"], run.config["serve"]
    model = _model(run, leaves)
    model.eval()
    # a page holds the keys and the values of every layer, float32
    page_bytes = 2 * m["num_layers"] * sv["page_size"] * m["hidden_size"] * 4
    return ServingEngine(model, ServingConfig(
        max_batch=sv["max_batch"], num_pages=pool_pages(sv, page_bytes),
        page_size=sv["page_size"], max_prompt_len=sv["max_prompt_len"],
        enable_prefix_caching=sv["enable_prefix_caching"],
        do_sample=sv["do_sample"], tensor_parallel=sv["tensor_parallel"],
        chunk_size=sv["chunk_size"]))


def build_training(run, leaves: dict):
    """(step, state, feed) of ``lib/train.py``'s ``hybrid_step`` over the
    model holding ``leaves``."""
    from benchmark.lib.train import hybrid_step

    tr = run.config["train"]
    return hybrid_step(run, _model(run, leaves,
                                   loss_chunk_size=tr["loss_chunk_size"],
                                   recompute=tr["recompute"]))


# ----------------------------------------------------- the plain reference
def _layer_norm(x, w, b):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + LN_EPS)
    return (y * w + b).astype(x.dtype)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden_states(p: dict, ids, model: dict, policy: str = "f32"):
    """[b, s] token ids -> [b, s, h] after the final LayerNorm."""
    mm = reference.mm(policy)
    act = jnp.bfloat16 if policy == "bf16" else jnp.float32
    nh = model["num_heads"]
    b, s = ids.shape
    hd = model["hidden_size"] // nh
    g = lambda name: p[name].astype(act)  # noqa: E731
    x = g("gpt.wte.weight")[ids] + g("gpt.wpe.weight")[jnp.arange(s)][None]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(model["num_layers"]):
        pre = f"gpt.blocks.{i}."
        y = _layer_norm(x, g(pre + "ln1.weight"), g(pre + "ln1.bias"))
        qkv = mm(y, g(pre + "attn.qkv_proj.weight")) \
            + g(pre + "attn.qkv_proj.bias")
        qkv = qkv.reshape(b, s, 3, nh, hd)
        q, k, v = (jnp.transpose(qkv[:, :, j], (0, 2, 1, 3))
                   for j in range(3))                      # [b, nh, s, hd]
        if policy == "fp8":
            q, k, v = round_f8(q), round_f8(k), round_f8(v)
        prec = None if policy == "bf16" else HIGHEST
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(act)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, v, precision=prec,
                       preferred_element_type=jnp.float32).astype(act)
        o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, nh * hd)
        x = x + (mm(o, g(pre + "attn.out_proj.weight"))
                 + g(pre + "attn.out_proj.bias")).astype(act)
        y = _layer_norm(x, g(pre + "ln2.weight"), g(pre + "ln2.bias"))
        y = _gelu((mm(y, g(pre + "mlp.fc1.weight"))
                   + g(pre + "mlp.fc1.bias")).astype(act))
        x = x + (mm(y, g(pre + "mlp.fc2.weight"))
                 + g(pre + "mlp.fc2.bias")).astype(act)
    return _layer_norm(x, g("gpt.ln_f.weight"), g("gpt.ln_f.bias"))


@functools.partial(jax.jit, static_argnames=("model_items", "policy"))
def _logits_at(p: dict, ids, positions, model_items: tuple, policy: str):
    h = hidden_states(p, ids, dict(model_items), policy)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    wte = p["gpt.wte.weight"]
    if policy == "bf16":
        wte = wte.astype(jnp.bfloat16)
    return reference.mm(policy)(h, wte.T).astype(jnp.float32)


def logits_at(leaves_of, ids, positions, model: dict, policy: str = "f32"):
    """float32 logits [b, n, vocab] of the sequences ``ids`` [b, s] at the
    given ``positions`` [b, n] (the logit at position t scores token
    t + 1). ``leaves_of(only=None)`` draws the float32 leaves; this model
    fits whole beside one block's activations, so it takes them all."""
    return _logits_at(leaves_of(), ids, positions, reference.freeze(model),
                      policy)


def mean_loss(p: dict, ids, labels, model: dict, policy: str = "f32"):
    """Mean cross-entropy of ``labels`` [b, s] under the model, over every
    position."""
    h = hidden_states(p, ids, model, policy)
    logits = reference.mm(policy)(h, p["gpt.wte.weight"].T).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def loss_and_grads(p: dict, ids, labels, model: dict, policy: str = "f32",
                   rows: int = 2):
    """Loss and gradient of the whole batch, ``rows`` rows to a block."""
    return reference.loss_and_grads_by_blocks(mean_loss, p, ids, labels,
                                              model, policy, rows)


def parts(tree: dict) -> dict:
    """The leaves as the published model has them: a fused ``qkv_proj``
    leaf is the query, key and value projections side by side, and each is
    a leaf of its own here (the key's bias has no gradient under softmax;
    fused, it would hide in a leaf that has)."""
    out = {}
    for n, a in tree.items():
        if "qkv_proj" in n:
            for tag, part in zip("qkv", jnp.split(a, 3, axis=-1)):
                out[f"{n}[{tag}]"] = part
        else:
            out[n] = a
    return out


# -------------------------------------------------------------------- work
def _sizes(model: dict):
    return (model["num_layers"], model["hidden_size"], model["num_heads"],
            model["hidden_size"] // model["num_heads"])


def _num_params(model: dict) -> int:
    return num_params(leaf_table(model))


def train_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of training: ``6 N + 12 L s h`` per token (forward and
    backward of every matrix product, and of attention's two at full
    sequence length; recomputation does not count) — ``bench.py``'s
    arithmetic, copied."""
    layers, h, _, _ = _sizes(model)
    per_token = 6.0 * _num_params(model) + 12.0 * layers * traced["seq"] * h
    tokens = traced.get("calls", 0) * traced["batch"] * traced["seq"]
    return {"flops": per_token * tokens, "bytes": 0.0}


def flash_train(model: dict, traced: dict) -> dict:
    """Causal attention, forward and backward, of every layer of every
    traced step. Seven ``s x s x d`` products a head (scores and values
    forward; scores again, dP, dV, dQ, dK backward), each halved by
    causality: ``7 b nh s^2 d`` FLOPs a layer. Bytes in bfloat16: q, k, v
    read and o written forward; q, k, v, o, dO read and dQ, dK, dV written
    backward: twelve ``b s h`` tensors a layer."""
    layers, h, nh, d = _sizes(model)
    b, s = traced["batch"], traced["seq"]
    calls = traced.get("calls", 0) * layers
    return {"flops": 7.0 * b * nh * s * s * d * calls,
            "bytes": 12.0 * b * s * h * 2 * calls}


def _weight_bytes(model: dict, itemsize: int = 4) -> float:
    return float(_num_params(model)) * itemsize


def serve_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of every token computed in the traced window: prompt
    tokens not served from the cache, and decoded tokens. ``2 N`` a token
    and ``4 L h`` a token of context attended."""
    layers, h, _, _ = _sizes(model)
    tokens = traced.get("prefill_tokens", 0) + traced.get("decode_tokens", 0)
    ctx = traced.get("prefill_ctx_tokens", 0) \
        + traced.get("decode_ctx_tokens", 0)
    return {"flops": 2.0 * _num_params(model) * tokens
            + 4.0 * layers * h * ctx, "bytes": 0.0}


def decode_steps(model: dict, traced: dict) -> dict:
    """What the traced decode steps must do: read every weight once a step
    and the keys and values of the live contexts (float32)."""
    layers, h, _, _ = _sizes(model)
    steps = traced.get("decode_steps", 0)
    ctx = traced.get("decode_ctx_tokens", 0)
    return {"flops": 2.0 * _num_params(model) * traced.get("decode_tokens", 0)
            + 4.0 * layers * h * ctx,
            "bytes": _weight_bytes(model) * steps
            + 2.0 * layers * h * 4 * ctx}


def ragged_attention(model: dict, traced: dict) -> dict:
    """Paged attention of every layer of every traced decode step and
    prefill: the keys and values of the live contexts (not of every page
    of the table), the queries in and the outputs back, float32."""
    layers, h, _, _ = _sizes(model)
    kv_tokens = traced.get("decode_ctx_tokens", 0) \
        + traced.get("prefill_kv_tokens", 0)
    q_tokens = traced.get("decode_tokens", 0) \
        + traced.get("prefill_tokens", 0)
    attended = traced.get("decode_ctx_tokens", 0) \
        + traced.get("prefill_ctx_tokens", 0)
    return {"flops": 4.0 * layers * h * attended,
            "bytes": layers * h * 4 * (2.0 * kv_tokens + 2.0 * q_tokens)}


WORK = {
    "train_model": train_model,
    "flash_train": flash_train,
    "serve_model": serve_model,
    "decode_steps": decode_steps,
    "ragged_attention": ragged_attention,
}
