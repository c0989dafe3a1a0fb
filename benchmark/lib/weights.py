"""Weights from ``--seed``, made by the benchmark for the program and for
the plain reference alike: one jitted call on the device, every leaf drawn
from its own fold of the seed, so the same seed gives the same model
whoever asks and in whatever order.

Leaves carry the names the program's ``GPTForCausalLM.functional_state()``
uses, because that dict is how weights are handed to it. The published
initialisation is N(0, 0.02) for matrices and embeddings, zeros for biases
and ones for LayerNorm scales; biases and scales here get a small seeded
perturbation so that a path that dropped one would show in ``correct``.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BIAS_STD = 0.01

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


def num_params(model: dict) -> int:
    n = 0
    for shape, _ in leaf_table(model).values():
        k = 1
        for d in shape:
            k *= d
        n += k
    return n


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, name: str, shape, kind: str):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32)
    if kind == "matrix":
        return INIT_STD * x
    if kind == "scale":
        return 1.0 + BIAS_STD * x
    return BIAS_STD * x


def make_weights(model: dict, seed: int, dtype=jnp.float32) -> dict:
    """Every leaf, drawn in float32 and rounded to ``dtype`` (the type the
    configuration trains or serves in), from one jitted call."""
    table = leaf_table(model)

    @jax.jit
    def make(key):
        return {n: _leaf(key, n, s, k).astype(dtype)
                for n, (s, k) in table.items()}

    return make(seed_key(seed))
