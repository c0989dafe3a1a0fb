"""The operations and bytes that the algorithm needs, from the
configuration's sizes and from what the traffic did in the traced window
(``traced``: counts of steps, tokens and context lengths that the drivers
record) — never from the implementation, so a kernel's roofline reads the
same work whatever implements it.

Every function returns ``{"flops": ..., "bytes": ...}`` for all the calls
of the traced window together. A training step's ``calls`` are counted by
the reader, from the trace: steps are dispatched ahead of the device, so
the host's count of dispatches is of other steps than the window holds.
"""
from __future__ import annotations

from .weights import num_params


def _sizes(model: dict):
    return (model["num_layers"], model["hidden_size"], model["num_heads"],
            model["hidden_size"] // model["num_heads"])


def train_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of training: ``6 N + 12 L s h`` per token (forward and
    backward of every matrix product, and of attention's two at full
    sequence length; recomputation does not count) — ``bench.py``'s
    arithmetic, copied."""
    layers, h, _, _ = _sizes(model)
    per_token = 6.0 * num_params(model) + 12.0 * layers * traced["seq"] * h
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
    return float(num_params(model)) * itemsize


def serve_model(model: dict, traced: dict) -> dict:
    """Model FLOPs of every token computed in the traced window: prompt
    tokens not served from the cache, and decoded tokens. ``2 N`` a token
    and ``4 L h`` a token of context attended."""
    layers, h, _, _ = _sizes(model)
    tokens = traced.get("prefill_tokens", 0) + traced.get("decode_tokens", 0)
    ctx = traced.get("prefill_ctx_tokens", 0) \
        + traced.get("decode_ctx_tokens", 0)
    return {"flops": 2.0 * num_params(model) * tokens
            + 4.0 * layers * h * ctx, "bytes": 0.0}


def decode_steps(model: dict, traced: dict) -> dict:
    """What the traced decode steps must do: read every weight once a step
    and the keys and values of the live contexts (float32)."""
    layers, h, _, _ = _sizes(model)
    steps = traced.get("decode_steps", 0)
    ctx = traced.get("decode_ctx_tokens", 0)
    return {"flops": 2.0 * num_params(model) * traced.get("decode_tokens", 0)
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


FUNCTIONS = {
    "train_model": train_model,
    "flash_train": flash_train,
    "serve_model": serve_model,
    "decode_steps": decode_steps,
    "ragged_attention": ragged_attention,
}
