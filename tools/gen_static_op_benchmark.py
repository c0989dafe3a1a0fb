"""Generate paddle_tpu/cost_model/static_op_benchmark.json.

The reference ships a GPU-measured static_op_benchmark.json consumed by
CostModel.get_static_op_time (cost_model/cost_model.py:61-86). Here each
entry is measured on the current JAX backend with provenance recorded
(device field) — rerun on a TPU-attached host to refresh with on-chip times.

Usage: JAX_PLATFORMS=cpu python tools/gen_static_op_benchmark.py
"""
import json
import os
import sys
import time

# this table must generate anywhere, so force CPU unless the caller opts
# into on-chip regeneration with GENOP_PLATFORM=tpu
os.environ["JAX_PLATFORMS"] = os.environ.get("GENOP_PLATFORM", "cpu")

import jax

jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


def _bench(fn, *args, iters=5):
    fn_j = jax.jit(fn)
    out = fn_j(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn_j(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def _bench_pair(fn, *args):
    fwd_ms = _bench(fn, *args)

    def loss(*a):
        return jnp.sum(jnp.abs(jnp.asarray(fn(*a), jnp.float32)))

    grad = jax.grad(loss, argnums=0)
    bwd_ms = _bench(grad, *args)
    return fwd_ms, bwd_ms


def main():
    rng = np.random.RandomState(0)
    f32 = lambda *s: jnp.asarray(rng.rand(*s), jnp.float32)
    entries = []
    device = jax.devices()[0].platform

    cases = [
        ("matmul", "float32 [512,512]x[512,512]",
         lambda a, b: a @ b, (f32(512, 512), f32(512, 512))),
        ("matmul", "float32 [1024,1024]x[1024,1024]",
         lambda a, b: a @ b, (f32(1024, 1024), f32(1024, 1024))),
        ("conv2d", "float32 [4,32,28,28]k3",
         lambda x, w: jax.lax.conv_general_dilated(
             x, w, (1, 1), "SAME"), (f32(4, 32, 28, 28), f32(32, 32, 3, 3))),
        ("relu", "float32 [1048576]", lambda x: jnp.maximum(x, 0),
         (f32(1048576),)),
        ("gelu", "float32 [1048576]", jax.nn.gelu, (f32(1048576),)),
        ("softmax", "float32 [256,4096]",
         lambda x: jax.nn.softmax(x, -1), (f32(256, 4096),)),
        ("layer_norm", "float32 [256,4096]",
         lambda x: (x - x.mean(-1, keepdims=True))
         / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5), (f32(256, 4096),)),
        ("reduce_sum", "float32 [4096,4096]", lambda x: x.sum(),
         (f32(4096, 4096),)),
        ("transpose", "float32 [2048,2048]", lambda x: x.T.copy(),
         (f32(2048, 2048),)),
        ("elementwise_add", "float32 [1048576]", lambda a, b: a + b,
         (f32(1048576), f32(1048576))),
        ("elementwise_mul", "float32 [1048576]", lambda a, b: a * b,
         (f32(1048576), f32(1048576))),
        ("sigmoid", "float32 [1048576]", jax.nn.sigmoid, (f32(1048576),)),
        ("tanh", "float32 [1048576]", jnp.tanh, (f32(1048576),)),
        ("sqrt", "float32 [1048576]", jnp.sqrt, (f32(1048576),)),
        ("embedding", "float32 [50304,512]g[8192]",
         lambda w, i: w[i],
         (f32(50304, 512), jnp.asarray(rng.randint(0, 50304, 8192)))),
        ("batch_norm", "float32 [4,32,28,28]",
         lambda x: (x - x.mean((0, 2, 3), keepdims=True))
         / jnp.sqrt(x.var((0, 2, 3), keepdims=True) + 1e-5),
         (f32(4, 32, 28, 28),)),
        ("pool2d", "float32 [4,32,28,28]w2",
         lambda x: jax.lax.reduce_window(
             x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID"),
         (f32(4, 32, 28, 28),)),
        ("dropout", "float32 [1048576]",
         lambda x: x * jax.random.bernoulli(
             jax.random.PRNGKey(0), 0.9, x.shape) / 0.9, (f32(1048576),)),
        ("cross_entropy", "float32 [256,50304]",
         lambda x, y: -jnp.take_along_axis(
             jax.nn.log_softmax(x, -1), y[:, None], 1).mean(),
         (f32(256, 50304), jnp.asarray(rng.randint(0, 50304, 256)))),
        ("mean", "float32 [4096,4096]", lambda x: x.mean(), (f32(4096, 4096),)),
    ]

    for op, config, fn, args in cases:
        try:
            fwd_ms, bwd_ms = _bench_pair(fn, *args)
        except Exception as e:  # non-differentiable first arg etc.
            fwd_ms, bwd_ms = _bench(fn, *args), None
        entries.append({
            "op": op,
            "config": config,
            "paddle_tpu_time": round(fwd_ms, 5),
            "paddle_tpu_time_backward":
                round(bwd_ms, 5) if bwd_ms is not None else None,
            "device": device,
        })
        print(f"{op:20s} {config:34s} fwd {fwd_ms:8.3f} ms  "
              f"bwd {bwd_ms if bwd_ms is None else round(bwd_ms, 3)} ms")

    out = os.path.join(os.path.dirname(__file__), os.pardir, "paddle_tpu",
                       "cost_model", "static_op_benchmark.json")
    with open(out, "w") as f:
        json.dump(entries, f, indent=1)
    print("wrote", os.path.abspath(out))


if __name__ == "__main__":
    main()
