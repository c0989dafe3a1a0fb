"""ResNet50 training throughput bench (BASELINE.md's second headline row:
images/sec/chip — reference model benchmarks run ResNet50 via the external
benchmark repo, tools/ci_model_benchmark.sh).

Same harness shape as bench.py: functional train step (bf16 params + fp32
master weights, Momentum+CE), INNER steps fused per dispatch via lax.scan,
median step time. On CPU it prints a smoke line (resnet18, tiny batch) —
never presented as an accelerator number.

Usage: python tools/resnet_bench.py            (auto platform)
       JAX_PLATFORMS=cpu python tools/resnet_bench.py
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np


def build_step(arch: str, batch: int, image: int, n_classes: int = 1000):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.core import rng as rng_mod, tape as tape_mod
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.vision import models

    paddle.seed(0)
    model = getattr(models, arch)(num_classes=n_classes)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters(),
                                    multi_precision=True)
    params, buffers = model.functional_state()
    p_arrays = {k: v._value for k, v in params.items() if not v.stop_gradient}
    n_params = sum(int(np.prod(v.shape)) for v in p_arrays.values())
    opt_state = opt.functional_init(p_arrays)

    def loss_fn(pvals, key, x, y):
        import paddle_tpu.nn.functional as F

        with tape_mod.no_grad(), rng_mod.trace_rng_scope(key):
            logits, _ = model.functional_call(pvals, {}, Tensor(x))
            loss = F.cross_entropy(
                Tensor(logits._value.astype("float32"))
                if hasattr(logits, "_value") else logits, Tensor(y))
        return loss._value

    def train_step(pvals, opt_st, key, x, y):
        import jax

        loss, grads = jax.value_and_grad(loss_fn)(pvals, key, x, y)
        new_p, new_st = opt.functional_update(pvals, grads, opt_st, 0.1)
        return loss, new_p, new_st

    return train_step, p_arrays, opt_state, n_params


def measure(arch: str, batch: int, image: int, steps=6, warmup=2,
            inner=None):
    import jax
    import jax.numpy as jnp

    train_step, p_arrays, opt_state, n_params = build_step(arch, batch, image)
    dev = jax.devices()[0]
    INNER = inner or int(os.environ.get("BENCH_INNER_STEPS", "8"))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_multi(pvals, opt_st, key, xs, ys):
        def body(carry, b):
            p, st = carry
            x, y = b
            loss, p, st = train_step(p, st, key, x, y)
            return (p, st), loss

        (pvals, opt_st), losses = jax.lax.scan(body, (pvals, opt_st),
                                               (xs, ys))
        return losses[-1], pvals, opt_st

    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.rand(INNER, batch, 3, image, image),
                     jnp.bfloat16)
    ys = jnp.asarray(rng.randint(0, 1000, (INNER, batch)), jnp.int32)
    key = jax.random.key(0)

    t0 = time.perf_counter()
    for _ in range(warmup):
        loss, p_arrays, opt_state = train_multi(p_arrays, opt_state, key,
                                                xs, ys)
        float(np.asarray(loss))
    print(f"[resnet_bench] {arch} b{batch}: warmup+compile "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss, p_arrays, opt_state = train_multi(p_arrays, opt_state, key,
                                                xs, ys)
        float(np.asarray(loss))
        times.append(time.perf_counter() - t0)
    dt = float(np.median(times)) / INNER
    ips = batch / dt
    return {
        "metric": f"{arch}_train_images_per_sec_per_chip"
                  if dev.platform != "cpu"
                  else f"{arch}_smoke_train_images_per_sec_cpu",
        "value": round(ips, 1),
        "unit": "images/s",
        "vs_baseline": None,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "mfu": None,
        "config": {"arch": arch, "params_m": round(n_params / 1e6, 1),
                   "batch": batch, "image": image, "inner": INNER},
    }


def main():
    import jax

    on_cpu = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if on_cpu:
        jax.config.update("jax_platforms", "cpu")
        result = measure("resnet18", batch=4, image=32, steps=2, warmup=1,
                         inner=2)
    else:
        # OOM ladder: b256 -> b128 -> b64
        result = None
        for b in (256, 128, 64):
            try:
                result = measure("resnet50", batch=b, image=224)
                break
            except Exception as e:  # noqa: BLE001
                s = f"{type(e).__name__}: {e}"
                if "RESOURCE_EXHAUSTED" not in s and "memory" not in s:
                    raise
                print(f"[resnet_bench] b{b} OOM; trying smaller",
                      file=sys.stderr, flush=True)
        if result is None:
            raise RuntimeError("no resnet batch size fit")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
