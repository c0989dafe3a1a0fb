"""BERT-base pretraining throughput bench (the reference model-benchmark
family's third headline after GPT and ResNet50: BERT MLM+NSP sequences/sec,
tools/ci_model_benchmark.sh spirit).

Same harness shape as resnet_bench.py: functional train step (bf16 params +
fp32 master weights, AdamW, fused chunked MLM head so [b, s, vocab] logits
never materialize), INNER steps fused per dispatch via lax.scan, median
step time, host-fetch sync. On CPU it prints a tiny smoke line.

Usage: python tools/bert_bench.py            (auto platform)
       JAX_PLATFORMS=cpu python tools/bert_bench.py
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np


def build_step(cfg_kwargs, batch, seq, lr=1e-4):
    import paddle_tpu as paddle
    from paddle_tpu.core import rng as rng_mod, tape as tape_mod
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.bert import BertConfig, BertForPretraining

    paddle.seed(0)
    cfg = BertConfig(**cfg_kwargs)
    model = BertForPretraining(cfg)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    params, _ = model.functional_state()
    p_arrays = {k: v._value for k, v in params.items() if not v.stop_gradient}
    n_params = sum(int(np.prod(v.shape)) for v in p_arrays.values())
    opt_state = opt.functional_init(p_arrays)

    def loss_fn(pvals, key, ids, mlm_labels, nsp_labels):
        with tape_mod.no_grad(), rng_mod.trace_rng_scope(key):
            loss = model.functional_call(
                pvals, {}, Tensor(ids),
                masked_lm_labels=Tensor(mlm_labels),
                next_sentence_labels=Tensor(nsp_labels))[0]
        return loss._value.astype("float32")

    def train_step(pvals, opt_st, key, ids, mlm, nsp):
        import jax

        loss, grads = jax.value_and_grad(loss_fn)(pvals, key, ids, mlm, nsp)
        new_p, new_st = opt.functional_update(pvals, grads, opt_st, lr)
        return loss, new_p, new_st

    return train_step, p_arrays, opt_state, n_params, cfg


def measure(cfg_kwargs, batch, seq, steps=6, warmup=2, inner=None,
            mask_frac=0.15):
    import jax
    import jax.numpy as jnp

    train_step, p_arrays, opt_state, n_params, cfg = build_step(
        cfg_kwargs, batch, seq)
    dev = jax.devices()[0]
    INNER = inner or int(os.environ.get("BENCH_INNER_STEPS", "8"))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_multi(pvals, opt_st, key, ids, mlm, nsp):
        def body(carry, b):
            p, st = carry
            loss, p, st = train_step(p, st, key, *b)
            return (p, st), loss

        (pvals, opt_st), losses = jax.lax.scan(
            body, (pvals, opt_st), (ids, mlm, nsp))
        return losses[-1], pvals, opt_st

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (INNER, batch, seq)),
                      jnp.int32)
    # MLM labels: mask_frac positions labeled, rest ignore_index -1
    mlm = np.full((INNER, batch, seq), -1, np.int32)
    sel = rng.rand(INNER, batch, seq) < mask_frac
    mlm[sel] = rng.randint(0, cfg.vocab_size, int(sel.sum()))
    mlm = jnp.asarray(mlm)
    nsp = jnp.asarray(rng.randint(0, 2, (INNER, batch)), jnp.int32)
    key = jax.random.key(0)

    t0 = time.perf_counter()
    for _ in range(warmup):
        loss, p_arrays, opt_state = train_multi(p_arrays, opt_state, key,
                                                ids, mlm, nsp)
        float(np.asarray(loss))
    print(f"[bert_bench] b{batch} s{seq}: warmup+compile "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss, p_arrays, opt_state = train_multi(p_arrays, opt_state, key,
                                                ids, mlm, nsp)
        float(np.asarray(loss))
        times.append(time.perf_counter() - t0)
    dt = float(np.median(times)) / INNER
    sps = batch / dt
    on_tpu = dev.platform != "cpu"
    return {
        "metric": "bert_base_pretrain_sequences_per_sec_per_chip"
                  if on_tpu else "bert_smoke_sequences_per_sec_cpu",
        "value": round(sps, 1),
        "unit": "sequences/s",
        "vs_baseline": None,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "mfu": None,
        "config": {"params_m": round(n_params / 1e6, 1), "batch": batch,
                   "seq": seq, "layers": cfg.num_layers,
                   "hidden": cfg.hidden_size, "inner": INNER},
    }


def main():
    import jax

    on_cpu = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if on_cpu:
        jax.config.update("jax_platforms", "cpu")
        result = measure(dict(vocab_size=512, hidden_size=64, num_layers=2,
                              num_heads=4, intermediate_size=128,
                              hidden_dropout=0.0, attn_dropout=0.0),
                         batch=4, seq=32, steps=2, warmup=1, inner=2)
    else:
        result = None
        for b in (64, 32, 16):  # OOM ladder, classic seq 128 pretraining
            try:
                result = measure(dict(hidden_dropout=0.0, attn_dropout=0.0),
                                 batch=b, seq=128)
                break
            except Exception as e:  # noqa: BLE001
                s = f"{type(e).__name__}: {e}"
                if "RESOURCE_EXHAUSTED" not in s and "memory" not in s:
                    raise
                print(f"[bert_bench] b{b} OOM; next rung", file=sys.stderr,
                      flush=True)
        if result is None:
            raise RuntimeError("no BERT rung fit on the device")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
