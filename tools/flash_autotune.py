"""On-chip flash-attention block-size autotune. For each (seq, head_dim) in the
bench-relevant set, times fwd+bwd of the Pallas dense-block kernel across
candidate block edges and writes the winners to
paddle_tpu/kernels/flash_tuned.json — the single `_block` source consults it,
so the dispatch gate and launch config stay consistent automatically.

TPU only (pallas kernels don't run on the CPU backend): exits non-zero
otherwise. The sweep records are printed with the table.

Usage: python tools/flash_autotune.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import paddle_tpu  # noqa: F401 — honours JAX_PLATFORMS=cpu, sets the cache
import numpy as np

SHAPES = [  # (batch, heads, seq, head_dim) — bench rungs + long-context
    (8, 16, 1024, 64),
    (4, 16, 2048, 64),
    (2, 16, 4096, 64),
    (1, 16, 8192, 64),
    (8, 8, 1024, 128),
]
CANDIDATES = [128, 256, 512, 1024]


def _time_config(q, k, v, block):
    import jax.numpy as jnp

    from _timing import time_grad_fn
    from paddle_tpu.kernels import flash_attention as fa

    fa._TUNED = {f"{q.shape[2]},{q.shape[3]}": block}

    def loss(q, k, v):
        return jnp.sum(fa._flash(q, k, v, True, 0.125).astype(jnp.float32))

    return time_grad_fn(loss, (q, k, v), iters=5, inner=40)


def main():
    import jax

    if jax.default_backend() != "tpu":
        sys.exit("[flash_autotune] needs a TPU: the compiled kernel is "
                 "what is tuned")
    dev = jax.devices()[0]
    table = {}
    records = []
    for b, h, s, d in SHAPES:
        rng = np.random.RandomState(0)
        import jax.numpy as jnp

        q = jnp.asarray(rng.rand(b, h, s, d), jnp.bfloat16)
        k = jnp.asarray(rng.rand(b, h, s, d), jnp.bfloat16)
        v = jnp.asarray(rng.rand(b, h, s, d), jnp.bfloat16)
        results = {}
        for blk in CANDIDATES:
            if blk > s or s % blk:
                continue
            try:
                results[blk] = _time_config(q, k, v, blk)
                print(f"[flash_autotune] s={s} d={d} block={blk}: "
                      f"{results[blk] * 1e3:.2f} ms", file=sys.stderr,
                      flush=True)
            except Exception as e:  # noqa: BLE001 — OOM/unsupported config
                print(f"[flash_autotune] s={s} d={d} block={blk}: "
                      f"{type(e).__name__}", file=sys.stderr, flush=True)
        if not results:
            continue
        best = min(results, key=results.get)
        default_t = results.get(min(512, s))
        table[f"{s},{d}"] = best
        records.append({
            "metric": "flash_attention_fwdbwd_ms",
            "value": round(results[best] * 1e3, 3),
            "unit": "ms",
            "vs_baseline": round(default_t / results[best], 3)
            if default_t else None,
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", "?"),
            "config": {"batch": b, "heads": h, "seq": s, "head_dim": d,
                       "best_block": best,
                       "sweep_ms": {str(kk): round(vv * 1e3, 3)
                                    for kk, vv in results.items()}},
            "provenance": "rung-experiment (flash_autotune)",
        })

    # validate BEFORE writing: a misaligned entry would otherwise be
    # rejected at every future load (kernels/flash_attention.py) — the
    # kernelcheck tiling constraints are the single source of truth
    from paddle_tpu.analysis.kernelcheck import validate_flash_tuned

    errors = validate_flash_tuned(table)
    if errors:
        raise ValueError(
            "flash_autotune produced entries violating the kernel tiling "
            "constraints (refusing to write flash_tuned.json):\n  "
            + "\n  ".join(errors))
    out_path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "paddle_tpu", "kernels", "flash_tuned.json")
    with open(out_path, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    print(f"[flash_autotune] wrote {os.path.abspath(out_path)}: {table}",
          file=sys.stderr)
    print(json.dumps({"tuned": table, "records": records}))


if __name__ == "__main__":
    main()
