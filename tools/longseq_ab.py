"""Long-context attention A/B: splash vs dense-block flash vs composite.

Splash ≈ dense flash at seq 1024 (attention ~12% of FLOPs); the
crossover where causal tile-skipping pays sits at longer context. This
harness measures it on the chip:

    python tools/longseq_ab.py              # seqs 1024 2048 4096 8192

Prints one JSON line per seq with the median fwd+bwd SECONDS of each
attention kernel (attention-only microbench — isolates the kernels from
the model; for model-level context run `bench.py --rung` with a seq in the
rung dict afterwards, where attention's FLOP share grows with seq). On CPU
it refuses: these numbers are only meaningful on-chip.
"""
from __future__ import annotations

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _bench_attn(kernel, q, k, v, iters=5, inner=40):
    """Full fwd+bwd timing via the shared hoisting/DCE-proof timer
    (tools/_timing.py — all three grads live, host-fetch barrier)."""
    from _timing import time_grad_fn

    def loss(q, k, v):
        return jnp.sum(kernel(q, k, v).astype(jnp.float32))

    return time_grad_fn(loss, (q, k, v), iters=iters, inner=inner)


def main():
    if jax.devices()[0].platform == "cpu":
        print("refusing: long-seq kernel A/B is only meaningful on-chip "
              "(pallas lowering + ICI/HBM characteristics)", file=sys.stderr)
        sys.exit(1)

    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels.attention import sdpa_reference

    rng = np.random.RandomState(0)
    b, h, d = 2, 16, 64
    for seq in (1024, 2048, 4096, 8192):
        q = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
        sm = 1.0 / d**0.5
        rows = {}
        kernels = {
            "flash_dense": functools.partial(fa._flash, causal=True,
                                             sm_scale=sm),
            "splash": functools.partial(fa._splash, sm_scale=sm),
        }
        if seq <= 2048:  # composite materializes S^2 logits: OOM above
            kernels["composite"] = functools.partial(
                sdpa_reference, is_causal=True)
        for name, kern in kernels.items():
            try:
                dt = _bench_attn(lambda q, k, v, _k=kern: _k(q, k, v), q, k, v)
                rows[name] = dt
            except Exception as e:  # noqa: BLE001
                rows[name] = f"FAILED: {type(e).__name__}: {str(e)[:120]}"
        out = {"seq": seq, "batch": b, "heads": h, "head_dim": d,
               "median_fwd_bwd_s": rows}
        if all(isinstance(x, float) for x in rows.values()) and \
                "splash" in rows and "flash_dense" in rows:
            out["splash_speedup_vs_dense"] = round(
                rows["flash_dense"] / rows["splash"], 3)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
