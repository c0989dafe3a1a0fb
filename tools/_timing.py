"""Honest on-chip micro-benchmark timer, shared by the kernel sweep tools.

Three failure modes this helper exists to defeat:

1. Dispatch is asynchronous: a timing that does not wait for the result
   measures the enqueue. Close every timed rep with a scalar
   device->host fetch (a barrier that cannot return early).
2. Per-dispatch host overhead swamps sub-ms kernels. Amortize `inner`
   calls per fetch with a lax.scan.
3. With loop-invariant inputs XLA hoists the computation OUT of the scan
   (LICM) and the loop times (overhead + ONE exec)/inner. Thread the carry into
   the inputs via a numerically-negligible perturbation, and fold EVERY
   output into the carry — a gradient that doesn't feed the carry is DCE'd
   (the dense-flash backward is two pallas kernels; dropping dk/dv silently
   removes one of them from the measurement).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def time_fn(fn, args, iters: int = 5, inner: int = 40) -> float:
    """Median seconds per FORWARD call of ``fn(*args)`` — the inference-
    kernel twin of :func:`time_grad_fn`, same anti-LICM discipline:
    float args are carry-perturbed so the computation can't be hoisted
    out of the scan (int operands — page tables, ctx_lens — pass through;
    the call still depends on the perturbed floats), and every output
    leaf folds into the carry so nothing is DCE'd."""
    def many(*args):
        def body(acc, _):
            perturbed = [
                (a.astype(jnp.float32) * (1.0 + acc * 1e-30)).astype(a.dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a
                for a in args
            ]
            out = fn(*perturbed)
            live = sum(jnp.sum(x.astype(jnp.float32))
                       for x in jax.tree_util.tree_leaves(out))
            return acc + live * 1e-30, None

        acc, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=inner)
        return acc

    step = jax.jit(many)
    float(np.asarray(step(*args)))  # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        float(np.asarray(step(*args)))
        ts.append((time.perf_counter() - t0) / inner)
    return float(np.median(ts))


def time_grad_fn(loss_fn, args, iters: int = 5, inner: int = 40) -> float:
    """Median seconds per fwd+bwd of `loss_fn(*args)` (argnums = all args).

    loss_fn must return a scalar; args are arrays. Returns median over
    `iters` reps of `inner` amortized calls each.
    """
    n = len(args)

    def many(*args):
        def body(acc, _):
            perturbed = [
                (a.astype(jnp.float32) * (1.0 + acc * 1e-30)).astype(a.dtype)
                for a in args
            ]
            grads = jax.grad(loss_fn, argnums=tuple(range(n)))(*perturbed)
            live = sum(jnp.sum(g.astype(jnp.float32)) for g in grads)
            return acc + live * 1e-30, None

        acc, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=inner)
        return acc

    step = jax.jit(many)
    float(np.asarray(step(*args)))  # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        float(np.asarray(step(*args)))
        ts.append((time.perf_counter() - t0) / inner)
    return float(np.median(ts))
