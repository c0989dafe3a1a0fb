"""On-chip launch-parameter autotune for the unified ragged
paged-attention kernel (kernels/ragged_paged_attention.py). For each
serving-relevant ``(page_size, num_heads, head_dim)``, times the
decode-mode kernel across the candidate grid of ``block_heads`` (heads
per grid step — grid parallelism vs per-step VMEM/DMA width) ×
``pipeline_chunk`` (pages staged per DMA chunk — chunk == pages_per_seq
is the exact single-buffer gather; a smaller chunk turns on the
double-buffered DMA/compute pipeline at ×2 staging VMEM) and writes the
winners to paddle_tpu/kernels/ragged_tuned.json — the single
``block_heads_for``/``pipeline_chunk_for`` source consults it, so the
dispatch gate and launch config stay consistent automatically (the
flash_autotune idiom).

Candidates are pre-filtered through the dispatch-side VMEM gate
(``_vmem_working_set`` INCLUDING the ×2 staged buffers a sub-row chunk
implies) before any is timed — a banked winner the gate then rejects
would silently route every call at that shape to the composite path,
the exact opposite of tuning.

The table is validated by ``analysis.kernelcheck.validate_ragged_tuned``
BEFORE writing — the same validator the kernel runs at load time (incl.
the stale-chunk rule: a pipeline_chunk must divide the pages_per_seq it
was tuned at), so load can never see an entry bank rejected.

The kernel stages a row's live chunks only, so its time depends on the
context lengths it is timed at: ``--ctx LO HI`` draws each row's
``ctx_lens`` uniformly from ``[LO, HI]`` (clamped to the shape's table),
and a winner is only worth banking when tuned on what serving runs. The
default is the whole table; ``--ctx 449 640`` is the benchmark's
``gpt3-1.3b-serve.batch-unshared`` (prompts of 449-512 tokens and up to
128 generated); ``--ctx 1023 1023`` a table-full row, the case in which
the bounded loop saves nothing.

TPU only (the compiled kernel; the CPU interpreter's timings are
meaningless): exits non-zero otherwise. The sweep records are printed
with the table.

Usage: python tools/ragged_autotune.py [--ctx LO HI]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import paddle_tpu  # noqa: F401 — honours JAX_PLATFORMS=cpu, sets the cache
import numpy as np

SHAPES = [  # (batch, num_heads, head_dim, page_size, pages_per_seq)
    (8, 8, 128, 16, 32),    # bench-model serving shape, 512-token window
    (4, 16, 128, 16, 64),   # gpt3-1.3b decode (1024-token window)
    (8, 8, 128, 32, 16),    # bigger pages, same window
]


def _candidates(num_heads: int, head_dim: int, page_size: int,
                pages_per_seq: int) -> list:
    """(block_heads, pipeline_chunk) pairs worth sweeping: block_heads
    must divide num_heads and be a head block the chip's compiler can
    DMA out of an fp32 pool (whole 8-sublane tiles, or every head), the
    chunk must divide pages_per_seq, and the pair must pass the
    dispatch-side VMEM eligibility gate — sized with the ×2 staged
    buffers a sub-row chunk implies — at the LARGEST query count a
    serving call makes (the 64-pad prefill bucket)."""
    from paddle_tpu.kernels.ragged_paged_attention import (
        _VMEM_GATE_BYTES, _vmem_working_set)

    total_kv = pages_per_seq * page_size
    chunks = [c for c in (2, 4, 8, 16, 32) if c < pages_per_seq
              and pages_per_seq % c == 0] + [pages_per_seq]
    return [(bh, c)
            for bh in sorted({bh for bh in (8, 16, num_heads)
                              if num_heads % bh == 0})
            for c in chunks
            if _vmem_working_set(head_dim, total_kv, 64, bh,
                                 pages_per_seq, False, pipeline_chunk=c)
            <= _VMEM_GATE_BYTES]


def _time_config(q, kp, vp, tab, ctx, block_heads, pipeline_chunk):
    import jax

    from _timing import time_fn
    from paddle_tpu.kernels import ragged_paged_attention as rp

    fn = jax.jit(lambda *a: rp.ragged_paged_attention(
        *a, block_heads=block_heads, pipeline_chunk=pipeline_chunk))
    return time_fn(fn, (q, kp, vp, tab, ctx), iters=5, inner=40)


def main():
    import jax

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--ctx", nargs=2, type=int, metavar=("LO", "HI"), default=None,
        help="draw each row's context length uniformly from [LO, HI] "
             "tokens, clamped to the shape's table (default: the whole "
             "table); e.g. --ctx 449 640, what the serving cell "
             "gpt3-1.3b-serve.batch-unshared sends")
    opts = parser.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("[ragged_autotune] needs a TPU: the compiled kernel is "
                 "what is tuned")
    from paddle_tpu.kernels import ragged_paged_attention as rp

    dev = jax.devices()[0]
    table = {}
    records = []
    for b, h, d, ps, pps in SHAPES:
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        npages = b * pps + 1
        q = jnp.asarray(rng.rand(b, h, 1, d), jnp.float32)
        kp = jnp.asarray(rng.rand(npages, ps, h, d), jnp.float32)
        vp = jnp.asarray(rng.rand(npages, ps, h, d), jnp.float32)
        tab = jnp.asarray(
            np.arange(1, 1 + b * pps, dtype=np.int32).reshape(b, pps))
        # the new token is in the pool already: ctx <= table - 1
        lo, hi = opts.ctx or (ps, ps * pps - 1)
        lo, hi = (min(max(v, 0), ps * pps - 1) for v in (lo, hi))
        ctx = jnp.asarray(rng.randint(lo, max(lo, hi) + 1, (b,)),
                          jnp.int32)
        results = {}
        for bh, chunk in _candidates(h, d, ps, pps):
            try:
                results[(bh, chunk)] = _time_config(q, kp, vp, tab, ctx,
                                                    bh, chunk)
                print(f"[ragged_autotune] ps={ps} h={h} d={d} "
                      f"block_heads={bh} chunk={chunk}: "
                      f"{results[(bh, chunk)] * 1e3:.3f} ms",
                      file=sys.stderr, flush=True)
            except Exception as e:  # noqa: BLE001 — OOM/unsupported config
                print(f"[ragged_autotune] ps={ps} h={h} d={d} "
                      f"block_heads={bh} chunk={chunk}: "
                      f"{type(e).__name__}",
                      file=sys.stderr, flush=True)
        if not results:
            continue
        best_bh, best_chunk = min(results, key=results.get)
        # against the untuned defaults
        default_bh = rp.block_heads_for(ps, h, d)
        default_t = results.get(
            (default_bh, rp.pipeline_chunk_for(ps, h, d, pps,
                                               block_heads=default_bh)))
        table[f"{ps},{h},{d}"] = {
            "block_heads": best_bh,
            "pipeline_chunk": best_chunk,
            # the chunk's divisibility anchor: validate_ragged_tuned
            # rejects the entry as STALE if a future sweep/model changes
            # the window so the chunk no longer divides the page count
            "pages_per_seq": pps,
        }
        best_t = results[(best_bh, best_chunk)]
        records.append({
            "metric": "ragged_paged_decode_ms",
            "value": round(best_t * 1e3, 4),
            "unit": "ms",
            "vs_baseline": round(default_t / best_t, 3)
            if default_t else None,
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", "?"),
            "config": {"batch": b, "heads": h, "head_dim": d,
                       "page_size": ps, "pages_per_seq": pps,
                       "ctx_range": [lo, hi],
                       "best_block_heads": best_bh,
                       "best_pipeline_chunk": best_chunk,
                       "sweep_ms": {f"{kk[0]},{kk[1]}": round(vv * 1e3, 4)
                                    for kk, vv in results.items()}},
            "provenance": "rung-experiment (ragged_autotune)",
        })

    # validate BEFORE writing: a bad entry would otherwise be rejected at
    # every future load (kernels/ragged_paged_attention.py) — the
    # kernelcheck constraints are the single source of truth
    from paddle_tpu.analysis.kernelcheck import validate_ragged_tuned

    errors = validate_ragged_tuned(table)
    if errors:
        raise ValueError(
            "ragged_autotune produced entries violating the kernel "
            "constraints (refusing to write ragged_tuned.json):\n  "
            + "\n  ".join(errors))
    out_path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "paddle_tpu", "kernels", "ragged_tuned.json")
    with open(out_path, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    print(f"[ragged_autotune] wrote {os.path.abspath(out_path)}: {table}",
          file=sys.stderr)
    print(json.dumps({"tuned": table, "records": records}))


if __name__ == "__main__":
    main()
