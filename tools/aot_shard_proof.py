#!/usr/bin/env python
"""AOT shard proof: compile the FULL hybrid-parallel training step for the
north-star GPT configs (1.3B, 6.7B) on virtual v5p meshes and account
per-device HBM — no chip and no weight materialization needed.

The model is built under paddle.LazyGuard (meta params), the step comes from
the production `fleet.hybrid_train.build_hybrid_step(..., with_aux=True)`
builder, and `jax.jit(...).lower(abstract_state).compile()` yields XLA's own
per-device buffer assignment (`memory_analysis()`) and FLOP count
(`cost_analysis()`). This converts "a toy GPT passes the dryrun" into "the
target model shards, compiles, and fits HBM" (VERDICT r4 missing #2).

Reference analog: the full-size GPT fixture used by the reference's
auto-parallel tests (python/paddle/fluid/tests/unittests/
auto_parallel_gpt_model.py:1) and the memory estimates of
python/paddle/distributed/auto_parallel/cost_model.py.

Usage:
  python tools/aot_shard_proof.py                 # all configs (subprocesses)
  python tools/aot_shard_proof.py --config NAME   # one config
  python tools/aot_shard_proof.py --impl NAME     # (internal) in-process run
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# v5p: 95 GB HBM per chip (TPU v5p spec).
HBM_BYTES = 95_000_000_000

CONFIGS = {
    # BASELINE.json configs[3]: GPT-3 1.3B Fleet data-parallel + ZeRO-1 on
    # one v5p-8 host: batch sharded over all 8 chips, opt slots ZeRO-sharded.
    "1.3b-v5p8-dp-zero1": dict(
        preset="gpt3-1.3b", n_dev=8, axes=(("dp", 4), ("sharding", 2)),
        zero=1, megatron=False, seq=1024, gbs=64, remat=False),
    # north-star on ONE v5p-8 host: 6.7B with mp=4 + ZeRO-3 over the
    # remaining axis, full-block rematerialization.
    "6.7b-v5p8-mp4-zero3-remat": dict(
        preset="gpt3-6.7b", n_dev=8, axes=(("sharding", 2), ("mp", 4)),
        zero=3, megatron=True, seq=2048, gbs=16, remat=True),
    # BASELINE.json north_star: 6.7B hybrid on v5p-64 — dp2 x zero4 x mp8.
    "6.7b-v5p64-dp2-zero4-mp8-remat": dict(
        preset="gpt3-6.7b", n_dev=64, axes=(("dp", 2), ("sharding", 4), ("mp", 8)),
        zero=3, megatron=True, seq=2048, gbs=64, remat=True),
}


def _tree_bytes_per_device(tree):
    """Sum per-device shard bytes over a pytree of sharded ShapeDtypeStructs."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = tuple(leaf.shape)
        sh = getattr(leaf, "sharding", None)
        shard = sh.shard_shape(shape) if sh is not None else shape
        total += int(np.prod(shard, dtype=np.int64)) * leaf.dtype.itemsize
    return int(total)


def impl(name: str) -> dict:
    cfg = CONFIGS[name]
    n_dev = cfg["n_dev"]
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n_dev}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.hybrid_train import (
        _batch_spec, build_hybrid_step)
    from paddle_tpu.distributed.fleet.meta_parallel import apply_megatron_specs
    from paddle_tpu.text.gpt import GPTConfig, _PRESETS

    axis_names = tuple(a for a, _ in cfg["axes"])
    axis_sizes = tuple(s for _, s in cfg["axes"])
    assert int(np.prod(axis_sizes)) == n_dev
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(axis_sizes), axis_names)

    gcfg = GPTConfig(max_seq_len=cfg["seq"],
                     recompute=cfg["remat"], dropout=0.0,
                     **_PRESETS[cfg["preset"]])
    t0 = time.time()
    with paddle.LazyGuard():
        from paddle_tpu.text.gpt import GPTForCausalLM

        model = GPTForCausalLM(gcfg)
    n_params = model.num_params()
    if cfg["megatron"]:
        n_tagged = apply_megatron_specs(model)
        assert n_tagged > 0
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    init_fn, step, _shard_batch, aux = build_hybrid_step(
        model, opt, lambda out: out, mesh, zero_stage=cfg["zero"],
        with_aux=True)
    state_struct = aux["abstract_state"]()

    from jax.sharding import NamedSharding

    bspec = _batch_spec(2, mesh)
    bsh = NamedSharding(mesh, bspec)
    ids = jax.ShapeDtypeStruct((cfg["gbs"], cfg["seq"]), np.int32, sharding=bsh)
    labels = jax.ShapeDtypeStruct((cfg["gbs"], cfg["seq"]), np.int32, sharding=bsh)
    key = jax.eval_shape(lambda: jax.random.key(0))

    t1 = time.time()
    lowered = step.lower(state_struct, key, 1e-4, (ids, labels), ())
    t2 = time.time()
    compiled = lowered.compile()
    t3 = time.time()

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}

    per_dev = {
        "params": _tree_bytes_per_device(state_struct["p"]),
        "frozen": _tree_bytes_per_device(state_struct["frozen"]),
        "buffers": _tree_bytes_per_device(state_struct["b"]),
        "opt_state": _tree_bytes_per_device(state_struct["opt"]),
        "batch": _tree_bytes_per_device([ids, labels]),
    }
    per_dev["arguments_xla"] = int(ma.argument_size_in_bytes)
    per_dev["temp_xla"] = int(ma.temp_size_in_bytes)  # activations/grads/workspace
    per_dev["output_xla"] = int(ma.output_size_in_bytes)
    # Resident set while the step runs = live arguments + XLA's temp arena +
    # outputs (donation aliases state-out onto state-in, so outputs beyond
    # the loss are already counted inside arguments). The CPU backend's
    # peak_memory_in_bytes leaves out the temp arena, so compute it ourselves
    # and keep XLA's number for reference.
    peak = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    per_dev["peak_xla_reported"] = int(
        getattr(ma, "peak_memory_in_bytes", 0))
    per_dev["peak"] = peak

    # --- remat-adjusted activation estimate -------------------------------
    # XLA:CPU's buffer assignment does not realize jax.checkpoint's memory
    # savings (verified: identical temp arena with/without remat even on a
    # clean probe), so temp_xla is a NO-REMAT upper bound. For rematted
    # configs, estimate the true TPU-side activation footprint from two
    # additional full-width compiles at L=1 and L=2:
    #   per_layer  = temp(L=2) - temp(L=1)      (one block's saved set)
    #   base       = temp(L=1) - per_layer      (embed/head/step overhead)
    #   remat_temp = base + L*block_input + 2*per_layer
    # (stash of every block input + one block recomputed + its bwd live).
    remat_est = None
    if cfg["remat"]:
        temps = {}
        for nl in (1, 2):
            sub = GPTConfig(max_seq_len=cfg["seq"], recompute=False,
                            dropout=0.0, **{**_PRESETS[cfg["preset"]],
                                            "num_layers": nl})
            with paddle.LazyGuard():
                from paddle_tpu.text.gpt import GPTForCausalLM

                sm = GPTForCausalLM(sub)
            if cfg["megatron"]:
                apply_megatron_specs(sm)
            sopt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                          parameters=sm.parameters())
            _, sstep, _, saux = build_hybrid_step(
                sm, sopt, lambda out: out, mesh, zero_stage=cfg["zero"],
                with_aux=True)
            scomp = sstep.lower(saux["abstract_state"](), key, 1e-4,
                                (ids, labels), ()).compile()
            temps[nl] = int(scomp.memory_analysis().temp_size_in_bytes)
        per_layer = max(0, temps[2] - temps[1])
        base = max(0, temps[1] - per_layer)
        rows = ids.sharding.shard_shape(ids.shape)[0]
        block_input = rows * cfg["seq"] * gcfg.hidden_size * 4  # fp32
        n_layers = gcfg.num_layers
        remat_temp = base + n_layers * block_input + 2 * per_layer
        remat_peak = int(ma.argument_size_in_bytes + remat_temp
                         + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        remat_est = {
            "temp_L1": temps[1], "temp_L2": temps[2],
            "per_layer_bytes": per_layer, "base_bytes": base,
            "block_input_stash_bytes": n_layers * block_input,
            "remat_temp_bytes": int(remat_temp),
            "remat_peak_bytes": remat_peak,
            "remat_peak_gb": round(remat_peak / 1e9, 3),
            "fits_hbm": bool(remat_peak <= HBM_BYTES),
        }

    flops = ca.get("flops", 0.0)
    result = {
        "config": name,
        "model": cfg["preset"],
        "n_params": int(n_params),
        "mesh": {a: int(s) for a, s in cfg["axes"]},
        "zero_stage": cfg["zero"],
        "seq": cfg["seq"], "global_batch": cfg["gbs"],
        "remat": cfg["remat"],
        "per_device_bytes": per_dev,
        "per_device_gb": {k: round(v / 1e9, 3) for k, v in per_dev.items()},
        "flops_per_device_step": float(flops),
        "hbm_budget_bytes": HBM_BYTES,
        "fits_hbm": bool(peak <= HBM_BYTES),
        "remat_estimate": remat_est,
        "build_s": round(t1 - t0, 1),
        "lower_s": round(t2 - t1, 1),
        "compile_s": round(t3 - t2, 1),
    }
    return result


def run_one(name: str, timeout: int = 3600) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={CONFIGS[name]['n_dev']}")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--impl", name],
        env=env, timeout=timeout, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    out = proc.stdout.decode(errors="replace")
    if proc.returncode != 0:
        raise RuntimeError(f"{name} failed rc={proc.returncode}\n{out[-4000:]}")
    line = [l for l in out.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None, help="run one config")
    ap.add_argument("--impl", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--out", default=os.path.join(REPO, "AOT_SHARD_PROOF.json"))
    args = ap.parse_args()

    if args.impl:
        res = impl(args.impl)
        print(json.dumps(res))
        return

    names = [args.config] if args.config else list(CONFIGS)
    results = []
    for name in names:
        print(f"[aot_shard_proof] {name} ...", flush=True)
        res = run_one(name)
        gb = res["per_device_gb"]
        print(f"[aot_shard_proof] {name}: params/dev {gb['params']} GB, "
              f"opt {gb['opt_state']} GB, temp {gb['temp_xla']} GB, "
              f"peak {gb['peak']} GB "
              f"({'FITS' if res['fits_hbm'] else 'DOES NOT FIT'} v5p 95 GB, "
              f"no-remat-credit bound), compile {res['compile_s']}s",
              flush=True)
        re_ = res.get("remat_estimate")
        if re_:
            print(f"[aot_shard_proof]   remat-adjusted peak "
                  f"{re_['remat_peak_gb']} GB "
                  f"({'FITS' if re_['fits_hbm'] else 'DOES NOT FIT'})",
                  flush=True)
        results.append(res)
    if not args.config:
        with open(args.out, "w") as f:
            json.dump({"hbm_budget_bytes": HBM_BYTES, "results": results}, f,
                      indent=1)
        print(f"[aot_shard_proof] wrote {args.out}")


if __name__ == "__main__":
    main()
