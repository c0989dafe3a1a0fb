"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two hot paths once, through the entry points a user calls, at
the full width of a model the repo supports (random weights from ``--seed``):

- ``train``: gpt3-350m (hidden 1024, 24 layers, 16 heads, vocab 50304) at
  batch 8 x seq 1024, bf16 params + fp32-master AdamW + fused chunked
  head+CE (the precision policy of ``bench.build_train_step``), built by
  ``build_hybrid_step`` on a one-device mesh. Three optimizer steps on one
  fixed batch: the loss is finite and falls, the compiled step holds the
  flash kernel's ``tpu_custom_call``, and state and outputs live on the TPU.
- ``serve``: gpt3-1.3b (hidden 2048, 24 layers, 16 heads x 128, vocab
  50304, context 1024) through ``ServingEngine`` with prefix caching on
  and the default fp32 KV pool. Six requests — prompts of about 16, 128
  and 500 tokens, two sharing a 128-token prefix, 32 new tokens each —
  added while others decode. All retire FINISHED, two are compared with
  single-request ``text.generation.generate`` on the chip, the engine
  traced one program per prefill bucket plus one decode, and those
  programs hold the ragged paged-attention kernel's ``tpu_custom_call``.

``--chips 4`` (run by hand on a four-chip host; the driver runs one chip)
runs only the cross-chip paths and what each is compared with: the serving
engine at ``tensor_parallel=4`` against ``tensor_parallel=1`` on the same
requests, and the train step on a dp2 x mp2 mesh against its one-chip loss.

One process, which imports JAX itself and starts no other. It fails at once
unless ``jax.devices()[0].platform == "tpu"``; any failed check raises, so
the exit code is non-zero and the last line is not printed. No rate, MFU or
utilization is printed: those belong to the benchmark. The last line of
standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

TRAIN_MODEL, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = \
    "gpt3-350m", 8, 1024, 3, 1e-4
SERVE_MODEL, SERVE_CONTEXT, SERVE_NEW_TOKENS = "gpt3-1.3b", 1024, 32
SERVE_MAX_BATCH, SERVE_MAX_PROMPT, SERVE_PAGE = 8, 512, 16
#: the prefill pad buckets ``make_requests`` lands in — these compile, and
#: no other
SERVE_BUCKETS = (16, 128, 512)
#: one trace per bucket used, one decode program
EXPECTED_TRACES = {"prefill": len(SERVE_BUCKETS), "decode": 1}
#: share of the device's memory limit the KV pool may take: the fp32
#: weights of gpt3-1.3b are 5.3 GB of a v5e's 16 GB, a prefill-512 program
#: needs about 1 GB of temporaries (the [1, 512, 50304] fp32 logits alone
#: are 103 MB), so a third of the limit leaves both room
SERVE_POOL_SHARE = 1 / 3
#: every token the engine emits must be the reference's best or a near
#: tie with it: the reference's own logit for that token lies within this
#: of the reference's maximum. fp32 matmuls run as bf16 passes on the MXU
#: and the engine's kernel folds the softmax in chunks, so logits (std
#: ~0.9 at this width with random weights) agree to ~1e-2, not bit for
#: bit; gaps of 0.005-0.01 were seen on a v5e
LOGIT_TIE_TOL = 0.1
#: dp2 x mp2 vs one chip, loss at step 1 (both bf16 forward passes of the
#: same weights; only the reduction order across shards differs)
TRAIN_MESH_LOSS_TOL = 0.05


def check(ok, what) -> None:
    """A failed check ends the run (a raise, not an ``assert``: python -O
    must not turn the smoke into a no-op)."""
    if not ok:
        raise AssertionError(what)


def say(phase: str, **fields) -> None:
    print(f"[chip_smoke] {phase}: " + json.dumps(fields, sort_keys=True),
          flush=True)


#: where every array of a phase must live
PLATFORM = "tpu"
#: the weight whose placement is printed (column-split under mp / tp)
QKV_WEIGHT = "gpt.blocks.0.attn.qkv_proj.weight"


def memory_stat(dev, key: str) -> int:
    return int(dev.memory_stats()[key])


def peak_bytes(dev) -> int:
    return memory_stat(dev, "peak_bytes_in_use")


def kernel_calls(compiled_text: str, label: str, at_least: int) -> int:
    """How many Pallas kernels the compiled program holds; fewer than
    ``at_least`` means a path ran without its kernel."""
    n = compiled_text.count("tpu_custom_call")
    check(n >= at_least,
          f"{label}: {n} tpu_custom_call in the compiled program, expected "
          f"at least {at_least} — the kernel is not on this path")
    return n


def on_platform(arr) -> bool:
    return all(d.platform == PLATFORM for d in arr.devices())


def release() -> None:
    """Drop everything the last phase left on the devices."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------------------ train
def build_train(mesh, seed: int):
    """The train step on ``mesh``: (init_fn, step, aux, shard_batch,
    host batch). With an ``mp`` axis the weights carry their Megatron
    specs."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.hybrid_train import build_hybrid_step
    from paddle_tpu.distributed.fleet.meta_parallel import \
        apply_megatron_specs
    from paddle_tpu.text.gpt import GPTForCausalLM, gpt_config

    cfg = gpt_config(TRAIN_MODEL, max_seq_len=TRAIN_SEQ, dropout=0.0,
                     loss_chunk_size=2048)
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    if "mp" in mesh.axis_names:
        check(apply_megatron_specs(model) > 0, "no weight took an mp spec")
    opt = paddle.optimizer.AdamW(learning_rate=TRAIN_LR,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    # forward(input_ids, labels) returns the fused head+CE loss itself
    init_fn, step, shard_batch, aux = build_hybrid_step(
        model, opt, lambda loss: loss, mesh, with_aux=True)
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (TRAIN_BATCH, TRAIN_SEQ))
    labels = rng.randint(0, cfg.vocab_size, (TRAIN_BATCH, TRAIN_SEQ))
    return init_fn, step, aux, shard_batch, \
        [ids.astype(np.int32), labels.astype(np.int32)]


def run_train(mesh, seed: int, name: str) -> list[float]:
    import jax

    from paddle_tpu import _compile_cache

    dev = mesh.devices.flat[0]
    t0 = time.perf_counter()
    init_fn, step, aux, shard_batch, host_batch = build_train(mesh, seed)
    state = init_fn()
    batch = tuple(shard_batch(host_batch))
    key = jax.random.key(seed)
    # fp32, not a Python float: the package turns x64 on, and a float
    # argument would enter the program as an f64 scalar
    lr = np.float32(TRAIN_LR)
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = step.lower(state, key, lr, batch, ()).compile()
    compile_s = time.perf_counter() - t0
    # the flash forward and the one fused backward kernel per layer: at
    # least the two
    n_kernels = kernel_calls(compiled.as_text(), name, 2)

    p0 = state["p"][QKV_WEIGHT]
    check(p0.devices() == set(mesh.devices.flat) and on_platform(p0),
          p0.devices())
    t0 = time.perf_counter()
    losses = []
    for _ in range(TRAIN_STEPS):
        loss, state = compiled(state, key, lr, batch, ())
        check(on_platform(loss), loss.devices())
        losses.append(float(np.asarray(loss)))
    run_s = time.perf_counter() - t0
    p0 = state["p"][QKV_WEIGHT]
    check(on_platform(p0), p0.devices())
    check(all(np.isfinite(losses)), losses)
    check(losses[-1] < losses[0], f"{name}: loss did not fall: {losses}")
    say(name, model=TRAIN_MODEL, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
        mesh=dict(zip(mesh.axis_names, mesh.devices.shape)),
        losses=losses, build_s=round(build_s, 1),
        compile_s=round(compile_s, 1), run_s=round(run_s, 2),
        cache_dir=_compile_cache.cache_dir(), attention_path="flash",
        flash_custom_calls=n_kernels,
        qkv_weight_shard_shape=list(p0.sharding.shard_shape(p0.shape)),
        peak_bytes_per_device=[peak_bytes(d) for d in mesh.devices.flat],
        device=str(dev))
    del state, compiled, step, init_fn, aux, shard_batch, batch
    release()
    return losses


def phase_train(seed: int) -> None:
    import jax
    from jax.sharding import Mesh

    run_train(Mesh(np.array(jax.devices()[:1]), ("dp",)), seed, "train")


def phase_train_mesh(seed: int) -> None:
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    one = run_train(Mesh(np.array(devs[:1]), ("dp",)), seed, "train[1 chip]")
    four = run_train(Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "mp")),
                     seed, "train[dp2 x mp2]")
    diff = abs(four[0] - one[0])
    say("train[dp2 x mp2 vs 1 chip]", loss_step1_1chip=one[0],
        loss_step1_dp2mp2=four[0], abs_diff=diff, tol=TRAIN_MESH_LOSS_TOL)
    check(diff <= TRAIN_MESH_LOSS_TOL, (one, four))


# ------------------------------------------------------------------ serve
def make_requests(seed: int, vocab: int) -> list[tuple[str, np.ndarray]]:
    """Six prompts that land in exactly three prefill buckets (16, 128,
    512): the second and the fourth share their first 128 tokens — eight
    full pages — so the fourth prefills only its 13-token tail."""
    rng = np.random.RandomState(seed)

    def toks(n):
        return rng.randint(1, vocab, n).astype(np.int32)

    shared = toks(128)
    return [("short-16", toks(16)),
            ("shared-128", shared),
            ("long-500", toks(500)),
            ("shared-128+13", np.concatenate([shared, toks(13)])),
            ("short-14", toks(14)),
            ("long-490", toks(490))]


def build_serve_model(seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.text.gpt import GPTForCausalLM, gpt_config

    paddle.seed(seed)
    model = GPTForCausalLM(gpt_config(
        SERVE_MODEL, max_seq_len=SERVE_CONTEXT, dropout=0.0))
    model.eval()
    return model


def serve_config(model, dev, tensor_parallel: int = 1):
    """A pool sized for the chip: ``SERVE_POOL_SHARE`` of what the device
    (or, sharded, the devices together) may allocate."""
    from paddle_tpu.serving import ServingConfig

    c = model.cfg
    page_bytes = 2 * c.num_layers * SERVE_PAGE * c.hidden_size * 4
    limit = memory_stat(dev, "bytes_limit") * tensor_parallel
    pages = int(limit * SERVE_POOL_SHARE) // page_bytes
    cfg = ServingConfig(max_batch=SERVE_MAX_BATCH, num_pages=pages,
                        page_size=SERVE_PAGE,
                        max_prompt_len=SERVE_MAX_PROMPT,
                        tensor_parallel=tensor_parallel)
    return cfg, dict(pool_pages=pages, page_bytes=page_bytes,
                     pool_bytes=pages * page_bytes,
                     pool_tokens=pages * SERVE_PAGE,
                     why=f"{SERVE_POOL_SHARE:.2f} of the "
                         f"{limit} B the device(s) may allocate")


def run_engine(engine, requests):
    """Add three requests, decode a few steps, add the other three while
    those decode, drain. Steps in which a program was traced are counted
    as compile time. Returns ({name: generated tokens}, facts)."""
    from paddle_tpu.serving.scheduler import FINISHED

    rids, compile_s, run_s, steps = {}, 0.0, 0.0, 0

    def step():
        nonlocal compile_s, run_s, steps
        before = sum(engine.compile_counts.values())
        t0 = time.perf_counter()
        engine.step()
        dt = time.perf_counter() - t0
        steps += 1
        if sum(engine.compile_counts.values()) > before:
            compile_s += dt
        else:
            run_s += dt

    for name, prompt in requests[:3]:
        rids[name] = engine.add_request(prompt, SERVE_NEW_TOKENS)
    for _ in range(4):
        step()
    decoding = len(engine.scheduler.running)
    for name, prompt in requests[3:]:
        rids[name] = engine.add_request(prompt, SERVE_NEW_TOKENS)
    while not engine.scheduler.all_done:
        step()
        check(steps < 2000, "engine did not drain")
    states = {name: engine.status(rid) for name, rid in rids.items()}
    check(all(s == FINISHED for s in states.values()), states)
    out = {name: np.asarray(engine.result(rid))[len(prompt):]
           for (name, prompt), rid in zip(requests, rids.values())}
    check(all(len(t) == SERVE_NEW_TOKENS for t in out.values()), out)
    return out, dict(states=states, steps=steps,
                     decoding_when_second_wave_arrived=decoding,
                     compile_s=round(compile_s, 1), run_s=round(run_s, 2),
                     compile_counts=dict(engine.compile_counts))


def compare_with_generate(model, name, prompt, got) -> dict:
    """One request against the plain reference on the chip, two ways.

    Token for token against single-request ``generate`` (the first
    divergence is reported, not failed: fp32 matmuls run as bf16 passes
    on the MXU, so the CPU's bit-identity contract between the two paths
    need not survive here). And every token the engine emitted against
    the model's own dense forward over the same sequence: the engine's
    token is the reference's best, or within ``LOGIT_TIE_TOL`` of it —
    a near tie the two paths may split, never a wrong token."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.generation import generate

    ref = np.asarray(generate(model, Tensor(prompt[None, :]),
                              max_new_tokens=SERVE_NEW_TOKENS)._value)
    ref = ref[0, len(prompt):]
    same = int(np.argmin(ref == got)) if (ref != got).any() \
        else SERVE_NEW_TOKENS
    fact = dict(request=name, tokens_equal_to_generate=same,
                of=SERVE_NEW_TOKENS)
    if same < SERVE_NEW_TOKENS:
        fact |= dict(first_divergence_at=same, engine_token=int(got[same]),
                     generate_token=int(ref[same]))
    return fact | reference_logit_gap(model, prompt, got)


def reference_logit_gap(model, prompt, got) -> dict:
    """The model's dense forward (no cache, no kernel, no batching) over
    prompt + emitted tokens, its matmuls in true fp32."""
    import jax

    from paddle_tpu.core.tensor import Tensor

    full = np.concatenate([prompt, got])[None, :-1]
    with jax.default_matmul_precision("highest"):
        logits = model(Tensor(full))._value[0, len(prompt) - 1:]
    logits = np.asarray(logits, np.float32)               # [new, vocab]
    gaps = logits.max(-1) - logits[np.arange(len(got)), got]
    fact = dict(max_reference_logit_gap=float(gaps.max()),
                tokens_not_reference_argmax=int((gaps > 0).sum()),
                tol=LOGIT_TIE_TOL)
    check(gaps.max() <= LOGIT_TIE_TOL, fact)
    return fact


def program_custom_calls(engine) -> dict:
    """``tpu_custom_call`` count in the compiled decode program and in
    each prefill bucket the requests used — the engine's own step
    functions lowered at the engine's own shapes."""
    import jax
    import jax.numpy as jnp

    cfg = engine.config
    pps = engine.cache.page_table.shape[1]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    pools = engine.cache.pools
    b = cfg.max_batch
    programs = {"decode": (engine.guards["decode"],
                           (i32(b, pps), i32(b), i32(b), i32(b),
                            jax.ShapeDtypeStruct((b,), jnp.bool_),
                            i32(b), i32(b)))}
    for bucket in SERVE_BUCKETS:
        # ids, their count, tokens resident, the page row, rid, slot, and
        # the last launch's tokens (engine._prefill_args)
        programs[f"prefill[{bucket}]"] = (
            engine.guards["prefill"],
            (i32(bucket), i32(), i32(), i32(pps), i32(), i32(),
             i32(*engine._prev_toks.shape)))
    counts = {}
    for label, (guard, rest) in programs.items():
        text = jax.jit(guard.fn, donate_argnums=guard.donate_argnums).lower(
            engine._p, pools, *rest).compile().as_text()
        counts[label] = kernel_calls(text, label, 1)
    return counts


def attention_path(model) -> str:
    from paddle_tpu.kernels import paged_attention as pa

    c = model.cfg
    ok, why = pa.decode_kernel_eligible(
        c.hidden_size // c.num_heads, SERVE_CONTEXT // SERVE_PAGE,
        SERVE_PAGE, num_heads=c.num_heads)
    check(ok, f"ragged kernel gated off: {why}")
    return "ragged_paged_attention"


def phase_serve(seed: int) -> None:
    import jax

    from paddle_tpu import _compile_cache
    from paddle_tpu.serving import ServingEngine

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    model = build_serve_model(seed)
    cfg, pool = serve_config(model, dev)
    say("serve", pool=pool)
    engine = ServingEngine(model, cfg)
    build_s = time.perf_counter() - t0
    w = model.gpt.wte.weight._value
    k_pool = engine.cache.pools[0]["k_pool"]
    check(w.devices() == {dev} and k_pool.devices() == {dev},
          (w.devices(), k_pool.devices()))

    requests = make_requests(seed, model.cfg.vocab_size)
    out, facts = run_engine(engine, requests)
    check(facts["decoding_when_second_wave_arrived"] >= 1, facts)
    check(facts["compile_counts"] == EXPECTED_TRACES, facts)
    snap = engine.metrics.snapshot()
    hit = int(snap["serving_prefix_tokens_saved"])
    check(hit == 128,
          f"prefix cache served {hit} tokens, not the 128 shared")

    prompts = dict(requests)
    compared = [compare_with_generate(model, n, prompts[n], out[n])
                for n in ("short-16", "shared-128+13")]
    calls = program_custom_calls(engine)
    say("serve", model=SERVE_MODEL, context=SERVE_CONTEXT,
        requests={n: len(p) for n, p in requests},
        new_tokens=SERVE_NEW_TOKENS, prefix_hit_tokens=hit,
        compared_with_generate=compared, custom_calls=calls,
        attention_path=attention_path(model),
        build_s=round(build_s, 1), cache_dir=_compile_cache.cache_dir(),
        peak_bytes=peak_bytes(dev), device=str(dev), **facts)
    del engine, model
    release()


def phase_serve_tp(seed: int) -> None:
    """``tensor_parallel=4`` against ``tensor_parallel=1`` on the same
    requests and weights."""
    import jax

    from paddle_tpu.serving import ServingEngine

    devs = jax.devices()[:4]
    model = build_serve_model(seed)
    requests = make_requests(seed, model.cfg.vocab_size)
    out = {}
    for tp in (1, 4):
        cfg, pool = serve_config(model, devs[0], tensor_parallel=tp)
        before = [memory_stat(d, "bytes_in_use") for d in devs]
        engine = ServingEngine(model, cfg)
        out[tp], facts = run_engine(engine, requests)
        check(facts["compile_counts"] == EXPECTED_TRACES, facts)
        k_pool = engine.cache.pools[0]["k_pool"]
        w = engine._p[QKV_WEIGHT]
        placed = dict(
            pool_devices=len(k_pool.devices()),
            pool_shard_shape=list(k_pool.sharding.shard_shape(k_pool.shape)),
            qkv_weight_devices=len(w.devices()),
            qkv_weight_shard_shape=list(w.sharding.shard_shape(w.shape)),
            bytes_in_use_per_device=[
                memory_stat(d, "bytes_in_use") - b0
                for d, b0 in zip(devs, before)])
        if tp == 4:
            heads = model.cfg.num_heads
            check(placed["pool_devices"] == placed["qkv_weight_devices"]
                  == 4, placed)
            check(placed["pool_shard_shape"][2] == heads // 4, placed)
            check(placed["qkv_weight_shard_shape"][1]
                  == 3 * model.cfg.hidden_size // 4, placed)
            # every chip holds its share: no device carries the whole pool
            share = pool["pool_bytes"] // 4
            check(all(b >= share
                      for b in placed["bytes_in_use_per_device"]), placed)
        say(f"serve[tp={tp}]", pool=pool, placed=placed,
            peak_bytes_per_device=[peak_bytes(d) for d in devs], **facts)
        del engine, k_pool, w
        release()
    prompts = dict(requests)
    agree = {name: int(np.argmin(out[1][name] == out[4][name]))
             if (out[1][name] != out[4][name]).any() else SERVE_NEW_TOKENS
             for name in prompts}
    # wherever tp=4 split from tp=1 it must be a near tie by the model's
    # own dense forward — checked on the two requests the one-chip phase
    # compares with generate
    checked = {name: reference_logit_gap(model, prompts[name], out[4][name])
               for name in ("short-16", "shared-128+13")}
    say("serve[tp=4 vs tp=1]", tokens_equal=agree, of=SERVE_NEW_TOKENS,
        tp4_against_reference=checked)
    del model
    release()


# ------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip paths and what each is "
                         "compared with (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, the training batch and the requests")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        sys.exit(f"chip_smoke.py needs a TPU: jax.devices()[0].platform is "
                 f"{devs[0].platform!r}. Nothing was run.")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke.py --chips {args.chips} needs {args.chips} "
                 f"TPU devices; jax.devices() has {len(devs)}.")
    say("start", chips=args.chips, seed=args.seed,
        device_kind=devs[0].device_kind, devices=len(devs),
        jax=jax.__version__)

    if args.chips == 1:
        phase_train(args.seed)
        phase_serve(args.seed)
    else:
        # the one-chip train step is the tightest fit (15.2 of 15.75 GiB):
        # it goes first, on a device nothing has touched
        phase_train_mesh(args.seed)
        phase_serve_tp(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
