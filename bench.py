"""Benchmark: flagship GPT causal-LM training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
vs_baseline = measured MFU / 0.40.

A benchmark run is a run on the chip: one process, which imports JAX
itself, fails at once unless ``jax.devices()[0].platform == "tpu"`` and
lets every phase's error propagate. There is no CPU fallback, no
placeholder result and no stale number carried over from an earlier run
— a run that finds no chip, or whose phase fails, exits non-zero and
prints no result. (ROADMAP S1 replaces this phase chain with a table of
cells; this file keeps only what still runs.)

The chip child walks an OOM-adaptive config ladder (batch/layers/remat
policy) until one fits. Device capacity is strategy, not a constant
(reference spirit: ipu_strategy.h:32 — num_ipus/micro-batch are strategy).
"""
from __future__ import annotations

import functools
import gc
import json
import os
import sys
import time

import numpy as np

_TPU_BUDGET_S = int(os.environ.get("BENCH_TPU_BUDGET_S", "540"))

# bf16 peak FLOP/s per chip, keyed by a substring of ``device_kind``
# (Google Cloud TPU documentation, per-generation system architecture)
_PEAK_FLOPS = {
    "v5 lite": 197e12, "v5litepod": 197e12, "v5e": 197e12, "v5p": 459e12,
    "v4": 275e12, "v6 lite": 918e12, "v6e": 918e12, "v3": 123e12, "v2": 45e12,
}


def _peak_flops(device) -> float:
    """bf16 peak FLOP/s of this chip. An accelerator kind that is not in
    the table is an error, not a default."""
    kind = getattr(device, "device_kind", "").lower()
    for k, v in _PEAK_FLOPS.items():
        if k in kind:
            return v
    raise ValueError(
        f"no peak FLOP/s known for device kind {kind!r} (platform "
        f"{device.platform!r}) — add it to bench._PEAK_FLOPS with its "
        f"source; an MFU against a guessed peak is not a measurement")


def _is_oom(err: BaseException) -> bool:
    s = f"{type(err).__name__}: {err}"
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "exceeds the limit" in s
            or "Attempting to reserve" in s)


# Config ladder for the TPU child, tried top-down until one fits.
# Model: GPT-3 350M (hidden 1024 x 24 layers) like the fleet GPT fixture;
# 125M as the last-resort rung.
_RUNG_350M = dict(hidden=1024, layers=24, heads=16)
_RUNG_125M = dict(hidden=768, layers=12, heads=12)
# Ladder measured on-chip (TPU v5e, round 3): no-remat b8 beats dots-remat b8
# (35.5k vs 31.2k tok/s) and b16 in either policy; remat rungs remain as OOM
# fallbacks for smaller-HBM chips.
_BASE_RUNGS = [
    dict(tag="350M-b8-off", batch=8, policy="off", **_RUNG_350M),
    dict(tag="350M-b8-dots", batch=8, policy="dots", **_RUNG_350M),
    dict(tag="350M-b8-full", batch=8, policy=None, **_RUNG_350M),
    dict(tag="350M-b4-full", batch=4, policy=None, **_RUNG_350M),
    dict(tag="125M-b8-full", batch=8, policy=None, **_RUNG_125M),
]


def build_train_step(rung: dict):
    """The exact per-step computation the bench times — model + AMP-O2
    AdamW + fused chunked CE loss. Shared with tools/profile_bench.py so
    the profiled computation can never drift from the benched one.

    Returns dict(train_step, p_arrays, opt_state, cfg, n_params, model, opt).
    """
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.core import rng as rng_mod, tape as tape_mod
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    policy = rung["policy"]  # None=full remat, "dots"=save MXU outputs, "off"=no remat
    cfg = GPTConfig(vocab_size=rung.get("vocab", 50304), hidden_size=rung["hidden"],
                    num_layers=rung["layers"], num_heads=rung["heads"],
                    max_seq_len=rung.get("seq", 1024), dropout=0.0,
                    recompute=policy != "off", recompute_policy=None if policy == "off" else policy,
                    loss_chunk_size=int(os.environ.get("BENCH_LOSS_CHUNK", "2048")))

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    n_params = model.num_params()
    # bf16 params + fp32 master weights (AMP O2; MXU-native)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(), multi_precision=True
    )

    params, _ = model.functional_state()
    p_arrays = {k: v._value for k, v in params.items() if not v.stop_gradient}
    opt_state = opt.functional_init(p_arrays)

    def loss_fn(pvals, key, ids, labels):
        with tape_mod.no_grad(), rng_mod.trace_rng_scope(key):
            # forward w/ labels -> fused chunked head+CE: never materializes
            # the [b, s, vocab] fp32 logits (nn/functional.linear_cross_entropy)
            loss, _ = model.functional_call(
                pvals, {}, Tensor(ids), labels=Tensor(labels)
            )
        return loss._value

    def train_step(pvals, opt_st, key, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(pvals, key, ids, labels)
        new_p, new_st = opt.functional_update(pvals, grads, opt_st, 1e-4)
        return loss, new_p, new_st

    return dict(train_step=train_step, p_arrays=p_arrays, opt_state=opt_state,
                cfg=cfg, n_params=n_params, model=model, opt=opt)


def _measure(rung: dict, steps: int, warmup: int) -> dict:
    """Build the model per `rung`, run the timed loop, return the raw result."""
    import jax
    import jax.numpy as jnp

    built = build_train_step(rung)
    dev = jax.devices()[0]
    train_step, cfg, n_params = (built["train_step"], built["cfg"],
                                 built["n_params"])
    p_arrays, opt_state = built["p_arrays"], built["opt_state"]
    model, opt = built["model"], built["opt"]
    batch, seq = rung["batch"], rung.get("seq", 1024)

    # steps fused per dispatch: amortizes host->device dispatch latency
    INNER = int(os.environ.get("BENCH_INNER_STEPS", "16"))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_multi(pvals, opt_st, key, ids_all, labels_all):
        def body(carry, batch):
            p, st = carry
            ids, labels = batch
            loss, p, st = train_step(p, st, key, ids, labels)
            return (p, st), loss
        (pvals, opt_st), losses = jax.lax.scan(
            body, (pvals, opt_st), (ids_all, labels_all)
        )
        return losses[-1], pvals, opt_st

    rng = np.random.RandomState(0)
    ids_all = jnp.asarray(rng.randint(0, cfg.vocab_size, (INNER, batch, seq)), jnp.int32)
    labels_all = jnp.asarray(rng.randint(0, cfg.vocab_size, (INNER, batch, seq)), jnp.int32)

    key = jax.random.key(0)
    t_compile = time.perf_counter()
    for i in range(warmup):
        loss, p_arrays, opt_state = train_multi(p_arrays, opt_state, key, ids_all, labels_all)
        float(np.asarray(loss))  # full host round-trip: the step has finished
    print(f"[bench] {rung['tag']}: warmup+compile {time.perf_counter() - t_compile:.1f}s",
          file=sys.stderr, flush=True)

    times = []
    for i in range(steps):
        t0 = time.perf_counter()
        loss, p_arrays, opt_state = train_multi(p_arrays, opt_state, key, ids_all, labels_all)
        float(np.asarray(loss))
        times.append(time.perf_counter() - t0)
    dt = float(np.median(times)) / INNER

    tokens_per_sec = batch * seq / dt
    flops_per_token = 6.0 * n_params + 12.0 * cfg.num_layers * seq * cfg.hidden_size
    mfu = tokens_per_sec * flops_per_token / _peak_flops(dev)
    result = {
        "metric": f"gpt_{n_params/1e6:.0f}M_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "?"),
        "mfu": round(mfu, 4),
        "config": {"params_m": round(n_params / 1e6, 1), "batch": batch,
                   "seq": seq, "layers": cfg.num_layers,
                   "remat": rung["policy"] or "full", "tag": rung["tag"]},
    }
    # free donated/current buffers before any subsequent attempt
    del p_arrays, opt_state, model, opt, built, train_multi
    gc.collect()
    return result


def _serving_prefix_bench() -> dict:
    """Serving phase: a shared-system-prompt workload (every request = one
    48-token system prompt + a private 8-token tail) served with the
    automatic prefix cache on vs off. Reports decode throughput and the
    prefill tokens actually computed in each mode — the hit-vs-miss delta
    is the tokens the cache saved.

    A SyncTally around the measured run CERTIFIES the decode loop
    sync-free — exactly one device->host sync per step boundary (the token
    fetch), zero strays — and the CompileGuards confirm zero over-budget
    retraces; both totals are emitted as ``analysis_*`` keys in the JSON.
    The timing itself runs with ``debug_checks`` OFF (the per-step strict
    audit is a debugging mode, and its host overhead would pollute the
    cache-on/off comparison); the tally and the guards' retrace counters
    work either way.

    Observability phase (PR 5): the caching-on run reports its latency
    decomposition — ``serving_ttft_s_p50/p99``, ``serving_tpot_s_p50/
    p99``, ``serving_queue_wait_s_p99`` from the obs histograms — and
    writes its Perfetto-loadable Chrome trace to
    ``profiles/serving_trace.json``. A third run with tracing DISABLED
    pins the obs overhead delta (``serving_obs_tokens_per_sec_on/off``):
    tracing is on by default, so its cost must stay in the noise.

    hlocheck phase (PR 6): a short ``debug_checks=True`` run audits every
    compiled program (both prefill buckets + decode) at the artifact
    level and emits the roll-up — ``serving_hlo_collective_ops``,
    ``serving_hlo_peak_hbm_bytes``, ``serving_hlo_flops_per_step`` plus a
    per-program breakdown. Static compiled-artifact facts, but emitted
    (not ratio-asserted) per the CPU-box noise rule; the audited engine
    itself RAISES if a collective, host transfer, or un-honored donation
    ever appears in a compiled serving step."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis import SyncTally
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(17)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    system = rng.randint(0, 512, (48,))
    prompts = [np.concatenate([system, rng.randint(0, 512, (8,))])
               .astype(np.int32) for _ in range(12)]
    budget = 8

    def drive(enable, tracing=True):
        engine = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=64, page_size=16, max_prompt_len=64,
            enable_prefix_caching=enable, enable_tracing=tracing))
        # warm BOTH prefill shapes out of the timing: the cold prompt's
        # bucket, then (caching on) the hit tail's smaller bucket — the
        # second request must run AFTER the first finishes to hit its pages
        for p in prompts[:2]:
            engine.add_request(p, budget)
            engine.run()
        pre = engine.metrics.snapshot()
        t0 = time.perf_counter()
        for p in prompts[2:]:
            engine.add_request(p, budget)
        with SyncTally() as tally:
            engine.run()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        # sync-free certification: the ONLY host syncs in the measured
        # region are the per-step-boundary token fetches (one per decode
        # step + one per prefill's first-token fetch) — UNCHANGED with
        # request tracing enabled (trace events never touch the device)
        fetches = int(snap["serving_decode_steps"]
                      - pre["serving_decode_steps"]
                      + snap["serving_prefills_total"]
                      - pre["serving_prefills_total"])
        assert tally.count == fetches, (
            f"decode loop not sync-free: {tally.count} syncs vs {fetches} "
            f"sanctioned token fetches — events: {tally.events[:20]}")
        assert snap["serving_analysis_retraces_total"] == 0, \
            "compile budget violated in the serving bench"
        return (len(prompts) - 2) * budget / dt, snap, tally.count, engine

    tps_on, snap_on, syncs_on, engine_on = drive(True)
    tps_off, snap_off, _, _ = drive(False)
    tps_obs_off, _, _, _ = drive(True, tracing=False)

    # hlocheck: audited engine — per-compiled-program census + roll-up.
    # Isolated in its own try so an audit environment hiccup can never
    # forfeit the prefix/obs numbers above.
    hlo: dict = {}
    try:
        eng_dbg = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=64, page_size=16, max_prompt_len=64,
            debug_checks=True))
        for p in prompts[:2]:  # cold (bucket 64) then hit tail (bucket 8)
            eng_dbg.add_request(p, 2)
            eng_dbg.run()
        snap_dbg = eng_dbg.metrics.snapshot()
        # the clean bench run must fire zero watchdog alerts on BOTH
        # engines
        assert all(v == 0 for k, v in snap_on.items()
                   if k.startswith("serving_alerts_total")), \
            "watchdog alert fired on the clean bench run"
        assert all(v == 0 for k, v in snap_dbg.items()
                   if k.startswith("serving_alerts_total")), \
            "watchdog alert fired on the clean debug bench run"
        hlo = {
            "serving_step_phase_s_p99": {
                k.split("phase=")[1].rstrip("}"): float(v)
                for k, v in sorted(snap_dbg.items())
                if k.startswith("serving_step_phase_s_p99{") and v},
            "serving_hlo_collective_ops":
                int(snap_dbg["serving_hlo_collective_ops"]),
            "serving_hlo_host_transfers":
                int(snap_dbg["serving_hlo_host_transfers"]),
            "serving_hlo_peak_hbm_bytes":
                int(snap_dbg["serving_hlo_peak_hbm_bytes"]),
            "serving_hlo_flops_per_step":
                float(snap_dbg["serving_hlo_flops_per_step"]),
            "serving_hlo": {
                name: {"collective_ops": len(r.collectives),
                       "host_transfers": len(r.host_transfers),
                       "peak_hbm_bytes": int(r.peak_bytes),
                       "flops_per_step": float(r.flops)}
                for name, r in sorted(eng_dbg.hlo_audits.items())},
        }
    except Exception as e:  # noqa: BLE001 — keep the serving numbers
        print(f"[bench] serving hlocheck phase failed: "
              f"{type(e).__name__}: {str(e)[:300]}",
              file=sys.stderr, flush=True)

    trace_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "profiles",
        "serving_trace.json")
    try:
        os.makedirs(os.path.dirname(trace_path), exist_ok=True)
        engine_on.export_chrome_trace(trace_path)
    except OSError as e:
        print(f"[bench] WARNING: could not write serving trace: {e}",
              file=sys.stderr, flush=True)
        trace_path = None
    return {
        "analysis_retraces_total":
            int(snap_on["serving_analysis_retraces_total"]),
        "analysis_host_syncs_total": syncs_on,
        "serving_prefix_tokens_per_sec_on": round(tps_on, 1),
        "serving_prefix_tokens_per_sec_off": round(tps_off, 1),
        "serving_prefix_prefill_tokens_on":
            int(snap_on["serving_prefill_tokens_total"]),
        "serving_prefix_prefill_tokens_off":
            int(snap_off["serving_prefill_tokens_total"]),
        "serving_prefix_tokens_saved":
            int(snap_on["serving_prefix_tokens_saved"]),
        "serving_prefix_hits": int(snap_on["serving_prefix_hits"]),
        "serving_prefix_misses": int(snap_on["serving_prefix_misses"]),
        "serving_prefix_hit_rate": round(
            snap_on["serving_prefix_hits"]
            / max(1, snap_on["serving_prefix_hits"]
                  + snap_on["serving_prefix_misses"]), 4),
        # latency decomposition of the caching-on run (obs histograms)
        "serving_ttft_s_p50": round(snap_on["serving_ttft_s_p50"], 6),
        "serving_ttft_s_p99": round(snap_on["serving_ttft_s_p99"], 6),
        "serving_tpot_s_p50": round(snap_on["serving_tpot_s_p50"], 6),
        "serving_tpot_s_p99": round(snap_on["serving_tpot_s_p99"], 6),
        "serving_queue_wait_s_p99":
            round(snap_on["serving_queue_wait_s_p99"], 6),
        # obs overhead delta: same workload, tracing on (default) vs off
        "serving_obs_tokens_per_sec_on": round(tps_on, 1),
        "serving_obs_tokens_per_sec_off": round(tps_obs_off, 1),
        "serving_trace_path": trace_path,
        **hlo,
    }


def _serving_chunked_bench() -> dict:
    """Serving phase: mixed long-prompt + short-prompt traffic (two
    48-token whales interleaved with six 6-token newcomers) served with
    chunked prefill + the SLO admission controller ON vs chunking OFF.
    Reports the latency decomposition of each mode — the whole point of
    chunking is the TAIL: newcomer ``serving_ttft_s_p99`` stops queueing
    behind whale prefills and running-request ``serving_tpot_s_p99``
    stops absorbing max-bucket prefill stalls. Numbers are EMITTED, not
    ratio-asserted (CPU box noise rule); the structural contracts —
    sync-free decode loop (SyncTally == token fetches, with chunking and
    the controller on), zero over-budget retraces — are asserted, since
    they are exact counts, not timings."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis import SyncTally
    from paddle_tpu.serving import ServingConfig, ServingEngine, SLOConfig
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(29)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=96, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(2)
    whales = [rng.randint(0, 512, (48,)).astype(np.int32)
              for _ in range(2)]
    shorts = [rng.randint(0, 512, (6,)).astype(np.int32)
              for _ in range(6)]
    # whale-first arrival: the head-of-line case chunking exists to fix
    arrivals = [whales[0]] + shorts[:3] + [whales[1]] + shorts[3:]
    budget = 8

    def drive(chunk_size, slo):
        engine = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=64, page_size=16, max_prompt_len=48,
            enable_prefix_caching=False, chunk_size=chunk_size, slo=slo))
        # warm both prompt shapes' compiles out of the timing
        engine.add_request(whales[0], 2)
        engine.run()
        engine.add_request(shorts[0], 2)
        engine.run()
        pre = engine.metrics.snapshot()
        t0 = time.perf_counter()
        for p in arrivals:
            engine.add_request(p, budget)
        with SyncTally() as tally:
            engine.run()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        fetches = int(snap["serving_decode_steps"]
                      - pre["serving_decode_steps"]
                      + snap["serving_prefills_total"]
                      - pre["serving_prefills_total"])
        assert tally.count == fetches, (
            f"decode loop not sync-free with chunk_size={chunk_size}: "
            f"{tally.count} syncs vs {fetches} sanctioned fetches — "
            f"events: {tally.events[:20]}")
        assert snap["serving_analysis_retraces_total"] == 0, \
            "compile budget violated in the chunked serving bench"
        return len(arrivals) * budget / dt, snap

    slo = SLOConfig(ttft_p99_s=2.0, tpot_p99_s=1.0, window_steps=8)
    tps_chunked, snap_c = drive(16, slo)
    tps_plain, snap_p = drive(0, None)
    return {
        "serving_chunked_tokens_per_sec": round(tps_chunked, 1),
        "serving_unchunked_tokens_per_sec": round(tps_plain, 1),
        "serving_chunked_ttft_s_p99":
            round(snap_c["serving_ttft_s_p99"], 6),
        "serving_unchunked_ttft_s_p99":
            round(snap_p["serving_ttft_s_p99"], 6),
        "serving_chunked_tpot_s_p99":
            round(snap_c["serving_tpot_s_p99"], 6),
        "serving_unchunked_tpot_s_p99":
            round(snap_p["serving_tpot_s_p99"], 6),
        "serving_chunked_ttft_s_p50":
            round(snap_c["serving_ttft_s_p50"], 6),
        "serving_unchunked_ttft_s_p50":
            round(snap_p["serving_ttft_s_p50"], 6),
        "serving_prefill_chunks_total":
            int(snap_c["serving_prefill_chunks_total"]),
        "serving_chunk_limit": int(snap_c["serving_chunk_limit"]),
        "serving_slo_throttles_total":
            int(snap_c["serving_slo_throttles_total"]),
    }


def _serving_kvq_bench() -> dict:
    """Serving phase: quantized paged KV + the host cache tier vs plain
    fp32 at a FIXED pool byte budget, under alternating bursts of warm
    system-prompt traffic and cold whales that wipe the pool. Three modes:

    - fp32 at the byte budget (17 usable pages): every whale burst evicts
      the warm system-prompt pages OUTRIGHT (the PR 3 purge), so the next
      warm burst re-prefills the 48-token prefix — thrash;
    - int8 at the SAME byte budget: ~4x the pages (``kv_bytes_per_token``
      1024 -> 260 B), so the prefix survives the whale bursts untouched;
    - int8 at the fp32 PAGE count plus the host tier: the whale bursts
      still evict, but the prefix pages spill to host memory and restore
      on the next warm hit instead of re-prefilling.

    Timings are EMITTED, never ratio-asserted (CPU noise rule). The
    structural evidence IS asserted — it's exact and deterministic: the
    fp32 run evicts with zero restores, the byte-matched int8 run never
    re-prefills the prefix after the first registration, and the tier run
    restores pages and saves at least as many prefill tokens as fp32."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(23)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(5)
    system = rng.randint(0, 512, (48,))  # 3 full pages at page_size 16
    warm = [np.concatenate([system, rng.randint(0, 512, (8,))])
            .astype(np.int32) for _ in range(12)]
    whales = [rng.randint(0, 512, (56,)).astype(np.int32)
              for _ in range(12)]
    budget = 8
    fp32_pages = 18  # 17 usable = one whale burst exactly fills the pool

    def drive(kv_dtype, num_pages, host_tier_bytes):
        engine = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=num_pages, page_size=16,
            max_prompt_len=64, kv_dtype=kv_dtype,
            host_tier_bytes=host_tier_bytes))
        engine.add_request(warm[0], budget)  # warm the compile + register
        engine.run()                         # the system prefix
        t0 = time.perf_counter()
        served = 0
        for cycle in range(3):  # warm burst, then a pool-wiping cold burst
            for p in warm[1 + 4 * cycle:1 + 4 * (cycle + 1)]:
                engine.add_request(p, budget)
            served += len(engine.run())
            for p in whales[4 * cycle:4 * (cycle + 1)]:
                engine.add_request(p, budget)
            served += len(engine.run())
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        assert snap["serving_analysis_retraces_total"] == 0, \
            f"compile budget violated in the kvq bench ({kv_dtype})"
        return served * budget / dt, snap

    # fp32 page bytes / int8 page bytes ~ 3.94: same HBM spend -> ~4x pages
    int8_pages = 70
    tps_f32, snap_f32 = drive("float32", fp32_pages, 0)
    tps_q8, snap_q8 = drive("int8", int8_pages, 0)
    tps_q8_tier, snap_t = drive("int8", fp32_pages, 8 << 20)

    # exact, deterministic structural evidence (not timings): fp32
    # thrashes (prefix purged and re-prefilled), byte-matched int8
    # doesn't, the tier run restores instead of re-prefilling
    assert snap_f32["serving_prefix_evictions"] > 0
    assert snap_f32["serving_host_tier_restores_total"] == 0
    assert snap_t["serving_host_tier_restores_total"] > 0
    assert snap_t["serving_prefill_tokens_total"] <= \
        snap_f32["serving_prefill_tokens_total"]
    assert snap_q8["serving_prefill_tokens_total"] <= \
        snap_t["serving_prefill_tokens_total"]
    return {
        "serving_kvq_tokens_per_sec_fp32": round(tps_f32, 1),
        "serving_kvq_tokens_per_sec_int8": round(tps_q8, 1),
        "serving_kvq_tokens_per_sec_int8_tier": round(tps_q8_tier, 1),
        # capacity: device bytes per resident token (the gauge the 4x
        # claim is measured by) and tokens each pool holds at once
        "serving_kv_bytes_per_token_fp32":
            int(snap_f32["serving_kv_bytes_per_token"]),
        "serving_kv_bytes_per_token_int8":
            int(snap_q8["serving_kv_bytes_per_token"]),
        "serving_kvq_pool_tokens_fp32": (fp32_pages - 1) * 16,
        "serving_kvq_pool_tokens_int8": (int8_pages - 1) * 16,
        # thrash evidence: prefill tokens actually computed (lower = the
        # warm prefix kept serving) and the tier's traffic
        "serving_kvq_prefill_tokens_fp32":
            int(snap_f32["serving_prefill_tokens_total"]),
        "serving_kvq_prefill_tokens_int8":
            int(snap_q8["serving_prefill_tokens_total"]),
        "serving_kvq_prefill_tokens_int8_tier":
            int(snap_t["serving_prefill_tokens_total"]),
        "serving_kvq_evictions_fp32":
            int(snap_f32["serving_prefix_evictions"]),
        "serving_host_tier_spills_total":
            int(snap_t["serving_host_tier_spills_total"]),
        "serving_host_tier_restores_total":
            int(snap_t["serving_host_tier_restores_total"]),
        "serving_host_tier_hits_total":
            int(snap_t["serving_host_tier_hits_total"]),
        "serving_host_tier_bytes":
            int(snap_t["serving_host_tier_bytes"]),
    }


def _serving_spec_bench() -> dict:
    """Serving phase: speculative decoding vs plain decode at batch 1 and
    batch 4 — the TPOT headline the ROADMAP names, where continuous
    batching alone leaves the chips idle. Three modes per batch size:
    plain decode, n-gram proposer (K=4), and draft-model proposer (K=4, a
    1-layer draft). The small vocab makes the greedy stream cycle, so the
    n-gram proposer genuinely accepts — tokens/s and TPOT are EMITTED,
    never ratio-asserted (CPU noise rule; a toy model's verify pass is
    dispatch-dominated on CPU anyway). The structural evidence IS
    asserted, exactly: outputs bit-identical to plain decode, ONE verify
    program per mode (zero retraces), one host fetch per engine step
    (SyncTally == decode steps + prefills with speculation ON), proposed
    == depth x verify steps x active slots, and the acceptance totals
    consistent across the metrics and the step timeline."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis import SyncTally
    from paddle_tpu.serving import ServingConfig, ServingEngine, SpecConfig
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(31)
    cfg = GPTConfig(vocab_size=64, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=96, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    draft_cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                          num_heads=2, max_seq_len=16, dropout=0.0)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 64, (12,)).astype(np.int32)
               for _ in range(4)]
    budget = 48

    def drive(spec, nreq):
        engine = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=64, page_size=16, max_prompt_len=16,
            enable_prefix_caching=False, spec=spec))
        engine.add_request(prompts[0], 2)  # warm the compiles
        engine.run()
        pre = engine.metrics.snapshot()
        rids = [engine.add_request(p, budget) for p in prompts[:nreq]]
        t0 = time.perf_counter()
        with SyncTally() as tally:
            outs = engine.run()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        fetches = int(snap["serving_decode_steps"]
                      - pre["serving_decode_steps"]
                      + snap["serving_prefills_total"]
                      - pre["serving_prefills_total"])
        assert tally.count == fetches, (
            f"verify loop not sync-free: {tally.count} syncs vs "
            f"{fetches} sanctioned fetches — events: {tally.events[:20]}")
        assert snap["serving_analysis_retraces_total"] == 0, \
            "compile budget violated in the spec serving bench"
        steps = int(snap["serving_decode_steps"]
                    - pre["serving_decode_steps"])
        rate = 0.0
        if spec is not None:
            proposed = int(snap["serving_spec_proposed_tokens_total"])
            accepted = int(snap["serving_spec_accepted_tokens_total"])
            active_steps = sum(r.batch for r in engine.timeline.records()
                               if r.batch)
            assert proposed == spec.depth * active_steps, \
                (proposed, spec.depth, active_steps)
            assert 0 <= accepted <= proposed
            assert sum(r.accepted for r in engine.timeline.records()) \
                == accepted, "timeline/metrics acceptance must agree"
            # the banked rate covers the MEASURED workload only — the
            # lifetime gauge would blend in the warm-up request's step
            rate = (accepted
                    - pre["serving_spec_accepted_tokens_total"]) / max(
                1, proposed - pre["serving_spec_proposed_tokens_total"])
        tpot = dt / max(1, nreq * budget - nreq)  # per decoded token
        return ([outs[r] for r in rids], nreq * budget / dt, tpot, steps,
                rate)

    out = {}
    for nreq, tag in ((1, "b1"), (4, "b4")):
        plain, tps_p, tpot_p, steps_p, _ = drive(None, nreq)
        for mode, spec in (
                ("ngram", SpecConfig(method="ngram", depth=4)),
                ("draft", SpecConfig(method="draft", depth=4,
                                     draft=draft_cfg, window=8))):
            spec_outs, tps_s, tpot_s, steps_s, rate_s = drive(spec, nreq)
            for a, b in zip(plain, spec_outs):
                assert np.array_equal(a, b), \
                    f"speculative {mode} {tag} output diverged from plain"
            out[f"serving_spec_{tag}_{mode}_tokens_per_sec"] = \
                round(tps_s, 1)
            out[f"serving_spec_{tag}_{mode}_tpot_s"] = round(tpot_s, 6)
            out[f"serving_spec_{tag}_{mode}_steps"] = steps_s
            out[f"serving_spec_{tag}_{mode}_acceptance_rate"] = round(
                float(rate_s), 4)
        out[f"serving_spec_{tag}_plain_tokens_per_sec"] = round(tps_p, 1)
        out[f"serving_spec_{tag}_plain_tpot_s"] = round(tpot_p, 6)
        out[f"serving_spec_{tag}_plain_steps"] = steps_p
    return out


def _serving_tenant_bench() -> dict:
    """Serving phase: per-tenant SLO observability — an interactive +
    batch traffic mix served by one engine with the goodput ledger,
    journeys, and the slo_burn watchdog ON. Per-tenant TTFT/TPOT p99s
    and goodput fractions are EMITTED, never ratio-asserted (CPU noise
    rule — a toy model's latency split says nothing about real SLO
    headroom). The structural evidence IS asserted, exactly: outputs
    bit-identical tenants-on vs tenants-off (the tenant label never
    enters a traced program; compile counts equal, zero retraces), the
    SyncTally certification formula (decode steps + prefills) unchanged
    with the whole tenant layer on, ZERO alerts on the clean leg (the
    targets are generous), and slo_burn firing EXACTLY ONCE on a rigged
    leg whose tenant declares an unmeetable TTFT target."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis import SyncTally
    from paddle_tpu.obs import validate_flight_record, validate_journey
    from paddle_tpu.serving import (ServingConfig, ServingEngine,
                                    TenantSLO)
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(33)
    cfg = GPTConfig(vocab_size=96, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=96, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(17)
    # interactive: short prompts, short outputs; batch: longer both ways
    jobs = [(rng.randint(0, 96, (6,)).astype(np.int32), 8, "interactive")
            for _ in range(6)] + \
           [(rng.randint(0, 96, (14,)).astype(np.int32), 24, "batch")
            for _ in range(3)]

    def drive(tenants, tag_tenants):
        engine = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=64, page_size=16, max_prompt_len=16,
            enable_prefix_caching=False, tenants=tenants))
        rids = [engine.add_request(p, n,
                                   tenant=t if tag_tenants else "default")
                for p, n, t in jobs]
        t0 = time.perf_counter()
        with SyncTally() as tally:
            outs = engine.run()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        fetches = int(snap["serving_decode_steps"]
                      + snap["serving_prefills_total"])
        assert tally.count == fetches, (
            f"tenant layer not sync-free: {tally.count} syncs vs "
            f"{fetches} sanctioned fetches — events: {tally.events[:20]}")
        assert snap["serving_analysis_retraces_total"] == 0, \
            "compile budget violated in the tenant serving bench"
        return engine, [outs[r] for r in rids], dt, snap

    out = {}
    # clean leg: generous targets, everything in_slo, zero alerts
    slos = {"interactive": TenantSLO(ttft_p99_s=300.0, tpot_p99_s=300.0),
            "batch": TenantSLO(ttft_p99_s=600.0, tpot_p99_s=600.0)}
    eng_off, plain, dt_off, _ = drive(None, False)
    eng_on, tagged, dt_on, snap = drive(slos, True)
    for a, b in zip(plain, tagged):
        assert np.array_equal(a, b), \
            "tenant labels changed the served outputs"
    assert eng_on.compile_counts == eng_off.compile_counts
    assert eng_on.alerts() == [], \
        f"clean tenant leg fired alerts: {eng_on.alerts()}"
    report = eng_on.tenant_report()
    total_tokens = sum(n for _, n, _ in jobs)
    ledger_tokens = sum(sum(e["tokens"].values())
                        for e in report.values())
    assert ledger_tokens == total_tokens == \
        int(snap["serving_tokens_total"]), \
        "per-tenant ledger tokens must reconcile with the engine total"
    for j in eng_on.journeys():
        validate_journey(j.to_wire())
    validate_flight_record(eng_on.flight_record())
    for tenant in ("interactive", "batch"):
        e = report[tenant]
        out[f"serving_tenant_{tenant}_ttft_p99_s"] = round(
            float(e.get("ttft_s_p99", 0.0)), 6)
        out[f"serving_tenant_{tenant}_tpot_p99_s"] = round(
            float(e.get("tpot_s_p99", 0.0)), 6)
        out[f"serving_tenant_{tenant}_goodput_fraction"] = round(
            float(e["goodput_fraction"]), 4)
        out[f"serving_tenant_{tenant}_goodput_tokens"] = \
            e["goodput_tokens"]
    out["serving_tenant_tokens_per_sec"] = round(total_tokens / dt_on, 1)
    out["serving_tenant_off_tokens_per_sec"] = round(
        total_tokens / dt_off, 1)

    # rigged leg: an unmeetable TTFT target — every retirement is
    # ttft_late, and the burn-rate watchdog fires exactly once
    rig, _, _, rig_snap = drive(
        {"interactive": TenantSLO(ttft_p99_s=1e-9, tpot_p99_s=1e-9),
         "batch": TenantSLO(ttft_p99_s=600.0, tpot_p99_s=600.0)}, True)
    alerts = rig.alerts()
    assert [a.rule for a in alerts] == ["slo_burn"], \
        f"rigged leg must fire slo_burn exactly once, got {alerts}"
    assert alerts[0].data["tenant"] == "interactive"
    assert rig_snap["serving_alerts_total{rule=slo_burn}"] == 1
    assert rig_snap["serving_tenant_goodput_tokens_total"
                    "{tenant=interactive}"] == 0
    out["serving_tenant_rigged_badput_tokens"] = int(
        rig_snap["serving_tenant_badput_tokens_total{tenant=interactive}"])
    return out


def _serving_fleet_bench() -> dict:
    """Serving phase: the N-replica fleet router — a shared-system-prompt
    multi-tenant mix through a 3-replica fleet with prefix-affinity
    routing, vs the same trace through one bare engine. Tokens/s and
    per-tenant p99s are EMITTED, never ratio-asserted (CPU noise rule —
    three toy replicas on one core say nothing about fleet speedup; on
    TPU the replicas still share one chip). The structural evidence IS
    asserted, exactly: zero retraces on every replica (routing never
    perturbs the compiled programs), affinity hits > 0 on the warm wave
    (the router really homes repeats on warm replicas), ZERO alerts on
    the clean leg, and EXACTLY ONE slo_burn weight change on a rigged
    leg with an unmeetable TTFT target."""
    import paddle_tpu as paddle
    from paddle_tpu.obs import TenantSLO, WatchdogConfig
    from paddle_tpu.serving import (FleetConfig, FleetRouter,
                                    ServingConfig, ServingEngine)
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(34)
    cfg = GPTConfig(vocab_size=96, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=96, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(18)
    system = rng.randint(0, 96, (16,)).astype(np.int32)  # one shared
    # warm prefix (4 pages) every request rides — the affinity signal

    def jobs():
        mk = lambda tail: np.concatenate(  # noqa: E731
            [system, rng.randint(0, 96, (tail,))]).astype(np.int32)
        return [(mk(4), 8, "interactive") for _ in range(6)] + \
               [(mk(8), 24, "batch") for _ in range(3)]

    eng_cfg = dict(max_batch=4, num_pages=64, page_size=4,
                   max_prompt_len=32)
    slos = {"interactive": TenantSLO(ttft_p99_s=300.0, tpot_p99_s=300.0),
            "batch": TenantSLO(ttft_p99_s=600.0, tpot_p99_s=600.0)}

    out = {}
    # clean leg: two waves through 3 replicas — wave 1 warms the gossip,
    # wave 2 must route on affinity
    fleet = FleetRouter(model, FleetConfig(
        num_replicas=3, engine=ServingConfig(tenants=slos, **eng_cfg)))
    trace = jobs() + jobs()
    total_tokens = sum(n for _, n, _ in trace)
    t0 = time.perf_counter()
    for p, n, t in jobs():
        fleet.submit(p, n, tenant=t)
    fleet.run()
    for p, n, t in jobs():  # the warm wave
        fleet.submit(p, n, tenant=t)
    fleet.run()
    dt = time.perf_counter() - t0
    snap = fleet.metrics.snapshot()
    assert snap["serving_analysis_retraces_total"] == 0, \
        "compile budget violated in the fleet serving bench"
    for i, eng in enumerate(fleet.replicas):
        assert eng.compile_counts.get("decode", 0) <= 1, \
            f"replica {i} retraced decode: {eng.compile_counts}"
    hits = int(snap["serving_fleet_prefix_affinity_hits_total"])
    assert hits > 0, "warm wave produced no affinity-routed requests"
    assert fleet.alerts() == [], \
        f"clean fleet leg fired alerts: {fleet.alerts()}"
    assert fleet.weight_changes == []
    out["serving_fleet_replicas"] = len(fleet.replicas)
    out["serving_fleet_affinity_hits"] = hits
    out["serving_fleet_spills"] = int(snap["serving_fleet_spills_total"])
    out["serving_fleet_prefill_tokens"] = int(
        snap["serving_prefill_tokens_total"])
    out["serving_fleet_tokens_per_sec"] = round(total_tokens / dt, 1)
    for tenant in ("interactive", "batch"):
        out[f"serving_fleet_{tenant}_ttft_p99_s"] = round(
            float(snap[f"serving_ttft_s_p99{{tenant={tenant}}}"]), 6)
        out[f"serving_fleet_{tenant}_tpot_p99_s"] = round(
            float(snap[f"serving_tpot_s_p99{{tenant={tenant}}}"]), 6)

    # baseline: the SAME trace through one bare engine (emitted only)
    engine = ServingEngine(model, ServingConfig(tenants=slos, **eng_cfg))
    t0 = time.perf_counter()
    for p, n, t in trace:
        engine.add_request(p, n, tenant=t)
    engine.run()
    out["serving_fleet_single_engine_tokens_per_sec"] = round(
        total_tokens / (time.perf_counter() - t0), 1)

    # rigged leg: an unmeetable interactive TTFT target through the
    # router — the burn onset must actuate the admission weight exactly
    # once (the watchdog's edge trigger is the dedupe)
    rig = FleetRouter(model, FleetConfig(num_replicas=1, engine=(
        ServingConfig(tenants={
            "interactive": TenantSLO(ttft_p99_s=1e-9, tpot_p99_s=1e-9),
            "batch": TenantSLO(ttft_p99_s=600.0, tpot_p99_s=600.0)},
            watchdog=WatchdogConfig(slo_burn_window_steps=16,
                                    slo_burn_min_retired=4),
            **eng_cfg))))
    for p, n, t in jobs():
        rig.submit(p, n, tenant=t)
    rig.run()
    assert [(t, w) for _, t, w in rig.weight_changes] == \
        [("interactive", 2.0)], \
        f"rigged leg must gain weight exactly once: {rig.weight_changes}"
    assert rig.weight("interactive") == 2.0
    out["serving_fleet_rigged_weight"] = rig.weight("interactive")
    return out


def _serving_wire_bench() -> dict:
    """Serving phase: the KV-fabric wire transport — codec throughput
    over a mixed fp32/int8 page bank, then the same fleet trace at
    0% / 2% / 10% seeded wire loss. Throughputs are EMITTED, never
    ratio-asserted (CPU noise rule — a host-side codec on a busy core
    says nothing about the fabric). The structural evidence IS
    asserted, exactly: ZERO lost rids at every loss rate (every
    submission completes — loss degrades, it never loses), the tenant
    ledger reconciles to the token counter at drain, and wire retries
    are observed at >0% loss ONLY (a lossless channel never retries —
    the bit-identical parity pin's precondition)."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import (FleetConfig, FleetRouter,
                                    ServingConfig)
    from paddle_tpu.serving.channel import (ChannelConfig, SimChannel,
                                            Transport, TransportConfig)
    from paddle_tpu.serving.kv_cache import SpilledPage
    from paddle_tpu.serving.wire import decode_frame, encode_page
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    out = {}
    # codec leg: encode + decode MB/s over 48 pages, alternating fp32
    # and int8+scales — the two pool dtypes the fleet actually ships
    rng = np.random.RandomState(7)
    shape = (4, 8, 4, 32)  # [layers, page, heads, head_dim]
    pages = []
    for i in range(48):
        key = (i, tuple(int(t) for t in rng.randint(0, 96, 4)))
        if i % 2:
            scale = rng.rand(4, 4).astype(np.float32)
            pages.append(SpilledPage(
                key=key, serial=i,
                k=rng.randint(-128, 128, shape).astype(np.int8),
                v=rng.randint(-128, 128, shape).astype(np.int8),
                k_scale=scale, v_scale=scale))
        else:
            pages.append(SpilledPage(
                key=key, serial=i,
                k=rng.randn(*shape).astype(np.float32),
                v=rng.randn(*shape).astype(np.float32),
                k_scale=None, v_scale=None))
    t0 = time.perf_counter()
    frames = [encode_page(p) for p in pages]
    enc_dt = time.perf_counter() - t0
    nbytes = sum(len(f) for f in frames)
    t0 = time.perf_counter()
    for f in frames:
        kind, _ = decode_frame(f)
        assert kind == "page"
    dec_dt = time.perf_counter() - t0
    out["serving_wire_frame_bytes"] = nbytes
    out["serving_wire_encode_mb_per_sec"] = round(nbytes / enc_dt / 1e6, 1)
    out["serving_wire_decode_mb_per_sec"] = round(nbytes / dec_dt / 1e6, 1)

    # fleet legs: one shared warm prefix (the affinity + page-fetch
    # signal), two waves through 2 replicas, the wire dialed from
    # lossless to 10% drop + 5% corrupt
    paddle.seed(34)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=96, dropout=0.0))
    model.eval()
    wrng = np.random.RandomState(21)
    system = wrng.randint(0, 96, (16,)).astype(np.int32)

    def jobs():
        mk = lambda tail: np.concatenate(  # noqa: E731
            [system, wrng.randint(0, 96, (tail,))]).astype(np.int32)
        return [(mk(4), 8) for _ in range(6)]

    eng = ServingConfig(max_batch=2, num_pages=64, page_size=4,
                        max_prompt_len=32, host_tier_bytes=1 << 20)
    for loss in (0.0, 0.02, 0.10):
        transport = Transport(
            SimChannel(ChannelConfig(seed=11, drop_rate=loss,
                                     corrupt_rate=loss / 2)),
            TransportConfig(seed=11, timeout_s=0.5))
        fleet = FleetRouter(model, FleetConfig(
            num_replicas=2, engine=eng, transport=transport,
            fetch_pages=True))
        trace = jobs() + jobs()
        total_tokens = sum(n for _, n in trace)
        rids, outs = [], {}
        t0 = time.perf_counter()
        for p, n in jobs():
            rids.append(fleet.submit(p, n))
        outs.update(fleet.run())
        for p, n in jobs():  # the warm wave rides the wire's fetches
            rids.append(fleet.submit(p, n))
        outs.update(fleet.run())
        dt = time.perf_counter() - t0
        tag = f"loss_{int(loss * 100)}pct"
        assert sorted(outs) == sorted(rids), \
            f"{tag}: wire loss lost rids " \
            f"{sorted(set(rids) - set(outs))}"
        snap = fleet.metrics.snapshot()
        good = sum(v for k, v in snap.items() if k.startswith(
            "serving_tenant_goodput_tokens_total"))
        bad = sum(v for k, v in snap.items() if k.startswith(
            "serving_tenant_badput_tokens_total"))
        assert good + bad == snap["serving_tokens_total"], \
            f"{tag}: ledger does not reconcile: {good}+{bad} != " \
            f"{snap['serving_tokens_total']}"
        if loss == 0.0:
            assert transport.retries_total == 0, \
                "lossless channel retried — the parity pin is void"
        else:
            assert transport.retries_total > 0, \
                f"{tag}: seeded loss produced no retries"
        out[f"serving_wire_tokens_per_sec_{tag}"] = round(
            total_tokens / dt, 1)
        out[f"serving_wire_retries_{tag}"] = transport.retries_total
        out[f"serving_wire_timeouts_{tag}"] = transport.timeouts_total
        out[f"serving_wire_tx_bytes_{tag}"] = transport.tx_bytes
        out[f"serving_wire_refetch_fallbacks_{tag}"] = int(
            snap["serving_wire_refetch_fallback_total"])
    return out


def _serving_ragged_kernel_bench() -> dict:
    """Serving phase: the unified ragged paged-attention kernel vs the
    gather+sdpa composite, fp32 and int8 — the ROADMAP's raw-decode A/B.
    Kernel-on runs the real Pallas program on TPU (dispatch-eligible by
    default) and the Pallas INTERPRETER on CPU (``FLAGS_ragged_interpret``
    — same program, bit-identity verifiable, timings dispatch-dominated);
    kernel-off forces the composite via ``FLAGS_use_pallas_kernels``.
    Tokens/s and TPOT are EMITTED, never ratio-asserted (CPU noise rule —
    and the interpreter is *expected* slower; the honest speed read is the
    on-chip run against the banked ``serving_kernel_speedup_predicted``
    gauges). Asserted: outputs bit-identical kernel-on vs off on the CPU
    interpreter (the test-pinned contract); on chip, where compiled
    Mosaic accumulation order is not bit-pinned against the composite,
    greedy divergence is BOUNDED instead (mean common-prefix >= 0.5, the
    PR 9 quality-contract idiom) and emitted. Always exact: zero
    retraces (one compiled program per mode either way), one host fetch
    per step (SyncTally == decode steps + prefills), zero Pallas
    fallbacks with the kernel on."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis import SyncTally
    from paddle_tpu.kernels._common import on_tpu_backend
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.utils.flags import set_flags

    on_tpu = on_tpu_backend()
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, 64, (10,)).astype(np.int32)
               for _ in range(3)]
    budget = 24

    def drive(kernel_on, kv):
        set_flags({"FLAGS_use_pallas_kernels": kernel_on,
                   "FLAGS_ragged_interpret": kernel_on and not on_tpu})
        try:
            paddle.seed(23)
            model = GPTForCausalLM(GPTConfig(
                vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=64, dropout=0.0))
            model.eval()
            engine = ServingEngine(model, ServingConfig(
                max_batch=3, num_pages=48, page_size=4,
                max_prompt_len=16, kv_dtype=kv,
                enable_prefix_caching=False))
            engine.add_request(prompts[0], 2)  # warm the compiles
            engine.run()
            pre = engine.metrics.snapshot()
            rids = [engine.add_request(p, budget) for p in prompts]
            t0 = time.perf_counter()
            with SyncTally() as tally:
                outs = engine.run()
            dt = time.perf_counter() - t0
            snap = engine.metrics.snapshot()
            fetches = int(snap["serving_decode_steps"]
                          - pre["serving_decode_steps"]
                          + snap["serving_prefills_total"]
                          - pre["serving_prefills_total"])
            assert tally.count == fetches, (
                f"ragged bench loop not sync-free: {tally.count} syncs "
                f"vs {fetches} sanctioned fetches")
            assert snap["serving_analysis_retraces_total"] == 0, \
                "compile budget violated in the ragged kernel bench"
            if kernel_on:
                assert engine._decode_pallas_eligible, \
                    "kernel-on leg did not dispatch the unified kernel"
                assert snap["serving_pallas_fallback_total"] == 0, \
                    "unified kernel fell back in the bench loop"
            total = len(prompts) * budget
            return ([outs[r] for r in rids], total / dt,
                    dt / max(1, total - len(prompts)))
        finally:
            set_flags({"FLAGS_use_pallas_kernels": True,
                       "FLAGS_ragged_interpret": False})

    out = {"serving_ragged_kernel_mode":
           "pallas-tpu" if on_tpu else "pallas-interpret"}
    for kv in ("float32", "int8"):
        comp, tps_c, tpot_c = drive(False, kv)
        kern, tps_k, tpot_k = drive(True, kv)
        tag = "fp32" if kv == "float32" else "int8"
        if not on_tpu:
            # the interpreter's bit-identity contract (test-pinned)
            for a, b in zip(comp, kern):
                assert np.array_equal(a, b), \
                    f"ragged kernel {kv} output diverged from composite"
        else:
            # compiled Mosaic accumulation order is NOT bit-pinned
            # against the XLA composite — on chip, bound the greedy
            # divergence the way the int8-vs-fp32 quality contract does
            # (PR 9: mean common-prefix >= 0.5) and emit the number
            prefix = []
            for a, b in zip(comp, kern):
                n = 0
                for x, y in zip(a, b):
                    if x != y:
                        break
                    n += 1
                prefix.append(n / max(1, min(len(a), len(b))))
            mean_prefix = sum(prefix) / len(prefix)
            assert mean_prefix >= 0.5, (
                f"ragged kernel {kv} on-chip divergence too large: "
                f"mean common-prefix {mean_prefix:.2f}")
            out[f"serving_ragged_{tag}_common_prefix"] = round(
                mean_prefix, 3)
        out[f"serving_ragged_{tag}_kernel_tokens_per_sec"] = round(tps_k, 1)
        out[f"serving_ragged_{tag}_composite_tokens_per_sec"] = \
            round(tps_c, 1)
        out[f"serving_ragged_{tag}_kernel_tpot_s"] = round(tpot_k, 6)
        out[f"serving_ragged_{tag}_composite_tpot_s"] = round(tpot_c, 6)
    return out


def _serving_tp_bench() -> dict:
    """Serving phase: the shared-system-prompt workload at TP=1 vs TP=2 —
    tensor-parallel sharded serving (Megatron weight shards + heads-
    sharded paged KV pool via shard_map, serving/tp.py). Emits
    ``serving_tp1_tokens_per_sec`` / ``serving_tp2_tokens_per_sec`` plus
    the per-step collective census of
    the sharded programs (op count and payload bytes per token, straight
    from the debug_checks hlocheck audit — the EQuARX baseline numbers).
    All timings EMITTED, never ratio-asserted; the structural contracts
    — TP=2 outputs bit-identical to TP=1, sync-free decode loop, zero
    retraces — are asserted, since they are exact.

    Needs >= 2 devices in THIS process (run_bench skips the phase by name
    otherwise): a parent that holds the chip cannot hand a child another
    backend and report its numbers as its own."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis import SyncTally
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.serving import scheduler as sched_mod
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(17)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    system = rng.randint(0, 512, (48,))
    prompts = [np.concatenate([system, rng.randint(0, 512, (8,))])
               .astype(np.int32) for _ in range(12)]
    budget = 8

    def drive(tp):
        import itertools

        sched_mod._rid_counter = itertools.count(50000)  # align rids
        engine = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=64, page_size=16, max_prompt_len=64,
            tensor_parallel=tp))
        for p in prompts[:2]:  # warm both prefill buckets out of timing
            engine.add_request(p, budget)
            engine.run()
        pre = engine.metrics.snapshot()
        t0 = time.perf_counter()
        outs = {}
        for p in prompts[2:]:
            engine.add_request(p, budget)
        with SyncTally() as tally:
            outs = engine.run()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        fetches = int(snap["serving_decode_steps"]
                      - pre["serving_decode_steps"]
                      + snap["serving_prefills_total"]
                      - pre["serving_prefills_total"])
        assert tally.count == fetches, (
            f"decode loop not sync-free at TP={tp}: {tally.count} syncs "
            f"vs {fetches} sanctioned token fetches")
        assert snap["serving_analysis_retraces_total"] == 0, \
            f"compile budget violated in the TP={tp} serving bench"
        return (len(prompts) - 2) * budget / dt, \
            [outs[k] for k in sorted(outs)]

    tps1, outs1 = drive(1)
    tps2, outs2 = drive(2)
    assert len(outs1) == len(outs2) and all(
        np.array_equal(a, b) for a, b in zip(outs1, outs2)), \
        "TP=2 outputs diverged from TP=1"

    # the sharded programs' collective census (static compiled-artifact
    # facts): one short debug_checks run audits every program
    eng_dbg = ServingEngine(model, ServingConfig(
        max_batch=4, num_pages=64, page_size=16, max_prompt_len=64,
        tensor_parallel=2, debug_checks=True))
    for p in prompts[:2]:
        eng_dbg.add_request(p, 2)
        eng_dbg.run()
    snap_dbg = eng_dbg.metrics.snapshot()
    return {
        "serving_tp1_tokens_per_sec": round(tps1, 1),
        "serving_tp2_tokens_per_sec": round(tps2, 1),
        "serving_tp_collective_ops_per_step":
            int(snap_dbg["serving_tp_collective_ops_per_step"]),
        "serving_tp_collective_bytes_per_token":
            round(snap_dbg["serving_tp_collective_bytes_per_token"], 1),
        "serving_tp_hlo": {
            name: {"collective_ops": len(r.collectives),
                   "collective_bytes": int(r.collective_bytes)}
            for name, r in sorted(eng_dbg.hlo_audits.items())},
    }


def _serving_overlap_bench() -> dict:
    """Serving phase: the decode-overlap triad at TP=2 — the
    latency-hiding-scheduler flag (``tp_overlap_scheduler``, a no-op on
    CPU backends) and the quantized logits all-reduce
    (``tp_quantized_logits``) against the baseline sharded engine (needs
    >= 2 devices in this process; skipped by name otherwise). Emits decode
    throughput + TPOT for the three legs, the compiled collective census
    (op count, bytes/token, overlap fraction) of the quantized programs,
    and the f32-vs-int8 bytes/token shrink. All timings EMITTED, never
    ratio-asserted; the structural contracts are asserted, since they are exact: the overlap-on /
    quantized-OFF leg is bit-identical to the baseline, every leg's
    decode loop is sync-free with zero retraces, and the census + gauges
    are populated."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis import SyncTally
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.serving import scheduler as sched_mod
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(17)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 512, (24,)).astype(np.int32)
               for _ in range(10)]
    budget = 12  # decode-heavy: TPOT is the number under test

    def drive(overlap, quantized):
        import itertools

        sched_mod._rid_counter = itertools.count(70000)  # align rids
        engine = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=64, page_size=16, max_prompt_len=32,
            tensor_parallel=2, tp_overlap_scheduler=overlap,
            tp_quantized_logits=quantized))
        for p in prompts[:2]:  # warm the prefill bucket out of timing
            engine.add_request(p, budget)
            engine.run()
        pre = engine.metrics.snapshot()
        for p in prompts[2:]:
            engine.add_request(p, budget)
        t0 = time.perf_counter()
        with SyncTally() as tally:
            outs = engine.run()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        fetches = int(snap["serving_decode_steps"]
                      - pre["serving_decode_steps"]
                      + snap["serving_prefills_total"]
                      - pre["serving_prefills_total"])
        assert tally.count == fetches, (
            f"decode loop not sync-free (overlap={overlap}, "
            f"quantized={quantized}): {tally.count} syncs vs {fetches} "
            f"sanctioned token fetches")
        assert snap["serving_analysis_retraces_total"] == 0, \
            f"compile budget violated (overlap={overlap}, q={quantized})"
        tokens = (len(prompts) - 2) * budget
        return tokens / dt, 1000.0 * dt / tokens, \
            [outs[k] for k in sorted(outs)]

    tps_base, tpot_base, outs_base = drive(False, False)
    tps_ov, tpot_ov, outs_ov = drive(True, False)
    # the scheduler flag reorders collectives, never what they compute —
    # and the quantized branch never traced: bit-identity is exact
    assert len(outs_base) == len(outs_ov) and all(
        np.array_equal(a, b) for a, b in zip(outs_base, outs_ov)), \
        "overlap-on / quantized-off leg diverged from the baseline"
    tps_q, tpot_q, _ = drive(True, True)

    # compiled-artifact facts for the quantized programs: one short
    # debug_checks run audits the census + feeds the gauges
    eng_dbg = ServingEngine(model, ServingConfig(
        max_batch=4, num_pages=64, page_size=16, max_prompt_len=32,
        tensor_parallel=2, tp_overlap_scheduler=True,
        tp_quantized_logits=True, debug_checks=True))
    for p in prompts[:2]:
        eng_dbg.add_request(p, 2)
        eng_dbg.run()
    snap_dbg = eng_dbg.metrics.snapshot()
    assert snap_dbg["serving_tp_collective_bytes_per_token"] > 0, \
        "census gauge not fed at the first-trace audit"
    assert "serving_tp_collective_overlap_frac" in snap_dbg, \
        "overlap gauge not seeded"
    # the f32 twin's bytes/token, for the shrink the JSON reports
    from paddle_tpu.serving.tp import TPContext
    f32_cap = TPContext(2, cfg).step_budget(batch=4, seq=1)
    q_cap = TPContext(2, cfg, quantized_logits=True).step_budget(4, 1)
    return {
        "serving_tp2_baseline_tokens_per_sec": round(tps_base, 1),
        "serving_tp2_overlap_tokens_per_sec": round(tps_ov, 1),
        "serving_tp2_overlap_qlogits_tokens_per_sec": round(tps_q, 1),
        "serving_tp2_baseline_tpot_ms": round(tpot_base, 2),
        "serving_tp2_overlap_tpot_ms": round(tpot_ov, 2),
        "serving_tp2_overlap_qlogits_tpot_ms": round(tpot_q, 2),
        "serving_tp_collective_bytes_per_token":
            round(snap_dbg["serving_tp_collective_bytes_per_token"], 1),
        "serving_tp_collective_overlap_frac":
            round(snap_dbg["serving_tp_collective_overlap_frac"], 3),
        "decode_collective_bytes_f32": int(f32_cap.max_collective_bytes),
        "decode_collective_bytes_qlogits":
            int(q_cap.max_collective_bytes),
        "serving_overlap_hlo": {
            name: {"collective_ops": len(r.collectives),
                   "collective_bytes": int(r.collective_bytes),
                   "async": r.async_collectives,
                   "overlapped": r.overlapped_collectives}
            for name, r in sorted(eng_dbg.hlo_audits.items())},
    }


#: the serving phases, in run order. Each still drives a toy model
#: (ROADMAP S1 replaces them with cells at published widths); what they
#: assert — bit identity, ledger reconciliation, zero lost rids — is exact
_SERVING_PHASES = (
    ("serving_prefix", _serving_prefix_bench, 1),
    ("serving_chunked", _serving_chunked_bench, 1),
    ("serving_tp", _serving_tp_bench, 2),
    ("serving_overlap", _serving_overlap_bench, 2),
    ("serving_kvq", _serving_kvq_bench, 1),
    ("serving_spec", _serving_spec_bench, 1),
    ("serving_ragged", _serving_ragged_kernel_bench, 1),
    ("serving_tenant", _serving_tenant_bench, 1),
    ("serving_fleet", _serving_fleet_bench, 1),
    ("serving_wire", _serving_wire_bench, 1),
)


def _require_tpu():
    """The first device, or a non-zero exit naming what is missing: a
    benchmark number comes from a chip run, never from the CPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py needs a TPU: jax.devices()[0].platform is "
                 f"{dev.platform!r}. No result is printed for a run "
                 f"without the chip.")
    return dev


def run_bench() -> dict:
    import jax

    dev = _require_tpu()
    print(f"[bench] platform={dev.platform} kind={dev.device_kind}",
          file=sys.stderr, flush=True)

    deadline = time.time() + _TPU_BUDGET_S
    remaining = lambda: deadline - time.time()  # noqa: E731

    result = None
    for rung in _BASE_RUNGS:
        try:
            result = _measure(rung, steps=6, warmup=2)
            break
        except Exception as e:  # noqa: BLE001 — only an OOM moves down the ladder
            if not _is_oom(e):
                raise
            print(f"[bench] {rung['tag']} OOM ({type(e).__name__}); "
                  f"falling to next rung, {remaining():.0f}s left",
                  file=sys.stderr, flush=True)
            gc.collect()
    if result is None:
        raise RuntimeError("no ladder rung fit on the device")

    # a phase that fails raises and the run prints no result; a phase that
    # cannot run here (too few devices, out of budget) is skipped BY NAME
    skipped = {}
    for name, phase, min_devices in _SERVING_PHASES:
        if len(jax.devices()) < min_devices:
            skipped[name] = (f"needs >= {min_devices} devices, "
                             f"{len(jax.devices())} visible")
        elif remaining() <= 45:
            skipped[name] = f"out of the {_TPU_BUDGET_S}s budget"
        else:
            result[name] = phase()
    if skipped:
        result["skipped"] = skipped
    return result


def main():
    # perf-experiment mode: `python bench.py --rung '{"tag":...,"batch":8,...}'
    # [steps]` measures one explicit rung and exits — used for on-chip
    # ladder exploration.
    if len(sys.argv) > 1 and sys.argv[1] == "--rung":
        _require_tpu()
        rung = json.loads(sys.argv[2])
        steps = int(sys.argv[3]) if len(sys.argv) > 3 else 4
        print(json.dumps(_measure(rung, steps=steps, warmup=2)), flush=True)
        return
    print(json.dumps(run_bench()), flush=True)


if __name__ == "__main__":
    main()
