"""Tensor-parallel sharded serving (ServingConfig(tensor_parallel=N)).

The contract under test: sharding is INVISIBLE except for speed — every
serving invariant the single-chip engine pins must survive the Megatron
weight split + heads-sharded paged KV pool:

- **Bit-identical outputs** TP=2 and TP=4 vs TP=1 (token streams, not
  logits bits): greedy, sampling (the (seed, rid, token) PRNG fold),
  prefix-cache hits, chunked prefill, and both preemption modes.
- **Compile-once unchanged**: same ``compile_counts`` as TP=1 — the
  sharded programs compile once per prefill bucket + once for decode.
- **Sync-free certification unchanged**: SyncTally == decode steps +
  completed prefills, the exact single-chip formula.
- **CollectiveBudget certification**: under ``debug_checks`` every
  sharded program audits to exactly 2 all-reduces per block + 1 for the
  logits (byte-capped, the serving/tp.py declaration) — and the
  zero-budget variant raises NAMING the offending collective.
- **KV-pool shard math**: each device owns [num_pages, page_size,
  heads/N, head_dim] per layer; logical page ids/tables are unsharded.

Runs entirely on the conftest-forced 8-device CPU mesh — a virtual-mesh
proof, no chips needed. Sharded CPU compiles are the cost center here,
so tests share engines where coverage allows (the module-scope
debug-audited engine feeds three tests) and single-bucket configs are
used wherever a second pad bucket adds no coverage.
"""
import itertools
import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import SyncTally
from paddle_tpu.analysis.hlocheck import (SINGLE_CHIP,
                                          CollectiveBudgetError, run_step)
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving import scheduler as sched_mod
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

pytestmark = pytest.mark.tp

HIDDEN, LAYERS, HEADS, VOCAB = 32, 2, 4, 97


@pytest.fixture(scope="module")
def model():
    if len(jax.devices()) < 4:
        pytest.skip("needs the conftest 8-device CPU mesh")
    paddle.seed(23)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
        num_heads=HEADS, max_seq_len=48, dropout=0.0))
    m.eval()
    return m


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, (n,)).astype(np.int32) for n in lens]


def _engine(model, tp=1, **kw):
    # align rids across the engines being compared: the sampling PRNG
    # folds (seed, rid, token), so parity needs identical rids (the
    # test_serving_chunked idiom)
    sched_mod._rid_counter = itertools.count(9000)
    kw.setdefault("num_pages", 24)
    kw.setdefault("max_prompt_len", 8)  # one pad bucket unless a test
    # spans two — every extra bucket is an extra sharded CPU compile
    return ServingEngine(model, ServingConfig(
        max_batch=2, page_size=4, tensor_parallel=tp, **kw))


def _drive(model, tp, prompts, budgets, **kw):
    eng = _engine(model, tp, **kw)
    rids = [eng.add_request(p, b) for p, b in zip(prompts, budgets)]
    outs = eng.run()
    return [outs[r] for r in rids], eng


# ------------------------------------------------------------------ parity
def test_greedy_parity_compile_counts_and_sync_free_tp2_tp4(model):
    """THE acceptance gate: greedy outputs bit-identical across TP
    degrees, compile_counts pinned IDENTICAL to TP=1 (one trace per
    bucket + one decode), and the sync-free certification formula —
    SyncTally == decode steps + completed prefills — byte-identical to
    single-chip (the token fetch reads one replicated output: still one
    sync per step boundary)."""
    prompts = _prompts(0, (3, 12, 7, 5))  # spans both buckets [8, 16]
    budgets = [6, 5, 7, 6]
    ref, e1 = _drive(model, 1, prompts, budgets, max_prompt_len=16)
    for tp in (2, 4):
        eng = _engine(model, tp, max_prompt_len=16)
        rids = [eng.add_request(p, b) for p, b in zip(prompts, budgets)]
        pre = eng.metrics.snapshot()
        with SyncTally() as tally:
            outs = eng.run()
        for i, rid in enumerate(rids):
            assert np.array_equal(ref[i], outs[rid]), \
                f"TP={tp} request {i} diverged"
        assert eng.compile_counts == e1.compile_counts == \
            {"prefill": 2, "decode": 1}
        snap = eng.metrics.snapshot()
        fetches = int(snap["serving_decode_steps"]
                      - pre["serving_decode_steps"]
                      + snap["serving_prefills_total"]
                      - pre["serving_prefills_total"])
        assert tally.count == fetches, (tp, tally.count, fetches,
                                        tally.events[:10])


def test_sampling_parity_tp2(model):
    prompts = _prompts(1, (4, 7, 6))
    kw = dict(do_sample=True, temperature=0.8, top_k=20, top_p=0.95,
              seed=5)
    ref, _ = _drive(model, 1, prompts, [7, 6, 5], **kw)
    outs, _ = _drive(model, 2, prompts, [7, 6, 5], **kw)
    assert all(np.array_equal(a, b) for a, b in zip(ref, outs))


def test_prefix_hit_parity_tp2(model):
    """Cache hits map LOGICAL page ids — per-shard pools hold each head
    slice's bytes, so a TP=2 hit serves exactly the KV a TP=2 cold
    prefill would recompute."""
    system = _prompts(2, (4,))[0]  # exactly 1 whole page
    chats = [np.concatenate([system, t])
             for t in _prompts(3, (3, 3, 3))]

    def seq(tp):
        eng = _engine(model, tp, num_pages=32)
        outs = []
        for p in chats:  # sequential: later bursts hit the index
            rid = eng.add_request(p, 5)
            outs.append(eng.run()[rid])
        return outs, eng

    ref, _ = seq(1)
    outs, eng = seq(2)
    assert all(np.array_equal(a, b) for a, b in zip(ref, outs))
    snap = eng.metrics.snapshot()
    assert snap["serving_prefix_hits"] == len(chats) - 1
    assert snap["serving_prefix_tokens_saved"] >= 4 * (len(chats) - 1)


@pytest.mark.slow  # re-tiered 2026-08 (PR 20): tier-1 crossed its 870 s
# budget; chunked parity stays pinned at TP=1 (test_serving_chunked) and
# greedy/sampling TP parity stays tier-1 above
def test_chunked_parity_tp2(model):
    whale = np.arange(1, 14, dtype=np.int32)
    prompts = [whale] + _prompts(4, (3, 6))
    kw = dict(chunk_size=4, max_prompt_len=16)
    ref, e1 = _drive(model, 1, prompts, [6, 5, 6], **kw)
    outs, e2 = _drive(model, 2, prompts, [6, 5, 6], **kw)
    assert all(np.array_equal(a, b) for a, b in zip(ref, outs))
    # chunks pad into the existing bucket set under TP too
    assert e2.compile_counts == e1.compile_counts


@pytest.mark.parametrize("mode", ["recompute", "swap"])
def test_preemption_parity_tp2(model, mode):
    """A 6-usable-page pool forces mid-decode preemption; both modes
    replay/resume bit-identically under TP=2 (swap: the per-shard
    gather/scatter round-trips every head shard's bytes exactly)."""
    prompts = _prompts(5, (3, 8, 7, 5))
    kw = dict(preemption_mode=mode, num_pages=7)
    ref, e1 = _drive(model, 1, prompts, [8] * 4, **kw)
    outs, e2 = _drive(model, 2, prompts, [8] * 4, **kw)
    assert e2.metrics.snapshot()["serving_preemptions_total"] >= 1
    assert all(np.array_equal(a, b) for a, b in zip(ref, outs))
    if mode == "swap":
        # the sharded swap movers compile once each, like single-chip
        assert e2.cache.compile_counts["swap_gather"] == 1
        assert e2.cache.compile_counts["swap_scatter"] == 1


def test_chunked_swap_preemption_parity_tp2(model):
    """The compound case: a whale mid-chunked-prefill swapped out and
    resumed — prefilled_tokens ride the per-shard swap handles."""
    whale = np.arange(2, 10, dtype=np.int32)
    prompts = [whale] + _prompts(6, (7, 5))
    kw = dict(chunk_size=4, preemption_mode="swap", num_pages=7)
    ref, _ = _drive(model, 1, prompts, [8, 8, 8], **kw)
    outs, e2 = _drive(model, 2, prompts, [8, 8, 8], **kw)
    assert all(np.array_equal(a, b) for a, b in zip(ref, outs))


# ---------------------------------------------------------- certifications
@pytest.fixture(scope="module")
def debug_engine(model):
    """ONE debug-audited TP=2 engine shared by the certification tests —
    each sharded program costs an extra AOT compile to audit, so the
    audits are paid once for the module."""
    eng = _engine(model, 2, debug_checks=True, max_prompt_len=16)
    for p, b in zip(_prompts(8, (3, 12)), (4, 3)):
        eng.add_request(p, b)
    eng.run()
    return eng


def test_debug_checks_certifies_declared_budgets_tp2(debug_engine):
    """Every sharded program (both prefill buckets + decode) hlo-audits
    under debug_checks to EXACTLY the declared collectives — 2 all-reduces
    per block + 1 for the logits, byte volumes matching the budget
    formula — with every donated pool shard aliased; the census feeds the
    serving_tp_* gauges."""
    eng = debug_engine
    audits = eng.hlo_audits
    assert set(audits) == {"prefill[8]", "prefill[16]", "decode"}
    expect_ar = 2 * LAYERS + 1
    for label, r in audits.items():
        assert r.counts() == {"all-reduce": expect_ar}, label
        b, s = eng._step_shape(label)
        assert r.collective_bytes == \
            (2 * LAYERS * b * s * HIDDEN + b * s * VOCAB) * 4, label
        assert r.host_transfers == (), label
        assert r.donated_leaves == 2 * LAYERS == r.aliased_leaves, label
    snap = eng.metrics.snapshot()
    assert snap["serving_tp_degree"] == 2
    assert snap["serving_tp_collective_ops_per_step"] == expect_ar
    # bytes/token is bucket-independent here: payloads scale with tokens
    assert snap["serving_tp_collective_bytes_per_token"] == \
        (2 * LAYERS * HIDDEN + VOCAB) * 4


def test_zero_budget_variant_raises_naming_the_collective(model):
    """The acceptance gate's negative half: the SAME sharded engine held
    to the single-chip (zero) budget must raise at the first audited
    program, naming the offending all-reduce instruction."""
    eng = _engine(model, 2, debug_checks=True)
    eng._step_budget = lambda label: SINGLE_CHIP  # the zero-budget variant
    eng.add_request(_prompts(9, (4,))[0], 3)
    with pytest.raises(CollectiveBudgetError) as ei:
        eng.run()
    msg = str(ei.value)
    assert "all-reduce" in msg and "budget of 0" in msg
    # the HLO instruction line is quoted: `%<name> = <type> all-reduce(`
    assert re.search(r"%[\w.\-]+ = \S+ all-reduce\(", msg)


def test_report_reenforcement_against_zero_budget_raises(debug_engine):
    """Same property off the recorded report (no engine surgery): a clean
    TP audit re-enforced at SINGLE_CHIP raises; at its declared budget it
    is idempotent."""
    report = debug_engine.hlo_audits["decode"]
    report.enforce(debug_engine._step_budget("decode"))  # idempotent
    with pytest.raises(CollectiveBudgetError):
        report.enforce(SINGLE_CHIP)


def test_registry_tp2_steps_certify_including_chunk(model):
    """The hlocheck registry's sharded variants certify against their
    declared budgets — notably engine_prefill_chunk's TP twin (the
    ROADMAP follow-up this PR closes) and the donated per-shard swap
    scatter."""
    chunk = run_step("tp2_engine_prefill_chunk")
    assert chunk.counts() == {"all-reduce": 2 * LAYERS + 1}
    scatter = run_step("tp2_swap_scatter")
    assert scatter.collectives == ()
    assert scatter.donated_leaves == scatter.aliased_leaves > 0


# ------------------------------------------------------------- shard math
def test_kv_pool_and_param_shard_math(model):
    """Each device owns [num_pages, page_size, heads/N, head_dim] per
    layer — the global (logical) pool shape is unchanged, page tables
    stay host-side ints. Megatron param placement: qkv column shards,
    row-parallel biases live on device 0 only (the psum adds them
    exactly once), embeddings replicated. Construction-only: no step
    ever compiles here."""
    hd = HIDDEN // HEADS
    for tp in (2, 4):
        eng = _engine(model, tp)
        for layer in eng.cache.pools:
            for pool in layer.values():
                assert pool.shape == (24, 4, HEADS, hd)  # logical
                shards = pool.addressable_shards
                assert len(shards) == tp
                assert all(s.data.shape == (24, 4, HEADS // tp, hd)
                           for s in shards)
        assert eng.cache.page_table.shape == (2, 12)  # host, unsharded
    eng = _engine(model, 2)
    p = eng._p
    qkv = next(v for k, v in p.items() if k.endswith("qkv_proj.weight"))
    assert qkv.addressable_shards[0].data.shape == (HIDDEN,
                                                    3 * HIDDEN // 2)
    fc2 = next(v for k, v in p.items() if k.endswith("fc2.bias"))
    assert fc2.shape == (2, HIDDEN)  # stacked: device 0 real, rest zero
    assert np.asarray(fc2.addressable_shards[1].data).max() == 0.0
    wte = next(v for k, v in p.items() if k.endswith("wte.weight"))
    assert wte.addressable_shards[0].data.shape == (VOCAB, HIDDEN)


# ------------------------------------------------------------- validation
def test_validation_errors_and_gauge_seeding(model):
    with pytest.raises(ValueError, match="tensor_parallel -1"):
        ServingEngine(model, ServingConfig(tensor_parallel=-1))
    with pytest.raises(ValueError, match="num_heads"):
        _engine(model, 3)  # 4 heads % 3 != 0
    with pytest.raises(ValueError, match="device"):
        _engine(model, 16)  # wider than the forced 8-device mesh
    # PT003/PT008 contract: the serving_tp_* gauges are visible at zero
    # before any audit, and tp_degree reflects the config from
    # construction
    from paddle_tpu.serving.metrics import ServingMetrics

    snap = ServingMetrics().snapshot()
    for k in ("serving_tp_degree", "serving_tp_collective_ops_per_step",
              "serving_tp_collective_bytes_per_token"):
        assert snap[k] == 0, k
    assert _engine(model, 2).metrics.snapshot()["serving_tp_degree"] == 2
