"""Granite 4.0-H (Mamba-2 layers beside grouped-head attention layers;
recurrent state a slot beside pages a token) against its plain reference
(``tests/refs/granite_hybrid_reference.py``) at a tiny size on the CPU,
with seeded weights: two periods of ``m m a m``. Everything is float32
here, so a tolerance is round-off alone: 2e-6 on logits of order 1 (sums
of a few hundred float32 products in another order: the chunked scan
against the token scan, a paged gather against a dense product), where a
dropped decay, a state carried to the wrong slot or past a row's padding,
or a wrong multiplier shows at 1e-2 and up.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.kv_cache import (PagedCacheConfig, PagedKVCache,
                                         init_pools)
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text.granite_hybrid import (SSM_COUNTERS,
                                            GraniteHybridConfig,
                                            GraniteHybridForCausalLM)
from paddle_tpu.utils.flags import flag, set_flags

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "refs"))
import granite_hybrid_reference as ref  # noqa: E402

TOL = 2e-6
KINDS = ["mamba", "mamba", "attention", "mamba"] * 2
TINY = dict(vocab_size=96, hidden_size=32, num_hidden_layers=8,
            layer_types=KINDS, num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=48, mamba_n_heads=4, mamba_d_head=16,
            mamba_d_state=8, mamba_chunk_size=8, max_position_embeddings=96,
            initializer_range=0.25)
#: the published multipliers make a tied head of random weights repeat its
#: input; at 1 the served tokens vary and a wrong state changes them
PLAIN = dict(embedding_multiplier=1.0, logits_scaling=1.0,
             residual_multiplier=1.0)
PAGE, PPS, PAGES, SLOTS = 4, 24, 40, 3


def build(seed=3, **over):
    """(model, config, its leaves) with every norm, bias and skip moved off
    its initial 1 or 0, decay rates spread over [1, 16] and steps over
    [0.02, 1], so that a path which dropped one would show."""
    paddle.seed(seed)
    cfg = GraniteHybridConfig(**dict(TINY, **over))
    model = GraniteHybridForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    params, _ = model.functional_state()
    for name, t in params.items():
        v, new = t._value, None
        if name.endswith("A_log"):
            new = np.log(rng.uniform(1, 16, v.shape))
        elif name.endswith("dt_bias"):
            new = rng.uniform(-4, 0, v.shape)
        elif "norm" in name or "bias" in name or name.endswith(".D"):
            new = np.asarray(v) + rng.normal(0, 0.1, v.shape)
        if new is not None:
            t._value = jnp.asarray(new, v.dtype)
    return model, cfg, {k: v._value for k, v in params.items()}


def ids_of(rng, *shape):
    return rng.integers(0, TINY["vocab_size"], shape).astype(np.int32)


@pytest.fixture
def interpret(monkeypatch):
    """Both decode kernels (the state update and the attention layers'
    grouped-head kernel) through the interpreter; the attention kernel's
    chunk at 8 tokens, two pages, so that its loop turns."""
    from paddle_tpu.kernels import paged_decode

    monkeypatch.setattr(paged_decode, "_GQA_CHUNK_TOKENS", 2 * PAGE)
    before = flag("FLAGS_ragged_interpret", False)
    yield lambda on: set_flags({"FLAGS_ragged_interpret": on})
    set_flags({"FLAGS_ragged_interpret": before})


# ------------------------------------------------------ model vs reference
@pytest.mark.parametrize("length", [21, 8, 3])
def test_full_forward_logits_match_the_reference(length):
    """Lengths over, at and under the scan's chunk of 8 (21 is no multiple
    of it), under the published multipliers."""
    model, cfg, p = build()
    ids = ids_of(np.random.default_rng(0), 2, length)
    got = model(paddle.to_tensor(ids))._value
    want = ref.forward(p, jnp.asarray(ids), cfg)
    assert got.shape == want.shape == (2, length, cfg.vocab_size)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def fresh_pools(model, dirty=None):
    spec = model.paged_cache_spec()
    pools = init_pools(PagedCacheConfig(
        num_layers=spec.num_layers, leaves_by_layer=spec.leaves_by_layer,
        num_pages=PAGES, page_size=PAGE, max_batch=SLOTS,
        pages_per_seq=PPS, enable_prefix_caching=False))
    if dirty is not None:   # what a slot's last owner left behind
        rng = np.random.default_rng(dirty)
        pools = [{k: jnp.asarray(rng.normal(size=a.shape), a.dtype)
                  for k, a in pl.items()} for pl in pools]
    return pools


def paged_call(model, pools, table, ids, ctx, valid, slots):
    """One paged call as the engine makes it; returns (logits, pools)."""
    caches = [dict(pl, page_table=table,
                   ctx_lens=jnp.asarray(ctx, jnp.int32), valid=valid,
                   kv_limit=None, slots=slots) for pl in pools]
    logits, new = model(paddle.to_tensor(ids), caches=caches)
    keys = [list(pl) for pl in pools]
    return logits._value, [{k: c[k] for k in ks}
                           for c, ks in zip(new, keys)], new


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("chunks", [(13,), (8, 5), (3, 3, 7)],
                         ids=["whole", "two_chunks", "three_chunks"])
def test_prefill_then_decode_through_state_and_pages(interpret, kernel,
                                                     chunks):
    """A 13-token prompt prefilled whole or in chunks (each padded into a
    bucket of 16: the state has to stop at the chunk's last real token and
    the next chunk has to start from it), into slot 1 of a pool whose
    every leaf is dirty, then 6 decode steps beside a dead slot 0 and a
    dead slot 2: the logits of every real position are the reference's."""
    interpret(kernel)
    model, cfg, p = build()
    rng = np.random.default_rng(1)
    seq = ids_of(rng, 19)
    want = ref.forward(p, jnp.asarray(seq)[None], cfg)[0]
    pools = fresh_pools(model, dirty=7)
    table = np.zeros((SLOTS, PPS), np.int32)
    table[1, :5] = [3, 9, 4, 11, 6]
    table = jnp.asarray(table)
    at, slot = 0, jnp.asarray([1], jnp.int32)
    for n in chunks:
        padded = np.zeros((1, 16), np.int32)
        padded[0, :n] = seq[at:at + n]
        padded[0, n:] = 77                  # what stands in the padding
        logits, pools, new = paged_call(
            model, pools, table[1:2], padded, [at],
            (jnp.arange(16) < n)[None], slot)
        assert float(jnp.max(jnp.abs(logits[0, :n] - want[at:at + n]))) < TOL
        at += n
    state0 = [np.asarray(pl["ssm_state"][0]) for pl in pools
              if "ssm_state" in pl]
    live = jnp.asarray([False, True, False])
    for t in range(13, 19):
        ids = np.full((SLOTS, 1), 5, np.int32)
        ids[1, 0] = seq[t]
        logits, pools, new = paged_call(
            model, pools, table, ids, [0, t, 0], live[:, None], None)
        assert float(jnp.max(jnp.abs(logits[1, 0] - want[t]))) < TOL
        counts = sum(c["counters"] for c in new if "counters" in c)
        # 6 Mamba layers: one live slot each; the kernel moves that one,
        # the plain recurrence rewrites all three
        assert counts.tolist() == [6, 6 if kernel else 6 * SLOTS]
    # a dead slot's state is bit for bit what it was
    for before, pl in zip(state0, [pl for pl in pools if "ssm_state" in pl]):
        assert np.array_equal(before, np.asarray(pl["ssm_state"][0]))


def test_a_verify_shaped_call_has_no_state_path():
    model, cfg, _ = build()
    pools = fresh_pools(model)
    with pytest.raises(NotImplementedError, match="verify"):
        paged_call(model, pools, jnp.zeros((SLOTS, PPS), jnp.int32),
                   np.zeros((SLOTS, 3), np.int32), [0] * SLOTS,
                   jnp.ones((SLOTS, 3), bool), None)


# ------------------------------------------------------------ the engine
def serve(model, prompts, new_tokens, **config):
    """Serve ``prompts`` (a list of waves, each added when the one before
    has finished) through ``ServingEngine``; returns (engine, sequences)."""
    eng = ServingEngine(model, ServingConfig(**dict(dict(
        max_batch=SLOTS, num_pages=PAGES, page_size=PAGE, max_prompt_len=32,
        enable_prefix_caching=False), **config)))
    seqs = []
    for wave in prompts:
        rids = [eng.add_request(pr, new_tokens) for pr in wave]
        out = {}
        while len(out) < len(rids):
            eng.step()
            out.update(eng.pop_finished())
        seqs += [np.asarray(out[r]) for r in rids]
    return eng, seqs


def gaps_under_the_reference(p, cfg, prompt, seq):
    toks = seq[len(prompt):]
    logits = ref.forward(p, jnp.asarray(seq[:-1])[None], cfg)[0]
    at = len(prompt) - 1 + np.arange(len(toks))
    return jnp.max(logits[at], -1) - logits[at, toks]


_SERVED = {}


def served(chunk_size):
    """Two waves over three slots: the second wave's requests are seated in
    slots the first wave's used."""
    if chunk_size not in _SERVED:
        model, cfg, p = build(**PLAIN)
        rng = np.random.default_rng(5)
        waves = [[ids_of(rng, n) for n in (13, 7, 22)],
                 [ids_of(rng, n) for n in (5, 18)]]
        snap0 = ServingEngine(model, ServingConfig(
            max_batch=1, num_pages=8, page_size=PAGE, max_prompt_len=8,
            enable_prefix_caching=False)).metrics.snapshot()
        eng, seqs = serve(model, waves, 9, chunk_size=chunk_size)
        snap = eng.metrics.snapshot()
        counts = {k: v - snap0[k] for k, v in snap.items() if k in snap0}
        _SERVED[chunk_size] = (eng, seqs, waves[0] + waves[1], counts, cfg, p)
    return _SERVED[chunk_size]


@pytest.mark.parametrize("chunk_size", [0, 6])
def test_engine_serves_the_reference_s_tokens_and_counts(interpret,
                                                         chunk_size):
    """Through ``ServingEngine``'s own add_request / step path, prompts
    whose lengths are no multiple of the scan's chunk (8), the prefill
    chunk (6) or a bucket: every served token is the reference's best at
    its position (gap 0 in float32); a prompt prefilled six tokens a step
    comes to the same tokens as one prefilled whole; a slot seated after
    another request served as if it were new; the programs compile once;
    the counters and gauges add up."""
    interpret(True)
    eng, seqs, prompts, counts, cfg, p = served(chunk_size)
    assert eng.compile_counts == {"prefill": 1 if chunk_size else 3,
                                  "decode": 1}
    assert eng._decode_pallas_eligible
    distinct = set()
    for prompt, seq, whole in zip(prompts, seqs, served(0)[1]):
        assert np.array_equal(seq, whole)
        assert len(seq) == len(prompt) + 9
        distinct |= set(seq[len(prompt):].tolist())
        assert float(gaps_under_the_reference(p, cfg, prompt, seq).max()) \
            < TOL
    assert len(distinct) > 9      # no request repeats one token
    count = counts.__getitem__
    mamba = KINDS.count("mamba")
    # a prefill launch advances one slot a Mamba layer; a chunk that is
    # not final is never fetched, and its counters with it (ROADMAP D15)
    prefills = count("serving_prefills_total")
    live = count("serving_ssm_state_rows_live_total")
    assert live == mamba * (prefills + 5 * 8)
    assert count("serving_ssm_state_rows_moved_total") == live
    snap = eng.metrics.snapshot()
    # two attention layers x (k + v) x 2 heads x 8 x 4 bytes
    assert snap["serving_kv_bytes_per_token"] == 2 * 2 * 2 * 8 * 4 \
        == eng.cache.cfg.kv_bytes_per_token
    # six Mamba layers x (4 x 16 x 8 state + 3 x 80 rows) x 4 bytes
    assert snap["serving_state_bytes_per_slot"] \
        == 6 * (4 * 16 * 8 + 3 * 80) * 4 == eng.cache.cfg.state_bytes_per_slot
    eng.cache.check_invariants()
    st = eng.cache.stats()
    assert st["slots_live"] == 0 and st["state_bytes_per_slot"] == 18048
    # the attention layers' pages are counted for this model: the decode
    # launches through the kernel's live chunks, so under the table's
    # width for every row of every launch
    live = count("serving_attention_pages_live_total")
    staged = count("serving_attention_pages_staged_total")
    launches = prefills + count("serving_decode_steps")
    assert 0 < live <= staged < PPS * (
        prefills + SLOTS * count("serving_decode_steps"))
    assert staged >= launches


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernel", "composite"])
def test_engine_counts_the_attention_layers_pages(interpret, monkeypatch,
                                                  kernel):
    """``serving_attention_pages_{live,staged}_total`` for this model: every
    launch adds ``ceil((ctx + tokens) / page_size)`` over its real rows and
    what an attention layer copies out of a pool for all its rows, by the
    path the call takes: with the kernel a decode row stages its live
    chunks of two pages (a dead slot one chunk), without it the table's
    width; a prefill the table's width either way."""
    from paddle_tpu.kernels import ragged_paged_attention as rp

    interpret(kernel)
    model, cfg, _ = build(**PLAIN)
    eng = ServingEngine(model, ServingConfig(
        max_batch=SLOTS, num_pages=PAGES, page_size=PAGE, max_prompt_len=32,
        enable_prefix_caching=False))
    launches = []
    count = eng._count_attention_pages

    def spy(ctx, s, tokens=None, live_rows=None):
        launches.append((np.array(ctx, copy=True).reshape(-1), s,
                         s if tokens is None else tokens,
                         None if live_rows is None else live_rows.copy()))
        count(ctx, s, tokens, live_rows)

    monkeypatch.setattr(eng, "_count_attention_pages", spy)
    pre = eng.metrics.snapshot()
    rng = np.random.default_rng(9)
    rids = [eng.add_request(ids_of(rng, n), 7) for n in (19, 6)]
    out = {}
    while len(out) < len(rids):
        eng.step()
        out.update(eng.pop_finished())
    snap = eng.metrics.snapshot()
    live, staged = (snap[k] - pre[k] for k in (
        "serving_attention_pages_live_total",
        "serving_attention_pages_staged_total"))
    want_live = want_staged = 0
    for ctx, s_, tokens, rows in launches:
        per_row = -(-(ctx + tokens) // PAGE)
        want_live += int((per_row if rows is None else per_row[rows]).sum())
        want_staged += int(rp.pages_staged(
            ctx, s_, page_size=PAGE, pages_per_seq=PPS,
            chunk_pages=2 if kernel and s_ == 1 else None,
            query_tile=1).sum())
    assert {s_ for _, s_, _, _ in launches} == {1, 8, 32}
    assert (live, staged) == (want_live, want_staged)
    decode = [ctx for ctx, s_, _, _ in launches if s_ == 1]
    assert max(int(c.max()) for c in decode) > 2 * PAGE   # past one chunk
    if kernel:
        assert 0 < live <= staged < PPS * sum(len(c) for c, *_ in launches)


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_a_preempted_request_finishes_with_the_undisturbed_tokens(mode):
    """Swap: the slot's state rides in the handle with its pages and goes
    back into whatever slot the request is seated in next (here another
    one: a second request took the first's). Recompute: a prefill from
    position 0 rebuilds the state."""
    model, cfg, p = build(**PLAIN)
    rng = np.random.default_rng(9)
    prompt, other = ids_of(rng, 10), ids_of(rng, 6)
    _, (want, want_other) = serve(model, [[prompt], [other]], 8)
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=PAGES, page_size=PAGE, max_prompt_len=32,
        enable_prefix_caching=False, preemption_mode=mode))
    rid = eng.add_request(prompt, 8)
    for _ in range(4):
        eng.step()
    eng._drain("preempt")
    req = eng.request(rid)
    assert req.slot == 0 and 0 < len(req.generated) < 8
    eng._preempt_one(req)
    if mode == "swap":
        assert len(req.swap.state) == 2          # ssm_state, conv_state
        assert req.swap.state[0].shape == (6, 4, 16, 8)
        assert req.swap.nbytes > sum(a.nbytes for a in req.swap.arrays)
    # the vacated slot goes to another request first
    rid2 = eng.add_request(other, 8)
    eng.scheduler.waiting.rotate(-1)             # the newcomer ahead
    out = {}
    while len(out) < 2:
        eng.step()
        out.update(eng.pop_finished())
    assert np.array_equal(np.asarray(out[rid]), want)
    assert np.array_equal(np.asarray(out[rid2]), want_other)
    if mode == "swap":
        assert eng.cache.compile_counts == {
            "swap_gather": 1, "swap_scatter": 1, "cow_copy": 0,
            "state_gather": 1, "state_scatter": 1}
    eng.cache.check_invariants()


@pytest.mark.parametrize("config, reason", [
    (dict(enable_prefix_caching=True), "no snapshot of a slot's state"),
    (dict(tensor_parallel=2), "tensor_parallel"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(spec=SpecConfig(method="ngram", depth=2)), "taken back"),
    (dict(enable_prefix_caching=True, host_tier_bytes=1 << 20),
     "no snapshot of a slot's state"),
])
def test_what_a_recurrent_state_cannot_do_refuses_at_construction(config,
                                                                  reason):
    model, _, _ = build()
    base = dict(max_batch=2, num_pages=16, page_size=PAGE, max_prompt_len=16,
                enable_prefix_caching=False)
    with pytest.raises(ValueError, match=reason):
        ServingEngine(model, ServingConfig(**dict(base, **config)))


def test_no_page_of_a_stateful_pool_crosses_the_wire():
    """``serving/wire.py`` carries prefix pages between replicas; a pool
    that keeps a state a slot has no prefix to share, and says so."""
    model, _, _ = build()
    spec = model.paged_cache_spec()
    layout = dict(num_layers=spec.num_layers,
                  leaves_by_layer=spec.leaves_by_layer, num_pages=8,
                  page_size=PAGE, max_batch=2, pages_per_seq=4)
    with pytest.raises(ValueError, match="cannot share pages by prefix"):
        PagedKVCache(PagedCacheConfig(**layout))
    cache = PagedKVCache(PagedCacheConfig(**layout,
                                          enable_prefix_caching=False))
    with pytest.raises(ValueError, match="cannot cross the wire"):
        cache.export_prefix_chain([1, 2, 3, 4])


# ----------------------------------------- what the other models still are
def test_the_spec_states_leaves_by_layer_kind():
    model, cfg, _ = build()
    spec = model.paged_cache_spec()
    assert spec.counters == SSM_COUNTERS and spec.no_prefix_sharing
    by_kind = dict(zip(cfg.layer_types, spec.leaves_by_layer))
    assert [(lf.name, lf.shape, lf.per_slot) for lf in by_kind["mamba"]] == [
        ("ssm_state", (4, 16, 8), True), ("conv_state", (3, 80), True)]
    assert by_kind["mamba"][0].dtype == jnp.float32
    assert [(lf.name, lf.shape, lf.per_slot)
            for lf in by_kind["attention"]] == [
        ("k_pool", (16,), False), ("v_pool", (16,), False)]
    layout = PagedCacheConfig(
        num_layers=8, leaves_by_layer=spec.leaves_by_layer, num_pages=16,
        page_size=PAGE, max_batch=3, enable_prefix_caching=False)
    assert layout.pool_leaf_keys == ("k_pool", "v_pool")
    assert layout.slot_leaf_keys == ("ssm_state", "conv_state")
    pools = init_pools(layout)
    assert [sorted(pl) for pl in pools] == [
        ["conv_state", "ssm_state"] if k == "mamba"
        else ["k_pool", "v_pool"] for k in KINDS]
    assert pools[0]["ssm_state"].shape == (3, 4, 16, 8)
    assert pools[2]["k_pool"].shape == (16, PAGE, 16)    # 2 heads x 8


def test_gpt_and_kimi_state_and_compile_what_they_did():
    """A model of pages alone: one set of leaves for every layer, no
    per-slot leaf, no state mover, and a prefill program that never reads
    the slot it is handed (``jit`` prunes the operand: the compiled
    program is the one it was)."""
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.text.kimi_k2 import KimiK2Config, KimiK2ForCausalLM

    paddle.seed(1)
    gpt = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                   num_layers=2, num_heads=2,
                                   max_seq_len=32))
    kimi = KimiK2ForCausalLM(KimiK2Config(
        vocab_size=96, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, num_hidden_layers=3,
        num_attention_heads=4, n_routed_experts=16, num_experts_per_tok=4,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, max_position_embeddings=64))
    for model, keys in ((gpt, ("k_pool", "v_pool")), (kimi, ("kv_pool",))):
        spec = model.paged_cache_spec()
        assert spec.leaves_by_layer is None and not spec.no_prefix_sharing
        eng = ServingEngine(model, ServingConfig(
            max_batch=2, num_pages=16, page_size=4, max_prompt_len=16))
        c = eng.cache.cfg
        assert c.pool_leaf_keys == keys and c.slot_leaf_keys == ()
        assert c.state_bytes_per_slot == 0
        assert c.layer_leaves == (tuple(spec.leaves),) * spec.num_layers
        assert [tuple(pl) for pl in eng.cache.pools] \
            == [keys] * spec.num_layers
        assert set(eng.cache.guards) == {"swap_gather", "swap_scatter",
                                         "cow_copy"}
        assert not eng._slot_state
        args = eng._prefill_args(eng._programs["prefill[16]"], 1, 7,
                                 np.arange(5, dtype=np.int32), 0)
        assert len(args) == 9 and int(args[-2]) == 1
        assert args[-1] is eng._prev_toks
        jaxpr = jax.make_jaxpr(eng._prefill_impl)(*args).jaxpr
        # the model never reads the slot: only the write of the sampled
        # token into prev_toks does, behind the sample
        slot = jaxpr.invars[-2]
        sampled = max(i for i, eqn in enumerate(jaxpr.eqns)
                      if eqn.primitive.name == "argmax")
        assert all(i > sampled for i, eqn in enumerate(jaxpr.eqns)
                   if slot in eqn.invars)
        rid = eng.add_request(np.arange(1, 8, dtype=np.int32), 3)
        while rid not in eng.pop_finished():
            eng.step()
        assert eng.compile_counts == {"prefill": 1, "decode": 1}
        eng.cache.check_invariants()
