"""Mellum 2 (``text/mellum.py``): window layers beside full ones, in two
page groups with lifetimes by layer kind, and a softmax-routed expert layer
held whole, against the plain reference (``tests/refs/mellum_reference.py``)
at a size the CPU holds: hidden 64, 4 query heads over 2 KV heads of 16, 8
experts top-2, a window of 8, pages of 4, layers ``S S S F S S S F``.

Tolerances. Everything here is float32 on the CPU, where a product is a
float32 product whatever the precision asked: the program and the
reference differ by the ORDER of their sums (a flat lane-dense pool row
against heads, an online softmax against a whole one, experts gathered
and sorted against every expert over every token). At weights of 0.1 N the
logits are of size 3 and eight layers carry a relative 1e-6 to 2e-5 of
that: ``TOL``. A served token is compared by how far it lies below the
reference's best at its position (0 where it is the best; a near-tie may
fall either way inside ``TOL``).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis.hlocheck import run_step
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text.kimi_k2 import yarn_inv_freq
from paddle_tpu.text.mellum import (FULL, WINDOW, MellumConfig,
                                    MellumForCausalLM, _flash_prefill,
                                    rotary_tables)
from paddle_tpu.utils.flags import flag, set_flags

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "refs"))
import mellum_reference as ref  # noqa: E402

TOL = 2e-5
KINDS = [WINDOW, WINDOW, WINDOW, FULL] * 2
W, PAGE = 8, 4
TINY = dict(vocab_size=97, hidden_size=64, moe_intermediate_size=32,
            num_hidden_layers=8, layer_types=KINDS, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=8,
            num_experts_per_tok=2, sliding_window=W,
            max_position_embeddings=64, initializer_range=0.1)
SLOTS, FULL_PAGES, WINDOW_PAGES = 2, 40, 14
#: what a decoding slot may hold in the window group: ceil(8 / 4) + 2
BOUND = -(-W // PAGE) + 2


def build(seed=3, **over):
    """(model, config, its leaves) with every norm moved off its initial
    1, so that a path which dropped one would show."""
    paddle.seed(seed)
    cfg = MellumConfig(**dict(TINY, **over))
    model = MellumForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    params, _ = model.functional_state()
    for name, t in params.items():
        if "norm" in name:
            t._value = jnp.asarray(
                np.asarray(t._value) + rng.normal(0, 0.1, t._value.shape),
                t._value.dtype)
    return model, cfg, {k: v._value for k, v in params.items()}


def ids_of(rng, *shape):
    return rng.integers(1, TINY["vocab_size"], shape).astype(np.int32)


def engine(model, **over):
    cfg = dict(max_batch=SLOTS, num_pages=FULL_PAGES,
               group_pages={"window": WINDOW_PAGES}, page_size=PAGE,
               max_prompt_len=32, enable_prefix_caching=False)
    cfg.update(over)
    return ServingEngine(model, ServingConfig(**cfg))


def serve(eng, prompts, new_tokens, each_step=None):
    """Run the prompts to the end; the whole sequences, in order."""
    rids = [eng.add_request(p, new_tokens) for p in prompts]
    out = {}
    while len(out) < len(rids):
        eng.step()
        if each_step is not None:
            each_step(eng)
        out.update(eng.pop_finished())
    return [np.asarray(out[r]) for r in rids]


def gaps_under_the_reference(p, cfg, prompt, seq):
    """How far each served token lies below the reference's best."""
    logits = np.asarray(ref.forward_one(p, jnp.asarray(seq[:-1]), cfg))
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    return logits[at].max(-1) - logits[at, seq[len(prompt):]]


@pytest.fixture
def interpret(monkeypatch):
    """The grouped-head decode kernel and the grouped flash forward
    through the interpreter; the decode kernel's chunk at 8 tokens, two
    pages, so that its loop turns and a window starts it off chunk 0."""
    from paddle_tpu.kernels import paged_decode

    monkeypatch.setattr(paged_decode, "_GQA_CHUNK_TOKENS", 2 * PAGE)
    before = flag("FLAGS_ragged_interpret", False)
    yield lambda on: set_flags({"FLAGS_ragged_interpret": on})
    set_flags({"FLAGS_ragged_interpret": before})


# ------------------------------------------------------ model vs reference
@pytest.mark.parametrize("length", [40, 9, 3])
def test_full_forward_logits_match_the_reference(length):
    """The whole pass, two sequences: longer than five windows, one token
    past the window, and inside it."""
    model, cfg, p = build()
    ids = ids_of(np.random.default_rng(0), 2, length)
    got = model(paddle.to_tensor(ids))._value
    want = ref.forward(p, ids, cfg)
    assert got.dtype == jnp.float32 and got.shape == (2, length, 97)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 1.0


def test_a_window_layer_does_not_see_behind_its_window():
    """A token 9 back reaches a window-8 layer's output at no position
    but through the residual of the layers between: with every layer a
    window layer, the logits at position t depend on token t - 8 * layers
    at the furthest; with one full layer they depend on token 0."""
    rng = np.random.default_rng(1)
    ids = ids_of(rng, 1, 30)
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % 96 + 1
    last = {}
    for kinds in ([WINDOW] * 2, [WINDOW, FULL]):
        model, _, _ = build(num_hidden_layers=2, layer_types=kinds)
        a = model(paddle.to_tensor(ids))._value[0, -1]
        b = model(paddle.to_tensor(other))._value[0, -1]
        last[kinds[1]] = float(jnp.max(jnp.abs(a - b)))
    assert last[WINDOW] == 0.0 and last[FULL] > 1e-4


def test_rotary_tables_are_the_formulas_by_layer_kind():
    """A window layer: ``inv_freq_i = theta^(-2i/d)``, cos and sin as they
    are. A full layer: YaRN's blend of ``inv_freq`` and ``inv_freq / 16``
    over the ramp between the correction dimensions of 32 and 1 rotations
    in 8,192 positions, at EVERY length (position 5 too), and cos and sin
    multiplied by ``attention_factor``."""
    cfg = MellumConfig(num_hidden_layers=4,
                       layer_types=[WINDOW, WINDOW, WINDOW, FULL])
    d, pos = cfg.head_dim, jnp.asarray([[0, 5, 1023, 5000]])
    tables = rotary_tables(pos, cfg)
    for kind in (WINDOW, FULL):
        cos, sin = tables[kind]
        want_cos, want_sin = ref.rotary_table(pos[0], d,
                                              cfg.rope_parameters[kind])
        assert cos.shape == (1, 4, d) and cos.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(cos[0] - want_cos))) < 1e-6
        assert float(jnp.max(jnp.abs(sin[0] - want_sin))) < 1e-6
    factor = cfg.rope_parameters[FULL]["attention_factor"]
    assert factor == 1.2772588722239782
    assert np.allclose(tables[FULL][0][0, 0], factor)       # cos(0) * f
    assert np.allclose(tables[WINDOW][0][0, 0], 1.0)
    # the blend by hand: 64 frequencies; the fast ones (up to dimension
    # 18) as they are, the slow ones (from 35) divided by 16, a ramp
    # between; below 8,192 positions as above them
    theta = 500000.0 ** (-np.arange(0, d, 2) / d)
    inv = np.asarray(yarn_inv_freq(d, 500000.0, cfg.rope_parameters[FULL]))
    low = np.floor(d * np.log(8192 / (32 * 2 * np.pi))
                   / (2 * np.log(500000.0)))
    high = np.ceil(d * np.log(8192 / (1 * 2 * np.pi))
                   / (2 * np.log(500000.0)))
    assert (low, high) == (18, 35)
    assert np.allclose(inv[:19], theta[:19], rtol=1e-6)
    assert np.allclose(inv[35:], theta[35:] / 16, rtol=1e-6)
    mid = (26 - low) / (high - low)
    assert np.isclose(inv[26], theta[26] / 16 * mid + theta[26] * (1 - mid),
                      rtol=1e-6)
    ang = np.asarray(tables[FULL][1][0, 1, :64]) / factor    # sin(5 * inv)
    assert np.allclose(ang, np.sin(5 * inv), atol=1e-6)


# ----------------------------------------------------- through the engine
def _watch(bound_seen):
    def each_step(eng):
        eng.cache.check_invariants()
        for slot, req in eng.scheduler.running.items():
            if req.state == "running":
                bound_seen.append(eng.cache.window_pages(slot)["window"])
    return each_step


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("chunk_size", [0, 6], ids=["whole", "chunked"])
def test_engine_serves_the_reference_s_tokens(interpret, chunk_size, kernel):
    """Through ``ServingEngine``'s own add_request / step path: two slots
    interleaved with a third request behind them, prompts of 21, 13 and 5
    tokens and 30 more, so every context crosses the window several times
    (one starts inside it). Every served token is the reference's best at
    its position; a prompt prefilled six tokens a step comes to the same
    tokens; the programs compile once; after every step the cache's
    invariants hold and no decoding slot holds more than ``ceil(8 / 4) +
    2`` window pages; the window group's pages went back as the contexts
    moved on, and what stayed resident is under the one-lifetime cache."""
    interpret(kernel)
    model, cfg, p = build()
    eng = engine(model, chunk_size=chunk_size)
    assert eng._decode_pallas_eligible == kernel
    rng = np.random.default_rng(5)
    prompts = [ids_of(rng, n) for n in (21, 13, 5)]
    held = []
    seqs = serve(eng, prompts, 30, _watch(held))
    assert eng.compile_counts == {"prefill": 1 if chunk_size else 3,
                                  "decode": 1}
    distinct = set()
    for prompt, seq in zip(prompts, seqs):
        assert len(seq) == len(prompt) + 30
        distinct |= set(seq[len(prompt):].tolist())
        assert float(gaps_under_the_reference(p, cfg, prompt, seq).max()) \
            < TOL
    assert len(distinct) > 9
    assert held and max(held) <= BOUND
    snap = eng.metrics.snapshot()
    groups = eng.cache.stats()["groups"]
    released = snap["serving_kv_window_pages_released_total"]
    assert released == groups["window"]["window_pages_released"] > 20
    assert groups["full"]["pages_in_use"] == 0 \
        == groups["window"]["pages_in_use"]
    resident = snap["serving_kv_resident_page_layers_total"]
    whole = snap["serving_kv_one_lifetime_page_layers_total"]
    # 2 full layers keep everything, 6 window layers at most BOUND pages
    assert 0 < resident < 0.7 * whole
    # every expert layer counted its assignments: top-2 of all 8, all held
    assert snap["serving_moe_assignments_total"] \
        == snap["serving_moe_local_assignments_total"] > 0


def test_the_whole_and_the_chunked_prefill_serve_the_same_tokens(interpret):
    interpret(False)
    model, _, _ = build()
    rng = np.random.default_rng(5)
    prompts = [ids_of(rng, n) for n in (21, 13)]
    whole = serve(engine(model), prompts, 12)
    chunked = serve(engine(model, chunk_size=6), prompts, 12)
    for a, b in zip(whole, chunked):
        assert np.array_equal(a, b)


def test_a_freed_window_page_poisoned_and_reused_changes_nothing(interpret):
    """Every page the window group frees is filled with 1e4 in every
    window layer's pools the moment it is freed, and the allocator hands
    it straight to the other slot (last freed, first given). Both requests
    still serve the tokens of the undisturbed run: a freed page's column
    reads the null page, the kernel starts past it and the mask behind the
    window is exact; the new owner overwrites what it writes and masks the
    rest."""
    interpret(True)
    model, cfg, p = build()
    rng = np.random.default_rng(6)
    prompts = [ids_of(rng, n) for n in (19, 11)]
    want = serve(engine(model), prompts, 24)
    eng = engine(model)
    window_layers = eng.cache.groups[1].layers
    release, poisoned = eng.cache.release_behind, set()

    def poisoning(slot, next_pos):
        g = eng.cache.groups[1]
        before = list(g.pages.get(slot, ()))
        n = release(slot, next_pos)
        freed = before[:n]
        if freed:
            assert not set(freed) & set(g.pages[slot])
            idx = jnp.asarray(freed)
            pools = list(eng.cache.pools)
            for i in window_layers:
                pools[i] = {k: a.at[idx].set(1e4)
                            for k, a in pools[i].items()}
            eng.cache.pools = pools
            poisoned.update(freed)
        return n

    eng.cache.release_behind = poisoning
    reused = set()

    def each_step(eng):
        eng.cache.check_invariants()
        for pages in eng.cache.groups[1].pages.values():
            reused.update(set(pages) & poisoned)

    got = serve(eng, prompts, 24, each_step)
    assert len(poisoned) > 6 and len(reused) > 3
    for a, b, prompt in zip(got, want, prompts):
        assert np.array_equal(a, b)
        assert float(gaps_under_the_reference(p, cfg, prompt, a).max()) < TOL


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_a_preempted_request_finishes_with_the_undisturbed_tokens(mode):
    """Preempted in the middle, its context two windows long. Swap: the
    handle carries both groups' pages (the window group's from the column
    they stood at) and they go back into whatever pages both allocators
    give. Recompute: a prefill from position 0."""
    model, cfg, p = build()
    rng = np.random.default_rng(9)
    prompt, other = ids_of(rng, 14), ids_of(rng, 6)
    want, = serve(engine(model), [prompt], 16)
    want_other, = serve(engine(model), [other], 16)
    eng = engine(model, preemption_mode=mode)
    rid = eng.add_request(prompt, 16)
    for _ in range(7):
        eng.step()
    eng._drain("preempt")
    req = eng.request(rid)
    assert req.slot == 0 and 4 < len(req.generated) < 16
    g = eng.cache.groups[1]
    first, held = g.first[0], len(g.pages[0])
    assert first > 0 and held <= BOUND
    eng._preempt_one(req)
    if mode == "swap":
        assert req.swap.rest == ((held, first),)
        assert req.swap.n_pages == -(-(14 + len(req.generated)) // PAGE) \
            > held
    eng.cache.check_invariants()
    # the vacated slot goes to another request first
    rid2 = eng.add_request(other, 16)
    eng.scheduler.waiting.rotate(-1)
    out = {}
    while len(out) < 2:
        eng.step()
        eng.cache.check_invariants()
        out.update(eng.pop_finished())
    assert np.array_equal(np.asarray(out[rid]), want)
    assert np.array_equal(np.asarray(out[rid2]), want_other)
    assert float(gaps_under_the_reference(p, cfg, prompt, want).max()) < TOL


def test_exhaustion_of_the_window_group_preempts(interpret):
    """The full group has room for everyone; the window group for two
    decoding slots and no third prompt. The engine preempts (recompute)
    and still serves every request its undisturbed tokens."""
    interpret(False)
    model, _, _ = build()
    rng = np.random.default_rng(4)
    prompts = [ids_of(rng, n) for n in (20, 18, 16)]
    want = serve(engine(model, max_batch=3, num_pages=60,
                        group_pages={"window": 40}), prompts, 10)
    eng = engine(model, max_batch=3, num_pages=60,
                 group_pages={"window": 9})
    got = serve(eng, prompts, 10, lambda e: e.cache.check_invariants())
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    # 8 usable window pages: the third prompt's five wait for the others'
    assert eng.cache.stats()["groups"]["window"]["usable_pages"] == 8


@pytest.mark.parametrize("chunk_size", [0, 128], ids=["whole", "two_chunks"])
def test_a_long_prefill_runs_the_grouped_flash_forward(interpret,
                                                       monkeypatch,
                                                       chunk_size):
    """A prompt of 200 tokens in a bucket of 256 (128 when chunked), blocks
    of 128, a window of 40: from position 0 the prefill takes the grouped
    flash forward (interpreted), a chunk behind cached tokens the composite
    over the pool, by the program's own ``cond``; the served tokens are
    the reference's."""
    interpret(True)
    monkeypatch.setattr(fa, "_GROUPED_BLOCK", 128)
    over = dict(sliding_window=40, max_position_embeddings=256,
                num_hidden_layers=4, layer_types=KINDS[:4])
    model, cfg, p = build(**over)
    assert _flash_prefill(256, 16) == (True, True)
    assert _flash_prefill(64, 16)[0] is False
    eng = engine(model, max_batch=1, num_pages=70,
                 group_pages={"window": 70}, max_prompt_len=256,
                 chunk_size=chunk_size)
    prompt = ids_of(np.random.default_rng(2), 200)
    seq, = serve(eng, [prompt], 6, lambda e: e.cache.check_invariants())
    text = str(jax.make_jaxpr(eng._prefill_impl)(
        *eng._prefill_args(eng._programs[
            "prefill[128]" if chunk_size else "prefill[256]"], 0, 0,
            prompt[:100], 0)))
    assert "flash_fwd_grouped_window" in text and "cond" in text
    assert float(gaps_under_the_reference(p, cfg, prompt, seq).max()) < TOL
    # 200 tokens at a window of 40: all but its last pages went back
    assert eng.cache.stats()["groups"]["window"][
        "window_pages_released"] >= (200 - 40) // PAGE - 1


def test_a_prefill_computes_its_head_at_the_last_token_alone():
    """No ``[bucket, vocab]`` array in the prefill program: the engine
    names the one position whose logits it reads, and the norm and the
    head run over that row."""
    model, _, _ = build()
    eng = engine(model)
    prog = eng._programs["prefill[32]"]
    args = eng._prefill_args(prog, 0, 0, np.arange(1, 20, dtype=np.int32), 0)
    text = str(jax.make_jaxpr(eng._prefill_impl)(*args))
    assert "f32[1,1,97]" in text
    assert "32,97]" not in text
    # GPT's program, which takes no head_at, still has it
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(1)
    gpt = ServingEngine(GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64, dropout=0.0)), ServingConfig(
            max_batch=2, num_pages=16, page_size=4, max_prompt_len=32))
    args = gpt._prefill_args(gpt._programs["prefill[32]"], 0, 0,
                             np.arange(1, 20, dtype=np.int32), 0)
    assert "32,97]" in str(jax.make_jaxpr(gpt._prefill_impl)(*args))


def test_engine_counts_attention_pages_with_the_window(interpret,
                                                       monkeypatch):
    """``serving_attention_pages_{live,staged}_total`` over ALL layers by
    kind: a full layer's live pages are the context's, a window layer's
    those inside the window; a decode launch stages a full layer's live
    chunks of two pages and a window layer's from the first chunk that
    holds a position inside the window."""
    from paddle_tpu.kernels import ragged_paged_attention as rp

    interpret(True)
    model, _, _ = build()
    eng = engine(model)
    pps = eng.cache.cfg.pages_per_seq
    launches = []
    count = eng._count_attention_pages

    def spy(ctx, s, tokens=None, live_rows=None):
        launches.append((np.array(ctx, copy=True).reshape(-1), s,
                         s if tokens is None else tokens,
                         None if live_rows is None else live_rows.copy()))
        count(ctx, s, tokens, live_rows)

    monkeypatch.setattr(eng, "_count_attention_pages", spy)
    serve(eng, [ids_of(np.random.default_rng(9), 19)], 12)
    snap = eng.metrics.snapshot()
    n_full, n_win = KINDS.count(FULL), KINDS.count(WINDOW)
    want_live = want_staged = 0
    for ctx, s_, tokens, rows in launches:
        last = (ctx + tokens - 1) // PAGE
        first = np.maximum(ctx - W + 1, 0) // PAGE
        per_row = n_full * (last + 1) + n_win * (last - first + 1)
        want_live += int((per_row if rows is None else per_row[rows]).sum())
        kernel = 2 if s_ == 1 else None
        staged = lambda w: rp.pages_staged(  # noqa: E731
            ctx, s_, page_size=PAGE, pages_per_seq=pps, chunk_pages=kernel,
            query_tile=1, window=w)
        want_staged += int((n_full * staged(None) + n_win * staged(W)).sum())
    assert snap["serving_attention_pages_live_total"] == want_live
    assert snap["serving_attention_pages_staged_total"] == want_staged
    decode = [c for c, s_, _, _ in launches if s_ == 1]
    assert max(int(c.max()) for c in decode) > 3 * W


# ------------------------------------------------------------- the contract
def test_the_spec_states_two_page_groups():
    model, cfg, _ = build()
    spec = model.paged_cache_spec()
    full, window = spec.groups
    assert (full.name, full.layers, full.window) == ("full", (3, 7), None)
    assert (window.name, window.layers, window.window) \
        == ("window", (0, 1, 2, 4, 5, 6), W)
    assert [lf.name for lf in spec.leaves] == ["k_pool", "v_pool"]
    assert spec.leaves[0].shape == (2 * 16,)        # flat: KV heads x d
    assert spec.head_at_positions and spec.no_prefix_sharing
    assert spec.counters == ("moe_assignments_total",
                             "moe_local_assignments_total",
                             "moe_expert_slots_total",
                             "moe_expert_hits_total")
    eng = engine(model)
    shapes = [pl["k_pool"].shape for pl in eng.cache.pools]
    assert shapes[3] == shapes[7] == (FULL_PAGES, PAGE, 32)
    assert shapes[0] == shapes[6] == (WINDOW_PAGES, PAGE, 32)
    assert eng.cache.tables.shape == (2, SLOTS, 16)


@pytest.mark.parametrize("config, reason", [
    (dict(tensor_parallel=2), "page groups have no placement"),
    (dict(kv_dtype="int8"), "int8 pool's write and gather are GPT's"),
    (dict(spec=SpecConfig(method="ngram", depth=2)),
     "no windowed kernel path"),
    (dict(enable_prefix_caching=True),
     "freed behind the window of whoever holds it"),
    (dict(group_pages=None), "does not give the pages"),
])
def test_what_it_cannot_do_yet_refuses_at_construction(config, reason):
    model, _, _ = build()
    with pytest.raises(ValueError, match=reason):
        engine(model, **config)


def test_no_page_of_a_grouped_pool_crosses_the_wire():
    model, _, _ = build()
    eng = engine(model)
    with pytest.raises(ValueError, match="cannot cross the wire"):
        eng.cache.export_prefix_chain(np.arange(1, 20))


@pytest.mark.parametrize("step", ["engine_prefill_window",
                                  "engine_decode_window"])
def test_the_window_steps_audit_clean(step):
    """Zero collectives and host transfers; both groups' donated pools
    aliased (4 layers x k_pool and v_pool)."""
    report = run_step(step)
    assert report.collectives == () and report.host_transfers == ()
    assert report.donated_leaves == 8 == report.aliased_leaves
