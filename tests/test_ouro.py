"""Ouro (``text/ouro.py``): all layers run ``total_ut_steps`` times a token,
each loop with its own keys and values in the same pages, the loop one loop
in the step programs, against the plain reference
(``tests/refs/ouro_reference.py``) at a size the CPU holds: hidden 64, 4
heads of 16 (group 1, as published), an MLP of 96, 3 layers run 3 times,
pages of 4.

Tolerances. Everything here is float32 on the CPU, where a product is a
float32 product whatever the precision asked: the program and the
reference differ by the ORDER of their sums (a flat lane-dense pool row
against heads, an online softmax against a whole one). At weights of 0.1 N
the logits are of size 3 and nine layer applications carry a relative
1e-6 to 1e-5 of that: ``TOL``. A served token is compared by how far it
lies below the reference's best at its position (0 where it is the best; a
near-tie may fall either way inside ``TOL``).
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
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text import ouro
from paddle_tpu.text.ouro import OuroConfig, OuroForCausalLM
from paddle_tpu.utils.flags import flag, set_flags

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "refs"))
import ouro_reference as ref  # noqa: E402

TOL = 2e-5
PAGE, LOOPS, LAYERS = 4, 3, 3
TINY = dict(vocab_size=97, hidden_size=64, intermediate_size=96,
            num_hidden_layers=LAYERS, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, total_ut_steps=LOOPS,
            max_position_embeddings=64, initializer_range=0.1)
SLOTS, PAGES = 2, 40


def build(seed=3, **over):
    """(model, config, its leaves) with every norm and the gate's bias
    moved off their initial values, so that a path which dropped one would
    show."""
    paddle.seed(seed)
    cfg = OuroConfig(**dict(TINY, **over))
    model = OuroForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    params, _ = model.functional_state()
    for name, t in params.items():
        if "norm" in name or "bias" in name:
            t._value = jnp.asarray(
                np.asarray(t._value) + rng.normal(0, 0.1, t._value.shape),
                t._value.dtype)
    return model, cfg, {k: v._value for k, v in params.items()}


def ids_of(rng, *shape):
    return rng.integers(1, TINY["vocab_size"], shape).astype(np.int32)


def engine(model, **over):
    cfg = dict(max_batch=SLOTS, num_pages=PAGES, page_size=PAGE,
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


def gaps_under_the_reference(p, cfg, prompt, seq, **fault):
    """How far each served token lies below the reference's best."""
    logits = np.asarray(ref.forward_one(p, jnp.asarray(seq[:-1]), cfg,
                                        **fault))
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    return logits[at].max(-1) - logits[at, seq[len(prompt):]]


@pytest.fixture
def interpret(monkeypatch):
    """The looped decode kernel and the grouped flash forward through the
    interpreter; the decode kernel's chunk at 8 tokens, two pages, so that
    its loop turns."""
    from paddle_tpu.kernels import paged_decode

    monkeypatch.setattr(paged_decode, "_GQA_CHUNK_TOKENS", 2 * PAGE)
    before = flag("FLAGS_ragged_interpret", False)
    yield lambda on: set_flags({"FLAGS_ragged_interpret": on})
    set_flags({"FLAGS_ragged_interpret": before})


# ------------------------------------------------------ model vs reference
@pytest.mark.parametrize("length", [23, 5, 1])
def test_whole_pass_logits_and_exits_match_the_reference(length):
    """The whole pass, two sequences: its logits, and the exit distribution
    over the loops at every position (the gate, a linear map with a bias,
    after every loop's final norm), which sums to 1."""
    model, cfg, p = build()
    ids = ids_of(np.random.default_rng(0), 2, length)
    got, exits = model(paddle.to_tensor(ids), with_exits=True)
    want, want_exits = ref.forward(p, ids, cfg, with_exits=True)
    assert got._value.dtype == jnp.float32
    assert got._value.shape == (2, length, 97)
    assert exits._value.shape == (2, LOOPS, length)
    assert float(jnp.max(jnp.abs(got._value - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(exits._value - want_exits))) < 1e-6
    assert np.allclose(np.asarray(exits._value).sum(1), 1.0, atol=1e-6)
    # the gate is not constant: its bias and weight reach every loop
    assert float(np.ptp(np.asarray(want_exits))) > 0.01


def test_every_loop_counts():
    """The logits of three loops are not those of two, nor of three loops
    that skip the final norm between them: the reference and the program
    both see the loop count."""
    model, cfg, p = build()
    ids = ids_of(np.random.default_rng(1), 1, 12)
    three = ref.forward(p, ids, cfg)
    two = ref.forward(p, ids, OuroConfig(**dict(TINY, total_ut_steps=2)))
    assert float(jnp.max(jnp.abs(three - two))) > 0.1
    model2, _, _ = build(total_ut_steps=2)
    got = model2(paddle.to_tensor(ids))._value
    assert float(jnp.max(jnp.abs(got - two))) < TOL


# ----------------------------------------------------- through the engine
@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("chunk_size", [0, 6], ids=["whole", "chunked"])
def test_engine_serves_the_reference_s_tokens(interpret, chunk_size, kernel):
    """Through ``ServingEngine``'s own add_request / step path: two slots
    interleaved with a third request behind them, prompts of 21, 13 and 5
    tokens and 20 more. Every served token is the reference's best at its
    position; a prompt prefilled six tokens a step (its tails read loop
    ``t``'s columns of the pool behind the cached tokens) comes to the same
    tokens; the programs compile once; the cache's invariants hold."""
    interpret(kernel)
    model, cfg, p = build()
    eng = engine(model, chunk_size=chunk_size)
    assert eng._decode_pallas_eligible == kernel
    rng = np.random.default_rng(5)
    prompts = [ids_of(rng, n) for n in (21, 13, 5)]
    seqs = serve(eng, prompts, 20, lambda e: e.cache.check_invariants())
    assert eng.compile_counts == {"prefill": 1 if chunk_size else 3,
                                  "decode": 1}
    distinct = set()
    for prompt, seq in zip(prompts, seqs):
        assert len(seq) == len(prompt) + 20
        distinct |= set(seq[len(prompt):].tolist())
        assert float(gaps_under_the_reference(p, cfg, prompt, seq).max()) \
            < TOL
    assert len(distinct) > 9
    assert eng.cache.stats()["pages_in_use"] == 0


def test_the_whole_and_the_chunked_prefill_serve_the_same_tokens(interpret):
    interpret(False)
    model, _, _ = build()
    rng = np.random.default_rng(5)
    prompts = [ids_of(rng, n) for n in (21, 13)]
    whole = serve(engine(model), prompts, 12)
    chunked = serve(engine(model, chunk_size=6), prompts, 12)
    for a, b in zip(whole, chunked):
        assert np.array_equal(a, b)


def test_a_prefix_hit_serves_the_cold_tokens(interpret):
    """A page holds every loop's keys of its tokens, and those depend on
    the prefix alone: a second request that shares 16 tokens (four pages)
    with the first takes them from the prefix cache, prefills its tail
    behind them in every loop, and serves the tokens of a cold run."""
    interpret(True)
    model, cfg, p = build()
    rng = np.random.default_rng(8)
    shared = ids_of(rng, 16)
    prompts = [np.concatenate([shared, ids_of(rng, n)]) for n in (5, 7)]
    cold = [serve(engine(model), [q], 10)[0] for q in prompts]
    eng = engine(model, enable_prefix_caching=True)
    first, = serve(eng, prompts[:1], 10)
    rid = eng.add_request(prompts[1], 10)
    eng.step()
    assert eng.request(rid).cached_tokens == 16
    out = {}
    while not out:
        eng.step()
        out.update(eng.pop_finished())
    assert eng.metrics.snapshot()["serving_prefix_tokens_saved"] == 16
    assert np.array_equal(first, cold[0])
    assert np.array_equal(np.asarray(out[rid]), cold[1])
    assert float(gaps_under_the_reference(p, cfg, prompts[1],
                                          cold[1]).max()) < TOL


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_a_preempted_request_finishes_with_the_undisturbed_tokens(mode):
    """Preempted in the middle. Swap: the handle carries its pages, every
    loop's columns of them, and they go back into whatever pages the
    allocator gives. Recompute: a prefill from position 0."""
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
    assert 4 < len(req.generated) < 16
    eng._preempt_one(req)
    if mode == "swap":
        assert req.swap.n_pages == -(-(14 + len(req.generated)) // PAGE)
    eng.cache.check_invariants()
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


def test_loops_fed_loop_one_s_keys_fail_the_comparison(monkeypatch):
    """The comparison sees the per-loop caches: a program whose every loop
    reads the FIRST loop's columns of the pool (its own keys still written
    to its own columns) serves tokens that the reference does not put
    first; the sound program's are the reference's, and the reference
    with the fault of cache sharing across loops does not put them
    first."""
    model, cfg, p = build()
    prompt = ids_of(np.random.default_rng(11), 9)
    sound, = serve(engine(model), [prompt], 16)
    composite = pa._grouped_composite

    def first_loop(q, k_pool, v_pool, table, ctx, scale, window=None,
                   loop=None, loops=1):
        # every loop reads the first loop's columns of the pool
        return composite(q, k_pool, v_pool, table, ctx, scale, window,
                         None if loop is None else 0, loops)

    monkeypatch.setattr(pa, "_grouped_composite", first_loop)
    faulty, = serve(engine(model), [prompt], 16)
    assert not np.array_equal(faulty, sound)
    assert float(gaps_under_the_reference(p, cfg, prompt, sound).max()) \
        < TOL
    assert float(gaps_under_the_reference(p, cfg, prompt, faulty).max()) \
        > 1e-3
    wrong = gaps_under_the_reference(p, cfg, prompt, sound,
                                     loop_zero_keys=True)
    assert float(wrong.max()) > 1e-3


def test_each_step_program_holds_each_layer_once(interpret):
    """The loops are one loop in the program: the decode program traces
    the looped decode kernel once a layer (3, not 9), and the prefill
    program each layer's flash forward once, inside one loop."""
    interpret(True)
    model, _, _ = build()
    eng = engine(model)
    decode = str(jax.make_jaxpr(eng._decode_impl)(*eng._decode_args()))
    assert decode.count("name=gqa_decode_attention_loop") == LAYERS
    assert "scan[" in decode or "while[" in decode
    prog = eng._programs["prefill[32]"]
    args = eng._prefill_args(prog, 0, 0, np.arange(1, 20, dtype=np.int32), 0)
    prefill = str(jax.make_jaxpr(eng._prefill_impl)(*args))
    # the logits at the last real token alone
    assert "f32[1,1,97]" in prefill and "32,97]" not in prefill


def test_a_long_prefill_runs_the_grouped_flash_forward(interpret,
                                                       monkeypatch):
    """A prompt of 200 tokens in a bucket of 256, blocks of 128: from
    position 0 every loop's prefill takes the grouped flash forward
    (interpreted) over its own q, k, v at group 1, by the program's own
    ``cond``; the served tokens are the reference's."""
    interpret(True)
    monkeypatch.setattr(fa, "_GROUPED_BLOCK", 128)
    model, cfg, p = build(max_position_embeddings=256)
    eng = engine(model, max_batch=1, num_pages=70, max_prompt_len=256)
    prompt = ids_of(np.random.default_rng(2), 200)
    seq, = serve(eng, [prompt], 6, lambda e: e.cache.check_invariants())
    text = str(jax.make_jaxpr(eng._prefill_impl)(
        *eng._prefill_args(eng._programs["prefill[256]"], 0, 0,
                           prompt[:100], 0)))
    assert "name=flash_fwd_grouped" in text and "cond" in text
    assert float(gaps_under_the_reference(p, cfg, prompt, seq).max()) < TOL


def test_engine_counts_pages_over_every_loop_and_layer(interpret,
                                                        monkeypatch):
    """``serving_attention_pages_{live,staged}_total`` count every (loop,
    layer): the context's pages and the decode kernel's live chunks of two
    pages, times 9. ``serving_kv_pages_{in_use,usable}_total``: at each
    decode launch the pages the live slots map and the pool's usable
    ones."""
    from paddle_tpu.kernels import ragged_paged_attention as rp

    interpret(True)
    model, _, _ = build()
    eng = engine(model)
    pps = eng.cache.cfg.pages_per_seq
    launches, held = [], []
    count = eng._count_attention_pages

    def spy(ctx, s, tokens=None, live_rows=None):
        launches.append((np.array(ctx, copy=True).reshape(-1), s,
                         s if tokens is None else tokens,
                         None if live_rows is None else live_rows.copy()))
        if s == 1:
            held.append(eng.cache.pages_held())
        count(ctx, s, tokens, live_rows)

    monkeypatch.setattr(eng, "_count_attention_pages", spy)
    serve(eng, [ids_of(np.random.default_rng(9), 19)], 12)
    snap = eng.metrics.snapshot()
    passes = LOOPS * LAYERS
    want_live = want_staged = 0
    for ctx, s_, tokens, rows in launches:
        per_row = passes * ((ctx + tokens - 1) // PAGE + 1)
        want_live += int((per_row if rows is None else per_row[rows]).sum())
        staged = rp.pages_staged(ctx, s_, page_size=PAGE, pages_per_seq=pps,
                                 chunk_pages=2 if s_ == 1 else None,
                                 query_tile=1)
        want_staged += int(passes * staged.sum())
    assert snap["serving_attention_pages_live_total"] == want_live
    assert snap["serving_attention_pages_staged_total"] == want_staged
    assert snap["serving_kv_pages_in_use_total"] \
        == sum(h[0] for h in held) > 0
    assert snap["serving_kv_pages_usable_total"] \
        == len(held) * (PAGES - 1)


# ------------------------------------------- the query and key projections
def _matmul_heads(y, w, d):
    """The query and key projections as ``y @ w``, the weight the right
    operand: the form whose weight the TPU compiler relayouts before the
    loop."""
    b, s, _ = y.shape
    return jnp.matmul(y, w).reshape(b, s, -1, d)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_the_weight_led_projections_serve_the_matmul_s_tokens(
        interpret, monkeypatch, kernel):
    """The query and key projections with the weight as the product's left
    operand (``ouro._heads_of``) against ``y @ w``: through the engine's
    prefill (whole and behind cached tokens: chunks of 6) and decode, in
    float32, the same tokens and the same pools, bit for bit."""
    interpret(kernel)
    rng = np.random.default_rng(5)
    prompts = [ids_of(rng, n) for n in (21, 13, 5)]

    def run():
        model, _, _ = build()
        eng = engine(model, chunk_size=6)
        seqs = serve(eng, prompts, 12)
        return seqs + [np.asarray(pl[k]) for pl in eng.cache.pools
                       for k in ("k_pool", "v_pool")]

    got = run()
    monkeypatch.setattr(ouro, "_heads_of", _matmul_heads)
    for a, b in zip(got, run(), strict=True):
        assert np.array_equal(a, b)


def test_a_published_checkpoint_loads_unchanged():
    """A checkpoint in the published ``[in, out]`` orientation (numpy, as a
    loader reads it; head_dim 8, so that the projections are not square)
    loads into the model as it is: the model's state is the checkpoint's,
    name for name, shape for shape, value for value, and an engine over it
    serves the tokens of the model the checkpoint came from."""
    src, _, _ = build(seed=3, head_dim=8)
    ckpt = {k: v.numpy() for k, v in src.state_dict().items()}
    assert ckpt["model.layers.0.self_attn.q_proj.weight"].shape == (64, 32)
    assert ckpt["model.layers.0.self_attn.k_proj.weight"].shape == (64, 32)
    prompt = ids_of(np.random.default_rng(4), 11)
    want, = serve(engine(src), [prompt], 10)
    dst, _, _ = build(seed=4, head_dim=8)
    assert dst.set_state_dict(ckpt) == ([], [])
    state = dst.state_dict()
    assert set(state) == set(ckpt)
    for k, v in state.items():
        assert v.numpy().shape == ckpt[k].shape
        assert np.array_equal(v.numpy(), ckpt[k])
    got, = serve(engine(dst), [prompt], 10)
    assert np.array_equal(got, want)


# ------------------------------------------------------------- the contract
def test_the_spec_states_one_group_of_every_loop_side_by_side():
    model, cfg, _ = build()
    spec = model.paged_cache_spec()
    assert spec.groups == () and spec.head_at_positions
    assert not spec.no_prefix_sharing and spec.no_wire
    assert [lf.name for lf in spec.leaves] == ["k_pool", "v_pool"]
    assert spec.leaves[0].shape == (LOOPS * 4 * 16,)
    eng = engine(model)
    assert [pl["k_pool"].shape for pl in eng.cache.pools] \
        == [(PAGES, PAGE, LOOPS * 64)] * LAYERS
    # a token: every layer's keys and values for every loop, float32
    assert eng.cache.cfg.kv_bytes_per_token == LAYERS * LOOPS * 2 * 64 * 4
    assert eng.metrics.snapshot()["serving_kv_bytes_per_token"] \
        == LAYERS * LOOPS * 2 * 64 * 4


@pytest.mark.parametrize("config, reason", [
    (dict(tensor_parallel=2), "every loop's heads side by side"),
    (dict(kv_dtype="int8"), "int8 pool's write and gather are GPT's"),
    (dict(spec=SpecConfig(method="ngram", depth=2)), "no looped kernel"),
])
def test_what_it_cannot_do_yet_refuses_at_construction(config, reason):
    model, _, _ = build()
    with pytest.raises(ValueError, match=reason):
        engine(model, **config)


def test_an_early_exit_threshold_below_one_refuses():
    model, _, _ = build(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="leaves early has no keys"):
        engine(model)


def test_no_page_crosses_the_wire():
    from paddle_tpu.serving.channel import Transport
    from paddle_tpu.serving.fleet import FleetConfig, FleetRouter

    model, _, _ = build()
    with pytest.raises(ValueError, match="every loop's keys in one flat"):
        FleetRouter(model, FleetConfig(
            num_replicas=2, transport=Transport(), fetch_pages=True,
            engine=ServingConfig(max_batch=SLOTS, num_pages=PAGES,
                                 page_size=PAGE, max_prompt_len=32,
                                 host_tier_bytes=1 << 20)))


@pytest.mark.parametrize("step", ["engine_prefill_loop",
                                  "engine_decode_loop"])
def test_the_loop_steps_audit_clean(step):
    """Zero collectives and host transfers; every layer's donated pools
    aliased through the program's loop (2 layers x k_pool and v_pool)."""
    report = run_step(step)
    assert report.collectives == () and report.host_transfers == ()
    assert report.donated_leaves == 2 * 2 == report.aliased_leaves
