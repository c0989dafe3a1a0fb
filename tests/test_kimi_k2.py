"""Kimi-K2 (MLA over a latent paged cache, a dropless expert layer that
holds a share of the experts) against its plain reference
(``tests/refs/kimi_k2_reference.py``) at a tiny size on the CPU, with
seeded weights. Everything is float32 here, so a tolerance is round-off
alone: 2e-6 on logits of order 0.5 (sums of a few hundred float32
products in another order), where a dropped term, a wrong position or a
wrong scale shows at 1e-2 and up.
"""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models import dropless_moe as dm
from paddle_tpu.kernels import latent_paged_attention as lpa
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text import kimi_k2 as kk
from paddle_tpu.text.kimi_k2 import KimiK2Config, KimiK2ForCausalLM
from paddle_tpu.utils.flags import flag, set_flags

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "refs"))
import kimi_k2_reference as ref  # noqa: E402

TOL = 2e-6
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=48, num_hidden_layers=3,
            num_attention_heads=4, n_routed_experts=16,
            num_experts_per_tok=4, kv_lora_rank=32, q_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            max_position_embeddings=64)


def build(seed=3, **over):
    """(model, config, its leaves) with every norm and the router's
    correction bias moved off their initial 1 and 0, so that a path which
    dropped one would show."""
    paddle.seed(seed)
    cfg = KimiK2Config(**dict(TINY, **over))
    model = KimiK2ForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    params, _ = model.functional_state()
    for name, t in params.items():
        if "norm" in name or "bias" in name:
            t._value = t._value + jnp.asarray(
                rng.normal(0, 0.1, t._value.shape), t._value.dtype)
    return model, cfg, {k: v._value for k, v in params.items()}


def ids_of(rng, *shape):
    return rng.integers(0, TINY["vocab_size"], shape).astype(np.int32)


@pytest.fixture
def interpret():
    before = flag("FLAGS_ragged_interpret", False)
    yield lambda on: set_flags({"FLAGS_ragged_interpret": on})
    set_flags({"FLAGS_ragged_interpret": before})


# ------------------------------------------------------ model vs reference
@pytest.mark.parametrize("held", [None, (4, 8)])
def test_full_forward_logits_match_the_reference(held):
    model, cfg, p = build(held_experts=held)
    ids = ids_of(np.random.default_rng(0), 2, 20)
    got = model(paddle.to_tensor(ids))._value
    want = ref.forward(p, jnp.asarray(ids), cfg, held=held)
    assert got.shape == want.shape == (2, 20, cfg.vocab_size)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def paged_call(model, pools, table, ids, ctx, n_valid, limit=None):
    s = ids.shape[1]
    caches = [dict(pl, page_table=table,
                   ctx_lens=jnp.asarray([ctx], jnp.int32),
                   valid=(jnp.arange(s) < n_valid)[None], kv_limit=limit)
              for pl in pools]
    logits, new = model(paddle.to_tensor(ids), caches=caches)
    return logits._value[0], [{"kv_pool": c["kv_pool"]} for c in new], new


@pytest.mark.parametrize("kernel", [False, True])
def test_prefill_then_decode_through_the_latent_cache(kernel, interpret):
    """A prompt prefilled in two parts (the second behind a cached prefix,
    read back from the pool), then decoded token by token across a page
    boundary, gives the logits of the reference's one full pass; with the
    decode kernel (interpreted) as with the composite gather."""
    interpret(kernel)
    model, cfg, p = build(held_experts=(4, 8))
    ids = ids_of(np.random.default_rng(1), 1, 15)
    want = ref.forward(p, jnp.asarray(ids), cfg, held=(4, 8))[0]
    spec = model.paged_cache_spec()
    (leaf,) = spec.leaves
    assert leaf.name == "kv_pool" and leaf.shape == (128,)   # 40 padded
    page = 4
    pools = [{"kv_pool": jnp.zeros((10, page) + leaf.shape, leaf.dtype)}
             for _ in range(spec.num_layers)]
    table = jnp.asarray([[3, 5, 2, 7, 1, 0, 0, 0]], jnp.int32)
    pad = np.zeros((1, 8), np.int32)
    pad[0, :6] = ids[0, :6]
    got, pools, _ = paged_call(model, pools, table, pad, 0, 6, limit=16)
    assert float(jnp.max(jnp.abs(got[:6] - want[:6]))) < TOL
    pad[0, :5] = ids[0, 6:11]
    got, pools, _ = paged_call(model, pools, table, pad, 6, 5, limit=16)
    assert float(jnp.max(jnp.abs(got[:5] - want[6:11]))) < TOL
    for t in range(11, 15):      # position 12 opens the fourth page
        got, pools, _ = paged_call(model, pools, table, ids[:, t:t + 1], t, 1)
        assert float(jnp.max(jnp.abs(got[0] - want[t]))) < TOL
    # the null page took every dead write and nothing else did
    assert float(jnp.abs(pools[0]["kv_pool"][9]).max()) == 0.0


def test_absorbed_attention_is_expanded_attention(interpret):
    """q_nope W_kvb[K] against the latent row, and P c_kv W_kvb[V], is
    per-head attention over the expanded keys and values; composite and
    kernel alike."""
    rng = np.random.default_rng(2)
    b, h, r, dr, dn, dv, page, pps = 3, 4, 32, 8, 16, 16, 4, 16
    pool = jnp.asarray(rng.normal(size=(40, page, 128)), jnp.float32)
    pool = pool.at[..., r + dr:].set(0.0)
    ctx = np.array([0, 37, 62], np.int32)
    table = np.zeros((b, pps), np.int32)
    for i, c in enumerate(ctx):
        n = c // page + 1
        table[i, :n] = rng.permutation(np.arange(1, 40))[:n]
    w_kvb = jnp.asarray(rng.normal(size=(r, h, dn + dv)), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(b, 1, h, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, 1, h, dr)), jnp.float32)
    args = (pool, jnp.asarray(table), jnp.asarray(ctx))
    expanded = lpa.latent_prefill_attention(
        q_nope, q_rope, *args, w_kvb, 0.3, r, kv_limit=page * pps)[:, 0]
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_kvb[..., :dn])
    for kernel in (False, True):
        interpret(kernel)
        o_lat = lpa.latent_decode_attention(q_lat, q_rope[:, 0], *args, 0.3)
        absorbed = jnp.einsum("bhr,rhd->bhd", o_lat, w_kvb[..., dn:])
        # outputs of order 10 from unit-variance rows: 4e-6 of them
        assert float(jnp.max(jnp.abs(absorbed - expanded))) < 5e-5, kernel


# ---------------------------------------------------- positions and scale
def test_yarn_frequencies_and_softmax_scale_closed_form():
    cfg = KimiK2Config()
    rs = cfg.rope_scaling
    freq = np.asarray(kk.yarn_inv_freq(64, 50000.0, rs), np.float64)
    theta = 50000.0 ** (-np.arange(0, 64, 2) / 64)

    def dim_of(rotations):
        return 64 * math.log(4096 / (rotations * 2 * math.pi)) \
            / (2 * math.log(50000.0))

    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (8, 20)
    np.testing.assert_allclose(freq[:low + 1], theta[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(freq[high:], theta[high:] / 64, rtol=1e-6)
    i = 14                                   # halfway up the ramp
    np.testing.assert_allclose(
        freq[i], theta[i] * 0.5 + theta[i] / 64 * 0.5, rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert kk.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    np.testing.assert_allclose(
        freq, np.asarray(ref.yarn_inv_freq(64, 50000.0, rs)), rtol=1e-7)
    assert ref.softmax_scale(cfg) == pytest.approx(kk.softmax_scale(cfg))


def test_rope_rotates_interleaved_pairs():
    cfg = KimiK2Config(**TINY)
    x = jnp.zeros((1, 3, 8)).at[:, :, 2].set(1.0)     # the pair (2, 3)
    out = np.asarray(kk._rope(x, jnp.asarray([[0, 1, 5]]), cfg))
    f = float(kk.yarn_inv_freq(8, cfg.rope_theta, cfg.rope_scaling)[1])
    np.testing.assert_allclose(out[0, :, 2], np.cos(f * np.array([0, 1, 5])),
                               atol=1e-6)
    np.testing.assert_allclose(out[0, :, 3], np.sin(f * np.array([0, 1, 5])),
                               atol=1e-6)
    assert np.abs(np.delete(out, [2, 3], axis=-1)).max() == 0.0


# -------------------------------------------------------- the expert layer
def experts_of(rng, count, h=16, f=8):
    return (jnp.asarray(rng.normal(0, 0.3, (count, h, f)), jnp.float32),
            jnp.asarray(rng.normal(0, 0.3, (count, h, f)), jnp.float32),
            jnp.asarray(rng.normal(0, 0.3, (count, f, h)), jnp.float32))


def test_dropless_when_every_token_goes_to_one_held_expert():
    """40 tokens, all routed to expert 5 (held) and expert 9 (not): none is
    dropped whatever the imbalance, and the absent expert adds nothing."""
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    g, u, d = experts_of(rng, 3)
    experts = jnp.tile(jnp.asarray([[5, 9]], jnp.int32), (40, 1))
    weights = jnp.asarray(rng.uniform(0.2, 1.0, (40, 2)), jnp.float32)
    out, counters = dm.dropless_experts(y, weights, experts, g, u, d,
                                        held=(4, 3))
    want = weights[:, :1] * ref.gated_mlp(y, g[1], u[1], d[1])
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5
    assert float(jnp.min(jnp.abs(out).sum(-1))) > 0       # no row dropped
    assert counters.tolist() == [80, 40, 3, 1]


def test_padding_tokens_are_routed_nowhere_and_counted_nowhere():
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    g, u, d = experts_of(rng, 2)
    experts = jnp.asarray([[0, 1]] * 6, jnp.int32)
    weights = jnp.ones((6, 2), jnp.float32)
    valid = jnp.asarray([True, True, False, True, False, False])
    out, counters = dm.dropless_experts(y, weights, experts, g, u, d,
                                        held=(0, 2), valid=valid)
    assert float(jnp.abs(out[~np.asarray(valid)]).max()) == 0.0
    assert counters.tolist() == [6, 6, 2, 2]


def test_router_is_sigmoid_top_k_of_biased_scores_unbiased_weights():
    rng = np.random.default_rng(6)
    y = jnp.asarray(rng.normal(size=(7, 16)), jnp.float32)
    w_r = jnp.asarray(rng.normal(0, 0.5, (16, 12)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.5, (12,)), jnp.float32)
    w, idx = dm.route_sigmoid_topk(y, w_r, bias, 3, 2.827)
    sig = 1 / (1 + np.exp(-np.asarray(y, np.float64) @ np.asarray(w_r)))
    want_idx = np.argsort(-(sig + np.asarray(bias)), axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(want_idx))
    chosen = np.take_along_axis(sig, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        w, 2.827 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def test_all_shares_add_up_to_the_uncut_layer():
    """The shares of a 16-expert layer held 4 experts a chip, the shared
    expert counted once, add up to the uncut reference layer."""
    model, cfg, p = build()
    pre = "model.layers.1.mlp."
    y = jnp.asarray(np.random.default_rng(7).normal(size=(2, 9, 64)),
                    jnp.float32)
    whole = ref.moe(p, pre, y, cfg)
    total = jnp.zeros_like(whole)
    for first in range(0, 16, 4):
        share_cfg = KimiK2Config(**dict(TINY, held_experts=(first, 4)))
        paddle.seed(0)
        layer = kk.KimiK2MoE(share_cfg)
        layer.gate.weight._value = p[pre + "gate.weight"]
        layer.gate.e_score_correction_bias._value = \
            p[pre + "gate.e_score_correction_bias"]
        for n in ("gate_proj", "up_proj", "down_proj"):
            getattr(layer.experts, n)._value = \
                p[pre + "experts." + n][first:first + 4]
            getattr(layer.shared_experts, n).weight._value = \
                p[pre + f"shared_experts.{n}.weight"]
        out, _ = layer(y, shared=first == 0)
        total = total + out
        sliced = dict(p, **{pre + "experts." + n:
                            p[pre + "experts." + n][first:first + 4]
                            for n in ("gate_proj", "up_proj", "down_proj")})
        want = ref.moe(sliced, pre, y, cfg, held=(first, 4),
                       shared=first == 0)
        assert float(jnp.max(jnp.abs(out - want))) < TOL
    assert float(jnp.max(jnp.abs(total - whole))) < TOL


# ------------------------------------------------------------- the engine
def serve(model, prompts, n_new, **config):
    eng = ServingEngine(model, ServingConfig(
        max_batch=4, num_pages=40, page_size=4, max_prompt_len=16, **config))
    out = {}
    for group in prompts:
        rids = [eng.add_request(pr, n_new) for pr in group]
        while not all(r in out for r in rids):
            eng.step()
            out.update(eng.pop_finished())
    return eng, [np.asarray(out[r]) for r in sorted(out)]


_SERVED = {}


def served(chunk_size):
    """(the engine, its served sequences, the prompts, what the run added
    to each counter, the configuration, the model's leaves) of one run of
    the parity traffic under ``chunk_size``, made once a process."""
    if chunk_size not in _SERVED:
        model, cfg, p = build(held_experts=(4, 8))
        rng = np.random.default_rng(8)
        first = [ids_of(rng, n) for n in (5, 11, 16)]
        second = [np.concatenate([first[2][:12], ids_of(rng, 3)])]
        snap0 = ServingEngine(model, ServingConfig(
            max_batch=4, num_pages=8, page_size=4,
            max_prompt_len=16)).metrics.snapshot()
        eng, seqs = serve(model, [first, second], 9, chunk_size=chunk_size)
        snap = eng.metrics.snapshot()  # one registry a process: read now
        counts = {k: v - snap0[k] for k, v in snap.items() if k in snap0}
        _SERVED[chunk_size] = (eng, seqs, first + second, counts, cfg, p)
    return _SERVED[chunk_size]


@pytest.mark.parametrize("chunk_size", [0, 4])
def test_engine_serves_the_reference_s_tokens_and_counts(interpret,
                                                         chunk_size):
    """Through ``ServingEngine``'s own add_request / step path: every
    served token is the reference's best at its position (gap 0 in
    float32), a second request hits the first one's cached prefix, the
    programs compile once, and the counters add up. A prompt prefilled
    four tokens a step comes to the same tokens as one prefilled whole:
    the final chunk's output carries the model's counters behind its
    token like any launch's (it raised ``TypeError`` at that fetch,
    engine-fatal, before the prefill path was one)."""
    interpret(True)
    eng, seqs, prompts, counts, cfg, p = served(chunk_size)
    # chunks of 4 all pad into the bucket of 8; whole tails use both
    assert eng.compile_counts == {"prefill": 1 if chunk_size else 2,
                                  "decode": 1}
    assert eng._decode_pallas_eligible
    for prompt, seq, whole in zip(prompts, seqs, served(0)[1]):
        assert np.array_equal(seq, whole)
        toks = seq[len(prompt):]
        assert len(toks) == 9
        logits = ref.forward(p, jnp.asarray(seq[:-1])[None], cfg,
                             held=(4, 8))[0]
        at = len(prompt) - 1 + np.arange(9)
        gap = jnp.max(logits[at], -1) - logits[at, toks]
        assert float(gap.max()) < TOL
    count = counts.__getitem__
    assert count("serving_prefix_tokens_saved") == 12
    assert count("serving_prefill_tokens_total") == 5 + 11 + 16 + 3
    # tokens counted: the uncached tails, and 8 decoded a request. A
    # chunk that is not final is never fetched, and its counters with it
    # (ROADMAP D15): of a chunked tail only the last chunk is counted
    tails = (1 + 3 + 4 + 3) if chunk_size else (5 + 11 + 16 + 3)
    tokens = tails + 4 * 8
    moe_layers = 2
    assert count("serving_moe_assignments_total") == tokens * 4 * moe_layers
    launches = count("serving_prefills_total") + count("serving_decode_steps")
    assert count("serving_moe_expert_slots_total") == 8 * moe_layers * launches
    assert 0 < count("serving_moe_expert_hits_total") \
        <= count("serving_moe_expert_slots_total")
    assert 0 < count("serving_moe_local_assignments_total") \
        < count("serving_moe_assignments_total")
    # six... the pool's own figure: 3 layers x 128 padded values x 4 bytes
    assert eng.metrics.snapshot()["serving_kv_bytes_per_token"] \
        == 3 * 128 * 4


def test_swap_preemption_round_trips_the_latent_pool():
    """The movers thread the model's one leaf: a request swapped out and
    back in finishes with the tokens it has without preemption."""
    model, cfg, p = build()
    rng = np.random.default_rng(9)
    prompt = ids_of(rng, 10)
    _, (want,) = serve(model, [[prompt]], 6)
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=40, page_size=4, max_prompt_len=16,
        preemption_mode="swap"))
    rid = eng.add_request(prompt, 6)
    eng.step()
    eng.step()
    eng._drain("preempt")
    eng._preempt_one(eng.scheduler.running[eng.request(rid).slot])
    assert eng.request(rid).swap is not None
    assert eng.request(rid).swap.v is None        # one leaf a layer
    out = {}
    while rid not in out:
        eng.step()
        out.update(eng.pop_finished())
    assert np.array_equal(np.asarray(out[rid]), want)
    assert eng.cache.compile_counts["swap_gather"] == 1


@pytest.mark.parametrize("config, reason", [
    (dict(tensor_parallel=2), "tensor_parallel"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(spec=SpecConfig(method="ngram", depth=2)), "speculative"),
])
def test_what_the_latent_model_cannot_do_refuses_at_construction(config,
                                                                 reason):
    model, _, _ = build()
    with pytest.raises(ValueError, match=reason):
        ServingEngine(model, ServingConfig(max_batch=2, num_pages=16,
                                           page_size=4, max_prompt_len=16,
                                           **config))


# ------------------------------------------- GPT under the same contract
def test_gpt_states_its_leaves_and_compiles_what_it_compiled():
    """D4: GPT states ``{k_pool, v_pool}`` of ``[heads, head_dim]`` (and
    the int8 scales), the engine builds its pool from that alone, and the
    programs and their counts are what they were."""
    from paddle_tpu.serving.kv_cache import PagedCacheConfig
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(1)
    gpt = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                   num_layers=2, num_heads=4,
                                   max_seq_len=32))
    spec = gpt.paged_cache_spec()
    assert [(lf.name, lf.shape, lf.per_page) for lf in spec.leaves] == [
        ("k_pool", (4, 8), False), ("v_pool", (4, 8), False)]
    q8 = gpt.paged_cache_spec(kv_dtype="int8").leaves
    assert [(lf.name, lf.shape, lf.per_page) for lf in q8[2:]] == [
        ("k_scale", (4,), True), ("v_scale", (4,), True)]
    eng = ServingEngine(gpt, ServingConfig(max_batch=2, num_pages=16,
                                           page_size=4, max_prompt_len=8))
    assert eng.cache.cfg.pool_leaf_keys == ("k_pool", "v_pool")
    assert eng.cache.pools[0]["k_pool"].shape == (16, 4, 4, 8)
    # the config by its two sizes gives the same pool (older callers)
    old = PagedCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                           num_pages=16, page_size=4)
    assert old.layer_leaves == eng.cache.cfg.layer_leaves
    assert old.kv_bytes_per_token == eng.cache.cfg.kv_bytes_per_token \
        == 2 * 2 * 4 * 8 * 4
    rid = eng.add_request(np.arange(1, 7), 5)
    out = {}
    while rid not in out:
        eng.step()
        out.update(eng.pop_finished())
    assert eng.compile_counts == {"prefill": 1, "decode": 1}
    # no counters behind the tokens: the decode's output is [max_batch]
    assert eng._prev_toks.shape == (2,)
    want = gpt.generate(paddle.to_tensor(np.arange(1, 7)[None]),
                        max_new_tokens=5)
    assert np.array_equal(np.asarray(out[rid])[6:],
                          np.asarray(want._value)[0, 6:])
