"""Brumby (``text/brumby.py``: every layer gated power retention of degree
2, a fixed float32 state a slot and layer and no page at all) against its
plain reference (``tests/refs/brumby_reference.py``, the retention in
attention form) at a size the CPU holds: hidden 64, head size 16 (so ``D``
= 136), group sizes 1 and 5, 2 layers.

Tolerances. Everything here is float32 on the CPU, where a product is a
float32 product whatever the precision asked: the program's forms and the
reference differ by the ORDER of their sums (a state of ``phi(k) v^T``
summed token by token or a chunk at a time, against a quadratic sum of
``(q . k)^2`` weights). The logits are of size 3. The chunked form reads
1e-5 off the reference: ``TOL``. The state form (the decode) takes ``(q .
k)^2`` as ``phi(q) . phi(k)``, a sum of ``D`` products each as large as
``|q|^2 |k|^2 / d``, so a weight carries an absolute rounding of about
``1e-7 |q|^2 |k|^2``; where a query sees few keys its normaliser is a few
such weights, and the first positions read up to 1.7e-4 off the
reference, later ones 1e-5: ``STATE_TOL``. A state rounded to bfloat16
after every token reads 4e-3 and more
(``test_a_bfloat16_state_fails_the_tolerance``), a state not carried past
the prompt 1e-1.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import power_retention as pr
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.kv_cache import PagedCacheConfig, init_pools
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text import brumby
from paddle_tpu.text.brumby import (RETENTION_COUNTERS, BrumbyConfig,
                                    BrumbyForCausalLM)
from paddle_tpu.utils.flags import flag, set_flags

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "refs"))
import brumby_reference as ref  # noqa: E402

TOL = 2e-5
STATE_TOL = 3e-4
VOCAB = 97
TINY = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=10,
            num_key_value_heads=2, head_dim=16,
            max_position_embeddings=64, initializer_range=0.1, chunk_size=8)
#: group sizes 1 and 5 (the published 40 over 8)
GROUPS = {"group1": dict(num_attention_heads=4, num_key_value_heads=4),
          "group5": {}}
SLOTS = 2


def build(seed=3, **over):
    """(model, config, its leaves) with every norm moved off 1 and the
    gates' biases put at 3 to 5 (a decay of 0.95 to 0.99 a token), so that
    a path which dropped one, or forgot the state, would show."""
    paddle.seed(seed)
    cfg = BrumbyConfig(**dict(TINY, **over))
    model = BrumbyForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    params, _ = model.functional_state()
    for name, t in params.items():
        v = np.asarray(t._value)
        if name.endswith("gate.bias"):
            t._value = jnp.asarray(rng.uniform(3, 5, v.shape), t._value.dtype)
        elif "norm" in name:
            t._value = jnp.asarray(v + rng.normal(0, 0.1, v.shape),
                                   t._value.dtype)
    return model, cfg, {k: v._value for k, v in params.items()}


def ids_of(rng, *shape):
    return rng.integers(1, VOCAB, shape).astype(np.int32)


@pytest.fixture
def interpret():
    """The decode kernel through the interpreter when asked."""
    before = flag("FLAGS_ragged_interpret", False)
    yield lambda on: set_flags({"FLAGS_ragged_interpret": on})
    set_flags({"FLAGS_ragged_interpret": before})


# ------------------------------------------------- the forms vs reference
@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("chunk", [7, 4, 64], ids=["chunk7", "chunk4",
                                                   "one_chunk"])
def test_chunked_form_equals_the_attention_form(group, chunk):
    """The whole pass (the chunked form) at chunks that do not (7) and do
    (4) divide the 20-token prompt, and of one chunk, against the
    reference's attention form."""
    model, cfg, p = build(chunk_size=chunk, **GROUPS[group])
    ids = ids_of(np.random.default_rng(0), 2, 20)
    got = model(paddle.to_tensor(ids))._value
    want = ref.forward(p, jnp.asarray(ids), cfg)
    assert got.shape == want.shape == (2, 20, VOCAB)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_state_form_equals_the_attention_form(monkeypatch, group):
    """The same pass with the retention run token by token
    (``retention_sequential``, the state form)."""
    model, cfg, p = build(**GROUPS[group])
    monkeypatch.setattr(brumby, "retention_chunked",
                        lambda q, k, v, g, s0, z0, chunk:
                        brumby.retention_sequential(q, k, v, g, s0, z0))
    ids = ids_of(np.random.default_rng(1), 2, 13)
    got = model(paddle.to_tensor(ids))._value
    want = ref.forward(p, jnp.asarray(ids), cfg)
    assert float(jnp.max(jnp.abs(got - want))) < STATE_TOL


def test_the_chunked_form_carries_a_state_it_is_given():
    """From a state that is not zero, chunked and sequential agree in the
    outputs, the final state and z, and padding (log gamma 0, zero key and
    value) leaves the state where the last real token left it."""
    rng = np.random.default_rng(2)
    b, s, g, h, d = 2, 11, 2, 3, 8
    dd = pr.feature_dim(d)
    q = jnp.asarray(rng.normal(size=(b, s, g, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, g, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, g, d)), jnp.float32)
    lg = jnp.asarray(-rng.uniform(0, 0.2, (b, s, g)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(b, g, dd, d)), jnp.float32)
    z0 = jnp.asarray(rng.uniform(1, 2, (b, g, dd)), jnp.float32)
    pad = np.arange(s) >= 8
    lg = jnp.where(pad[None, :, None], 0.0, lg)
    k = jnp.where(pad[None, :, None, None], 0.0, k)
    v = jnp.where(pad[None, :, None, None], 0.0, v)
    want = brumby.retention_sequential(q, k, v, lg, s0, z0)
    got = brumby.retention_chunked(q, k, v, lg, s0, z0, 4)
    for a, w in zip(got, want):
        assert float(jnp.max(jnp.abs(a - w))) < 1e-4 * float(
            jnp.max(jnp.abs(w)))
    short = brumby.retention_sequential(q[:, :8], k[:, :8], v[:, :8],
                                        lg[:, :8], s0, z0)
    assert float(jnp.max(jnp.abs(got[1] - short[1]))) < 1e-4 * float(
        jnp.max(jnp.abs(short[1])))


# ------------------------------------------- through the cache, as served
def fresh_pools(model, dirty=None):
    spec = model.paged_cache_spec()
    pools = init_pools(PagedCacheConfig(
        num_layers=spec.num_layers, leaves=spec.leaves, max_batch=SLOTS,
        enable_prefix_caching=False))
    if dirty is not None:   # what a slot's last owner left behind
        rng = np.random.default_rng(dirty)
        pools = [{k: jnp.asarray(rng.normal(size=a.shape), a.dtype)
                  for k, a in pl.items()} for pl in pools]
    return pools


def cache_call(model, pools, ids, ctx, valid, slots, head_at=None):
    """One call as the engine makes it (no page table: the model keeps
    none); returns (logits, pools, counters)."""
    caches = [dict(pl, ctx_lens=jnp.asarray(ctx, jnp.int32),
                   valid=jnp.asarray(valid), kv_limit=None, slots=slots)
              for pl in pools]
    if head_at is not None:
        caches[0]["head_at"] = jnp.asarray(head_at, jnp.int32)
    logits, new = model(paddle.to_tensor(ids), caches=caches)
    counters = sum(np.asarray(c["counters"]) for c in new)
    return logits._value, [{k: c[k] for k in pl}
                           for c, pl in zip(new, pools)], counters


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("group", sorted(GROUPS))
def test_prefill_then_decode_equals_the_reference_in_logits(interpret,
                                                            kernel, group):
    """The engine's sequence of calls: A's prompt prefilled into slot 0
    (dirty: a last owner's state), decoded; B joins mid-flight in slot 1
    and both decode; A leaves and C reuses slot 0 from position 0. Every
    logit of every real position is the reference's."""
    interpret(kernel)
    model, cfg, p = build(**GROUPS[group])
    rng = np.random.default_rng(5)
    seqs = {"A": ids_of(rng, 22), "B": ids_of(rng, 17), "C": ids_of(rng, 15)}
    prompt = {"A": 9, "B": 6, "C": 7}
    want = {r: np.asarray(ref.forward_one(p, jnp.asarray(s), cfg))
            for r, s in seqs.items()}
    pools = fresh_pools(model, dirty=7)
    where = {}      # slot -> (request, its next position)

    def prefill(r, slot, bucket=16):
        n = prompt[r]
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = seqs[r][:n]
        valid = (np.arange(bucket) < n)[None]
        logits, new, counters = cache_call(
            model, pools, ids, [0], valid, jnp.asarray([slot]),
            head_at=[n - 1])
        assert float(np.max(np.abs(np.asarray(logits[0, 0])
                                   - want[r][n - 1]))) < TOL
        assert list(counters) == [cfg.num_hidden_layers] * 2
        where[slot] = (r, n)
        return new

    def decode():
        ids = np.zeros((SLOTS, 1), np.int32)
        ctx = np.zeros(SLOTS, np.int32)
        live = np.zeros((SLOTS, 1), bool)
        for slot, (r, t) in where.items():
            ids[slot, 0], ctx[slot], live[slot, 0] = seqs[r][t], t, True
        logits, new, counters = cache_call(model, pools, ids, ctx, live,
                                           None)
        for slot, (r, t) in where.items():
            assert float(np.max(np.abs(np.asarray(logits[slot, 0])
                                       - want[r][t]))) < STATE_TOL, (r, t)
            where[slot] = (r, t + 1)
        # the kernel moves the live slots alone, the plain recurrence all
        n = len(where) * cfg.num_hidden_layers
        assert counters[0] == n
        assert counters[1] == (n if kernel else
                               SLOTS * cfg.num_hidden_layers)
        return new

    pools = prefill("A", 0)
    for _ in range(3):
        pools = decode()
    pools = prefill("B", 1)
    for _ in range(5):
        pools = decode()
    del where[0]                # A leaves; its slot keeps its state
    pools = prefill("C", 0)
    for _ in range(6):
        pools = decode()


def test_a_dead_slot_s_state_is_untouched(interpret):
    """A decode step with slot 1 dead leaves its state and z bit for bit,
    under the kernel and the plain recurrence alike."""
    for kernel in (False, True):
        interpret(kernel)
        model, _, _ = build()
        pools = fresh_pools(model, dirty=11)
        _, new, _ = cache_call(model, pools, np.array([[5], [6]], np.int32),
                               [4, 9], np.array([[True], [False]]), None)
        for old, nw in zip(pools, new):
            for key in ("state", "z"):
                assert np.array_equal(np.asarray(old[key][1]),
                                      np.asarray(nw[key][1]))
                assert not np.array_equal(np.asarray(old[key][0]),
                                          np.asarray(nw[key][0]))


def test_a_bfloat16_state_fails_the_tolerance(interpret, monkeypatch):
    """The tolerance is tight enough to tell the configuration's float32
    state from one rounded to bfloat16 after every token."""
    interpret(False)
    model, cfg, p = build()
    real = pr.retention_update_reference

    def bf16_state(state, z, *a, **k):
        new, nz, o = real(state, z, *a, **k)
        return (new.astype(jnp.bfloat16).astype(jnp.float32),
                nz.astype(jnp.bfloat16).astype(jnp.float32), o)

    monkeypatch.setattr(pr, "retention_update_reference", bf16_state)
    seq = ids_of(np.random.default_rng(8), 30)
    want = np.asarray(ref.forward_one(p, jnp.asarray(seq), cfg))
    pools = fresh_pools(model)
    worst = 0.0
    for t in range(len(seq)):
        ids = np.zeros((SLOTS, 1), np.int32)
        ids[0, 0] = seq[t]
        logits, pools, _ = cache_call(
            model, pools, ids, [t, 0], np.array([[True], [False]]), None)
        worst = max(worst, float(np.max(np.abs(np.asarray(logits[0, 0])
                                               - want[t]))))
    assert worst > 10 * STATE_TOL


# --------------------------------------------------------------- engine
def gaps(p, cfg, prompt, seq):
    logits = np.asarray(ref.forward_one(p, jnp.asarray(seq[:-1]), cfg))
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    return logits[at].max(-1) - logits[at, seq[len(prompt):]]


def engine(model, **over):
    cfg = dict(max_batch=SLOTS, page_size=4, max_prompt_len=16,
               enable_prefix_caching=False)
    cfg.update(over)
    return ServingEngine(model, ServingConfig(**cfg))


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_engine_serves_the_reference_s_tokens_and_counts(interpret, kernel):
    """Five requests over two slots (joins mid-flight, every slot reused):
    each served token is the reference's best at its position (a near-tie
    within STATE_TOL either way), and the counters count the retention
    layers' rows."""
    interpret(kernel)
    model, cfg, p = build()
    eng = engine(model)
    rng = np.random.default_rng(4)
    prompts = [ids_of(rng, n) for n in (9, 14, 5, 11, 7)]
    before = eng.metrics.snapshot()
    rids = [eng.add_request(pr_, 6) for pr_ in prompts]
    out = {}
    while len(out) < len(rids):
        eng.step()
        out.update(eng.pop_finished())
    for r, prompt in zip(rids, prompts):
        seq = np.asarray(out[r])
        assert len(seq) == len(prompt) + 6
        assert float(gaps(p, cfg, prompt, seq).max()) < STATE_TOL
    snap = eng.metrics.snapshot()
    count = lambda k: snap[k] - before.get(k, 0)  # noqa: E731
    live, moved = (count("serving_" + n) for n in RETENTION_COUNTERS)
    assert 0 < live <= moved
    if kernel:
        assert live == moved
    assert snap["serving_kv_bytes_per_token"] == 0
    assert snap["serving_state_bytes_per_slot"] \
        == eng.cache.cfg.state_bytes_per_slot


def test_state_bytes_are_the_pool_s_own_leaves():
    """At the benchmark's widths: 8 layers of 8 groups of S (8,256 x 128)
    and z (65 lane rows of 128, the last half zeros), float32: 272.7 MB a
    slot; nothing a token."""
    with paddle.LazyGuard():
        model = BrumbyForCausalLM(BrumbyConfig(num_hidden_layers=8))
    spec = model.paged_cache_spec()
    cfg = PagedCacheConfig(num_layers=8, leaves=spec.leaves, max_batch=16)
    assert [(lf.name, lf.shape) for lf in spec.leaves] == [
        ("state", (8, 8256, 128)), ("z", (8, 65, 128))]
    assert all(lf.per_slot for lf in spec.leaves)
    assert cfg.kv_bytes_per_token == 0 and cfg.pool_leaf_keys == ()
    assert cfg.state_bytes_per_slot == 8 * 8 * 4 * (8256 * 128 + 65 * 128)
    assert round(cfg.state_bytes_per_slot / 1e6, 1) == 272.7


@pytest.mark.parametrize("config,why", [
    (dict(enable_prefix_caching=True), "no page to share"),
    (dict(spec=SpecConfig(method="ngram", depth=2)), "recurrent state"),
    (dict(kv_dtype="int8"), "no keys and values"),
    (dict(tensor_parallel=2), "no placement"),
])
def test_what_a_state_alone_cannot_do_refuses_at_construction(config, why):
    model, _, _ = build()
    with pytest.raises(ValueError, match=why):
        engine(model, **config)


def test_a_verify_shaped_call_has_no_state_path():
    model, _, _ = build()
    pools = fresh_pools(model)
    with pytest.raises(NotImplementedError, match="verify"):
        cache_call(model, pools, np.ones((SLOTS, 3), np.int32), [2, 2],
                   np.ones((SLOTS, 3), bool), None)
