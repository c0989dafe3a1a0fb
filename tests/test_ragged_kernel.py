"""Unified ragged paged-attention kernel (kernels/ragged_paged_attention).

- interpret-mode BIT-IDENTITY vs the jitted composite (gather + ragged-
  masked sdpa) for all four serving modes — prefill, chunked-prefill
  tail, decode, spec K+1 verify — in fp32 AND int8 (dequant fused into
  the page gather), incl. head_dim 64 and tuned block_heads
- the eligibility gate (single source of truth with the dispatch and the
  kernelcheck coverage report)
- ragged_tuned.json validation at LOAD (the flash_tuned discipline)
- engine-level: kernel path FORCED ON via FLAGS_ragged_interpret —
  outputs bit-identical to the composite engine, compile_counts equal,
  sync-free certification unchanged, zero fallbacks; kernel A/B gauges
  seeded from the bank; ineligible (CPU, flag off) stays composite with
  the fallback gauge at zero
- the flash seq-%512 pad route and edge counter (kernels/attention.py)
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.utils import monitor
from paddle_tpu.utils.flags import set_flags

pytestmark = pytest.mark.ragged

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.kernels import ragged_paged_attention as rp  # noqa: E402


@pytest.fixture
def ragged_interpret():
    set_flags({"FLAGS_ragged_interpret": True})
    yield
    set_flags({"FLAGS_ragged_interpret": False})


# ------------------------------------------------------ kernel-level parity
def _composite(q, kp, vp, tab, ctx, k_scale=None, v_scale=None,
               scale=None):
    from paddle_tpu.kernels.attention import sdpa

    s = q.shape[2]
    if k_scale is not None:
        k_all = pa.paged_gather_quant(kp, k_scale, tab, q.dtype)
        v_all = pa.paged_gather_quant(vp, v_scale, tab, q.dtype)
    else:
        k_all = pa.paged_gather(kp, tab)
        v_all = pa.paged_gather(vp, tab)
    mask = pa.ragged_mask(ctx, k_all.shape[2], s)
    return sdpa(q, k_all, v_all, mask=mask, scale=scale)


def _args(seed, b, h, s, d, ps, pps, npages, ctx_vals, quant=False):
    rng = np.random.RandomState(seed)
    if quant:
        kp = jnp.asarray(rng.randint(-127, 128, (npages, ps, h, d)),
                         jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 128, (npages, ps, h, d)),
                         jnp.int8)
        kw = dict(
            k_scale=jnp.asarray(np.abs(rng.randn(npages, h)) + 0.1,
                                jnp.float32),
            v_scale=jnp.asarray(np.abs(rng.randn(npages, h)) + 0.1,
                                jnp.float32))
    else:
        kp = jnp.asarray(rng.randn(npages, ps, h, d), jnp.float32)
        vp = jnp.asarray(rng.randn(npages, ps, h, d), jnp.float32)
        kw = {}
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    tab = jnp.asarray(
        rng.choice(npages, (b, pps), replace=False).astype(np.int32))
    ctx = jnp.asarray(ctx_vals, jnp.int32)
    return (q, kp, vp, tab, ctx), kw


# (mode, batch, heads, s, head_dim, page_size, pages_per_seq, num_pages,
#  ctx_lens) — every serving contract: cold prefill (ctx 0), chunk tail
# (ctx mid-prompt), decode (s=1), spec verify (s=K+1), ragged ctx mixes
_MODES = [
    ("prefill", 1, 2, 8, 8, 4, 4, 16, [0]),
    ("chunk", 1, 2, 8, 8, 4, 4, 16, [4]),
    ("decode", 2, 2, 1, 8, 4, 4, 16, [5, 9]),
    ("verify", 3, 4, 5, 16, 4, 8, 40, [10, 3, 17]),
]


@pytest.mark.parametrize("quant", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("mode", [m[0] for m in _MODES])
def test_interpret_bit_identical_to_composite(mode, quant):
    spec = next(m for m in _MODES if m[0] == mode)
    # deterministic seed (hash() is salted per process — a failing run
    # must be reproducible from the test id alone)
    seed = [m[0] for m in _MODES].index(mode) * 2 + int(quant) + 1
    args, kw = _args(seed, *spec[1:], quant=quant)
    ref = jax.jit(lambda *a: _composite(*a, **kw))(*args)
    out = jax.jit(lambda *a: rp.ragged_paged_attention(
        *a, interpret=True, **kw))(*args)
    assert np.array_equal(np.asarray(out), np.asarray(ref)), \
        f"{mode}/{'int8' if quant else 'fp32'} diverged from composite"


def test_interpret_bit_identical_head_dim_64_and_block_heads():
    """The head_dim-64 coverage gap closed for real, and the tuned
    block_heads knob changes the launch config without changing a bit."""
    args, kw = _args(11, 2, 4, 1, 64, 4, 4, 16, [7, 12])
    ref = jax.jit(lambda *a: _composite(*a))(*args)
    for bh in (1, 2, 4):
        out = jax.jit(lambda *a, _bh=bh: rp.ragged_paged_attention(
            *a, interpret=True, block_heads=_bh))(*args)
        assert np.array_equal(np.asarray(out), np.asarray(ref)), \
            f"block_heads={bh} diverged"


def test_scale_override_matches_composite():
    args, _ = _args(13, 2, 2, 1, 8, 4, 4, 16, [5, 9])
    ref = jax.jit(lambda *a: _composite(*a, scale=0.25))(*args)
    out = jax.jit(lambda *a: rp.ragged_paged_attention(
        *a, scale=0.25, interpret=True))(*args)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# --------------------------------------------------------- eligibility gate
def test_ragged_kernel_eligible_gates():
    ok, why = rp.ragged_kernel_eligible(128, 32, 16, 1, num_heads=8)
    assert ok and why == ""
    # int8, unaligned widths, multi-token: all served
    for kw in (dict(quantized=True), dict(num_query_tokens=5),
               dict(num_query_tokens=64)):
        ok, why = rp.ragged_kernel_eligible(128, 30, 16, num_heads=8, **kw)
        assert ok, (kw, why)
    # head_dim 64: the chip's compiler refuses the page DMA — the gate
    # says so in its words; the interpreter (the CPU test path) has no
    # such rule
    ok, why = rp.ragged_kernel_eligible(64, 30, 16, num_heads=8)
    assert not ok and "aligned to tiling (128), but is 64" in why
    ok, why = rp.ragged_kernel_eligible(64, 30, 16, num_heads=8,
                                        on_tpu=False, interpret=True)
    assert ok, why
    ok, why = rp.ragged_kernel_eligible(128, 32, 16, flags_on=False)
    assert not ok and "FLAGS_use_pallas_kernels" in why
    ok, why = rp.ragged_kernel_eligible(128, 32, 16, on_tpu=False)
    assert not ok and "FLAGS_ragged_interpret" in why
    ok, why = rp.ragged_kernel_eligible(128, 32, 16, on_tpu=False,
                                        interpret=True)
    assert ok  # the interpreter sanctions the CPU backend
    # the default chunk shrinks until the working set fits, so only a
    # page too large to stage one at a time trips the VMEM gate
    ok, why = rp.ragged_kernel_eligible(128, 64, 4096)
    assert not ok and "VMEM" in why
    # a 64-page fp32 row of 8 heads x 128 does not fit VMEM whole: the
    # default is then the chunk of _CHUNK_TOKENS (128 tokens = 8 pages),
    # not the largest that fits (32 for decode, 16 for a prefill tile,
    # until PR 30): the loop runs to the row's live length and wastes
    # half a chunk a row, so a small chunk wins (PERF.md section 6)
    assert rp.pipeline_chunk_for(16, 16, 128, 64, block_heads=8) == 8
    assert rp.pipeline_chunk_for(16, 16, 128, 64, block_heads=8,
                                 num_query_tokens=512) == 8
    # a row that fits VMEM whole stays the exact single-chunk path
    assert rp.pipeline_chunk_for(16, 8, 128, 32) == 32


def test_validate_ragged_tuned():
    from paddle_tpu.analysis.kernelcheck import validate_ragged_tuned

    assert validate_ragged_tuned({"16,8,128": 4, "16,16,64": 1}) == []
    errors = validate_ragged_tuned({
        "16,8,128": 3,       # does not divide num_heads
        "16,8": 2,           # unparseable key
        "16,8,64": 0,        # non-positive
        "16,8,96": "2",      # non-int value
        "-4,8,64": 2,        # negative page size
    })
    msgs = "\n".join(errors)
    assert "does not divide num_heads" in msgs
    assert "page_size,num_heads,head_dim" in msgs
    assert "positive int" in msgs and "must be positive" in msgs


def test_shipped_ragged_tuned_table_is_valid():
    from paddle_tpu.analysis.kernelcheck import validate_ragged_tuned

    table = rp._tuned_table()  # raises on a bad shipped table
    assert validate_ragged_tuned(table) == []


def test_ragged_tuned_load_rejects_bad_entry(tmp_path, monkeypatch):
    bad = tmp_path / "ragged_tuned.json"
    bad.write_text(json.dumps({"16,8,128": 3}))
    monkeypatch.setattr(rp, "_TUNED_PATH", str(bad))
    monkeypatch.setattr(rp, "_TUNED", None)
    with pytest.raises(ValueError, match="does not divide"):
        rp._tuned_table()
    monkeypatch.setattr(rp, "_TUNED", None)  # don't poison the cache
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"4,2,8": 2}))
    monkeypatch.setattr(rp, "_TUNED_PATH", str(good))
    assert rp.block_heads_for(4, 2, 8) == 2
    # untuned default: one sublane tile of the pool dtype, or every head
    assert rp.block_heads_for(16, 8, 128) == 8
    assert rp.block_heads_for(16, 16, 128) == 8
    assert rp.block_heads_for(16, 16, 128, pool_itemsize=1) == 16
    assert rp.block_heads_for(16, 4, 128) == 4
    monkeypatch.setattr(rp, "_TUNED", None)


# ------------------------------------------- the loop bounded by ctx_lens
# (mode, batch, heads, s, head_dim, page_size, pages_per_seq, ctx_lens,
#  chunk): every serving contract through the PIPELINED kernel, with live
# lengths that end inside a chunk so its last pages are dead
_LIVE_MODES = [
    ("decode", 3, 2, 1, 8, 4, 8, [5, 9, 0], 2),
    ("prefill", 1, 2, 8, 8, 4, 8, [0], 4),
    ("chunk", 2, 2, 8, 8, 4, 8, [4, 13], 4),
    ("verify", 3, 4, 5, 16, 4, 8, [10, 4, 17], 2),
]


@pytest.mark.parametrize("quant", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("mode", [m[0] for m in _LIVE_MODES])
def test_dead_pages_are_never_read(mode, quant):
    """Every table entry past a row's live pages points at a POISONED
    pool page (NaN keys and values; NaN scales under int8, whose codes
    cannot hold one) and nothing zeroes the staging buffers: the
    pipelined kernel's output is finite and the composite's over the
    same pool with those entries on the null page. A masked position's
    probability is exactly 0, and 0 * NaN is NaN: the kernel may not
    stage what it has not copied, nor copy through a dead entry."""
    _, b, h, s, d, ps, pps, ctx_vals, chunk = next(
        m for m in _LIVE_MODES if m[0] == mode)
    npages = b * pps + 2
    (q, kp, vp, tab, ctx), kw = _args(21 + int(quant), b, h, s, d, ps, pps,
                                      npages, ctx_vals, quant=quant)
    # keep the null page and the poison page out of every live entry
    poison = npages - 1
    tab = np.array(1 + np.asarray(tab) % (npages - 2))
    live = -(-(np.asarray(ctx_vals) + s) // ps)
    dead = np.arange(pps)[None, :] >= live[:, None]
    assert dead.any(axis=1).all() and (live % chunk).any()
    clean = jnp.asarray(np.where(dead, 0, tab).astype(np.int32))
    dirty = jnp.asarray(np.where(dead, poison, tab).astype(np.int32))
    if quant:
        kw = {k: v.at[poison].set(jnp.nan) for k, v in kw.items()}
    else:
        kp, vp = kp.at[poison].set(jnp.nan), vp.at[poison].set(jnp.nan)
    ref_kw = ({k: v.at[poison].set(1.0) for k, v in kw.items()}
              if quant else {})
    ref = jax.jit(lambda *a: _composite(*a, **ref_kw))(
        q, jnp.nan_to_num(kp) if not quant else kp,
        jnp.nan_to_num(vp) if not quant else vp, clean, ctx)
    out = jax.jit(lambda *a: rp.ragged_paged_attention(
        *a, interpret=True, pipeline_chunk=chunk, **kw))(
            q, kp, vp, dirty, ctx)
    assert np.isfinite(np.asarray(out)).all(), "a dead page was staged"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _staged_brute(ctx, s, ps, pps, chunk, tq):
    """Pages staged for one row, counted the slow way: per query tile,
    every chunk that holds a position some query of the tile can see
    (query t sees positions <= ctx + t), clamped to the table."""
    n = 0
    for t0 in range(0, s, tq):
        seen = min(max(ctx + t0 + tq, 1), ps * pps)  # positions 0..seen-1
        n += chunk * sum(1 for c in range(pps // chunk)
                         if c * chunk * ps < seen)
    return n


@pytest.mark.parametrize("s,tq", [(1, 1), (5, 5), (256, 128)],
                         ids=["decode", "verify", "prefill-2-tiles"])
def test_pages_staged_matches_brute_force(s, tq):
    """The exported arithmetic of the kernel's loop bound, on its edges:
    ctx on, one under and one over a chunk boundary, 0, the table's end,
    and a dead slot's garbage beyond it."""
    ps, pps, chunk = 16, 64, 8
    ck, total = chunk * ps, ps * pps
    edges = [0, 1, ck - s - 1, ck - s, ck - s + 1, ck - 1, ck, ck + 1,
             3 * ck, total - s - 1, total - s, total, 10 ** 6]
    edges = [max(0, e) for e in edges]
    assert rp.query_tile_for(s) == tq
    got = rp.pages_staged(edges, s, page_size=ps, pages_per_seq=pps,
                          chunk_pages=chunk)
    want = [_staged_brute(c, s, ps, pps, chunk, tq) for c in edges]
    assert got.tolist() == want
    # the single-chunk kernel stages the table a tile, the composite once
    assert rp.pages_staged(edges, s, page_size=ps, pages_per_seq=pps,
                           chunk_pages=pps).tolist() == \
        [pps * (s // tq)] * len(edges)
    assert rp.pages_staged(edges, s, page_size=ps, pages_per_seq=pps,
                           chunk_pages=None).tolist() == [pps] * len(edges)


# ------------------------------------------------------------- engine level
def _mk_engine(kv="float32", spec=None, **over):
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=61, hidden_size=16, num_layers=2, num_heads=2,
        max_seq_len=64, dropout=0.0))
    model.eval()
    cfg = dict(max_batch=2, num_pages=32, page_size=4, max_prompt_len=16,
               kv_dtype=kv, spec=spec)
    cfg.update(over)
    return ServingEngine(model, ServingConfig(**cfg))


def _drive(eng, budget=10):
    rng = np.random.RandomState(3)
    rids = [eng.add_request(rng.randint(0, 61, (n,)).astype(np.int32),
                            budget) for n in (5, 9)]
    outs = eng.run()
    return [outs[r] for r in rids]


def test_engine_kernel_on_bit_identical_and_sync_free(ragged_interpret):
    """The whole serving loop with EVERY attention dispatch through the
    unified kernel (interpret mode): outputs bit-identical to the
    composite engine, compile counts equal, the sync-free certification
    formula unchanged, zero fallbacks."""
    from paddle_tpu.analysis import SyncTally
    from paddle_tpu.serving.spec import SpecConfig

    set_flags({"FLAGS_ragged_interpret": False})
    base = _mk_engine(spec=SpecConfig(method="ngram", depth=2))
    off = _drive(base)
    cc_off = dict(base.compile_counts)

    set_flags({"FLAGS_ragged_interpret": True})
    eng = _mk_engine(spec=SpecConfig(method="ngram", depth=2))
    rng = np.random.RandomState(3)
    rids = [eng.add_request(rng.randint(0, 61, (n,)).astype(np.int32), 10)
            for n in (5, 9)]
    pre = eng.metrics.snapshot()
    with SyncTally() as tally:
        outs = eng.run()
    on = [outs[r] for r in rids]
    for a, b in zip(off, on):
        assert np.array_equal(a, b), "kernel-on output diverged"
    assert dict(eng.compile_counts) == cc_off
    snap = eng.metrics.snapshot()
    fetches = int(snap["serving_decode_steps"] - pre["serving_decode_steps"]
                  + snap["serving_prefills_total"]
                  - pre["serving_prefills_total"])
    assert tally.count == fetches, (
        f"kernel-on loop not sync-free: {tally.count} syncs vs "
        f"{fetches} sanctioned fetches")
    assert snap["serving_pallas_fallback_total"] == 0
    assert snap["serving_analysis_retraces_total"] == 0


@pytest.mark.slow  # re-tiered 2026-08 (PR 20): tier-1 crossed its 870 s
# budget; the fp32 engine-level bit-identity pin above keeps the
# kernel-on path hot in tier-1, int8 interpret numerics stay pinned too
def test_engine_kernel_on_int8_bit_identical(ragged_interpret):
    """The int8 pool — the config the old dispatch BANNED from the
    kernel — served through the fused-dequant gather, bit-identical to
    the quantized composite engine."""
    set_flags({"FLAGS_ragged_interpret": False})
    off = _drive(_mk_engine(kv="int8"))
    set_flags({"FLAGS_ragged_interpret": True})
    eng = _mk_engine(kv="int8")
    on = _drive(eng)
    for a, b in zip(off, on):
        assert np.array_equal(a, b), "int8 kernel-on output diverged"
    assert eng.metrics.snapshot()["serving_pallas_fallback_total"] == 0


def test_engine_counts_attention_pages(ragged_interpret, monkeypatch):
    """serving_attention_pages_{live,staged}_total after a short run with
    the kernel pipelined (a tuned 2-page chunk over the 16-page table):
    every launch adds ceil((ctx + tokens) / page_size) over its real rows
    and ``pages_staged`` of the ctx_lens it uploaded over all its rows;
    live <= staged, and staged stays under the table-wide count. The
    composite engine (no kernel on the CPU) stages the table's width."""
    monkeypatch.setattr(rp, "_TUNED", {
        "4,2,8": {"block_heads": 2, "pipeline_chunk": 2,
                  "pages_per_seq": 16}})
    eng = _mk_engine()
    ps, pps = 4, eng.cache.cfg.pages_per_seq
    assert pps == 16
    launches = []
    count = eng._count_attention_pages

    def spy(ctx, s, tokens=None, live_rows=None):
        launches.append((np.array(ctx, copy=True).reshape(-1), s,
                         s if tokens is None else tokens,
                         None if live_rows is None else live_rows.copy()))
        count(ctx, s, tokens, live_rows)

    monkeypatch.setattr(eng, "_count_attention_pages", spy)
    pre = eng.metrics.snapshot()
    _drive(eng, budget=6)
    snap = eng.metrics.snapshot()
    live = snap["serving_attention_pages_live_total"] \
        - pre["serving_attention_pages_live_total"]
    staged = snap["serving_attention_pages_staged_total"] \
        - pre["serving_attention_pages_staged_total"]
    want_live = want_staged = table_wide = 0
    for ctx, s_, tokens, rows in launches:
        per_row = -(-(ctx + tokens) // ps)
        want_live += int((per_row if rows is None else per_row[rows]).sum())
        want_staged += int(rp.pages_staged(
            ctx, s_, page_size=ps, pages_per_seq=pps, chunk_pages=2).sum())
        table_wide += pps * len(ctx)
    # decodes, and the two prompts' prefill buckets
    assert {s_ for _, s_, _, _ in launches} == {1, 8, 16}
    assert (live, staged) == (want_live, want_staged)
    assert 0 < live <= staged < table_wide

    set_flags({"FLAGS_ragged_interpret": False})
    eng = _mk_engine()
    pre = eng.metrics.snapshot()
    _drive(eng, budget=4)
    snap = eng.metrics.snapshot()
    steps = snap["serving_decode_steps"] - pre["serving_decode_steps"]
    prefills = snap["serving_prefills_total"] - pre["serving_prefills_total"]
    assert snap["serving_attention_pages_staged_total"] \
        - pre["serving_attention_pages_staged_total"] \
        == pps * (2 * steps + prefills)


def test_engine_ineligible_stays_composite_with_zero_fallbacks():
    """CPU without the interpret flag: the gate (not a fallback) routes
    to the composite — the fallback gauge stays at its pre-seeded
    zero."""
    eng = _mk_engine()
    assert eng._decode_pallas_eligible is False
    _drive(eng, budget=4)
    snap = eng.metrics.snapshot()
    assert snap["serving_pallas_fallback_total"] == 0


# ------------------------------------------- flash %512 pad-or-fallback
def test_flash_route_and_pad_edge():
    from paddle_tpu.kernels import flash_attention as fa

    shape = (1, 8, 1024, 128)
    assert fa.flash_route(shape, shape, causal=True) == "direct"
    s640 = (1, 8, 640, 128)
    assert fa.flash_route(s640, s640, causal=True) == "pad"
    assert fa.pad_seq_to_block(640) == 1024
    assert fa.flash_route(s640, s640, causal=False) == ""
    assert fa.edge_missed(s640, s640)
    tiny = (1, 8, 64, 128)
    assert fa.flash_route(tiny, tiny, causal=True) == ""
    assert not fa.edge_missed(tiny, tiny)  # sub-kernel, not an edge
    # cross-attention and >2x pad blowups don't pad
    assert fa.flash_route((1, 8, 640, 128), (1, 8, 1280, 128),
                          causal=True) == ""


def test_sdpa_pad_route_counts_gauge_and_a_failing_kernel_raises(monkeypatch):
    """Force the TPU gates on CPU: the 640 causal dispatch takes the pad
    route (counted on serving_flash_pad_total) and the padded flash,
    which cannot lower on the CPU backend, RAISES — the composite is not
    served in a routed kernel's place."""
    from paddle_tpu.kernels import attention as at

    monkeypatch.setattr(at, "_on_tpu", lambda: True)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 640, 64), jnp.float32)
    before_pad = monitor.stat_get("serving_flash_pad_total", 0)
    with pytest.raises(ValueError, match="interpret mode"):
        at.sdpa(q, q, q, is_causal=True)
    assert monitor.stat_get("serving_flash_pad_total", 0) == before_pad + 1
    # non-causal 640: no route — the loudly-counted composite fallback
    before_edge = monitor.stat_get("serving_flash_edge_fallback_total", 0)
    at.sdpa(q, q, q, is_causal=False)
    assert monitor.stat_get("serving_flash_edge_fallback_total", 0) \
        == before_edge + 1


def test_flash_edge_gauges_pre_seeded():
    from paddle_tpu.serving.metrics import ServingMetrics

    snap = ServingMetrics().snapshot()
    assert snap["serving_flash_pad_total"] == 0
    assert snap["serving_flash_edge_fallback_total"] == 0
    prom = ServingMetrics().prometheus()
    assert "# TYPE serving_flash_pad_total counter" in prom
    assert "# TYPE serving_flash_edge_fallback_total counter" in prom
