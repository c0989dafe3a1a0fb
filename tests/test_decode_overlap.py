"""Decode overlap triad (PR 18): the double-buffered page-DMA pipeline in
the ragged kernel, the hlocheck async-collective overlap census, and the
quantized logits all-reduce.

- **Overlap census on hand-built HLO**: sync-only programs report 0/N
  with byte counts identical to their async compilation, a ``-start``
  immediately followed by its ``-done`` counts as NOT overlapped (and
  fails a ``min_overlap_frac`` budget), fully interleaved programs count
  every in-flight instruction, and XLA's variadic combiner-merged form
  charges the result half of the tuple — so byte caps hold across
  sync/async/combined compilation of the same traffic.
- **Pipelined kernel parity**: chunked double-buffered staging (chunk <
  pages_per_seq) stays within float tolerance of the jitted composite in
  interpret mode for decode/verify x fp32/int8, and the chunk ==
  pages_per_seq path is BIT-identical to the default single-buffer
  gather; tuned-table dict schema + stale-chunk validation.
- **Quantized psum**: numeric parity vs the exact f32 psum (shared-scale
  int8 codes can never overflow the int8 accumulator), zero-input safe.
- **Engine level (TP=2 on the conftest CPU mesh)**: overlap-scheduler on
  + quantized off is bit-identical to the baseline sharded engine; the
  quantized logits all-reduce certifies at 2L+2 all-reduces with the
  census bytes UNDER the f32 budget's cap (the measurable shrink), at
  bounded greedy divergence (mean common-prefix >= 0.5); the
  ``serving_tp_collective_overlap_frac`` gauge is pre-seeded and fed at
  the first-trace audit.
"""
import itertools

import numpy as np
import pytest

import paddle_tpu as paddle

pytestmark = pytest.mark.overlap

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.analysis import hlocheck  # noqa: E402
from paddle_tpu.analysis.hlocheck import (  # noqa: E402
    CollectiveBudget, CollectiveOverlapError, HloAuditReport, census)
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.kernels import ragged_paged_attention as rp  # noqa: E402
from paddle_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from paddle_tpu.serving import scheduler as sched_mod  # noqa: E402
from paddle_tpu.serving.tp import TPContext, quantized_psum  # noqa: E402
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM  # noqa: E402

# ------------------------------------------------- hand-built HLO fixtures
_SYNC = """
ENTRY %main {
  %p0 = f32[4,8] parameter(0)
  %mul = f32[4,8] multiply(%p0, %p0)
  %ar.1 = f32[4,8] all-reduce(%mul), replica_groups={}
  %add = f32[4,8] add(%ar.1, %p0)
  %ar.2 = f32[4,8] all-reduce(%add), replica_groups={}
  ROOT %out = f32[4,8] add(%ar.2, %mul)
}
"""

_ASYNC_OVERLAPPED = """
ENTRY %main {
  %p0 = f32[4,8] parameter(0)
  %ars.1 = (f32[4,8], f32[4,8]) all-reduce-start(%p0), replica_groups={}
  %mul = f32[4,8] multiply(%p0, %p0)
  %ard.1 = f32[4,8] all-reduce-done(f32[4,8] %ars.1)
  %ars.2 = (f32[4,8], f32[4,8]) all-reduce-start(%ard.1), replica_groups={}
  %mul2 = f32[4,8] multiply(%mul, %mul)
  %mul3 = f32[4,8] multiply(%mul2, %mul)
  %ard.2 = f32[4,8] all-reduce-done(f32[4,8] %ars.2)
  ROOT %out = f32[4,8] add(%ard.2, %mul3)
}
"""

_ASYNC_SERIALIZED = """
ENTRY %main {
  %p0 = f32[4,8] parameter(0)
  %ars = (f32[4,8], f32[4,8]) all-reduce-start(%p0), replica_groups={}
  %ard = f32[4,8] all-reduce-done(f32[4,8] %ars)
  ROOT %out = f32[4,8] add(%ard, %p0)
}
"""

# XLA's all-reduce combiner merged two collectives (f32 + sub-byte s8
# payloads) into ONE variadic async pair: the start's tuple carries the
# operand AND result halves
_ASYNC_VARIADIC = """
ENTRY %main {
  %p0 = f32[4,8] parameter(0)
  %p1 = s8[16] parameter(1)
  %ars = (f32[4,8], s8[16], f32[4,8], s8[16]) all-reduce-start(%p0, %p1), replica_groups={}
  %mul = f32[4,8] multiply(%p0, %p0)
  %ard = (f32[4,8], s8[16]) all-reduce-done((f32[4,8], s8[16]) %ars)
  ROOT %out = f32[4,8] add(%mul, %p0)
}
"""


def _report(name, text):
    colls, hosts = census(text)
    return HloAuditReport(name=name, collectives=colls,
                          host_transfers=hosts)


def test_census_sync_only_reports_zero_overlap():
    r = _report("sync", _SYNC)
    assert len(r.collectives) == 2
    assert all(not c.is_async and c.overlap == 0 for c in r.collectives)
    assert r.async_collectives == 0
    assert r.overlapped_collectives == 0
    assert r.overlap_frac == 0.0
    assert "overlap n/a (sync)" in r.summary()
    assert "compiled sync" in r.overlap_summary()


def test_census_async_fully_overlapped():
    r = _report("async", _ASYNC_OVERLAPPED)
    assert [c.is_async for c in r.collectives] == [True, True]
    # first pair hides the one multiply, second pair hides two
    assert [c.overlap for c in r.collectives] == [1, 2]
    assert r.async_collectives == 2
    assert r.overlapped_collectives == 2
    assert r.overlap_frac == 1.0
    assert "overlap 2/2 async" in r.summary()
    assert "2/2 async collective(s) overlapped" in r.overlap_summary()


def test_census_start_immediately_done_is_not_overlapped():
    """The async FORM alone buys nothing: a -start whose -done is the
    very next instruction hid zero compute and must count that way."""
    r = _report("serialized", _ASYNC_SERIALIZED)
    (c,) = r.collectives
    assert c.is_async and c.overlap == 0
    assert r.overlap_frac == 0.0
    # ...and it fails an overlap-demanding budget, naming the op
    with pytest.raises(CollectiveOverlapError) as ei:
        r.enforce(CollectiveBudget(all_reduce=1, min_overlap_frac=1.0))
    assert "0/1" in str(ei.value) and "all-reduce-start" in str(ei.value)


def test_census_min_overlap_frac_is_vacuous_for_sync_programs():
    """CPU backends compile collectives sync — the SAME overlap-demanding
    budget the tp2 registry entries declare must pass there, so the
    certification runs anywhere (and bites only where async pairs
    exist)."""
    budget = CollectiveBudget(all_reduce=2, min_overlap_frac=1.0)
    _report("sync", _SYNC).enforce(budget)  # must not raise
    # zero-collective programs pass too
    HloAuditReport(name="empty").enforce(
        CollectiveBudget(min_overlap_frac=1.0))
    # and a fully overlapped async program passes the same budget
    _report("async", _ASYNC_OVERLAPPED).enforce(budget)


def test_census_variadic_combiner_merged_form():
    """The merged start charges the RESULT half of its tuple — bytes the
    sync form(s) would report — with sub-byte-accurate s8 widths, and
    still tracks overlap until its (tuple-typed) done."""
    r = _report("variadic", _ASYNC_VARIADIC)
    (c,) = r.collectives
    assert c.is_async
    assert c.nbytes == 4 * 8 * 4 + 16  # f32[4,8] + s8[16], result half
    assert c.overlap == 1  # the one multiply before the done
    assert r.overlap_frac == 1.0


def test_census_bytes_and_counts_identical_sync_vs_async():
    """One budget certifies one traffic pattern regardless of how the
    backend compiled it: counts() and collective_bytes agree between the
    sync program and its async compilation, so a byte cap written
    against either holds for both."""
    sync = _report("s", _SYNC)
    async_ = _report("a", _ASYNC_OVERLAPPED)
    assert sync.counts() == async_.counts() == {"all-reduce": 2}
    assert sync.collective_bytes == async_.collective_bytes == 2 * 128
    cap = CollectiveBudget(all_reduce=2, max_collective_bytes=256)
    sync.enforce(cap)
    async_.enforce(cap)


def test_cli_overlap_view_and_child_forwarding(monkeypatch, capsys):
    """--overlap prints the per-collective view in-process, and a step
    respawned onto a forced CPU mesh carries the flag into the child
    command line (the child prints the view for us)."""
    rep = _report("engine_decode", _ASYNC_OVERLAPPED)
    monkeypatch.setattr(hlocheck, "run_step", lambda name: rep)
    rc = hlocheck.main(["--step", "engine_decode", "--overlap"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "2/2 async collective(s) overlapped" in out
    assert "overlap=2" in out

    import subprocess

    recorded = {}

    class _Done:
        returncode, stdout = 0, b""

    def fake_run(cmd, **kw):
        recorded["cmd"] = cmd
        return _Done()

    monkeypatch.setattr(subprocess, "run", fake_run)
    spec = hlocheck.StepSpec("fake", "doc", lambda: None, min_devices=99)
    hlocheck._run_in_subprocess(spec, overlap=True)
    assert "--overlap" in recorded["cmd"]
    hlocheck._run_in_subprocess(spec, overlap=False)
    assert "--overlap" not in recorded["cmd"]


# ------------------------------------------------ pipelined kernel parity
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


# (batch, heads, s, head_dim, page_size, pages_per_seq, npages, ctx):
# decode (1 query) and spec-verify (K+1 queries) — the two shapes the
# pipeline serves on the decode hot path
_PIPE_SHAPES = {
    "decode": (2, 2, 1, 8, 4, 4, 16, [5, 9]),
    "verify": (3, 4, 5, 16, 4, 8, 40, [10, 3, 17]),
}


@pytest.mark.parametrize("quant", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("mode", sorted(_PIPE_SHAPES))
def test_pipelined_chunks_match_composite(mode, quant):
    """Every chunking of the page row — including the 1-page chunk, the
    deepest pipeline — stays within fp32-accumulation tolerance of the
    composite: the online-softmax fold re-orders the reduction, so the
    pin is tight allclose, not bit-equality (that's the chunk == pps
    test below). Page accounting is exact: identical tables, ctx
    lengths, and output shape for every chunk."""
    shape = _PIPE_SHAPES[mode]
    pps = shape[5]
    args, kw = _args(3 + int(quant), *shape, quant=quant)
    ref = jax.jit(lambda *a: _composite(*a, **kw))(*args)
    for chunk in [c for c in (1, 2, 4) if c < pps] + [pps]:
        out = jax.jit(lambda *a, c=chunk: rp.ragged_paged_attention(
            *a, interpret=True, pipeline_chunk=c, **kw))(*args)
        assert out.shape == ref.shape
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5,
            err_msg=f"{mode}/{'int8' if quant else 'fp32'} chunk={chunk}")


def _table_wide_chunks(q, kp, vp, tab, ctx, chunk_kv, k_scale=None,
                       v_scale=None):
    """The composite masked chunk by chunk over the WHOLE table, in the
    pipelined kernel's order and operations (online softmax, -1e30 for a
    masked logit): what the kernel computed before its loop was bounded
    by ctx_lens, written in plain jnp."""
    b, h, s, d = q.shape
    if k_scale is not None:
        k_all = pa.paged_gather_quant(kp, k_scale, tab, q.dtype)
        v_all = pa.paged_gather_quant(vp, v_scale, tab, q.dtype)
    else:
        k_all, v_all = pa.paged_gather(kp, tab), pa.paged_gather(vp, tab)
    mask = pa.ragged_mask(ctx, k_all.shape[2], s)    # [b, 1, s, S]
    sc = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    m = jnp.full((b, h, s, 1), -1e30, jnp.float32)
    l = jnp.zeros((b, h, s, 1), jnp.float32)
    acc = jnp.zeros((b, h, s, d), jnp.float32)
    for j0 in range(0, k_all.shape[2], chunk_kv):
        kc, vc = k_all[:, :, j0:j0 + chunk_kv], v_all[:, :, j0:j0 + chunk_kv]
        logits = jnp.einsum("bhsd,bhjd->bhsj", q, kc,
                            preferred_element_type=jnp.float32) * sc
        logits = jnp.where(mask[..., j0:j0 + chunk_kv], logits,
                           jnp.float32(-1e30))
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhsj,bhjd->bhsd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        m = m_new
    return (acc / l).astype(q.dtype)


# mode -> (heads, s, head_dim, page_size, pages_per_seq, chunk pages)
_BOUND_SHAPES = {
    "decode": (2, 1, 8, 4, 8, 2),
    "verify": (4, 5, 16, 4, 8, 2),
    "chunk": (2, 8, 8, 4, 8, 4),
    "prefill-2-tiles": (2, 256, 8, 16, 32, 4),
}


@pytest.mark.parametrize("quant", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("mode", sorted(_BOUND_SHAPES))
def test_live_chunk_loop_matches_the_table_wide_one(mode, quant):
    """Same chunk, same bits. The kernel's loop runs to the last chunk a
    query tile can see, and a chunk it leaves out is one the mask zeroes
    whole (p == 0, alpha == 1 exactly): at a fixed ``pipeline_chunk`` its
    output EQUALS the table-wide chunk loop's, and stays within the
    pipelined path's tolerance of the composite. Rows: a live length on,
    one under and one over a chunk boundary, ctx 0, ctx + s at the
    table's end, and a dead slot whose garbage length lies beyond the
    table (clamped inside the kernel, every position visible)."""
    h, s, d, ps, pps, chunk = _BOUND_SHAPES[mode]
    ck, total = chunk * ps, ps * pps
    on = (-(-(s + 1) // ck) + 1) * ck     # a chunk boundary past s
    ctx_vals = [on - s, on - s - 1, on - s + 1, 0, total - s, 10 ** 6]
    b = len(ctx_vals)
    args, kw = _args(17 + int(quant), b, h, s, d, ps, pps, b * pps + 1,
                     ctx_vals, quant=quant)
    out = jax.jit(lambda *a: rp.ragged_paged_attention(
        *a, interpret=True, pipeline_chunk=chunk, **kw))(*args)
    wide = jax.jit(lambda *a: _table_wide_chunks(*a, ck, **kw))(*args)
    assert np.array_equal(np.asarray(out), np.asarray(wide)), \
        f"{mode}: the bounded loop changed a bit"
    ref = jax.jit(lambda *a: _composite(*a, **kw))(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_single_chunk_is_bit_identical_to_default():
    """chunk == pages_per_seq is the exact pre-pipeline path: same DMA
    plan, same op-for-op compute — bit-identical to calling without the
    knob (the tier-1 ragged suite's bit-identity pins ride this path)."""
    args, kw = _args(9, 2, 2, 1, 8, 4, 4, 16, [5, 9])
    base = jax.jit(lambda *a: rp.ragged_paged_attention(
        *a, interpret=True, **kw))(*args)
    pinned = jax.jit(lambda *a: rp.ragged_paged_attention(
        *a, interpret=True, pipeline_chunk=4, **kw))(*args)
    assert np.array_equal(np.asarray(base), np.asarray(pinned))


def test_bad_pipeline_chunk_falls_back_to_single_chunk():
    """A chunk that doesn't divide the call's page count (e.g. a tuned
    entry from a different window) must not crash or change numbers —
    the launch falls back to the exact single-chunk plan."""
    args, kw = _args(9, 2, 2, 1, 8, 4, 4, 16, [5, 9])
    base = jax.jit(lambda *a: rp.ragged_paged_attention(
        *a, interpret=True, **kw))(*args)
    for bad in (3, 0, -2, 8):
        out = jax.jit(lambda *a, c=bad: rp.ragged_paged_attention(
            *a, interpret=True, pipeline_chunk=c, **kw))(*args)
        assert np.array_equal(np.asarray(base), np.asarray(out)), bad


def test_tuned_dict_schema_and_stale_chunk_validation(monkeypatch):
    from paddle_tpu.analysis.kernelcheck import validate_ragged_tuned

    # dict schema: block_heads + pipeline_chunk resolved from the table
    monkeypatch.setattr(rp, "_tuned_table", lambda: {
        "16,8,128": {"block_heads": 4, "pipeline_chunk": 8,
                     "pages_per_seq": 32},
        "32,8,128": 2,  # legacy bare-int schema still resolves
    })
    assert rp.block_heads_for(16, 8, 128) == 4
    assert rp.pipeline_chunk_for(16, 8, 128, 32) == 8
    # the tuned chunk still divides a 24-page call (usable), but a
    # 20-page call can't mis-tile — fall back to the exact single chunk
    assert rp.pipeline_chunk_for(16, 8, 128, 24) == 8
    assert rp.pipeline_chunk_for(16, 8, 128, 20) == 20
    assert rp.block_heads_for(32, 8, 128) == 2
    assert rp.pipeline_chunk_for(32, 8, 128, 16) == 16  # legacy: no knob

    ok = {"16,8,128": {"block_heads": 4, "pipeline_chunk": 8,
                       "pages_per_seq": 32}}
    assert validate_ragged_tuned(ok) == []
    stale = {"16,8,128": {"block_heads": 4, "pipeline_chunk": 5,
                          "pages_per_seq": 32}}
    errs = validate_ragged_tuned(stale)
    assert errs and "stale" in errs[0]
    unknown = {"16,8,128": {"block_heads": 4, "pipeline_speed": 9}}
    assert validate_ragged_tuned(unknown)
    # a chunk with no divisibility anchor is unverifiable -> rejected
    anchorless = {"16,8,128": {"block_heads": 4, "pipeline_chunk": 8}}
    assert validate_ragged_tuned(anchorless)


def test_vmem_model_prices_double_buffered_staging():
    """chunk < pages_per_seq stages TWO buffers of chunk pages per pool:
    the dispatch-gate working set must price exactly that (the x2 the
    kernelcheck scratch certification matches), and chunk ==
    pages_per_seq must reproduce the pre-pipeline single-buffer number."""
    d, total_kv, nq, bh, pps = 128, 512, 1, 1, 32
    single = rp._vmem_working_set(d, total_kv, nq, bh, pps, False)
    pinned = rp._vmem_working_set(d, total_kv, nq, bh, pps, False,
                                  pipeline_chunk=pps)
    assert single == pinned
    chunked = rp._vmem_working_set(d, total_kv, nq, bh, pps, False,
                                   pipeline_chunk=8)
    chunk_kv = 8 * (total_kv // pps)
    # staging shrinks 32 pages -> 2 x 8 pages per pool (K and V, fp32) at
    # the padded footprint: the one-head block pads to an 8-sublane tile
    staging = 2 * (total_kv - 2 * chunk_kv) * 8 * d * 4
    # the body's live values shrink with the chunk too: the staged K and
    # V transposed heads-major, and the logits + probabilities (the one
    # query token pads to 8 sublanes)
    live = 2 * bh * (total_kv - chunk_kv) * d * 4 \
        + 2 * bh * 8 * (total_kv - chunk_kv) * 4
    assert single - chunked == staging + live


# ------------------------------------------------------- quantized psum
def test_quantized_psum_parity_and_safety():
    if len(jax.devices()) < 4:
        pytest.skip("needs the conftest 8-device CPU mesh")
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    f = jax.jit(jax.shard_map(
        lambda xs: quantized_psum(xs[0], "tp"), mesh=mesh,
        in_specs=(P("tp", None, None),), out_specs=P()))

    x = np.random.RandomState(0).randn(4, 8, 97).astype(np.float32) * 3
    out, exact = np.asarray(f(jnp.asarray(x))), x.sum(0)
    rel = np.abs(out - exact).max() / np.abs(exact).max()
    assert rel < 0.05, rel
    # greedy decisions survive: the argmax rows agree
    assert (out.argmax(-1) == exact.argmax(-1)).mean() >= 0.9
    # all-zero input: the step guard keeps it NaN-free and exact
    z = np.asarray(f(jnp.zeros((4, 8, 97), np.float32)))
    assert np.all(z == 0)
    # overflow safety: identical extreme shards sum WITHOUT int8 wrap
    # (the shared step is sum(absmax)/(127-n), so accumulated codes are
    # provably < 127) — a naive absmax/127 scale wraps here
    e = np.full((4, 8, 97), 1e4, np.float32)
    oe = np.asarray(f(jnp.asarray(e)))
    assert np.all(oe > 0), "int8 accumulator wrapped"
    assert np.abs(oe - e.sum(0)).max() / 4e4 < 0.05


# --------------------------------------------------- engine level (TP=2)
HIDDEN, LAYERS, HEADS, VOCAB = 32, 2, 4, 97


@pytest.fixture(scope="module")
def model():
    if len(jax.devices()) < 2:
        pytest.skip("needs the conftest 8-device CPU mesh")
    paddle.seed(31)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
        num_heads=HEADS, max_seq_len=48, dropout=0.0))
    m.eval()
    return m


def _drive(model, prompts, budgets, **kw):
    sched_mod._rid_counter = itertools.count(9000)
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=24, page_size=4, max_prompt_len=8,
        tensor_parallel=2, **kw))
    rids = [eng.add_request(p, b) for p, b in zip(prompts, budgets)]
    outs = eng.run()
    return [outs[r] for r in rids], eng


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, (n,)).astype(np.int32) for n in lens]


def test_budget_shapes_quantized_and_overlap():
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=HIDDEN,
                    num_layers=LAYERS, num_heads=HEADS)
    plain = TPContext(2, cfg).step_budget(batch=2, seq=1)
    assert plain.all_reduce == 2 * LAYERS + 1
    assert plain.min_overlap_frac == 0.0
    ov = TPContext(2, cfg, overlap_scheduler=True).step_budget(2, 1)
    assert ov.all_reduce == 2 * LAYERS + 1
    assert ov.min_overlap_frac == 1.0
    q = TPContext(2, cfg, quantized_logits=True).step_budget(2, 1)
    assert q.all_reduce == 2 * LAYERS + 2
    f32_logits, q_logits = 2 * 1 * VOCAB * 4, 2 * 1 * VOCAB * 1 + 4
    assert plain.max_collective_bytes - q.max_collective_bytes == \
        f32_logits - q_logits


def test_overlap_on_quantized_off_is_bit_identical(model):
    """tp_overlap_scheduler changes WHEN collectives run, never what
    they compute — and is a declared no-op on backends without the
    scheduler (CPU) — so the token streams must match the baseline
    sharded engine bit for bit. tp_quantized_logits=False must too: the
    quantized branch never traces."""
    prompts, budgets = _prompts(4, (3, 6)), [6, 5]
    ref, _ = _drive(model, prompts, budgets)
    outs, eng = _drive(model, prompts, budgets,
                       tp_overlap_scheduler=True,
                       tp_quantized_logits=False)
    assert all(np.array_equal(a, b) for a, b in zip(ref, outs))
    assert eng.compile_counts == {"prefill": 1, "decode": 1}


def test_quantized_logits_census_divergence_and_gauges(model):
    """The acceptance pins in one sharded debug_checks engine: the
    quantized decode program audits at 2L+2 all-reduces with census
    bytes UNDER the f32 budget's cap (the measurable bytes/token
    shrink), greedy outputs diverge boundedly (mean common-prefix >=
    0.5 vs quantized-off), zero retraces, and the overlap/bytes gauges
    are pre-seeded then fed at the first-trace audit."""
    prompts, budgets = _prompts(4, (3, 6)), [6, 5]
    ref, _ = _drive(model, prompts, budgets)
    outs, eng = _drive(model, prompts, budgets, debug_checks=True,
                       tp_overlap_scheduler=True,
                       tp_quantized_logits=True)

    # bounded greedy divergence (the kvq idiom: loose bound, tight
    # measurement — these toy streams measure 1.0 most seeds)
    fracs = []
    for a, b in zip(ref, outs):
        common = 0
        for x, y in zip(a, b):
            if x != y:
                break
            common += 1
        fracs.append(common / len(a))
    assert np.mean(fracs) >= 0.5, f"divergence too high: {fracs}"

    # the compiled census: exactly 2L+2 all-reduces, bytes under the
    # unquantized budget's cap — the shrink is measured, not assumed
    report = eng.hlo_audits["decode"]
    assert report.counts() == {"all-reduce": 2 * LAYERS + 2}
    f32_cap = TPContext(2, model.cfg).step_budget(
        batch=2, seq=1).max_collective_bytes
    assert report.collective_bytes < f32_cap
    assert eng.compile_counts == {"prefill": 1, "decode": 1}
    assert all(g.retraces == 0 for g in eng.guards.values())

    # gauges: seeded names present; bytes/token fed and under the f32
    # cap per token; overlap_frac fed (0.0 — CPU compiles these sync)
    snap = eng.metrics.snapshot()
    assert "serving_tp_collective_overlap_frac" in snap
    bpt = snap["serving_tp_collective_bytes_per_token"]
    assert 0 < bpt < f32_cap / 2
    assert snap["serving_tp_collective_overlap_frac"] == 0.0


def test_registry_quantized_logits_step_certifies():
    """The tp2_engine_decode_qlogits REGISTRY entry certifies end to end
    on this process's mesh (conftest forces 8 CPU devices): budget
    2L+2, int8 logits payload counted bit-accurately, overlap contract
    declared."""
    if len(jax.devices()) < 2:
        pytest.skip("needs the conftest 8-device CPU mesh")
    report = hlocheck.run_step("tp2_engine_decode_qlogits")
    assert report.counts() == {"all-reduce": 2 * 2 + 2}
    sync_bytes = hlocheck.run_step("tp2_engine_decode").collective_bytes
    assert report.collective_bytes < sync_bytes
