"""The grouped-head decode kernel (``kernels/paged_decode.py``) through the
Pallas interpreter on the CPU, against ``_grouped_composite`` on the same
pools: group sizes, dtypes, contexts on and around a chunk's edge, the
gate's reasons, the dispatch, and the host's count of staged pages against
the kernel's own loop bound.

Sizes are tiny (2 KV heads of 8, pages of 4 tokens, 8 pages a row) and the
chunk is patched to 8 tokens so that a full row is four turns of the
loop. float32 agrees to round-off (another order of the same sums);
bfloat16 to the rounding of the probabilities (the kernel rounds them
before the division by their sum, the composite after it).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — x64 on, as in production
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import paged_decode as pd
from paddle_tpu.kernels import ragged_paged_attention as rp
from paddle_tpu.utils.flags import flag, set_flags

KV, D, PAGE, PPS, PAGES = 2, 8, 4, 8, 40
CHUNK = 8                      # tokens: two pages
TOTAL = PAGE * PPS
TOL = {"float32": 2e-6, "bfloat16": 2e-2}
SCALE = 0.25


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pd, "_GQA_CHUNK_TOKENS", CHUNK)
    before = flag("FLAGS_ragged_interpret", False)
    set_flags({"FLAGS_ragged_interpret": True})
    yield
    set_flags({"FLAGS_ragged_interpret": before})


def operands(g, dtype, rows, seed=0):
    rng = np.random.default_rng(seed)
    dtype = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(rows, KV * g, 1, D)), dtype)
    k = jnp.asarray(rng.normal(size=(PAGES, PAGE, KV * D)), dtype)
    v = jnp.asarray(rng.normal(size=(PAGES, PAGE, KV * D)), dtype)
    table = jnp.asarray(rng.integers(1, PAGES, (rows, PPS)), jnp.int32)
    return q, k, v, table


def gap(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


@functools.lru_cache(maxsize=None)
def both_paths():
    """(kernel, composite), each jitted once a shape: a context is an
    operand, so the cases of one group size and dtype share a trace."""
    return (jax.jit(lambda q, k, v, t, c: pd.gqa_decode_attention(
                q, k, v, t, c, SCALE, interpret=True)),
            jax.jit(lambda q, k, v, t, c: pa._grouped_composite(
                q, k, v, t, c, SCALE)))


# ------------------------------------------------- kernel vs the composite
@pytest.mark.parametrize("ctx", [0, 1, CHUNK - 1, CHUNK, TOTAL - 1, 1000],
                         ids=["one_token", "two_tokens", "chunk_edge",
                              "past_the_edge", "full_table",
                              "past_the_table"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("g", [1, 4, 8])
def test_kernel_is_the_composite(interpret, g, dtype, ctx):
    """One row at context ``ctx`` (the new token is position ``ctx``): a
    length of one chunk exactly, one token more, the whole table, and a
    dead slot's garbage length, which the kernel clamps inside the table
    and the composite's mask admits whole."""
    q, k, v, table = operands(g, dtype, 1)
    kernel, composite = both_paths()
    c = jnp.asarray([ctx], jnp.int32)
    got, want = kernel(q, k, v, table, c), composite(q, k, v, table, c)
    assert got.shape == want.shape == (1, KV * g, 1, D)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    assert gap(got, want) < TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_of_one_batch_differ(interpret, dtype):
    """Five rows whose lengths end in different chunks: the pipeline hands
    over from a row's last chunk to the next row's first whatever buffer
    it ends in (odd and even chunk counts), and every row is its own
    one-row call."""
    q, k, v, table = operands(4, dtype, 5, seed=1)
    ctx = jnp.asarray([TOTAL - 1, 0, CHUNK, 3 * CHUNK - 1, 5000], jnp.int32)
    kernel, composite = both_paths()
    got = kernel(q, k, v, table, ctx)
    assert gap(got, composite(q, k, v, table, ctx)) < TOL[dtype]
    for r in range(5):
        alone = kernel(q[r:r + 1], k, v, table[r:r + 1], ctx[r:r + 1])
        assert jnp.array_equal(alone[0], got[r])


def test_what_the_mask_zeroes_does_not_reach_the_result(interpret):
    """Positions past ``ctx`` hold what a page's last owner left: it must
    not reach the output, in the chunk that holds the context's end or in
    the chunks left out. Keys of any kind there (a score is replaced, not
    scaled) and values of any finite size (their probability is an exact
    zero; the composite's is too)."""
    q, k, v, table = operands(4, "float32", 1)
    ctx = CHUNK + 2
    table = jnp.asarray(np.arange(1, PPS + 1)[None], jnp.int32)
    dirty = np.asarray(v).copy()
    dirty_k = np.asarray(k).copy()
    page, off = divmod(ctx + 1, PAGE)
    dirty[page + 1, off:] = 1e30           # behind the new token, its page
    dirty[page + 2:] = -1e30               # and every later page
    dirty_k[page + 1, off:] = np.inf
    kernel, _ = both_paths()
    c = jnp.asarray([ctx], jnp.int32)
    clean = kernel(q, k, v, table, c)
    got = kernel(q, jnp.asarray(dirty_k), jnp.asarray(dirty), table, c)
    assert bool(jnp.isfinite(got).all())
    assert jnp.array_equal(got, clean)


# ------------------------------------------------------------- the gate
GATE = dict(heads=32, kv_heads=8, head_dim=64, page_size=16,
            pages_per_seq=96)


@pytest.mark.parametrize("change, reason", [
    (dict(flags_on=False), "FLAGS_use_pallas_kernels is off"),
    (dict(on_tpu=False), "FLAGS_ragged_interpret"),
    (dict(num_query_tokens=512), "one token a row"),
    (dict(flat_pool=False), "lane-dense pool"),
    (dict(heads=30), "do not group over 8 KV heads"),
    (dict(kv_heads=4, heads=32, head_dim=24), "not whole 128-lane rows"),
    (dict(heads=4096), "VMEM working set"),
], ids=["flag_off", "cpu", "several_tokens", "heads_axis", "no_grouping",
        "lane_rows", "vmem"])
def test_the_gate_names_what_blocks_the_kernel(change, reason):
    ok, why = pd.gqa_kernel_eligible(**dict(GATE, **change))
    assert not ok and reason in why, why


def test_the_gate_holds_at_the_serving_shape_and_in_the_interpreter():
    """granite-4.0-h-micro's attention (32 heads over 8 of 64, pages of
    16, a table of 96) is eligible on a TPU; on the CPU the interpreter
    admits it, and a pool row that is no whole lane row with it (the
    interpreter has no lanes)."""
    assert pd.gqa_kernel_eligible(**GATE) == (True, "")
    assert pd.gqa_kernel_eligible(**GATE, on_tpu=False,
                                  interpret=True) == (True, "")
    small = dict(heads=8, kv_heads=KV, head_dim=D, page_size=PAGE,
                 pages_per_seq=PPS)
    assert not pd.gqa_kernel_eligible(**small)[0]
    assert pd.gqa_kernel_eligible(**small, on_tpu=False, interpret=True)[0]


# ----------------------------------------------------------- the dispatch
@pytest.mark.parametrize("s, path", [(1, "kernel"), (3, "composite")])
def test_dispatch_takes_the_kernel_for_one_token_a_row(interpret,
                                                       monkeypatch, s, path):
    """On the same pools ``paged_attention`` runs the kernel for one new
    token a row and the composite for several (a prefill, a chunk's
    tail), and both give the composite's numbers."""
    q, k, v, table = operands(4, "float32", 2)
    q = jnp.tile(q, (1, 1, s, 1))
    ctx = jnp.asarray([5, 17], jnp.int32)
    took = []
    real_kernel, real_composite = pd.gqa_decode_attention, \
        pa._grouped_composite
    monkeypatch.setattr(
        pd, "gqa_decode_attention",
        lambda *a, **kw: took.append("kernel") or real_kernel(*a, **kw))
    monkeypatch.setattr(
        pa, "_grouped_composite",
        lambda *a, **kw: took.append("composite") or real_composite(*a, **kw))
    got = pa.paged_attention(q, k, v, table, ctx, scale=SCALE)
    assert took == [path]
    assert gap(got, real_composite(q, k, v, table, ctx, SCALE)) < 2e-6


@pytest.mark.parametrize("case", ["heads_axis", "cpu_no_interpreter"])
def test_what_the_gate_refuses_takes_the_composite(monkeypatch, case):
    q, k, v, table = operands(4, "float32", 2)
    ctx = jnp.asarray([5, 17], jnp.int32)
    want = pa._grouped_composite(q, k, v, table, ctx, SCALE)
    if case == "heads_axis":
        set_flags({"FLAGS_ragged_interpret": True})
        k, v = (a.reshape(PAGES, PAGE, KV, D) for a in (k, v))
    monkeypatch.setattr(pd, "decode_kernel_call", None)   # never reached
    try:
        got = pa.paged_attention(q, k, v, table, ctx, scale=SCALE)
    finally:
        set_flags({"FLAGS_ragged_interpret": False})
    assert jnp.array_equal(got, want)


def test_an_eligible_kernel_that_fails_raises(interpret, monkeypatch):
    """Nothing turns a kernel failure into a composite result."""
    def boom(*a, **kw):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(pd, "decode_kernel_call", boom)
    q, k, v, table = operands(4, "float32", 1)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        pa.paged_attention(q, k, v, table, jnp.asarray([3], jnp.int32),
                           scale=SCALE)


def test_grouped_heads_have_no_int8_path():
    q, k, v, table = operands(4, "float32", 1)
    with pytest.raises(ValueError, match="no int8 path"):
        pa.paged_attention(q, k, v, table, jnp.asarray([3], jnp.int32),
                           k_scale=jnp.ones((PAGES, KV)),
                           v_scale=jnp.ones((PAGES, KV)))


# ------------------------------------------- the host's count of the loop
def test_pages_staged_is_the_kernel_s_own_loop_bound(interpret):
    """``grouped_pages_staged_fn`` for a decode launch counts, for every
    ``ctx_lens`` from an empty row to a garbage length, the chunks the
    kernel's loop runs (the one ``_live_span``, traced in the kernel's
    own primitives here) times the pages a chunk; for a launch of several
    tokens a row, the table's width (the composite gathers it)."""
    ctx = np.r_[np.arange(0, TOTAL + 3), 50_000].astype(np.int32)
    staged = pa.grouped_pages_staged_fn(8, KV, D, PAGE, PPS, 1, itemsize=4)
    chunk_pages = pd.gqa_chunk_pages(PAGE, PPS)
    assert chunk_pages == CHUNK // PAGE

    def traced(c):
        length = jax.lax.clamp(np.int32(1), c + np.int32(1), np.int32(TOTAL))
        return rp._live_span(length, CHUNK, PAGE, TOTAL, ops=rp._LAX)[0]

    chunks = np.asarray(jax.vmap(traced)(jnp.asarray(ctx)))
    assert staged(ctx).tolist() == (chunks * chunk_pages).tolist()
    assert staged(ctx)[0] == chunk_pages and staged(ctx)[-1] == PPS
    several = pa.grouped_pages_staged_fn(8, KV, D, PAGE, PPS, 16,
                                         itemsize=4)
    assert several(ctx).tolist() == [PPS] * len(ctx)


def test_pages_staged_is_the_table_s_width_without_the_kernel():
    """Where the gate refuses the kernel (the CPU without the interpreter)
    a decode launch is counted as the composite stages it."""
    staged = pa.grouped_pages_staged_fn(8, KV, D, PAGE, PPS, 1, itemsize=4)
    assert staged(np.asarray([0, 9, 31])).tolist() == [PPS] * 3


# ------------------------------------------------------- a window layer's
WINDOW = 12                     # a chunk and a half: its edge off a page's


@functools.lru_cache(maxsize=None)
def windowed(window):
    return (jax.jit(lambda q, k, v, t, c: pd.gqa_decode_attention(
                q, k, v, t, c, SCALE, interpret=True, window=window)),
            jax.jit(lambda q, k, v, t, c: pa._grouped_composite(
                q, k, v, t, c, SCALE, window)))


def plain_formula(q, k, v, table, ctx, window):
    """Attention of the token at position ``ctx`` to positions ``ctx -
    window + 1 .. ctx`` (from 0), each head to its KV head, float64."""
    heads, g = q.shape[1], q.shape[1] // KV
    rows = np.asarray(k, np.float64)[np.asarray(table[0])].reshape(
        TOTAL, KV, D)
    vals = np.asarray(v, np.float64)[np.asarray(table[0])].reshape(
        TOTAL, KV, D)
    lo = max(0, ctx + 1 - window)
    out = np.zeros((heads, D))
    for h in range(heads):
        s = rows[lo:ctx + 1, h // g] @ np.asarray(q, np.float64)[0, h, 0] \
            * SCALE
        w = np.exp(s - s.max())
        out[h] = (w / w.sum()) @ vals[lo:ctx + 1, h // g]
    return out


@pytest.mark.parametrize("ctx", [0, 5, WINDOW - 2, WINDOW - 1, WINDOW,
                                 WINDOW + 1, 2 * CHUNK - 1, 2 * CHUNK + 3,
                                 TOTAL - 1],
                         ids=lambda c: f"ctx{c}")
@pytest.mark.parametrize("g", [1, 4])
def test_window_kernel_is_the_plain_formula(interpret, g, ctx):
    """Contexts under, at and over the window, its first position on and
    off a page's and a chunk's edge: the kernel, the composite and the
    formula written out agree (float32 round-off)."""
    q, k, v, table = operands(g, "float32", 1)
    kernel, composite = windowed(WINDOW)
    c = jnp.asarray([ctx], jnp.int32)
    got = kernel(q, k, v, table, c)
    want = plain_formula(q, k, v, table, ctx, WINDOW)
    assert got.shape == (1, KV * g, 1, D)
    assert np.abs(np.asarray(got)[0, :, 0] - want).max() < TOL["float32"]
    assert gap(got, composite(q, k, v, table, c)) < TOL["float32"]


def test_window_none_is_the_kernel_it_was(interpret):
    """``window=None`` traces the kernel without the argument, operand for
    operand (the same jaxpr), and a window no context reaches gives its
    result bit for bit."""
    q, k, v, table = operands(4, "bfloat16", 3, seed=2)
    ctx = jnp.asarray([3, CHUNK, TOTAL - 1], jnp.int32)
    plain = lambda *a: pd.gqa_decode_attention(  # noqa: E731
        *a, SCALE, interpret=True)
    none = lambda *a: pd.gqa_decode_attention(  # noqa: E731
        *a, SCALE, interpret=True, window=None)
    args = (q, k, v, table, ctx)
    assert str(jax.make_jaxpr(plain)(*args)) \
        == str(jax.make_jaxpr(none)(*args))
    assert "gqa_decode_attention_window" not in str(
        jax.make_jaxpr(none)(*args))
    wide = windowed(TOTAL + 5)[0]
    assert jnp.array_equal(wide(*args), both_paths()[0](*args))


def test_window_rows_of_one_batch_and_what_lies_behind(interpret):
    """Five rows whose windows start in different chunks (the pipeline
    hands a row's last chunk over to the next row's FIRST LIVE chunk), and
    behind every window keys of any kind and values of any finite size,
    on freed pages' columns too (the null page): none of it reaches the
    result."""
    q, k, v, _ = operands(4, "float32", 5, seed=1)
    table = np.tile(np.arange(1, PPS + 1), (5, 1))
    ctx = np.asarray([TOTAL - 1, 2, CHUNK + 1, 3 * CHUNK - 1, 5000], np.int32)
    kernel, composite = windowed(WINDOW)
    clean = kernel(q, k, v, jnp.asarray(table, jnp.int32), jnp.asarray(ctx))
    # (the last row is a dead slot's garbage length: the kernel clamps it
    # inside the table, the composite's mask hides every position)
    assert gap(clean[:4], composite(
        q, k, v, jnp.asarray(table, jnp.int32),
        jnp.asarray(ctx))[:4]) < TOL["float32"]
    # behind each row's window: the table names the null page, as the
    # cache leaves a freed page's column, and the null page holds dirt
    freed = table.copy()
    for r, c in enumerate(np.minimum(ctx, TOTAL - 1)):
        freed[r, :max(0, c + 1 - WINDOW) // PAGE] = 0
    dirty_k, dirty_v = np.asarray(k).copy(), np.asarray(v).copy()
    dirty_k[0], dirty_v[0] = 1e4, -1e30
    got = kernel(q, jnp.asarray(dirty_k), jnp.asarray(dirty_v),
                 jnp.asarray(freed, jnp.int32), jnp.asarray(ctx))
    assert jnp.array_equal(got, clean)
    for r in range(5):
        alone = kernel(q[r:r + 1], k, v, jnp.asarray(table[r:r + 1],
                                                     jnp.int32),
                       jnp.asarray(ctx[r:r + 1]))
        assert jnp.array_equal(alone[0], clean[r])


def test_pages_staged_with_a_window_is_the_kernel_s_own_loop(interpret):
    """The host's count for a window layer's decode launch: the chunks
    from the first that holds a position inside the window
    (``_live_start``, traced here in the kernel's own primitives) to the
    last live one, times the pages a chunk, for every ``ctx_lens``."""
    ctx = np.r_[np.arange(0, TOTAL + 3), 50_000].astype(np.int32)
    staged = pa.grouped_pages_staged_fn(8, KV, D, PAGE, PPS, 1, itemsize=4,
                                        window=WINDOW)
    whole = pa.grouped_pages_staged_fn(8, KV, D, PAGE, PPS, 1, itemsize=4)
    chunk_pages = pd.gqa_chunk_pages(PAGE, PPS)

    def traced(c):
        length = jax.lax.clamp(np.int32(1), c + np.int32(1), np.int32(TOTAL))
        end = rp._live_span(length, CHUNK, PAGE, TOTAL, ops=rp._LAX)[0]
        start, lo = rp._live_start(c + np.int32(1), CHUNK, WINDOW, TOTAL,
                                   ops=rp._LAX)
        return end - start, lo

    chunks, lo = (np.asarray(a) for a in jax.vmap(traced)(jnp.asarray(ctx)))
    assert staged(ctx).tolist() == (chunks * chunk_pages).tolist()
    assert lo[:TOTAL].tolist() == np.maximum(
        0, ctx[:TOTAL] + 1 - WINDOW).tolist()
    # never more than the whole context's, and at most the chunks a window
    # can touch: 12 positions lie in at most 3 chunks of 8
    assert (staged(ctx) <= whole(ctx)).all()
    assert staged(ctx).max() == 3 * chunk_pages < whole(ctx).max() == PPS
    assert staged(ctx)[0] == chunk_pages


# ------------------------------------- a loop: several loops' keys a row
LOOPS = 3


@pytest.mark.parametrize("loop", [0, 1, LOOPS - 1])
@pytest.mark.parametrize("g", [1, 4])
def test_loop_operand_is_copy_then_kernel(interpret, g, loop):
    """Pools whose rows hold three loops' keys side by side: the kernel
    given the loop reads loop ``loop``'s columns of each live page in
    place, and gives bit for bit what the kernel without the operand gives
    over a copy of those columns; the composite over the same columns
    agrees. Rows at contexts inside, on and past a chunk's edge, and every
    other loop's columns hold values of any size: none reaches the
    result."""
    rng = np.random.default_rng(7)
    q, _, _, table = operands(g, "bfloat16", 3, seed=4)
    k = jnp.asarray(rng.normal(size=(PAGES, PAGE, LOOPS * KV * D)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(PAGES, PAGE, LOOPS * KV * D)),
                    jnp.bfloat16)
    cols = slice(loop * KV * D, (loop + 1) * KV * D)
    # the other loops' columns: large, so that a wrong column shows
    others = np.ones(LOOPS * KV * D, bool)
    others[cols] = False
    k = k.at[..., others].multiply(1e3)
    v = v.at[..., others].multiply(1e3)
    ctx = jnp.asarray([3, CHUNK, TOTAL - 1], jnp.int32)
    looped = jax.jit(lambda q, k, v, t, c, i: pd.gqa_decode_attention(
        q, k, v, t, c, SCALE, interpret=True, loop=i, loops=LOOPS))
    got = looped(q, k, v, table, ctx, jnp.int32(loop))
    copied = both_paths()[0](q, k[..., cols], v[..., cols], table, ctx)
    assert jnp.array_equal(got, copied)
    composite = pa._grouped_composite(q, k, v, table, ctx, SCALE, None,
                                      jnp.int32(loop), LOOPS)
    assert gap(got, composite) < TOL["bfloat16"]
    assert "gqa_decode_attention_loop" in str(jax.make_jaxpr(
        looped)(q, k, v, table, ctx, jnp.int32(loop)))


@pytest.mark.parametrize("tokens", [1, 6], ids=["rows", "pages"])
def test_paged_write_of_a_loop_touches_its_columns_alone(tokens):
    """``paged_write_loop(..., loop=1)``: a token's keys land in columns
    ``[w, 2 w)`` of its row, every other column and row as it was; one
    token a row is written by rows, several by the pages they touch (a row
    from position 5, across a page's edge, and a row of no real token,
    which writes nothing but the null page)."""
    rng = np.random.default_rng(3)
    w = KV * D
    k = jnp.asarray(rng.normal(size=(PAGES, PAGE, LOOPS * w)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(3, tokens, w)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, PAGES))[:3 * PPS]
                        .reshape(3, PPS), jnp.int32)
    ctx = jnp.asarray([5, 0, 9], jnp.int32)
    valid = jnp.asarray([[True] * tokens, [True] * tokens,
                         [False] * tokens])
    k2, v2 = pa.paged_write_loop(k, k + 1, new, new, table, ctx, valid,
                                 jnp.int32(1))
    for base, got in ((k, k2), (k + 1, v2)):
        want = np.asarray(base).copy()
        for b in range(2):
            for t in range(tokens):
                pos = int(ctx[b]) + t
                want[table[b, pos // PAGE], pos % PAGE, w:2 * w] = new[b, t]
        # the null page aside
        assert np.array_equal(np.asarray(got)[1:], want[1:])
    rows = pa.loop_pages(k2, table[:1], jnp.int32(1), w)
    assert rows.shape == (1, PPS, PAGE, w)
    assert np.array_equal(np.asarray(rows[0, 1, 1]), np.asarray(new[0, 0]))


#: the jaxpr of ``gqa_decode_attention`` without a loop (and with a window
#: of 128) at a fixed shape, hashed on the parent of PR 39: the looped
#: instance adds nothing to what cells G and M trace
_TODAY = {None: "cc7eef8447a478545ed235bd5e0b79e67779066b6978dd3414eaadf22b4"
                "eaf0e",
          128: "f19d4fe213792cb3caaf201187fa54833152cc508568daa868fbd10d4b96"
               "de5c"}


@pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
def test_without_the_loop_the_kernel_traces_as_it_did(window):
    import hashlib

    def sds(s, d):
        return jax.ShapeDtypeStruct(s, d)

    q = sds((2, 16, 1, 128), jnp.bfloat16)
    pool = sds((64, 16, 2048), jnp.bfloat16)
    text = str(jax.make_jaxpr(
        lambda q, k, v, t, c: pd.gqa_decode_attention(
            q, k, v, t, c, 0.088, interpret=False, window=window))(
        q, pool, pool, sds((2, 40), jnp.int32), sds((2,), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest() == _TODAY[window]
