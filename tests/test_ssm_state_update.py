"""The Mamba-2 recurrence in its three forms (``kernels/ssm_state_update``
and ``text/granite_hybrid``): the plain float32 recurrence token by token,
the chunked scan a prefill runs, and the decode kernel through the Pallas
interpreter. Everything is float32 on the CPU, so a tolerance is round-off
alone: 2e-6 on states of order 1 (the same products summed in another
order: a chunk's 8-16 terms), 2e-5 on outputs that sum 128 of them; a
dropped decay, a wrong chunk boundary or a state written to the wrong slot
shows at 1e-2 and up.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.kernels import ssm_state_update as su
from paddle_tpu.text.granite_hybrid import ssd_chunked, ssd_sequential
from paddle_tpu.utils.flags import flag, set_flags

STATE_TOL, OUT_TOL = 2e-6, 2e-5
SLOTS, HEADS, P, N = 6, 8, 16, 128

MASKS = {
    "all_live": [1, 1, 1, 1, 1, 1],
    "dead_between": [0, 1, 0, 0, 1, 0],
    "none_live": [0, 0, 0, 0, 0, 0],
    "last_only": [0, 0, 0, 0, 0, 1],
    "first_only": [1, 0, 0, 0, 0, 0],
}


def operands(seed=0, slots=SLOTS, heads=HEADS, p=P, n=N):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(
        state=f(slots, heads, p, n), x=f(slots, heads, p),
        dt=jnp.asarray(rng.uniform(0.001, 0.5, (slots, heads)), jnp.float32),
        a=-jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32),
        b_in=f(slots, n), c_out=f(slots, n))


@pytest.fixture
def interpret():
    before = flag("FLAGS_ragged_interpret", False)
    yield lambda on: set_flags({"FLAGS_ragged_interpret": on})
    set_flags({"FLAGS_ragged_interpret": before})


@pytest.mark.parametrize("heads", [HEADS, 128])
@pytest.mark.parametrize("mask", MASKS, ids=list(MASKS))
def test_kernel_is_the_recurrence_and_skips_dead_slots(mask, heads):
    """Every slot mask, at a small layer and at one of 128 heads (another
    Mamba-2 width: two blocks of 64 heads a slot)."""
    o = operands(heads=heads)
    active = jnp.asarray(MASKS[mask], bool)
    want_s, want_y = su.ssm_update_reference(**o, active=active)
    got_s, got_y = su.ssm_decode_update(**o, active=active, interpret=True)
    assert float(jnp.max(jnp.abs(got_s - want_s))) < STATE_TOL
    assert float(jnp.max(jnp.abs(got_y - want_y))) < OUT_TOL
    dead = ~np.asarray(active)
    # a dead slot's state is bit for bit what it was; its output is 0
    assert np.array_equal(np.asarray(got_s)[dead], np.asarray(o["state"])[dead])
    assert not np.asarray(got_y)[dead].any()


@pytest.mark.parametrize("mask, rows", [
    ("all_live", [0, 1, 2, 3, 4, 5]), ("dead_between", [1, 1, 1, 1, 4, 4]),
    ("none_live", [0, 0, 0, 0, 0, 0]), ("last_only", [5, 5, 5, 5, 5, 5]),
    ("first_only", [0, 0, 0, 0, 0, 0])])
def test_a_dead_slot_names_the_live_block_the_pipeline_holds(mask, rows):
    """A run of dead slots names ONE block, the live slot's before it (the
    first live one's, ahead of it): consecutive grid steps with the same
    block index copy nothing."""
    got = su.live_rows(jnp.asarray(MASKS[mask], bool))
    assert got.dtype == jnp.int32 and got.tolist() == rows


@pytest.mark.parametrize("heads, block", [(HEADS, 4), (128, 32)])
def test_kernel_takes_heads_in_blocks(monkeypatch, heads, block):
    """Blocks of fewer heads than the layer has: the grid's outer axis.
    A block's decays, ``dt * x`` columns and ``y`` are whole blocks of
    their own arrays, so any block that divides the heads is legal."""
    monkeypatch.setitem(su._TUNED, "block_heads", block)
    assert su._block_heads(heads) == block
    o = operands(seed=1, heads=heads)
    active = jnp.asarray(MASKS["dead_between"], bool)
    want_s, want_y = su.ssm_update_reference(**o, active=active)
    got_s, got_y = su.ssm_decode_update(**o, active=active, interpret=True)
    assert float(jnp.max(jnp.abs(got_s - want_s))) < STATE_TOL
    assert float(jnp.max(jnp.abs(got_y - want_y))) < OUT_TOL


@pytest.mark.parametrize("heads, p, n, kw, ok, reason", [
    (64, 64, 128, {}, True, ""),
    (128, 64, 128, {}, True, ""),
    (64, 64, 128, dict(flags_on=False), False, "FLAGS_use_pallas_kernels"),
    (64, 64, 128, dict(on_tpu=False), False, "CPU backend"),
    (64, 64, 96, {}, False, "128-lane rows"),
    (64, 60, 128, {}, False, "8-sublane"),
    (64, 60, 96, dict(on_tpu=False, interpret=True), True, ""),
])
def test_the_gate_names_what_blocks_the_kernel(heads, p, n, kw, ok, reason):
    got, why = su.ssm_kernel_eligible(heads, p, n, **kw)
    assert got is ok and reason in why


@pytest.mark.parametrize("kernel", [False, True])
def test_dispatch_counts_the_rows_it_moves(interpret, kernel):
    """Under the kernel what moves is what is live; the plain recurrence
    rewrites the pool, every slot of it."""
    interpret(kernel)
    o = operands(seed=2)
    active = jnp.asarray(MASKS["dead_between"], bool)
    want_s, want_y = su.ssm_update_reference(**o, active=active)
    got_s, got_y, moved = su.ssm_update(*o.values(), active)
    assert float(jnp.max(jnp.abs(got_s - want_s))) < STATE_TOL
    assert float(jnp.max(jnp.abs(got_y - want_y))) < OUT_TOL
    assert int(moved) == (2 if kernel else SLOTS)


def sequence(seed, b, s, heads=4, p=8, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)  # noqa: E731
    return dict(
        x=f(b, s, heads, p),
        dt=jnp.asarray(rng.uniform(0.001, 0.6, (b, s, heads)), jnp.float32),
        a=-jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32),
        b_in=f(b, s, n), c_out=f(b, s, n), s0=f(b, heads, p, n))


@pytest.mark.parametrize("s, chunk", [(21, 8), (16, 8), (5, 8), (33, 16),
                                      (1, 8)])
def test_the_three_forms_of_the_scan_agree(s, chunk):
    """Token by token, in chunks (lengths that are no multiple of the
    chunk, shorter than one, and exactly two), and the decode kernel a
    token at a time: the same outputs and the same final state, from a
    state that is not zero."""
    q = sequence(3, 2, s)
    want_y, want_s = ssd_sequential(**q)
    got_y, got_s = ssd_chunked(**q, chunk=chunk)
    assert got_y.shape == want_y.shape == (2, s, 4, 8)
    assert float(jnp.max(jnp.abs(got_s - want_s))) < STATE_TOL
    assert float(jnp.max(jnp.abs(got_y - want_y))) < OUT_TOL
    state, ys = q["s0"], []
    live = jnp.ones(2, bool)
    for t in range(s):
        state, y = su.ssm_decode_update(
            state, q["x"][:, t], q["dt"][:, t], q["a"], q["b_in"][:, t],
            q["c_out"][:, t], live, interpret=True)
        ys.append(y)
    assert float(jnp.max(jnp.abs(state - want_s))) < STATE_TOL
    assert float(jnp.max(jnp.abs(jnp.stack(ys, 1) - want_y))) < OUT_TOL


def test_a_step_of_zero_leaves_the_state_where_it_was():
    """A padded position (step 0) neither decays the state nor adds to
    it: a row padded to a bucket ends in the state of its last real
    token, whatever stands in the padding."""
    q = sequence(4, 2, 24)
    tail = jnp.asarray([13, 24])
    real = jnp.arange(24)[None, :] < tail[:, None]
    padded = dict(q, dt=jnp.where(real[..., None], q["dt"], 0.0))
    _, got = ssd_chunked(**padded, chunk=8)
    for row, n in enumerate((13, 24)):
        cut = {k: (v if k == "a" else v[row:row + 1, :n] if v.ndim > 2
                   and k != "s0" else v[row:row + 1])
               for k, v in q.items()}
        _, want = ssd_sequential(**cut)
        assert float(jnp.max(jnp.abs(got[row] - want[0]))) < STATE_TOL


def test_the_chunked_scan_masks_a_decay_before_its_exponential():
    """The decay between a token and a LATER one is masked before the
    exponential, not after: a long chunk's positive exponents would
    overflow to inf and inf * 0 is nan."""
    q = sequence(5, 1, 64)
    q["dt"] = q["dt"] * 40.0          # decays of exp(-300) and their mirror
    y, s = ssd_chunked(**q, chunk=64)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(s)))
    want_y, want_s = ssd_sequential(**q)
    # outputs of order 1,000 here: round-off relative to the largest
    assert float(jnp.max(jnp.abs(y - want_y))) \
        < 1e-5 * float(jnp.max(jnp.abs(want_y)))
