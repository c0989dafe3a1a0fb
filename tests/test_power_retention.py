"""The decode step of gated power retention of degree 2
(``kernels/power_retention.py``): the symmetric degree-2 expansion, the
Pallas kernel through the interpreter against the plain recurrence, the
skipping of dead slots (``live_rows``, shared with the Mamba kernel) and
the one dispatch gate. Everything is float32 on the CPU: states of order
1 to 10 move by a decayed add, exact to the last bit or two (``TOL``
relative); outputs are ratios of sums of ``D`` products, 1e-5 relative.
A dropped decay, a z left behind or a state written to the wrong slot
shows at 1e-2 and up. The ``_full`` cases hold every output to
``FULL_TOL`` of itself, under what the smallest of the readout's six
products of bfloat16 pieces moves it by.
"""
import functools
import math

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.kernels import power_retention as pr
from paddle_tpu.kernels import ssm_state_update as su
from paddle_tpu.utils.flags import flag, set_flags

TOL = 1e-5
SLOTS = 6

MASKS = {
    "all_live": [1, 1, 1, 1, 1, 1],
    "dead_between": [0, 1, 0, 0, 1, 0],
    "none_live": [0, 0, 0, 0, 0, 0],
    "last_only": [0, 0, 0, 0, 0, 1],
    "first_only": [1, 0, 0, 0, 0, 0],
}
#: (head size, groups, group): D = 36 (4 whole offsets and a half), 136
#: (8 and a half) and 528 (16 and a half), group sizes 1 and 5
SHAPES = {"d8_g1": (8, 3, 1), "d16_g5": (16, 2, 5), "d32_g5": (32, 2, 5)}
#: a ``_full`` case's operands are positive, so that a readout's float32
#: sums cancel nothing and land within about 1.5e-6 of the reference, and
#: its state's and queries' significands fill all 24 bits, so that none of
#: their bfloat16 pieces is 0: leaving out any one of the readout's six
#: products of pieces then moves an output by 8e-6 of itself or more
FULL_TOL = 3e-6


def full_significand(a):
    """``a`` (float32) with bits 15 and 0 of each number set: its
    significand fills all 24 bits, and none of its three bfloat16 pieces
    (``_pieces``: bits 22-16, 15-8 and 7-0) is 0."""
    bits = np.asarray(a, np.float32).view(np.int32) | np.int32(0x8001)
    return jnp.asarray(bits.view(np.float32))


def operands(d, groups, group, seed=0, slots=SLOTS, full=False):
    rng = np.random.default_rng(seed)
    dd = pr.feature_dim(d)
    draw = functools.partial(rng.uniform, 0.5, 2.0) if full else rng.normal
    f = lambda *s: jnp.asarray(draw(size=s), jnp.float32)  # noqa: E731
    fill = full_significand if full else (lambda a: a)
    z = pr.z_rows(jnp.asarray(rng.uniform(0.5, 2.0, (slots, groups, dd)),
                              jnp.float32), d)
    return dict(
        state=fill(f(slots, groups, dd, d)),
        z=z,
        k=f(slots, groups, d),
        q=fill(f(slots, groups, group, d)),
        v=f(slots, groups, d),
        log_gamma=-jnp.asarray(rng.uniform(0.0, 0.1, (slots, groups)),
                               jnp.float32))


def close(a, b, tol=TOL):
    return float(jnp.max(jnp.abs(a - b))) <= tol * max(
        1.0, float(jnp.max(jnp.abs(b))))


@pytest.fixture
def interpret():
    before = flag("FLAGS_ragged_interpret", False)
    yield lambda on: set_flags({"FLAGS_ragged_interpret": on})
    set_flags({"FLAGS_ragged_interpret": before})


def test_phi_is_the_symmetric_degree_2_expansion():
    """``phi(a) . phi(b) = (a . b)^2`` over ``d (d + 1) / 2`` entries, by
    cyclic offset: every unordered pair of lanes once."""
    rng = np.random.default_rng(1)
    a, b = (jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
            for _ in range(2))
    pa, pb = pr.phi(a), pr.phi(b)
    assert pa.shape == (3, 136) == (3, pr.feature_dim(16))
    assert close(jnp.sum(pa * pb, -1), jnp.sum(a * b, -1) ** 2, 1e-5)
    # lane row t holds offset o = offset_of(t): lane l is the pair (l, l + o
    # mod 16); the rows run 1 .. 7, then the squares, then offset 8, which
    # stops at lane 8
    offs = [pr.offset_of(t, 16) for t in range(9)]
    assert offs == [1, 2, 3, 4, 5, 6, 7, 0, 8]
    i = np.concatenate([np.arange(16 if o < 8 else 8) for o in offs])
    j = np.concatenate([(np.arange(16 if o < 8 else 8) + o) % 16
                        for o in offs])
    pairs = {tuple(sorted(p)) for p in zip(i, j)}
    assert len(pairs) == 136 == len(i)
    want = np.asarray(a)[:, i] * np.asarray(a)[:, j] \
        * np.where(i == j, 1.0, math.sqrt(2.0))
    assert np.allclose(np.asarray(pa), want, rtol=1e-6, atol=1e-7)
    assert pr.feature_dim(128) == 8256
    # 64 whole offsets and a half: 65 lane rows of z, 64 lanes padded
    assert pr.z_shape(8, 128) == (8, 65, 128)
    x = jnp.arange(2 * 8256, dtype=jnp.float32).reshape(2, 8256) + 1
    rows = pr.z_rows(x, 128)
    assert rows.shape == (2, 65, 128)
    assert not np.asarray(rows)[:, 64, 64:].any()
    assert np.array_equal(np.asarray(pr.z_flat(rows, 128)), np.asarray(x))


@pytest.mark.parametrize(
    "shape", sorted(SHAPES) + [s + "_full" for s in sorted(SHAPES)])
@pytest.mark.parametrize("mask", MASKS, ids=list(MASKS))
def test_kernel_is_the_recurrence_and_skips_dead_slots(mask, shape):
    """Every slot mask: the live slots' S, z and outputs are the plain
    recurrence's, a dead slot's S and z are untouched bit for bit and its
    output is 0. A ``_full`` case also holds each live output to
    ``FULL_TOL`` of itself: the readout keeps all six products."""
    full = shape.endswith("_full")
    o = operands(*SHAPES[shape.removesuffix("_full")], full=full)
    active = jnp.asarray(MASKS[mask], bool)
    want = pr.retention_update_reference(**o, active=active)
    got = pr.retention_decode_update(**o, active=active, interpret=True)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert close(g, w)
    live = np.asarray(active)
    if full:
        g, w = np.asarray(got[2])[live], np.asarray(want[2])[live]
        assert np.all(np.abs(g - w) <= FULL_TOL * np.abs(w))
    dead = ~live
    assert np.array_equal(np.asarray(got[0])[dead], np.asarray(o["state"])[
        dead])
    assert np.array_equal(np.asarray(got[1])[dead], np.asarray(o["z"])[dead])
    assert not np.asarray(got[2])[dead].any()


def test_the_normaliser_is_the_gated_sum_of_the_keys():
    """From zero state and z, one token: every head's output is ``v`` (the
    one key's weight over itself), whatever the gate. The queries are the
    key, so that the weight ``|k|^4`` stands far above ``EPS``."""
    o = operands(16, 2, 5)
    o["q"] = jnp.repeat(o["k"][:, :, None], 5, axis=2)
    o["state"] = jnp.zeros_like(o["state"])
    o["z"] = jnp.zeros_like(o["z"])
    active = jnp.ones((SLOTS,), bool)
    _, z, out = pr.retention_decode_update(**o, active=active,
                                           interpret=True)
    v = np.asarray(o["v"])[:, :, None, :]
    assert np.allclose(np.asarray(out), np.broadcast_to(v, out.shape),
                       rtol=1e-4, atol=1e-5)
    phi_k = np.asarray(pr.phi(o["k"]))
    flat = np.asarray(z).reshape(SLOTS, 2, -1)
    assert np.allclose(flat[..., :136], phi_k, rtol=1e-6)
    assert not flat[..., 136:].any()
    assert np.allclose(np.asarray(pr.z_flat(z, 16)), phi_k, rtol=1e-6)


@pytest.mark.parametrize("mask,rows", [
    ([1, 1, 1], [0, 1, 2]),
    ([0, 1, 0, 0, 1, 0], [1, 1, 1, 1, 4, 4]),
    ([0, 0, 1], [2, 2, 2]),
    ([0, 0, 0], [0, 0, 0]),
])
def test_a_dead_slot_names_the_live_block_the_pipeline_holds(mask, rows):
    """The kernel's grid steps name their state blocks through the Mamba
    kernel's ``live_rows``: one rule for both recurrent kernels."""
    assert pr.live_rows is su.live_rows
    got = pr.live_rows(jnp.asarray(mask, bool))
    assert np.asarray(got).tolist() == rows


@pytest.mark.parametrize("d,kw,ok,reason", [
    (128, {}, True, ""),
    (64, {}, False, "128-lane"),
    (16, dict(interpret=True, on_tpu=False), True, ""),
    (128, dict(on_tpu=False), False, "CPU backend"),
    (128, dict(flags_on=False), False, "FLAGS_use_pallas_kernels"),
])
def test_the_gate_names_what_blocks_the_kernel(d, kw, ok, reason):
    got, why = pr.retention_kernel_eligible(d, **kw)
    assert got is ok and reason in why


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_dispatch_counts_the_rows_it_moves(interpret, kernel):
    """The kernel moves the live slots (at least one block when none is);
    the plain recurrence rewrites the whole pool."""
    interpret(kernel)
    o = operands(8, 3, 1)
    for mask in ([0, 1, 0, 0, 1, 0], [0] * SLOTS):
        active = jnp.asarray(mask, bool)
        *out, moved = pr.retention_update(**o, active=active)
        want = pr.retention_update_reference(**o, active=active)
        for g, w in zip(out, want):
            assert close(g, w)
        assert int(moved) == (max(sum(mask), 1) if kernel else SLOTS)
