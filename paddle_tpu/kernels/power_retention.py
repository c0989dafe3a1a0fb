"""The decode step of gated power retention of degree 2 (Gelada, Buckman,
Zhang et al., *Scaling Context Requires Rethinking Attention*, arXiv
2507.04239): one token a slot against the slot's fixed recurrent state.

``phi(x)`` is the symmetric degree-2 expansion of a ``d``-vector, ``D = d
(d + 1) / 2`` numbers with ``phi(a) . phi(b) = (a . b)^2``: every product
``x_i x_j`` of an unordered pair, times ``sqrt(2)`` where ``i != j``. Its
order here is by CYCLIC OFFSET (:func:`phi`): a ``d``-lane row of offset
``o`` holds ``x_a x_{(a + o) mod d}`` for lanes ``a`` (``o = 0`` the
squares, ``o = 1 .. d/2 - 1`` whole rows, ``o = d/2`` its first ``d/2``
lanes alone, the pairs ``(a, a + d/2)``), so a row is ``x`` times ``x``
rolled by ``o`` lanes; the rows run ``1 .. d/2 - 1``, ``0``, ``d/2``
(:func:`offset_of`). A KV group's state moves by

    S <- gamma * S + phi(k) v^T        [D, d]
    z <- gamma * z + phi(k)            [D]
    o_h = phi(q_h)^T S / (phi(q_h)^T z + eps)   every query head h of the group

with ``gamma`` the group's gate for the token (``exp`` of a logsigmoid).

The state is the largest stream of a decode step (8 groups x 8,256 x 128
float32 = 33.8 MB a slot a layer at head size 128, read and written), so
the update is one Pallas kernel, :func:`retention_decode_update`: a grid
step a (group, slot, part) brings a part of the group's ``S`` (half of its
``D`` rows: 2.1 MB) to VMEM once, forms the new state a ``d``-row tile
(one offset) at a time, writes it back IN PLACE (``input_output_aliases``:
the pool is donated) and reads every query head of the group out of it,
the readout summed over the parts. A slot that is not ``active`` is SKIPPED as
in ``ssm_state_update``: its grid steps name the block of the live slot
before it (``live_rows``), the one the pipeline holds already, so nothing
of a dead slot's state is read or written.

The kernel takes the raw vectors, ``k`` in row 0 and the group's query
heads in rows ``1 .. group`` of one ``[8, d]`` block, and expands them
itself: a tile's ``phi`` rows are the block times the block rolled ``o``
lanes (one XLU rotate and two multiplies). A tile's other work:

- the outer product ``phi(k) v^T`` is ONE bfloat16 pass on the MXU,
  contracting over 8 sublanes: ``phi(k)`` and ``v`` each cut into three
  bfloat16 pieces that sum to them exactly (``_pieces``), and the six
  products of pieces that ``HIGHEST`` would form set against each other
  row by row (``_ROW_K``, ``_ROW_V``), so the accumulator sums in one pass
  what six passes would;
- the readout of every query head at once, the ``phi`` rows against the
  new tile accumulated over the tiles, in float32 arithmetic: the six
  products of bfloat16 pieces that ``HIGHEST`` forms, but with each of the
  tile's three pieces latched into the MXU once, the rows' pieces stacked
  against it (``_readout``);
- ``z`` is kept lane-dense, ``[tiles, d]`` (row ``t`` the entries of
  ``phi``'s lane row ``t``, the last row's upper half zero): a row
  updates with the ``phi(k)`` row of the same lanes, and the normaliser
  is the lane sum of the ``phi`` rows times it.

Everything else is float32.

Dispatch (:func:`retention_update`): the kernel on a TPU behind
``FLAGS_use_pallas_kernels``, or through the Pallas interpreter under
``FLAGS_ragged_interpret`` (the CPU test path); the plain recurrence
(:func:`retention_update_reference`) elsewhere. A kernel the gate called
eligible that fails to lower raises.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ._common import i32_index_scope
from .ssm_state_update import live_rows

__all__ = ["phi", "feature_dim", "tiles", "offset_of", "z_shape",
           "z_rows", "z_flat",
           "live_rows", "retention_update", "retention_decode_update",
           "retention_update_reference", "retention_kernel_eligible",
           "OP_NAME", "EPS"]

#: kernelcheck certificate of this module's pallas_call (lint PT011)
KERNELCHECK_CERTS = ("power_retention_decode",)

#: the kernel's name in a device trace (``power_retention_decode_roofline``)
OP_NAME = "power_retention_decode"

#: the normaliser's floor: ``o = num / (den + EPS)``. The config states
#: none; ``den`` is a gated sum of ``(q . k)^2``, hundreds and more at
#: serving widths, so this only keeps a zero state from dividing by 0
EPS = 1e-6

#: lanes of a vector register: the kernel's head size is whole rows of them
LANES = 128

#: rows of the kernel's vector block: ``k``, then a query head a row,
#: padded to whole 8-sublane tiles
_ROW_TILE = 8

#: the outer product's six products of bfloat16 pieces, a row each:
#: ``phi(k)``'s piece ``_ROW_K[r]`` against ``v``'s piece ``_ROW_V[r]``
#: (0 the largest), the six of ``i + j <= 2`` that ``HIGHEST`` forms
_ROW_K = (0, 1, 2, 0, 1, 0)
_ROW_V = (0, 0, 0, 1, 1, 2)

#: a grid step's share of a group's state: ``D / STATE_PARTS`` rows
#: (8,256 / 2 = 4,128 at head size 128, 2.1 MB), so that the state in and
#: out and their second buffers hold 8.5 MB of VMEM. A part's edge may
#: fall inside an offset's tile (``_segments``)
STATE_PARTS = 2

#: a grid step's blocks: the state in and out and their second buffers at
#: 2.1 MB each, under this
_TUNED = {"vmem_limit_bytes": 32 << 20}


def feature_dim(d: int) -> int:
    """``D``: the entries of ``phi`` of a ``d``-vector."""
    return d * (d + 1) // 2


def tiles(d: int) -> int:
    """``d``-lane rows of ``phi``: the offsets ``0 .. d/2``."""
    return d // 2 + 1


def offset_of(t: int, d: int) -> int:
    """The offset of ``phi``'s lane row ``t``: ``1 .. d/2 - 1``, then the
    squares (0), then the half row (``d/2``). A sum of ``phi(a) phi(b)``
    in this order cancels less on the way than one that starts from the
    squares, which are all of one sign."""
    return t + 1 if t < d // 2 - 1 else (0 if t == d // 2 - 1 else d // 2)


def z_shape(groups: int, d: int) -> tuple:
    """A slot's ``z``: ``[groups, tiles, d]``, lane-dense, the last row's
    upper half zero."""
    return (groups, tiles(d), d)


def _check_even(d: int) -> None:
    if d % 2:
        raise ValueError(f"head size {d} is odd: phi's offsets pair lanes")


def phi(x):
    """The symmetric degree-2 expansion of ``x [..., d]`` (float32):
    ``[..., D]``, lane row ``t`` the ``d`` (the last: ``d / 2``) entries
    ``c_o x_a x_{(a + o) mod d}`` of offset ``o = offset_of(t)``, ``c_0 =
    1`` and ``c_o = sqrt(2)``. One gather of rolled copies and one
    product, so XLA fuses it into whatever reads it."""
    x = x.astype(jnp.float32)
    d = x.shape[-1]
    _check_even(d)
    off = np.asarray([offset_of(t, d) for t in range(tiles(d))])
    at = (off[:, None] + np.arange(d)[None, :]) % d             # [n, d]
    scale = np.where(off == 0, 1.0, math.sqrt(2.0))[:, None]
    rows = x[..., None, :] * jnp.take(x, at, axis=-1) \
        * jnp.asarray(scale, jnp.float32)
    return rows.reshape(x.shape[:-1] + (tiles(d) * d,))[
        ..., :feature_dim(d)]


def z_rows(a, d: int):
    """``[..., D]`` as the stored ``[..., tiles, d]`` (zeros past ``D``)."""
    pad = tiles(d) * d - feature_dim(d)
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a.reshape(a.shape[:-1] + (tiles(d), d))


def z_flat(z, d: int):
    """The inverse of :func:`z_rows`: ``[..., D]``."""
    return z.reshape(z.shape[:-2] + (-1,))[..., :feature_dim(d)]


def retention_update_reference(state, z, k, q, v, log_gamma, active=None):
    """The plain recurrence, float32: state ``[slots, groups, D, d]``, z
    ``[slots, groups, tiles, d]`` (:func:`z_shape`), k and v ``[slots,
    groups, d]``, q ``[slots, groups, group, d]``, log_gamma ``[slots,
    groups]`` -> (new state, new z, o ``[slots, groups, group, d]``). A
    slot that is not ``active`` keeps its state; its ``o`` is 0."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    d = state.shape[3]
    gamma = jnp.exp(log_gamma.astype(f32))
    phi_k, phi_q = phi(k), phi(q)
    new = gamma[..., None, None] * state + phi_k[..., None] \
        * v.astype(f32)[:, :, None, :]
    new_z = gamma[..., None] * z_flat(z, d) + phi_k
    num = jnp.einsum("sghD,sgDd->sghd", phi_q, new, precision=hi)
    den = jnp.einsum("sghD,sgD->sgh", phi_q, new_z, precision=hi)
    o = num / (den[..., None] + EPS)
    new_z = z_rows(new_z, d)
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, state)
        new_z = jnp.where(active[:, None, None, None], new_z, z)
        o = jnp.where(active[:, None, None, None], o, 0.0)
    return new, new_z, o


def retention_kernel_eligible(head_dim: int, *, on_tpu: bool = True,
                              flags_on: bool = True,
                              interpret: bool = False) -> tuple[bool, str]:
    """The one dispatch gate of the decode kernel: ``(eligible, reason)``,
    the reason naming the first gate that blocks it."""
    if not flags_on:
        return False, "FLAGS_use_pallas_kernels is off"
    if not on_tpu and not interpret:
        return False, ("CPU backend: Pallas TPU kernels unavailable (set "
                       "FLAGS_ragged_interpret for the interpreter)")
    if interpret:
        return True, ""
    if head_dim % LANES:
        return False, (f"head_dim {head_dim} is not whole 128-lane rows: "
                       "the state's minor axis")
    return True, ""


def _pieces(x):
    """``x`` (float32) as three float32 numbers that bfloat16 holds
    exactly and that sum to ``x`` exactly: the top 8 bits of the
    significand, the next 8 and the last 8 (truncation by mask, which no
    compiler drops as it may a cast down and up again)."""
    def top(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.int32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.int32(-65536), jnp.float32)
    p0 = top(x)
    rest = x - p0
    p1 = top(rest)
    return p0, p1, rest - p1


def _readout(pq, new):
    """``phi^T new`` of a tile in float32 arithmetic: ``pq`` the three
    pieces of the ``[r, d]`` ``phi`` rows, ``new`` the tile ``[d, d]``.
    The six products of pieces ``i + j <= 2`` that ``HIGHEST`` forms, with
    each of the tile's pieces the MXU's stationary operand ONCE (where
    ``HIGHEST`` latches it once a product): its piece ``j`` against the
    rows' pieces ``0 .. 2 - j`` stacked, ``3 r``, ``2 r`` and ``r`` rows.
    Summed smallest first."""
    r = pq[0].shape[0]
    rows = jnp.concatenate(pq, axis=0).astype(jnp.bfloat16)     # [3 r, d]
    # b_j's row block i: the rows' piece i against the tile's piece j
    b0, b1, b2 = (jax.lax.dot_general(
        rows[:(3 - j) * r], piece.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        for j, piece in enumerate(_pieces(new)))
    return ((b0[2 * r:] + b1[r:] + b2) + (b0[r:2 * r] + b1[:r])) + b0[:r]


def state_parts(d: int) -> int:
    """Parts a group's state is moved in: ``STATE_PARTS`` where each is
    whole 8-sublane tiles, else 1."""
    dd = feature_dim(d)
    return STATE_PARTS if dd % (8 * STATE_PARTS) == 0 else 1


def _segments(d: int, part: int, parts: int) -> list:
    """The static pieces of tiles that part ``part`` of ``parts`` holds:
    ``(lane row, its first row here, rows, first row of the part)``. A
    part's edge may fall inside a tile; the tile's rows are then split
    between the two parts."""
    size = feature_dim(d) // parts
    lo, hi = part * size, (part + 1) * size
    out = []
    for t in range(tiles(d)):
        start, n = t * d, d if t < d // 2 else d // 2
        a, b = max(start, lo), min(start + n, hi)
        if a < b:
            out.append((t, a - start, b - a, a - lo))
    return out


def _kernel(d, parts, rows_ref, mode_ref, gamma_ref, x_ref, v_ref,
            state_ref, z_ref, new_ref, new_z_ref, o_ref, num_ref, den_ref):
    """One (group, slot, part). gamma ``[1, 1, groups]`` in SMEM, x ``[8,
    d]`` (``k`` in row 0, a query head a row after it, zeros beyond), v
    ``[8, d]`` (``v``'s pieces in the rows of ``_ROW_V``, zeros beyond),
    state ``[D / parts, d]``, z ``[tiles, d]``, o ``[8, d]`` (row ``1 + h``
    is head ``h``'s output; row 0 is not read); ``num`` and ``den`` carry
    the readout over the parts. Part 0 also moves all of ``z``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, s, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    half = d // 2

    def phi_rows(x, t):
        """The ``phi`` rows of lane row ``t``: ``[8, d]``."""
        o = offset_of(t, d)
        ph = x if o == 0 else math.sqrt(2.0) * pltpu.roll(x, d - o, 1)
        ph = x * ph
        if o == half:   # the pairs (a, a + d/2) once: lanes < d/2
            lane = jax.lax.broadcasted_iota(jnp.int32, ph.shape, 1)
            ph = jnp.where(lane < half, ph, 0.0)
        return ph

    def advance(part):
        gam = gamma_ref[0, 0, g]
        x = x_ref[0, 0]                                   # [8, d]
        vm = v_ref[0, 0]                                  # [8, d]
        r = x.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (r, d), 0)
        num = jnp.zeros((r, d), jnp.float32) if part == 0 else num_ref[...]
        if part == 0:
            den = jnp.zeros((r, d), jnp.float32)
            for t in range(tiles(d)):
                ph = phi_rows(x, t)
                zrow = gam * z_ref[0, 0, t:t + 1, :] + ph[0:1]
                new_z_ref[0, 0, t:t + 1, :] = zrow
                den = den + ph * zrow
            den_ref[...] = den
        for t, a, n, at in _segments(d, part, parts):
            pq = _pieces(phi_rows(x, t))
            # phi(k)'s pieces against v's: the tile's outer product in one
            # pass (the pieces are bfloat16 numbers, so the pass's rounding
            # to bfloat16 changes nothing)
            lhs = jnp.zeros((r, d), jnp.float32)
            for i, piece in enumerate(_ROW_K):
                lhs = jnp.where(row == i, pq[piece][0:1], lhs)
            outer = jax.lax.dot_general(
                lhs, vm, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [d, d]
            new = gam * state_ref[0, 0, at:at + n, :] + outer[a:a + n]
            new_ref[0, 0, at:at + n, :] = new
            if n < d:   # a piece of a tile: the other rows read 0
                around = [jnp.zeros((m, d), jnp.float32)
                          for m in (a, d - a - n)]
                new = jnp.concatenate(
                    ([around[0]] if a else []) + [new]
                    + ([around[1]] if d - a - n else []), axis=0)
            num = num + _readout(pq, new)
        if part < parts - 1:
            num_ref[...] = num
        else:
            total = jnp.sum(den_ref[...], axis=1, keepdims=True)   # [8, 1]
            o_ref[0, 0] = num / (total + EPS)

    for part in range(parts):
        @pl.when((mode_ref[s] == LIVE) & (p == part))
        def _():
            advance(part)

    # no live slot at all: this step's blocks (slot 0's) are written back
    # when their index changes, so they have to hold what they held
    @pl.when(mode_ref[s] == NONE_LIVE)
    def _():
        new_ref[...] = state_ref[...]
        new_z_ref[...] = z_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


#: a slot's grid steps by its ``mode``: 1 advances it; 0 (dead, after a
#: live slot) names the block the live slot before it left last, its last
#: part; 3 (dead, before the first live slot) the first live slot's first
#: part, which comes next; 2 (no slot live: slot 0) copies its blocks.
#: Either way a dead step names the block the pipeline holds, so nothing is
#: copied in or written back for it
LIVE, DEAD_AFTER, NONE_LIVE, DEAD_BEFORE = 1, 0, 2, 3


@functools.partial(jax.jit, static_argnames=("interpret",))
def _launch(state, z, gamma, x_blk, v_blk, rows, mode, *, interpret):
    """The ``pallas_call``, jitted so that a program's layers trace it
    once. Operands as ``retention_decode_update`` lays them out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, groups, dd, d = state.shape
    nt = z.shape[2]
    r = x_blk.shape[2]
    parts = state_parts(d)

    def part(s, p, mode):
        m = mode[s]
        return jnp.where(m == DEAD_BEFORE, 0,
                         jnp.where(m == DEAD_AFTER, parts - 1, p))

    blk = lambda g, s, p, rows, mode: (  # noqa: E731
        rows[s], g, part(s, p, mode), 0)
    whole = lambda g, s, p, rows, mode: (rows[s], g, 0, 0)  # noqa: E731
    with i32_index_scope():  # the package's x64 would make index maps i64
        return pl.pallas_call(
            functools.partial(_kernel, d, parts),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                # the slot is the middle axis: a run of dead slots names
                # one block for the whole run, and no copy is made
                grid=(groups, slots, parts),
                in_specs=[
                    pl.BlockSpec((1, 1, groups),
                                 lambda g, s, p, rows, mode: (rows[s], 0, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((1, 1, r, d), whole),
                    pl.BlockSpec((1, 1, r, d), whole),
                    pl.BlockSpec((1, 1, dd // parts, d), blk),
                    pl.BlockSpec((1, 1, nt, d), whole)],
                out_specs=[pl.BlockSpec((1, 1, dd // parts, d), blk),
                           pl.BlockSpec((1, 1, nt, d), whole),
                           pl.BlockSpec((1, 1, r, d), whole)],
                scratch_shapes=[pltpu.VMEM((r, d), jnp.float32),
                                pltpu.VMEM((r, d), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(z.shape, z.dtype),
                       jax.ShapeDtypeStruct((slots, groups, r, d),
                                            jnp.float32)],
            # operands 5 and 6 (after the two prefetched vectors) are the
            # state and z
            input_output_aliases={5: 0, 6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_TUNED["vmem_limit_bytes"]),
            interpret=interpret, name=OP_NAME,
        )(rows, mode, gamma, x_blk, v_blk, state, z)


def retention_decode_update(state, z, k, q, v, log_gamma, active,
                            *, interpret: bool = False):
    """The kernel's launch: shapes as :func:`retention_update_reference`,
    ``state`` and ``z`` float32 (inside a program that donates them the
    results take their buffers), ``active`` ``[slots]`` bool. Returns (new
    state, new z, o); a dead slot's state is untouched and its ``o`` is
    0."""
    slots, groups, group, _ = q.shape
    f32 = jnp.float32
    r = -(-(1 + group) // _ROW_TILE) * _ROW_TILE
    x = jnp.concatenate([k.astype(f32)[:, :, None], q.astype(f32)], axis=2)
    x_blk = jnp.pad(x, ((0, 0), (0, 0), (0, r - 1 - group), (0, 0)))
    pv = _pieces(v.astype(f32))
    v_blk = jnp.stack([pv[i] for i in _ROW_V], axis=2)
    v_blk = jnp.pad(v_blk, ((0, 0), (0, 0), (0, r - len(_ROW_V)), (0, 0)))
    gamma = jnp.exp(log_gamma.astype(f32))[:, None, :]
    rows = live_rows(active)
    idx = jnp.arange(active.shape[0], dtype=jnp.int32)
    before = idx < jnp.argmax(active)
    mode = jnp.where(active, LIVE, jnp.where(before, DEAD_BEFORE,
                                             DEAD_AFTER)).astype(jnp.int32)
    mode = mode.at[0].set(jnp.where(jnp.any(active), mode[0], NONE_LIVE))
    new, new_z, o = _launch(state, z, gamma, x_blk, v_blk, rows, mode,
                            interpret=interpret)
    o = o[:, :, 1:1 + group]
    return new, new_z, jnp.where(active[:, None, None, None], o, 0.0)


def retention_update(state, z, k, q, v, log_gamma, active):
    """(new state, new z, o, rows moved): the decode update by the path
    the gate picks. ``rows moved`` (int32 scalar) is the slots whose state
    the update read and wrote: the live ones under the kernel, every slot
    under the plain recurrence, which rewrites the pool."""
    from ..utils.flags import flag
    from ._common import on_tpu_backend

    interpret = bool(flag("FLAGS_ragged_interpret", False))
    ok, _ = retention_kernel_eligible(
        state.shape[3], on_tpu=on_tpu_backend(),
        flags_on=bool(flag("FLAGS_use_pallas_kernels", True)),
        interpret=interpret)
    if ok:
        new, new_z, o = retention_decode_update(
            state, z, k, q, v, log_gamma, active, interpret=interpret)
        moved = jnp.maximum(jnp.sum(active, dtype=jnp.int32), 1)
        return new, new_z, o, moved
    new, new_z, o = retention_update_reference(state, z, k, q, v,
                                               log_gamma, active)
    return new, new_z, o, jnp.int32(state.shape[0])
