"""The decode step of a selective state-space layer (Mamba-2, arXiv
2405.21060 section 3): one token a slot against the slot's recurrent state.

A head's state ``S [head_dim, d_state]`` moves by

    S <- exp(dt * A) * S + (dt * x) (x) B          y = S C

with ``dt`` the head's step (after its softplus), ``A`` the head's negative
decay rate, ``x [head_dim]`` the head's input, ``B``, ``C [d_state]`` the
token's input and output projections (one group: every head's alike). The
skip ``D * x`` and the gate are the caller's: elementwise XLA on what the
kernel returns.

The state is the largest stream of a decode step (64 heads x 64 x 128
float32 = 2 MB a slot a layer, read and written), so the update is one
Pallas kernel, :func:`ssm_decode_update`: a grid step a slot and block of
heads brings the block to VMEM once, forms the new state, writes it back IN
PLACE (``input_output_aliases``: the pool is donated, no second copy of it
exists) and emits ``y``. A slot that is not ``active`` is SKIPPED: its
grid step names the block of the live slot before it (``live_rows``), which
is the block the pipeline already holds, so nothing of a dead slot's state
is read or written, and its body does nothing.

A grid step lays out its work so that nothing is done once a head that can
be done once a block. The state's minor axis is ``d_state`` (128: a whole
lane row). A head's decay ``exp(dt * A)`` is one number: it comes in SMEM
and multiplies the head's 8 vector registers as a scalar. What multiplies
a state ROW (``dt * x``, one number a ``head_dim`` index) has to be a
COLUMN of numbers, one a sublane, so ``dt * x`` arrives transposed, ``[..,
head_dim, heads]``, and head ``h``'s column is a one-lane slice broadcast
over the lanes (forming ``(dt * x) (x) B`` on the MXU instead compiled to
more bundles). The readout is ONE product a block on the MXU: C on 8
sublanes against the block's ``[heads * head_dim, d_state]`` rows,
contracting ``d_state`` on both sides, at ``HIGHEST`` precision (float32
arithmetic), which leaves ``y`` lane-dense in the caller's ``[slots, heads,
head_dim]`` order: no lane reduction, no one-lane store, no transpose
after the launch. Everything is float32.

Measured alone on a v5e at granite-4.0-h-micro's shape (64 slots x 64
heads x 64 x 128, donated; ms a call of a loop of 20 in one program, which
adds 0.04-0.047 ms of its own; PR 38), at 64 / 33 / 7 live slots: the
kernel with a lane reduction and a one-lane store a head (PR 33) 0.508 /
0.376-0.451 / 0.134-0.202; the same ``pallas_call`` with its body a copy
0.462 / 0.277 / 0.122; XLA's fusion of the plain recurrence 0.463-0.470 at
every count; this body 0.474-0.475 / 0.276-0.350 / 0.119-0.191. A plain
in-place stream (0.456-0.466) and a copy through VMEM by manual DMA with
two or three buffers and 1-16 copies a slot (0.460-0.471) read what the
copy-only body reads: the HBM's stream with reads and writes in flight
together, about 78% of 819 GB/s, is this schedule's ceiling, and this body
is 0.013 ms above it. Only copies that never read and write at once beat
it (8-16 slots in, then out: 0.432-0.438, copy only).

Dispatch (:func:`ssm_update`): the kernel on a TPU behind
``FLAGS_use_pallas_kernels``, or through the Pallas interpreter under
``FLAGS_ragged_interpret`` (the CPU test path); the plain recurrence
(:func:`ssm_update_reference`) elsewhere. A kernel the gate called
eligible that fails to lower raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._common import i32_index_scope

__all__ = ["ssm_update", "ssm_decode_update", "ssm_update_reference",
           "ssm_kernel_eligible", "live_rows", "OP_NAME"]

#: kernelcheck certificate of this module's pallas_call (lint PT011)
KERNELCHECK_CERTS = ("ssm_decode_update",)

#: the kernel's name in a device trace (``ssm_state_update_roofline``)
OP_NAME = "ssm_decode_update"

#: heads to a grid step: all 64 of a layer make one 2 MB block, so a slot is
#: one step and the pipeline holds in, out and their second buffers in 8 MB
_TUNED = {"block_heads": 64, "vmem_limit_bytes": 32 << 20}


def ssm_update_reference(state, x, dt, a, b_in, c_out, active=None):
    """The plain recurrence, float32: state ``[slots, heads, p, n]``, x
    ``[slots, heads, p]``, dt ``[slots, heads]``, a ``[heads]``, b_in and
    c_out ``[slots, n]`` -> (new state, y ``[slots, heads, p]``). A slot
    that is not ``active`` keeps its state; its ``y`` is 0."""
    f32 = jnp.float32
    state, x, dt = state.astype(f32), x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))[:, :, None, None]
    new = decay * state + (dt[:, :, None] * x)[..., None] \
        * b_in.astype(f32)[:, None, None, :]
    y = jnp.sum(new * c_out.astype(f32)[:, None, None, :], axis=-1)
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, state)
        y = jnp.where(active[:, None, None], y, 0.0)
    return new, y


def live_rows(active):
    """``[slots] int32``: for every slot the slot whose state block its
    grid step names: its own if it is live, else the nearest live slot
    before it, else (before the first live slot) the first live one. Runs
    of dead slots then name the block the pipeline holds already, and
    nothing is copied for them. All 0 when no slot is live."""
    n = active.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(active, idx, -1))
    first = jnp.argmax(active).astype(jnp.int32)
    return jnp.where(before >= 0, before, first).astype(jnp.int32)


def ssm_kernel_eligible(heads: int, head_dim: int, d_state: int, *,
                        on_tpu: bool = True, flags_on: bool = True,
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
    if d_state % 128:
        return False, (f"d_state {d_state} is not whole 128-lane rows: "
                       "the state's minor axis")
    if head_dim % 8:
        return False, f"head_dim {head_dim} is not whole 8-sublane tiles"
    return True, ""


def _block_heads(heads: int) -> int:
    bh = min(heads, _TUNED["block_heads"])
    while heads % bh:
        bh -= 1
    return bh


def _kernel(block_heads, rows_ref, active_ref, decay_ref, dtx_ref, b_ref,
            c_ref, state_ref, new_ref, y_ref):
    """One slot, one block of heads. decay ``[1, 1, 1, bh]`` in SMEM (a
    number a head), dtx ``[p, bh]`` (a head a lane), b and c ``[1, n]``,
    state ``[bh, p, n]``, y ``[1, bh * p]`` (lane-dense, heads major)."""
    from jax.experimental import pallas as pl

    s = pl.program_id(1)

    @pl.when(active_ref[s] == 1)
    def _():
        b_row = b_ref[0]                                 # [1, n]
        for h in range(block_heads):
            new_ref[0, h] = decay_ref[0, 0, 0, h] * state_ref[0, h] \
                + dtx_ref[0, 0, :, h:h + 1] * b_row
        # the readout of the whole block as ONE product on the MXU: C
        # (8 sublanes alike) against the block's rows, contracting d_state
        n = b_row.shape[-1]
        rows = new_ref[0].reshape(block_heads * new_ref.shape[2], n)
        y = jax.lax.dot_general(
            jnp.broadcast_to(c_ref[0], (8, n)), rows,
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        y_ref[0, 0] = y[:1]

    # no live slot at all: this step's block (slot 0's) is written back
    # when the grid ends, so it has to hold what it held
    @pl.when(active_ref[s] == 2)
    def _():
        new_ref[...] = state_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("block_heads", "interpret"))
def _launch(state, decay, dtx_t, b_in, c_out, rows, mode, *, block_heads,
            interpret):
    """The ``pallas_call``, jitted so that a program's 36 layers trace it
    once. Operands as ``ssm_decode_update`` lays them out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, p, n = state.shape
    bh = block_heads
    at = lambda j, s, rows, mode: (rows[s], j, 0, 0)  # noqa: E731
    small = lambda shape, **kw: pl.BlockSpec(  # noqa: E731
        (1, 1) + shape, at, **kw)
    row = pl.BlockSpec((1, 1, n), lambda j, s, rows, mode: (rows[s], 0, 0))
    big = pl.BlockSpec((1, bh, p, n), at)
    with i32_index_scope():  # the package's x64 would make index maps i64
        return pl.pallas_call(
            functools.partial(_kernel, bh),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                # the slot is the inner axis: a run of dead slots names
                # one block for the whole run, and no copy is made
                grid=(heads // bh, slots),
                in_specs=[small((1, bh), memory_space=pltpu.SMEM),
                          small((p, bh)), row, row, big],
                out_specs=[big, small((1, bh * p))]),
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct((slots, heads // bh, 1, bh * p),
                                            jnp.float32)],
            # operand 6 (after the two prefetched vectors) is the state
            input_output_aliases={6: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_TUNED["vmem_limit_bytes"]),
            interpret=interpret, name=OP_NAME,
        )(rows, mode, decay, dtx_t, b_in, c_out, state)


def ssm_decode_update(state, x, dt, a, b_in, c_out, active, *,
                      interpret: bool = False):
    """The kernel's launch: shapes as :func:`ssm_update_reference`,
    ``state`` float32 (inside a program that donates it the result takes
    its buffer), ``active`` ``[slots]`` bool. Returns (new state, y); a dead slot's state is
    untouched and its ``y`` is 0."""
    slots, heads, p, n = state.shape
    bh = _block_heads(heads)
    nj = heads // bh
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))
    dtx = dt[:, :, None] * x.astype(f32)
    # [slots, heads, ...] -> [slots, blocks, ..., heads of the block]
    decay = decay.reshape(slots, nj, 1, bh)
    dtx_t = dtx.reshape(slots, nj, bh, p).transpose(0, 1, 3, 2)
    rows = live_rows(active)
    # 1: advance the slot; 0: skip it; 2: no slot is live, keep the block
    mode = active.astype(jnp.int32)
    mode = mode.at[0].set(jnp.where(jnp.any(active), mode[0], 2))
    new, y = _launch(state, decay, dtx_t,
                     b_in.astype(f32)[:, None, :],
                     c_out.astype(f32)[:, None, :], rows, mode,
                     block_heads=bh, interpret=interpret)
    # y is heads major already: the caller's order
    y = y.reshape(slots, heads, p)
    return new, jnp.where(active[:, None, None], y, 0.0)


def ssm_update(state, x, dt, a, b_in, c_out, active):
    """(new state, y, rows moved): the decode update by the path the gate
    picks. ``rows moved`` (int32 scalar) is the slots whose state the
    update read and wrote: the live ones under the kernel, every slot
    under the plain recurrence, which rewrites the pool."""
    from ..utils.flags import flag
    from ._common import on_tpu_backend

    interpret = bool(flag("FLAGS_ragged_interpret", False))
    ok, _ = ssm_kernel_eligible(
        state.shape[1], state.shape[2], state.shape[3],
        on_tpu=on_tpu_backend(),
        flags_on=bool(flag("FLAGS_use_pallas_kernels", True)),
        interpret=interpret)
    if ok:
        new, y = ssm_decode_update(state, x, dt, a, b_in, c_out, active,
                                   interpret=interpret)
        moved = jnp.maximum(jnp.sum(active, dtype=jnp.int32), 1)
        return new, y, moved
    new, y = ssm_update_reference(state, x, dt, a, b_in, c_out, active)
    return new, y, jnp.int32(state.shape[0])
