"""Static certification of Pallas kernels: VMEM budgets, tiling lint,
grid-race detection, and roofline contracts — before hardware ever runs one.

Every Pallas kernel in-tree shipped uncertified: the paged-decode dispatch
in ``kernels/paged_attention.py`` had never run on a chip, silently fell
back on *any* exception, and was skipped entirely for the int8 pools the
production path would actually serve. PRs 6 and 10 set the pattern —
freeze a static budget, audit every compiled artifact once, fail loudly on
drift — and this module extends that certification discipline down to the
kernel level. The unified ragged-attention kernel
(``kernels/ragged_paged_attention.py``, arxiv 2604.15464) landed through
exactly this strip: registered, budgeted, its data-dependent output map
proven injective at runtime ``index_args``, roofline banked.

``certify(fn, args)`` traces a kernel entry point to its jaxpr (under the
same ``i32_index_scope`` its launches use), finds every ``pallas_call``
(recursing through custom_vjp/pjit/scan sub-jaxprs), and checks each
against a frozen :class:`KernelBudget`:

- **VMEM working set** — per grid step, the sum of every VMEM-space
  block's bytes at the PADDED footprint (last two dims rounded up to the
  dtype's (sublanes, 128) tile; ×2 for grid-varying blocks: Mosaic
  double-buffers the pipeline; ×1 for grid-invariant blocks) plus
  scratch, likewise padded, against the
  per-generation VMEM cap (:data:`VMEM_CAPS`). ``ANY``/HBM-space operands
  (manually DMA'd pools) and semaphores don't occupy the budget.
- **Tiling lint** — block shapes against the (sublane, lane) minimums per
  dtype ((8,128) f32, (16,128) bf16, (32,128) int8): a lane-misaligned
  block that doesn't cover its array axis is an ERROR (layout-breaking); a
  sub-minimum sublane is a WARNING (Mosaic pads the tile — wasteful, not
  wrong). Array dims must divide by block dims (a partial trailing block
  is silently-unwritten output, the ``fused_layernorm`` rows%8 hazard).
- **Grid-race detection** — each *output* BlockSpec ``index_map`` is
  evaluated over the full grid (bounded by ``budget.max_race_points``)
  and proven injective. Two grid points mapping to the same output block
  along a ``parallel`` dimension is a write race — an error even when
  sequential revisits are declared, unless the budget additionally
  declares ``allow_parallel_revisits`` (the splash scratch-as-output
  idiom: every core writes its own copy, safe only as per-core scratch).
  A revisit along ``arbitrary`` (sequential) dimensions is the legal
  online-accumulation idiom (flash attention revisits its output across
  the KV dim) and passes only when the budget declares
  ``allow_output_revisits``. Index maps reading scalar-prefetch operands
  are data-dependent — injectivity is undecidable statically, so they
  fail closed unless ``allow_data_dependent_outputs`` — AND, when
  ``certify(..., index_args=)`` supplies concrete runtime values for the
  scalar operands (the ragged kernel's ``(ctx_lens, cu_q_lens,
  page_table)``), the map is evaluated for real and the standard
  injectivity proof runs on it: the declaration sanctions the
  data-dependence, the runtime proof resolves it.
- **Roofline contract** — analytical FLOPs (declared per registry entry),
  a static HBM traffic model (block bytes × index-map *transitions* over
  the row-major grid — Mosaic skips the refetch when consecutive steps
  reuse a block), and arithmetic intensity, banked to
  ``profiles/kernelcheck.json`` and diffed against the composite path's
  hlocheck cost roll-up (``hlocheck.audit`` flops + materialized bytes),
  so every kernel carries a predicted-speedup record an on-chip A/B
  (``tools/flash_autotune.py`` idiom) can confirm or refute. Re-running against the bank fails loudly on drift
  in any analytic field; the composite-measured side is re-measured and
  reported, never hard-pinned (XLA cost models move across versions).

:data:`REGISTRY` names the in-tree kernel families (flash/splash
attention, the unified ragged paged kernel at its four mode shapes, the
legacy library paged decode, fused layernorm fwd+dx, the fused Adam
update), mirroring ``hlocheck.REGISTRY``; ``run_kernel`` certifies one
entry the way ``hlocheck.run_step`` audits one step.
``coverage_report()`` statically enumerates the dispatch gates
(``FLAGS_use_pallas_kernels``, the unified ``ragged_kernel_eligible``
rules, flash ``flash_route`` incl. the causal pad-to-block rescue) and
reports which serving configs reach a Pallas kernel vs the composite —
PR 11's "int8 decode has no fast kernel" finding flipped to covered when
the ragged kernel landed; "head_dim 64 is kernel-less" is open again,
because the v5e compiler refuses that page DMA (the gate's reason quotes
it) — a static check on CPU could never see that.

CLI: ``python -m paddle_tpu.analysis kernelcheck [--kernel NAME] [--bank]
[--json PATH]`` (also ``tools/kernelcheck.py``), exit 0 clean / 1 on any
violation / 2 bad usage — everything runs on CPU, no TPU required: only
jaxprs are inspected and only composite references are (AOT-)compiled.

Like hlocheck, this module never imports the kernels at module level —
the registry builders import them lazily, and ``kernels/`` modules import
only :func:`validate_flash_tuned` from here (lazily, at table load).
"""
from __future__ import annotations

import itertools
import json
import math
import os
from dataclasses import dataclass, field

__all__ = ["KernelBudget", "KernelFinding", "PallasCallReport",
           "KernelCertReport", "KernelCheckError", "VMEM_CAPS", "LANE",
           "certify", "KernelSpec", "REGISTRY", "run_kernel",
           "coverage_report", "validate_flash_tuned",
           "validate_ragged_tuned", "bank_path", "diff_banked", "main"]


class KernelCheckError(RuntimeError):
    """A kernel failed static certification."""


# ------------------------------------------------------------------ budgets
#: lane width of every TPU vector tile (minor-most dim), all generations
LANE = 128

#: minimum tile second-to-minor size × dtype width == 32 bytes: (8,128)
#: f32, (16,128) bf16, (32,128) int8/fp8
_SUBLANE_BYTES = 32

#: per-core VMEM by TPU generation (the guide's ~16 MiB/core; kernels are
#: certified against the oldest generation they claim to serve)
VMEM_CAPS = {
    "v3": 16 << 20,
    "v4": 16 << 20,
    "v5e": 16 << 20,
    "v5p": 16 << 20,
}

DEFAULT_GENERATION = "v5e"


@dataclass(frozen=True)
class KernelBudget:
    """Frozen per-kernel certification contract.

    ``vmem_frac`` leaves headroom for Mosaic's internal scratch below the
    hardware cap. ``allow_output_revisits`` sanctions the sequential-
    accumulation idiom (same output block revisited along ``arbitrary``
    grid dims — flash attention's KV loop); a collision along a
    ``parallel`` dim is a race regardless, unless
    ``allow_parallel_revisits`` additionally sanctions it (the splash
    scratch-as-output idiom — statically indistinguishable from a
    megacore write race, so it takes its own explicit declaration and
    still warns). ``allow_data_dependent_outputs`` sanctions output
    index maps that read scalar-prefetch operands (injectivity
    undecidable statically — fail closed by default).
    ``max_race_points`` bounds the grid enumeration of the race proof."""
    generation: str = DEFAULT_GENERATION
    vmem_frac: float = 0.9
    allow_output_revisits: bool = False
    allow_parallel_revisits: bool = False
    allow_data_dependent_outputs: bool = False
    max_race_points: int = 4096

    @property
    def vmem_cap(self) -> int:
        return int(VMEM_CAPS[self.generation] * self.vmem_frac)


# ----------------------------------------------------------------- findings
@dataclass(frozen=True)
class KernelFinding:
    kind: str      # vmem | tiling | race | dispatch | trace | drift
    severity: str  # "error" (fails certification) | "warn" (reported)
    message: str

    def __str__(self) -> str:
        return f"[{self.kind}/{self.severity}] {self.message}"


@dataclass(frozen=True)
class PallasCallReport:
    """Everything one ``pallas_call`` admits statically."""
    name: str
    grid: tuple
    dimension_semantics: tuple
    vmem_bytes: int
    vmem_cap: int
    hbm_bytes: int          # static traffic model (see module docstring)
    block_shapes: tuple     # (operand kind, block dims, array shape, dtype)
    output_revisits: int    # legal sequential revisits observed
    findings: tuple = ()


@dataclass(frozen=True)
class KernelCertReport:
    """One kernel entry point's certificate: every pallas_call it traces
    to, plus the entry-level dispatch-constraint results."""
    name: str
    calls: tuple = ()
    findings: tuple = ()  # entry-level (dispatch constraints, trace)

    def all_findings(self) -> tuple:
        out = list(self.findings)
        for c in self.calls:
            out.extend(c.findings)
        return tuple(out)

    @property
    def errors(self) -> tuple:
        return tuple(f for f in self.all_findings() if f.severity == "error")

    @property
    def ok(self) -> bool:
        return not self.errors

    @property
    def vmem_bytes(self) -> int:
        return max((c.vmem_bytes for c in self.calls), default=0)

    @property
    def hbm_bytes(self) -> int:
        return sum(c.hbm_bytes for c in self.calls)

    def summary(self) -> str:
        grids = ", ".join(str(c.grid) for c in self.calls) or "none"
        state = "OK" if self.ok else \
            f"{len(self.errors)} violation(s)"
        warns = sum(1 for f in self.all_findings() if f.severity == "warn")
        wtxt = f", {warns} warning(s)" if warns else ""
        cap = self.calls[0].vmem_cap if self.calls else 0
        return (f"kernelcheck {self.name}: {len(self.calls)} pallas_call(s);"
                f" grid {grids}; vmem {_fmt_bytes(self.vmem_bytes)} / "
                f"{_fmt_bytes(cap)}; hbm/call {_fmt_bytes(self.hbm_bytes)}; "
                f"{state}{wtxt}")


from .hlocheck import _fmt_bytes  # noqa: E402 — one formatter, two auditors


# ------------------------------------------------------------ jaxpr walking
def _find_pallas_eqns(jaxpr, out=None) -> list:
    """Every ``pallas_call`` eqn in a jaxpr, recursing through sub-jaxprs
    (custom_vjp/pjit/scan/cond params carry Jaxpr/ClosedJaxpr values)."""
    from jax.extend import core as jex_core

    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for x in vals:
                if isinstance(x, jex_core.ClosedJaxpr):
                    _find_pallas_eqns(x.jaxpr, out)
                elif isinstance(x, jex_core.Jaxpr):
                    _find_pallas_eqns(x, out)
    return out


def _memory_space(aval) -> str:
    """Normalized memory-space tag of a block/scratch aval: 'vmem' (the
    default), 'any', 'smem', 'semaphore', ..."""
    ms = getattr(aval, "memory_space", None)
    return "vmem" if ms is None else str(ms).lower()


def _int_block_dims(block_shape) -> list:
    """(axis, size) for the sized dims of a block shape (``Blocked`` /
    ``Element`` carry a ``block_size``) — ``Squeezed`` dims don't exist in
    the VMEM tile."""
    return [(ax, int(d.block_size)) for ax, d in enumerate(block_shape)
            if hasattr(d, "block_size")]


def _block_text(block_shape) -> tuple:
    """Block dims as strings — the size, or ``None`` for a squeezed dim."""
    return tuple(str(getattr(d, "block_size", None)) for d in block_shape)


def _block_nbytes(bm) -> int:
    """Logical bytes of one block — what a fetch moves over HBM."""
    import numpy as np

    n = int(np.dtype(bm.array_aval.dtype).itemsize)
    for _, d in _int_block_dims(bm.block_shape):
        n *= d
    return n


def _vmem_nbytes(dims, dtype) -> int:
    """Bytes a buffer of these dims occupies in VMEM, at the padded tile
    footprint — the model the kernels' own gates use
    (``kernels._common.vmem_nbytes``)."""
    import numpy as np

    from ..kernels._common import vmem_nbytes

    return vmem_nbytes(dims, int(np.dtype(dtype).itemsize))


def _index_map_info(bm, n_grid: int):
    """(data_dependent, constant): does the index map read scalar-prefetch
    operands / is it invariant over the grid (all-literal outputs)?"""
    from jax.extend import core as jex_core

    jx = bm.index_map_jaxpr.jaxpr
    used = set()
    for eqn in jx.eqns:
        for v in eqn.invars:
            if isinstance(v, jex_core.Var):
                used.add(v)
    outs = {v for v in jx.outvars if isinstance(v, jex_core.Var)}
    scalar_refs = jx.invars[n_grid:]
    data_dependent = any(v in used or v in outs for v in scalar_refs)
    constant = not any(v in used or v in outs for v in jx.invars[:n_grid])
    return data_dependent, constant


def _eval_index_map(bm, grid, max_points: int, index_args=None):
    """The index map's block-index tuple at each grid point, in row-major
    (pipeline) order. Returns (points, tuples, truncated). Evaluated
    under the i32 scope the map was traced in — the package-global x64
    would promote the literal arithmetic and break mixed-dtype selects.

    ``index_args`` supplies CONCRETE runtime values for the map's
    scalar-prefetch operands (``ctx_lens``/``cu_q_lens``/page tables —
    the ragged kernel's parameterization): with them a data-dependent
    map is evaluated for real and its injectivity PROVEN for that
    representative call instead of failing closed. Scalar-prefetch
    operands appear in the map jaxpr as Refs, so the jaxpr is discharged
    to functional form first (discharge appends the final ref values as
    extra outputs — sliced off)."""
    import jax
    import numpy as np

    from ..kernels._common import i32_index_scope

    jx = bm.index_map_jaxpr
    n_grid = len(grid)
    extras = jx.jaxpr.invars[n_grid:]
    jaxpr, consts = jx.jaxpr, jx.consts
    n_out = len(jaxpr.outvars)
    if extras:
        from jax._src.state.discharge import discharge_state

        jaxpr, consts = discharge_state(jaxpr, consts)
    if index_args is not None:
        vals = [np.asarray(a) for a in index_args]
        if len(vals) != len(extras):
            raise ValueError(
                f"index_args supplies {len(vals)} scalar-prefetch "
                f"value(s) but the index map takes {len(extras)}")
    else:
        # non-data-dependent maps never read these; shape-correct zeros
        # keep the discharged jaxpr evaluable either way
        vals = [np.zeros(tuple(getattr(v.aval, "shape", ()) or ()),
                         getattr(v.aval, "dtype", np.int32))
                for v in extras]
    points, tuples = [], []
    it = itertools.product(*(range(int(g)) for g in grid))
    with i32_index_scope():
        for point in itertools.islice(it, max_points):
            args = [np.int32(i) for i in point] + vals
            out = jax.core.eval_jaxpr(jaxpr, consts, *args)[:n_out]
            points.append(point)
            tuples.append(tuple(int(x) for x in out))
    total = 1
    for g in grid:
        total *= int(g)
    return points, tuples, total > len(points)


# ------------------------------------------------------------- certify core
def _certify_call(eqn, budget: KernelBudget, name: str,
                  index_args=None) -> PallasCallReport:
    import numpy as np

    gm = eqn.params["grid_mapping"]
    grid = tuple(gm.grid)
    cp = (eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    semantics = tuple(getattr(cp, "dimension_semantics", None)
                      or ("arbitrary",) * len(grid))
    findings: list[KernelFinding] = []
    blocks = []

    n_steps = 1
    for g in grid:
        n_steps *= int(g)

    # ---- VMEM + HBM models + tiling lint over the block mappings
    vmem = 0
    hbm = 0
    in_out = ["in"] * gm.num_inputs + ["out"] * gm.num_outputs
    for kind, bm in zip(in_out, gm.block_mappings):
        arr = bm.array_aval
        dt = np.dtype(arr.dtype)
        space = _memory_space(bm.block_aval)
        nbytes = _block_nbytes(bm)
        blocks.append((kind, _block_text(bm.block_shape),
                       tuple(arr.shape), str(dt)))
        data_dep, constant = _index_map_info(bm, len(grid))

        # tiling lint (VMEM-resident blocks only — ANY-space operands are
        # DMA'd manually and tile at their copy sites)
        if space.startswith("vmem") or space == "vmem":
            ints = _int_block_dims(bm.block_shape)
            for ax, d in ints:
                ad = int(arr.shape[ax])
                if d < ad and ad % d:
                    findings.append(KernelFinding(
                        "tiling", "error",
                        f"{name} {kind} block {_block_text(bm.block_shape)} over array "
                        f"{tuple(arr.shape)}: axis {ax} dim {ad} is not "
                        f"divisible by block dim {d} — the grid truncates "
                        f"and the partial trailing block is silently "
                        f"unwritten/unread"))
            if ints:
                lane_ax, lane_d = ints[-1]
                if lane_d % LANE and lane_d < int(arr.shape[lane_ax]):
                    findings.append(KernelFinding(
                        "tiling", "error",
                        f"{name} {kind} block {_block_text(bm.block_shape)} ({dt}): "
                        f"minor dim {lane_d} is neither a {LANE}-lane "
                        f"multiple nor the whole array axis "
                        f"({arr.shape[lane_ax]}) — Mosaic cannot lay out "
                        f"a strided partial-lane tile"))
            if len(ints) >= 2:
                sub_ax, sub_d = ints[-2]
                min_sub = max(1, _SUBLANE_BYTES // dt.itemsize)
                if sub_d % min_sub and sub_d < int(arr.shape[sub_ax]):
                    findings.append(KernelFinding(
                        "tiling", "warn",
                        f"{name} {kind} block {_block_text(bm.block_shape)} ({dt}): "
                        f"sublane dim {sub_d} is below/off the "
                        f"({min_sub}, {LANE}) minimum tile for {dt} — "
                        f"Mosaic pads the tile (wasteful, not wrong)"))

        # VMEM working set: ×2 for grid-varying blocks (pipeline double
        # buffer), ×1 for invariant blocks; ANY/HBM operands excluded
        if "any" in space or "hbm" in space:
            hbm += int(np.prod(arr.shape)) * dt.itemsize  # manual-DMA bound
            continue
        if "semaphore" in space:
            continue
        vmem += _vmem_nbytes(
            [d for _, d in _int_block_dims(bm.block_shape)],
            dt) * (1 if constant else 2)
        # HBM traffic: one fetch per index-map transition in row-major
        # order (consecutive equal indices reuse the resident block)
        if constant:
            hbm += nbytes
        elif data_dep and index_args is None:
            hbm += nbytes * n_steps  # undecidable: every-step upper bound
        else:
            # data-dependent maps WITH runtime index_args evaluate for
            # real — the banked HBM model reflects the canonical call
            # instead of the every-step upper bound
            _, tuples, truncated = _eval_index_map(
                bm, grid, budget.max_race_points, index_args)
            transitions = 1 + sum(1 for a, b in zip(tuples, tuples[1:])
                                  if a != b)
            hbm += nbytes * (n_steps if truncated else transitions)

    # scratch (already sized with its own buffering)
    n_io = gm.num_index_operands + gm.num_inputs + gm.num_outputs
    inner = eqn.params["jaxpr"]
    for var in inner.invars[n_io:]:
        aval = var.aval
        space = _memory_space(aval)
        if "semaphore" in space:
            continue
        shape = getattr(getattr(aval, "inner_aval", aval), "shape", ())
        dtype = getattr(getattr(aval, "inner_aval", aval), "dtype", None)
        try:
            np.dtype(dtype)
        except TypeError:  # exotic ref dtypes don't budget
            continue
        vmem += _vmem_nbytes(shape, dtype)

    cap = budget.vmem_cap
    if vmem > cap:
        findings.append(KernelFinding(
            "vmem", "error",
            f"{name}: per-grid-step VMEM working set "
            f"{_fmt_bytes(vmem)} exceeds the {budget.generation} budget "
            f"{_fmt_bytes(cap)} ({budget.vmem_frac:.0%} of "
            f"{_fmt_bytes(VMEM_CAPS[budget.generation])}) — shrink the "
            f"block shapes or move operands to ANY/HBM with manual DMA"))

    # ---- grid-race detection over the OUTPUT block mappings
    revisits = 0
    for out_i, bm in enumerate(gm.block_mappings[gm.num_inputs:
                                                 gm.num_inputs
                                                 + gm.num_outputs]):
        data_dep, constant = _index_map_info(bm, len(grid))
        if data_dep and not (index_args is not None
                             and budget.allow_data_dependent_outputs):
            sev = ("warn" if budget.allow_data_dependent_outputs
                   else "error")
            findings.append(KernelFinding(
                "race", sev,
                f"{name} output {out_i}: index_map reads scalar-prefetch "
                f"operands — injectivity over the grid is data-dependent "
                f"and cannot be proven statically"
                + (" (pass index_args= with runtime scalar values to "
                   "prove it for a representative call)" if sev == "warn"
                   else " (declare allow_data_dependent_outputs to "
                        "sanction)")))
            continue
        # a data-dependent output map that reaches here is RESOLVED:
        # allow_data_dependent_outputs is declared AND index_args carry
        # the runtime scalar values, so the map evaluates for real below
        # and the standard run/reappear injectivity proof applies to it
        if len(grid) == 0:
            continue
        points, tuples, truncated = _eval_index_map(
            bm, grid, budget.max_race_points, index_args)
        if truncated:
            findings.append(KernelFinding(
                "race", "warn",
                f"{name} output {out_i}: grid has more than "
                f"{budget.max_race_points} points — race proof covers the "
                f"first {len(points)} (row-major) only"))
        # Mosaic writes an output block back to HBM only when its index
        # CHANGES between consecutive grid steps — a contiguous run of
        # equal indices is the resident-block accumulation idiom (flash's
        # KV loop), legal when the budget declares it. A block index that
        # REAPPEARS after the map moved away is the true overwrite race:
        # the first run's writeback is refetched (or clobbered) by the
        # second. A run whose points differ along a 'parallel' dim spans
        # megacore partitions — a write race (an error even when
        # sequential revisits are declared) unless the budget sanctions
        # it as per-core scratch via allow_parallel_revisits (the splash
        # scratch-as-output idiom), in which case it still warns.
        closed: dict[tuple, tuple] = {}
        run_start = None
        raced = reappeared = par_warned = False
        for point, t in zip(points, tuples):
            if run_start is not None and t == prev_t:
                revisits += 1
                if not par_warned:
                    diff = [ax for ax in range(len(grid))
                            if run_start[ax] != point[ax]]
                    if any(semantics[ax] == "parallel" for ax in diff):
                        par_warned = True
                        par_sev = ("warn" if budget.allow_parallel_revisits
                                   else "error")
                        findings.append(KernelFinding(
                            "race", par_sev,
                            f"{name} output {out_i}: block {t} is "
                            f"revisited across a 'parallel' grid dim "
                            f"({run_start} .. {point}) — a megacore "
                            f"split would write it from both cores; "
                            f"safe only as per-core scratch (the "
                            f"scratch-as-output idiom"
                            + (")" if par_sev == "warn" else
                               " — declare allow_parallel_revisits to "
                               "sanction)")))
                if not budget.allow_output_revisits and not raced:
                    raced = True
                    findings.append(KernelFinding(
                        "race", "error",
                        f"{name} output {out_i}: grid points {run_start} "
                        f"and {point} both map to output block {t} — the "
                        f"in-place accumulation idiom, but this budget "
                        f"does not declare allow_output_revisits, so the "
                        f"kernel overwrites its own output"))
                continue
            if run_start is not None:
                closed[prev_t] = run_start
            if t in closed and not reappeared:
                reappeared = True
                findings.append(KernelFinding(
                    "race", "error",
                    f"{name} output {out_i}: output block {t} written by "
                    f"grid point {point} REAPPEARS after the index map "
                    f"already moved away (first run started at "
                    f"{closed[t]}) — Mosaic wrote the first run back to "
                    f"HBM and this visit clobbers it; two grid indices "
                    f"mapping to the same output block is a write race"))
            run_start, prev_t = point, t

    return PallasCallReport(
        name=name, grid=grid, dimension_semantics=semantics,
        vmem_bytes=int(vmem), vmem_cap=cap, hbm_bytes=int(hbm),
        block_shapes=tuple(blocks), output_revisits=revisits,
        findings=tuple(findings))


def certify(fn, args, *, name: str | None = None,
            budget: KernelBudget | None = None,
            constraints=(), index_args=None) -> KernelCertReport:
    """Trace ``fn(*args)`` to a jaxpr (args may be ShapeDtypeStructs —
    nothing executes, nothing materializes) and certify every
    ``pallas_call`` it contains against ``budget``. ``constraints`` are
    pre-evaluated entry-level dispatch checks ``(name, ok, detail)`` —
    a False one is a dispatch violation (the composite-fallback rules,
    e.g. flash's %block gate, checked statically instead of discovered
    at runtime). ``index_args`` are concrete runtime values for the
    kernel's scalar-prefetch operands (``ctx_lens``/``cu_q_lens``/page
    table): with them, data-dependent output index maps sanctioned by
    ``allow_data_dependent_outputs`` get a REAL injectivity proof for
    the representative call (and data-dependent HBM traffic is counted
    from actual transitions) — resolved, not suppressed."""
    import jax

    from ..kernels._common import i32_index_scope

    name = name or getattr(fn, "__name__", "kernel")
    budget = budget or KernelBudget()
    findings: list[KernelFinding] = []
    for cname, ok, detail in constraints:
        if not ok:
            findings.append(KernelFinding(
                "dispatch", "error",
                f"{name}: dispatch constraint {cname!r} does not hold for "
                f"the certified shapes — {detail}"))
    try:
        with i32_index_scope():  # kernels trace like their launches
            jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    except Exception as e:  # noqa: BLE001 — an untraceable kernel is the
        # finding (the paged-decode x64 bug shipped exactly this way)
        findings.append(KernelFinding(
            "trace", "error",
            f"{name}: kernel entry point failed to trace "
            f"({type(e).__name__}: {str(e)[:300]}) — every launch would "
            f"raise"))
        return KernelCertReport(name=name, findings=tuple(findings))
    eqns = _find_pallas_eqns(jaxpr.jaxpr)
    if not eqns:
        findings.append(KernelFinding(
            "trace", "error",
            f"{name}: no pallas_call reached from the entry point — the "
            f"certified function dispatches to a composite path"))
    calls = tuple(
        _certify_call(eqn, budget,
                      name if len(eqns) == 1 else f"{name}[{i}]",
                      index_args=index_args)
        for i, eqn in enumerate(eqns))
    return KernelCertReport(name=name, calls=calls,
                            findings=tuple(findings))


# --------------------------------------------------------- flash_tuned lint
def validate_flash_tuned(table: dict) -> list[str]:
    """Tiling-constraint validation for ``kernels/flash_tuned.json``
    entries (``"seq,head_dim" -> block edge``), shared by the load site in
    ``kernels/flash_attention.py`` and the writer in
    ``tools/flash_autotune.py``: a misaligned entry is rejected with a
    clear error at load/bank time, never discovered as a runtime Pallas
    failure. Returns error strings (empty = clean)."""
    errors = []
    for key, blk in sorted(table.items()):
        try:
            s, d = (int(x) for x in str(key).split(","))
        except ValueError:
            errors.append(f"{key!r}: key must be 'seq,head_dim' ints")
            continue
        if not isinstance(blk, int) or blk <= 0:
            errors.append(f"{key!r}: block edge {blk!r} must be a "
                          f"positive int")
            continue
        if blk % LANE:
            errors.append(f"{key!r}: block edge {blk} is not a multiple "
                          f"of the {LANE}-lane MXU tile")
        if blk > s:
            errors.append(f"{key!r}: block edge {blk} exceeds seq {s}")
        elif s % blk:
            errors.append(f"{key!r}: block edge {blk} does not tile "
                          f"seq {s} (s % block != 0 dies inside Pallas)")
        if d % 64:
            errors.append(f"{key!r}: head_dim {d} is not a multiple of "
                          f"the 64-lane tile the kernel requires")
    return errors


def validate_ragged_tuned(table: dict) -> list[str]:
    """Constraint validation for ``kernels/ragged_tuned.json`` entries,
    shared by the load site in ``kernels/ragged_paged_attention.py`` and
    the writer in ``tools/ragged_autotune.py`` — the flash_tuned
    discipline: load can never see an entry bank rejected. A value under
    a ``"page_size,num_heads,head_dim"`` key is either the legacy bare
    ``block_heads`` int or the pipeline-aware dict schema
    ``{"block_heads": B, "pipeline_chunk": C, "pages_per_seq": P}``:
    ``B`` must divide ``num_heads`` and ``C`` must divide the ``P``
    recorded at tune time — a STALE entry whose chunk no longer divides
    its page count is rejected here, not discovered as a mis-tiled
    launch. Returns error strings (empty = clean)."""
    errors = []
    for key, val in sorted(table.items()):
        try:
            ps, h, d = (int(x) for x in str(key).split(","))
        except ValueError:
            errors.append(f"{key!r}: key must be "
                          f"'page_size,num_heads,head_dim' ints")
            continue
        if ps <= 0 or h <= 0 or d <= 0:
            errors.append(f"{key!r}: page_size/num_heads/head_dim must "
                          f"be positive")
            continue
        if isinstance(val, dict):
            unknown = set(val) - {"block_heads", "pipeline_chunk",
                                  "pages_per_seq"}
            if unknown:
                errors.append(f"{key!r}: unknown field(s) "
                              f"{sorted(unknown)} — the dict schema is "
                              f"block_heads/pipeline_chunk/pages_per_seq")
                continue
            bh = val.get("block_heads", 1)
            chunk = val.get("pipeline_chunk")
            pages = val.get("pages_per_seq")
        else:
            bh, chunk, pages = val, None, None
        if not isinstance(bh, int) or bh <= 0:
            errors.append(f"{key!r}: block_heads {bh!r} must be a "
                          f"positive int")
            continue
        if h % bh:
            errors.append(f"{key!r}: block_heads {bh} does not divide "
                          f"num_heads {h} — the head grid dim would "
                          f"truncate and the tail heads would be "
                          f"silently unserved")
        if chunk is None:
            continue
        if not isinstance(chunk, int) or chunk <= 0:
            errors.append(f"{key!r}: pipeline_chunk {chunk!r} must be a "
                          f"positive int")
            continue
        if not isinstance(pages, int) or pages <= 0:
            errors.append(f"{key!r}: pipeline_chunk {chunk} without a "
                          f"positive pages_per_seq — the chunk is only "
                          f"meaningful against the page count it was "
                          f"tuned at")
            continue
        if pages % chunk:
            errors.append(f"{key!r}: pipeline_chunk {chunk} does not "
                          f"divide pages_per_seq {pages} — a stale "
                          f"entry (the page count moved since the "
                          f"tune); re-run tools/ragged_autotune.py")
    return errors


# ----------------------------------------------------------------- registry
@dataclass(frozen=True)
class KernelSpec:
    """A named certifiable kernel: ``build()`` returns a dict with the
    entry point, example args (ShapeDtypeStructs — trace-only), budget,
    dispatch constraints, analytic FLOPs, and the composite reference the
    roofline is diffed against through ``hlocheck.audit``."""
    name: str
    doc: str
    build: object = field(repr=False)


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _flash_args():
    import jax.numpy as jnp

    b, h, s, d = 1, 2, 1024, 128
    return (b, h, s, d), _sds((b, h, s, d), jnp.float32)


def _build_flash():
    from ..kernels import flash_attention as fa
    from ..kernels.attention import sdpa_reference

    (b, h, s, d), q = _flash_args()
    blk = fa._block(s, d)
    constraints = (
        ("supports_shape", fa.supports_shape((b, h, s, d), (b, h, s, d)),
         f"seq {s} must tile the tuned block edge {blk} and the 128 MXU "
         f"tile, head_dim {d} the 64-lane tile"),
        ("composite_fallback_640",
         not fa.supports_shape((b, h, 640, d), (b, h, 640, d)),
         "s=640 passes %128 but not %512 — it must take the composite "
         "path, not die inside Pallas (the flash_attention.py "
         "supports_shape rule, now certified statically)"),
    )
    return dict(
        fn=lambda q, k, v: fa._flash(q, k, v, True, 0.125),
        args=(q, q, q),
        # the KV grid dim revisits the output block — the online-softmax
        # accumulation contract
        budget=KernelBudget(allow_output_revisits=True),
        constraints=constraints,
        # causal MACs ~ half the dense s_q x s_k square, x2 matmuls (qk,
        # av), x2 flops/MAC
        flops=float(2 * b * h * s * s * d),
        composite=lambda q, k, v: sdpa_reference(q, k, v, is_causal=True,
                                                 scale=0.125),
        composite_args=(q, q, q))


def _build_flash_bwd():
    import jax
    import jax.numpy as jnp

    from ..kernels import flash_attention as fa
    from ..kernels.attention import sdpa_reference

    (b, h, s, d), q = _flash_args()
    lse = _sds((b, h, 1, s), jnp.float32)

    def composite(q, k, v, do):
        return jax.vjp(lambda q, k, v: sdpa_reference(
            q, k, v, is_causal=True, scale=0.125), q, k, v)[1](do)

    return dict(
        fn=lambda q, k, v, o, lse, do: fa._flash_bwd_call(
            q, k, v, o, lse, do, True, 0.125, fa._edges(q, k),
            fa._dq_resident(q), False),
        args=(q, q, q, q, lse, q),
        # dK and dV are revisited across the q grid dim and a head's whole
        # dQ across both: the accumulators of the one fused backward
        budget=KernelBudget(allow_output_revisits=True),
        constraints=(
            ("dq_resident", fa._dq_resident(q),
             "a head's dQ (fp32 accumulator + two fp32 output buffers) "
             "stays in VMEM at the certified shape (above "
             "_DQ_RESIDENT_BYTES it leaves as per-kv-block partials)"),),
        # five products (k.qT, pT.dO, v.dOT, dsT.q, ds.k) over the causal
        # half, x2 flops/MAC
        flops=float(5 * b * h * s * s * d),
        composite=composite, composite_args=(q, q, q, q))


def _build_splash():
    import jax.numpy as jnp

    from ..kernels import flash_attention as fa
    from ..kernels.attention import sdpa_reference

    b, h, s, d = 1, 2, 1024, 128
    q = _sds((b, h, s, d), jnp.float32)
    return dict(
        fn=lambda q, k, v: fa._splash_impl(q, k, v, 0.125, False),
        args=(q, q, q),
        # the library splash kernel emits its logsumexp/max stats as
        # outputs revisited across the parallel head dim — per-core
        # scratch-as-output, sanctioned explicitly (and still warned)
        budget=KernelBudget(allow_output_revisits=True,
                            allow_parallel_revisits=True),
        constraints=(
            ("block_tiles_seq", s % fa._block(s, d) == 0,
             "splash block edges must tile the sequence"),),
        flops=float(2 * b * h * s * s * d),
        composite=lambda q, k, v: sdpa_reference(q, k, v, is_causal=True,
                                                 scale=0.125),
        composite_args=(q, q, q))


# the canonical serving decode shape the coverage report and the paged
# certificate share: bench-model head_dim on the 128-lane tile, 16-token
# pages, 32 pages per sequence (512-token context window)
_PAGED_SHAPE = dict(batch=2, heads=2, head_dim=128, num_pages=64,
                    page_size=16, pages_per_seq=32)

#: granite-4.0-h-micro's attention: 32 query heads over 8 KV heads of 64,
#: pages of 16, a table of 96 (the coverage report's grouped-head rows)
_GQA_SHAPE = dict(heads=32, kv_heads=8, head_dim=64, page_size=16,
                  pages_per_seq=96)


def _build_paged_decode():
    import jax.numpy as jnp

    from ..kernels import paged_attention as pa
    from ..kernels.attention import sdpa_reference

    p = _PAGED_SHAPE
    b, h, d = p["batch"], p["heads"], p["head_dim"]
    ps, pps = p["page_size"], p["pages_per_seq"]
    S = ps * pps
    q = _sds((b, h, 1, d), jnp.float32)
    pool = _sds((p["num_pages"], ps, h, d), jnp.float32)
    table = _sds((b, pps), jnp.int32)
    ctx = _sds((b,), jnp.int32)
    ok, _why = pa.decode_kernel_eligible(d, pps, ps, num_heads=h)
    ok_q8, why_q8 = pa.decode_kernel_eligible(d, pps, ps, num_heads=h,
                                              quantized=True)
    constraints = (
        ("decode_kernel_eligible", ok,
         "the serving decode shape must pass every dispatch gate"),
        # the PR 11 'int8_skip_is_declared' constraint, inverted: the
        # unified ragged kernel fuses the dequant, so the quantized
        # serving path is now kernel-ELIGIBLE — certified here so the
        # coverage flip can never silently regress
        ("int8_served_by_unified_kernel", ok_q8, why_q8),
    )

    def composite(q, kp, vp, table, ctx):
        k_all = pa.paged_gather(kp, table)
        v_all = pa.paged_gather(vp, table)
        mask = pa.ragged_mask(ctx, k_all.shape[2], 1)
        return sdpa_reference(q, k_all, v_all, mask=mask)

    return dict(
        fn=lambda q, kp, vp, t, c: pa._pallas_decode(q, kp, vp, t, c, None),
        args=(q, pool, pool, table, ctx),
        budget=KernelBudget(),
        constraints=constraints,
        flops=float(4 * b * h * S * d),
        composite=composite,
        composite_args=(q, pool, pool, table, ctx))


def _build_ragged(mode: str):
    """The unified ragged paged-attention kernel at one serving mode's
    canonical shape: ``decode`` (s=1 fp32), ``q8`` (s=1, int8 codes +
    per-page-per-head scales, dequant fused into the gather), ``verify``
    (the spec K+1=5 contract), ``prefill`` (single-row chunk tail, 64-pad
    bucket at ctx0=192). All four trace to the SAME program shape — one
    kernel, four certificates — and all four certify the PIPELINED form
    (``pipeline_chunk=8``, the serving default of 128 tokens, over the
    32-page canonical row: its live chunks, 3 and 2 of 4 at the canonical
    ctx_lens, through 2 alternating staging buffers), so the scratch the
    VMEM model prices carries the ×2 double-buffer cost explicitly in its
    leading axis (and the one SMEM word that carries the staging buffer
    from a grid step to the next). ``index_args`` carry the canonical runtime
    scalar-prefetch values (ctx_lens, cu_q_lens, page table) so the
    data-dependent output index map is PROVEN injective, and the HBM
    model counts the canonical call's actual block transitions."""
    import numpy as np

    import jax.numpy as jnp

    from ..kernels import paged_attention as pa
    from ..kernels import ragged_paged_attention as rp
    from ..kernels.attention import sdpa_reference

    p = _PAGED_SHAPE
    b, h, d = p["batch"], p["heads"], p["head_dim"]
    ps, pps, npages = p["page_size"], p["pages_per_seq"], p["num_pages"]
    s = {"decode": 1, "q8": 1, "verify": 5, "prefill": 64}[mode]
    if mode == "prefill":
        b = 1
    quant = mode == "q8"
    S = ps * pps
    q = _sds((b, h, s, d), jnp.float32)
    pool = _sds((npages, ps, h, d), jnp.int8 if quant else jnp.float32)
    table = _sds((b, pps), jnp.int32)
    ctx = _sds((b,), jnp.int32)
    # canonical runtime values: a non-trivial page permutation and ragged
    # mid-context lengths — what the injectivity proof and the banked HBM
    # transition counts are evaluated at
    tab_np = (np.arange(1, 1 + b * pps, dtype=np.int32)
              .reshape(b, pps) % npages)
    ctx_np = (np.asarray([192], np.int32) if mode == "prefill"
              else np.asarray([317, 129][:b], np.int32))
    cu_np = np.arange(b + 1, dtype=np.int32) * s
    chunk = 8  # 4 chunks over the canonical 32-page row: pipeline ON
    ok, why = rp.ragged_kernel_eligible(d, pps, ps, s, num_heads=h,
                                        quantized=quant,
                                        pipeline_chunk=chunk)
    constraints = (
        ("ragged_kernel_eligible", ok, why or
         "the canonical shape must pass every unified-kernel gate "
         "(incl. the x2 staged buffers at the certified chunk)"),
    )

    if quant:
        scale = _sds((npages, h), jnp.float32)

        def fn(q, kp, vp, t, c, ksc, vsc):
            return rp.ragged_paged_attention(q, kp, vp, t, c,
                                             k_scale=ksc, v_scale=vsc,
                                             pipeline_chunk=chunk)

        def composite(q, kp, vp, t, c, ksc, vsc):
            k_all = pa.paged_gather_quant(kp, ksc, t, q.dtype)
            v_all = pa.paged_gather_quant(vp, vsc, t, q.dtype)
            mask = pa.ragged_mask(c, k_all.shape[2], s)
            return sdpa_reference(q, k_all, v_all, mask=mask)

        args = (q, pool, pool, table, ctx, scale, scale)
    else:
        def fn(q, kp, vp, t, c):
            return rp.ragged_paged_attention(q, kp, vp, t, c,
                                             pipeline_chunk=chunk)

        def composite(q, kp, vp, t, c):
            k_all = pa.paged_gather(kp, t)
            v_all = pa.paged_gather(vp, t)
            mask = pa.ragged_mask(c, k_all.shape[2], s)
            return sdpa_reference(q, k_all, v_all, mask=mask)

        args = (q, pool, pool, table, ctx)

    return dict(
        fn=fn, args=args,
        # the data-dependent output map (cu_q_lens[b] // s) is sanctioned
        # AND resolved: index_args below give the proof its runtime values
        budget=KernelBudget(allow_data_dependent_outputs=True),
        constraints=constraints,
        index_args=(ctx_np, cu_np, tab_np),
        # qk + av MACs over the gathered width, x2 flops/MAC
        flops=float(4 * b * h * s * S * d),
        composite=composite, composite_args=args)


def _build_mla_decode():
    """The absorbed latent-attention decode kernel at a canonical serving
    shape: 2 rows of 4 heads over a 640-wide latent pool (rank 512 + 64
    rotary + the lane pad), 32 pages a row staged 16 pages a chunk through
    two alternating buffers. The output map is the row's own index, so no
    data-dependent output needs a proof; the HBM model counts the q and
    output blocks (the pool rides manual DMA)."""
    import jax.numpy as jnp

    from ..kernels import latent_paged_attention as lp

    b, h, rank, rope, ps, pps, npages = 2, 4, 512, 64, 16, 32, 64
    width = lp.padded_width(rank + rope)
    q_lat = _sds((b, h, rank), jnp.float32)
    q_rope = _sds((b, h, rope), jnp.float32)
    pool = _sds((npages, ps, width), jnp.float32)
    table = _sds((b, pps), jnp.int32)
    ctx = _sds((b,), jnp.int32)
    ok, why = lp.mla_kernel_eligible(h, width, rank, ps, pps, itemsize=4)
    constraints = (
        ("mla_kernel_eligible", ok, why or
         "the canonical shape must pass the absorbed-decode kernel's gate"),
    )

    def fn(ql, qr, pool, t, c):
        q = jnp.concatenate(
            [ql, qr, jnp.zeros((b, h, width - rank - rope), ql.dtype)], -1)
        return lp.mla_decode_kernel_call(q, pool, t, c, rank=rank,
                                         scale=0.1)

    def composite(ql, qr, pool, t, c):
        seq = lp.latent_gather(pool, t)
        scores = (jnp.einsum("bhr,bsr->bhs", ql, seq[..., :rank])
                  + jnp.einsum("bhd,bsd->bhs", qr,
                               seq[..., rank:rank + rope])) * 0.1
        w = lp._ragged_softmax(scores[:, :, None, :], c, 1)[:, :, 0]
        return jnp.einsum("bhs,bsr->bhr", w, seq[..., :rank])

    args = (q_lat, q_rope, pool, table, ctx)
    return dict(
        fn=fn, args=args, budget=KernelBudget(), constraints=constraints,
        # scores over rank + rope, values over rank, all heads, the whole
        # gathered width, x2 flops/MAC
        flops=float(2 * b * h * ps * pps * (2 * rank + rope)),
        composite=composite, composite_args=args)


def _build_flash_grouped():
    """The grouped flash forward of a serving prefill at a canonical
    shape: 8 query heads over 2 KV heads of 128, a sequence of 2,048 from
    position 0 in blocks of 512, a window of 512: the in-tree ``flash_fwd``
    body with query head ``h`` reading KV head ``h // g`` and the kv
    blocks wholly behind a q block's window skipped like those above the
    diagonal (9 of 16 grid steps a head compute). The composite is the
    banded masked attention with the KV heads repeated."""
    import jax
    import jax.numpy as jnp

    from ..kernels import flash_attention as fa

    b, h, kv, s, d, window = 1, 8, 2, 2048, 128, 512
    q = _sds((b, h, s, d), jnp.bfloat16)
    k = _sds((b, kv, s, d), jnp.bfloat16)
    live = fa.grouped_live_steps(s, window)
    constraints = (
        ("grouped_supported", fa.grouped_supported(s, d),
         f"seq {s} must be whole blocks of {fa.grouped_edge(s)}"),
        ("window_skips_blocks", live == 7 < fa.grouped_live_steps(s) == 10,
         "a window of one block leaves the diagonal block and the one "
         "before it: 7 of the causal 10 live steps a head"),
        ("composite_below_a_block",
         not fa.grouped_supported(64, d),
         "a sequence under 128 tokens must take the composite"),
    )

    def composite(q, k, v):
        g = h // kv
        kk, vv = (jnp.repeat(t, g, axis=1) for t in (k, v))
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * 0.125
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        seen = (j <= i) & (i - j < window)
        pr = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", pr.astype(q.dtype), vv)

    return dict(
        fn=lambda q, k, v: fa.flash_fwd_grouped(q, k, v, 0.125, window),
        args=(q, k, k),
        budget=KernelBudget(allow_output_revisits=True),
        constraints=constraints,
        # the band's pairs: window x s less the corner, x2 matmuls, x2
        flops=float(4 * b * h * d * (window * s - window * (window - 1)
                                     // 2)),
        composite=composite, composite_args=(q, k, k))


def _build_gqa_decode(window: int | None = None):
    """The grouped-head decode kernel at a canonical serving shape: 2 rows
    of 8 query heads over 2 KV heads of 64 (a lane-dense pool row of 128),
    32 pages a row staged a chunk at a time, keys and values in the two
    halves of one staging buffer. The same pipeline as ``mla_decode``,
    given a values pool. What is certified is the kernel call with the
    block-diagonal queries built and each head's own columns picked off
    around it, as the dispatch runs it; the composite is
    ``_grouped_composite`` on the same pools. ``window``: the instance of
    a window layer, whose loop starts at the first chunk that holds a
    position inside the window."""
    import jax.numpy as jnp

    from ..kernels import paged_attention as pa
    from ..kernels import paged_decode as pd

    b, heads, kv, d, ps, pps, npages = 2, 8, 2, 64, 16, 32, 64
    q = _sds((b, heads, 1, d), jnp.float32)
    pool = _sds((npages, ps, kv * d), jnp.float32)
    table = _sds((b, pps), jnp.int32)
    ctx = _sds((b,), jnp.int32)
    ok, why = pd.gqa_kernel_eligible(heads, kv, d, ps, pps, itemsize=4)
    constraints = (
        ("gqa_kernel_eligible", ok, why or
         "the canonical shape must pass the grouped-head kernel's gate"),
        ("decode_only",
         not pd.gqa_kernel_eligible(heads, kv, d, ps, pps, 64)[0],
         "a call of several tokens a row must take the composite"),
        ("lane_dense_pool_only",
         not pd.gqa_kernel_eligible(heads, kv, d, ps, pps,
                                    flat_pool=False)[0],
         "a pool with a heads axis must take the composite"),
    )

    def fn(q, kp, vp, t, c):
        return pd.gqa_decode_attention(q, kp, vp, t, c, 0.125,
                                       window=window)

    def composite(q, kp, vp, t, c):
        return pa._grouped_composite(q, kp, vp, t, c, 0.125, window)

    args = (q, pool, pool, table, ctx)
    return dict(
        fn=fn, args=args, budget=KernelBudget(), constraints=constraints,
        # what the attention needs: scores and values over head_dim, all
        # heads, the whole gathered width, x2 flops/MAC (the kernel's
        # block-diagonal products do kv times that)
        flops=float(4 * b * heads * ps * pps * d),
        composite=composite, composite_args=args)


def _build_ssm_decode_update():
    """The decode state-update kernel at a canonical serving shape: 4
    slots of 8 heads x 16 x 128 float32 state, all heads a block, so a slot
    is one grid step. Its block index is the slot's LIVE ROW (a scalar-
    prefetched vector: a dead slot names the live slot before it, whose
    block the pipeline holds already), and so is the block of its decays
    (in SMEM) and of its lane-dense ``y``: data-dependent by declaration,
    resolved at ``index_args`` with every slot live, where it is the
    identity; a run of dead slots revisits one block in sequence, by
    declaration too (nothing is written in those steps). The state is
    aliased in place; the HBM model counts it in and out once a slot."""
    import jax.numpy as jnp
    import numpy as np

    from ..kernels import ssm_state_update as su

    slots, heads, p, n = 4, 8, 16, 128
    f32 = jnp.float32
    state = _sds((slots, heads, p, n), f32)
    x = _sds((slots, heads, p), f32)
    dt = _sds((slots, heads), f32)
    a = _sds((heads,), f32)
    row = _sds((slots, n), f32)
    active = _sds((slots,), jnp.bool_)
    ok, why = su.ssm_kernel_eligible(heads, p, n)
    constraints = (
        ("ssm_kernel_eligible", ok, why or
         "the canonical shape must pass the decode update's gate"),
        ("lane_rows_only", not su.ssm_kernel_eligible(heads, p, 96)[0],
         "a state whose minor axis is not whole 128-lane rows must take "
         "the plain recurrence"),
    )
    args = (state, x, dt, a, row, row, active)
    return dict(
        fn=su.ssm_decode_update, args=args,
        budget=KernelBudget(allow_data_dependent_outputs=True,
                            allow_output_revisits=True),
        constraints=constraints,
        index_args=(np.arange(slots, dtype=np.int32),
                    np.ones(slots, np.int32)),
        # decay, outer product and add on the VPU; the readout on the MXU,
        # C's row on 8 sublanes against the block's rows, x2 flops/MAC
        flops=float((3 + 2 * 8) * slots * heads * p * n),
        composite=su.ssm_update_reference, composite_args=args)


def _build_ln(which: str):
    import jax.numpy as jnp

    from ..kernels import fused_layernorm as fl

    rows, d = 256, 512
    x = _sds((rows, d), jnp.float32)
    vec = _sds((d,), jnp.float32)
    stat = _sds((rows, 1), jnp.float32)
    constraints = (
        ("rows_divisible", rows % fl._ROW_BLOCK == 0,
         f"rows % {fl._ROW_BLOCK} != 0 truncates the grid — the partial "
         f"trailing block would be silently UNWRITTEN output"),
        ("lane_tileable", d % fl._LANE == 0,
         "the norm dim must tile the 128-lane VPU row"),
        ("dispatch_min_rows", rows >= fl._MIN_ROWS,
         "below _MIN_ROWS the launch overhead loses to XLA fusion"),
    )

    def composite_fwd(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) * (x - mu), axis=-1, keepdims=True)
        rstd = 1.0 / jnp.sqrt(var + 1e-5)
        return (x - mu) * rstd * g + b, mu, rstd

    if which == "fwd":
        return dict(
            fn=lambda x, g, b: fl._call_fwd(x, g, b, 1e-5, False),
            args=(x, vec, vec), budget=KernelBudget(),
            constraints=constraints,
            flops=float(8 * rows * d),  # mean + centered var + normalize
            composite=composite_fwd, composite_args=(x, vec, vec))

    def composite_dx(x, g, mu, rstd, dy):
        xhat = (x - mu) * rstd
        wdy = dy * g
        c1 = jnp.mean(wdy, axis=-1, keepdims=True)
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        return rstd * (wdy - c1 - xhat * c2)

    return dict(
        fn=lambda x, g, mu, rstd, dy: fl._call_dx(x, g, mu, rstd, dy,
                                                  False),
        args=(x, vec, stat, stat, x), budget=KernelBudget(),
        constraints=constraints,
        flops=float(11 * rows * d),
        composite=composite_dx, composite_args=(x, vec, stat, stat, x))


def _build_adam():
    import jax.numpy as jnp

    from ..kernels import fused_optimizer as fo

    n = 1 << 16
    buf = _sds((n,), jnp.float32)
    sc = _sds((), jnp.float32)
    tile = fo._LANE * 8 * fo._ROWS_PER_BLOCK
    constraints = (
        ("size_tileable", n % tile == 0,
         f"size % {tile} != 0 would force a pad-copy of all four inputs — "
         f"the exact HBM traffic the kernel exists to avoid"),
        ("dispatch_min_size", n >= fo._MIN_FUSED_SIZE,
         "small params are free under XLA fusion"),
    )

    def composite(p, g, m, v, lr, bc1, bc2):
        beta1, beta2, eps = 0.9, 0.999, 1e-8
        m2 = beta1 * m + (1.0 - beta1) * g
        v2 = beta2 * v + (1.0 - beta2) * (g * g)
        p2 = p - lr * (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
        return p2, m2, v2

    return dict(
        fn=lambda p, g, m, v, lr, bc1, bc2: fo.fused_adam_update(
            p, g, m, v, lr, bc1, bc2, beta1=0.9, beta2=0.999, eps=1e-8),
        args=(buf, buf, buf, buf, sc, sc, sc),
        budget=KernelBudget(), constraints=constraints,
        flops=float(14 * n),  # m(3) + v(4) + update(6) + apply(1) per elem
        composite=composite,
        composite_args=(buf, buf, buf, buf, sc, sc, sc))


REGISTRY: dict[str, KernelSpec] = {s.name: s for s in (
    KernelSpec("flash_fwd", "dense-block flash attention forward (causal, "
               "seq 1024, head_dim 128) — output revisited across the KV "
               "grid dim by declaration", _build_flash),
    KernelSpec("flash_bwd", "dense-block flash attention backward, one "
               "fused kernel (scores recomputed transposed, lse and di "
               "one fp32 number a row) — dK/dV revisited across the q "
               "grid dim and dQ across both by declaration",
               _build_flash_bwd),
    KernelSpec("splash_fwd", "causal splash attention forward (tile-"
               "skipping mask, seq 1024) — same accumulation contract",
               _build_splash),
    KernelSpec("paged_decode", "LEGACY library paged-decode kernel at "
               "the canonical serving shape — kept certified as the "
               "pre-unification A/B baseline; dispatch routes through "
               "ragged_paged instead", _build_paged_decode),
    KernelSpec("ragged_paged", "UNIFIED ragged paged attention, decode "
               "mode (s=1, fp32) — one Pallas program for all four "
               "serving attention modes; data-dependent output map "
               "proven injective at runtime index_args",
               lambda: _build_ragged("decode")),
    KernelSpec("ragged_paged_q8", "unified ragged kernel, int8 mode: "
               "per-page-per-head dequant fused into the page gather — "
               "the quantized serving path's first kernel (closes the "
               "int8-decode coverage gap)",
               lambda: _build_ragged("q8")),
    KernelSpec("ragged_paged_verify", "unified ragged kernel at the "
               "speculative K+1=5 verify contract — the per-depth "
               "verify programs collapse onto the one program shape",
               lambda: _build_ragged("verify")),
    KernelSpec("ragged_paged_prefill", "unified ragged kernel at the "
               "single-row chunked-prefill tail (64-pad bucket, "
               "ctx0=192) — prefill and chunk ride the same program",
               lambda: _build_ragged("prefill")),
    KernelSpec("mla_decode", "absorbed latent-attention (MLA) decode over "
               "a latent paged pool: one grid step a row, the row's live "
               "pages staged once for all heads through two alternating "
               "buffers, online softmax", _build_mla_decode),
    KernelSpec("gqa_decode", "grouped-head decode attention over a lane-"
               "dense paged pool: the mla_decode pipeline given a values "
               "pool, a row's live pages staged once for all query heads, "
               "block-diagonal queries so that no slice is narrower than a "
               "lane row", _build_gqa_decode),
    KernelSpec("gqa_decode_window", "the grouped-head decode kernel of a "
               "WINDOW layer (window 128 of 512 positions): the loop runs "
               "over the chunks of [ctx + 1 - window, ctx] only and the "
               "positions behind the window are masked exactly",
               lambda: _build_gqa_decode(window=128)),
    KernelSpec("flash_fwd_grouped", "grouped-head flash forward of a "
               "serving prefill from position 0 (8 query heads over 2 KV "
               "heads of 128, seq 2048, window 512): the flash_fwd body, "
               "blocks behind the window skipped", _build_flash_grouped),
    KernelSpec("ssm_decode_update", "Mamba-2 decode state update: a grid "
               "step a slot brings the slot's float32 state to VMEM once, "
               "advances it in place and reads it out in one MXU product; "
               "dead slots name the live block before them and move "
               "nothing",
               _build_ssm_decode_update),
    KernelSpec("fused_layernorm_fwd", "fused LayerNorm forward (one HBM "
               "pass per row block, stats saved for the backward)",
               lambda: _build_ln("fwd")),
    KernelSpec("fused_layernorm_dx", "fused LayerNorm dx backward (row-"
               "local second kernel)", lambda: _build_ln("dx")),
    KernelSpec("fused_adam", "fused Adam/AdamW update (one read + one "
               "write per buffer — the bandwidth floor)", _build_adam),
)}


def run_kernel(name: str) -> tuple[KernelCertReport, dict]:
    """Build and certify one registered kernel; returns (report, record)
    where record is the bankable roofline entry — analytic FLOPs, the
    static HBM model, arithmetic intensity, and the composite path's
    hlocheck cost roll-up with the predicted bandwidth-bound speedup."""
    spec = REGISTRY.get(name)
    if spec is None:
        raise KeyError(f"unknown kernelcheck kernel {name!r} "
                       f"(have: {', '.join(REGISTRY)})")
    b = spec.build()
    report = certify(b["fn"], b["args"], name=name, budget=b["budget"],
                     constraints=b.get("constraints", ()),
                     index_args=b.get("index_args"))
    hbm = report.hbm_bytes
    flops = b["flops"]
    record = {
        "grid": [list(c.grid) for c in report.calls],
        "vmem_bytes": report.vmem_bytes,
        "flops": flops,
        "hbm_bytes": hbm,
        "intensity": round(flops / hbm, 3) if hbm else None,
    }
    if b.get("composite") is not None:
        from .hlocheck import audit

        comp = audit(b["composite"], b["composite_args"],
                     name=f"{name}_composite")
        # the composite's materialized traffic: arguments + every
        # intermediate the fused kernel keeps on-chip + outputs
        comp_bytes = (comp.argument_bytes + comp.temp_bytes
                      + comp.output_bytes)
        record["composite"] = {
            "flops": comp.flops,
            "materialized_bytes": comp_bytes,
            "peak_bytes": comp.peak_bytes,
        }
        record["predicted_speedup"] = (
            round(comp_bytes / hbm, 3) if hbm else None)
    return report, record


# --------------------------------------------------------- banking + drift
#: analytic record fields frozen by the bank — drift here is a violation
#: (the PR 6 fail-loudly contract); composite-measured fields re-measure
ANALYTIC_KEYS = ("grid", "vmem_bytes", "flops", "hbm_bytes")


def bank_path() -> str:
    """profiles/kernelcheck.json beside the repo root — the one TRACKED
    file under the otherwise-gitignored profiles/ (it is the frozen
    contract every sweep diffs against, so it must survive a fresh
    checkout)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "profiles", "kernelcheck.json")


def diff_banked(records: dict, banked: dict) -> list[KernelFinding]:
    """Drift check of fresh records against the banked roofline: any
    analytic field that moved is an error naming the field and both
    values; composite re-measurements drift only as warnings (XLA cost
    models move across versions); a kernel missing from the bank asks for
    a --bank run."""
    findings = []
    for name, rec in sorted(records.items()):
        old = banked.get(name)
        if old is None:
            findings.append(KernelFinding(
                "drift", "error",
                f"{name}: no banked roofline entry — run `python -m "
                f"paddle_tpu.analysis kernelcheck --bank` to freeze it"))
            continue
        for key in ANALYTIC_KEYS:
            if old.get(key) != rec.get(key):
                findings.append(KernelFinding(
                    "drift", "error",
                    f"{name}: analytic roofline field {key!r} drifted "
                    f"from the banked contract: {old.get(key)!r} -> "
                    f"{rec.get(key)!r} — re-bank deliberately or fix the "
                    f"kernel"))
        oc, nc = old.get("composite"), rec.get("composite")
        if oc and nc:
            for key in ("flops", "materialized_bytes"):
                a, bb = oc.get(key) or 0, nc.get(key) or 0
                if a and bb and not math.isclose(a, bb, rel_tol=0.25):
                    findings.append(KernelFinding(
                        "drift", "warn",
                        f"{name}: composite {key} moved {a:.4g} -> "
                        f"{bb:.4g} (re-measured, not pinned)"))
    return findings


# ----------------------------------------------------- dispatch coverage
def coverage_report() -> dict:
    """Statically enumerate the kernel-dispatch gates and report which
    serving configs reach a Pallas kernel vs the composite path.

    Rows come from the SAME predicates the runtime dispatch calls
    (``paged_attention.decode_kernel_eligible``,
    ``flash_attention.supports_shape``), so the table cannot drift from
    the dispatch. ``kernel_less`` lists the production-relevant configs
    (TPU backend, kernels flag on) that still take the composite — the
    machine-readable version of "int8 decode has no fast kernel"."""
    from ..kernels import flash_attention as fa
    from ..kernels import paged_attention as pa

    p = _PAGED_SHAPE
    rows = []
    for platform in ("tpu", "cpu"):
        for flags_on in (True, False):
            for kv in ("float32", "int8"):
                ok, why = pa.decode_kernel_eligible(
                    p["head_dim"], p["pages_per_seq"], p["page_size"],
                    num_heads=p["heads"], quantized=kv == "int8",
                    on_tpu=platform == "tpu", flags_on=flags_on)
                rows.append({
                    "family": "paged_decode",
                    "config": (f"platform={platform} "
                               f"pallas_flag={'on' if flags_on else 'off'}"
                               f" kv_dtype={kv}"),
                    "path": "pallas" if ok else "composite",
                    "blocked_by": why})
    ok, why = pa.decode_kernel_eligible(64, p["pages_per_seq"],
                                        p["page_size"],
                                        num_heads=p["heads"])
    rows.append({"family": "paged_decode",
                 "config": ("platform=tpu pallas_flag=on kv_dtype=float32 "
                            "head_dim=64"),
                 "path": "pallas" if ok else "composite",
                 "blocked_by": why})
    # the unified kernel's multi-token modes: chunked-prefill tail (the
    # pad bucket) and the speculative K+1 verify, both dtypes — the SAME
    # decode_kernel_eligible predicate at num_query_tokens > 1, so these
    # rows track the dispatch for free
    for mode, nq in (("verify[K+1=5]", 5), ("prefill[64]", 64)):
        for kv in ("float32", "int8"):
            ok, why = pa.decode_kernel_eligible(
                p["head_dim"], p["pages_per_seq"], p["page_size"],
                num_heads=p["heads"], quantized=kv == "int8",
                num_query_tokens=nq)
            rows.append({
                "family": "ragged_paged",
                "config": (f"platform=tpu pallas_flag=on kv_dtype={kv} "
                           f"mode={mode}"),
                "path": "pallas" if ok else "composite",
                "blocked_by": why})
    # grouped KV heads over a lane-dense pool (granite-4.0-h-micro's
    # attention layers): the grouped branch's own gate, the predicate
    # paged_attention's dispatch asks
    from ..kernels import paged_decode as pd

    g = _GQA_SHAPE
    for mode, nq in (("decode", 1), ("prefill[512]", 512)):
        ok, why = pd.gqa_kernel_eligible(
            g["heads"], g["kv_heads"], g["head_dim"], g["page_size"],
            g["pages_per_seq"], nq)
        rows.append({
            "family": "gqa_decode",
            "config": f"platform=tpu pallas_flag=on kv_dtype=bfloat16 "
                      f"mode={mode}",
            "path": "pallas" if ok else "composite",
            "blocked_by": why})
    for s in (1024, 640, 512):
        shape = (1, 8, s, 128)
        route = fa.flash_route(shape, shape, causal=True)
        path = {"direct": "pallas", "pad": "pallas[padded]"}.get(
            route, "composite")
        rows.append({
            "family": "flash_prefill",
            "config": f"platform=tpu pallas_flag=on seq={s} causal",
            "path": path,
            "blocked_by": "" if route else (
                f"seq {s} fails supports_shape (%128 MXU tile and "
                f"%{fa._block(s, 128)} block edge) and the causal "
                f"pad-to-block route")})
    # the %512 edge WITHOUT the causal pad rescue: non-causal can't pad
    # (padded keys would be attended) — a loudly-counted fallback
    # (serving_flash_edge_fallback_total), never a silent one
    shape = (1, 8, 640, 128)
    route = fa.flash_route(shape, shape, causal=False)
    rows.append({
        "family": "flash_prefill",
        "config": "platform=tpu pallas_flag=on seq=640 non-causal",
        "path": "pallas" if route else "composite[counted]",
        "blocked_by": "" if route else (
            "non-causal seq 640 cannot pad-to-block; composite serves "
            "and serving_flash_edge_fallback_total counts it")})
    for gate, why in (("pallas_flag=off", "FLAGS_use_pallas_kernels off"),
                      ("platform=cpu", "CPU backend: Pallas TPU kernels "
                                       "unavailable")):
        rows.append({"family": "flash_prefill",
                     "config": f"{gate} seq=1024",
                     "path": "composite", "blocked_by": why})
    kernel_less = [
        f"{r['family']}: {r['config']} — {r['blocked_by']}"
        for r in rows
        if r["path"] == "composite"
        and "platform=tpu" in r["config"]
        and "pallas_flag=off" not in r["config"]]
    return {"rows": rows, "kernel_less": kernel_less}


# ---------------------------------------------------------------------- CLI
def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis kernelcheck",
        description="Static Pallas-kernel certification: VMEM budgets, "
                    "tiling lint, grid-race proofs, roofline contracts, "
                    "and the dispatch-coverage report — all on CPU.")
    parser.add_argument("--kernel", action="append", default=None,
                        metavar="NAME",
                        help="certify only these registered kernels "
                             "(repeatable; default: all)")
    parser.add_argument("--list-kernels", action="store_true",
                        help="print the kernel registry and exit")
    parser.add_argument("--bank", action="store_true",
                        help="write the roofline records to the profile "
                             "instead of diffing against it")
    parser.add_argument("--json", default=None, metavar="PATH",
                        help="also dump the full report (certs, "
                             "rooflines, coverage) as JSON")
    parser.add_argument("--profile", default=None, metavar="PATH",
                        help=f"banked-roofline path (default: "
                             f"{bank_path()})")
    parser.add_argument("--no-coverage", action="store_true",
                        help="skip the dispatch-coverage report")
    args = parser.parse_args(argv)

    if args.list_kernels:
        for s in REGISTRY.values():
            print(f"{s.name}  {s.doc}")
        return 0
    names = args.kernel or list(REGISTRY)
    unknown = [n for n in names if n not in REGISTRY]
    if unknown:
        print(f"unknown kernel(s): {', '.join(unknown)} "
              f"(have: {', '.join(REGISTRY)})")
        return 2

    violations = 0
    records: dict[str, dict] = {}
    reports: dict[str, KernelCertReport] = {}
    failures: dict[str, str] = {}
    for name in names:
        try:
            report, record = run_kernel(name)
        except Exception as e:  # noqa: BLE001 — one broken entry must not
            # abort the sweep (the hlocheck CLI contract)
            failures[name] = f"{type(e).__name__}: {e} (execution error)"
            print(f"FAIL {name}: {failures[name]}")
            violations += 1
            continue
        reports[name] = report
        records[name] = record
        print(report.summary())
        for f in report.all_findings():
            print(f"  {f}")
        if not report.ok:
            violations += 1

    profile = args.profile or bank_path()
    drift: list[KernelFinding] = []
    if args.bank:
        if violations:
            print("not banking: certification violations above")
        else:
            os.makedirs(os.path.dirname(profile), exist_ok=True)
            merged = dict(records)
            if set(names) != set(REGISTRY) and os.path.exists(profile):
                # partial --kernel bank: merge into the existing bank —
                # overwriting it would destroy the OTHER kernels' frozen
                # contracts. A full sweep rewrites (drops stale entries).
                with open(profile) as fh:
                    merged = {**json.load(fh), **records}
            with open(profile, "w") as fh:
                json.dump(merged, fh, indent=1, sort_keys=True)
            print(f"banked {len(records)} roofline record(s) to {profile}")
    elif os.path.exists(profile):
        # diff_banked walks `records`, so a --kernel subset diffs exactly
        # the selected entries — drift is never silently unchecked
        with open(profile) as fh:
            drift = diff_banked(records, json.load(fh))
        for f in drift:
            print(f"  {f}")
        violations += sum(1 for f in drift if f.severity == "error")
    else:
        print(f"no banked roofline at {profile} — run --bank to freeze "
              f"the contracts")

    cov = None
    if not args.no_coverage:
        cov = coverage_report()
        print("\ndispatch coverage (gates evaluated statically):")
        for r in cov["rows"]:
            blocked = f"  [{r['blocked_by']}]" if r["blocked_by"] else ""
            print(f"  {r['family']:14s} {r['config']:58s} "
                  f"-> {r['path']}{blocked}")
        if cov["kernel_less"]:
            print("kernel-less production configs "
                  "(TPU + kernels flag on, still composite):")
            for k in cov["kernel_less"]:
                print(f"  !! {k}")

    # roofline table (the README's per-kernel view)
    if records:
        print("\nroofline contracts (analytic, banked):")
        print(f"  {'kernel':22s} {'flops':>12s} {'hbm bytes':>12s} "
              f"{'intensity':>9s} {'vs composite':>12s}")
        for name, rec in records.items():
            sp = rec.get("predicted_speedup")
            print(f"  {name:22s} {rec['flops']:12.4g} "
                  f"{rec['hbm_bytes']:12d} "
                  f"{rec['intensity'] or 0:9.2f} "
                  f"{('%.2fx' % sp) if sp else '-':>12s}")

    if args.json:
        payload = {
            "kernels": {**{n: {
                "ok": reports[n].ok,
                "findings": [str(f) for f in reports[n].all_findings()],
                **records.get(n, {})} for n in reports},
                # a kernel whose run_kernel() raised must not vanish from
                # the machine-readable report while stdout says FAIL
                **{n: {"ok": False, "findings": [msg]}
                   for n, msg in failures.items()}},
            "coverage": cov,
            "drift": [str(f) for f in drift],
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)

    if violations:
        print(f"\n{violations} kernel(s)/check(s) in violation")
    else:
        print(f"\nkernelcheck clean: {len(reports)} kernel(s) certified")
    return 1 if violations else 0
