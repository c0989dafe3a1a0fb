"""Compiled-artifact auditor: what did XLA *actually* build for a step?

PR 4's CompileGuard/SyncTally certify the serving invariants at the Python
trace level — but the ROADMAP's tensor-parallel arc needs those contracts
to survive sharding, and a sharded step can silently acquire implicit
all-gathers, resharding copies, or un-honored donation that no trace-level
check can see. This module reads the truth straight off the compiled
artifact, the way ``tools/aot_shard_proof.py`` already reads
``memory_analysis`` for training:

- **Collective census** — AOT-lower a step and walk the optimized HLO for
  ``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
  ``collective-permute`` / ``all-to-all`` instructions (sync and
  ``-start`` async forms; ``-done`` halves are not double-counted), each
  with its payload byte volume parsed from the result shape. The census is
  enforced against a declared :class:`CollectiveBudget` — a decode step on
  a single chip budgets ZERO, a tensor-parallel step budgets exactly the
  collectives its sharding implies.
- **Host-transfer check** — the compiled-level twin of SyncTally: flag
  ``infeed``/``outfeed``, host ``send``/``recv``, and host-callback
  ``custom-call``s (``xla_python_cpu_callback`` & friends) baked into a
  hot step. A trace-level tally can only see syncs the *host* initiates;
  this sees the ones the *program* performs.
- **Aliasing verification** — the compiled proof behind lint rule PT006:
  confirm XLA's ``input_output_alias`` table actually honors every
  ``donate_argnums`` leaf. A donated-but-copied KV pool silently holds two
  pools live (a 2x HBM cost no Python-level check can observe — jax still
  marks the donated buffer deleted either way).
- **Resource roll-up** — ``cost_analysis()`` flops and
  ``memory_analysis()`` peak bytes per step (arguments + temp arena +
  outputs − aliased), reported through ``serving_hlo_*`` metrics and the
  bench JSON.

:data:`REGISTRY` names the repo's auditable steps (the serving engine's
prefill/chunk/decode, the paged cache's swap/COW jits, the toy 8-device
``shard_map`` step that gated the sharded-serving arc, and the REAL
tensor-parallel serving steps it grew into — ``tp2_engine_*`` + the
per-shard cache movers, certified against the budgets the engine itself
declares);
``python -m paddle_tpu.analysis --hlo [--step NAME]`` sweeps them with
clean exit codes. ``ServingConfig(debug_checks=True)`` audits every engine
step once per compiled program (per prefill bucket + decode) at its first
trace — one extra AOT lower+compile per program, a debugging cost, never a
serving-path cost.

Like tracecheck, this module never imports the serving stack at module
level — serving imports us; the registry builders import serving lazily.
"""
from __future__ import annotations

import inspect
import os
import re
import warnings
from dataclasses import dataclass, field

__all__ = ["CollectiveBudget", "CollectiveOp", "HostTransfer",
           "HloAuditReport", "HloCheckError", "CollectiveBudgetError",
           "CollectiveOverlapError", "HostTransferError",
           "AliasingViolation", "SINGLE_CHIP", "census", "audit",
           "audit_guard", "StepSpec", "REGISTRY", "run_step", "main"]


class HloCheckError(RuntimeError):
    """A compiled-artifact audit failed."""


class CollectiveBudgetError(HloCheckError):
    """The compiled step issues more collective traffic than its declared
    CollectiveBudget. The message names the op kind, count, and bytes."""


class CollectiveOverlapError(HloCheckError):
    """The compiled step's async collectives do not overlap enough compute:
    fewer than ``min_overlap_frac`` of the ``-start``/``-done`` pairs have
    ANY instruction scheduled between them — the scheduler serialized the
    collective against the compute it was supposed to hide under."""


class HostTransferError(HloCheckError):
    """The compiled step contains host-transfer ops (infeed/outfeed/host
    callback) beyond its budget — a hidden device<->host stall per step."""


class AliasingViolation(HloCheckError):
    """XLA did not honor a donated buffer with input-output aliasing: the
    donated-and-deleted input is COPIED into its output, so two copies are
    live — for a pool-sized buffer, a silent 2x HBM cost."""


# --------------------------------------------------------------- HLO parsing
# element widths in BITS — sub-byte dtypes (s2/s4, the EQuARX-style
# quantized-collective payloads these byte volumes are the baseline for)
# must not round up per element, only per buffer
_DTYPE_BITS = {
    "pred": 8, "s2": 2, "u2": 2, "s4": 4, "u4": 4, "s8": 8, "u8": 8,
    "f8e5m2": 8, "f8e4m3": 8, "f8e4m3fn": 8, "f8e4m3b11fnuz": 8,
    "f8e5m2fnuz": 8, "f8e4m3fnuz": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64, "c128": 128,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")

# one HLO instruction: `%name = TYPE opcode(...)` where TYPE is a scalar/
# array type or a tuple `(t1, t2)` (tuple element types never nest parens)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<iname>[\w.\-]+)\s*=\s*"
    r"(?P<type>\([^=]*?\)|\S+)\s+(?P<op>[\w\-]+)\(")

_ALIAS_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)")

# replica_groups in the explicit form `{{0,1},{2,3}}` (empty `{}` = one
# group of every participant) and the iota form `[2,2]<=[4]` (optionally
# transposed: `[2,2]<=[2,2]T(1,0)`) newer XLA emits for large meshes.
# collective-permute carries `source_target_pairs` instead — same `{{a,b}}`
# surface, pair semantics.
_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)=\{((?:\{[\d,\s]*\},?\s*)*)\}")
_GROUP_RE = re.compile(r"\{([\d,\s]*)\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")

_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all",
                    "collective-broadcast")

# host-callback custom-call targets (CPU + TPU spellings)
_HOST_TARGET_RE = re.compile(r"callback|host|infeed|outfeed", re.IGNORECASE)


def _shape_elem_bytes(type_str: str) -> list[int]:
    """Per-array-element byte volumes of an HLO type string. Layouts
    (``{1,0}``) and token/opaque elements contribute nothing."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(type_str):
        bits = _DTYPE_BITS.get(dtype)
        if bits is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((n * bits + 7) // 8)
    return out


def _type_bytes(type_str: str) -> int:
    """Total byte volume of an HLO result type — ``f32[4,8]{1,0}`` or a
    tuple ``(f32[4]{0}, bf16[2,2]{1,0})``."""
    return sum(_shape_elem_bytes(type_str))


@dataclass(frozen=True)
class CollectiveOp:
    kind: str     # base opcode: all-reduce, all-gather, ...
    nbytes: int   # payload bytes parsed from the result type
    instr: str    # HLO instruction name (%...)
    line: str     # the instruction line, trimmed
    # async `-start`/`-done` pair (vs the sync single-instruction form)
    is_async: bool = False
    # overlap depth: instructions the scheduler placed between this
    # collective's -start and its -done — the compute it hides under.
    # Always 0 for sync collectives (nothing can interleave)
    overlap: int = 0
    # participant structure, parsed once here so --overlap and meshcheck
    # share a single HLO walk. For collective-permute these are the
    # (source, target) pairs; empty with group_count 0 means the
    # instruction named no groups (= one group of every participant).
    replica_groups: tuple = ()
    group_count: int = 0
    channel_id: int | None = None
    use_global_device_ids: bool = False


@dataclass(frozen=True)
class HostTransfer:
    kind: str    # infeed | outfeed | send | recv | custom-call
    detail: str  # custom_call_target for callbacks, else the opcode
    line: str


_REF_RE = re.compile(r"%([\w.\-]+)")


def _parse_replica_groups(raw: str) -> tuple[tuple, int]:
    """Decode the participant groups of one collective instruction line.
    Handles the explicit ``replica_groups={{0,1},{2,3}}`` form (and the
    same-surface ``source_target_pairs`` of collective-permute), plus the
    iota form ``replica_groups=[G,S]<=[d0,d1]T(p0,p1)``: ranks 0..prod(d)-1
    reshaped to ``[d0,d1,...]`` C-order, transposed by the permutation,
    flattened, and chunked into G groups of S. Returns (groups, count);
    ``((), 0)`` when the line names no groups at all."""
    m = _GROUPS_RE.search(raw)
    if m is not None:
        groups = tuple(
            tuple(int(x) for x in g.split(",") if x.strip())
            for g in _GROUP_RE.findall(m.group(1)))
        groups = tuple(g for g in groups if g)
        return groups, len(groups)
    m = _IOTA_GROUPS_RE.search(raw)
    if m is not None:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        perm = ([int(p) for p in m.group(4).split(",")] if m.group(4)
                else list(range(len(dims))))
        tdims = [dims[p] for p in perm]
        flat = []
        for pos in range(n_groups * group_size):
            # multi-index in the transposed shape, C-order
            tidx, rem = [], pos
            for d in reversed(tdims):
                tidx.append(rem % d)
                rem //= d
            tidx.reverse()
            # map back through the permutation and ravel in the original
            oidx = [0] * len(dims)
            for axis, t in zip(perm, tidx):
                oidx[axis] = t
            rank = 0
            for d, i in zip(dims, oidx):
                rank = rank * d + i
            flat.append(rank)
        groups = tuple(tuple(flat[g * group_size:(g + 1) * group_size])
                       for g in range(n_groups))
        return groups, n_groups
    return (), 0


def census(hlo_text: str) -> tuple[tuple[CollectiveOp, ...],
                                   tuple[HostTransfer, ...]]:
    """Walk optimized HLO text and collect (collectives, host transfers).
    Async ``-start``/``-done`` pairs count once (at the start), and each
    carries its OVERLAP depth: the number of instructions the scheduler
    placed between the ``-start`` and its matching ``-done`` — the compute
    the collective hides under. A ``-start`` immediately followed by its
    ``-done`` overlaps nothing (the async form bought no latency hiding),
    which is exactly what the latency-hiding-scheduler census exists to
    catch."""
    entries: list[dict] = []   # mutable while scanning (overlap counts)
    hosts: list[HostTransfer] = []
    open_starts: dict[str, int] = {}  # -start instr name -> entries index
    for raw in hlo_text.splitlines():
        m = _INSTR_RE.match(raw)
        if m is None:
            continue
        op = m.group("op")
        line = raw.strip()[:200]
        if op.endswith("-done") and op[:-5] in COLLECTIVE_KINDS:
            # close the start this done names (its operand): instructions
            # after this point no longer overlap that collective
            ref = _REF_RE.search(raw[m.end():])
            if ref is not None:
                open_starts.pop(ref.group(1), None)
            continue
        base = op[:-6] if op.endswith("-start") else op
        if base in COLLECTIVE_KINDS:
            # an async `-start` result is a tuple carrying operand AND
            # result buffers — ((op, res)) scalar form, ((op0..opN-1,
            # res0..resN-1)) when XLA's combiner merged N collectives.
            # Charge the result half only: the payload the sync form(s)
            # would report, so byte caps hold across sync/async/combined
            # compilation of the same traffic
            is_async = op.endswith("-start")
            elems = _shape_elem_bytes(m.group("type"))
            nbytes = (sum(elems[len(elems) // 2:])
                      if is_async and len(elems) > 1 else sum(elems))
            if is_async:
                open_starts[m.group("iname")] = len(entries)
            groups, group_count = _parse_replica_groups(raw)
            ch = _CHANNEL_RE.search(raw)
            entries.append(dict(
                kind=base, nbytes=nbytes, instr=m.group("iname"),
                line=line, is_async=is_async,
                replica_groups=groups, group_count=group_count,
                channel_id=int(ch.group(1)) if ch else None,
                use_global_device_ids="use_global_device_ids=true" in raw))
            continue
        # any other instruction scheduled while a -start is in flight is
        # work the collective overlaps (credited to every open start)
        for idx in open_starts.values():
            entries[idx]["overlap"] = entries[idx].get("overlap", 0) + 1
        if op in ("infeed", "outfeed"):
            hosts.append(HostTransfer(op, op, line))
        elif op in ("send", "recv") and "is_host_transfer=true" in raw:
            hosts.append(HostTransfer(op, op, line))
        elif op == "custom-call":
            t = _TARGET_RE.search(raw)
            if t is not None and _HOST_TARGET_RE.search(t.group(1)):
                hosts.append(HostTransfer("custom-call", t.group(1), line))
    return tuple(CollectiveOp(**e) for e in entries), tuple(hosts)


# ------------------------------------------------------------------ budgets
@dataclass(frozen=True)
class CollectiveBudget:
    """Per-step ceiling on compiled collective/host-transfer traffic. The
    default is the single-chip serving contract: ZERO everything — a
    sharded step declares exactly the collectives its partitioning implies
    (and optionally caps their total payload bytes)."""
    all_reduce: int = 0
    all_gather: int = 0
    reduce_scatter: int = 0
    collective_permute: int = 0
    all_to_all: int = 0
    collective_broadcast: int = 0
    host_transfers: int = 0
    max_collective_bytes: int | None = None
    # per-medium arms: byte/op caps split by the link each collective
    # rides — ICI (within a host) vs DCN (across hosts). Enforcement
    # needs a declared MeshTopology to classify each collective's axis,
    # so these are checked by meshcheck's MeshReport.check(), not by
    # HloAuditReport.enforce() (which stays topology-blind)
    max_ici_bytes: int | None = None
    max_dcn_bytes: int | None = None
    max_dcn_ops: int | None = None
    # minimum fraction of ASYNC collectives that must overlap at least one
    # instruction (latency-hiding-scheduler census). Enforced over async
    # `-start`/`-done` pairs ONLY: a backend that compiles everything to
    # sync collectives (CPU) has nothing to schedule and passes vacuously,
    # so the same budget certifies on a forced host mesh and on chip
    min_overlap_frac: float = 0.0

    def allowed(self, kind: str) -> int:
        return getattr(self, kind.replace("-", "_"), 0)


#: the single-chip serving contract: no collectives, no host transfers
SINGLE_CHIP = CollectiveBudget()


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB"):
        if abs(n) < 1024:
            return f"{n:.0f} {unit}" if unit == "B" else f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.2f} GiB"


# ------------------------------------------------------------------- report
@dataclass(frozen=True)
class HloAuditReport:
    """Everything the compiled artifact admits about one jitted step."""
    name: str
    collectives: tuple[CollectiveOp, ...] = ()
    host_transfers: tuple[HostTransfer, ...] = ()
    donated_leaves: int = 0
    aliased_leaves: int = 0
    donated_bytes: int = 0
    alias_bytes: int = 0
    # donated leaf names with no alias entry; () when compiled-parameter
    # pruning makes the name mapping ambiguous (counts still enforced)
    unaliased: tuple[str, ...] = ()
    flops: float = 0.0
    argument_bytes: int = 0
    temp_bytes: int = 0
    output_bytes: int = 0
    peak_bytes: int = 0

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for c in self.collectives:
            out[c.kind] = out.get(c.kind, 0) + 1
        return out

    @property
    def collective_bytes(self) -> int:
        return sum(c.nbytes for c in self.collectives)

    @property
    def async_collectives(self) -> int:
        """Collectives compiled to the async -start/-done form."""
        return sum(1 for c in self.collectives if c.is_async)

    @property
    def overlapped_collectives(self) -> int:
        """Async collectives with at least one instruction scheduled
        between their -start and -done — actually hidden under compute."""
        return sum(1 for c in self.collectives
                   if c.is_async and c.overlap > 0)

    @property
    def overlap_frac(self) -> float:
        """overlapped / async collectives; 0.0 when the program has no
        async collectives (sync-only compilation overlaps nothing)."""
        n = self.async_collectives
        return self.overlapped_collectives / n if n else 0.0

    def enforce(self, budget: CollectiveBudget) -> "HloAuditReport":
        """Raise naming the offending op when the artifact exceeds the
        budget; aliasing of donated buffers is always enforced."""
        for kind, n in sorted(self.counts().items()):
            allowed = budget.allowed(kind)
            if n > allowed:
                first = next(c for c in self.collectives if c.kind == kind)
                raise CollectiveBudgetError(
                    f"hlocheck({self.name!r}): {kind} x{n} "
                    f"({_fmt_bytes(self.collective_bytes)} total collective "
                    f"payload) exceeds the declared budget of {allowed} — "
                    f"first over-budget op: {first.line}")
        if budget.max_collective_bytes is not None and \
                self.collective_bytes > budget.max_collective_bytes:
            raise CollectiveBudgetError(
                f"hlocheck({self.name!r}): total collective payload "
                f"{self.collective_bytes} bytes exceeds the declared cap of "
                f"{budget.max_collective_bytes} bytes "
                f"({', '.join(sorted(self.counts()))})")
        n_async = self.async_collectives
        if budget.min_overlap_frac > 0.0 and n_async and \
                self.overlap_frac < budget.min_overlap_frac:
            worst = next(c for c in self.collectives
                         if c.is_async and c.overlap == 0)
            raise CollectiveOverlapError(
                f"hlocheck({self.name!r}): only "
                f"{self.overlapped_collectives}/{n_async} async "
                f"collective(s) overlap any compute "
                f"(frac {self.overlap_frac:.2f} < declared minimum "
                f"{budget.min_overlap_frac:.2f}) — the scheduler "
                f"serialized -start against -done. First serialized op: "
                f"{worst.line}")
        if len(self.host_transfers) > budget.host_transfers:
            first = self.host_transfers[0]
            raise HostTransferError(
                f"hlocheck({self.name!r}): {len(self.host_transfers)} "
                f"host-transfer op(s) compiled into the step (budget "
                f"{budget.host_transfers}) — every one stalls the dispatch "
                f"pipeline mid-program. First: {first.kind} "
                f"({first.detail})")
        if self.donated_leaves and (
                self.aliased_leaves < self.donated_leaves
                or self.alias_bytes < self.donated_bytes):
            who = (f" — unaliased leaf/leaves: "
                   f"{', '.join(self.unaliased)}" if self.unaliased else "")
            raise AliasingViolation(
                f"hlocheck({self.name!r}): {self.donated_leaves} donated "
                f"leaf/leaves ({_fmt_bytes(self.donated_bytes)}) but the "
                f"compiled artifact aliases only {self.aliased_leaves} "
                f"({_fmt_bytes(self.alias_bytes)}) — a donated-but-copied "
                f"buffer holds TWO copies live (for a KV pool, a silent 2x "
                f"HBM cost){who}")
        return self

    def summary(self) -> str:
        c = self.counts()
        coll = ", ".join(f"{k}x{v}" for k, v in sorted(c.items())) or "none"
        alias = (f"{self.aliased_leaves}/{self.donated_leaves} donated "
                 f"aliased" if self.donated_leaves else "no donation")
        ov = (f"overlap {self.overlapped_collectives}/"
              f"{self.async_collectives} async"
              if self.async_collectives else "overlap n/a (sync)")
        return (f"hlocheck {self.name}: collectives {coll} "
                f"({_fmt_bytes(self.collective_bytes)}); {ov}; host "
                f"transfers {len(self.host_transfers)}; {alias}; "
                f"flops/step {self.flops:.4g}; peak HBM "
                f"{_fmt_bytes(self.peak_bytes)}")

    def overlap_summary(self) -> str:
        """The ``--overlap`` CLI view: one line per collective naming its
        compiled form (sync vs async) and the number of instructions the
        scheduler placed while it was in flight."""
        head = (f"hlocheck {self.name}: "
                f"{self.overlapped_collectives}/{self.async_collectives} "
                f"async collective(s) overlapped"
                if self.async_collectives else
                f"hlocheck {self.name}: all collectives compiled sync "
                f"(no async -start/-done pairs to overlap)")
        lines = [head]
        for c in self.collectives:
            form = "async" if c.is_async else "sync"
            lines.append(f"  {form:<5} {c.kind:<20} "
                         f"{_fmt_bytes(c.nbytes):>9}  overlap={c.overlap}"
                         f"  %{c.instr}")
        return "\n".join(lines)


# -------------------------------------------------------------------- audit
def _leaf_nbytes(leaf) -> int:
    """Per-DEVICE bytes of one argument leaf: for a sharded array, the
    shard each device actually holds — XLA's ``memory_analysis`` numbers
    (incl. ``alias_size_in_bytes``, which the donation check compares
    against) are all per-device, so a donated heads-sharded KV pool must
    be costed at pool/tp bytes or the aliasing check would demand more
    aliased bytes than any device owns."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        try:
            shape = sharding.shard_shape(leaf.shape)
            n = leaf.dtype.itemsize
            for d in shape:
                n *= d
            return int(n)
        except Exception:  # noqa: BLE001 — fall back to the global size
            pass
    n = getattr(leaf, "nbytes", None)
    if n is not None:
        return int(n)
    return 0  # python scalar: negligible and never donated in practice


def audit(fn, args, *, name: str | None = None, static_argnums=(),
          donate_argnums=(), budget: CollectiveBudget | None = None
          ) -> HloAuditReport:
    """AOT-lower ``jax.jit(fn, static_argnums, donate_argnums)`` on
    ``args``, compile it, and audit the artifact. Lowering never executes
    or donates anything — the caller's buffers stay live. With ``budget``
    the report is enforced before being returned.

    The lower+compile runs with SyncTally counting suspended: lowering
    materializes traced constants host-side, which is compile-time work,
    not a serving-path sync."""
    import jax
    from jax.tree_util import keystr, tree_flatten_with_path

    from .tracecheck import sync_tally_paused

    name = name or getattr(fn, "__name__", "jitted")
    static_argnums = tuple(static_argnums)
    donate_argnums = tuple(donate_argnums)
    jit_kwargs = {}
    if static_argnums:
        jit_kwargs["static_argnums"] = static_argnums
    if donate_argnums:
        jit_kwargs["donate_argnums"] = donate_argnums
    with sync_tally_paused(), warnings.catch_warnings():
        # "Some donated buffers were not usable" becomes a structured
        # AliasingViolation below — don't also leak the warning
        warnings.simplefilter("ignore")
        compiled = jax.jit(fn, **jit_kwargs).lower(*args).compile()
        txt = compiled.as_text()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
    colls, hosts = census(txt)

    # flatten the non-static args the way jit does: flat leaf i <-> compiled
    # parameter i, UNLESS XLA pruned unused parameters (detected below)
    try:
        params = [p.name for p in inspect.signature(fn).parameters.values()]
    except (TypeError, ValueError):
        params = []
    flat: list[str] = []
    donated_idx: set[int] = set()
    donated_bytes = 0
    for i, arg in enumerate(args):
        if i in static_argnums:
            continue
        arg_name = params[i] if i < len(params) else f"arg{i}"
        for path, leaf in tree_flatten_with_path(arg)[0]:
            if i in donate_argnums:
                donated_idx.add(len(flat))
                donated_bytes += _leaf_nbytes(leaf)
            flat.append(arg_name + keystr(path))

    alias_entries = _ALIAS_RE.findall(txt)
    aliased_params = {int(p) for _out, p in alias_entries}
    entry = txt[txt.rfind("\nENTRY"):]
    n_entry_params = len(set(re.findall(r"parameter\((\d+)\)", entry)))
    unaliased: tuple[str, ...] = ()
    if n_entry_params == len(flat):
        # no parameter pruning: compiled param numbers ARE flat leaf indices
        unaliased = tuple(flat[i] for i in sorted(donated_idx)
                          if i not in aliased_params)

    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float((ca or {}).get("flops", 0.0))
    arg_b = int(ma.argument_size_in_bytes)
    temp_b = int(ma.temp_size_in_bytes)
    out_b = int(ma.output_size_in_bytes)
    alias_b = int(ma.alias_size_in_bytes)
    report = HloAuditReport(
        name=name, collectives=colls, host_transfers=hosts,
        donated_leaves=len(donated_idx), aliased_leaves=len(alias_entries),
        donated_bytes=donated_bytes, alias_bytes=alias_b,
        unaliased=unaliased, flops=flops, argument_bytes=arg_b,
        temp_bytes=temp_b, output_bytes=out_b,
        # resident set while the step runs (the aot_shard_proof formula:
        # XLA:CPU's peak_memory_in_bytes leaves out the temp arena)
        peak_bytes=arg_b + temp_b + out_b - alias_b)
    if budget is not None:
        report.enforce(budget)
    return report


def audit_guard(guard, args, budget: CollectiveBudget | None = None,
                name: str | None = None) -> HloAuditReport:
    """Audit a CompileGuard-wrapped step: the wrapped impl and its
    static/donate argnums are read off the guard itself, so the audited
    artifact can never desynchronize from what the guard's jit builds."""
    return audit(guard.fn, args, name=name or guard.name,
                 static_argnums=guard.static_argnums,
                 donate_argnums=guard.donate_argnums, budget=budget)


# ----------------------------------------------------------------- registry
@dataclass(frozen=True)
class StepSpec:
    """A named auditable step: ``build()`` returns ``(target, args,
    jit_kwargs, budget)`` where target is a CompileGuard or a plain
    callable (jit_kwargs supplies static/donate argnums for the latter)."""
    name: str
    doc: str
    build: object = field(repr=False)
    min_devices: int = 1


def _tiny_latent_model():
    """A toy Kimi-K2 (text/kimi_k2.py): MLA over the latent pool, a dense
    layer and an expert layer that holds 4 of 8 experts."""
    from ..text.kimi_k2 import KimiK2Config, KimiK2ForCausalLM

    return KimiK2ForCausalLM(KimiK2Config(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=2,
        num_attention_heads=2, n_routed_experts=8, num_experts_per_tok=2,
        kv_lora_rank=16, q_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, max_position_embeddings=32,
        held_experts=(2, 4)))


def _tiny_window_model():
    """A toy Mellum (text/mellum.py): window layers beside a full one in
    two page groups, a softmax-routed expert layer held whole."""
    from ..text.mellum import MellumConfig, MellumForCausalLM

    return MellumForCausalLM(MellumConfig(
        vocab_size=97, hidden_size=32, moe_intermediate_size=16,
        num_hidden_layers=4, layer_types=["sliding_attention"] * 3
        + ["full_attention"], num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, num_experts=8, num_experts_per_tok=2, sliding_window=8,
        max_position_embeddings=32))


def _build_engine_step(which: str, tensor_parallel: int = 1,
                       kv_dtype: str = "float32",
                       quantized_logits: bool = False,
                       latent: bool = False, window: bool = False):
    """Engine-step audit targets. ``tensor_parallel=2`` builds the SAME
    step on a 2-device mesh (Megatron weight + KV-pool shards via
    serving/tp.py shard_map) with the budget the engine itself declares:
    2 all-reduces per block + 1 for the logits, byte-capped — the
    single-chip variants certify at SINGLE_CHIP (all zeros).
    ``kv_dtype="int8"`` builds the quantized-pool twin: the SAME budgets
    must hold (quantization is per-device arithmetic — zero extra
    collectives), and the donated int8 pools + scale leaves must all
    alias (a donated-but-copied quantized pool would silently forfeit
    the 4x HBM win the mode exists for). ``which="verify_spec"`` builds
    the speculative-decoding verify step (serving/spec.py, n-gram
    proposer at depth 2): the in-jit propose + K+1-token ragged verify +
    accept count as ONE program — zero collectives single-chip, the
    target's own 2L+1 all-reduces (and not one more: the proposer adds
    no collectives) under tensor parallelism, donated pools aliased
    either way."""
    import numpy as np

    import paddle_tpu as paddle

    from ..serving.engine import ServingConfig, ServingEngine
    from ..serving.spec import SpecConfig
    from ..text.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(7)
    # ``latent``: the same steps over a latent-attention model's one-leaf
    # pool (the model's own counters ride behind the tokens) — single
    # chip, zero collectives, the donated pool aliased
    # ``window``: the same steps over a model of two page groups (the
    # groups' tables uploaded stacked, each layer handed its own; the
    # prefill's head at the last real token alone)
    model = _tiny_latent_model() if latent else _tiny_window_model() \
        if window else GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
            max_seq_len=32, dropout=0.0))
    model.eval()
    spec = (SpecConfig(method="ngram", depth=2)
            if which == "verify_spec" else None)
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=16, page_size=4, max_prompt_len=8,
        group_pages={"window": 12} if window else None,
        enable_prefix_caching=not window,
        tensor_parallel=tensor_parallel, kv_dtype=kv_dtype, spec=spec,
        # the tp2 entries certify WITH the overlap contract declared:
        # min_overlap_frac=1.0 over async collectives (vacuous where the
        # backend compiles them sync — the forced CPU mesh — and binding
        # on chip, where the latency-hiding scheduler must deliver)
        tp_overlap_scheduler=tensor_parallel > 1,
        tp_quantized_logits=quantized_logits))
    # the operands are the engine's own, as a launch builds them: the
    # order of a step program's operands is known to engine.py alone
    if which == "verify_spec":
        prog, args = eng._programs["verify"], eng._verify_args()
    elif which in ("prefill", "prefill_chunk"):
        # prefill: a cold prompt. prefill_chunk: a MID-PROMPT chunk —
        # queries enter at start > 0 against already-resident KV, through
        # the SAME prefill program shape (chunk padded to its bucket).
        # Audited separately so the registry certifies the exact call
        # signature the chunk phase dispatches, not just the cold case.
        ids, start = (((5, 7, 11), 0) if which == "prefill"
                      else ((3, 5, 7, 11), 4))
        prog = eng._prefill_program(len(ids))
        args = eng._prefill_args(prog, 0, 1, np.asarray(ids, np.int32),
                                 start)
    else:
        prog, args = eng._programs["decode"], eng._decode_args()
    return eng.guards[prog.phase], args, None, eng._step_budget(prog.label)


def _build_cache_step(which: str, tensor_parallel: int = 1,
                      kv_dtype: str = "float32"):
    """Cache-mover audit targets. ``tensor_parallel=2`` shards the pools'
    heads axis and runs the mover per-shard (shard_map over replicated
    page indices) — pure local data movement, so the declared budget
    stays ZERO collectives either way. ``kv_dtype="int8"`` moves int8
    codes + scale stacks instead of f32 pages (the spill/restore payload
    of the host tier) — still zero collectives, scatter still aliases
    every donated leaf."""
    import jax.numpy as jnp
    import numpy as np

    from ..serving.kv_cache import PagedCacheConfig, PagedKVCache

    tp = None
    if tensor_parallel > 1:
        from ..serving.tp import TPContext
        from ..text.gpt import GPTConfig

        tp = TPContext(tensor_parallel, GPTConfig(
            vocab_size=97, hidden_size=8, num_layers=2, num_heads=2))
    cache = PagedKVCache(PagedCacheConfig(
        num_layers=2, num_heads=2, head_dim=4, num_pages=8, page_size=4,
        max_batch=2, pages_per_seq=4, tp=tp, kv_dtype=kv_dtype))
    cfg = cache.cfg
    idx = jnp.asarray(np.zeros(cfg.pages_per_seq, np.int32))
    if which == "swap_gather":
        return cache._gather_jit, (cache.pools, idx), None, SINGLE_CHIP
    if which == "swap_scatter":
        shape = (cfg.num_layers, cfg.pages_per_seq, cfg.page_size,
                 cfg.num_heads, cfg.head_dim)
        if cfg.quantized:
            sshape = (cfg.num_layers, cfg.pages_per_seq, cfg.num_heads)
            args = (cache.pools, idx, jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape, jnp.int8), jnp.zeros(sshape),
                    jnp.zeros(sshape))
        else:
            args = (cache.pools, idx, jnp.zeros(shape), jnp.zeros(shape))
        return cache._scatter_jit, args, None, SINGLE_CHIP
    args = (cache.pools, jnp.asarray(1, jnp.int32),
            jnp.asarray(2, jnp.int32))
    return cache._copy_jit, args, None, SINGLE_CHIP


_TP8_BATCH, _TP8_HIDDEN, _TP8_FF = 2, 16, 64


def _build_tp8_decode():
    """A toy tensor-parallel decode step: the Megatron split — column-
    parallel first matmul, row-parallel second, ONE psum of the [B, H]
    partials per step. Its declared budget is exactly that all-reduce;
    anything more (an implicit resharding all-gather, say) is a bug."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("tp",))

    def tp_block(x, w1, w2):
        h = jnp.maximum(x @ w1, 0.0)   # [B, FF/8] — column shard, local
        y = h @ w2                     # [B, H] partial sums
        return jax.lax.psum(y, "tp")   # the ONE declared all-reduce

    fn = jax.shard_map(tp_block, mesh=mesh,
                       in_specs=(P(None, None), P(None, "tp"),
                                 P("tp", None)),
                       out_specs=P(None, None), check_vma=False)
    args = (jnp.ones((_TP8_BATCH, _TP8_HIDDEN), jnp.float32),
            jnp.ones((_TP8_HIDDEN, _TP8_FF), jnp.float32),
            jnp.ones((_TP8_FF, _TP8_HIDDEN), jnp.float32))
    budget = CollectiveBudget(
        all_reduce=1,
        max_collective_bytes=_TP8_BATCH * _TP8_HIDDEN * 4)
    return fn, args, {}, budget


REGISTRY: dict[str, StepSpec] = {s.name: s for s in (
    StepSpec("swap_gather", "paged-cache swap-out gather (read-only, no "
             "donation)", lambda: _build_cache_step("swap_gather")),
    StepSpec("swap_scatter", "paged-cache swap-in scatter (pools donated)",
             lambda: _build_cache_step("swap_scatter")),
    StepSpec("cow_copy", "prefix-cache copy-on-write page copy (pools "
             "donated)", lambda: _build_cache_step("cow_copy")),
    StepSpec("engine_prefill", "serving prefill step, smallest pad bucket "
             "(toy GPT)", lambda: _build_engine_step("prefill")),
    StepSpec("engine_prefill_chunk", "serving CHUNKED prefill step: one "
             "mid-prompt chunk at ctx0 > 0 through the same prefill "
             "program (toy GPT)",
             lambda: _build_engine_step("prefill_chunk")),
    StepSpec("engine_decode", "serving decode step, whole batch (toy GPT)",
             lambda: _build_engine_step("decode")),
    StepSpec("engine_verify_spec", "speculative-decoding verify step: "
             "in-jit n-gram propose + whole-batch K+1-token ragged "
             "verify + accept count, one program (budget: zero "
             "collectives, donated pools aliased)",
             lambda: _build_engine_step("verify_spec")),
    StepSpec("tp8_decode", "toy tensor-parallel shard_map step on an "
             "8-device mesh: budget = exactly one all-reduce",
             _build_tp8_decode, min_devices=8),
    # ---- tensor-parallel serving (ServingConfig(tensor_parallel=2) on a
    # 2-device mesh): the REAL sharded engine steps, certified against the
    # budgets the engine itself declares — 2 all-reduces per block + 1 for
    # the logits, byte-capped (serving/tp.py step_budget); the per-shard
    # cache movers certify at ZERO collectives
    StepSpec("tp2_engine_prefill", "TENSOR-PARALLEL serving prefill step "
             "(tp=2 Megatron shards, budget 2L+1 all-reduces)",
             lambda: _build_engine_step("prefill", tensor_parallel=2),
             min_devices=2),
    StepSpec("tp2_engine_prefill_chunk", "TENSOR-PARALLEL chunked prefill "
             "step: mid-prompt chunk at ctx0 > 0 through the same sharded "
             "program (budget 2L+1 all-reduces)",
             lambda: _build_engine_step("prefill_chunk",
                                        tensor_parallel=2),
             min_devices=2),
    StepSpec("tp2_engine_decode", "TENSOR-PARALLEL serving decode step, "
             "whole batch (budget 2L+1 all-reduces)",
             lambda: _build_engine_step("decode", tensor_parallel=2),
             min_devices=2),
    StepSpec("tp2_engine_verify_spec", "TENSOR-PARALLEL speculative "
             "verify step: the SAME 2L+1 all-reduce budget as decode — "
             "the in-jit proposer adds zero collectives",
             lambda: _build_engine_step("verify_spec", tensor_parallel=2),
             min_devices=2),
    StepSpec("tp2_swap_gather", "per-shard swap-out gather over the "
             "heads-sharded pools (budget: zero collectives)",
             lambda: _build_cache_step("swap_gather", tensor_parallel=2),
             min_devices=2),
    StepSpec("tp2_swap_scatter", "per-shard swap-in scatter (pools "
             "donated; budget: zero collectives)",
             lambda: _build_cache_step("swap_scatter", tensor_parallel=2),
             min_devices=2),
    StepSpec("tp2_cow_copy", "per-shard COW page copy (pools donated; "
             "budget: zero collectives)",
             lambda: _build_cache_step("cow_copy", tensor_parallel=2),
             min_devices=2),
    # ---- quantized paged KV pool (kv_dtype="int8"): int8 codes + per-
    # page-per-head scale leaves, all donated and all aliased; budgets
    # identical to the fp32 twins — quantize/dequantize is per-device
    # arithmetic, so a collective appearing here is a sharding bug
    StepSpec("engine_decode_q8", "serving decode step over the INT8-"
             "quantized pool (codes + scale leaves donated/aliased; "
             "budget: zero collectives)",
             lambda: _build_engine_step("decode", kv_dtype="int8")),
    StepSpec("swap_gather_q8", "swap/spill gather over the int8 pool — "
             "the host-tier spill payload: raw codes + scales, never "
             "dequantized (read-only, no donation)",
             lambda: _build_cache_step("swap_gather", kv_dtype="int8")),
    StepSpec("swap_scatter_q8", "swap/restore scatter into the int8 pool "
             "(codes + scale leaves donated)",
             lambda: _build_cache_step("swap_scatter", kv_dtype="int8")),
    StepSpec("tp2_engine_decode_q8", "TENSOR-PARALLEL decode over the "
             "heads-sharded int8 pool (budget 2L+1 all-reduces — "
             "unchanged by quantization)",
             lambda: _build_engine_step("decode", tensor_parallel=2,
                                        kv_dtype="int8"),
             min_devices=2),
    # ---- the latent-attention model (text/kimi_k2.py) under the same
    # engine: one kv_pool leaf a layer, donated and aliased; the dropless
    # expert layer's sort and grouped product add no host transfer
    StepSpec("engine_prefill_latent", "serving prefill step of the latent-"
             "attention expert model (expanded MLA over rows read back "
             "from the pool; budget: zero collectives)",
             lambda: _build_engine_step("prefill", latent=True)),
    StepSpec("engine_decode_latent", "serving decode step of the latent-"
             "attention expert model (absorbed MLA over the latent pool, "
             "dropless expert layer; budget: zero collectives)",
             lambda: _build_engine_step("decode", latent=True)),
    # ---- a model of window layers beside full ones (text/mellum.py): two
    # page groups, their tables one stacked operand; both groups' donated
    # pools aliased, zero collectives
    StepSpec("engine_prefill_window", "serving prefill step of the model "
             "of window and full layers (two page groups, the head at the "
             "last real token alone; budget: zero collectives)",
             lambda: _build_engine_step("prefill", window=True)),
    StepSpec("engine_decode_window", "serving decode step of the model of "
             "window and full layers (each layer its own group's table, "
             "a dropless expert layer held whole; budget: zero "
             "collectives)",
             lambda: _build_engine_step("decode", window=True)),
    # ---- quantized logits all-reduce (tp_quantized_logits=True): the
    # b*s*V f32 logits payload ships as int8 codes + a 4-byte shared
    # scale — budget 2L+2 all-reduces with the logits byte term counted
    # at 1 byte/element by the census's bit-accurate dtype table. The
    # byte cap is ~4x tighter than the f32 twin's, so a silently
    # unquantized psum fails loudly here
    StepSpec("tp2_engine_decode_qlogits", "TENSOR-PARALLEL decode with "
             "the EQuARX-style int8 logits all-reduce (budget 2L+2 "
             "all-reduces, logits bytes counted at s8 width + 4-byte "
             "scale)",
             lambda: _build_engine_step("decode", tensor_parallel=2,
                                        quantized_logits=True),
             min_devices=2),
)}


def run_step(name: str) -> HloAuditReport:
    """Build and audit one registered step, enforcing its declared budget.
    Raises HloCheckError on violation (or when the step needs more devices
    than the process has — the CLI respawns onto a forced CPU mesh)."""
    spec = REGISTRY.get(name)
    if spec is None:
        raise KeyError(f"unknown hlocheck step {name!r} "
                       f"(have: {', '.join(REGISTRY)})")
    import jax

    have = len(jax.devices())
    if have < spec.min_devices:
        raise HloCheckError(
            f"step {name!r} needs {spec.min_devices} devices, have {have} "
            f"— run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{spec.min_devices} (the --hlo CLI does this automatically)")
    target, args, jit_kwargs, budget = spec.build()
    from .tracecheck import CompileGuard

    if isinstance(target, CompileGuard):
        return audit_guard(target, args, budget=budget, name=name)
    kw = jit_kwargs or {}
    return audit(target, args, name=name, budget=budget, **kw)


# ---------------------------------------------------------------------- CLI
_CHILD_ENV = "PADDLE_TPU_HLOCHECK_CHILD"  # set in respawned children


def _run_in_subprocess(spec: StepSpec,
                       overlap: bool = False,
                       cmd_args: list | None = None,
                       label: str = "hlocheck") -> tuple[int, str]:
    """Re-run one step in a child forced onto a CPU mesh wide enough for
    it (the certification is a virtual-mesh proof, not an on-chip run).
    Returns (exit code, relayed child output) so the caller can classify
    a nonzero exit as budget violation vs execution error. meshcheck
    reuses this respawn mechanism by supplying its own ``cmd_args``
    (the argv after ``-m paddle_tpu.analysis``) and ``label``; only
    ``spec.name`` and ``spec.min_devices`` are read then."""
    import pathlib
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env[_CHILD_ENV] = "1"  # recursion guard: a child never respawns
    # APPEND the forced count (last occurrence wins in XLA) so operator-
    # supplied flags (--xla_dump_to=...) survive into the child
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{spec.min_devices}").strip()
    root = str(pathlib.Path(__file__).resolve().parents[2])
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    print(f"[{label}] {spec.name}: needs {spec.min_devices} devices — "
          f"re-running on a forced {spec.min_devices}-device CPU mesh")
    if cmd_args is None:
        cmd_args = ["--hlo", "--step", spec.name]
        if overlap:  # the child prints the per-collective view for us
            cmd_args.append("--overlap")
    cmd = [sys.executable, "-m", "paddle_tpu.analysis"] + list(cmd_args)
    try:
        proc = subprocess.run(
            cmd, env=env, timeout=900,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    except subprocess.TimeoutExpired as e:
        # a wedged child must not crash the sweep: report it as an
        # execution error (rc 124, the conventional timeout code) so the
        # remaining steps still run and the summary stays honest
        tail = (e.stdout or b"").decode(errors="replace")[-2000:]
        print(f"[{label}] {spec.name}: child timed out after 900s"
              + (f"\n{tail}" if tail else ""))
        return 124, ""
    out = proc.stdout.decode(errors="replace")
    print(out, end="")
    return proc.returncode, out


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis --hlo",
        description="Compiled-artifact auditor: collective census, "
                    "host-transfer & aliasing verification, HBM/flops "
                    "roll-up for every registered jitted step.")
    parser.add_argument("--step", action="append", default=None,
                        metavar="NAME",
                        help="audit only these registered steps "
                             "(repeatable; default: all)")
    parser.add_argument("--list-steps", action="store_true",
                        help="print the step registry and exit")
    parser.add_argument("--overlap", action="store_true",
                        help="print the per-collective overlap census "
                             "(sync/async form + instructions scheduled "
                             "in flight) for each audited step")
    args = parser.parse_args(argv)

    if args.list_steps:
        for s in REGISTRY.values():
            extra = (f" [needs {s.min_devices} devices]"
                     if s.min_devices > 1 else "")
            print(f"{s.name}  {s.doc}{extra}")
        return 0
    names = args.step or list(REGISTRY)
    unknown = [n for n in names if n not in REGISTRY]
    if unknown:
        print(f"unknown step(s): {', '.join(unknown)} "
              f"(have: {', '.join(REGISTRY)})")
        return 2
    import jax

    violations = errors = 0
    for name in names:
        spec = REGISTRY[name]
        if len(jax.devices()) < spec.min_devices:
            if os.environ.get(_CHILD_ENV):
                # already the respawned child and the forced device count
                # still didn't take: report, never spawn a grandchild
                print(f"FAIL {name}: forced "
                      f"{spec.min_devices}-device CPU mesh did not take "
                      f"effect in the respawned child (execution error, "
                      f"not a budget violation)")
                errors += 1
                continue
            rc, out = _run_in_subprocess(spec, overlap=args.overlap)
            if rc == 0:
                continue
            # a child exits 1 for a real budget violation AND for its own
            # error paths (which self-report "not a budget violation") or
            # an uncaught crash — classify by the child's report, so the
            # summary never sends a reader chasing a nonexistent HLO
            # budget breach
            if rc == 1 and "FAIL" in out \
                    and "not a budget violation" not in out:
                violations += 1
            else:
                print(f"FAIL {name}: respawned child exited rc={rc} "
                      f"(execution error, not a budget violation)")
                errors += 1
            continue
        try:
            report = run_step(name)
            print(report.summary())
            if args.overlap:
                print(report.overlap_summary())
        except HloCheckError as e:
            print(f"FAIL {name}: {e}")
            violations += 1
        except Exception as e:  # noqa: BLE001 — one broken step must not
            # abort the sweep: the remaining steps still run and the
            # summary stays honest, same contract as the child path
            print(f"FAIL {name}: {type(e).__name__}: {e} "
                  f"(execution error, not a budget violation)")
            errors += 1
    if violations or errors:
        print(f"{violations} step(s) over budget, {errors} step(s) "
              f"errored")
    else:
        print(f"hlocheck clean: {len(names)} step(s) within budget")
    return 1 if (violations or errors) else 0
