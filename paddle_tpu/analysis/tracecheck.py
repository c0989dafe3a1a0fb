"""Trace-time auditing for jitted callables.

Three engines, all built around the same observation: the serving stack's
load-bearing contract — compile once, never sync the host mid-stream, never
touch a donated buffer again — has so far been enforced by hand-maintained
test pins (``compile_counts`` dicts, ad-hoc ``is_deleted`` probes). This
module turns those pins into enforced, *explained* checks:

- :class:`CompileGuard` wraps a callable in ``jax.jit``, counts actual
  traces (the wrapped python body runs exactly once per compilation),
  records the abstract signature of every trace, and enforces a declared
  compile budget. On an unexpected retrace it doesn't just raise — it diffs
  the offending signature against the closest prior trace and names the
  argument (and axis) whose shape/dtype/weak-type/static value changed.
  In ``strict`` mode the over-budget retrace is refused BEFORE paying the
  recompile; donated buffers are audited on the way in (use-after-donation
  and double donation raise :class:`DonationViolation`).

- :func:`donation_audit` is the jaxpr-level complement: it traces a
  function once and reports donated leaves the computation never consumes
  (donation of an unused buffer can alias nothing — almost always a wrong
  ``donate_argnums``) and donated leaves returned unchanged.

- :class:`SyncTally` counts host-sync events (``jax.device_get``,
  ``Array.__array__`` — the ``np.asarray(jax_array)`` path — ``.item()``,
  ``.tolist()``, ``int()``/``float()``/``bool()`` coercions of device
  arrays, and iteration over a device array — the ``for tok in toks`` /
  ``list(toks)`` pattern, one event per loop) inside a ``with`` region, so
  a decode loop can be *certified* sync-free up to its one sanctioned
  token fetch per step. Tallies nest; each active tally counts every
  event. :func:`sync_tally_paused` suspends counting for compile-time
  host work (AOT lowering materializes traced constants host-side).

None of this imports the serving stack — serving imports us.
"""
from __future__ import annotations

import contextlib
import functools
import inspect
import re
import threading

import numpy as np

__all__ = ["CompileGuard", "RetraceError", "DonationViolation",
           "SyncViolation", "SyncTally", "donation_audit",
           "abstract_signature", "explain_signature_diff",
           "sync_tally_paused"]


class RetraceError(RuntimeError):
    """A guarded callable exceeded its declared compile budget. The message
    names the argument whose abstract signature changed and how."""


class DonationViolation(RuntimeError):
    """A donated buffer was misused: referenced again after a donating call
    consumed it, or the same buffer donated through two arguments at once."""


class SyncViolation(RuntimeError):
    """A guarded region performed more host syncs than it declared."""


# --------------------------------------------------------------- signatures
def _leaf_spec(leaf):
    """The abstract signature of one pytree leaf — the facts jax keys its
    trace cache on: shape, dtype, weak type (python scalars trace weakly
    typed, committed arrays don't)."""
    import jax

    if isinstance(leaf, jax.Array):
        return ("array", tuple(leaf.shape), str(leaf.dtype),
                bool(leaf.weak_type))
    if isinstance(leaf, np.ndarray):
        return ("array", tuple(leaf.shape), str(leaf.dtype), False)
    if isinstance(leaf, (bool, int, float, complex)):
        # a python scalar traces as a weak 0-d array of its default dtype;
        # its VALUE does not key the cache, its type does
        return ("array", (), type(leaf).__name__, True)
    return ("static", repr(leaf))


def abstract_signature(args, kwargs=None, param_names=(),
                       static_argnums=()) -> tuple:
    """The abstract signature of a call: an ordered tuple of
    ``(leaf_name, spec)`` pairs over every argument's pytree leaves, with
    ``static_argnums`` arguments keyed by VALUE (their repr) the way jit's
    static arguments are. Pytree structure is part of the signature (leaf
    names include the path), so a list growing an element reads as
    added/removed leaves in the diff."""
    from jax.tree_util import keystr, tree_flatten_with_path

    sig = []
    for i, arg in enumerate(args):
        name = param_names[i] if i < len(param_names) else f"arg{i}"
        if i in static_argnums:
            sig.append((name, ("static", repr(arg))))
            continue
        for path, leaf in tree_flatten_with_path(arg)[0]:
            sig.append((name + keystr(path), _leaf_spec(leaf)))
    for k in sorted(kwargs or ()):
        for path, leaf in tree_flatten_with_path(kwargs[k])[0]:
            sig.append((k + keystr(path), _leaf_spec(leaf)))
    return tuple(sig)


def _describe_change(name: str, old, new) -> str:
    if old[0] != new[0]:
        return f"{name}: {old[0]} {old[1:]} -> {new[0]} {new[1:]}"
    if old[0] == "static":
        return f"{name}: static value {old[1]} -> {new[1]}"
    parts = []
    if old[1] != new[1]:
        axes = [f"axis {i}: {a} -> {b}"
                for i, (a, b) in enumerate(zip(old[1], new[1])) if a != b]
        if len(old[1]) != len(new[1]):
            axes.append(f"rank {len(old[1])} -> {len(new[1])}")
        parts.append(f"shape {old[1]} -> {new[1]} ({', '.join(axes)})")
    if old[2] != new[2]:
        parts.append(f"dtype {old[2]} -> {new[2]}")
    if old[3] != new[3]:
        parts.append(f"weak_type {old[3]} -> {new[3]} "
                     f"(python scalar vs committed array)")
    return f"{name}: " + ", ".join(parts)


def explain_signature_diff(prior: tuple, new: tuple) -> list[str]:
    """Human-readable differences between two abstract signatures, one
    string per changed/added/removed leaf (empty = identical)."""
    po, no_ = dict(prior), dict(new)
    out = []
    for name, spec in no_.items():
        if name not in po:
            out.append(f"{name}: new leaf {spec} (pytree structure changed)")
        elif po[name] != spec:
            out.append(_describe_change(name, po[name], spec))
    for name in po:
        if name not in no_:
            out.append(f"{name}: leaf removed (pytree structure changed)")
    return out


# ------------------------------------------------------------ CompileGuard
def _identifier(x) -> str:
    """``x`` as part of a program's name: a group id such as ``(512,)``
    reads ``512``, ``(8, 128)`` reads ``8_128``."""
    parts = x if isinstance(x, (tuple, list)) else (x,)
    return re.sub(r"\W+", "_", "_".join(str(p) for p in parts)).strip("_")


def _on_a_chunk_of_its_own(fn, args, kwargs):
    """``fn(*args, **kwargs)``, called from a frame so large that CPython
    gives it a chunk of the thread's data stack to itself with half a
    megabyte to spare, so that no call below it crosses a chunk boundary.

    CPython (3.11 on) keeps interpreter frames in 16 KiB chunks; a call
    that does not fit the current chunk maps a new one and the matching
    return unmaps it at once. Tracing a program is a few hundred thousand
    nested calls: where one of its inner loops happens to straddle a
    boundary, every turn pays a map and an unmap. On a sandboxed host a
    system call is 50-100 us, and a serving program's trace went from 7.5
    to 12 s because frames ABOVE it had grown by 40 slots (PERF.md section
    6, PR 31). Which loop straddles is decided by the sizes of all frames
    below: this takes the caller's out of it."""
    return fn(*args, **kwargs)


# a frame of just over 2**16 slots (512 KiB) is given a chunk of 1 MiB: the
# callee's frames have the other half
_on_a_chunk_of_its_own.__code__ = _on_a_chunk_of_its_own.__code__.replace(
    co_stacksize=2 ** 16 + 64)


class CompileGuard:
    """``jax.jit`` with an audit trail: trace counting, per-trace abstract
    signatures, compile budgets, retrace explanation, and donation checks.

    ``guard.traces`` counts actual compilations (the wrapped python body
    runs once per trace — the idiom the serving tests already pin);
    ``guard.signatures`` holds the abstract signature recorded at each
    trace; ``guard.retraces`` counts traces beyond ``budget``.

    ``strict=False`` (default) only counts — drop-in for the old ad-hoc
    counters with zero per-call overhead beyond the jit dispatch.
    ``strict=True`` audits every call BEFORE dispatch: an over-budget novel
    signature raises :class:`RetraceError` without paying the recompile,
    a deleted (donated-and-consumed) input or the same buffer donated
    through two arguments raises :class:`DonationViolation`.

    ``group_by`` (a callable over the call's positional args returning a
    hashable group id) declares that each group compiles AT MOST ONCE —
    e.g. the serving prefill groups by pad-bucket shape. Without it, an
    aggregate budget of N would let a real same-bucket retrace hide inside
    unused-bucket headroom; with it, a second trace of any group is a
    retrace even when the aggregate budget has room.

    What it jits is NAMED: after ``program`` (default: the guard's
    ``name``) and, under ``group_by``, the group — one ``jax.jit`` per
    group, so each compiled program has a module name of its own
    (``jit_<program>`` / ``jit_<program>_<group>``: the serving prefill's
    pad buckets read ``jit_serve_prefill_128`` and
    ``jit_serve_prefill_512`` on the profiler's "XLA Modules" line; one
    shared jit would give every bucket the same name). Each group still
    compiles at most once: nothing is compiled that one shared jit would
    not compile.
    """

    def __init__(self, fn, name: str | None = None, *, budget: int | None
                 = None, strict: bool = False, static_argnums=(),
                 donate_argnums=(), group_by=None, compiler_options=None,
                 program: str | None = None):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "jitted")
        self.program = _identifier(program or self.name)
        self.budget = budget
        self.strict = strict
        self.static_argnums = tuple(static_argnums)
        self.donate_argnums = tuple(donate_argnums)
        # per-jit XLA options (e.g. the TP latency-hiding scheduler);
        # None/{} = backend defaults, byte-identical to the old guard
        self.compiler_options = dict(compiler_options or {})
        self.traces = 0
        self.calls = 0
        self.retraces = 0  # traces beyond budget (counted even unstrict)
        self.group_by = group_by
        self.signatures: list[tuple] = []
        self._seen: set[tuple] = set()
        self._refused: set[tuple] = set()  # strict-mode pre-raised sigs
        self._groups: set = set()  # group ids that have traced already
        try:
            self._params = [p.name for p in
                            inspect.signature(fn).parameters.values()]
        except (TypeError, ValueError):
            self._params = []

        self._jits: dict = {}  # group id (None ungrouped) -> its jax.jit

    def _jit_for(self, group):
        """The ``jax.jit`` of ``group``'s program, made at its first call
        and named after ``program`` and the group."""
        jitted = self._jits.get(group)
        if jitted is not None:
            return jitted
        import jax

        fn = self.fn

        @functools.wraps(fn)
        def counted(*args, **kwargs):
            self.traces += 1
            return fn(*args, **kwargs)

        # the module is named jit_<__name__>
        counted.__name__ = counted.__qualname__ = self.program if \
            group is None else f"{self.program}_{_identifier(group)}"
        jit_kwargs = {}
        if self.static_argnums:
            jit_kwargs["static_argnums"] = self.static_argnums
        if self.donate_argnums:
            jit_kwargs["donate_argnums"] = self.donate_argnums
        if self.compiler_options:
            jit_kwargs["compiler_options"] = self.compiler_options
        jitted = self._jits[group] = jax.jit(counted, **jit_kwargs)
        return jitted

    # ------------------------------------------------------------- auditing
    def signature_of(self, args, kwargs=None) -> tuple:
        return abstract_signature(args, kwargs, self._params,
                                  self.static_argnums)

    def _check_donation(self, args) -> None:
        """Use-after-donation and double donation, caught at the call
        boundary with the offending argument named."""
        import jax
        from jax.tree_util import keystr, tree_flatten_with_path

        donated: dict[int, str] = {}
        for i, arg in enumerate(args):
            name = (self._params[i] if i < len(self._params) else f"arg{i}")
            for path, leaf in tree_flatten_with_path(arg)[0]:
                if not isinstance(leaf, jax.Array):
                    continue
                where = name + keystr(path)
                if leaf.is_deleted():
                    raise DonationViolation(
                        f"{self.name}: argument {where} is a deleted buffer "
                        f"— it was donated to (and consumed by) an earlier "
                        f"call and is referenced again; rebind the caller "
                        f"to the call's RETURNED arrays instead")
                if i in self.donate_argnums:
                    prev = donated.get(id(leaf))
                    if prev is not None:
                        raise DonationViolation(
                            f"{self.name}: double donation — {prev} and "
                            f"{where} are the same buffer, donated twice "
                            f"in one call (XLA would alias it to two "
                            f"outputs)")
                    donated[id(leaf)] = where

    def _explain(self, sig: tuple, group=None) -> str:
        trace_no = len(self.signatures) + 1
        if group is not None:
            why = (f"group {group!r} has already compiled (budget: one "
                   f"trace per group)")
        else:
            why = f"trace #{trace_no} exceeds the compile budget of " \
                  f"{self.budget}"
        head = (f"CompileGuard({self.name!r}): unexpected retrace — "
                f"{why}.")
        if not self.signatures:
            return head + " No prior trace recorded (budget 0?)."
        diffs_per = [explain_signature_diff(prev, sig)
                     for prev in self.signatures]
        best_i = min(range(len(diffs_per)), key=lambda i: len(diffs_per[i]))
        diffs = diffs_per[best_i]
        if not diffs:
            return (head + f" The call's abstract signature matches trace "
                    f"#{best_i + 1} exactly — the retrace was keyed on "
                    f"something outside the audited signature (a closure, "
                    f"global, or jit cache eviction).")
        unchanged = len(sig) - len([d for d in diffs if "removed" not in d])
        return (head + f" vs trace #{best_i + 1} (closest of "
                f"{len(self.signatures)}), {len(diffs)} leaf(s) changed: "
                + "; ".join(diffs)
                + f". {max(unchanged, 0)} other leaf(s) unchanged.")

    # --------------------------------------------------------------- call
    def __call__(self, *args, **kwargs):
        self.calls += 1
        sig = None
        group = self.group_by(*args) if self.group_by is not None else None
        if self.strict:
            self._check_donation(args)
            sig = self.signature_of(args, kwargs)
            regroup = group is not None and group in self._groups
            if sig not in self._seen and (
                    (self.budget is not None
                     and self.traces >= self.budget) or regroup):
                # retraces counts retrace EVENTS (novel over-budget
                # signatures), not refused calls — a caller retrying the
                # same bad signature matches non-strict accounting
                if sig not in self._refused:
                    self._refused.add(sig)
                    self.retraces += 1
                raise RetraceError(self._explain(
                    sig, group if regroup else None))
        before = self.traces
        jitted = self._jit_for(group)
        if group in self._groups:  # built already: the hot path
            out = jitted(*args, **kwargs)
        else:  # this call traces and lowers the program
            out = _on_a_chunk_of_its_own(jitted, args, kwargs)
        if self.traces > before:
            # shape/dtype metadata stays readable on donated-and-deleted
            # arrays (only the data is gone), so post-call recording is safe
            sig = sig if sig is not None else self.signature_of(args, kwargs)
            over = (self.budget is not None and self.traces > self.budget)
            regroup = group is not None and group in self._groups
            if over or regroup:
                self.retraces += 1
            self._groups.add(group)
            if (over or regroup) and self.strict:
                err = RetraceError(self._explain(
                    sig, group if regroup else None))
                self.signatures.append(sig)
                self._seen.add(sig)
                raise err
            self.signatures.append(sig)
            self._seen.add(sig)
        return out


# ---------------------------------------------------------- donation audit
def donation_audit(fn, donate_argnums, *args) -> list[str]:
    """Jaxpr-level donation check: trace ``fn`` on ``args`` and report
    donated leaves the computation (a) never consumes — donation of an
    unused buffer can alias nothing into any output, almost always a wrong
    ``donate_argnums`` — or (b) returns unchanged (the alias is an identity
    copy; donation works but buys nothing). Returns human-readable report
    strings, empty when donation is clean."""
    import jax
    from jax.tree_util import keystr, tree_flatten_with_path

    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr
    try:
        params = [p.name for p in inspect.signature(fn).parameters.values()]
    except (TypeError, ValueError):
        params = []

    def is_var(v):
        return type(v).__name__ not in ("Literal", "DropVar")

    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if is_var(v):
                used.add(id(v))
    outs = {id(v) for v in jaxpr.outvars if is_var(v)}

    # invars are the flattened args in order: walk per-arg leaf counts
    reports, pos = [], 0
    for i, arg in enumerate(args):
        leaves = tree_flatten_with_path(arg)[0]
        name = params[i] if i < len(params) else f"arg{i}"
        for path, _ in leaves:
            v = jaxpr.invars[pos]
            pos += 1
            if i not in donate_argnums:
                continue
            where = name + keystr(path)
            if id(v) not in used and id(v) not in outs:
                reports.append(
                    f"donated leaf {where} is never consumed by the "
                    f"computation — donation cannot alias it into any "
                    f"output (wrong donate_argnums?)")
            elif id(v) in outs and id(v) not in used:
                reports.append(
                    f"donated leaf {where} is returned unchanged — the "
                    f"alias is an identity pass-through")
    return reports


# ------------------------------------------------------------- host syncs
_tally_lock = threading.Lock()
_active_tallies: list["SyncTally"] = []
_saved_attrs: list[tuple[object, str, object]] = []
_in_event = threading.local()


def _record(kind: str) -> None:
    for t in _active_tallies:
        t.count += 1
        t.events.append(kind)


def _wrap(kind: str, orig):
    @functools.wraps(orig)
    def wrapper(*args, **kwargs):
        # a sync primitive implemented atop another (item -> __array__)
        # must count once, not per layer
        if getattr(_in_event, "on", False):
            return orig(*args, **kwargs)
        _in_event.on = True
        try:
            _record(kind)
            return orig(*args, **kwargs)
        finally:
            _in_event.on = False
    return wrapper


def _wrap_numpy(kind: str, orig):
    """numpy entry points sync only when handed a device array — a CPU
    jax Array satisfies the buffer protocol, so ``Array.__array__`` never
    fires and the conversion must be counted at the numpy call site."""
    import jax

    @functools.wraps(orig)
    def wrapper(*args, **kwargs):
        # the operand may arrive by keyword (np.asarray(a=x),
        # np.array(object=x)) — never shadow it with a positional param
        obj = args[0] if args else kwargs.get("a", kwargs.get("object"))
        if isinstance(obj, jax.Array) and not getattr(_in_event, "on",
                                                      False):
            _in_event.on = True
            try:
                _record(kind)
                return orig(*args, **kwargs)
            finally:
                _in_event.on = False
        return orig(*args, **kwargs)
    return wrapper


def _install_patches() -> None:
    import jax
    from jax._src import array as jarray

    targets = [(jax, "device_get", "device_get", _wrap)]
    impl = jarray.ArrayImpl
    # tolist is a full-array materialization; __iter__ covers BOTH the
    # `for tok in device_array` loop and `list(device_array)` (including
    # the __len__/__getitem__ sequence-protocol fallback) — per-element
    # coercions inside the loop still count separately, the iteration
    # itself counts once (the PR 6 SyncTally blind-spot fix)
    for attr, kind in (("__array__", "np.asarray"), ("item", "item"),
                       ("tolist", "tolist"), ("__iter__", "iter"),
                       ("__int__", "int"), ("__float__", "float"),
                       ("__bool__", "bool"), ("__index__", "index")):
        if hasattr(impl, attr):
            targets.append((impl, attr, kind, _wrap))
    for attr in ("asarray", "array"):
        targets.append((np, attr, f"np.{attr}", _wrap_numpy))
    for obj, attr, kind, wrap in targets:
        orig = getattr(obj, attr)
        _saved_attrs.append((obj, attr, orig))
        setattr(obj, attr, wrap(kind, orig))


def _remove_patches() -> None:
    while _saved_attrs:
        obj, attr, orig = _saved_attrs.pop()
        setattr(obj, attr, orig)


@contextlib.contextmanager
def sync_tally_paused():
    """Suspend SyncTally counting for the region. For compile-time host
    work that is not a serving-path sync — AOT lowering (hlocheck audits)
    converts traced constants through ``np.asarray`` on device arrays,
    which would otherwise pollute a step's certified sync count. Nested
    real sync events inside the region are deliberately NOT counted."""
    prev = getattr(_in_event, "on", False)
    _in_event.on = True
    try:
        yield
    finally:
        _in_event.on = prev


class SyncTally:
    """Counts device->host sync events inside a ``with`` region:
    ``jax.device_get``, ``Array.__array__`` (the ``np.asarray(jax_array)``
    path), ``.item()``, ``.tolist()``, ``int()``/``float()``/``bool()``
    coercions of device arrays, and iteration over a device array (one
    event per ``for``/``list()`` pass — per-element coercions inside the
    loop still count on top). ``allowed=N`` turns the tally into an
    assertion: leaving the region with more than N syncs raises
    :class:`SyncViolation`.

    Reentrant — nested tallies each count every event — but not
    thread-safe: the patches are process-global, so tally regions on
    concurrent threads would observe each other's syncs."""

    def __init__(self, allowed: int | None = None, name: str = "region"):
        self.allowed = allowed
        self.name = name
        self.count = 0
        self.events: list[str] = []

    def __enter__(self) -> "SyncTally":
        with _tally_lock:
            if not _active_tallies:
                _install_patches()
            _active_tallies.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        with _tally_lock:
            _active_tallies.remove(self)
            if not _active_tallies:
                _remove_patches()
        if exc_type is None and self.allowed is not None \
                and self.count > self.allowed:
            raise SyncViolation(
                f"{self.name}: {self.count} host sync(s) in a region that "
                f"allows {self.allowed} — events: {self.events}")
        return False
