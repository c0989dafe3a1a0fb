"""AST repo linter: rules distilled from bugs this repo actually shipped.

Every rule encodes a regression that cost a review cycle (or worse, landed):

- PT001 — a ``@dataclass`` with an ndarray/Array field and no ``eq=False``:
  the generated ``__eq__`` compares arrays elementwise; numpy 2 raises on
  shape mismatch, and ``deque.remove`` corrupted the PR 2 waiting queue
  exactly this way.
- PT002 — a host ``for`` loop doing ``.at[...].set(...)`` per layer over a
  stacked pool: each iteration is a separate dispatch that functionally
  copies the ENTIRE pool (the PR 3 swap bug — O(pool) bytes per layer per
  swap event). One jitted gather/scatter over a stacked view replaces it.
  (Comprehensions inside to-be-jitted closures trace once and are exempt.)
- PT003 — a monitor counter incremented (``stat_add``) without pre-seeding
  in the module's ``_SEEDED`` registry: dashboards key on presence, so a
  counter that first appears when the first bad event happens is invisible
  exactly until it matters.
- PT004 — ``time.time()`` inside ``serving/``: the engine clock is
  pluggable (``ServingConfig(clock=)``) so deadlines/budgets are testable
  without sleeping; raw wall-clock reads bypass the virtual clock and the
  ``slow_step`` fault skew.
- PT005 — a host-sync call (``np.asarray``/``np.array``/``jax.device_get``/
  ``.item()``) inside a ``step()``/decode hot path in ``serving/``: every
  sync stalls the dispatch pipeline; the ONE sanctioned sync (the step's
  token fetch) carries an explicit pragma. (The dynamic complement is
  ``analysis.tracecheck.SyncTally`` — this rule catches what's visible
  statically.)
- PT006 — jitting a function with pool-sized parameters without
  ``donate_argnums``: without input/output aliasing every ``.at[]`` write
  copies the whole pool and holds two pools live.
- PT007 — mutable default argument: the shared-default-instance classic.
- PT008 — a monitor gauge written (``stat_set``/``stat_max``) without
  pre-seeding in the module's ``_SEEDED`` registry: the unseeded-GAUGE
  mirror of PT003. A gauge that first appears at its first write is
  invisible on dashboards exactly until the condition it reports starts
  happening (the serving gauges shipped this way — a snapshot taken
  before the first step had no ``serving_queue_depth``).
- PT009 — raw ``jax.jit`` in ``serving/`` not routed through an
  ``analysis.CompileGuard``: an unregistered jitted step is invisible to
  the compile budgets, the retrace explainer, AND the hlocheck
  compiled-artifact audits (collective census, aliasing verification,
  HBM/flops roll-up) — exactly the steps those exist to certify.
- PT010 — ``shard_map`` in ``serving/`` (the attribute, or any
  ``from jax.experimental.shard_map import shard_map`` respelling):
  a sharded step whose wrapped computation is not registered with a
  declared ``CollectiveBudget`` in the hlocheck registry can acquire
  implicit resharding collectives no budget ever audits — the exact
  regression the tensor-parallel serving arc certifies against. The one
  sanctioned entry point (serving/tp.py, whose wrapped steps ARE
  registered: tp2_engine_* + the per-shard cache movers) carries the
  pragma.
- PT011 — a ``pl.pallas_call`` (or ``from ... import pallas_call``) in a
  module with no registered kernelcheck certificate: an uncertified
  Pallas kernel ships with no VMEM budget, no tiling lint, no grid-race
  proof, and no roofline contract — exactly how the paged-decode
  dispatch shipped a kernel that could not even trace. A pallas-kernel
  module declares ``KERNELCHECK_CERTS = (...)`` naming its
  ``analysis.kernelcheck.REGISTRY`` entries (a tier-1 test pins each
  name to a live entry).
- PT012 — a LABELED stat family used at a ``stat_add``/``stat_set``/
  ``stat_max`` call site (a name shaped ``base{label=value}`` — or
  multi-label ``base{a=,b=}`` — usually built with an f-string) whose
  base is in neither ``_SEEDED`` nor the module's ``_FAMILIES``
  registry: the dynamically formatted name is invisible to PT003/PT008
  — exactly the gap the ``serving_alerts_total{rule=}`` /
  ``serving_step_phase_s{phase=}`` families opened — so an unregistered
  family ships with no pre-seeded members and appears on dashboards
  only once its first event fires. Also fires when the call site's
  statically visible label KEYS (or their order) disagree with the
  ``_FAMILIES`` declaration: keys are part of the registry key, so a
  reordered ``{class=,tenant=}`` write builds a member the seeding
  never created.
- PT013 — a direct ``.add_request(...)`` call in ``serving/fleet*.py``:
  every fleet-side admission must flow through the router's weighted
  admission path (prefix-affinity placement, per-tenant weights,
  spill-before-shed, journeys + fleet counters) — a direct engine call
  silently bypasses ALL of it, the exact hole the fleet layer exists to
  close. The router's one sanctioned dispatch site carries the pragma;
  anything else in a fleet module fires.
- PT014 — a raw serialization/transport primitive (``pickle``/``socket``
  imports, ``pickle.*``/``socket.*`` attribute use, or ``struct``
  pack/unpack) in ``serving/`` outside ``wire.py``: every byte that
  crosses a replica boundary must go through the ONE versioned codec
  (``serving/wire.py`` — magic + version + length-prefixed frames, CRC
  trailer, typed ``WireError`` taxonomy). Ad-hoc framing forks the
  schema invisibly, pickle swallows corruption that the taxonomy counts
  by kind, and a raw socket bypasses the transport's retry/breaker
  policy AND its fault points — the codec module itself is gated out by
  filename (it IS the sanctioned user).

Suppression: a ``# lint: disable=PT001`` (comma-separated for several)
pragma on the finding's line, or an entry in :data:`ALLOWLIST` mapping a
path substring to rule codes exempt in matching files. Rules carry a
``scope`` path-part restriction (PT002/PT004/PT005/PT006/PT009 fire only
under ``serving/`` — they encode serving-stack contracts).

CLI: ``python -m paddle_tpu.analysis [paths] [--rule PTxxx] [--path SUB]``
(also ``tools/lint.py``). With no paths the DEFAULT sweep covers the
installed package plus the repo's ``tests/`` and ``examples/`` trees
(``--include`` overrides the extra trees) — the lint fixtures'
intentional positives are exempted via :data:`ALLOWLIST`, and a tier-1
test pins the whole default sweep at zero findings. Exit code 0 = clean,
1 = findings, 2 = bad usage.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path

__all__ = ["Finding", "RULES", "ALLOWLIST", "lint_source", "lint_paths",
           "main"]

# path substring -> rule codes exempt in matching files. Kept to the one
# entry that CANNOT be a pragma: the lint fixtures are intentional
# positives whose tests assert the rules DO fire — a pragma in the fixture
# would defeat the fixture. Everything else should use pragmas, which are
# visible at the offending line.
ALLOWLIST: dict[str, set[str]] = {
    "lint_fixtures": {f"PT00{i}" for i in range(1, 10)}
    | {"PT010", "PT011", "PT012", "PT013", "PT014", "PT015", "PT016",
       "PT017"},
}

_PRAGMA = re.compile(r"#\s*lint:\s*disable=([A-Z0-9_,\s]+)")
_ARRAY_ANN = re.compile(r"\bndarray\b|\bArray\b")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


def _unparse(node) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # noqa: BLE001 — diagnostics only
        return "<?>"


def _is_at_set_call(node) -> bool:
    """``X.at[...].set(...)`` — the functional scatter-write idiom."""
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "set"
            and isinstance(node.func.value, ast.Subscript)
            and isinstance(node.func.value.value, ast.Attribute)
            and node.func.value.value.attr == "at")


# ------------------------------------------------------------------- rules
def _pt001(tree, path):
    """dataclass with ndarray/Array field missing eq=False."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        deco = next((d for d in node.decorator_list
                     if "dataclass" in _unparse(d)), None)
        if deco is None:
            continue
        if isinstance(deco, ast.Call) and any(
                k.arg == "eq" and isinstance(k.value, ast.Constant)
                and k.value.value is False for k in deco.keywords):
            continue
        arr = [f"{b.target.id}: {_unparse(b.annotation)}"
               for b in node.body
               if isinstance(b, ast.AnnAssign) and b.annotation is not None
               and isinstance(b.target, ast.Name)
               and _ARRAY_ANN.search(_unparse(b.annotation))]
        if arr:
            # anchored at the decorator: that line carries the fix (and
            # any pragma)
            yield (deco.lineno,
                   f"dataclass {node.name!r} has array field(s) "
                   f"({', '.join(arr)}) but no eq=False — the generated "
                   f"__eq__ compares arrays elementwise (numpy 2 raises on "
                   f"shape mismatch; deque.remove corrupted the PR 2 "
                   f"queue). Use @dataclass(eq=False).")


def _pt002(tree, path):
    """Per-layer host .at[].set loop over a stacked pool."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.For):
            continue
        if "pool" not in _unparse(node.iter).lower():
            continue
        hit = next((n for n in ast.walk(node) if _is_at_set_call(n)), None)
        if hit is not None:
            yield (node.lineno,
                   f"host for-loop over {_unparse(node.iter)!r} performs "
                   f".at[].set per iteration — each is a separate dispatch "
                   f"that functionally copies the ENTIRE pool (O(pool) "
                   f"bytes per layer per event, the PR 3 swap bug). Move "
                   f"the loop inside ONE jitted gather/scatter over a "
                   f"layer-stacked view.")


def _seeding_contract(tree):
    """The module's (seeded names, stat prefix) — the registry PT003 and
    PT008 check against. ``seeded`` is None when the module declares no
    ``_SEEDED`` tuple (no contract to enforce)."""
    seeded, prefix = None, ""
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            tgt = node.targets[0].id
            if tgt == "_SEEDED" and isinstance(node.value, (ast.Tuple,
                                                            ast.List)):
                seeded = {e.value for e in node.value.elts
                          if isinstance(e, ast.Constant)}
            elif tgt == "PREFIX" and isinstance(node.value, ast.Constant):
                prefix = node.value.value
    return seeded, prefix


#: stands in for each formatted field in a resolved name SKELETON — a
#: character no real stat name contains
_FMT_PLACEHOLDER = "\x00"


def _stat_name_text(node, fn_suffixes, prefix):
    """The statically visible text of a ``stat_xxx`` call's name
    argument — the ONE resolver behind PT003/PT008 (whole names) and
    PT012 (labeled-family heads AND label keys), so a newly supported
    naming idiom lands in exactly one place and the rules can never
    disagree about which call sites they see. Resolves ``PREFIX +
    "..."`` / ``PREFIX + f"..."`` concatenations and bare (f-)strings
    carrying the prefix inline. Returns ``(text, whole, skeleton)``:
    ``text`` is the leading constant, ``whole`` says it is the ENTIRE
    name (a plain constant), and ``skeleton`` is the full name with
    every formatted field replaced by a placeholder — the surface the
    multi-label family check (``base{a=,b=}``) reads its label keys
    off. None when the call isn't one of ``fn_suffixes`` or nothing is
    statically visible (runtime-computed names can't be checked
    statically)."""
    if not (isinstance(node, ast.Call) and node.args
            and _unparse(node.func).endswith(fn_suffixes)):
        return None
    arg = node.args[0]
    strip = True  # bare names carry the prefix inline; PREFIX + x doesn't
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add) \
            and _unparse(arg.left) == "PREFIX":
        arg, strip = arg.right, False
    if isinstance(arg, ast.Constant):
        text, whole, skeleton = arg.value, True, arg.value
    elif isinstance(arg, ast.JoinedStr) and arg.values \
            and isinstance(arg.values[0], ast.Constant):
        text, whole = arg.values[0].value, False
        skeleton = "".join(
            str(v.value) if isinstance(v, ast.Constant)
            else _FMT_PLACEHOLDER for v in arg.values)
    else:
        return None
    if not isinstance(text, str) or not isinstance(skeleton, str):
        return None
    if strip:
        if not (prefix and text.startswith(prefix)):
            return None
        text = text[len(prefix):]
        skeleton = skeleton[len(prefix):]
    return text, whole, skeleton


def _stat_call_name(node, fn_suffixes, prefix):
    """The statically visible WHOLE stat name of a ``stat_xxx`` call;
    None when the name has a formatted tail, or is a labeled-family
    member (contains ``{`` — PT012's domain, where the check is against
    ``_FAMILIES``, not ``_SEEDED``)."""
    resolved = _stat_name_text(node, fn_suffixes, prefix)
    if resolved is None:
        return None
    text, whole, _ = resolved
    if not whole or "{" in text:
        return None  # formatted tail / labeled family: PT012's domain
    return text


_STAT_FNS = ("stat_add", "stat_set", "stat_max")

# a COMPLETE static family shape: base{k=...,k2=...} with only the label
# VALUES possibly formatted — the precondition for reading label keys
_FULL_FAMILY = re.compile(
    r"^[A-Za-z0-9_]+\{[A-Za-z_][A-Za-z0-9_]*=[^{}]*\}$")
_LABEL_KEYS = re.compile(r"[{,]([A-Za-z_][A-Za-z0-9_]*)=")


def _labeled_stat_family(node, prefix):
    """``(base, keys)`` of a labeled stat name at a ``stat_xxx`` call
    site — ``base`` is the head before the first ``{`` of the leading
    constant text (the ``base{label=value}`` / multi-label
    ``base{a=,b=}`` family shapes, e.g. ``PREFIX +
    f"base{{a={x},b={y}}}"``), and ``keys`` the ORDERED tuple of label
    keys when the whole label structure is statically visible (only the
    VALUES formatted), else None. None for anything else — a name whose
    brace only appears after a formatted field (e.g. the family
    percentile mirrors ``f"base_{suffix}{{label=...}}"``) has no
    checkable base, the same documented blindness PT003 has to fully
    dynamic names."""
    resolved = _stat_name_text(node, _STAT_FNS, prefix)
    if resolved is None:
        return None
    text, _, skeleton = resolved
    if "{" not in text:
        return None
    base = text.split("{", 1)[0]
    keys = None
    if _FULL_FAMILY.match(skeleton):
        keys = tuple(_LABEL_KEYS.findall(skeleton))
    return base, keys


def _pt003(tree, path):
    """Counter incremented without pre-seeding in the monitor registry."""
    seeded, prefix = _seeding_contract(tree)
    if seeded is None:  # no seeding registry in this module: no contract
        return
    for node in ast.walk(tree):
        name = _stat_call_name(node, ("stat_add",), prefix)
        if name is not None and name not in seeded:
            yield (node.lineno,
                   f"counter {name!r} is incremented but never pre-seeded "
                   f"in _SEEDED — a snapshot taken before its first "
                   f"increment omits it, and dashboards key on presence. "
                   f"Add it to _SEEDED so reset() seeds the zero.")


def _pt004(tree, path):
    """time.time() in serving/ instead of the pluggable engine clock."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "time"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in ("time", "_time")):
            yield (node.lineno,
                   "time.time() in serving/ bypasses the pluggable engine "
                   "clock (ServingConfig clock= + slow_step fault skew) — "
                   "deadlines and budgets become untestable without "
                   "sleeping. Use engine.now() / the injected clock.")


# the engine's step, and what every launch of a step program goes through
_HOT_NAMES = ("step", "_step", "_launch", "_fetch")


def _pt005(tree, path):
    """Host-sync call inside a step()/decode hot path."""
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not (fn.name in _HOT_NAMES or "decode" in fn.name):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            sync = None
            if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
                if f.value.id in ("np", "numpy") and \
                        f.attr in ("asarray", "array"):
                    sync = f"np.{f.attr}"
                elif f.value.id == "jax" and f.attr == "device_get":
                    sync = "jax.device_get"
            if isinstance(f, ast.Attribute) and f.attr == "item" \
                    and not node.args and not node.keywords:
                sync = ".item()"
            if sync:
                yield (node.lineno,
                       f"{sync} inside hot path {fn.name!r} blocks on a "
                       f"device->host sync every step. If this is a "
                       f"sanctioned token fetch, annotate it with "
                       f"`# lint: disable=PT005`; otherwise move it off "
                       f"the decode path. NOTE: bare int()/float() "
                       f"coercions of device arrays sync too but are "
                       f"invisible statically — route them through "
                       f"np.asarray so this rule sees them, and rely on "
                       f"SyncTally to certify the loop dynamically.")


def _pt006(tree, path):
    """jit of pool-sized args without donate_argnums."""
    defs = {n.name: n for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef)}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fname = _unparse(node.func)
        if not (fname.endswith("jit") or fname.endswith("CompileGuard")):
            continue
        if any(k.arg == "donate_argnums" for k in node.keywords):
            continue
        target = node.args[0]
        if isinstance(target, ast.Name):
            fn = defs.get(target.id)
        elif isinstance(target, ast.Attribute):
            fn = defs.get(target.attr)
        else:
            fn = None
        if fn is None:
            continue
        pool_args = [a.arg for a in fn.args.args if "pool" in a.arg.lower()]
        if pool_args:
            yield (node.lineno,
                   f"{fname}({fn.name}) takes pool-sized argument(s) "
                   f"{pool_args} but declares no donate_argnums — without "
                   f"input/output aliasing every .at[] write copies the "
                   f"whole pool and holds two pools live. Donate the pool, "
                   f"or pragma-suppress if the function only READS it.")


def _pt007(tree, path):
    """Mutable default argument."""
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
            continue
        name = getattr(fn, "name", "<lambda>")
        for d in list(fn.args.defaults) + [x for x in fn.args.kw_defaults
                                           if x is not None]:
            mutable = isinstance(d, (ast.List, ast.Dict, ast.Set,
                                     ast.ListComp, ast.DictComp,
                                     ast.SetComp)) or (
                isinstance(d, ast.Call) and isinstance(d.func, ast.Name)
                and d.func.id in ("list", "dict", "set"))
            if mutable:
                yield (d.lineno,
                       f"mutable default {_unparse(d)!r} in {name}() is "
                       f"created ONCE and shared across every call — use "
                       f"None and construct inside, or a dataclass "
                       f"default_factory.")


def _pt008(tree, path):
    """Gauge written (stat_set/stat_max) without pre-seeding — the
    unseeded-gauge mirror of PT003."""
    seeded, prefix = _seeding_contract(tree)
    if seeded is None:
        return
    for node in ast.walk(tree):
        name = _stat_call_name(node, ("stat_set", "stat_max"), prefix)
        if name is not None and name not in seeded:
            yield (node.lineno,
                   f"gauge {name!r} is written but never pre-seeded in "
                   f"_SEEDED — it first appears in the registry when the "
                   f"condition it reports starts happening, so a "
                   f"dashboard keyed on presence is blind exactly until "
                   f"then. Add it to _SEEDED so reset() seeds the zero.")


def _pt009(tree, path):
    """Raw jax.jit in serving/ escaping the CompileGuard registry. Any
    reference to the ``jax.jit`` attribute counts — a call, a decorator,
    a ``functools.partial(jax.jit, ...)``, or a bare alias assignment all
    produce a jitted step no guard (and no hlocheck audit) can see — and
    so does importing the name bare (``from jax import jit``), the
    trivial respelling that would otherwise evade the attribute check."""
    msg = ("raw jax.jit in serving/ bypasses the CompileGuard "
           "registry — compile budgets, the retrace explainer, "
           "and the hlocheck compiled-artifact audits (collective "
           "census, donation aliasing, HBM/flops budgets) cannot "
           "see unregistered steps. Wrap the step in "
           "analysis.CompileGuard (or pragma-suppress a "
           "sanctioned raw jit).")
    jax_names = {"jax"} | {
        a.asname for node in ast.walk(tree) if isinstance(node, ast.Import)
        for a in node.names if a.name == "jax" and a.asname}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id in jax_names):
            yield (node.lineno, msg)
        elif isinstance(node, ast.ImportFrom) and node.module == "jax" \
                and any(a.name == "jit" for a in node.names):
            yield (node.lineno,
                   "`from jax import jit` in serving/ imports the raw "
                   "jit bare — every use is a step the CompileGuard "
                   "registry (and hlocheck) can't see, and the bare name "
                   "is invisible to the jax.jit attribute check. " + msg)


def _pt010(tree, path):
    """shard_map in serving/ outside the registered tensor-parallel
    wrapper. Flags the ENTRY POINTS — any ``.shard_map`` attribute access
    and any ``from ... import shard_map`` (aliased or not) — so every
    respelling is caught where the name enters the module; a sanctioned
    use (a wrapper whose wrapped steps are registered with declared
    CollectiveBudgets in the hlocheck registry) pragma-suppresses its one
    import/attribute line."""
    msg = ("shard_map in serving/ builds a sharded step the hlocheck "
           "registry doesn't know: without a registered, declared "
           "CollectiveBudget the compiled program can acquire implicit "
           "resharding collectives no audit ever counts. Route sharding "
           "through serving/tp.py (whose wrapped steps are registered as "
           "tp2_engine_* / the per-shard cache movers), or register the "
           "step's budget and pragma-suppress this entry point.")
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "shard_map":
            yield (node.lineno, msg)
        elif isinstance(node, ast.ImportFrom) and (
                (node.module or "").endswith("shard_map")
                or any(a.name == "shard_map" for a in node.names)):
            yield (node.lineno,
                   "importing shard_map bare makes every call site "
                   "invisible to the attribute check. " + msg)


def _pt011(tree, path):
    """pallas_call in a module with no registered kernelcheck
    certificate. A module sanctions itself by declaring a top-level
    ``KERNELCHECK_CERTS = ("entry", ...)`` tuple naming its
    analysis.kernelcheck REGISTRY entries — the declaration is what a
    tier-1 test cross-checks against the live registry, so a stale name
    can't silently satisfy the rule."""
    def _declares(node):
        if isinstance(node, ast.Assign):
            return (len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "KERNELCHECK_CERTS")
        if isinstance(node, ast.AnnAssign):  # KERNELCHECK_CERTS: tuple = ...
            return (isinstance(node.target, ast.Name)
                    and node.target.id == "KERNELCHECK_CERTS"
                    and node.value is not None)
        return False

    has_certs = any(_declares(node) for node in tree.body)
    if has_certs:
        return
    msg = ("pallas_call in a module with no registered kernelcheck "
           "certificate — the kernel ships with no VMEM budget, tiling "
           "lint, grid-race proof, or roofline contract. Register it in "
           "analysis/kernelcheck.py REGISTRY and declare "
           "KERNELCHECK_CERTS = (\"<entry>\", ...) at module top level "
           "(or pragma-suppress a sanctioned uncertified call).")
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "pallas_call":
            yield (node.lineno, msg)
        elif isinstance(node, ast.ImportFrom) and any(
                a.name == "pallas_call" for a in node.names):
            yield (node.lineno,
                   "importing pallas_call bare makes every launch site "
                   "invisible to the attribute check. " + msg)


def _family_registry(tree):
    """The module's declared labeled families: ``{base: label keys}``
    from a top-level ``_FAMILIES = {...}`` dict — a string value
    normalizes to a 1-tuple, a tuple/list of strings is a multi-label
    declaration in registry-key order, anything non-constant maps to
    None (declared, keys not statically checkable). None when the
    module declares no registry."""
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "_FAMILIES" \
                and isinstance(node.value, ast.Dict):
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if not isinstance(k, ast.Constant):
                    continue
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    out[k.value] = (v.value,)
                elif isinstance(v, (ast.Tuple, ast.List)) and all(
                        isinstance(e, ast.Constant) for e in v.elts):
                    out[k.value] = tuple(e.value for e in v.elts)
                else:
                    out[k.value] = None
            return out
    return None


def _pt012(tree, path):
    """Labeled stat family written without a ``_FAMILIES`` declaration —
    the dynamically-formatted-name gap of PT003/PT008 — or written with
    label keys (or key ORDER) disagreeing with the declaration: the
    label keys are part of the registry key, so a mismatched write
    builds a member the seeding never created and dashboards keyed on
    presence go blind exactly like the undeclared case. Gated, like
    PT003/PT008, on the module declaring a ``_SEEDED`` contract."""
    seeded, prefix = _seeding_contract(tree)
    if seeded is None:  # no seeding registry in this module: no contract
        return
    families = _family_registry(tree) or {}

    def registered(base):
        # a declared family sanctions its derived mirror names too
        # (step_phase_s -> step_phase_s_count / step_phase_s_p99)
        return base in seeded or any(
            base == fam or base.startswith(fam + "_") for fam in families)

    for node in ast.walk(tree):
        resolved = _labeled_stat_family(node, prefix)
        if resolved is None:
            continue
        base, keys = resolved
        if not registered(base):
            yield (node.lineno,
                   f"labeled stat family {base!r} ({base}{{...=...}}) is "
                   f"written but declared in neither _FAMILIES nor "
                   f"_SEEDED — the formatted name is invisible to "
                   f"PT003/PT008, so its members are never pre-seeded "
                   f"and dashboards keyed on presence are blind until "
                   f"the first event. Declare the base in _FAMILIES and "
                   f"seed its label values (ServingMetrics.seed_family).")
        elif keys is not None and families.get(base) is not None \
                and keys != families[base]:
            yield (node.lineno,
                   f"labeled stat family {base!r} is written with label "
                   f"keys {keys} but _FAMILIES declares "
                   f"{families[base]} — label keys and their ORDER are "
                   f"part of the registry key, so this write builds a "
                   f"member the seeding never created (it reads as "
                   f"absent on dashboards and never resets). Write the "
                   f"labels exactly as declared.")


def _pt013(tree, path):
    """Direct ServingEngine.add_request call in a fleet module. Scope is
    the serving/fleet* files only (gated on the filename — the rule
    encodes a fleet-layer contract, not an engine one): the router's
    single sanctioned dispatch site — the line every request reaches
    only AFTER weighted admission placed it — pragma-suppresses itself;
    any other ``.add_request`` attribute access in a fleet module is an
    admission bypass."""
    if not Path(path).name.startswith("fleet"):
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "add_request":
            yield (node.lineno,
                   "direct .add_request in a fleet module bypasses the "
                   "router's admission path — no prefix-affinity "
                   "placement, no per-tenant weight, no "
                   "spill-before-shed, no fleet counters or journey "
                   "hops. Route the request through "
                   "FleetRouter.submit() (the router's one sanctioned "
                   "dispatch site carries the pragma).")


_PT014_MODULES = ("pickle", "socket")
_PT014_STRUCT_FNS = ("pack", "unpack", "pack_into", "unpack_from",
                     "iter_unpack", "calcsize", "Struct")


def _pt014(tree, path):
    """Raw serialization/transport primitive in serving/ outside the
    codec module. Gated on the filename (like PT013): serving/wire.py
    IS the sanctioned user — the rule exists so the versioned framed
    codec stays the only place replica-boundary bytes are shaped."""
    if Path(path).name == "wire.py":
        return
    for node in ast.walk(tree):
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name.split(".")[0] for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [(node.module or "").split(".")[0]]
        for m in mods:
            if m in _PT014_MODULES + ("struct",):
                yield (node.lineno,
                       f"raw {m!r} import in serving/ outside wire.py — "
                       f"bytes that cross a replica boundary go through "
                       f"the versioned wire codec (serving/wire.py: "
                       f"encode_*/decode_frame, CRC-trailed, typed "
                       f"WireError taxonomy). Ad-hoc {m} framing forks "
                       f"the schema and skips corruption accounting, "
                       f"retry policy, and the wire fault points.")
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name):
            base = node.value.id
            if base in _PT014_MODULES or (
                    base == "struct" and node.attr in _PT014_STRUCT_FNS):
                yield (node.lineno,
                       f"raw {base}.{node.attr} in serving/ outside "
                       f"wire.py — shape these bytes through the "
                       f"versioned wire codec (serving/wire.py) so the "
                       f"frame format stays single-sourced and every "
                       f"decode failure lands in the typed WireError "
                       f"taxonomy the transport counts by kind.")


def _pt015(tree, path):
    """Raw ``psum`` in serving/ outside tp.py. Gated on the filename
    (like PT013/PT014): serving/tp.py IS the sanctioned collective entry
    point — its ``quantized_psum`` and the model's ``tp_axis`` psums are
    the only reductions the declared CollectiveBudgets (and hlocheck's
    overlap/byte census) account for. A raw ``lax.psum`` anywhere else in
    serving/ is an unbudgeted collective: it lands over the step budget
    at the first debug_checks audit at best, and silently serializes a
    decode step against the mesh at worst. Flags the attribute forms
    (``lax.psum``/``jax.lax.psum``) and the from-import (any alias)."""
    if Path(path).name == "tp.py":
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "lax" or mod.endswith(".lax") or mod == "jax.lax":
                for a in node.names:
                    if a.name == "psum":
                        yield (node.lineno,
                               f"raw `from {mod} import psum"
                               + (f" as {a.asname}`" if a.asname else "`")
                               + " in serving/ outside tp.py — every "
                               "serving collective must route through "
                               "serving/tp.py (quantized_psum or the "
                               "tp_axis model psums) so it is declared "
                               "in the step's CollectiveBudget and "
                               "counted by hlocheck's byte/overlap "
                               "census. An unbudgeted psum fails the "
                               "first debug_checks audit.")
        elif isinstance(node, ast.Attribute) and node.attr == "psum":
            base, dotted = node.value, None
            if isinstance(base, ast.Name):
                dotted = base.id
            elif isinstance(base, ast.Attribute) \
                    and isinstance(base.value, ast.Name):
                dotted = f"{base.value.id}.{base.attr}"
            if dotted in ("lax", "jax.lax"):
                yield (node.lineno,
                       f"raw {dotted}.psum in serving/ outside tp.py — "
                       f"route the reduction through serving/tp.py "
                       f"(quantized_psum, or a tp_axis model psum) so "
                       f"the collective is declared in the step's "
                       f"CollectiveBudget and counted by hlocheck's "
                       f"byte/overlap census; an undeclared collective "
                       f"lands over budget at the first debug_checks "
                       f"audit and hides unbudgeted mesh traffic until "
                       f"then.")


_PT016_SANCTIONED = ("engine.py", "channel.py")
_PT016_SEEDED_CTORS = ("RandomState", "default_rng", "Generator", "Random",
                      "PRNGKey", "key")


def _pt016(tree, path):
    """Determinism fence: nondeterminism sources in serving/ outside the
    clock- and channel-sanctioned modules. Gated on the filename (like
    PT013/PT014/PT015): serving/engine.py OWNS the pluggable clock
    (``self._clock = clock or time.monotonic`` is the one sanctioned
    wall-clock binding) and serving/channel.py owns the seeded lossy-
    channel RNG. Everything else in serving/ must be replayable from
    (config, seed, trace) alone — the discipline ``chaos_soak``'s
    >=5-seed matrix and ``SimChannel``'s deterministic loss schedule
    depend on. Flags:

    - ``time.monotonic`` (attribute use or from-import — ``time.time``
      is already PT004's arm of the same fence; together they close the
      wall clock),
    - the process-global RNGs: any ``random.*`` call, any
      ``np.random.*`` / ``numpy.random.*`` call that is not a SEEDED
      constructor (``RandomState(seed)`` / ``default_rng(seed)`` /
      ``Random(seed)`` with an explicit argument),
    - ``id()``-keyed ordering: ``key=id`` in a sort/min/max call or an
      ``id(x)`` subscript key — iteration order then depends on
      allocator addresses, which no seed replays."""
    if Path(path).name in _PT016_SANCTIONED:
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "monotonic" \
                and isinstance(node.value, ast.Name) \
                and node.value.id in ("time", "_time"):
            yield (node.lineno,
                   "time.monotonic in serving/ outside engine.py — the "
                   "engine clock is pluggable (ServingConfig(clock=)); a "
                   "raw monotonic read is wall time no seed replays and "
                   "no virtual clock can skew. Take the engine's clock "
                   "(engine.now() / the injected clock callable) "
                   "instead.")
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name in ("monotonic", "time"):
                    yield (node.lineno,
                           f"`from time import {a.name}` in serving/ "
                           f"outside engine.py — binds the wall clock "
                           f"directly; route timing through the "
                           f"pluggable engine clock so replay and the "
                           f"slow_step fault skew keep working.")
        elif isinstance(node, ast.ImportFrom) and node.module == "random":
            for a in node.names:
                if a.name not in ("Random", "SystemRandom"):
                    yield (node.lineno,
                           f"`from random import {a.name}` in serving/ "
                           f"— the process-global RNG is shared mutable "
                           f"state no (config, seed) pair replays. Use "
                           f"a seeded random.Random(seed) / "
                           f"np.random.RandomState(seed) instance owned "
                           f"by the component.")
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                    and f.value.id == "random":
                if not (f.attr in ("Random", "SystemRandom") and node.args):
                    yield (node.lineno,
                           f"random.{f.attr}(...) in serving/ — the "
                           f"global RNG's state is shared across every "
                           f"module and call order; chaos_soak's seed "
                           f"matrix and SimChannel replay need a seeded "
                           f"per-component random.Random(seed) / "
                           f"RandomState(seed) instead.")
            elif isinstance(f, ast.Attribute) \
                    and isinstance(f.value, ast.Attribute) \
                    and f.value.attr == "random" \
                    and isinstance(f.value.value, ast.Name) \
                    and f.value.value.id in ("np", "numpy"):
                if not (f.attr in _PT016_SEEDED_CTORS and node.args):
                    yield (node.lineno,
                           f"np.random.{f.attr}(...) in serving/ — "
                           f"global numpy RNG (or an unseeded "
                           f"constructor): not replayable from (config, "
                           f"seed). Construct "
                           f"np.random.RandomState(seed) / "
                           f"default_rng(seed) with an explicit seed "
                           f"and own it on the component.")
            for kw in node.keywords:
                if kw.arg == "key" and isinstance(kw.value, ast.Name) \
                        and kw.value.id == "id":
                    yield (node.lineno,
                           "key=id ordering in serving/ — sorts by "
                           "allocator address, which differs run to run "
                           "under identical (config, seed, trace). Key "
                           "on a stable field (rid, arrival index) "
                           "instead.")
        elif isinstance(node, ast.Subscript):
            sl = node.slice
            if isinstance(sl, ast.Call) and isinstance(sl.func, ast.Name) \
                    and sl.func.id == "id":
                yield (node.lineno,
                       "id()-keyed table in serving/ — the key is an "
                       "allocator address: dict iteration order (and "
                       "anything derived from it) stops being "
                       "replayable. Key on a stable identity (rid, "
                       "sequence number) instead.")


def _pt017(tree, path):
    """Contextless wire exchange: a ``.exchange(...)`` call in serving/
    that omits the ``rid=`` or ``step=`` keyword. Those two keywords are
    what ties an exchange to a request journey and an engine step — an
    exchange without them produces a span/journey hop nothing can join
    against (rid) or order (step), which is exactly the blind spot
    fleetscope exists to close. Calls that deliberately carry no
    request (gossip) must say so with an explicit ``rid=None``; a
    ``**kwargs`` splat is assumed to forward the context."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "exchange"):
            continue
        kws = {kw.arg for kw in node.keywords}
        if None in kws:  # **splat forwards the caller's context
            continue
        missing = [k for k in ("rid", "step") if k not in kws]
        if missing:
            yield (node.lineno,
                   f".exchange(...) without {'/'.join(missing)}= — the "
                   f"exchange is invisible to fleetscope: no rid to "
                   f"join the span to a journey, no step to order it "
                   f"on the fleet timeline. Pass rid= (rid=None if the "
                   f"exchange genuinely carries no request, e.g. "
                   f"gossip) and step=.")


@dataclass(frozen=True)
class Rule:
    code: str
    doc: str
    check: object  # generator fn(tree, path) -> (line, message)
    scope: str | None = None  # path part required for the rule to fire


RULES: dict[str, Rule] = {r.code: r for r in (
    Rule("PT001", "dataclass with ndarray/Array field missing eq=False",
         _pt001),
    Rule("PT002", "per-layer host .at[].set loop over a stacked pool",
         _pt002, scope="serving"),
    Rule("PT003", "metric counter incremented without pre-seeding", _pt003),
    Rule("PT004", "time.time() in serving/ instead of the engine clock",
         _pt004, scope="serving"),
    Rule("PT005", "host-sync call inside a step()/decode hot path", _pt005,
         scope="serving"),
    Rule("PT006", "jit of pool-sized args without donate_argnums", _pt006,
         scope="serving"),
    Rule("PT007", "mutable default argument", _pt007),
    Rule("PT008", "metric gauge written (stat_set/stat_max) without "
         "pre-seeding", _pt008),
    Rule("PT009", "raw jax.jit in serving/ not routed through a "
         "CompileGuard", _pt009, scope="serving"),
    Rule("PT010", "shard_map in serving/ whose wrapped step is not "
         "registered with a CollectiveBudget in the hlocheck registry",
         _pt010, scope="serving"),
    Rule("PT011", "pallas_call in a module with no registered "
         "kernelcheck certificate (KERNELCHECK_CERTS)", _pt011),
    Rule("PT012", "labeled stat family (base{label=}, incl. multi-label "
         "base{a=,b=}) written without a _FAMILIES declaration, or with "
         "label keys disagreeing with it — the PT003/PT008 gap for "
         "formatted names", _pt012),
    Rule("PT013", "direct ServingEngine.add_request in serving/fleet* "
         "bypassing the router's weighted admission path", _pt013,
         scope="serving"),
    Rule("PT014", "raw pickle/socket/struct in serving/ outside "
         "wire.py — replica-boundary bytes must go through the "
         "versioned wire codec", _pt014, scope="serving"),
    Rule("PT015", "raw lax.psum / jax.lax.psum (attribute or "
         "from-import, incl. aliases) in serving/ outside tp.py — the "
         "budgeted/quantized psum wrappers are the single collective "
         "entry point", _pt015, scope="serving"),
    Rule("PT016", "determinism fence: time.monotonic / global or "
         "unseeded random / id()-keyed ordering in serving/ outside the "
         "clock-sanctioned engine.py and RNG-sanctioned channel.py — "
         "with PT004 (time.time) this closes every nondeterminism "
         "source deterministic replay depends on", _pt016,
         scope="serving"),
    Rule("PT017", "wire .exchange(...) in serving/ without rid=/step= "
         "keywords — the exchange's span/journey hop cannot be joined "
         "to a request or ordered on the fleet timeline (rid=None is "
         "the explicit no-request spelling)", _pt017, scope="serving"),
)}


# ------------------------------------------------------------------ driver
def _pragmas(source: str) -> dict[int, set[str]]:
    out: dict[int, set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _PRAGMA.search(line)
        if m:
            out[i] = {c.strip() for c in m.group(1).split(",") if c.strip()}
    return out


def lint_source(source: str, path: str, rules=None,
                allowlist=None) -> list[Finding]:
    """Lint one module's source. ``path`` scopes path-restricted rules (a
    fixture can be linted "as if" it lived under serving/)."""
    allowlist = ALLOWLIST if allowlist is None else allowlist
    parts = Path(path).parts
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("PT000", path, e.lineno or 0,
                        f"syntax error: {e.msg}")]
    pragmas = _pragmas(source)
    exempt = set().union(*(codes for sub, codes in allowlist.items()
                           if sub in path), set())
    findings = []
    for rule in RULES.values():
        if rules is not None and rule.code not in rules:
            continue
        if rule.scope is not None and rule.scope not in parts:
            continue
        if rule.code in exempt:
            continue
        for line, msg in rule.check(tree, path):
            if rule.code in pragmas.get(line, ()):
                continue
            findings.append(Finding(rule.code, path, line, msg))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def lint_paths(paths, rules=None, path_filter: str | None = None,
               allowlist=None) -> list[Finding]:
    """Lint every ``.py`` file under the given files/directories."""
    files: list[Path] = []
    for p in paths:
        p = Path(p)
        files.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
    findings = []
    for f in files:
        rel = f.as_posix()
        if path_filter is not None and path_filter not in rel:
            continue
        findings.extend(lint_source(f.read_text(), rel, rules=rules,
                                    allowlist=allowlist))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="Repo linter: invariants this repo shipped bugs "
                    "against, enforced (rules PT001-PT017).")
    parser.add_argument("paths", nargs="*",
                        help="files or directories (default: the installed "
                             "paddle_tpu package plus the repo's --include "
                             "trees)")
    parser.add_argument("--include", action="append", default=None,
                        metavar="DIR",
                        help="repo-root-relative trees swept in addition "
                             "to the package when no paths are given "
                             "(default: tests, examples; missing trees "
                             "are skipped)")
    parser.add_argument("--rule", action="append", default=None,
                        metavar="PTxxx", help="run only these rules "
                        "(repeatable / comma-separated)")
    parser.add_argument("--path", default=None, metavar="SUBSTR",
                        help="lint only files whose path contains SUBSTR")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule table and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        for r in RULES.values():
            scope = f" [scope: {r.scope}/]" if r.scope else ""
            print(f"{r.code}  {r.doc}{scope}")
        return 0
    rules = None
    if args.rule:
        rules = {c.strip() for spec in args.rule for c in spec.split(",")}
        unknown = rules - set(RULES)
        if unknown:
            print(f"unknown rule(s): {', '.join(sorted(unknown))} "
                  f"(have: {', '.join(RULES)})")
            return 2
    paths = args.paths
    if not paths:
        # default sweep: the package itself + the repo's test/example
        # trees (the satellites where a serving contract regression can
        # hide just as well; intentional fixture findings are exempted
        # via ALLOWLIST, so the sweep pins zero NON-fixture findings)
        pkg = Path(__file__).resolve().parent.parent
        include = args.include if args.include is not None \
            else ["tests", "examples"]
        paths = [pkg] + [p for d in include
                         if (p := pkg.parent / d).is_dir()]
    findings = lint_paths(paths, rules=rules, path_filter=args.path)
    for f in findings:
        print(f)
    n = len(findings)
    print(f"{n} finding(s)" if n else "clean: 0 findings")
    return 1 if findings else 0
