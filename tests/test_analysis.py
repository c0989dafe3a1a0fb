"""paddle_tpu.analysis — trace-time auditor + repo linter.

Three layers of coverage:

- tracecheck golden tests: the retrace explainer must name the RIGHT
  argument (and axis/dtype/static value) when a signature changes; budget
  and donation violations raise; SyncTally counts exactly the host-sync
  events and nothing else.
- serving integration: the engine's pinned ``compile_counts`` surface now
  reads off CompileGuard unchanged; ``debug_checks=True`` turns an
  unexpected decode retrace into a RetraceError naming the argument and
  runs the cache invariant sweep each step.
- lint: one fixture per rule (positive + pragma-suppressed), the repo
  self-lint at ZERO findings (the tier-1 enforcement of every fix this PR
  made), and reintroduction tests proving the linter would catch the PR 2
  ``eq`` bug and a ``time.time()`` in serving again.
"""
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import (RULES, CompileGuard, DonationViolation,
                                 RetraceError, SyncTally, SyncViolation,
                                 donation_audit, lint_paths, lint_source)
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

pytestmark = pytest.mark.analysis

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = pathlib.Path(__file__).resolve().parent / "lint_fixtures"


# ----------------------------------------------------------- CompileGuard
def test_guard_counts_traces_not_calls():
    g = CompileGuard(lambda x: x * 2, "double", budget=2)
    for _ in range(3):
        g(jnp.zeros((4,)))
    g(jnp.zeros((8,)))
    assert g.calls == 4 and g.traces == 2 and g.retraces == 0
    assert len(g.signatures) == 2


def test_guard_gives_a_programs_first_call_a_stack_chunk_of_its_own():
    """CPython keeps frames in 16 KiB chunks and maps / unmaps one at
    every call that crosses a boundary: a loop that straddles one pays a
    system call pair a turn (found on the chip's host, where the caller's
    frame sizes decided how long a program takes to trace). A guard's
    first call of a program runs below a frame big enough to get a chunk
    to itself; later calls go straight through."""
    from paddle_tpu.analysis import tracecheck

    own = tracecheck._on_a_chunk_of_its_own
    assert own.__code__.co_stacksize > 2 ** 16
    assert own(lambda a, b=1: (a, b), (2,), {"b": 3}) == (2, 3)
    with pytest.raises(ZeroDivisionError):
        own(lambda: 1 / 0, (), {})

    calls = []
    real = tracecheck._on_a_chunk_of_its_own
    g = CompileGuard(lambda x: x * 2, "double", group_by=lambda x: x.shape[0])
    try:
        tracecheck._on_a_chunk_of_its_own = \
            lambda f, a, k: (calls.append(a[0].shape), real(f, a, k))[1]
        for n in (4, 4, 8, 4):
            g(jnp.zeros((n,)))
    finally:
        tracecheck._on_a_chunk_of_its_own = real
    assert calls == [(4,), (8,)] and g.traces == 2


def test_guard_budget_counts_overage_when_not_strict():
    g = CompileGuard(lambda x: x + 1, "inc", budget=1)
    g(jnp.zeros((2,)))
    g(jnp.zeros((3,)))  # over budget but unstrict: counted, not raised
    assert g.traces == 2 and g.retraces == 1


def test_retrace_explainer_names_argument_and_axis():
    g = CompileGuard(lambda lhs, rhs: lhs @ rhs, "mm", budget=1, strict=True)
    g(jnp.zeros((4, 8)), jnp.zeros((8, 2)))
    with pytest.raises(RetraceError) as ei:
        g(jnp.zeros((4, 16)), jnp.zeros((16, 2)))
    msg = str(ei.value)
    assert "'mm'" in msg and "budget of 1" in msg
    assert "lhs" in msg and "rhs" in msg
    assert "axis 1: 8 -> 16" in msg  # lhs changed on axis 1
    assert "axis 0: 8 -> 16" in msg  # rhs changed on axis 0
    # strict mode refuses BEFORE paying the recompile
    assert g.traces == 1 and g.retraces == 1


def test_retrace_explainer_names_dtype_change():
    g = CompileGuard(lambda ctx, tok: ctx + tok, "step", budget=1,
                     strict=True)
    g(jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32))
    with pytest.raises(RetraceError) as ei:
        g(jnp.zeros((4,), jnp.float32), jnp.zeros((4,), jnp.int32))
    msg = str(ei.value)
    assert "ctx" in msg and "dtype int32 -> float32" in msg
    assert "tok:" not in msg  # the unchanged argument is not blamed


def test_retrace_explainer_names_static_value():
    g = CompileGuard(lambda x, width: x[:width], "slice", budget=1,
                     strict=True, static_argnums=(1,))
    g(jnp.arange(8), 4)
    g(jnp.arange(8), 4)  # same static value: cache hit
    with pytest.raises(RetraceError) as ei:
        g(jnp.arange(8), 6)
    assert "width" in str(ei.value)
    assert "static value 4 -> 6" in str(ei.value)


def test_retrace_explainer_pytree_structure_change():
    g = CompileGuard(lambda pools: [p * 2 for p in pools], "pools",
                     budget=1, strict=True)
    g([jnp.zeros(2)])
    with pytest.raises(RetraceError) as ei:
        g([jnp.zeros(2), jnp.zeros(2)])
    assert "pytree structure changed" in str(ei.value)


def test_strict_retry_of_refused_signature_counts_one_retrace():
    # retraces counts retrace EVENTS, matching non-strict accounting: a
    # caller looping on the same refused signature is one event, N raises
    g = CompileGuard(lambda x: x + 1, "inc", budget=1, strict=True)
    g(jnp.zeros((2,)))
    for _ in range(3):
        with pytest.raises(RetraceError):
            g(jnp.zeros((5,)))
    assert g.traces == 1 and g.retraces == 1
    with pytest.raises(RetraceError):
        g(jnp.zeros((7,)))  # a DIFFERENT bad signature is a second event
    assert g.retraces == 2


def test_group_budget_catches_same_group_retrace_despite_headroom():
    # the prefill shape: aggregate budget 4 (buckets), but bucket (8,) must
    # compile ONCE — a dtype drift re-tracing it is refused even though
    # the aggregate budget has room for 3 more traces
    g = CompileGuard(lambda ids: ids * 2, "prefill", budget=4, strict=True,
                     group_by=lambda ids: tuple(ids.shape))
    g(jnp.zeros((8,), jnp.int32))
    g(jnp.zeros((16,), jnp.int32))  # a new bucket: allowed
    with pytest.raises(RetraceError) as ei:
        g(jnp.zeros((8,), jnp.float32))  # same bucket, drifted dtype
    msg = str(ei.value)
    assert "group (8,)" in msg and "dtype int32 -> float32" in msg
    assert g.traces == 2 and g.retraces == 1


def test_sync_tally_keeps_keyword_numpy_calls_working():
    with SyncTally() as t:
        out = np.asarray(a=jnp.arange(3))  # operand by keyword
        np.asarray(np.ones(2), dtype=np.float32)
    assert out.tolist() == [0, 1, 2] and t.count == 1


def test_guard_use_after_donation_raises():
    g = CompileGuard(lambda pool, i: pool.at[i].set(0.0), "scatter",
                     donate_argnums=(0,), strict=True)
    pool = jnp.ones((4, 2))
    new_pool = g(pool, jnp.asarray(1))
    with pytest.raises(DonationViolation) as ei:
        g(pool, jnp.asarray(2))  # consumed buffer referenced again
    assert "pool" in str(ei.value) and "donated" in str(ei.value)
    g(new_pool, jnp.asarray(2))  # the returned array is the live one


def test_guard_double_donation_raises():
    g = CompileGuard(lambda a, b: (a.at[0].set(1.0), b.at[0].set(2.0)),
                     "dd", donate_argnums=(0, 1), strict=True)
    x = jnp.ones((3,))
    with pytest.raises(DonationViolation) as ei:
        g(x, x)
    assert "double donation" in str(ei.value)


def test_donation_audit_reports_unused_donated_leaf():
    reports = donation_audit(lambda pool, dead: pool * 2, (0, 1),
                             jnp.ones(3), jnp.ones(4))
    assert len(reports) == 1 and "dead" in reports[0] \
        and "never consumed" in reports[0]
    assert donation_audit(lambda pool: pool * 2, (0,), jnp.ones(3)) == []


# -------------------------------------------------------------- SyncTally
def test_sync_tally_counts_sync_events_only():
    with SyncTally() as t:
        arr = jnp.arange(4)
        jnp.sum(arr)            # device compute: not a sync
        np.asarray(np.ones(2))  # host->host: not a sync
        np.asarray(arr)         # sync
        int(arr[0])             # sync
        arr[1].item()           # sync
        jax.device_get(arr)     # sync
    assert t.count == 4
    assert t.events == ["np.asarray", "int", "item", "device_get"]
    # patches removed on exit: no counting outside the region
    before = t.count
    np.asarray(jnp.zeros(2))
    assert t.count == before


def test_sync_tally_counts_tolist_and_iteration():
    """The PR 6 blind-spot fix: ``.tolist()`` is a full-array host
    materialization and iterating a device array (``for``/``list()``,
    including the __len__/__getitem__ sequence-protocol path) drives a
    per-element dispatch loop from the host — both must count. Per-element
    coercions inside a loop still count on top of the iteration event."""
    with SyncTally() as t:
        arr = jnp.arange(3)
        arr.tolist()                    # sync: full materialization
        for _ in arr:                   # sync: one event per loop
            pass
        total = sum(int(x) for x in arr)  # iter + 3 int coercions
    assert total == 3
    assert t.events == ["tolist", "iter", "iter", "int", "int", "int"], \
        t.events
    # patches removed on exit
    before = t.count
    jnp.zeros(2).tolist()
    assert t.count == before


def test_sync_tally_paused_suppresses_counting():
    """hlocheck AOT-lowers steps inside debug_checks step tallies;
    lowering materializes traced constants host-side — compile-time work
    the certification must not count. Nested pauses restore correctly."""
    from paddle_tpu.analysis import sync_tally_paused

    with SyncTally() as t:
        with sync_tally_paused():
            np.asarray(jnp.zeros(2))
            jnp.zeros(2).tolist()
        np.asarray(jnp.zeros(2))  # counting resumes after the pause
    assert t.count == 1 and t.events == ["np.asarray"]


def test_sync_tally_nests_and_enforces_allowance():
    with SyncTally() as outer:
        with SyncTally() as inner:
            np.asarray(jnp.zeros(2))
        np.asarray(jnp.zeros(2))
    assert inner.count == 1 and outer.count == 2
    with pytest.raises(SyncViolation) as ei:
        with SyncTally(allowed=1, name="decode"):
            np.asarray(jnp.zeros(2))
            np.asarray(jnp.zeros(2))
    assert "decode" in str(ei.value) and "allows 1" in str(ei.value)


# ------------------------------------------------------ serving integration
def _toy_engine(**overrides):
    paddle.seed(23)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=48, dropout=0.0))
    model.eval()
    kw = dict(max_batch=2, num_pages=20, page_size=4, max_prompt_len=8,
              debug_checks=True)
    kw.update(overrides)
    return ServingEngine(model, ServingConfig(**kw))


@pytest.mark.slow  # re-tiered 2026-08 (PR 8): tier-1 budget; the read-through property is exercised
# by every compile_counts pin across test_serving*/test_serving_tp and the demo
def test_engine_compile_counts_surface_reads_off_guards():
    engine = _toy_engine()
    rng = np.random.RandomState(0)
    for n, b in ((3, 4), (6, 3)):
        engine.add_request(rng.randint(0, 97, (n,)).astype(np.int32), b)
    engine.run()
    # the exact dict-shaped pin PR 1-3 rely on, now a CompileGuard view
    assert engine.compile_counts == {"prefill": 1, "decode": 1}
    assert engine.compile_counts["decode"] == \
        engine.guards["decode"].traces
    assert dict(engine.cache.compile_counts) == \
        {"swap_gather": 0, "swap_scatter": 0, "cow_copy": 0}


def test_engine_debug_checks_retrace_raises_naming_argument():
    engine = _toy_engine()
    rng = np.random.RandomState(1)
    engine.add_request(rng.randint(0, 97, (4,)).astype(np.int32), 3)
    engine.run()  # compiles prefill + decode once, audits clean
    # an unexpected decode retrace: ctx at the wrong width. The guard must
    # refuse it (budget 1 already spent) and blame exactly 'ctx'.
    b = engine.config.max_batch
    with pytest.raises(RetraceError) as ei:
        engine._decode_jit(
            engine._p, engine.cache.pools,
            jnp.asarray(engine.cache.page_table),
            jnp.zeros((b + 1,), jnp.int32),  # <- ctx grew an element
            *engine._decode_args()[4:])
    msg = str(ei.value)
    assert "'decode'" in msg and "ctx" in msg
    assert f"axis 0: {b} -> {b + 1}" in msg
    assert engine.compile_counts == {"prefill": 1, "decode": 1}


def test_engine_debug_checks_serves_correctly_and_counts_syncs():
    # debug_checks must not change behavior: outputs still match the
    # reference loop, invariants sweep clean, and the analysis metrics
    # report the per-step token fetches as the only host syncs
    engine = _toy_engine()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 97, (n,)).astype(np.int32) for n in (3, 5)]
    rids = [engine.add_request(p, 4) for p in prompts]
    outs = engine.run()
    from paddle_tpu.core.tensor import Tensor
    for rid, p in zip(rids, prompts):
        ref = np.asarray(engine.model.generate(
            Tensor(p[None]), max_new_tokens=4)._value)[0]
        np.testing.assert_array_equal(ref, outs[rid])
    snap = engine.metrics.snapshot()
    assert snap["serving_analysis_retraces_total"] == 0
    # every decode step fetches its token batch (1 sync), every prefill
    # fetches its first token (1 sync) — and NOTHING else syncs
    expected = snap["serving_decode_steps"] + snap["serving_prefills_total"]
    assert snap["serving_analysis_host_syncs_total"] == expected


def test_debug_checks_runs_donation_audit_at_first_trace():
    """PR 5 satellite: debug_checks audits each jitted step at jaxpr
    level before its FIRST trace — the engine's donated pools must all be
    consumed by the computation (a donated-but-unused buffer is a wrong
    donate_argnums). Clean audits are recorded per step name."""
    engine = _toy_engine()
    assert engine._donation_audits == {}  # nothing traced yet
    rng = np.random.RandomState(3)
    engine.add_request(rng.randint(0, 97, (4,)).astype(np.int32), 3)
    engine.run()
    assert set(engine._donation_audits) == {"prefill", "decode"}
    # the engine's donation is clean: no dead donated leaves survived to
    # raise, and no identity pass-through reports were recorded either
    assert engine._donation_audits == {"prefill": [], "decode": []}


def test_donation_audit_helper_raises_on_dead_donated_leaf():
    # the audit reads the impl and donate_argnums OFF THE GUARD, so it
    # can never desynchronize from what the jit actually donates
    engine = _toy_engine()
    bad = CompileGuard(lambda pool, dead: pool * 2, "bad_step",
                       donate_argnums=(0, 1))
    with pytest.raises(DonationViolation) as ei:
        engine._audit_donation(bad, (jnp.ones(3), jnp.ones(4)))
    msg = str(ei.value)
    assert "bad_step" in msg and "dead" in msg and "never consumed" in msg
    assert "bad_step" not in engine._donation_audits  # fatal, not recorded


def test_debug_checks_off_skips_donation_audit():
    engine = _toy_engine(debug_checks=False)
    rng = np.random.RandomState(4)
    engine.add_request(rng.randint(0, 97, (4,)).astype(np.int32), 3)
    engine.run()
    assert engine._donation_audits == {}


def test_analysis_counters_pre_seeded():
    engine = _toy_engine(debug_checks=False)
    snap = engine.metrics.snapshot()
    assert snap["serving_analysis_retraces_total"] == 0
    assert snap["serving_analysis_host_syncs_total"] == 0
    # the PT003 backfill: every counter is visible before its first event
    for k in ("tokens_total", "prefills_total", "prefill_tokens_total",
              "decode_steps", "preemptions_total"):
        assert snap["serving_" + k] == 0, k


# ------------------------------------------------------------------- lint
# fixture file -> (path the rule scope sees, {line: rule} expected)
_FIXTURE_CASES = {
    "pt001_dataclass_eq.py": ("pt001.py", {7: "PT001"}),
    "pt002_pool_loop.py": ("serving/pt002.py", {5: "PT002"}),
    "pt003_unseeded_counter.py": ("pt003.py", {18: "PT003", 21: "PT003"}),
    "pt004_wall_clock.py": ("serving/pt004.py", {6: "PT004"}),
    "pt005_hot_sync.py": ("serving/pt005.py",
                          {8: "PT005", 9: "PT005", 10: "PT005"}),
    "pt006_jit_no_donate.py": ("serving/pt006.py", {23: "PT006"}),
    "pt007_mutable_default.py": ("pt007.py", {4: "PT007", 14: "PT007"}),
    "pt008_unseeded_gauge.py": ("pt008.py",
                                {16: "PT008", 17: "PT008", 18: "PT008"}),
    "pt009_raw_jit.py": ("serving/pt009.py",
                         {13: "PT009", 15: "PT009", 18: "PT009",
                          25: "PT009", 29: "PT009"}),
    "pt010_shard_map.py": ("serving/pt010.py",
                           {6: "PT010", 7: "PT010", 13: "PT010"}),
    "pt011_uncertified_pallas.py": ("kernels/pt011.py",
                                    {7: "PT011", 11: "PT011"}),
    "pt012_unregistered_family.py": ("pt012.py",
                                     {14: "PT012", 19: "PT012",
                                      24: "PT012", 44: "PT012",
                                      55: "PT012", 61: "PT012"}),
    "pt013_direct_add_request.py": ("serving/fleet_rogue.py",
                                    {9: "PT013"}),
    "pt014_raw_wire.py": ("serving/sidechannel.py",
                          {5: "PT014", 6: "PT014", 7: "PT014",
                           8: "PT014", 12: "PT014", 16: "PT014",
                           20: "PT014"}),
    "pt015_raw_psum.py": ("serving/rogue_collective.py",
                          {6: "PT015", 7: "PT015",
                           11: "PT015", 12: "PT015"}),
    "pt016_wallclock.py": ("serving/pt016.py",
                           {13: "PT016", 18: "PT016", 22: "PT016",
                            23: "PT016", 29: "PT016"}),
    "pt017_contextless_exchange.py": ("serving/pt017.py",
                                      {9: "PT017", 14: "PT017",
                                       19: "PT017"}),
}


@pytest.mark.parametrize("fixture", sorted(_FIXTURE_CASES))
def test_lint_rule_fixture(fixture):
    """Each rule: the positive cases fire at the expected lines, the
    pragma-suppressed twin of the same defect stays quiet, clean code
    stays quiet."""
    as_path, expected = _FIXTURE_CASES[fixture]
    src = (FIXTURES / fixture).read_text()
    findings = lint_source(src, as_path)
    assert {(f.line, f.rule) for f in findings} == set(expected.items()), \
        [str(f) for f in findings]
    assert "lint: disable" not in "".join(
        src.splitlines()[f.line - 1] for f in findings)


def test_lint_rule_table_is_complete():
    assert sorted(RULES) == [f"PT00{i}" for i in range(1, 10)] + [
        "PT010", "PT011", "PT012", "PT013", "PT014", "PT015", "PT016",
        "PT017"]
    for code, rule in RULES.items():
        assert rule.doc and rule.code == code


def test_serving_scoped_rules_do_not_fire_outside_serving():
    src = (FIXTURES / "pt004_wall_clock.py").read_text()
    assert lint_source(src, "io/dataloader_helper.py") == []


def test_allowlist_exempts_matching_paths():
    src = (FIXTURES / "pt004_wall_clock.py").read_text()
    assert lint_source(src, "serving/legacy.py",
                       allowlist={"legacy": {"PT004"}}) == []
    assert lint_source(src, "serving/fresh.py",
                       allowlist={"legacy": {"PT004"}}) != []


def test_repo_self_lint_zero_findings():
    """The tier-1 enforcement: every invariant the linter encodes holds
    over paddle_tpu/ itself. A regression in any fixed violation (the
    SwapHandle eq, the unseeded counters, a stray sync in step()) fails
    here, forever."""
    findings = lint_paths([REPO / "paddle_tpu"])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_tests_and_examples_lint_zero_nonfixture_findings():
    """The PR 5 widening: the default sweep also covers tests/ and
    examples/ — a serving contract regression (mutable default, unseeded
    stat, array-field dataclass) hides in a test helper as easily as in
    the package. The lint fixtures' INTENTIONAL positives are exempted
    via the ALLOWLIST (a pragma inside a fixture would defeat the
    fixture), so the pin is zero NON-fixture findings."""
    findings = lint_paths([REPO / "tests", REPO / "examples"])
    assert findings == [], "\n".join(str(f) for f in findings)
    # the allowlist is doing real work: without it the fixtures DO fire
    fixture_findings = lint_paths([REPO / "tests" / "lint_fixtures"],
                                  allowlist={})
    assert fixture_findings, "fixture positives vanished — dead fixtures"


def test_self_lint_catches_reintroduced_unseeded_gauge():
    """Deliberately strip a gauge from metrics._SEEDED: PT008 must fail
    the way PT003 would for a counter."""
    path = REPO / "paddle_tpu" / "serving" / "metrics.py"
    src = path.read_text()
    bad = src.replace('"queue_depth_peak", "page_pool_peak")',
                      '"queue_depth_peak",)')
    assert bad != src, "metrics.py no longer seeds the peak gauges"
    findings = lint_source(bad, "paddle_tpu/serving/metrics.py")
    assert any(f.rule == "PT008" and "page_pool_peak" in f.message
               for f in findings)


def test_self_lint_catches_reintroduced_pr2_eq_bug():
    """Deliberately strip SwapHandle's eq=False: the linter must fail the
    way it would have failed PR 2's review."""
    path = REPO / "paddle_tpu" / "serving" / "kv_cache.py"
    src = path.read_text()
    bad = src.replace("@dataclass(eq=False)  # ndarray fields: identity "
                      "semantics (lint rule PT001)", "@dataclass")
    assert bad != src, "kv_cache.py no longer carries the PT001 fix marker"
    findings = lint_source(bad, "paddle_tpu/serving/kv_cache.py")
    assert any(f.rule == "PT001" and "SwapHandle" in f.message
               for f in findings)


def test_self_lint_catches_reintroduced_raw_jit():
    """Deliberately route the engine's decode step through a raw jax.jit
    instead of its CompileGuard: PT009 must fire — an unregistered step is
    invisible to the compile budgets AND the hlocheck artifact audits."""
    path = REPO / "paddle_tpu" / "serving" / "engine.py"
    src = path.read_text()
    bad = src.replace("self._decode_jit = CompileGuard(",
                      "self._decode_jit = jax.jit(")
    assert bad != src, "engine.py no longer guards the decode step"
    findings = lint_source(bad, "paddle_tpu/serving/engine.py")
    assert any(f.rule == "PT009" and "CompileGuard" in f.message
               for f in findings)
    # the guarded original is clean: the guard IS the sanctioned route
    assert not any(f.rule == "PT009"
                   for f in lint_source(src, "paddle_tpu/serving/engine.py"))


def test_self_lint_catches_reintroduced_rogue_shard_map():
    """Deliberately give the engine its own shard_map import (the way a
    quick hack would shard a step without declaring its budget): PT010
    must fire — an unregistered sharded step can acquire implicit
    resharding collectives no hlocheck audit ever counts. The sanctioned
    serving/tp.py entry point (registered tp2_engine_* steps) stays
    clean under its pragma."""
    path = REPO / "paddle_tpu" / "serving" / "engine.py"
    src = path.read_text()
    bad = src.replace(
        "from ..analysis import hlocheck",
        "from ..analysis import hlocheck\n"
        "from jax.experimental.shard_map import shard_map")
    assert bad != src
    findings = lint_source(bad, "paddle_tpu/serving/engine.py")
    assert any(f.rule == "PT010" and "hlocheck registry" in f.message
               for f in findings)
    tp_src = (REPO / "paddle_tpu" / "serving" / "tp.py").read_text()
    assert "lint: disable=PT010" in tp_src
    assert not any(f.rule == "PT010"
                   for f in lint_source(tp_src,
                                        "paddle_tpu/serving/tp.py"))


def test_self_lint_catches_uncertified_pallas_kernel():
    """Deliberately strip fused_layernorm's KERNELCHECK_CERTS declaration:
    PT011 must fire on every pallas_call — an uncertified kernel ships
    with no VMEM budget, tiling lint, race proof, or roofline contract.
    The declared original stays clean."""
    path = REPO / "paddle_tpu" / "kernels" / "fused_layernorm.py"
    src = path.read_text()
    bad = "\n".join(line for line in src.splitlines()
                    if not line.startswith("KERNELCHECK_CERTS"))
    assert bad != src, "fused_layernorm.py no longer declares its certs"
    findings = lint_source(bad, "paddle_tpu/kernels/fused_layernorm.py")
    assert any(f.rule == "PT011" and "kernelcheck" in f.message
               for f in findings)
    assert not any(f.rule == "PT011" for f in lint_source(
        src, "paddle_tpu/kernels/fused_layernorm.py"))
    # the annotated declaration form sanctions the module just the same
    ann = src.replace("KERNELCHECK_CERTS = ",
                      "KERNELCHECK_CERTS: tuple = ")
    assert ann != src
    assert not any(f.rule == "PT011" for f in lint_source(
        ann, "paddle_tpu/kernels/fused_layernorm.py"))


def test_self_lint_catches_unregistered_stat_family():
    """Deliberately strip the alerts family from metrics._FAMILIES: PT012
    must fire at the on_alert stat_add — a formatted family name
    PT003/PT008 can't resolve would otherwise ship with no pre-seeded
    members. The declared original stays clean."""
    path = REPO / "paddle_tpu" / "serving" / "metrics.py"
    src = path.read_text()
    bad = "\n".join(line for line in src.splitlines()
                    if '"alerts_total": "rule",' not in line)
    assert bad != src, "metrics.py no longer declares the alerts family"
    findings = lint_source(bad, "paddle_tpu/serving/metrics.py")
    assert any(f.rule == "PT012" and "alerts_total" in f.message
               for f in findings)
    assert not any(f.rule in ("PT003", "PT008", "PT012")
                   for f in lint_source(
                       src, "paddle_tpu/serving/metrics.py"))


def test_self_lint_catches_unregistered_multilabel_family():
    """Deliberately strip the multi-label tenant_retired_total family
    from metrics._FAMILIES: PT012 must fire at the on_tenant_retire
    stat_add — the ``base{tenant=,class=}`` shape must not dodge the
    registry — and reordering the write's label keys against the
    declaration must fire the key-mismatch arm (keys are part of the
    registry key the seeding created)."""
    path = REPO / "paddle_tpu" / "serving" / "metrics.py"
    src = path.read_text()
    marker = '"tenant_retired_total": ("tenant", "class"),'
    bad = "\n".join(line for line in src.splitlines()
                    if marker not in line)
    assert bad != src, "metrics.py no longer declares the tenant grid"
    findings = lint_source(bad, "paddle_tpu/serving/metrics.py")
    assert any(f.rule == "PT012" and "tenant_retired_total" in f.message
               for f in findings)
    # a write whose label ORDER disagrees with the declaration fires too
    swapped = src.replace(
        "tenant_retired_total{{tenant={tenant},class={cls}}}",
        "tenant_retired_total{{class={cls},tenant={tenant}}}")
    assert swapped != src
    findings = lint_source(swapped, "paddle_tpu/serving/metrics.py")
    assert any(f.rule == "PT012" and "label keys" in f.message
               for f in findings)
    assert not any(f.rule == "PT012" for f in lint_source(
        src, "paddle_tpu/serving/metrics.py"))


def test_self_lint_catches_unsanctioned_fleet_dispatch():
    """Deliberately strip the pragma off the fleet router's one
    sanctioned add_request site: PT013 must fire — a fleet dispatch
    outside the weighted admission path is the bypass the rule exists
    to close. The pragma'd original stays clean, and the pragma must
    actually exist (a silently deleted site would pass vacuously)."""
    path = REPO / "paddle_tpu" / "serving" / "fleet.py"
    src = path.read_text()
    assert "# lint: disable=PT013" in src, \
        "fleet.py lost its sanctioned dispatch pragma"
    bad = src.replace("  # lint: disable=PT013", "")
    assert bad != src
    findings = lint_source(bad, "paddle_tpu/serving/fleet.py")
    assert any(f.rule == "PT013" and "admission" in f.message
               for f in findings)
    assert not any(f.rule == "PT013" for f in lint_source(
        src, "paddle_tpu/serving/fleet.py"))


def test_self_lint_pt014_gate_is_the_filename():
    """serving/wire.py is the ONE sanctioned struct user: the very same
    codec source linted under any other serving filename fires PT014 —
    the gate is the filename, so moving frame-packing bytes out of
    wire.py (a second codec, a 'quick' side channel) reintroduces the
    raw-struct finding. The real wire.py stays clean, and it genuinely
    exercises the gate (it must actually use struct)."""
    path = REPO / "paddle_tpu" / "serving" / "wire.py"
    src = path.read_text()
    assert "struct" in src, "wire.py no longer packs with struct?"
    assert lint_source(src, "paddle_tpu/serving/wire.py") == []
    findings = lint_source(src, "paddle_tpu/serving/wire2.py")
    assert any(f.rule == "PT014" for f in findings)


def test_self_lint_pt015_gate_is_the_filename():
    """serving/tp.py is the ONE sanctioned psum user: the very same
    module linted under any other serving filename fires PT015 — moving
    a collective out of tp.py (a 'quick' raw reduction beside the
    budgeted wrappers) reintroduces the unbudgeted-psum finding. The
    real tp.py stays clean, and it genuinely exercises the gate (it must
    actually call lax.psum — quantized_psum does)."""
    path = REPO / "paddle_tpu" / "serving" / "tp.py"
    src = path.read_text()
    assert "lax.psum" in src, "tp.py no longer reduces with lax.psum?"
    assert not any(f.rule == "PT015" for f in lint_source(
        src, "paddle_tpu/serving/tp.py"))
    findings = lint_source(src, "paddle_tpu/serving/tp_rogue.py")
    assert any(f.rule == "PT015" for f in findings)
    # and a raw psum pasted into any other serving module fires too —
    # the strip-reintroduction direction: engine.py grows a psum, PT015
    # catches it at the line
    eng = (REPO / "paddle_tpu" / "serving" / "engine.py").read_text()
    bad = eng + "\n\ndef _rogue(x):\n    import jax\n" \
                "    return jax.lax.psum(x, 'tp')\n"
    findings = lint_source(bad, "paddle_tpu/serving/engine.py")
    assert any(f.rule == "PT015" and "tp.py" in f.message
               for f in findings)
    assert not any(f.rule == "PT015" for f in lint_source(
        eng, "paddle_tpu/serving/engine.py"))


def test_self_lint_catches_reintroduced_wall_clock():
    path = REPO / "paddle_tpu" / "serving" / "engine.py"
    src = path.read_text()
    bad = src.replace("self._clock = clock or time.monotonic",
                      "self._clock = clock or (lambda: time.time())")
    assert bad != src
    findings = lint_source(bad, "paddle_tpu/serving/engine.py")
    assert any(f.rule == "PT004" for f in findings)


def test_self_lint_pt016_determinism_fence():
    """PT016's two strip-reintroduction directions. (1) chaos.py's RNG is
    sanctioned ONLY because it is seeded: stripping the seed argument
    from its RandomState fires. (2) the clock gate is the filename:
    engine.py's pluggable-clock default (`clock or time.monotonic`) is
    the one sanctioned wall-clock binding — the very same module linted
    under any other serving filename fires, so moving the clock binding
    out of engine.py reintroduces the finding."""
    chaos = (REPO / "paddle_tpu" / "serving" / "chaos.py").read_text()
    assert "np.random.RandomState(cfg.seed)" in chaos, \
        "chaos.py no longer seeds its RNG this way?"
    assert not any(f.rule == "PT016" for f in lint_source(
        chaos, "paddle_tpu/serving/chaos.py"))
    unseeded = chaos.replace("np.random.RandomState(cfg.seed)",
                             "np.random.RandomState()")
    findings = lint_source(unseeded, "paddle_tpu/serving/chaos.py")
    assert any(f.rule == "PT016" and "seed" in f.message
               for f in findings)

    eng = (REPO / "paddle_tpu" / "serving" / "engine.py").read_text()
    assert "clock or time.monotonic" in eng
    assert not any(f.rule == "PT016" for f in lint_source(
        eng, "paddle_tpu/serving/engine.py"))
    findings = lint_source(eng, "paddle_tpu/serving/scheduler.py")
    assert any(f.rule == "PT016" and "monotonic" in f.message
               for f in findings)


def test_self_lint_pt017_contextless_exchange():
    """PT017 strip-reintroduction: fleet.py's gossip exchange carries an
    EXPLICIT ``rid=None`` — that spelling is the sanctioning. Stripping
    it (the natural refactor slip: "gossip has no request, drop the
    keyword") reintroduces the finding on the very call the rule was
    written for."""
    fleet = (REPO / "paddle_tpu" / "serving" / "fleet.py").read_text()
    assert "step=self._step_idx, rid=None, span=sid" in fleet, \
        "fleet.py's gossip exchange no longer spells rid=None this way?"
    assert not any(f.rule == "PT017" for f in lint_source(
        fleet, "paddle_tpu/serving/fleet.py"))
    stripped = fleet.replace("step=self._step_idx, rid=None, span=sid",
                             "step=self._step_idx, span=sid")
    findings = lint_source(stripped, "paddle_tpu/serving/fleet.py")
    assert any(f.rule == "PT017" and "rid" in f.message
               for f in findings)


@pytest.mark.slow  # re-tiered 2026-08 (PR 8): tier-1 crossed its 870 s budget on the 1-core box; --durations top mover
def test_lint_cli_exit_codes_and_filters(tmp_path):
    clean = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "paddle_tpu/"],
        cwd=REPO, capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "0 findings" in clean.stdout

    bad = tmp_path / "serving" / "dirty.py"
    bad.parent.mkdir()
    bad.write_text("import time\n\n\ndef step(self, q=[]):\n"
                   "    return time.time()\n")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 1
    assert "PT004" in r.stdout and "PT007" in r.stdout

    only = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", str(tmp_path),
         "--rule", "PT007"],
        cwd=REPO, capture_output=True, text=True)
    assert only.returncode == 1
    assert "PT007" in only.stdout and "PT004" not in only.stdout

    r2 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", str(tmp_path),
         "--path", "nonexistent-substring"],
        cwd=REPO, capture_output=True, text=True)
    assert r2.returncode == 0

    unknown = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--rule", "PT999"],
        cwd=REPO, capture_output=True, text=True)
    assert unknown.returncode == 2


@pytest.mark.slow  # re-tiered 2026-08 (PR 8): tier-1 crossed its 870 s budget on the 1-core box; --durations top mover
def test_lint_cli_default_sweep_covers_tests_and_examples():
    """No-path invocation lints the package + tests/ + examples/ (clean
    because fixtures are allowlisted); --include overrides the extra
    trees."""
    clean = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis"],
        cwd=REPO, capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "0 findings" in clean.stdout

    # the default sweep actually REACHES tests/: a transient dirty helper
    # dropped there is found by the no-path invocation...
    probe = REPO / "tests" / "_lint_probe_tmp_do_not_commit.py"
    probe.write_text("def helper(q=[]):\n    return q\n")
    try:
        dirty = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis"],
            cwd=REPO, capture_output=True, text=True)
        assert dirty.returncode == 1 and "PT007" in dirty.stdout
        # ...and --include overrides the extra trees away again
        narrowed = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis",
             "--include", "examples"],
            cwd=REPO, capture_output=True, text=True)
        assert narrowed.returncode == 0, narrowed.stdout + narrowed.stderr
    finally:
        probe.unlink()


@pytest.mark.slow  # re-tiered 2026-08 (PR 8): tier-1 crossed its 870 s budget on the 1-core box; --durations top mover
def test_tools_lint_entry_point():
    r = subprocess.run([sys.executable, str(REPO / "tools" / "lint.py")],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 findings" in r.stdout
