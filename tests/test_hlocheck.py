"""paddle_tpu.analysis.hlocheck — the compiled-artifact auditor.

Four layers of coverage:

- parsing: byte volumes off HLO result types, census over real compiled
  text (collectives classified with payload bytes, host callbacks
  flagged, -done halves not double-counted).
- budgets: a declared CollectiveBudget passes, the zero (single-chip)
  budget raises NAMING the op; byte caps and host-transfer floors raise.
- aliasing: donated-and-consumed pools verified against XLA's
  input_output_alias table; an unaliasable donation raises naming the
  leaf (the compiled proof behind PT006).
- integration: the ACCEPTANCE GATES — engine prefill+decode pass under
  debug_checks (zero collectives, zero host transfers, all donations
  aliased, serving_hlo_* metrics live), and the toy 8-device shard_map
  step certifies against a budget of exactly one all-reduce while the
  over-budget variant raises (the registry + CLI share all of it).
"""
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import hlocheck
from paddle_tpu.analysis.hlocheck import (REGISTRY, SINGLE_CHIP,
                                          AliasingViolation,
                                          CollectiveBudget,
                                          CollectiveBudgetError,
                                          HostTransferError, audit, census,
                                          run_step)
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

pytestmark = pytest.mark.hlocheck

REPO = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------------------ parsing
def test_type_bytes_parser():
    tb = hlocheck._type_bytes
    assert tb("f32[4,8]{1,0}") == 128
    assert tb("bf16[2,2]{1,0}") == 8
    assert tb("(f32[4]{0}, bf16[2,2]{1,0})") == 24
    assert tb("f32[]") == 4       # scalar
    assert tb("s8[3]{0}") == 3
    assert tb("u32[2]{0}") == 8
    assert tb("pred[5]{0}") == 5
    # sub-byte dtypes pack: an int4 quantized collective (the EQuARX-style
    # payload these volumes baseline) is NOT charged a byte per element
    assert tb("s4[1024]{0}") == 512
    assert tb("u2[5]{0}") == 2    # ceil(10 bits / 8)


def test_census_classifies_and_skips_done_halves():
    text = """
  %all-reduce.1 = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %x), channel_id=1
  %ag-start = (f32[2]{0}, f32[16]{0}) all-gather-start(f32[2]{0} %y)
  %ag-done = f32[16]{0} all-gather-done((f32[2]{0}, f32[16]{0}) %ag-start)
  %arc = (f32[2]{0}, f32[4]{0}, f32[2]{0}, f32[4]{0}) all-reduce-start(f32[2]{0} %c0, f32[4]{0} %c1), channel_id=3
  %rs = f32[2]{0} reduce-scatter(f32[16]{0} %z), dimensions={0}
  %cp = f32[4]{0} collective-permute(f32[4]{0} %w)
  %cc = () custom-call(f32[] %v), custom_call_target="xla_python_cpu_callback"
  %mm = f32[4,4]{1,0} custom-call(f32[4,4]{1,0} %a), custom_call_target="__onednn$matmul"
  %send.1 = (f32[2]{0}, u32[], token[]) send(f32[2]{0} %s, token[] %t), channel_id=2, is_host_transfer=true
  %infeed.1 = (f32[3]{0}, token[]) infeed(token[] %t2)
"""
    colls, hosts = census(text)
    kinds = sorted(c.kind for c in colls)
    assert kinds == ["all-gather", "all-reduce", "all-reduce",
                     "collective-permute", "reduce-scatter"]
    ar = next(c for c in colls if c.instr == "all-reduce.1")
    assert ar.nbytes == 128
    # the -start counts once and charges only its RESULT buffer(s) (64 B
    # for the f32[16] gather, not the (operand, result) tuple's 72), so
    # byte caps hold whether XLA compiles the sync or async form; a
    # combiner-merged variadic -start charges its whole result half
    # (24 B = f32[2] + f32[4], not just the last element); -done never
    ag = next(c for c in colls if c.kind == "all-gather")
    assert ag.nbytes == 64
    arc = next(c for c in colls if c.instr == "arc")
    assert arc.nbytes == 24
    # host transfers: the python callback, the host send, the infeed —
    # NOT the oneDNN matmul custom-call
    assert sorted(h.kind for h in hosts) == ["custom-call", "infeed", "send"]
    cb = next(h for h in hosts if h.kind == "custom-call")
    assert cb.detail == "xla_python_cpu_callback"


# ---------------------------------------------------- budgets on real steps
@pytest.fixture(scope="module")
def tp8_report():
    """The toy tensor-parallel shard_map step, audited ONCE for the whole
    module (enforced against its own declared budget inside run_step)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the conftest 8-device CPU mesh")
    return run_step("tp8_decode")


def test_tp8_certifies_against_declared_budget(tp8_report):
    """THE acceptance gate for the sharded-serving arc: the Megatron-split
    step compiles to exactly its declared collective — one all-reduce of
    the [B, H] partials — and nothing else (no implicit resharding
    all-gathers, no host transfers)."""
    assert tp8_report.counts() == {"all-reduce": 1}
    assert tp8_report.collective_bytes == \
        hlocheck._TP8_BATCH * hlocheck._TP8_HIDDEN * 4
    assert tp8_report.host_transfers == ()
    assert tp8_report.flops > 0 and tp8_report.peak_bytes > 0
    # re-enforcing the declared budget is idempotent (pure over the report)
    tp8_report.enforce(CollectiveBudget(
        all_reduce=1,
        max_collective_bytes=tp8_report.collective_bytes))


def test_tp8_over_budget_raises_naming_the_op(tp8_report):
    """The over-budget variant: the SAME compiled step held to the
    single-chip (zero) budget must raise naming the op, its count, and
    its payload."""
    with pytest.raises(CollectiveBudgetError) as ei:
        tp8_report.enforce(SINGLE_CHIP)
    msg = str(ei.value)
    assert "all-reduce" in msg and "budget of 0" in msg
    assert "128 B" in msg            # the payload volume
    # the offending HLO instruction, by its name (the installed XLA names
    # instructions after the JAX op: `%psum.7 = f32[..] all-reduce(`)
    assert f"%{tp8_report.collectives[0].instr} = " in msg


def test_tp8_byte_cap_raises(tp8_report):
    with pytest.raises(CollectiveBudgetError) as ei:
        tp8_report.enforce(CollectiveBudget(all_reduce=1,
                                            max_collective_bytes=64))
    assert "exceeds the declared cap of 64" in str(ei.value)


def test_single_device_step_has_no_collectives():
    r = audit(lambda x, y: x @ y,
              (jnp.ones((4, 8), jnp.float32), jnp.ones((8, 2), jnp.float32)),
              budget=SINGLE_CHIP)
    assert r.collectives == () and r.host_transfers == ()
    assert r.flops > 0 and r.peak_bytes > 0


def test_host_callback_flagged_and_budgeted():
    def f(x):
        y = jax.pure_callback(lambda a: np.asarray(a) * 2,
                              jax.ShapeDtypeStruct((4,), jnp.float32), x)
        return y + 1

    r = audit(f, (jnp.ones((4,), jnp.float32),))
    assert len(r.host_transfers) == 1
    assert "callback" in r.host_transfers[0].detail
    with pytest.raises(HostTransferError) as ei:
        r.enforce(SINGLE_CHIP)
    assert "callback" in str(ei.value)
    r.enforce(CollectiveBudget(host_transfers=1))  # sanctioned: passes


# ---------------------------------------------------------------- aliasing
def test_donated_pools_verified_aliased():
    def scatter(pools, x):
        return [{"k": p["k"].at[0].set(x), "v": p["v"].at[0].set(x)}
                for p in pools]

    pools = [{"k": jnp.ones((4, 2), jnp.float32),
              "v": jnp.ones((4, 2), jnp.float32)} for _ in range(2)]
    r = audit(scatter, (pools, jnp.ones((2,), jnp.float32)),
              donate_argnums=(0,), budget=SINGLE_CHIP)
    assert r.donated_leaves == 4 == r.aliased_leaves
    assert r.unaliased == () and r.alias_bytes == r.donated_bytes > 0


def test_unaliasable_donation_raises_naming_leaf():
    """XLA cannot alias a donated buffer into a smaller output — the
    compiled artifact has NO alias entry for it, and the audit must say
    which leaf lost its donation (the silent-2x-HBM failure mode)."""
    r = audit(lambda pool: pool[0] * 2,
              (jnp.ones((8, 4), jnp.float32),), donate_argnums=(0,))
    assert r.donated_leaves == 1 and r.aliased_leaves == 0
    with pytest.raises(AliasingViolation) as ei:
        r.enforce(SINGLE_CHIP)
    msg = str(ei.value)
    assert "pool" in msg and "TWO copies" in msg


# ------------------------------------------------------- engine integration
def _toy_engine(**overrides):
    paddle.seed(23)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=48, dropout=0.0))
    model.eval()
    kw = dict(max_batch=2, num_pages=24, page_size=4, max_prompt_len=16,
              debug_checks=True)
    kw.update(overrides)
    return ServingEngine(model, ServingConfig(**kw))


def test_engine_steps_pass_hlocheck_under_debug_checks():
    """The single-chip acceptance gate: every compiled program (one per
    prefill bucket + decode) audits clean — zero collectives, zero host
    transfers, every donated pool leaf aliased — and the roll-up lands in
    the serving_hlo_* metrics."""
    engine = _toy_engine()
    snap0 = engine.metrics.snapshot()
    for k in ("serving_hlo_collective_ops", "serving_hlo_host_transfers",
              "serving_hlo_peak_hbm_bytes", "serving_hlo_flops_per_step"):
        assert snap0[k] == 0, k  # pre-seeded: visible before any audit
    assert engine.hlo_audits == {}
    rng = np.random.RandomState(0)
    for n, b in ((3, 4), (12, 3)):  # spans both pad buckets [8, 16]
        engine.add_request(rng.randint(0, 97, (n,)).astype(np.int32), b)
    engine.run()
    audits = engine.hlo_audits
    assert set(audits) == {"prefill[8]", "prefill[16]", "decode"}
    for name, r in audits.items():
        assert r.collectives == (), name
        assert r.host_transfers == (), name
        assert r.donated_leaves == 4 == r.aliased_leaves, name  # 2 layers k+v
        assert r.unaliased == (), name
        assert r.flops > 0 and r.peak_bytes > 0, name
    snap = engine.metrics.snapshot()
    assert snap["serving_hlo_collective_ops"] == 0
    assert snap["serving_hlo_host_transfers"] == 0
    assert snap["serving_hlo_peak_hbm_bytes"] == \
        max(r.peak_bytes for r in audits.values())
    assert snap["serving_hlo_flops_per_step"] == \
        max(r.flops for r in audits.values())
    # the audits did not disturb the PR 4/5 certifications
    assert snap["serving_analysis_retraces_total"] == 0
    expected = snap["serving_decode_steps"] + snap["serving_prefills_total"]
    assert snap["serving_analysis_host_syncs_total"] == expected


def test_engine_audits_once_per_compiled_program():
    """The cost contract: one hlocheck audit per compiled program, not per
    step — a second same-bucket prefill or later decode steps add no new
    reports (and compile_counts pins the real trace counts unchanged)."""
    engine = _toy_engine(max_prompt_len=8)
    rng = np.random.RandomState(1)
    for n in (3, 4, 5):
        engine.add_request(rng.randint(0, 97, (n,)).astype(np.int32), 3)
    engine.run()
    assert set(engine.hlo_audits) == {"prefill[8]", "decode"}
    assert engine.compile_counts == {"prefill": 1, "decode": 1}
    snap = engine.metrics.snapshot()
    assert snap["serving_hlo_collective_ops"] == 0


def test_debug_checks_off_skips_hlo_audit():
    engine = _toy_engine(debug_checks=False, max_prompt_len=8)
    rng = np.random.RandomState(2)
    engine.add_request(rng.randint(0, 97, (4,)).astype(np.int32), 3)
    engine.run()
    assert engine.hlo_audits == {}


# ----------------------------------------------------------- registry + CLI
def test_registry_cache_steps_audit_clean():
    gather = run_step("swap_gather")
    assert gather.donated_leaves == 0 and gather.collectives == ()
    scatter = run_step("swap_scatter")
    assert scatter.donated_leaves == 4 == scatter.aliased_leaves
    cow = run_step("cow_copy")
    assert cow.donated_leaves == 4 == cow.aliased_leaves


@pytest.mark.parametrize("which, label, live", [
    ("prefill", "prefill[8]", {}),
    ("prefill_chunk", "prefill[8]", {"chunk_size": 4}),
    ("decode", "decode", {}),
    ("verify_spec", "verify",
     {"spec": SpecConfig(method="ngram", depth=2)}),
])
def test_registry_audits_the_engine_s_own_operands(monkeypatch, which,
                                                   label, live):
    """What ``_build_engine_step`` hands the audit is what the engine's
    own builders make (hlocheck builds no operand tuple): the same tree,
    shapes and dtypes as a live launch of that program uploads, for the
    program's own guard."""
    launches = {}
    real = ServingEngine._launch

    def spy(self, prog, args, *a, **k):
        launches.setdefault(prog.label, (prog, args))
        return real(self, prog, args, *a, **k)

    monkeypatch.setattr(ServingEngine, "_launch", spy)
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=32, dropout=0.0))
    eng = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=16, page_size=4, max_prompt_len=8, **live))
    eng.add_request(np.arange(1, 8, dtype=np.int32), 3)
    eng.run()
    prog, args = launches[label]

    target, audited, _, budget = hlocheck._build_engine_step(which)
    assert target.name == prog.phase and budget == SINGLE_CHIP
    sig = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: (a.shape, a.dtype), tree)
    assert jax.tree.structure(audited) == jax.tree.structure(args)
    assert sig(audited) == sig(args)


def test_run_step_unknown_name_raises():
    with pytest.raises(KeyError) as ei:
        run_step("nonexistent")
    assert "tp8_decode" in str(ei.value)  # the error lists the registry


def test_registry_names_are_stable():
    assert set(REGISTRY) == {"swap_gather", "swap_scatter", "cow_copy",
                             "engine_prefill", "engine_prefill_chunk",
                             "engine_decode", "engine_verify_spec",
                             "tp8_decode",
                             "tp2_engine_prefill",
                             "tp2_engine_prefill_chunk",
                             "tp2_engine_decode",
                             "tp2_engine_verify_spec", "tp2_swap_gather",
                             "tp2_swap_scatter", "tp2_cow_copy",
                             "engine_decode_q8", "swap_gather_q8",
                             "swap_scatter_q8", "tp2_engine_decode_q8",
                             "tp2_engine_decode_qlogits",
                             "engine_prefill_latent",
                             "engine_decode_latent",
                             "engine_prefill_window",
                             "engine_decode_window"}
    assert REGISTRY["tp8_decode"].min_devices == 8
    assert all(REGISTRY[n].min_devices == 2 for n in REGISTRY
               if n.startswith("tp2_"))


@pytest.mark.slow  # re-tiered 2026-08 (PR 8): tier-1 crossed its 870 s budget on the 1-core box; the tp8/tp2 certifications stay tier-1 in-process (run_step), only the CLI subprocess plumbing moves
def test_cli_hlo_step_and_exit_codes():
    """`python -m paddle_tpu.analysis --hlo` shares the entry point with
    the lint CLI: clean steps exit 0 with a census summary, unknown steps
    exit 2. The tp8 certification runs on the forced 8-device CPU mesh
    (the suite's own conftest environment, inherited by the child)."""
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PATH": "/usr/bin:/bin"}
    import os
    env = {**os.environ, **env}
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--hlo",
         "--step", "tp8_decode", "--step", "swap_gather"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all-reducex1" in r.stdout and "within budget" in r.stdout

    unknown = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--hlo",
         "--step", "nope"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert unknown.returncode == 2
    assert "unknown step" in unknown.stdout

    listing = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--hlo",
         "--list-steps"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert listing.returncode == 0
    for name in REGISTRY:
        assert name in listing.stdout


@pytest.mark.slow  # re-tiered 2026-08 (PR 8): tier-1 crossed its 870 s budget on the 1-core box; the tp8/tp2 certifications stay tier-1 in-process (run_step), only the CLI subprocess plumbing moves
def test_cli_respawned_child_never_respawns_again():
    """The recursion guard: a respawned child that STILL sees too few
    devices (forced flag didn't take) must report an execution error and
    exit 1 — never spawn a grandchild."""
    import os
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           hlocheck._CHILD_ENV: "1"}
    env.pop("XLA_FLAGS", None)  # 1 device: the forced mesh "didn't take"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--hlo",
         "--step", "tp8_decode"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "did not take effect" in r.stdout
    assert "re-running" not in r.stdout  # no grandchild spawned
