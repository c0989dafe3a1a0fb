"""kernelcheck: static certification of Pallas kernels.

- the registered in-tree kernel families certify (VMEM, tiling, race
  proof, roofline banked + composite diff) on CPU, no TPU required
- two deliberately defective fixture kernels are flagged: a colliding
  output index_map (write race) and an over-VMEM block config
- interpret-mode numerics smoke: certified kernels match their (jitted)
  composite references bit-for-bit on CPU (ULP-bounded where the lowering
  genuinely differs — see the test comments)
- the dispatch-coverage report names the int8 decode path as kernel-less
- an eligible kernel that fails raises (no composite stand-in)
- flash_tuned.json tiling validation at load and at autotune-bank time
- KERNELCHECK_CERTS module declarations cross-check the live registry
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import kernelcheck as kc
from paddle_tpu.utils import monitor

pytestmark = pytest.mark.kernelcheck

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


# certify each registry entry at most once per session — tracing the
# library kernels is the dominant cost, every test below reads the result
_RUNS: dict = {}


def _run(name):
    if name not in _RUNS:
        _RUNS[name] = kc.run_kernel(name)
    return _RUNS[name]


FAST_FAMILIES = ("fused_layernorm_fwd", "fused_layernorm_dx", "fused_adam",
                 "paged_decode", "ragged_paged", "ragged_paged_q8",
                 "ragged_paged_verify", "ragged_paged_prefill",
                 "mla_decode", "gqa_decode")


# ------------------------------------------------------------ certification
@pytest.mark.parametrize("name", FAST_FAMILIES)
def test_registry_kernel_certifies(name):
    report, record = _run(name)
    assert report.ok, [str(f) for f in report.all_findings()]
    assert len(report.calls) == 1
    assert report.vmem_bytes > 0
    assert report.vmem_bytes <= report.calls[0].vmem_cap
    # the banked roofline record carries the full contract
    assert record["flops"] > 0 and record["hbm_bytes"] > 0
    assert record["intensity"] == round(
        record["flops"] / record["hbm_bytes"], 3)
    assert record["composite"]["flops"] > 0
    assert record["predicted_speedup"] is not None


def test_flash_and_splash_certify_with_declared_revisits():
    """The attention kernels revisit their output across the KV grid dim
    (online-softmax accumulation) — legal exactly because their budgets
    declare allow_output_revisits."""
    for name in ("flash_fwd", "flash_bwd", "splash_fwd"):
        report, record = _run(name)
        assert report.ok, (name, [str(f) for f in report.all_findings()])
        assert sum(c.output_revisits for c in report.calls) > 0, name
        assert record["predicted_speedup"] > 1.0, name


def test_paged_decode_certifies_the_int8_flip():
    """PR 11 certified the int8 SKIP as a declared constraint; the
    unified ragged kernel inverts it — int8 decode is now
    kernel-ELIGIBLE, certified on the legacy paged certificate so the
    coverage flip can never silently regress."""
    report, _ = _run("paged_decode")
    assert report.ok
    spec = kc.REGISTRY["paged_decode"].build()
    names = {c[0]: c[1] for c in spec["constraints"]}
    assert names["int8_served_by_unified_kernel"] is True
    assert names["decode_kernel_eligible"] is True


def test_ragged_entries_resolve_data_dependent_output_map():
    """The unified kernel's output index map reads the prefetched
    cu_q_lens (data-dependent) — and certifies with ZERO race findings:
    the budget declares allow_data_dependent_outputs AND the builder's
    index_args let kernelcheck evaluate the map at the canonical runtime
    values and run the real injectivity proof. Resolved, not
    suppressed."""
    for name in ("ragged_paged", "ragged_paged_q8", "ragged_paged_verify",
                 "ragged_paged_prefill"):
        report, record = _run(name)
        assert report.ok, (name, [str(f) for f in report.all_findings()])
        races = [f for f in report.all_findings() if f.kind == "race"]
        assert races == [], (name, [str(f) for f in races])
        assert record["predicted_speedup"] > 1.0, name
    # WITHOUT index_args the same kernel fails closed (error) or warns
    # under the declaration — the resolve path is the index_args
    spec = kc.REGISTRY["ragged_paged"].build()
    undeclared = kc.certify(spec["fn"], spec["args"], name="ragged_paged",
                            budget=kc.KernelBudget())
    assert any(f.kind == "race" and f.severity == "error"
               and "allow_data_dependent_outputs" in f.message
               for f in undeclared.errors)
    declared = kc.certify(spec["fn"], spec["args"], name="ragged_paged",
                          budget=spec["budget"])
    warns = [f for f in declared.all_findings()
             if f.kind == "race" and f.severity == "warn"]
    assert warns and "index_args" in warns[0].message
    resolved = kc.certify(spec["fn"], spec["args"], name="ragged_paged",
                          budget=spec["budget"],
                          index_args=spec["index_args"])
    assert not [f for f in resolved.all_findings() if f.kind == "race"]


def test_ragged_q8_fused_dequant_speedup_banked():
    """The int8 entry's roofline captures WHY the fused dequant matters:
    the kernel moves int8 codes (+ tiny scales) where the composite
    materializes the dequantized f32 gather — the banked predicted
    speedup is the int8-decode headline."""
    _, rec = _run("ragged_paged_q8")
    _, rec_f32 = _run("ragged_paged")
    assert rec["hbm_bytes"] < rec_f32["hbm_bytes"] / 2
    assert rec["predicted_speedup"] > rec_f32["predicted_speedup"]


# -------------------------------------------------------- defect fixtures
def _fixture_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _racy_call(x):
    """Deliberate write race: grid point i writes output block i % 2 —
    block 0 REAPPEARS at i=2 after the map moved away at i=1."""
    from jax.experimental import pallas as pl

    return pl.pallas_call(  # lint: disable=PT011
        _fixture_kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i % 2, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32))(x)


def test_race_fixture_flagged():
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    report = kc.certify(_racy_call, (x,), name="racy")
    assert not report.ok
    races = [f for f in report.errors if f.kind == "race"]
    assert races and "REAPPEARS" in races[0].message
    assert "write race" in races[0].message


def _revisit_call(x):
    """Every grid point maps to output block 0 — the accumulation idiom,
    an error unless the budget declares it."""
    from jax.experimental import pallas as pl

    return pl.pallas_call(  # lint: disable=PT011
        _fixture_kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))(x)


def test_undeclared_revisit_flagged_and_declarable():
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    report = kc.certify(_revisit_call, (x,), name="revisit")
    assert not report.ok
    assert any("allow_output_revisits" in f.message for f in report.errors)
    sanctioned = kc.certify(
        _revisit_call, (x,), name="revisit",
        budget=kc.KernelBudget(allow_output_revisits=True))
    assert sanctioned.ok
    assert sanctioned.calls[0].output_revisits == 3


def _over_vmem_call(x):
    """One 64 MiB f32 block — 4x the v5e VMEM, before double-buffering."""
    from jax.experimental import pallas as pl

    return pl.pallas_call(  # lint: disable=PT011
        _fixture_kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec((8192, 2048), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8192, 2048), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16384, 2048), jnp.float32))(x)


def test_over_vmem_fixture_flagged():
    # ShapeDtypeStructs only — nothing this size ever materializes
    x = jax.ShapeDtypeStruct((16384, 2048), jnp.float32)
    report = kc.certify(_over_vmem_call, (x,), name="whale")
    assert not report.ok
    vmem = [f for f in report.errors if f.kind == "vmem"]
    assert vmem and "VMEM working set" in vmem[0].message
    assert "exceeds" in vmem[0].message
    # 2 blocks x 64 MiB x 2 (pipeline double buffer)
    assert report.vmem_bytes == 2 * 8192 * 2048 * 4 * 2


def _misaligned_call(x):
    from jax.experimental import pallas as pl

    return pl.pallas_call(  # lint: disable=PT011
        _fixture_kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 100), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((32, 400), jnp.float32))(x)


def test_tiling_lane_misalignment_flagged():
    x = jax.ShapeDtypeStruct((32, 400), jnp.float32)
    report = kc.certify(_misaligned_call, (x,), name="misaligned")
    tiling = [f for f in report.errors if f.kind == "tiling"]
    assert tiling, [str(f) for f in report.all_findings()]
    assert any("128-lane" in f.message for f in tiling)


def test_dispatch_constraint_failure_flagged():
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    report = kc.certify(
        _revisit_call, (x,), name="gated",
        budget=kc.KernelBudget(allow_output_revisits=True),
        constraints=(("the_%512_rule", False,
                      "s=640 must take the composite path"),))
    assert not report.ok
    assert any(f.kind == "dispatch" and "the_%512_rule" in f.message
               for f in report.errors)


def test_untraceable_kernel_is_the_finding():
    """A kernel entry that cannot even trace (the paged-decode x64 bug's
    shape) certifies as a trace-kind violation, not a checker crash."""
    def broken(x):
        raise TypeError("mosaic legalization failed")

    report = kc.certify(broken, (jax.ShapeDtypeStruct((8,), jnp.float32),),
                        name="broken")
    assert not report.ok
    assert any(f.kind == "trace" and "every launch would raise" in f.message
               for f in report.errors)


# ------------------------------------------------- interpret-mode numerics
# The reference is the registry's own composite, JITTED: interpret-mode
# pallas runs under jit, and eager-vs-jit constant folding alone costs
# thousands of ULPs on a reduction. Jit-to-jit, layernorm is bitwise.
def test_fused_layernorm_interpret_matches_composite_bitwise():
    from paddle_tpu.kernels import fused_layernorm as fl

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 256), jnp.float32)
    g = jnp.asarray(rng.randn(256), jnp.float32)
    b = jnp.asarray(rng.randn(256), jnp.float32)
    y = fl.fused_layer_norm(x, g, b, 1e-5, interpret=True)
    spec = kc.REGISTRY["fused_layernorm_fwd"].build()
    ref, _, _ = jax.jit(spec["composite"])(x, g, b)
    assert np.array_equal(np.asarray(y), np.asarray(ref))


def test_fused_adam_interpret_matches_composite_bitwise():
    from paddle_tpu.kernels import fused_optimizer as fo

    rng = np.random.RandomState(1)
    n = 1 << 16
    p, g, m, v = (jnp.asarray(rng.randn(n), jnp.float32) for _ in range(4))
    v = jnp.abs(v)
    lr, bc1, bc2 = (jnp.asarray(s, jnp.float32)
                    for s in (1e-3, 0.9, 0.999))
    out = fo.fused_adam_update(p, g, m, v, lr, bc1, bc2, beta1=0.9,
                               beta2=0.999, eps=1e-8, interpret=True)
    spec = kc.REGISTRY["fused_adam"].build()
    ref = jax.jit(spec["composite"])(p, g, m, v, lr, bc1, bc2)
    # m/v are bitwise; p's div-by-(sqrt+eps) lowers differently inside the
    # pallas interpreter (measured max 32 ULP under jax 0.9.0)
    assert np.array_equal(np.asarray(out[1]), np.asarray(ref[1]))
    assert np.array_equal(np.asarray(out[2]), np.asarray(ref[2]))
    np.testing.assert_array_max_ulp(np.asarray(out[0]), np.asarray(ref[0]),
                                    maxulp=32)


# ------------------------------------------------------- dispatch coverage
def test_coverage_int8_decode_covered_and_head_dim_64_declared():
    """Of the two kernel-less findings PR 11's coverage report named,
    int8 decode is CLOSED by the unified kernel; head_dim 64 is kernel-
    less on the chip (the v5e compiler refuses the page DMA) and the
    report says so with the compiler's words instead of claiming cover.
    The seq-%512 flash edge routes through the causal pad instead of
    silently falling off."""
    cov = kc.coverage_report()
    # the one kernel-less serving paged config is the declared d=64 row
    paged_less = [k for k in cov["kernel_less"] if "paged" in k]
    assert len(paged_less) == 1 and "head_dim=64" in paged_less[0], \
        cov["kernel_less"]
    by_config = {(r["family"], r["config"]): r for r in cov["rows"]}
    hot = by_config[("paged_decode",
                     "platform=tpu pallas_flag=on kv_dtype=float32")]
    assert hot["path"] == "pallas" and not hot["blocked_by"]
    q8 = by_config[("paged_decode",
                    "platform=tpu pallas_flag=on kv_dtype=int8")]
    assert q8["path"] == "pallas" and not q8["blocked_by"]
    d64 = by_config[("paged_decode",
                     "platform=tpu pallas_flag=on kv_dtype=float32 "
                     "head_dim=64")]
    assert d64["path"] == "composite"
    assert "aligned to tiling (128), but is 64" in d64["blocked_by"]
    cpu = by_config[("paged_decode",
                     "platform=cpu pallas_flag=on kv_dtype=float32")]
    assert cpu["path"] == "composite"
    assert "FLAGS_ragged_interpret" in cpu["blocked_by"]
    # the multi-token modes ride the same predicate, both dtypes
    for kv in ("float32", "int8"):
        for mode in ("verify[K+1=5]", "prefill[64]"):
            r = by_config[("ragged_paged",
                           f"platform=tpu pallas_flag=on kv_dtype={kv} "
                           f"mode={mode}")]
            assert r["path"] == "pallas", r
    # the %512 edge: causal pads to the block, non-causal is a
    # loudly-counted composite — neither is silent anymore
    pad = by_config[("flash_prefill",
                     "platform=tpu pallas_flag=on seq=640 causal")]
    assert pad["path"] == "pallas[padded]"
    nc = by_config[("flash_prefill",
                    "platform=tpu pallas_flag=on seq=640 non-causal")]
    assert nc["path"] == "composite[counted]"
    assert "serving_flash_edge_fallback_total" in nc["blocked_by"]
    assert not any("flash" in k and "640" in k for k in cov["kernel_less"])


def test_coverage_names_the_grouped_branch_s_gate():
    """Grouped KV heads over a lane-dense pool at granite-4.0-h-micro's
    shape: a decode step reaches the grouped-head kernel, a prefill is
    kernel-less and says why, in the words of the gate
    ``paged_attention``'s grouped branch asks."""
    from paddle_tpu.kernels import paged_decode as pd

    cov = kc.coverage_report()
    rows = {r["config"].split("mode=")[1]: r for r in cov["rows"]
            if r["family"] == "gqa_decode"}
    assert rows["decode"]["path"] == "pallas"
    assert not rows["decode"]["blocked_by"]
    g = kc._GQA_SHAPE
    prefill = rows["prefill[512]"]
    assert prefill["path"] == "composite"
    assert prefill["blocked_by"] == pd.gqa_kernel_eligible(
        g["heads"], g["kv_heads"], g["head_dim"], g["page_size"],
        g["pages_per_seq"], 512)[1]
    assert any(prefill["blocked_by"] in k for k in cov["kernel_less"])


def test_gqa_decode_is_certified_against_the_grouped_composite():
    """The grouped-head kernel's certificate: the gate's three constraints
    hold, the banked record is the fresh one, and the composite it is
    measured against materializes more than the kernel moves."""
    import json

    report, record = _run("gqa_decode")
    assert report.ok
    spec = kc.REGISTRY["gqa_decode"].build()
    assert {c[0]: c[1] for c in spec["constraints"]} == {
        "gqa_kernel_eligible": True, "decode_only": True,
        "lane_dense_pool_only": True}
    with open(kc.bank_path()) as fh:
        banked = json.load(fh)
    assert kc.diff_banked({"gqa_decode": record,
                           "mla_decode": _run("mla_decode")[1]},
                          banked) == []
    assert record["predicted_speedup"] > 1.0


def test_coverage_predicate_is_the_runtime_gate():
    """The coverage rows come from decode_kernel_eligible — now the
    unified ragged_kernel_eligible gate the dispatch calls, so the table
    can't drift. The PR 11 gates it retired (head_dim % 128, page-table
    width alignment, the int8 ban) stay retired."""
    from paddle_tpu.kernels import paged_attention as pa

    ok, why = pa.decode_kernel_eligible(128, 32, 16)
    assert ok and why == ""
    # head_dim 64: refused by the chip's compiler, so gated with its words
    ok, why = pa.decode_kernel_eligible(64, 32, 16)
    assert not ok and "Mosaic refuses the page DMA" in why
    # the closed int8 coverage gap — eligible
    ok, why = pa.decode_kernel_eligible(128, 32, 16, quantized=True)
    assert ok and why == ""
    # unaligned page-table widths no longer fall off the fast path
    ok, _ = pa.decode_kernel_eligible(128, 30, 16)
    assert ok
    # the remaining honest gates
    ok, why = pa.decode_kernel_eligible(128, 32, 16, flags_on=False)
    assert not ok and "FLAGS_use_pallas_kernels" in why
    ok, why = pa.decode_kernel_eligible(128, 32, 16, on_tpu=False)
    assert not ok and "FLAGS_ragged_interpret" in why
    # a page too large to stage even one at a time (chunk = 1 page)
    ok, why = pa.decode_kernel_eligible(128, 64, 4096)
    assert not ok and "VMEM" in why
    ok, why = pa.decode_kernel_eligible(128, 32, 16, num_query_tokens=0)
    assert not ok and "num_query_tokens" in why


# -------------------------------------------------- flash_tuned validation
def test_validate_flash_tuned():
    assert kc.validate_flash_tuned({"1024,128": 512, "2048,64": 1024}) == []
    errors = kc.validate_flash_tuned({
        "1024,128": 500,      # not a 128 multiple
        "1000,64": 512,       # does not tile seq
        "512,64": 1024,       # block exceeds seq
        "bogus": 512,         # unparseable key
        "1024,96": 512,       # head_dim off the 64 tile
        "1024,64": "512",     # non-int value
    })
    msgs = "\n".join(errors)
    assert "128-lane" in msgs and "does not tile" in msgs
    assert "exceeds seq" in msgs and "seq,head_dim" in msgs
    assert "head_dim 96" in msgs and "positive int" in msgs


def test_shipped_flash_tuned_table_is_valid():
    from paddle_tpu.kernels import flash_attention as fa

    table = fa._tuned_table()  # raises on a misaligned shipped table
    assert kc.validate_flash_tuned(table) == []


def test_flash_tuned_load_rejects_misaligned(tmp_path, monkeypatch):
    from paddle_tpu.kernels import flash_attention as fa

    bad = tmp_path / "flash_tuned.json"
    bad.write_text(json.dumps({"1024,64": 500}))
    monkeypatch.setattr(fa, "_TUNED_PATH", str(bad))
    monkeypatch.setattr(fa, "_TUNED", None)
    with pytest.raises(ValueError, match="tiling constraints"):
        fa._tuned_table()
    monkeypatch.setattr(fa, "_TUNED", None)  # don't poison the cache


def test_autotune_refuses_to_bank_misaligned(monkeypatch):
    """tools/flash_autotune.py validates before writing — the same
    validator, so the load site can never see a table the bank site
    accepted."""
    from paddle_tpu.analysis.kernelcheck import validate_flash_tuned

    assert validate_flash_tuned({"1024,64": 500})  # what main() raises on


# ------------------------------------------------------ no silent fallback
def test_eligible_kernel_that_fails_raises(monkeypatch):
    """A kernel the gate called eligible that fails to trace or lower
    RAISES out of the dispatch: serving the composite in its place would
    answer requests while the only kernel never ran."""
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels import ragged_paged_attention as rp

    monkeypatch.setattr(pa, "_use_ragged_kernel",
                        lambda *a, **k: (True, True))

    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(rp, "ragged_paged_attention", boom)
    q = jnp.zeros((1, 2, 1, 8), jnp.float32)
    pool = jnp.zeros((4, 2, 2, 8), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    ctx = jnp.zeros((1,), jnp.int32)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        pa.paged_attention(q, pool, pool, table, ctx)
    assert not hasattr(pa, "fallback_hook")


# --------------------------------------------- registry <-> module certs
def test_kernelcheck_certs_declarations_match_registry():
    """Every pallas-kernel module's KERNELCHECK_CERTS names live registry
    entries, and every registry entry is declared by exactly one module —
    PT011's declaration can't go stale in either direction."""
    from paddle_tpu.kernels import (flash_attention, fused_layernorm,
                                    fused_optimizer, paged_attention,
                                    paged_decode, power_retention,
                                    ragged_paged_attention,
                                    ssm_state_update)

    declared = []
    for mod in (flash_attention, fused_layernorm, fused_optimizer,
                paged_attention, paged_decode, power_retention,
                ragged_paged_attention, ssm_state_update):
        certs = mod.KERNELCHECK_CERTS
        assert certs, mod.__name__
        declared.extend(certs)
    assert sorted(declared) == sorted(kc.REGISTRY)
    assert len(declared) == len(set(declared))


# ----------------------------------------------------------- bank + drift
def test_bank_and_drift_detection():
    _, rec = _run("fused_adam")
    records = {"fused_adam": rec}
    banked = json.loads(json.dumps(records))  # round-trip like the file
    assert kc.diff_banked(records, banked) == []
    banked["fused_adam"]["flops"] += 1
    drift = kc.diff_banked(records, banked)
    assert any(f.kind == "drift" and f.severity == "error"
               and "flops" in f.message for f in drift)
    missing = kc.diff_banked({"fused_adam": rec, "new_kernel": rec}, banked)
    assert any("--bank" in f.message for f in missing)
    # composite re-measurements drift only as warnings
    banked = json.loads(json.dumps(records))
    banked["fused_adam"]["composite"]["flops"] *= 2
    drift = kc.diff_banked(records, banked)
    assert drift and all(f.severity == "warn" for f in drift)


# ----------------------------------------------------------------- CLI
def test_cli_inprocess(tmp_path, capsys):
    assert kc.main(["--list-kernels"]) == 0
    assert "paged_decode" in capsys.readouterr().out
    assert kc.main(["--kernel", "bogus"]) == 2
    capsys.readouterr()
    profile = tmp_path / "kernelcheck.json"
    rc = kc.main(["--kernel", "fused_adam", "--kernel",
                  "fused_layernorm_fwd", "--bank", "--no-coverage",
                  "--profile", str(profile)])
    out = capsys.readouterr().out
    assert rc == 0 and profile.exists()
    assert "banked 2 roofline record(s)" in out
    banked = json.loads(profile.read_text())
    assert set(banked) == {"fused_adam", "fused_layernorm_fwd"}
    assert banked["fused_adam"]["flops"] == 14 * (1 << 16)


def test_cli_coverage_and_violation_exit(tmp_path, capsys):
    """A drifted bank fails the default sweep loudly (the PR 6 contract);
    the coverage table shows the int8 flip, and its kernel-less
    production section holds exactly the declared head_dim-64 row with
    the compiler's refusal and the grouped heads' prefill, which has no
    kernel yet (every other TPU-flags-on serving config reaches a kernel
    or a counted fallback)."""
    profile = tmp_path / "kernelcheck.json"
    bad = {name: {"grid": [], "vmem_bytes": 0, "flops": -1,
                  "hbm_bytes": 0} for name in kc.REGISTRY}
    profile.write_text(json.dumps(bad))
    rc = kc.main(["--profile", str(profile)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "drifted from the banked contract" in out
    less = out.split("kernel-less production configs")[1].split("\n\n")[0]
    assert less.count("!!") == 2 and "head_dim=64" in less
    assert "gqa_decode" in less and "mode=prefill[512]" in less
    assert "aligned to tiling (128), but is 64" in less
    assert "kv_dtype=int8" in out  # the flipped row still prints, as pallas
