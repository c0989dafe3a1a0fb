"""Ahead-of-time compiles for the chip, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
device that is described, not attached (``jax.experimental.topologies``).
Interpret mode cannot show what it shows: the ragged paged-attention
kernel passed every interpret-mode identity test while Mosaic refused its
block shapes, its one-head page DMA and its head_dim-64 pool. Each case
here lowers one main-path kernel at a published width for ``v5e:2x2`` and
finds the kernel's ``tpu_custom_call`` in the compiled HLO — about two
seconds each, no chip time. Nothing runs, so nothing here says anything
about results or speed. Later cases compile a tiny Ouro engine's whole
step programs and read what the compiler moves before their loop, and
Brumby's power-retention decode: the kernel at serving widths, and the
decode program of a model that keeps no page.

Widths: gpt3-1.3b serving (16 heads x 128, page 16, 64 pages per
sequence) and gpt3-350m training (16 heads x 64, batch 8 x seq 1024).
"""
import math
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu  # noqa: F401 — x64 on, as in production

pytestmark = pytest.mark.tpu_compile


@pytest.fixture(scope="module")
def topo():
    """The described four-chip v5e host. Skips where the topology cannot
    be described (no TPU compiler in this installation). The persistent
    compilation cache stays off around these compiles: an entry written
    for a described device cannot be read back without a chip, and the
    next compile would warn about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def v5e(topo):
    """One described v5e chip."""
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes, device):
    args = [jax.ShapeDtypeStruct(s, d, sharding=device) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# ------------------------------------------------- ragged paged attention
PAGE, PPS, POOL_PAGES = 16, 64, 1024

RAGGED_CASES = [
    # id, batch, query tokens, head_dim, quantized
    ("decode-fp32", 8, 1, 128, False),
    ("decode-int8", 8, 1, 128, True),
    ("prefill128-fp32", 1, 128, 128, False),
    ("prefill128-int8", 1, 128, 128, True),
    ("prefill512-fp32", 1, 512, 128, False),
    ("verify5-fp32", 8, 5, 128, False),
    ("verify5-int8", 8, 5, 128, True),
    ("decode-bf16", 8, 1, 128, False),
]


@pytest.mark.parametrize("name,b,s,d,quant", RAGGED_CASES,
                         ids=[c[0] for c in RAGGED_CASES])
def test_ragged_kernel_compiles_for_v5e(v5e, name, b, s, d, quant):
    from paddle_tpu.kernels import ragged_paged_attention as rp

    h = 16
    qdt = jnp.bfloat16 if name.endswith("bf16") else jnp.float32
    ok, why = rp.ragged_kernel_eligible(
        d, PPS, PAGE, s, num_heads=h, quantized=quant,
        q_itemsize=jnp.dtype(qdt).itemsize)
    assert ok, why
    pool = ((POOL_PAGES, PAGE, h, d), jnp.int8 if quant else qdt)
    shapes = [((b, h, s, d), qdt), pool, pool, ((b, PPS), jnp.int32),
              ((b,), jnp.int32)]
    if quant:
        shapes += [((POOL_PAGES, h), jnp.float32)] * 2

        def fn(q, k, v, tab, ctx, ks, vs):
            return rp.ragged_paged_attention(q, k, v, tab, ctx,
                                             k_scale=ks, v_scale=vs)
    else:
        fn = rp.ragged_paged_attention
    assert "tpu_custom_call" in _compiled_text(fn, *shapes, device=v5e)


def test_ragged_head_dim_64_is_gated_with_the_compilers_reason(v5e):
    """gpt3-350m widths (16 heads x 64): the chip's compiler refuses the
    page DMA, so the gate declares the composite path and quotes the
    refusal — and the refusal is still what the compiler says, so the
    gate can be lifted the day it stops being true."""
    from paddle_tpu.kernels import ragged_paged_attention as rp

    ok, why = rp.ragged_kernel_eligible(64, PPS, PAGE, 1, num_heads=16)
    assert not ok
    assert "aligned to tiling (128), but is 64" in why
    pool = ((POOL_PAGES, PAGE, 16, 64), jnp.float32)
    with pytest.raises(Exception, match=r"aligned to tiling \(128\)"):
        _compiled_text(rp.ragged_paged_attention,
                       ((8, 16, 1, 64), jnp.float32), pool, pool,
                       ((8, PPS), jnp.int32), ((8,), jnp.int32),
                       device=v5e)


# ------------------------------------------------ flash / splash attention
def _fwd_bwd(q, k, v):
    from paddle_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _fwd(q, k, v):
    from paddle_tpu.kernels.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True)


FLASH_CASES = [
    # id, fn, (batch, heads, seq, head_dim), dtype, custom calls: the
    # in-tree flash path is one forward and ONE fused backward kernel; the
    # library's splash path a forward, a dq and a dkv kernel
    ("flash-fwd+bwd-bf16-350m", _fwd_bwd, (8, 16, 1024, 64), jnp.bfloat16, 2),
    ("flash-fwd-fp32-1.3b", _fwd, (1, 16, 1024, 128), jnp.float32, 1),
    ("flash-fwd+bwd-fp32-1.3b", _fwd_bwd, (1, 16, 1024, 128), jnp.float32, 2),
    ("splash-fwd+bwd-bf16-s4096", _fwd_bwd, (1, 16, 4096, 64),
     jnp.bfloat16, 3),
]


@pytest.mark.parametrize("name,fn,shape,dtype,n_calls", FLASH_CASES,
                         ids=[c[0] for c in FLASH_CASES])
def test_flash_kernels_compile_for_v5e(v5e, monkeypatch, name, fn, shape,
                                       dtype, n_calls):
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.utils import flags

    # the shipped policy, whatever an earlier test left behind (set_flags
    # coerces to the current value's type, so a bool never goes back)
    monkeypatch.setitem(flags._FLAGS, "FLAGS_use_splash_attention", "auto")
    assert fa.flash_route(shape, shape, True) == "direct"
    # the splash case is the one the auto policy routes to splash
    assert fa._want_splash(True, shape[2], shape[2]) == \
        name.startswith("splash")
    text = _compiled_text(fn, *[(shape, dtype)] * 3, device=v5e)
    assert text.count("tpu_custom_call") == n_calls


def test_flash_statistics_stay_one_number_a_row_in_hbm(v5e, monkeypatch):
    """At the training cell's shape the compiled forward + backward holds no
    fp32 array of ``[8, 16, 1024, n]`` with ``n >= 128`` outside the
    kernels: the library's lane-broadcast ``l``, ``m`` (128 wide) and
    ``di`` (512 wide) were such arrays, 470 MB of copies a layer, and this
    is how they would come back unseen. The statistics that are there are
    ``f32[8,16,1,1024]``."""
    import re

    from paddle_tpu.utils import flags

    monkeypatch.setitem(flags._FLAGS, "FLAGS_use_splash_attention", "auto")
    shape = (8, 16, 1024, 64)
    text = _compiled_text(_fwd_bwd, *[(shape, jnp.bfloat16)] * 3, device=v5e)
    wide = {int(n) for n in re.findall(r"f32\[8,16,1024,(\d+)\]", text)
            if int(n) >= 128}
    assert not wide, wide
    assert "f32[8,16,1,1024]" in text


def test_flash_runs_per_shard_under_a_training_mesh(topo, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device
    training mesh the flash call runs inside a shard_map — batch over dp,
    heads over mp. Compiled for the four described chips as dp2 x mp2 at
    gpt3-350m widths: the kernels are there and attention adds no
    collective."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.fleet.hybrid_train import mesh_scope
    from paddle_tpu.kernels import _common, attention

    # the dispatch asks the backend, which is the CPU here: steer it
    monkeypatch.setattr(_common, "on_tpu_backend", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    sh = NamedSharding(mesh, P("dp", "mp", None, None))
    qkv = [jax.ShapeDtypeStruct((8, 16, 1024, 64), jnp.bfloat16,
                                sharding=sh)] * 3

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            with mesh_scope(mesh):
                return attention.sdpa(q, k, v, is_causal=True).astype(
                    jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(fwd_bwd).lower(*qkv).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    for coll in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute"):
        assert f" {coll}(" not in text and f" {coll}-start(" not in text


# ------------------------------------------------- the decode state update
@pytest.mark.parametrize("heads", [64, 128])
def test_ssm_decode_update_compiles_for_v5e_in_place(v5e, heads):
    """The Mamba-2 decode state update at granite-4.0-h-micro's widths
    (64 slots x 64 heads x 64 x 128 float32), and at 128 heads (two blocks
    of 64 a slot): Mosaic takes its one-lane column slices, its SMEM decays
    and its readout on the MXU, and the 134 MB state is aliased, not
    copied."""
    from paddle_tpu.kernels import ssm_state_update as su

    slots, p, n = 64, 64, 128
    f32 = jnp.float32
    shapes = [((slots, heads, p, n), f32), ((slots, heads, p), f32),
              ((slots, heads), f32), ((heads,), f32), ((slots, n), f32),
              ((slots, n), f32), ((slots,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(su.ssm_decode_update, donate_argnums=(0,)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        == slots * heads * p * n * 4


# ------------------------------------- the decode pipeline's two instances
def test_gqa_decode_kernel_compiles_for_v5e_at_cell_g_s_shape(v5e,
                                                              monkeypatch):
    """Grouped heads over the lane-dense pool at granite-4.0-h-micro's
    widths (64 rows, 32 query heads over 8 KV heads of 64, 96 pages of 16,
    bf16): a page copy is 16 whole rows of four lane tiles, no slice of
    the head size appears in the kernel, Mosaic takes it, and nothing of
    the table's width (64 x 1,536 positions) is left around it."""
    import re

    from paddle_tpu.kernels import _common
    from paddle_tpu.kernels import paged_attention as pa

    # the dispatch asks the backend, which is the CPU here: steer it
    monkeypatch.setattr(_common, "on_tpu_backend", lambda: True)
    bf = jnp.bfloat16
    pool = ((12902, 16, 8 * 64), bf)
    text = _compiled_text(
        lambda q, k, v, t, c: pa.paged_attention(q, k, v, t, c,
                                                 scale=1 / 64),
        ((64, 32, 1, 64), bf), pool, pool, ((64, 96), jnp.int32),
        ((64,), jnp.int32), device=v5e)
    assert text.count("tpu_custom_call") == 1
    assert "gqa_decode_attention" in text
    assert not re.findall(r"\[64,(?:\d+,)*1536(?:,\d+)*\]", text)


def test_gqa_prefill_takes_the_composite_on_the_same_pools(v5e,
                                                           monkeypatch):
    """A call of 512 tokens a row (cell G's prefill) has no kernel: the
    gate says so and the compiled call holds none."""
    from paddle_tpu.kernels import _common
    from paddle_tpu.kernels import paged_attention as pa

    monkeypatch.setattr(_common, "on_tpu_backend", lambda: True)
    bf = jnp.bfloat16
    pool = ((12902, 16, 8 * 64), bf)
    text = _compiled_text(
        lambda q, k, v, t, c: pa.paged_attention(q, k, v, t, c,
                                                 scale=1 / 64),
        ((1, 32, 512, 64), bf), pool, pool, ((1, 96), jnp.int32),
        ((1,), jnp.int32), device=v5e)
    assert "tpu_custom_call" not in text


def test_mla_decode_kernel_still_compiles_for_v5e_at_cell_k_s_shape(v5e):
    """The latent kernel is the same pipeline given no values pool: at
    Kimi's serving shape (256 rows, 64 heads, a 640-wide bf16 latent pool,
    160 pages of 16) it lowers under its own name."""
    from paddle_tpu.kernels import latent_paged_attention as lp

    bf = jnp.bfloat16
    text = _compiled_text(
        lambda q, pool, t, c: lp.mla_decode_kernel_call(
            q, pool, t, c, rank=512, scale=0.1),
        ((256, 64, 640), bf), ((34402, 16, 640), bf),
        ((256, 160), jnp.int32), ((256,), jnp.int32), device=v5e)
    assert text.count("tpu_custom_call") == 1
    assert "mla_decode_attention" in text


# --------------------------------------- Ouro's loop and its projections
def _ouro_engine():
    """A tiny bfloat16 Ouro engine on the CPU (3 layers run 3 times, 4
    heads of 16)."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.text import ouro

    paddle.seed(0)
    model = ouro.OuroForCausalLM(ouro.OuroConfig(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, total_ut_steps=3, max_position_embeddings=64))
    model.to(dtype="bfloat16")
    return ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=40, page_size=4, max_prompt_len=32,
        enable_prefix_caching=False))


def _matmul_heads(y, w, d):
    b, s, _ = y.shape
    return jnp.matmul(y, w).reshape(b, s, -1, d)


@pytest.mark.parametrize("weight_led", [True, False],
                         ids=["weight-led", "matmul"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_ouro_loop_relayouts_no_weight_before_it(v5e, monkeypatch, program,
                                                 weight_led):
    """The engine's decode and prefill programs of a tiny Ouro, compiled
    for a v5e: inside the loop the compiler takes the weight of ``y @ w``
    for the query and key projections with ``in`` minor and copies it into
    that order before the loop, every call (two copies a layer: at
    Ouro-2.6B 96 of 8 MB a decode step). With the weight as the product's
    left operand (``ouro._heads_of``) no rank-2 weight is copied or
    transposed outside the loop. The CPU's compiler cannot show it."""
    import re

    from jax.experimental.layout import Format

    from paddle_tpu.kernels import _common
    from paddle_tpu.text import ouro

    if not weight_led:
        monkeypatch.setattr(ouro, "_heads_of", _matmul_heads)
    eng = _ouro_engine()
    if program == "decode":
        fn, args = eng._decode_impl, eng._decode_args()
    else:
        fn, args = eng._prefill_impl, eng._prefill_args(
            eng._prefill_program(8), 0, 0, [5, 6, 7], 0)
    monkeypatch.setattr(_common, "on_tpu_backend", lambda: True)
    # the operands' layouts as the engine holds them (a shape alone leaves
    # the parameters' layouts to the compiler)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=Format(a.format.layout, v5e)), args)
    text = jax.jit(fn, donate_argnums=(1,)).lower(*shapes).compile() \
        .as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    assert entry.count(" while(") == 1
    moved = re.findall(r"= bf16\[\d+,\d+\]\S* (?:copy|transpose)\(", entry)
    assert len(moved) == (0 if weight_led else 2 * 3)


# ------------------------------------------ Brumby's power-retention decode
def test_power_retention_decode_kernel_compiles(v5e):
    """The decode kernel at Brumby-14B's serving widths: 16 slots of 8
    groups of 5 query heads of 128 (a group's state 8,256 x 128 float32),
    the state and z donated and aliased in place."""
    from paddle_tpu.kernels import power_retention as pr

    slots, groups, group, d = 16, 8, 5, 128
    dd, f32 = pr.feature_dim(d), jnp.float32

    def step(state, z, k, q, v, g, active):
        return pr.retention_decode_update(state, z, k, q, v, g, active)

    shapes = [((slots, groups, dd, d), f32),
              ((slots,) + pr.z_shape(groups, d), f32),
              ((slots, groups, d), f32), ((slots, groups, group, d), f32),
              ((slots, groups, d), f32), ((slots, groups), f32),
              ((slots,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=v5e) for s, t in shapes]
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "power_retention_decode" in text
    # the state stays where it is: its 541 MB are aliased, not copied
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= slots * groups * dd * d * 4


def test_brumby_decode_program_takes_no_page_pool(v5e, monkeypatch):
    """The engine's decode program of a one-layer Brumby at its published
    widths, compiled for a v5e from shapes alone: its arguments are the
    weights, the slots' state and the per-slot vectors, and nothing else
    (no page pool, no page table); the kernel is in it."""
    from jax.experimental.layout import Format, Layout

    import paddle_tpu as paddle
    from paddle_tpu.kernels import _common
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.serving import kv_cache
    from paddle_tpu.text.brumby import BrumbyConfig, BrumbyForCausalLM

    slots = 2
    with paddle.LazyGuard():
        model = BrumbyForCausalLM(BrumbyConfig(num_hidden_layers=1))
    params, _ = model.functional_state()
    for t in params.values():   # bfloat16 shapes, never materialized
        t._value = jax.ShapeDtypeStruct(tuple(t._value.shape), jnp.bfloat16)
        t._lazy_init = None
    model.eval()
    monkeypatch.setattr(kv_cache, "init_pools", lambda cfg: [
        {lf.name: jax.ShapeDtypeStruct((cfg.max_batch,) + tuple(lf.shape),
                                       lf.dtype) for lf in leaves}
        for leaves in cfg.layer_leaves])
    eng = ServingEngine(model, ServingConfig(
        max_batch=slots, max_prompt_len=16, enable_prefix_caching=False,
        enable_tracing=False))
    monkeypatch.setattr(_common, "on_tpu_backend", lambda: True)
    args = eng._decode_args()
    assert args[2] is None
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=Format(
            Layout(major_to_minor=tuple(range(len(a.shape)))), v5e)), args)
    compiled = jax.jit(eng._decode_impl, donate_argnums=(1,)).lower(
        *shapes).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    weights = sum(math.prod(a.shape) * 2 for a in eng._p.values())
    state = slots * eng.cache.cfg.state_bytes_per_slot
    small = compiled.memory_analysis().argument_size_in_bytes \
        - weights - state
    # what is left is the per-slot vectors and the tiling's padding of the
    # small leaves (the gate's [5120, 8] weight takes whole 128-lane rows:
    # 1.2 MB); a pool of keys and values for the two slots' contexts
    # (32,768 tokens at 4 KB a token) would be 268 MB
    assert 0 <= small < 2 << 20, small
    leaves = jax.tree_util.tree_leaves(args)
    assert all(a.dtype != jnp.int32 or a.ndim < 2 for a in leaves)
