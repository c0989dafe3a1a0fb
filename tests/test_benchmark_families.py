"""The guards between the program and the benchmark's families (tier-1, so
that a program change which renames a leaf or moves a reference breaks
here and not unseen in a chip run): every file under ``benchmark/configs/``
resolves its family; each family's ``leaf_table`` is the program's
``functional_state()`` by name and shape at a tiny size; and the
benchmark's copy of each plain reference agrees with the repo's.
"""
import glob
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(os.path.dirname(__file__), "refs")):
    if path not in sys.path:
        sys.path.insert(0, path)

import paddle_tpu as paddle  # noqa: E402

CONFIGS = sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs",
                                        "*.json")))

TINY_GPT3 = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
                 head_size=32, ffn_hidden=256, max_seq_len=32)
TINY_KIMI = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
                 moe_intermediate_size=48, num_hidden_layers=3,
                 num_attention_heads=4, n_routed_experts=4, router_width=16,
                 held_experts_first=8, num_experts_per_tok=4,
                 kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16,
                 max_position_embeddings=64)
TINY_GRANITE = dict(vocab_size=96, hidden_size=32, num_hidden_layers=8,
                    layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
                    num_attention_heads=4, num_key_value_heads=2,
                    shared_intermediate_size=48, mamba_n_heads=4,
                    mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
                    max_position_embeddings=64)
TINY_MELLUM = dict(vocab_size=96, hidden_size=64, moe_intermediate_size=32,
                   num_hidden_layers=8,
                   layer_types=["sliding_attention"] * 3
                   + ["full_attention"] + ["sliding_attention"] * 3
                   + ["full_attention"],
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   num_experts=8, num_experts_per_tok=2, sliding_window=8,
                   max_position_embeddings=64)


def load(path):
    with open(path) as f:
        return json.load(f)


def tiny_model(config: dict) -> dict:
    tiny = {"gpt3": TINY_GPT3, "kimi_k2": TINY_KIMI,
            "granite_hybrid": TINY_GRANITE,
            "mellum": TINY_MELLUM}[config["family"]]
    return dict(config["model"], **tiny)


def program_model(config: dict, model: dict):
    """The program's model of a family at the tiny size, shapes only."""
    if config["family"] == "gpt3":
        from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

        with paddle.LazyGuard():
            return GPTForCausalLM(GPTConfig(
                vocab_size=model["vocab_size"],
                hidden_size=model["hidden_size"],
                num_layers=model["num_layers"],
                num_heads=model["num_heads"],
                max_seq_len=model["max_seq_len"]))
    if config["family"] == "granite_hybrid":
        from benchmark.families import granite_hybrid
        from paddle_tpu.text.granite_hybrid import GraniteHybridForCausalLM

        with paddle.LazyGuard():
            return GraniteHybridForCausalLM(
                granite_hybrid.program_config(model))
    if config["family"] == "mellum":
        from benchmark.families import mellum
        from paddle_tpu.text.mellum import MellumForCausalLM

        with paddle.LazyGuard():
            return MellumForCausalLM(mellum.program_config(model))
    from benchmark.families import kimi_k2
    from paddle_tpu.text.kimi_k2 import KimiK2ForCausalLM

    with paddle.LazyGuard():
        return KimiK2ForCausalLM(kimi_k2.program_config(model))


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_every_configuration_resolves_its_family(path):
    from benchmark.lib.common import family_of

    config = load(path)
    family = family_of(config)
    family.check(config)
    assert family.leaf_table(config["model"]) and family.WORK
    assert callable(family.logits_at) and callable(
        getattr(family, "build_serving", None)
        or getattr(family, "build_training"))


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_leaf_table_is_the_programs_functional_state(path):
    from benchmark.lib.common import family_of

    config = load(path)
    model = tiny_model(config)
    table = family_of(config).leaf_table(model)
    params, _ = program_model(config, model).functional_state()
    assert {n: shape for n, (shape, _) in table.items()} \
        == {n: tuple(t._value.shape) for n, t in params.items()}
    assert {kind for _, kind in table.values()} <= {"matrix", "scale", "bias"}


def test_kimi_k2_copy_of_the_reference_gives_the_repos_logits():
    """The same float32 leaves through ``benchmark/families/kimi_k2.py``'s
    ``logits_at`` (a layer's leaves at a time) and through
    ``tests/refs/kimi_k2_reference.py``: the same logits, to float32
    round-off of values of order 0.5 (2e-6)."""
    import kimi_k2_reference as ref

    from benchmark.families import kimi_k2
    from benchmark.lib import weights

    config = {"model": dict(load(os.path.join(
        ROOT, "benchmark", "configs", "kimi-k2-ep32-serve.json"))["model"],
        **TINY_KIMI), "precision": {"parameters": "bfloat16"}}
    model = config["model"]
    leaves_of = weights.for_reference(kimi_k2, config, seed=2147483659)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 96, (2, 24)), jnp.int32)
    positions = jnp.asarray([[3, 10, 23], [0, 5, 22]], jnp.int32)
    got = kimi_k2.logits_at(leaves_of, ids, positions, model)
    cfg = kimi_k2.program_config(model)
    want = ref.forward(kimi_k2.as_used(leaves_of()), ids, cfg,
                       held=cfg.held_experts)
    want = jnp.take_along_axis(want, positions[..., None], axis=1)
    assert got.shape == want.shape == (2, 3, 96)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    # the control's policy runs, and is not the reference
    low = kimi_k2.logits_at(leaves_of, ids, positions, model, "fp8")
    assert 1e-4 < float(jnp.max(jnp.abs(low - want))) < 1.0


def test_kimi_k2_copy_gives_an_expert_its_tokens_or_raises():
    """Over 4,096 tokens a block the copy computes an expert over the
    tokens routed to it, an eighth of the block at most: the padding
    behind the last position asked for is routed nowhere, the logits are
    the repo's, and an expert routed more than its room RAISES."""
    import kimi_k2_reference as ref

    from benchmark.families import kimi_k2
    from benchmark.lib import weights

    model = dict(load(os.path.join(
        ROOT, "benchmark", "configs", "kimi-k2-ep32-serve.json"))["model"],
        **dict(TINY_KIMI, max_position_embeddings=1536,
               num_hidden_layers=2))
    config = {"model": model, "precision": {"parameters": "bfloat16"}}
    leaves_of = weights.for_reference(kimi_k2, config, seed=11)
    rng = np.random.default_rng(3)
    ids = np.zeros((3, 1536), np.int32)          # 4,608 tokens: room 576
    ids[:, :100] = rng.integers(1, 96, (3, 100))
    positions = jnp.asarray([[5, 99], [0, 80], [42, 60]], jnp.int32)
    got = kimi_k2.logits_at(leaves_of, jnp.asarray(ids), positions, model)
    cfg = kimi_k2.program_config(model)
    want = ref.forward(kimi_k2.as_used(leaves_of()),
                       jnp.asarray(ids[:, :100]), cfg,
                       held=cfg.held_experts)
    want = jnp.take_along_axis(want, positions[..., None], axis=1)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    # every position asked for: the zeros behind all route alike
    with pytest.raises(ValueError, match="an expert was routed"):
        kimi_k2.logits_at(leaves_of, jnp.asarray(ids),
                          jnp.asarray([[5, 1535]] * 3, jnp.int32), model)


def test_granite_hybrid_copy_of_the_reference_gives_the_repos_logits():
    """The same float32 leaves, with the family's initialisation added
    (``as_used``), through ``benchmark/families/granite_hybrid.py``'s
    ``logits_at`` (a layer's leaves at a time, the recurrence stopped at
    the block's last position asked for, zeros padded behind the rows) and
    through ``tests/refs/granite_hybrid_reference.py``: the same logits,
    to float32 round-off of values of order 0.1 (2e-6)."""
    import granite_hybrid_reference as ref

    from benchmark.families import granite_hybrid
    from benchmark.lib import weights

    config = {"model": dict(load(os.path.join(
        ROOT, "benchmark", "configs",
        "granite-4.0-h-micro-serve.json"))["model"], **TINY_GRANITE),
        "precision": {"parameters": "bfloat16"}}
    model = config["model"]
    leaves_of = weights.for_reference(granite_hybrid, config,
                                      seed=2147483659)
    rng = np.random.default_rng(0)
    ids = np.zeros((2, 40), np.int32)
    ids[:, :24] = rng.integers(1, 96, (2, 24))
    positions = jnp.asarray([[3, 10, 23], [0, 5, 22]], jnp.int32)
    got = granite_hybrid.logits_at(leaves_of, jnp.asarray(ids), positions,
                                   model)
    cfg = granite_hybrid.program_config(model)
    used = granite_hybrid.as_used(leaves_of())
    want = ref.forward(used, jnp.asarray(ids[:, :24]), cfg)
    want = jnp.take_along_axis(want, positions[..., None], axis=1)
    assert got.shape == want.shape == (2, 3, 96)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    # the initialisation the family adds: A over [1, 16], steps over
    # [0.001, 0.1], the convolution's taps at a standard deviation of 0.29
    pre = "model.layers.0.mamba."
    a = np.exp(np.asarray(used[pre + "A_log"]))
    assert 0.9 < a[0] < 1.1 and 15 < a[-1] < 17
    step = np.log1p(np.exp(np.asarray(used[pre + "dt_bias"])))
    assert 0.9e-3 < step[0] < 1.1e-3 and 0.09 < step[-1] < 0.11
    assert 0.2 < float(np.std(np.asarray(used[pre + "conv1d.weight"]))) < 0.4
    # the control's policy runs, and is not the reference
    low = granite_hybrid.logits_at(leaves_of, jnp.asarray(ids), positions,
                                   model, "fp8")
    assert 1e-4 < float(jnp.max(jnp.abs(low - want))) < 1.0


def _tiny_mellum():
    from benchmark.families import mellum
    from benchmark.lib import weights

    config = {"model": dict(load(os.path.join(
        ROOT, "benchmark", "configs",
        "mellum2-12b-a2.5b-serve.json"))["model"], **TINY_MELLUM),
        "precision": {"parameters": "bfloat16"}}
    leaves_of = weights.for_reference(mellum, config, seed=2147483659)
    rng = np.random.default_rng(0)
    ids = np.zeros((2, 40), np.int32)
    ids[:, :30] = rng.integers(1, 96, (2, 30))
    positions = jnp.asarray([[3, 10, 29], [0, 17, 28]], jnp.int32)
    return mellum, config["model"], leaves_of, ids, positions


def test_mellum_copy_of_the_reference_gives_the_repos_logits():
    """The same float32 leaves through ``benchmark/families/mellum.py``'s
    ``logits_at`` (a layer's leaves at a time, attention a block of
    queries at a time, the experts one after another, the head at the
    positions asked for, zeros padded behind the rows) and through
    ``tests/refs/mellum_reference.py``: the same logits at positions
    inside the window and three windows on, to float32 round-off of
    values of order 0.3 (2e-6)."""
    import mellum_reference as ref

    mellum, model, leaves_of, ids, positions = _tiny_mellum()
    got = mellum.logits_at(leaves_of, jnp.asarray(ids), positions, model)
    cfg = mellum.program_config(model)
    want = ref.forward(mellum.as_used(leaves_of()), jnp.asarray(ids[:, :30]),
                       cfg)
    want = jnp.take_along_axis(want, positions[..., None], axis=1)
    assert got.shape == want.shape == (2, 3, 96)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    # the control's policy runs, and is not the reference
    low = mellum.logits_at(leaves_of, jnp.asarray(ids), positions, model,
                           "fp8")
    assert 1e-4 < float(jnp.max(jnp.abs(low - want))) < 1.0
    # the reference sees the window: with every layer's MASK full, and
    # with every layer's windowed (the rotary tables stay each kind's),
    # the logits inside the first window are the same and those three
    # windows on are others
    windowed = mellum._window_of
    try:
        for window_of in (lambda m, kind: None,
                          lambda m, kind: m["sliding_window"]):
            mellum._window_of = window_of
            mellum._layer.clear_cache()     # traced under the other mask
            other = mellum.logits_at(leaves_of, jnp.asarray(ids), positions,
                                     model)
            assert float(jnp.max(jnp.abs(other - got)[:, 0])) < 2e-6
            assert float(jnp.max(jnp.abs(other - got)[:, 2])) > 1e-4
    finally:
        mellum._window_of = windowed
        mellum._layer.clear_cache()


def test_mellum_copy_of_the_reference_gives_the_programs_logits():
    """The benchmark's leaves installed into the program's model
    (``install_weights``, bfloat16 as the configuration serves them) and
    its whole forward in float32 arithmetic against the family's
    ``logits_at`` over the same leaves held in float32."""
    from benchmark.lib import weights
    from benchmark.lib.common import install_weights
    from paddle_tpu.text.mellum import MellumForCausalLM

    mellum, model, leaves_of, ids, positions = _tiny_mellum()
    with paddle.LazyGuard():
        program = MellumForCausalLM(mellum.program_config(model))
    config = {"model": model, "precision": {"parameters": "float32"}}
    install_weights(program, mellum.as_used(
        {n: a.astype(jnp.float32) for n, a in weights.for_program(
            mellum, dict(config, precision={"parameters": "bfloat16"}),
            2147483659).items()}))
    program.eval()
    got = program(paddle.to_tensor(ids[:, :30]))._value
    got = jnp.take_along_axis(got, positions[..., None], axis=1)
    want = mellum.logits_at(leaves_of, jnp.asarray(ids), positions, model)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6


def test_gpt3_copy_of_the_reference_gives_the_programs_logits():
    """GPT-3's copy against the program itself in float32 on the CPU (the
    repo's own reference of it, ``tests/numpy_gpt.py``, is a training
    harness): logits of order 1, agreement to 2e-5."""
    from benchmark.families import gpt3
    from benchmark.lib import weights
    from benchmark.lib.common import install_weights

    config = load(os.path.join(ROOT, "benchmark", "configs",
                               "gpt3-1.3b-serve.json"))
    model = tiny_model(config)
    table = gpt3.leaf_table(model)
    leaves = weights.make_weights(table, 7, "float32")
    program = program_model(config, model)
    install_weights(program, leaves)
    program.eval()
    ids = np.random.default_rng(1).integers(0, 128, (2, 16)).astype(np.int32)
    positions = jnp.asarray([[0, 7, 15], [1, 2, 3]], jnp.int32)
    want = jnp.take_along_axis(program(paddle.to_tensor(ids))._value,
                               positions[..., None], axis=1)
    got = gpt3.logits_at(lambda only=None: leaves, jnp.asarray(ids),
                         positions, model)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
