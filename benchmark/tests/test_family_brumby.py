"""The ``brumby`` family and its cell at a size the CPU holds, through
``run.execute`` (everything of a run after the look for a chip): the sound
run is correct and reads its counters' share, both controls are judged,
the file holds the published sizes, and the work functions count what a
count by hand counts."""
import copy
import time

import pytest
import tiny

CELL = "brumby-14b-serve.continue-batch-16"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

#: every mechanism at toy widths: 2 layers of 10 query heads over 2 KV
#: heads of 16 (D = 136), chunks of 8
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=10,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=128,
            chunk_size=8)


def tiny_run(seed=3, seconds=1.5, control=False):
    from benchmark.lib.common import Run
    from paddle_tpu.utils.flags import set_flags

    set_flags({"FLAGS_ragged_interpret": True})
    bench = tiny.bench_json()
    name, traffic = CELL.rsplit(".", 1)
    config = copy.deepcopy(tiny.load("configs", name + ".json"))
    mix = copy.deepcopy(tiny.load("traffic", traffic + ".json"))
    check = copy.deepcopy(tiny.load("limits", CELL + ".json"))
    config["model"].update(TINY)
    config["serve"].update(max_batch=4, max_prompt_len=64)
    mix["tail_tokens"].update(min=40, max=64)
    mix["output_tokens"].update(median=8, min=3, max=16)
    mix["programs"] = ["prefill[64]", "decode"]
    mix["warmup"] = [{"prefix": False, "tail_tokens": 50, "output_tokens": 3}]
    mix.update(clients=8, pool=32)
    check["compared_requests"] = 4
    return Run(root=tiny.ROOT, workload=CELL, seed=seed, seconds=seconds,
               trace=False, config=config, mix=mix, check=check,
               peaks=tiny.load("peaks.json")["TPU v5 lite"],
               t_process=time.time(), control=control), bench


@pytest.fixture(autouse=True)
def _path(monkeypatch):
    monkeypatch.syspath_prepend(tiny.BENCH)


def test_file_holds_the_published_sizes():
    from benchmark.families import brumby
    from benchmark.lib.weights import num_params

    config = tiny.load("configs", "brumby-14b-serve.json")
    brumby.check(config)
    entry = next(c for c in tiny.bench_json()["configs"]
                 if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40}
    for key, size in dict(
            hidden_size=5120, intermediate_size=17408, num_hidden_layers=8,
            num_attention_heads=40, num_key_value_heads=8, head_dim=128,
            vocab_size=151936, rope_theta=1000000, rms_norm_eps=1e-6,
            max_position_embeddings=32768).items():
        assert config[key] == config["model"][key] == size, key
    assert config["tie_word_embeddings"] is False
    assert config["model"]["power"] == 2
    # 8 layers of 330,352,904, the embedding, the untied head, the norm
    m = config["model"]
    assert num_params(brumby.leaf_table(dict(m, num_hidden_layers=1))) \
        - num_params(brumby.leaf_table(dict(m, num_hidden_layers=0))) \
        == 330352904
    assert num_params(brumby.leaf_table(m)) == 4198652992
    assert "4,198,652,992" in config["deployment"]
    assert config["serve"]["max_batch"] == 16
    assert "num_pages" not in config["serve"]


def test_the_gate_bias_spreads_the_decay():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import brumby

    leaves = {"model.layers.0.self_attn.gate.bias":
              jnp.zeros((8,), jnp.bfloat16),
              "model.layers.0.self_attn.q_norm.weight":
              jnp.ones((4,), jnp.bfloat16)}
    used = brumby.as_used(leaves)
    gamma = 1.0 / (1.0 + np.exp(-np.asarray(
        used["model.layers.0.self_attn.gate.bias"], np.float32)))
    # 0.99 to 0.9999, as far as bfloat16 holds the bias
    assert abs(gamma[0] - 0.99) < 1e-3 and abs(gamma[-1] - 0.9999) < 1e-4
    assert np.all(np.diff(gamma) > 0)
    assert used["model.layers.0.self_attn.q_norm.weight"] is leaves[
        "model.layers.0.self_attn.q_norm.weight"]


def test_work_counts_what_a_count_by_hand_counts():
    from benchmark.families import brumby as g

    m = tiny.load("configs", "brumby-14b-serve.json")["model"]
    c = g._counts(m)
    assert c["D"] == 8256 and c["layers"] == 8
    assert c["matmul"] + c["small"] == 330352904
    # a slot's S and z a layer: 34,080,768 B; 272.6 MB over 8 layers
    assert g._state_bytes(m) == 34080768
    # every layer's weights and the head once: 6.84 GB a step
    assert round(g._weight_bytes(m) / 1e9, 2) == 6.84
    assert g._state_flops(m) == 2 * 8256 * 128 * (8 + 40)
    traced = {"decode_steps": 1, "decode_tokens": 16, "prefills": 1,
              "prefill_tokens": 1000}
    d = g.decode_steps(m, traced)
    state = 2 * 8 * 34080768 * 16
    assert d["bytes"] == g._weight_bytes(m) + state
    # the state is 56% of a full step's floor bytes
    assert round(state / d["bytes"], 2) == 0.56
    k = g.power_retention_decode(m, traced)
    assert k["bytes"] == 8 * 16 * (2 * 34080768
                                   + 4 * (2 * 40 * 128 + 2 * 8 * 128 + 8))
    assert k["flops"] == 8 * 16 * 2 * 8256 * 128 * 48
    s = g.serve_model(m, traced)
    per_token = 2 * (330352904 - c["small"]) + 2 * 8256 * 128 * 48
    chunk = 4 * 40 * 128 * 129 / 2
    assert s["flops"] == 8 * (per_token * 1016 + chunk * 1000) \
        + 2 * 5120 * 151936 * 17


def test_sound_run_is_correct_and_counts():
    import run as runpy

    run, bench = tiny_run()
    # bfloat16 weights and activations through two layers against the
    # float32 reference: a near-tie may fall the other way; a state left
    # behind reads 1e-2 and more (test_the_controls_are_judged)
    run.check["limits"]["served_gap_meansq"] = 1e-4
    res = runpy.execute(run, bench, DEVICE)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"itl_p95_ms", "serve_out_tokens_per_s", "setup_s"} <= set(
        res["metrics"])
    c = run.facts["counters"]
    # the kernel skips dead slots: every row it moved was live
    live = c["serving_retention_state_rows_live_total"]
    assert 0 < live == c["serving_retention_state_rows_moved_total"]


@pytest.mark.parametrize("policy", ["fp8", "state_reset"])
def test_the_controls_are_judged(policy):
    """Each control goes through the comparison as a run does (on the chip
    it has to come out not correct; the path is held here, and the state
    left behind at the prompt's end reads far from the program)."""
    import run as runpy

    run, bench = tiny_run(control=True)
    run.config["precision"]["controls"] = [policy]
    res = runpy.execute(run, bench, DEVICE)
    assert res["compared"]["served_gap_meansq"]["value"] is not None
