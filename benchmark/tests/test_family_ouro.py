"""The ``ouro`` family and its cell at a size the CPU holds, through
``run.execute`` (everything of a run after the look for a chip): the sound
run is correct and reads its counters' shares, the fp8 control is judged,
the file holds the published sizes, and the work functions count what a
count by hand counts."""
import copy
import time

import pytest
import tiny

CELL = "ouro-2.6b-serve.reason-batch-8"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

#: every mechanism at toy widths: 3 layers run 3 times
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
            head_dim=16, total_ut_steps=3, max_position_embeddings=96)


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
    config["serve"].update(max_batch=4, max_prompt_len=64, page_size=8,
                           num_pages=49)
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
    from benchmark.families import ouro
    from benchmark.lib.weights import num_params

    config = tiny.load("configs", "ouro-2.6b-serve.json")
    ouro.check(config)
    entry = next(c for c in tiny.bench_json()["configs"]
                 if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"] == [
        "max_position_embeddings"]
    assert config["published"] == {"max_position_embeddings": 65536}
    for key, size in dict(
            hidden_size=2048, intermediate_size=5632, num_hidden_layers=48,
            num_attention_heads=16, num_key_value_heads=16, head_dim=128,
            vocab_size=49152, rope_theta=1000000, rms_norm_eps=1e-6,
            total_ut_steps=4, early_exit_threshold=1,
            max_position_embeddings=640).items():
        assert config[key] == config["model"][key] == size, key
    assert config["tie_word_embeddings"] is False
    assert config["layer_types"] == ["full_attention"] * 48
    # the whole model: 48 layers, the embedding, the untied head, the
    # final norm and the exit gate
    assert num_params(ouro.leaf_table(config["model"])) == 2667974657
    assert "2,667,974,657" in config["deployment"]
    sv = config["serve"]
    assert (sv["max_batch"], sv["num_pages"]) == (8, 321)
    # a token is 1.5 MiB: every layer's keys and values for all 4 loops
    assert ouro.page_bytes(config["model"], 1) == 1572864
    assert (sv["num_pages"] - 1) * ouro.page_bytes(
        config["model"], 16) == 8053063680


def test_work_counts_what_a_count_by_hand_counts():
    from benchmark.families import ouro as g

    m = tiny.load("configs", "ouro-2.6b-serve.json")["model"]
    c = g._counts(m)
    assert (c["layers"], c["loops"], c["passes"]) == (48, 4, 192)
    assert c["attention"] == 4 * 2048 * 2048 == 16777216
    assert c["mlp"] == 3 * 2048 * 5632 == 34603008
    # every layer's weights once a loop and the head once: 19.9 GB a step
    assert round(g._weight_bytes(m) / 1e9, 2) == 19.93
    assert g._kv_row_bytes(m) == 8192 and g._pair_flops(m) == 8192
    # one decode step of 8 rows at 290 tokens each, one prefill of 200
    traced = {"decode_steps": 1, "decode_tokens": 8,
              "decode_ctx_tokens": 8 * 290, "prefills": 1,
              "prefill_tokens": 200, "prefill_ctx_tokens": 200 * 201 // 2}
    d = g.decode_steps(m, traced)
    kv = 8192 * 192 * 8 * 290
    assert d["bytes"] == g._weight_bytes(m) + kv
    assert round(kv / 1e9, 2) == 3.65
    a = g.gqa_decode_attention(m, traced)
    assert a["bytes"] == kv + 192 * 8 * 2 * 16 * 128 * 2
    assert a["flops"] == 8192 * 192 * 8 * 290
    half = g.gqa_decode_attention(m, dict(traced, decode_steps=2, calls=1))
    assert half["bytes"] == a["bytes"] / 2
    s = g.serve_model(m, traced)
    per_token = 2 * 192 * (16777216 + 34603008)
    assert s["flops"] == per_token * 208 + 2 * 2048 * 49152 * 9 \
        + 8192 * 192 * (200 * 201 // 2 + 8 * 290)
    # a prefill of 256 tokens: about 5.05 TFLOP
    p256 = g.serve_model(m, {"prefills": 1, "prefill_tokens": 256,
                             "prefill_ctx_tokens": 256 * 257 // 2})
    assert round(p256["flops"] / 1e12, 2) == 5.1


def test_sound_run_is_correct_and_counts():
    import run as runpy

    run, bench = tiny_run()
    # bfloat16 weights and activations through nine layer applications
    # against the float32 reference: a near-tie may fall the other way
    # (3e-7 read); a wrong loop count or loop's keys reads 1e-3 and more
    run.check["limits"]["served_gap_meansq"] = 1e-5
    res = runpy.execute(run, bench, DEVICE)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"itl_p95_ms", "serve_out_tokens_per_s", "setup_s"} <= set(
        res["metrics"])
    c = run.facts["counters"]
    assert 0 < c["serving_kv_pages_in_use_total"] \
        <= c["serving_kv_pages_usable_total"]
    assert 0 < c["serving_attention_pages_live_total"] \
        <= c["serving_attention_pages_staged_total"]


def test_the_control_is_judged():
    """The fp8 control goes through the comparison as a run does (on the
    chip it has to come out not correct; only the path is held here)."""
    import run as runpy

    run, bench = tiny_run(control=True)
    res = runpy.execute(run, bench, DEVICE)
    assert res["compared"]["served_gap_meansq"]["value"] is not None
