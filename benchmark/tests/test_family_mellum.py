"""The ``mellum`` family and its cell at a size the CPU holds, through
``run.execute`` (everything of a run after the look for a chip): the sound
run is correct, reads its counters' shares and frees window pages, the fp8
control is judged, the file holds the published sizes, and the work
functions count what a count by hand counts."""
import copy
import time

import pytest
import tiny

CELL = "mellum2-12b-a2.5b-serve.code-context-48"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

#: every mechanism at toy widths: two periods of ``S S S F``
TINY = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
            num_hidden_layers=8,
            layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=8, num_experts_per_tok=2, sliding_window=16,
            max_position_embeddings=128)


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
                           num_pages=80, group_pages={"window": 40})
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
    from benchmark.families import mellum
    from benchmark.lib.weights import num_params

    config = tiny.load("configs", "mellum2-12b-a2.5b-serve.json")
    mellum.check(config)
    entry = next(c for c in tiny.bench_json()["configs"]
                 if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert config["published"] == {"num_hidden_layers": 28,
                                   "max_position_embeddings": 131072}
    for key, size in dict(
            hidden_size=2304, num_attention_heads=32, num_key_value_heads=4,
            head_dim=128, moe_intermediate_size=896, num_experts=64,
            num_experts_per_tok=8, sliding_window=1024, vocab_size=98304,
            rms_norm_eps=1e-6, num_hidden_layers=12,
            max_position_embeddings=5120).items():
        assert config[key] == config["model"][key] == size, key
    assert config["intermediate_size"] == 7168      # as published, unused
    assert config["norm_topk_prob"] is True
    assert len(config["layer_types"]) == 28 == len(config["mlp_layer_types"])
    kinds = config["model"]["layer_types"]
    assert kinds == (["sliding_attention"] * 3 + ["full_attention"]) * 3
    rp = config["rope_parameters"]
    assert rp["full_attention"]["attention_factor"] == 1.2772588722239782
    assert rp["sliding_attention"] == {"rope_type": "default",
                                       "rope_theta": 500000}
    # the deployment's count: the stage's 12 layers, embedding and head
    assert num_params(mellum.leaf_table(config["model"])) == 5465956608
    assert "5,465,956,608" in config["deployment"]
    sv = config["serve"]
    assert (sv["max_batch"], sv["num_pages"], sv["group_pages"]) == (
        48, 15361, {"window": 3457})
    # 48 slots x 320 pages, and 48 x 72: the pools' bytes by group
    assert (sv["num_pages"] - 1) * mellum.page_bytes(
        config["model"], 16, "full_attention") == 1509949440
    assert (sv["group_pages"]["window"] - 1) * mellum.page_bytes(
        config["model"], 16, "sliding_attention") == 1019215872


def test_work_counts_what_a_count_by_hand_counts():
    from benchmark.families import mellum as g

    m = tiny.load("configs", "mellum2-12b-a2.5b-serve.json")["model"]
    c = g._counts(m)
    assert (c["full"], c["window"], c["layers"]) == (3, 9, 12)
    assert c["attention"] == 2304 * 4096 * 2 + 2 * 2304 * 512 == 21233664
    assert c["expert"] == 3 * 2304 * 896 == 6193152
    # every weight but the embedding table: 10.48 GB a decode step
    assert round(g._weight_bytes(m) / 1e9, 2) == 10.48
    assert g._kv_row_bytes(m) == 2048 and g._pair_flops(m) == 16384
    # one decode step of 48 rows at 4,600 tokens each, one prefill of 4,000
    traced = {"decode_steps": 1, "decode_tokens": 48,
              "decode_ctx_tokens": 48 * 4600, "prefills": 1,
              "prefill_tokens": 4000,
              "prefill_ctx_tokens": 4000 * 4001 // 2}
    pairs = g._pairs(m, traced)
    assert pairs["decode"] == (48 * 4600, 48 * 1024)
    assert pairs["prefill"] == (4000 * 4001 // 2,
                                1024 * 4000 - 1024 * 1023 // 2)
    # the window layer's prefill pairs, counted pair by pair
    assert pairs["prefill"][1] == sum(min(i + 1, 1024) for i in range(4000))
    d = g.decode_steps(m, traced)
    kv = 2048 * (3 * 48 * 4600 + 9 * 48 * 1024)
    assert d["bytes"] == g._weight_bytes(m) + kv
    assert round(kv / 1e9, 2) == 2.26
    a = g.gqa_decode_attention(m, traced)
    assert a["bytes"] == kv + 12 * 48 * 2 * 32 * 128 * 2
    assert a["flops"] == 16384 * (3 * 48 * 4600 + 9 * 48 * 1024)
    # a metric that names its module hands over the calls the trace holds
    half = g.gqa_decode_attention(m, dict(traced, decode_steps=2, calls=1))
    assert half["bytes"] == a["bytes"] / 2
    f = g.prefill_attention(m, traced)
    assert f["flops"] == 16384 * (3 * pairs["prefill"][0]
                                  + 9 * pairs["prefill"][1])
    assert round(f["flops"] / 1e12, 2) == 0.92
    e = g.expert_matmul(m, traced)
    assert e["flops"] == 2 * 6193152 * (4048 * 8 * 12)
    assert e["bytes"] == 2 * (2 * 12 * 64 * 6193152
                              + 2 * 2304 * 4048 * 8 * 12)
    s = g.serve_model(m, traced)
    per_token = 2 * 12 * (21233664 + 2304 * 64 + 8 * 6193152)
    assert s["flops"] == per_token * 4048 + 2 * 2304 * 98304 * 49 \
        + f["flops"] + a["flops"]
    # about 8 TFLOP a prefill, of which the head at one token is nothing
    assert 6.5e12 < g.serve_model(m, {k: v for k, v in traced.items()
                                      if "prefill" in k})["flops"] < 8.5e12


def test_sound_run_is_correct_and_counts():
    import run as runpy

    run, bench = tiny_run()
    run.check["limits"]["served_gap_meansq"] = 1e-9   # float32 on the CPU
    res = runpy.execute(run, bench, DEVICE)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"itl_p95_ms", "serve_out_tokens_per_s", "setup_s"} <= set(
        res["metrics"])
    c = run.facts["counters"]
    # the window layers' pages went back: what stayed resident is under
    # what one lifetime would hold
    assert 0 < c["serving_kv_resident_page_layers_total"] \
        < c["serving_kv_one_lifetime_page_layers_total"]
    assert 0 < c["serving_attention_pages_live_total"] \
        <= c["serving_attention_pages_staged_total"]
    assert c["serving_moe_expert_hits_total"] > 0


def test_the_control_is_judged():
    """The fp8 control goes through the comparison as a run does (on the
    chip it has to come out not correct; the toy's logits are too flat for
    a limit to mean anything here, so only the path is held)."""
    import run as runpy

    run, bench = tiny_run(control=True)
    res = runpy.execute(run, bench, DEVICE)
    assert res["compared"]["served_gap_meansq"]["value"] is not None
