"""The ``kimi_k2`` family and its cell at a size the CPU holds, through
``run.execute`` (everything of a run after the look for a chip): the sound
run is correct and reads its counters' shares, the fp8 control is judged,
and the work functions count what the issue's table counts."""
import copy
import time

import pytest
import tiny

CELL = "kimi-k2-ep32-serve.code-batch-256"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

#: every mechanism at toy widths: 1 dense + 2 expert layers, 4 of 16
#: experts held, top-4
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=48, num_hidden_layers=3,
            num_attention_heads=4, n_routed_experts=4, router_width=16,
            held_experts_first=4, num_experts_per_tok=4, kv_lora_rank=32,
            q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, max_position_embeddings=128)


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
                           num_pages=80)
    config["serve"].pop("pool_share_of_device_limit")
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


def test_file_holds_the_published_widths():
    from benchmark.families import kimi_k2

    config = tiny.load("configs", "kimi-k2-ep32-serve.json")
    kimi_k2.check(config)
    entry = next(c for c in tiny.bench_json()["configs"]
                 if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"]
    assert config["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 384,
        "vocab_size": 163840, "max_position_embeddings": 262144}
    for key, width in dict(
            hidden_size=7168, num_attention_heads=64, q_lora_rank=1536,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, intermediate_size=18432,
            moe_intermediate_size=2048, num_experts_per_tok=8,
            n_shared_experts=1, rope_theta=50000).items():
        assert config[key] == config["model"][key] == width, key
    assert config["model"]["router_width"] == 384


def test_work_counts_what_the_table_counts():
    """The issue's own count at 2 bytes a parameter: 4,173M parameters,
    101.12M in a layer's attention, 8.05 GB read a decode step."""
    from benchmark.families import kimi_k2
    from benchmark.lib.weights import num_params

    m = tiny.load("configs", "kimi-k2-ep32-serve.json")["model"]
    assert round(num_params(kimi_k2.leaf_table(m)) / 1e6) == 4173
    assert round(kimi_k2._counts(m)["attention"] / 1e6, 2) == 101.12
    assert round(kimi_k2._held_weight_bytes(m) / 1e9, 2) == 8.05
    traced = {"decode_steps": 1, "decode_tokens": 256,
              "decode_ctx_tokens": 256 * 1030}
    w = kimi_k2.decode_steps(m, traced)
    # the latent rows of 264k live tokens over six layers: 1.8 GB
    assert round((w["bytes"] - 8.05e9) / 1e9, 1) == 1.8
    a = kimi_k2.mla_attention(m, traced)
    assert a["flops"] == 6 * 2 * 64 * (576 + 512) * 256 * 1030
    e = kimi_k2.expert_matmul(m, traced)
    # 12 experts x 5 layers x 88 MB once a launch
    assert round(e["bytes"] / 1e9, 1) == 5.3
    assert e["flops"] == 2 * 3 * 7168 * 2048 * 256 * 0.25 * 5


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
    assert c["serving_moe_assignments_total"] > 0
    assert 0 < c["serving_moe_local_assignments_total"] \
        < c["serving_moe_assignments_total"]
    assert 0 < c["serving_moe_expert_hits_total"] \
        <= c["serving_moe_expert_slots_total"]


def test_the_control_is_judged():
    """The fp8 control goes through the comparison as a run does (on the
    chip it has to come out not correct; the toy's logits are too flat for
    a limit to mean anything here, so only the path is held)."""
    import run as runpy

    run, bench = tiny_run(control=True)
    res = runpy.execute(run, bench, DEVICE)
    assert res["compared"]["served_gap_meansq"]["value"] is not None
