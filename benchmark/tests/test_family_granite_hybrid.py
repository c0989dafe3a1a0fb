"""The ``granite_hybrid`` family and its cell at a size the CPU holds,
through ``run.execute`` (everything of a run after the look for a chip):
the sound run is correct and reads its counters' share, the fp8 control is
judged, the file holds the published sizes, and the work functions count
what the issue's table counts."""
import copy
import time

import pytest
import tiny

CELL = "granite-4.0-h-micro-serve.chat-batch-64"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

#: every mechanism at toy widths: two periods of ``m m a m``
TINY = dict(vocab_size=256, hidden_size=32, num_hidden_layers=8,
            layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
            num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=48, mamba_n_heads=4, mamba_d_head=16,
            mamba_d_state=8, mamba_chunk_size=16,
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


def test_file_holds_the_published_sizes():
    from benchmark.families import granite_hybrid

    config = tiny.load("configs", "granite-4.0-h-micro-serve.json")
    granite_hybrid.check(config)
    entry = next(c for c in tiny.bench_json()["configs"]
                 if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"] == [
        "max_position_embeddings"]
    assert config["published"] == {"max_position_embeddings": 131072}
    for key, size in dict(
            hidden_size=2048, num_hidden_layers=40, vocab_size=100352,
            num_attention_heads=32, num_key_value_heads=8,
            shared_intermediate_size=8192, mamba_n_heads=64,
            mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
            mamba_expand=2, mamba_chunk_size=256, mamba_n_groups=1,
            embedding_multiplier=12, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8).items():
        assert config[key] == config["model"][key] == size, key
    kinds = config["layer_types"]
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35] and kinds.count("mamba") == 36


def test_work_counts_what_the_table_counts():
    """The issue's own count at 2 bytes a parameter: 3,191M parameters,
    6.38 GB; 75.5 MB of state a slot; 8 KB of keys and values a token;
    128 KB a page."""
    from benchmark.families import granite_hybrid as g
    from benchmark.lib.weights import num_params

    m = tiny.load("configs", "granite-4.0-h-micro-serve.json")["model"]
    assert round(num_params(g.leaf_table(m)) / 1e6) == 3191
    assert round(g._weight_bytes(m) / 1e9, 2) == 6.38
    assert round(36 * g._state_bytes(m) / 1e6, 1) == 76.4   # + conv rows
    assert g._kv_bytes(m) == 8192 and g.page_bytes(m, 16) == 131072
    traced = {"decode_steps": 1, "decode_tokens": 64,
              "decode_ctx_tokens": 64 * 700}
    w = g.decode_steps(m, traced)
    # 64 slots' state and convolution rows read and written: 9.78 GB; their
    # contexts: 0.37 GB
    assert round((w["bytes"] - g._weight_bytes(m)) / 1e9, 1) == 10.2
    u = g.ssm_state_update(m, traced)
    assert u["flops"] == 36 * 5 * 4096 * 128 * 64
    assert round(u["bytes"] / 1e9, 2) == 9.74
    s = g.serve_model(m, {"decode_tokens": 1, "decode_ctx_tokens": 1})
    assert s["flops"] == 2 * g._matmul_params(m) \
        + 36 * 5 * 4096 * 128 + 4 * 4 * 2048


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
    # the interpreted kernel skips dead slots: what moved is what was live
    assert 0 < c["serving_ssm_state_rows_live_total"] \
        == c["serving_ssm_state_rows_moved_total"]


def test_the_control_is_judged():
    """The fp8 control goes through the comparison as a run does (on the
    chip it has to come out not correct; the toy's logits are too flat for
    a limit to mean anything here, so only the path is held)."""
    import run as runpy

    run, bench = tiny_run(control=True)
    res = runpy.execute(run, bench, DEVICE)
    assert res["compared"]["served_gap_meansq"]["value"] is not None
