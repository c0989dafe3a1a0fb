"""The ``gpt3`` family's work functions against counts worked by hand for
both configurations."""
import tiny

from benchmark.families import gpt3 as work
from benchmark.lib import weights

TRAIN = tiny.load("configs", "gpt3-350m-train.json")["model"]
SERVE = tiny.load("configs", "gpt3-1.3b-serve.json")["model"]


def params_by_hand(vocab, ctx, h, layers):
    block = (2 * h) + (h * 3 * h + 3 * h) + (h * h + h) + (2 * h) \
        + (h * 4 * h + 4 * h) + (4 * h * h + h)
    return vocab * h + ctx * h + layers * block + 2 * h


def num_params(model):
    return weights.num_params(work.leaf_table(model))


def test_num_params():
    assert num_params(TRAIN) == params_by_hand(50304, 1024, 1024, 24)
    assert num_params(TRAIN) == 354_871_296
    assert num_params(SERVE) == params_by_hand(50304, 1024, 2048, 24)
    assert num_params(SERVE) == 1_313_722_368


def test_train_model_flops():
    t = {"calls": 1, "batch": 8, "seq": 1024}
    per_token = 6 * 354_871_296 + 12 * 24 * 1024 * 1024
    assert work.train_model(TRAIN, t)["flops"] == per_token * 8192
    # a step cut by the end of the traced window counts by its share
    assert work.train_model(TRAIN, dict(t, calls=16.25))["flops"] \
        == per_token * 8192 * 16.25
    assert work.train_model(TRAIN, {"batch": 8, "seq": 1024})["flops"] == 0
    assert abs(per_token - 2.431e9) < 1e6


def test_flash_train():
    t = {"calls": 2, "batch": 8, "seq": 1024}
    w = work.flash_train(TRAIN, t)
    # per layer: 7 products x 8 rows x 16 heads x 1024^2 x 64
    assert w["flops"] == 7 * 8 * 16 * 1024 * 1024 * 64 * 24 * 2
    # twelve [8, 1024, 1024] bf16 tensors a layer
    assert w["bytes"] == 12 * 8 * 1024 * 1024 * 2 * 24 * 2


def test_decode_steps():
    # 3 steps, 8 slots live each, contexts of 500 tokens
    t = {"decode_steps": 3, "decode_tokens": 24,
         "decode_ctx_tokens": 24 * 500}
    w = work.decode_steps(SERVE, t)
    n = 1_313_722_368
    assert w["bytes"] == n * 4 * 3 + 2 * 24 * 2048 * 4 * 24 * 500
    assert w["flops"] == 2 * n * 24 + 4 * 24 * 2048 * 24 * 500


def test_serve_model_flops():
    t = {"prefill_tokens": 100, "prefill_ctx_tokens": 100 * 384 + 5050,
         "decode_tokens": 10, "decode_ctx_tokens": 5000}
    w = work.serve_model(SERVE, t)
    assert w["flops"] == 2 * 1_313_722_368 * 110 \
        + 4 * 24 * 2048 * (100 * 384 + 5050 + 5000)


def test_ragged_attention():
    t = {"decode_tokens": 8, "decode_ctx_tokens": 4000,
         "prefill_tokens": 100, "prefill_kv_tokens": 484,
         "prefill_ctx_tokens": 100 * 384 + 5050}
    w = work.ragged_attention(SERVE, t)
    assert w["bytes"] == 24 * 2048 * 4 * (2 * 4484 + 2 * 108)
    assert w["flops"] == 4 * 24 * 2048 * (4000 + 100 * 384 + 5050)


def test_nothing_traced_is_nothing():
    assert work.serve_model(SERVE, {})["flops"] == 0
    assert work.ragged_attention(SERVE, {})["bytes"] == 0
