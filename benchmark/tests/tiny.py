"""Tiny stand-ins for the two configurations, for runs on the CPU: the same
files and drivers at sizes a test can hold. Never a source of a device
number."""
from __future__ import annotations

import copy
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_MODEL = dict(vocab_size=512, hidden_size=256, num_layers=2,
                  num_heads=2, head_size=128, ffn_hidden=1024)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def register_presets():
    from paddle_tpu.text import gpt
    from paddle_tpu.utils.flags import set_flags

    gpt._PRESETS["bench-tiny"] = dict(
        hidden_size=TINY_MODEL["hidden_size"],
        num_layers=TINY_MODEL["num_layers"],
        num_heads=TINY_MODEL["num_heads"],
        vocab_size=TINY_MODEL["vocab_size"])
    set_flags({"FLAGS_ragged_interpret": True})


def tiny_run(workload: str, seed: int = 1, seconds: float = 1.5,
             trace: bool = False, limits_of: str = ""):
    """A ``Run`` of ``workload`` cut to the tiny model (and, for serving,
    to short prompts and a small pool)."""
    from benchmark.lib.common import Run

    register_presets()
    bench = bench_json()
    # a cell is named <config>.<traffic>. A mix that PERF.md keeps for a
    # later cell has no limits yet, and runs here under ``limits_of``'s
    name, traffic = workload.rsplit(".", 1)
    config = copy.deepcopy(load("configs", name + ".json"))
    mix = copy.deepcopy(load("traffic", traffic + ".json"))
    check = copy.deepcopy(load("limits", (limits_of or workload) + ".json"))
    config["program_preset"] = "bench-tiny"
    config["model"].update(TINY_MODEL)
    if config["driver"] == "train":
        config["model"]["max_seq_len"] = 64
        config["train"]["loss_chunk_size"] = 64
        mix.update(batch=4, seq=64)
    else:
        config["model"]["max_seq_len"] = 128
        config["serve"].update(max_batch=4, max_prompt_len=64, page_size=8,
                               num_pages=48)
        if mix.get("prefix"):
            mix["prefix"].update(count=6, tokens=32)
            mix["tail_tokens"].update(min=9, max=16)
            mix["programs"] = ["prefill[16]", "prefill[64]", "decode"]
            mix["warmup"] = [
                {"prefix": True, "tail_tokens": 12, "output_tokens": 3},
                {"prefix": True, "tail_tokens": 10, "output_tokens": 3}]
            mix["arrivals"]["rate_per_s"] = 6.0
        else:
            mix["tail_tokens"].update(min=40, max=64)
            mix["programs"] = ["prefill[64]", "decode"]
            mix["warmup"] = [{"prefix": False, "tail_tokens": 50,
                              "output_tokens": 3}]
            mix.update(clients=8, pool=32)
        mix["output_tokens"].update(median=8, min=3, max=16)
        check["compared_requests"] = 4
    peaks = load("peaks.json")["TPU v5 lite"]
    return Run(root=ROOT, workload=workload, seed=seed, seconds=seconds,
               trace=trace, config=config, mix=mix, check=check, peaks=peaks,
               t_process=time.time()), bench
