"""A family is files only, and the ``gpt3`` family is the program's.

- A second family arrives as new files (its module, a configuration, a mix,
  a limits file, two metric files and entries of ``BENCHMARK.json``) in a
  copy of the tree, runs through ``run.execute`` and the serving driver on
  the CPU, and its per-layer metrics read the family's own work function
  and the engine counter its metric file names, with no file that was
  there changed.
- The guards that the program's side needs: the ``gpt3`` family's leaves
  are the program's ``functional_state()``, the weights of a seed are what
  they were when the cells' limits were set, and a part of the table drawn
  alone has the values of the whole call.
"""
import glob
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
import zlib

import numpy as np
import pytest
import tiny

FAMILY = '''
"""A stand-in second family: the tiny GPT under another name, with a work
function of its own."""
from benchmark.families import gpt3
from benchmark.families.gpt3 import check, leaf_table, logits_at  # noqa: F401


def build_serving(run, leaves):
    from paddle_tpu.text import gpt as program
    from paddle_tpu.utils.flags import set_flags

    m = run.config["model"]
    program._PRESETS[run.config["program_preset"]] = {
        k: m[k] for k in ("hidden_size", "num_layers", "num_heads",
                          "vocab_size")}
    set_flags({"FLAGS_ragged_interpret": True})
    return gpt3.build_serving(run, leaves)


def second_flops(model, traced):
    return {"flops": 4242.0 * traced.get("decode_tokens", 0), "bytes": 0.0}


WORK = {"second_flops": second_flops}
'''

#: run.main() after its look for a chip, on the CPU
DRIVE = '''
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "benchmark"))
import run as runpy
from benchmark.lib.common import Run
bench, cell, config, mix, check = runpy.cell_files("second-serve.second-mix")
run = Run(root=runpy.ROOT, workload=cell["name"], seed=2147483659,
          seconds=1.0, trace=True, config=config, mix=mix, check=check,
          peaks=runpy.load(runpy.HERE, "peaks.json")["TPU v5 lite"],
          t_process=time.time())
res = runpy.execute(run, bench, {"platform": "cpu", "kind": "cpu",
                                 "count": 1})
print(json.dumps({"result": res, "traced": run.facts["traced"],
                  "counters": run.facts["counters"]}))
'''


def digests(root):
    return {os.path.relpath(p, root): hashlib.sha256(
        open(p, "rb").read()).hexdigest()
        for p in glob.glob(os.path.join(root, "**", "*"), recursive=True)
        if os.path.isfile(p) and "__pycache__" not in p}


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def test_a_second_family_is_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(tiny.BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(tiny.ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    before = digests(os.path.join(root, "benchmark"))
    bench = tiny.bench_json()

    # ---- the new files: the cell's run at the tiny size
    run, _ = tiny.tiny_run("gpt3-1.3b-serve.batch-unshared")
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "families", "second.py"), "w") as f:
        f.write(textwrap.dedent(FAMILY))
    config = dict(run.config, name="second-serve", family="second",
                  program_preset="second-tiny")
    write_json(os.path.join(b, "configs", "second-serve.json"), config)
    write_json(os.path.join(b, "traffic", "second-mix.json"), run.mix)
    write_json(os.path.join(b, "limits", "second-serve.second-mix.json"),
               run.check)
    metric = {"layer": "step programs", "unit": "%", "source": "device_trace",
              "moves": "serve_out_tokens_per_s"}
    write_json(os.path.join(b, "metrics", "second_mfu.json"), dict(
        metric, reader="mfu", args={"work": "second_flops"}))
    write_json(os.path.join(b, "metrics", "second_miss_share.json"), dict(
        metric, reader="counter_share", source="program_counter",
        args={"counter": "serving_prefix_misses",
              "over": "serving_prefills_total"}))
    cell = "second-serve.second-mix"
    entry = {"unit": "%", "better": "higher", "layer": "step programs",
             "moves": "serve_out_tokens_per_s", "workloads": [cell]}
    added = json.loads(json.dumps(bench))
    added["configs"].append(dict(
        bench["configs"][1], name="second-serve",
        file="benchmark/configs/second-serve.json"))
    added["workloads"].append({"name": cell, "config": "second-serve",
                               "traffic": "second-mix", "chips": 1,
                               "why": "a family that is files only"})
    for m in added["end_to_end"]:
        if m["name"] in ("itl_p95_ms", "serve_out_tokens_per_s"):
            m["workloads"] = m["workloads"] + [cell]
    added["per_layer"] += [
        dict(entry, name="second_mfu", source="device_trace"),
        dict(entry, name="second_miss_share", source="program_counter")]
    write_json(os.path.join(root, "BENCHMARK.json"), added)

    # ---- one traced run of the new cell
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(DRIVE)],
                       cwd=root, capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    res, traced, counters = out["result"], out["traced"], out["counters"]
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # only the metrics that list the new cell, each from the new family's
    # function or the counters its file names
    assert set(res["metrics"]) == {"second_mfu", "second_miss_share"}
    peak = tiny.load("peaks.json")["TPU v5 lite"]["bf16_flops_per_s"]
    assert traced["decode_tokens"] > 0
    assert res["metrics"]["second_mfu"]["value"] == pytest.approx(
        100.0 * 4242.0 * traced["decode_tokens"]
        / (res["device"]["window_s"] * peak))
    assert counters["serving_prefills_total"] > 0
    assert res["metrics"]["second_miss_share"]["value"] == pytest.approx(
        100.0 * counters["serving_prefix_misses"]
        / counters["serving_prefills_total"])
    # a counter that no metric of the cell names is not snapshot
    assert "serving_decode_overlapped_total" not in counters

    # ---- and nothing that was there changed
    after = digests(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/second-serve.json", "families/second.py",
        "limits/second-serve.second-mix.json", "metrics/second_mfu.json",
        "metrics/second_miss_share.json", "traffic/second-mix.json"]
    for key in ("configs", "workloads", "per_layer"):
        assert added[key][:len(bench[key])] == bench[key]


# ------------------------------------------------- the gpt3 family's guards
def tiny_model():
    return dict(tiny.TINY_MODEL, max_seq_len=64, dropout=0.0)


def test_gpt3_leaves_are_the_programs():
    """A program change that renames or reshapes a leaf breaks the harness
    here, on the CPU, and not first on the chip."""
    import paddle_tpu as paddle
    from paddle_tpu.text.gpt import GPTForCausalLM, gpt_config

    from benchmark.families import gpt3

    tiny.register_presets()
    with paddle.LazyGuard():
        model = GPTForCausalLM(gpt_config("bench-tiny", max_seq_len=64,
                                          dropout=0.0))
    params, _ = model.functional_state()
    table = gpt3.leaf_table(tiny_model())
    assert {n: shape for n, (shape, _) in table.items()} \
        == {n: tuple(t._value.shape) for n, t in params.items()}
    assert {kind for _, kind in table.values()} \
        == {"matrix", "scale", "bias"}


#: (seed, dtype, leaf) -> CRC-32 of the leaf's float32 bytes, taken from
#: ``lib/weights.py`` as PR 24 wrote it, before the table moved to the
#: family (CPU backend, the tiny model)
PINNED = {
    (7, "float32", "gpt.wte.weight"): 1082039521,
    (7, "float32", "gpt.blocks.1.attn.qkv_proj.bias"): 2422248033,
    (7, "float32", "gpt.ln_f.weight"): 3199342979,
    (2147483659, "bfloat16", "gpt.wte.weight"): 802873921,
    (2147483659, "bfloat16", "gpt.blocks.1.attn.qkv_proj.bias"): 3403538789,
    (2147483659, "bfloat16", "gpt.ln_f.weight"): 4169232142,
}


def crc(a) -> int:
    return zlib.crc32(np.asarray(a, np.float32).tobytes())


@pytest.mark.parametrize("seed,dtype", [(7, "float32"),
                                        (2147483659, "bfloat16")])
def test_a_seed_gives_the_weights_it_gave(seed, dtype):
    from benchmark.families import gpt3
    from benchmark.lib import weights

    table = gpt3.leaf_table(tiny_model())
    assert weights.num_params(table) == 1_727_488 and len(table) == 28
    config = {"model": tiny_model(), "precision": {"parameters": dtype}}
    whole = weights.make_weights(table, seed, dtype)
    for (s, d, name), want in PINNED.items():
        if (s, d) == (seed, dtype):
            assert crc(whole[name]) == want, name
    assert all(crc(a) == crc(whole[n]) for n, a in
               weights.for_program(gpt3, config, seed).items())
    # a part drawn alone (by prefix, by set) has the whole call's values
    block = weights.make_weights(table, seed, dtype, only="gpt.blocks.1.")
    assert sorted(block) == sorted(n for n in table
                                   if n.startswith("gpt.blocks.1."))
    some = weights.make_weights(table, seed, dtype,
                                only={"gpt.ln_f.weight", "gpt.wte.weight"})
    assert sorted(some) == ["gpt.ln_f.weight", "gpt.wte.weight"]
    for part in (block, some):
        for name, a in part.items():
            assert a.dtype == whole[name].dtype
            assert crc(a) == crc(whole[name]), name
    # as the reference takes them: the same values held in float32
    leaves_of = weights.for_reference(gpt3, config, seed)
    held = leaves_of("gpt.blocks.1.")
    assert all(str(a.dtype) == "float32" for a in held.values())
    assert all(crc(held[n]) == crc(whole[n]) for n in held)
    assert leaves_of() is leaves_of()


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(tiny.BENCH, "configs", "*.json"))), ids=os.path.basename)
def test_every_configuration_resolves_its_family(path):
    from benchmark.lib.common import family_of

    with open(path) as f:
        cfg = json.load(f)
    family = family_of(cfg)
    family.check(cfg)
    assert family.leaf_table(cfg["model"]) and family.WORK
