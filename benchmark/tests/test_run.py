"""``run.py`` as a command: no result without a TPU, none without the
program; ``BENCHMARK.json`` and the data files agree with each other."""
import json
import os
import shutil
import subprocess
import sys

import tiny

CMD = [sys.executable, "benchmark/run.py", "--workload",
       "gpt3-350m-train.b8-s1024", "--seed", "1", "--seconds", "1",
       "--trace", "0"]


def last_line_is_result(out: str) -> bool:
    lines = [x for x in out.strip().splitlines() if x.strip()]
    if not lines:
        return False
    try:
        return "metrics" in json.loads(lines[-1])
    except ValueError:
        return False


def test_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    p = subprocess.run(CMD, cwd=tiny.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not last_line_is_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_exits_non_zero_without_the_program(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(CMD, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not last_line_is_result(p.stdout)
    assert "paddle_tpu" in p.stderr


#: what a driver takes from a configuration's family, beside ``check``,
#: ``leaf_table`` and ``WORK``
FAMILY_PARTS = {"serve": ("build_serving", "logits_at"),
                "train": ("build_training", "loss_and_grads", "parts")}


def test_benchmark_json_and_data_files_agree():
    from benchmark.lib import reduce
    from benchmark.lib.common import family_of

    bench, work = tiny.bench_json(), {}
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for c in bench["configs"]:
        cfg = tiny.load(*c["file"].split("/")[1:])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # its family resolves by name, agrees with its sizes and offers
        # the parts that its driver takes
        family = family_of(cfg)
        family.check(cfg)
        assert family.leaf_table(cfg["model"])
        assert cfg["model"]["vocab_size"] > 0
        for part in FAMILY_PARTS[cfg["driver"]]:
            assert callable(getattr(family, part)), (cfg["family"], part)
        work[c["name"]] = family.WORK
    for w in bench["workloads"]:
        assert w["name"] == f'{w["config"]}.{w["traffic"]}'
        tiny.load("traffic", w["traffic"] + ".json")
        limits = tiny.load("limits", w["name"] + ".json")["limits"]
        assert limits and all(v >= 0 for v in limits.values())
        mine = [m for m in e2e.values()
                if "workloads" not in m or w["name"] in m["workloads"]]
        assert len(mine) >= 2
    layers = {}
    for m in bench["per_layer"]:
        spec = tiny.load("metrics", m["name"] + ".json")
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"] and spec["source"] == m["source"]
        assert spec["reader"] in reduce.READERS
        if "work" in spec.get("args", {}):
            # in the family of every cell that reports the metric
            for w in m["workloads"]:
                assert spec["args"]["work"] in work[w.rsplit(".", 1)[0]]
        assert set(m["workloads"]) <= cells
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in cells:
        assert any(w in m["workloads"] for m in bench["per_layer"])
