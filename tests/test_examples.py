"""examples/ must keep running end-to-end (each asserts its own learning/
round-trip invariants internally)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run(script, extra_env=None, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = ""  # examples put the repo on sys.path themselves
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples", script)],
        env=env, cwd=_ROOT, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


@pytest.mark.slow
@pytest.mark.parametrize("script,extra", [
    ("train_gpt.py", None),
    ("static_train_export.py", None),
    ("fleet_hybrid.py",
     {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}),
    ("fluid_legacy.py", None),
    ("auto_parallel_plan.py",
     {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}),
    ("serving_demo.py", None),
])
def test_example_runs(script, extra):
    proc = _run(script, extra)
    assert proc.returncode == 0, proc.stdout.decode()[-2000:]
