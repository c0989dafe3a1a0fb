"""The launcher and the chip: no silent CPU pin, no backend in the parent.

A chip belongs to one process. A multi-process launch without ``--devices``
cannot give its workers the TPU, so it pins them to the CPU — on a host
that has a TPU that must be asked for (``JAX_PLATFORMS=cpu``), not assumed.
And the launcher itself must initialise no JAX backend: a parent that has
touched JAX holds the chip, and the workers it forks fail or hang.
"""
import os
import subprocess
import sys

import pytest

from paddle_tpu.distributed.launch import controller as ctl
from paddle_tpu.distributed.launch.context import Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pod_envs(argv, monkeypatch, has_tpu, platforms):
    monkeypatch.setattr(ctl, "_host_has_tpu", lambda: has_tpu)
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    c = ctl.CollectiveController(Context(argv + ["--log_dir", "log", "w.py"]))
    ranks, recs = c._rendezvous()
    c.build_pod(ranks, recs)
    return [cont.env for cont in c.pod.containers]


@pytest.mark.parametrize("has_tpu,platforms,devices,want", [
    (True, None, None, "error"),        # TPU host, nothing asked: refuse
    (True, "tpu", None, "error"),
    (True, "cpu", None, "cpu"),         # CPU simulation asked for
    (False, None, None, "cpu"),         # no TPU here: the CPU simulation
    (True, None, "0,1", "devices"),     # chips partitioned across workers
])
def test_multi_proc_launch_never_pins_cpu_silently(
        monkeypatch, has_tpu, platforms, devices, want):
    argv = ["--nproc_per_node", "2"] + (
        ["--devices", devices] if devices else [])
    if want == "error":
        with pytest.raises(ValueError, match="--devices"):
            _pod_envs(argv, monkeypatch, has_tpu, platforms)
        return
    envs = _pod_envs(argv, monkeypatch, has_tpu, platforms)
    assert len(envs) == 2
    if want == "cpu":
        assert [e["JAX_PLATFORMS"] for e in envs] == ["cpu", "cpu"]
    else:
        assert all("JAX_PLATFORMS" not in e for e in envs)
        assert [e["TPU_VISIBLE_DEVICES"] for e in envs] == ["0", "1"]


def test_launcher_import_initialises_no_backend():
    code = ("import paddle_tpu.distributed.launch\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, dict(xla_bridge._backends)\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       timeout=120, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
