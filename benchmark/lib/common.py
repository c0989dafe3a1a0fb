"""What both drivers share: the run's context, earlier-line printing, the
profiler window of a traced run and the release of device memory."""
from __future__ import annotations

import gc
import glob
import importlib
import json
import os
import shutil
import time
from dataclasses import dataclass, field


@dataclass
class Run:
    """One run of one cell, as ``run.py`` hands it to a driver."""
    root: str                 # the checkout
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: dict              # benchmark/configs/<config>.json
    mix: dict                 # benchmark/traffic/<traffic>.json
    check: dict               # benchmark/limits/<workload>.json
    peaks: dict               # this device's row of peaks.json
    t_process: float          # time.time() at process start
    control: bool = False     # also judge the control and planted faults
    counters: frozenset = frozenset()   # the counters its metric files read
    facts: dict = field(default_factory=dict)


def say(what: str, **fields) -> None:
    """An earlier line of standard output (never the last)."""
    print(f"[bench] {what}: " + json.dumps(fields, sort_keys=True,
                                           default=str), flush=True)


def family_of(config: dict):
    """The module of the configuration's family:
    ``benchmark/families/<family>.py``, found by that name alone."""
    return importlib.import_module("benchmark.families." + config["family"])


def install_weights(model, mine: dict) -> None:
    """Hand the benchmark's weights to a model built under LazyGuard, leaf
    by name."""
    params, _ = model.functional_state()
    if set(mine) != set(params):
        raise SystemExit("the program's leaves are not the benchmark's: "
                         f"{sorted(set(mine) ^ set(params))[:4]}")
    for name, t in params.items():
        if tuple(t._value.shape) != mine[name].shape:
            raise SystemExit(f"{name}: {t._value.shape} in the program, "
                             f"{mine[name].shape} here")
        t._value, t._lazy_init = mine[name], None


def release() -> None:
    """Drop what the program left on the device (the reference follows)."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def peak_bytes() -> int:
    """``peak_bytes_in_use`` on the fullest chip."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class TraceWindow:
    """The profiler around the last seconds of a traced run's window. The
    driver calls ``open()`` and ``close()`` between two calls into the
    program. Starting the profiler stalls the host for seconds, so the
    trace sits at the end of the window: what the driver's own clock
    recorded before ``opened_s`` is untouched by it, and the per-layer
    metrics that come from that clock read only that part."""

    def __init__(self, run: Run, seconds: float = 4.0):
        self.run = run
        self.dir = os.path.join(run.root, ".bench_trace", run.workload)
        self.length = min(seconds, run.seconds * 0.4)
        self.start_at = run.seconds - self.length
        self.opened = self.closed = self.opened_s = None

    def due(self, now_s: float) -> bool:
        return self.run.trace and self.opened is None \
            and now_s >= self.start_at

    def open(self, now_s: float) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # the Python tracer slows the host and fills the trace: off. The
        # drivers' TraceAnnotations are host TraceMe events and stay.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.opened_s = now_s
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.opened = time.perf_counter()

    def close(self) -> None:
        import jax

        if self.opened is None or self.closed is not None:
            return
        self.closed = time.perf_counter()
        jax.profiler.stop_trace()

    def file(self) -> str | None:
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def discard(self) -> None:
        """Traces are large and the host keeps every block once written:
        drop the file as soon as it is read."""
        shutil.rmtree(self.dir, ignore_errors=True)
