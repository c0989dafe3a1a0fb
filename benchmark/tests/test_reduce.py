"""``reduce.py``'s arithmetic on hand-written event records, and its loader
on a trace taken on the CPU here."""
import glob
import os

import pytest
import tiny  # noqa: F401

from benchmark.lib import reduce
from benchmark.lib.reduce import Event

DEV = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def ev(name, s, e, line=reduce.OPS_LINE, plane=DEV):
    return Event(plane, line, name, s, e)


def test_union_seconds():
    assert reduce.union_seconds([]) == 0.0
    assert reduce.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert reduce.union_seconds([(3, 4), (0, 1), (1, 2)]) == 3.0
    assert reduce.union_seconds([(0, 10), (2, 3)]) == 10.0


def test_sum_by_pattern():
    evs = [ev("flash_fwd.1", 0, 1), ev("fusion.7", 1, 3),
           ev("flash_bwd_dq.2", 3, 3.5)]
    assert reduce.sum_by_pattern(evs, "flash") == (1.5, 2)
    assert reduce.sum_by_pattern(evs, "^fusion") == (2.0, 1)
    assert reduce.sum_by_pattern(evs, "nothing") == (0.0, 0)


def test_idle_gaps_and_attribution():
    gaps = reduce.idle_gaps([(1, 2), (4, 5)], 0, 6)
    assert gaps == [(0, 1), (2, 4), (5, 6)]
    host = [ev("bench.step", 0, 3, "python", "/host:CPU"),
            ev("bench.add_request", 3, 3.5, "python", "/host:CPU"),
            ev("bench.fetch", 2, 3, "python", "/host:CPU")]
    att = reduce.attribute_gaps(gaps, host)
    # 0-1 and 2-3 lie in bench.step, but 2-3 also in the shorter
    # bench.fetch, which wins; 3-3.5 in add_request; 3.5-4 and 5-6 nowhere
    assert att == {"bench.step": 1.0, "bench.fetch": 1.0,
                   "bench.add_request": 0.5, "(unattributed)": 1.5}


def test_percentile():
    assert reduce.percentile([], 95) is None
    assert reduce.percentile([5.0], 95) == 5.0
    assert reduce.percentile(list(range(1, 101)), 95) == 95.0
    assert reduce.percentile(list(range(1, 21)), 95) == 19.0
    assert reduce.percentile([3, 1, 2], 50) == 2.0


def trace():
    return reduce.Trace([
        ev("bench.step", 0.0, 4.0, "python", "/host:CPU"),
        ev("bench.step", 4.0, 10.0, "python", "/host:CPU"),
        ev("jit_step(1)", 0.5, 3.5, reduce.MODULES_LINE),
        ev("jit_step(1)", 4.5, 9.5, reduce.MODULES_LINE),
        ev("fusion.1", 0.5, 2.0), ev("kern_a.3", 2.0, 3.0),
        ev("fusion.1", 5.0, 7.0), ev("kern_a.3", 7.0, 9.0),
        ev("fusion.9", 20.0, 21.0),        # outside the host window
    ])


def test_trace_busy_idle_and_modules():
    t = trace()
    assert t.window_s == 10.0 and t.devices == [DEV]
    assert t.busy_s() == pytest.approx(6.5)
    assert reduce.read_idle_share(t, {}, {}, PEAKS) == pytest.approx(35.0)
    assert reduce.read_module_ms_per_call(
        t, {}, {"module": "jit_step"}, PEAKS) == pytest.approx(3250.0)
    assert reduce.read_module_ms_per_call(
        t, {}, {"module": "decode"}, PEAKS) is None
    assert reduce.read_host_ms_per_call(
        t, {}, {"span": "bench.step"}, PEAKS) == pytest.approx(1750.0)


def test_roofline_readers_and_silence():
    t = trace()
    # a metric's ``args.work`` is looked up in the family's WORK, which
    # ``run.execute`` hands over as facts["work"]
    facts = {"model": {}, "traced": {}, "work": {
        "unit": lambda model, traced: {"flops": 60.0, "bytes": 15.0},
        "none": lambda model, traced: {"flops": 0.0, "bytes": 0.0}}}
    # least time max(60/100, 15/10) = 1.5 s over 3 s of kern_a
    assert reduce.read_kernel_roofline(
        t, facts, {"op": "kern_a", "work": "unit"}, PEAKS) \
        == pytest.approx(50.0)
    # a kernel that is not in the trace is left out, never reported as 0
    assert reduce.read_kernel_roofline(
        t, facts, {"op": "gone", "work": "unit"}, PEAKS) is None
    assert reduce.read_module_roofline(
        t, facts, {"module": "jit_step", "work": "unit"}, PEAKS) \
        == pytest.approx(100 * 1.5 / 6.5)
    assert reduce.read_mfu(t, facts, {"work": "unit"}, PEAKS) \
        == pytest.approx(100 * 60.0 / (10.0 * 100.0))
    assert reduce.read_mfu(t, facts, {"work": "none"}, PEAKS) is None


def run_ahead_trace(step=2.0, first=0.0, n=7, lo=1.0, hi=10.5):
    """A train window with steps in flight: the device runs ``n`` steps of
    ``step`` seconds back to back from ``first``, each with one kernel of
    a quarter of the step in its middle; the host's ``bench.step`` spans
    (dispatches, each far shorter than a step) cover ``[lo, hi]`` and are
    of other steps than the device is running."""
    evs = [ev("bench.step", lo, lo + 0.01, "python", "/host:CPU"),
           ev("bench.fetch_loss", hi - 0.5, hi, "python", "/host:CPU")]
    for k in range(n):
        s = first + k * step
        evs.append(ev("jit_step(7)", s, s + step, reduce.MODULES_LINE))
        evs.append(ev("fusion.1", s, s + 0.375 * step))
        evs.append(ev("flash_fwd.2", s + 0.375 * step, s + 0.625 * step))
        evs.append(ev("fusion.3", s + 0.625 * step, s + step))
    return reduce.Trace(evs)


def test_work_is_counted_over_the_window_its_time_is():
    """Seven steps of 2 s from 0 to 14, traced from 1 to 10.5: the window
    holds 0.5 + 4 + 0.25 steps, whatever number the host dispatched in it
    (with eight in flight, the old count: one more than the window
    held)."""
    t = run_ahead_trace()
    assert t.window_s == pytest.approx(9.5)
    assert t.module_calls("jit_step") == pytest.approx(4.75)
    assert t.module_calls("decode") == 0.0
    work = {"unit": lambda model, traced: {
        "flops": 25.0 * traced.get("calls", 0),
        "bytes": 1.0 * traced.get("calls", 0)}}
    # the host's own count is not read, however wrong
    facts = {"model": {}, "traced": {"calls": 99}, "work": work}
    step = {"work": "unit", "module": "jit_step"}
    # a step of 2 s does 25 FLOPs at a peak of 100 FLOP/s: 12.5%, however
    # the window cuts the steps (here 4.75 x 25 over 9.5 x 100)
    assert reduce.read_mfu(t, facts, step, PEAKS) == pytest.approx(12.5)
    for lo, hi in ((0.3, 9.1), (1.9, 13.2), (0.0, 14.0)):
        assert reduce.read_mfu(run_ahead_trace(lo=lo, hi=hi), facts, step,
                               PEAKS) == pytest.approx(12.5)
    # the kernel takes 0.5 s of a step for work whose least time is
    # max(25/100, 1/10) = 0.25 s: 50%. Read of the four steps that lie
    # whole inside the window (2..10); the kernel calls of the cut steps
    # (0.75..1.25, partly inside, and 10.75..11.25) are not counted
    kern = dict(step, op="flash")
    assert reduce.read_kernel_roofline(t, facts, kern, PEAKS) \
        == pytest.approx(50.0)
    assert reduce.read_kernel_roofline(
        run_ahead_trace(lo=0.0, hi=14.0), facts, kern, PEAKS) \
        == pytest.approx(50.0)
    # without the module the reader takes the driver's count, as serving's
    # synchronous steps give it: five calls' work over the 2.5 s of the
    # five kernel events that touch the window
    assert reduce.read_kernel_roofline(
        t, {"model": {}, "traced": {"calls": 5}, "work": work},
        {"op": "flash", "work": "unit"}, PEAKS) == pytest.approx(50.0)


def test_counter_and_span_readers():
    facts = {"counters": {"saved": 30.0, "offered": 120.0},
             "spans": {"wait": [1.0, 2.0, 3.0, 100.0]}}
    assert reduce.read_counter_share(
        None, facts, {"counter": "saved", "over": "offered"}, PEAKS) == 25.0
    assert reduce.read_counter_share(
        None, {}, {"counter": "saved", "over": "offered"}, PEAKS) is None
    assert reduce.read_span_percentile(
        None, facts, {"span": "wait", "percentile": 95}, PEAKS) == 100.0
    assert reduce.read_span_percentile(
        None, facts, {"span": "other", "percentile": 95}, PEAKS) is None


def test_breakdown_lists():
    b = trace().breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(3.5)]
    assert dict(b["idle_gaps"])["bench.step"] == pytest.approx(3.5)


def test_loader_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    t = reduce.load(path)
    assert len([e for e in t.host if e.name == "bench.step"]) == 3
    assert t.window_s > 0
    # no device plane on the CPU: device readers stay silent, and the run
    # that asked for them is the harness's to refuse
    assert t.devices == [] and t.busy_s() == 0.0
    assert reduce.read_idle_share(t, {}, {}, PEAKS) is None
