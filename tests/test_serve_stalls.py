"""The stall record (``obs/stall.py``) and the step's seconds counters.

On clocks the tests move by hand and a hand-made ``/proc`` directory; no
timing is asserted anywhere. What is pinned:

- flagging: a blocking span (``*.fetch`` / ``*.upload`` / ``*.dispatch``)
  that outlasts its name's norm by the rule is one record, one
  count and its excess in ``serving_stall_seconds_total{held_by=}``, and
  the step's unstalled seconds are the step less that excess; a calm run
  flags nothing; a name with no norm yet flags nothing; a span that is
  not blocking is never looked at;
- evidence: each ``held_by`` word from hand-written samples, the rules'
  order included; a file that is absent reads ``None`` and raises nothing;
- the counters: ``serving_step_seconds_total`` is the timeline's summed
  ``t_end - t_start`` and ``host = step - sum(*.fetch)``, exactly, on an
  integer clock; with ``enable_tracing=False`` there is no thread and no
  such counter;
- the sampler thread ends with the engine, adds no device sync, and
  loses no record under a shortened switch interval;
- a fatal step still closes its spans and clears the slot; the record is
  in the timeline, the ring, a flight-record dump and ``--stalls``.
"""
import gc
import glob
import json
import os
import sys
import threading

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import SyncTally
from paddle_tpu.obs import (HELD_BY, SPANS, PhaseAccumulator, StallWatch,
                            stall, stall_table, validate_flight_record)
from paddle_tpu.obs.__main__ import main as obs_main
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

pytestmark = pytest.mark.obs

ENGINE_TID = 4242
BLOCKING_SPANS = [s for s in SPANS if s.endswith(stall.BLOCKING)]


class HandClock:
    """A clock that moves only when a test moves it."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class StepClock:
    """The engine tests' clock: 1.0 s a read, so every sum is exact."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


class Value:
    """What a blocking span awaits: ready when the test says so."""

    def __init__(self, ready=False):
        self.ready = ready

    def is_ready(self):
        return self.ready


# ------------------------------------------------------- a /proc made by hand
def _stat(tid, comm, state, ticks, core, majflt):
    f = ["0"] * 49
    f[0], f[9], f[11], f[12], f[36] = state, str(majflt), str(ticks), "0", \
        str(core)
    return f"{tid} ({comm}) " + " ".join(f)


def write_proc(root, threads, psi=(), vmstat=None, loadavg=None,
               switches=None, schedstat=True, machine=None):
    """``threads``: {tid: (comm, state, cpu_s, runq_s, core, majflt)};
    ``psi``: {kind: seconds}; ``vmstat``: {line: count}; ``switches``:
    the engine thread's (voluntary, involuntary); ``machine``: (busy,
    stolen) seconds of all cores. What is not given is not written."""
    if machine is not None:
        os.makedirs(root, exist_ok=True)
        with open(os.path.join(root, "stat"), "w") as f:
            f.write(f"cpu  {round(machine[0] / stall._TICK_S)} 0 0 900 0 0 "
                    f"0 {round(machine[1] / stall._TICK_S)} 0 0\ncpu0 1 2 "
                    "3 4 5 6 7 8 9 10\n")
    for tid, (comm, state, cpu, runq, core, majflt) in threads.items():
        d = os.path.join(root, "self", "task", str(tid))
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "stat"), "w") as f:
            f.write(_stat(tid, comm, state, round(cpu / stall._TICK_S),
                          core, majflt))
        if schedstat:
            with open(os.path.join(d, "schedstat"), "w") as f:
                f.write(f"{round(cpu * 1e9)} {round(runq * 1e9)} 7\n")
    if switches is not None:
        with open(os.path.join(root, "self", "task", str(ENGINE_TID),
                               "status"), "w") as f:
            f.write("Name:\tpython\nvoluntary_ctxt_switches:\t"
                    f"{switches[0]}\nnonvoluntary_ctxt_switches:\t"
                    f"{switches[1]}\n")
    for kind, seconds in dict(psi).items():
        os.makedirs(os.path.join(root, "pressure"), exist_ok=True)
        with open(os.path.join(root, "pressure", kind), "w") as f:
            f.write("some avg10=0.00 avg60=0.00 avg300=0.00 "
                    f"total={round(seconds * 1e6)}\nfull avg10=0.00 "
                    "avg60=0.00 avg300=0.00 total=0\n")
    if vmstat is not None:
        with open(os.path.join(root, "vmstat"), "w") as f:
            f.write("nr_free_pages 12345\n" + "".join(
                f"{k} {v}\n" for k, v in vmstat.items()))
    if loadavg is not None:
        with open(os.path.join(root, "loadavg"), "w") as f:
            f.write(loadavg + "\n")


def _thread(cpu=0.0, runq=0.0, majflt=0, comm="python", state="S", core=1):
    return (comm, state, cpu, runq, core, majflt)


CALM = dict(threads={ENGINE_TID: _thread(), 4300: _thread(comm="tpu-rt")},
            psi={"cpu": 10.0, "memory": 2.0, "io": 3.0},
            vmstat={"pgmajfault": 5, "allocstall_normal": 0,
                    "compact_stall": 1},
            loadavg="0.50 0.40 0.30 1/200 999", switches=(100, 3))


def _grown(**over):
    """``CALM`` after a stall of one second, with ``over`` laid on."""
    out = dict(CALM, switches=(101, 3))
    out.update(over)
    return out


def _watch(tmp_path, clock, cpu=None):
    """A watch over the hand-made /proc; ``cpu``: a list the test moves,
    [process CPU seconds, the engine thread's]."""
    cpu = [5.0, 1.0] if cpu is None else cpu
    watch = StallWatch(proc_root=str(tmp_path), clock=clock,
                       cpu=lambda: tuple(cpu))
    watch.tid = ENGINE_TID
    return watch


def _spans(acc, name, durations, clock, awaited=None, inside=None,
           frac=0.5):
    """One step a duration, each holding one span ``name`` of that
    length; ``inside(i)`` runs ``frac`` of the way through span ``i``.
    Returns what each step's close handed back."""
    closed = []
    for i, dt in enumerate(durations):
        acc.enter_step(i)
        acc.begin()
        with acc.span(name, awaited=awaited):
            clock.t += dt * frac
            if inside is not None:
                inside(i)
            clock.t += dt * (1 - frac)
        acc.finish()
        acc.exit_step()
        closed.append(acc.stalls.close_step(clock.t))
    return closed


def _nothing(closed) -> bool:
    """A step's close that flagged and completed nothing."""
    return closed[0] == 0.0 and not closed[1] and not closed[2]


def _sampled_stall(tmp_path, last, awaited=None, ready_at=None,
                   cpu_grew=(0.0, 0.0), late=0.0, late_cpu=0.0):
    """One stall of 1.0 s on a 10 ms norm. The sampler passes once before
    it (on time), finds it overdue half-way through (``late`` seconds
    behind its own schedule; its first sample reads ``CALM``), sees the
    awaited value turn ready ``ready_at`` seconds on, and takes its last
    sample (``last``) at the release; the process uses ``late_cpu``
    seconds of CPU while the sampler is late, and the process's and the
    engine thread's CPU grow by ``cpu_grew`` after. Returns the record."""
    clock, cpu = HandClock(), [5.0, 1.0]
    watch = _watch(tmp_path, clock, cpu)
    acc = PhaseAccumulator(clock, stalls=watch)

    def on_time():
        watch._due = None  # this pass comes when it was due

    def sampler(i):
        if i == stall.REFRESH - 1:
            on_time()
            assert watch.poll() == stall.IDLE_PERIOD_S   # nothing overdue
        if i < stall.REFRESH:
            return
        # the sampler's passes inside the stalled span, in order
        write_proc(str(tmp_path), **CALM)
        watch._due = clock.t - late
        cpu[0] += late_cpu
        assert watch.poll() == stall.POLL_PERIOD_S       # first sample
        if ready_at is not None:
            clock.t += ready_at
            awaited.ready = True
            on_time()
            assert watch.poll() == stall.POLL_PERIOD_S   # sees it ready
            clock.t -= ready_at
        write_proc(str(tmp_path), **last)
        cpu[0] += cpu_grew[0]
        cpu[1] += cpu_grew[1]
        on_time()  # (the release's pass is made by the step's close)

    durations = [0.01] * stall.REFRESH + [1.0]
    closed = _spans(acc, "decode.fetch", durations, clock, awaited, sampler)
    stall_s, flagged, done = closed[-1]
    assert len(flagged) == len(done) == 1 and flagged[0] is done[0]
    assert stall_s == pytest.approx(0.99)
    rec = done[0]
    assert rec["sampler_late_ms"] == pytest.approx(1e3 * late)
    assert rec["late_cpu_ms"] == pytest.approx(1e3 * late_cpu)
    assert rec["process_cpu_ms"] == pytest.approx(
        1e3 * (late_cpu + cpu_grew[0]))
    assert rec["thread_cpu_ms"] == pytest.approx(1e3 * cpu_grew[1])
    return rec


# ------------------------------------------------------------------ flagging
@pytest.mark.parametrize("name", BLOCKING_SPANS)
def test_a_blocking_span_past_its_norm_is_one_stall(name, tmp_path):
    clock = HandClock()
    acc = PhaseAccumulator(clock, stalls=_watch(tmp_path, clock))
    norm = [0.009] * 16
    closed = _spans(acc, name, norm + [0.120, 0.009], clock)
    assert all(map(_nothing, closed[:16]))
    stall_s, flagged, done = closed[16]
    assert stall_s == pytest.approx(0.111) and not done
    (rec,) = flagged
    assert (rec["span"], rec["step"]) == (name, 16)
    assert rec["ms"] == pytest.approx(120) and \
        rec["norm_ms"] == pytest.approx(9) and \
        rec["excess_ms"] == pytest.approx(111) and rec["held_by"] is None
    # no sampler ran: closed "unsampled" once the grace has passed
    assert _nothing(closed[17])
    clock.t += stall.GRACE_S + 1
    _, _, done = acc.stalls.close_step(clock.t)
    assert done == [rec] and rec["held_by"] == "unsampled" and \
        rec["psi"] is None and list(acc.stalls.ring) == [rec]


@pytest.mark.parametrize("norm_s,long_s,is_stall", [
    (0.009, 0.058, False),   # under the floor of 50 ms past the norm
    (0.009, 0.060, True),
    (0.100, 0.199, False),   # cell M's prefill.fetch: twice the norm
    (0.100, 0.201, True),
    (0.020, 0.069, False),   # cell K's first-step uploads
])
def test_the_rule_is_the_norm_plus_the_larger_of_50ms_and_itself(
        norm_s, long_s, is_stall, tmp_path):
    clock = HandClock()
    acc = PhaseAccumulator(clock, stalls=_watch(tmp_path, clock))
    closed = _spans(acc, "prefill.fetch", [norm_s] * 8 + [long_s], clock)
    assert bool(closed[-1][1]) is is_stall
    assert closed[-1][0] == pytest.approx(
        long_s - norm_s if is_stall else 0.0)


@pytest.mark.parametrize("seen,norm_s", [
    # what nine in ten of the name's spans stayed under
    ([0.002] * 9 + [0.100], 0.002),
    ([0.002] * 8 + [0.100] * 2, 0.100),
    # a window's first step: the device's queue fills and every upload
    # waits out a prefill; the norm follows within a span or two
    ([0.002] * 8 + [0.100], 0.100),
    ([0.005, 0.007] * 10 + [3.4], 0.007),   # one stall moves nothing
])
def test_the_norm_is_what_nine_in_ten_stayed_under(seen, norm_s, tmp_path):
    clock = HandClock()
    acc = PhaseAccumulator(clock, stalls=_watch(tmp_path, clock))
    _spans(acc, "prefill.upload", seen, clock)
    assert acc.stalls._norms["prefill.upload"].norm == pytest.approx(norm_s)


def test_the_device_s_backpressure_at_a_window_s_start_is_no_stall(tmp_path):
    # cell M: one warm-up prefill, then 13 prompts admitted at once: three
    # uploads find room in the device's queue, ten wait out a prefill each
    clock = HandClock()
    acc = PhaseAccumulator(clock, stalls=_watch(tmp_path, clock))
    closed = _spans(acc, "prefill.upload",
                    [0.002] * 4 + [0.100] * 10 + [0.002] * 20, clock)
    assert all(map(_nothing, closed))
    # ... and a wait of seconds behind them still is one
    (closed,) = _spans(acc, "prefill.upload", [3.4], clock)
    assert closed[0] == pytest.approx(3.4 - 0.100) and len(closed[1]) == 1


def test_the_first_spans_of_a_name_have_no_norm_and_flag_nothing(tmp_path):
    clock = HandClock()
    acc = PhaseAccumulator(clock, stalls=_watch(tmp_path, clock))
    # a window's first step: seven long waits before any norm exists
    closed = _spans(acc, "prefill.upload", [5.0] * (stall.REFRESH - 1),
                    clock)
    assert all(map(_nothing, closed))
    assert acc.stalls.waiting is None and not acc.stalls.ring


@pytest.mark.parametrize("name", [s for s in SPANS
                                  if s not in BLOCKING_SPANS])
def test_a_span_that_is_not_blocking_is_never_watched(name, tmp_path):
    clock = HandClock()
    acc = PhaseAccumulator(clock, stalls=_watch(tmp_path, clock))
    seen = []
    _spans(acc, name, [0.001] * 16 + [3.0], clock,
           inside=lambda i: seen.append(acc.stalls.waiting))
    assert seen == [None] * 17 and not acc.stalls._norms
    assert acc.span_s[name] == pytest.approx(3.0)


# ------------------------------------------------------------------ evidence
HELD = {
    "device_never_ready": (dict(last=_grown(), awaited=Value()), "device"),
    "device_ready_at_the_release": (
        dict(last=_grown(), awaited=Value(), ready_at=0.496), "device"),
    "device_done_early_wake_up_late": (
        dict(last=_grown(), awaited=Value(), ready_at=0.1), "asleep"),
    "cpu_queue_a_thread_waited_for_a_core": (
        dict(last=_grown(threads={ENGINE_TID: _thread(),
                                  4300: _thread(runq=0.6, comm="tpu-rt")})),
        "cpu_queue"),
    "cpu_queue_by_psi": (
        dict(last=_grown(psi={"cpu": 10.6, "memory": 2.0, "io": 3.0})),
        "cpu_queue"),
    "memory_by_psi": (
        dict(last=_grown(psi={"cpu": 10.0, "memory": 2.7, "io": 3.0})),
        "memory"),
    "memory_by_an_allocation_stall": (
        dict(last=_grown(vmstat={"pgmajfault": 5, "allocstall_normal": 2,
                                 "compact_stall": 1})), "memory"),
    "io_by_psi": (
        dict(last=_grown(psi={"cpu": 10.0, "memory": 2.0, "io": 3.8})),
        "io"),
    "io_by_a_major_fault": (
        dict(last=_grown(threads={ENGINE_TID: _thread(majflt=1),
                                  4300: _thread(comm="tpu-rt")})), "io"),
    "runtime_busy": (dict(last=_grown(), cpu_grew=(0.3, 0.02)),
                     "runtime_busy"),
    # the sampler's own wake-up stood still with the wait: the process
    # as a whole did not run ...
    "late_by_under_half_the_wait_is_not_frozen": (
        dict(last=_grown(), awaited=Value(), late=0.48), "device"),
    "frozen": (dict(last=_grown(), awaited=Value(), late=0.51), "frozen"),
    "frozen_whatever_else_shows": (
        dict(last=_grown(psi={"cpu": 10.9, "memory": 2.9, "io": 3.9}),
             late=0.9, cpu_grew=(0.4, 0.0)), "frozen"),
    # ... unless somebody ran all the while and kept the rest from it
    "runtime_busy_while_everyone_else_stood_still": (
        dict(last=_grown(), late=0.9, late_cpu=0.8), "runtime_busy"),
    "asleep_nobody_ran_nobody_waited": (dict(last=_grown()), "asleep"),
    # the order: every later sign is there too
    "order_device_first": (
        dict(last=_grown(
            threads={ENGINE_TID: _thread(majflt=2),
                     4300: _thread(runq=0.9, cpu=0.9, comm="tpu-rt")},
            psi={"cpu": 10.9, "memory": 2.9, "io": 3.9}), awaited=Value(),
            cpu_grew=(0.4, 0.0)), "device"),
    "order_cpu_queue_before_memory": (
        dict(last=_grown(psi={"cpu": 10.9, "memory": 2.9, "io": 3.9}),
             cpu_grew=(0.4, 0.0)), "cpu_queue"),
    "order_memory_before_io": (
        dict(last=_grown(psi={"cpu": 10.0, "memory": 2.9, "io": 3.9}),
             cpu_grew=(0.4, 0.0)), "memory"),
    "order_io_before_runtime_busy": (
        dict(last=_grown(psi={"cpu": 10.0, "memory": 2.0, "io": 3.9}),
             cpu_grew=(0.4, 0.0)), "io"),
}


@pytest.mark.parametrize("case", list(HELD))
def test_held_by_from_hand_written_samples(case, tmp_path):
    kw, word = HELD[case]
    kw = dict(kw)
    if "awaited" in kw:
        kw["awaited"] = Value()  # a fresh one a run
    rec = _sampled_stall(tmp_path, **kw)
    assert rec["held_by"] == word and word in HELD_BY
    # the raw fields stay, for a reader who distrusts the rule
    assert rec["sampled_ms"] == pytest.approx(500.0)
    assert rec["sampled_from_ms"] == pytest.approx(500.0)
    assert rec["nvcsw"] == 1 and rec["nivcsw"] == 0
    assert rec["loadavg"] == "0.50 0.40 0.30 1/200 999"
    assert {t["tid"] for t in rec["threads"]} == {ENGINE_TID, 4300}
    assert set(rec["psi"]) == {"cpu", "memory", "io"}
    if "awaited" not in kw:
        assert rec["device_ready_after_ms"] is None
    elif "ready_at" in kw:
        assert rec["device_ready_after_ms"] == pytest.approx(
            500.0 + 1e3 * kw["ready_at"])


@pytest.mark.parametrize("absent", ["pressure", "vmstat", "loadavg",
                                    "schedstat", "status", "everything"])
def test_an_absent_file_reads_none_and_raises_nothing(absent, tmp_path):
    both = {k: v for k, v in CALM.items()
            if k not in {"pressure": ("psi",), "vmstat": ("vmstat",),
                         "loadavg": ("loadavg",), "status": ("switches",),
                         }.get(absent, ())}
    if absent == "schedstat":
        both["schedstat"] = False
    if absent == "everything":
        both = dict(threads={})
    clock = HandClock()
    watch = _watch(tmp_path, clock)
    acc = PhaseAccumulator(clock, stalls=watch)

    def sampler(i):
        if i == stall.REFRESH:
            write_proc(str(tmp_path), **both)
            watch.poll()
            watch._due = None  # the release's pass comes when it is due

    closed = _spans(acc, "decode.fetch", [0.01] * stall.REFRESH + [1.0],
                    clock, inside=sampler)
    (rec,) = closed[-1][2]
    assert rec["held_by"] == "asleep"
    if absent in ("pressure", "everything"):
        assert rec["psi"] == {"cpu": None, "memory": None, "io": None}
    if absent in ("vmstat", "everything"):
        assert rec["vmstat"] is None
    if absent in ("loadavg", "everything"):
        assert rec["loadavg"] is None
    if absent == "schedstat":
        assert all(t["runq_wait_ms"] is None for t in rec["threads"])
    if absent in ("status", "everything"):
        assert rec["nvcsw"] is None and rec["nivcsw"] is None
    if absent == "everything":
        assert rec["threads"] == [] and rec["majflt"] is None
    # no test writes a /proc/stat
    assert rec["machine_cpu_ms"] is None and rec["steal_ms"] is None
    json.dumps(rec)  # a record is plain data


def test_the_machine_s_busy_and_stolen_time_come_from_proc_stat(tmp_path):
    rec = _sampled_stall(tmp_path, _grown(machine=(7.25, 0.75)))
    assert rec["machine_cpu_ms"] is None  # the first sample had no file
    write_proc(str(tmp_path), **dict(CALM, machine=(7.0, 0.5)))
    first = stall.read_sample(str(tmp_path), ENGINE_TID, 1.0)
    assert first["machine"] == pytest.approx((7.0, 0.5))


def test_a_pass_of_the_sampler_that_comes_late_is_a_pause(tmp_path):
    # the process as a whole stood still, inside a blocking span or not
    clock = HandClock()
    watch = _watch(tmp_path, clock)
    assert watch.poll() == stall.IDLE_PERIOD_S
    clock.t += stall.IDLE_PERIOD_S + 0.004
    watch.poll()
    assert not watch.pauses
    due = clock.t + stall.IDLE_PERIOD_S
    clock.t = due + 0.104
    watch.poll()
    ((at, late, cpu),) = watch.pauses
    assert at == pytest.approx(due) and late == pytest.approx(0.104) \
        and cpu == 0.0


@pytest.mark.parametrize("cpu_s,word", [(0.0, "frozen"),
                                        (0.09, "runtime_busy")])
def test_a_wait_the_sampler_slept_through_is_held_by_its_late_pass(
        cpu_s, word, tmp_path):
    # the sampler stood still with the wait and woke with its release:
    # it saw no open span and left no evidence, only its own late pass
    clock, cpu = HandClock(), [5.0, 1.0]
    watch = _watch(tmp_path, clock, cpu)
    acc = PhaseAccumulator(clock, stalls=watch)
    watch.poll()
    closed = _spans(acc, "decode.fetch", [0.009] * 16 + [0.120], clock)
    (rec,) = closed[-1][1]
    watch._due = rec["at_s"] + 0.020
    cpu[0] += cpu_s
    assert watch.poll() == stall.IDLE_PERIOD_S and len(watch.pauses) == 1
    clock.t += stall.GRACE_S + 1
    _, _, done = watch.close_step(clock.t)
    assert done == [rec] and rec["held_by"] == word
    assert rec["sampler_late_ms"] == pytest.approx(100.0)
    assert rec["late_cpu_ms"] == pytest.approx(1e3 * cpu_s)
    assert rec["sampled_ms"] is None and rec["threads"] is None


def test_a_sampled_span_that_is_no_stall_leaves_no_record(tmp_path):
    # the sampler starts at the norm + 20 ms, the rule flags at + 50 ms
    clock = HandClock()
    watch = _watch(tmp_path, clock)
    acc = PhaseAccumulator(clock, stalls=watch)

    def sampler(i):
        if i == stall.REFRESH:
            write_proc(str(tmp_path), **CALM)
            assert watch.poll() == stall.POLL_PERIOD_S

    closed = _spans(acc, "decode.fetch", [0.010] * stall.REFRESH + [0.059],
                    clock, inside=sampler, frac=0.6)
    assert _nothing(closed[-1]) and not watch.ring
    assert watch.poll() == stall.IDLE_PERIOD_S and watch._cur is None


def test_the_sampled_part_is_a_serve_stall_event_of_the_profiler(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _sampled_stall(tmp_path, _grown())
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path / "trace"), "plugins",
                                  "profile", "*", "*.xplane.pb"))[0]
    events = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name == "serve.stall"]
    assert len(events) == 1
    stats = dict(events[0].stats)
    assert stats["span"] == "decode.fetch" and \
        int(stats["step"]) == stall.REFRESH


# ---------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def model():
    paddle.seed(29)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=64, dropout=0.0))
    m.eval()
    return m


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 97, (n,)).astype(np.int32)


def _engine(model, clock="step", **overrides):
    kw = dict(max_batch=2, num_pages=40, page_size=4, max_prompt_len=8)
    kw.update(overrides)
    return ServingEngine(model, ServingConfig(**kw),
                         clock=StepClock() if clock == "step" else clock)


def _stretch(engine, attr, span, by=100.0, inside=None):
    """Make one call of ``engine.<attr>`` last ``by`` seconds longer on
    the engine's clock: the first made inside an open span ``span`` whose
    name has a norm. ``inside()`` stands for the sampler's pass inside
    the wait (default: one ``poll``)."""
    real, fired = getattr(engine, attr), []

    def slow(*args, **kwargs):
        waiting = engine._stalls.waiting
        if not fired and waiting is not None and waiting[0] == span \
                and waiting[2] != float("inf"):  # the name has a norm
            fired.append(waiting)
            if by:
                engine._clock.t += by
            (inside or engine._stalls.poll)()
        return real(*args, **kwargs)

    setattr(engine, attr, slow)
    return fired


@pytest.mark.parametrize("attr,span", [
    ("_fetch", "decode.fetch"), ("_decode_args", "decode.upload"),
    ("_decode_jit", "decode.dispatch"), ("_prefill_args", "prefill.upload"),
    ("_prefill_jit", "prefill.dispatch")])
def test_engine_records_one_stall_and_counts_it(model, tmp_path, attr, span):
    engine = _engine(model)
    engine._stalls.proc_root = str(tmp_path)
    write_proc(str(tmp_path), **CALM)
    assert engine._stalls.thread is None  # a clock of its own: no thread
    for i in range(12):
        engine.add_request(_prompt(5, seed=i), 4)
    _stretch(engine, attr, span)
    engine.run()
    (rec,) = engine.stalls
    # (the awaited value, a fetch's own or the newest launch's, may or
    # may not be ready on the CPU backend at the sampler's one look)
    word = rec["held_by"]
    assert rec["span"] == span and word in ("asleep", "device")
    assert rec["norm_ms"] == 1e3 and rec["ms"] >= 101e3
    excess = 1e-3 * rec["excess_ms"]  # as the engine counts it
    whole = round(excess)             # the clock is whole seconds
    assert excess == pytest.approx(whole) == 1e-3 * rec["ms"] - 1.0
    # in its step's record, the same object as in the ring
    tl = engine.timeline.records()
    (holder,) = [r for r in tl if r.extra]
    assert holder.step == rec["step"] and holder.extra["stalls"] == [rec]
    # (a step of two prefills holds two spans of a prefill's name)
    assert holder.span_s[span] in (pytest.approx(1e-3 * rec["ms"]),
                                   pytest.approx(1e-3 * rec["ms"] + 1))
    snap = engine.metrics.snapshot()
    for w in HELD_BY:
        n = 1 if w == word else 0
        assert snap[f"serving_stalls_total{{held_by={w}}}"] == n
        assert snap[f"serving_stall_seconds_total{{held_by={w}}}"] == \
            n * excess
    step_s = snap["serving_step_seconds_total"]
    assert step_s == sum(r.duration for r in tl)
    assert snap["serving_step_unstalled_seconds_total"] == step_s - whole


def test_a_calm_run_has_no_stall_and_the_seconds_are_the_timeline_s(model):
    engine = _engine(model)
    snap = engine.metrics.snapshot()  # seeded at 0 before the first step
    assert snap["serving_step_seconds_total"] == 0 and \
        snap["serving_step_host_seconds_total"] == 0 and \
        snap["serving_step_unstalled_seconds_total"] == 0
    for span in SPANS:
        assert snap[f"serving_step_span_seconds_total{{span={span}}}"] == 0
    for i in range(5):
        engine.add_request(_prompt(5, seed=i), 6)
    engine.run()
    assert engine.stalls == []
    tl = engine.timeline.records()
    snap = engine.metrics.snapshot()
    step_s = sum(r.duration for r in tl)
    assert snap["serving_step_seconds_total"] == step_s
    assert snap["serving_step_unstalled_seconds_total"] == step_s
    by_span = {}
    for r in tl:
        for k, v in r.span_s.items():
            by_span[k] = by_span.get(k, 0.0) + v
    for k, v in by_span.items():
        assert snap[f"serving_step_span_seconds_total{{span={k}}}"] == v
    fetch_s = sum(v for k, v in by_span.items() if k.endswith(".fetch"))
    assert fetch_s > 0
    assert snap["serving_step_host_seconds_total"] == step_s - fetch_s
    assert all(v == 0 for k, v in snap.items()
               if k.startswith("serving_stall"))
    # a phase's mean: the sum is published beside the count
    assert snap["serving_step_phase_s_sum{phase=decode}"] == sum(
        r.phase_s.get("decode", 0.0) for r in tl)
    assert snap["serving_step_duration_s_sum"] == step_s
    text = engine.metrics.prometheus()
    assert "# TYPE serving_step_seconds_total counter" in text
    assert "# TYPE serving_step_span_seconds_total counter" in text
    assert 'serving_stalls_total{held_by="device"} 0' in text


def test_a_verify_fetch_is_watched_too(model, tmp_path):
    engine = _engine(model, spec=SpecConfig(method="ngram", depth=2))
    engine._stalls.proc_root = str(tmp_path)
    for i in range(3):
        engine.add_request(_prompt(5, seed=i), 30)
    _stretch(engine, "_fetch", "verify.fetch")
    engine.run()
    assert [s["span"] for s in engine.stalls] == ["verify.fetch"]


def test_tracing_off_starts_no_thread_and_seeds_no_counter(model):
    before = {t.ident for t in threading.enumerate()}
    engine = _engine(model, clock=None, enable_tracing=False)
    assert engine._stalls is None and engine._attr.stalls is None
    assert {t.ident for t in threading.enumerate()} <= before
    engine.add_request(_prompt(5), 4)
    engine.run()
    snap = engine.metrics.snapshot()
    assert not [k for k in snap if k.startswith(
        ("serving_step_seconds", "serving_step_host", "serving_step_span",
         "serving_step_unstalled", "serving_stall"))]
    assert engine.stalls == [] and "stalls" in engine.flight_record()
    engine.close()  # nothing to end: no error


def test_the_sampler_thread_ends_with_the_engine(model):
    engine = _engine(model, clock=None)
    thread = engine._stalls.thread
    assert thread.is_alive() and thread.daemon
    engine.close()
    thread.join(10)
    assert not thread.is_alive()
    # ... and with an engine that is dropped without a close()
    engine = _engine(model, clock=None)
    thread = engine._stalls.thread
    engine.add_request(_prompt(5), 3)
    engine.run()
    del engine
    gc.collect()
    thread.join(10)
    assert not thread.is_alive()


def test_the_sampler_adds_no_device_sync_to_a_decode_loop(model):
    # the SyncTally formula of tests/test_obs_attribution.py, with the
    # sampler thread running and asking is_ready() of a stalled fetch
    engine = _engine(model, clock=None)
    try:
        for i in range(3):
            engine.add_request(_prompt(4, seed=i), 12)
        asked = threading.Event()

        class Asked:
            """The awaited array, telling when the sampler asked it."""

            def __init__(self, out):
                self.out = out

            def is_ready(self):
                asked.set()
                return self.out.is_ready()

        def hold():
            # the span stays open until the sampler has looked at it
            w = engine._stalls.waiting
            engine._stalls.waiting = w[:3] + (Asked(w[3]),) + w[4:]
            assert asked.wait(30)

        fired = _stretch(engine, "_fetch", "decode.fetch", by=0,
                         inside=hold)
        with SyncTally() as tally:
            engine.run()
        snap = engine.metrics.snapshot()
        assert tally.count == int(snap["serving_decode_steps"]
                                  + snap["serving_prefills_total"])
        assert asked.is_set() and fired
    finally:
        engine.close()


def test_a_fatal_step_closes_its_spans_and_clears_the_slot(model):
    engine = _engine(model)
    engine.add_request(_prompt(5), 30)
    for _ in range(12):
        engine.step()
    seen = []

    def boom(prog, out):
        seen.append(engine._stalls.waiting)
        engine._clock.t += 100.0
        raise RuntimeError("induced fetch failure")

    engine._fetch = boom
    with pytest.raises(RuntimeError, match="induced fetch failure"):
        engine.step()
    assert seen[0][0] == "decode.fetch" and seen[0][3] is not None
    assert engine._stalls.waiting is None
    fatal = engine.timeline.records()[-1]
    assert fatal.extra["fatal"].startswith("RuntimeError")
    # the fetch that raised had outlasted its norm: flagged, in the
    # fatal step's own record and in the dump
    assert [s["span"] for s in fatal.extra["stalls"]] == ["decode.fetch"] * \
        len(fatal.extra["stalls"]) and fatal.extra["stalls"]
    rec = engine.last_flight_record
    assert rec["steps"][-1]["extra"]["stalls"][0]["span"] == "decode.fetch"


def test_the_record_is_in_a_dump_and_in_the_cli_s_table(model, tmp_path,
                                                       capsys):
    engine = _engine(model)
    engine._stalls.proc_root = str(tmp_path / "proc")
    write_proc(str(tmp_path / "proc"), **CALM)
    for i in range(4):
        engine.add_request(_prompt(5, seed=i), 8)
    _stretch(engine, "_fetch", "decode.fetch")
    engine.run()
    (rec,) = engine.stalls
    path = tmp_path / "dump.json"
    dump = validate_flight_record(engine.dump_flight_record(path))
    assert dump["stalls"] == [rec]
    (step,) = [s for s in dump["steps"] if s["extra"]]
    assert step["extra"]["stalls"] == [rec] and step["step"] == rec["step"]
    assert obs_main(["--flight-record", str(path), "--stalls"]) == 0
    out = capsys.readouterr().out
    assert out.rstrip("\n") == stall_table([rec])
    row = out.splitlines()[1].split()
    assert row[0] == str(rec["step"]) and row[1] == "decode.fetch" and \
        row[-1] == rec["held_by"]
    assert obs_main(["--flight-record", str(path)]) == 0
    assert "stalls (1; --stalls prints all)" in capsys.readouterr().out
    # a dump written before the ring was has none to show
    del dump["stalls"]
    old = tmp_path / "old.json"
    old.write_text(json.dumps(dump))
    assert obs_main(["--flight-record", str(old), "--stalls"]) == 0
    assert capsys.readouterr().out.rstrip("\n") == stall_table([])
    assert obs_main(["--fleet-record", str(old), "--stalls"]) == 2


def test_no_record_is_lost_between_the_two_threads(tmp_path):
    """More spans than the sampler can follow, under a switch interval a
    thousand times shorter: every flagged span is completed once, sampled
    or not, and none twice."""
    write_proc(str(tmp_path), **CALM)
    watch = StallWatch(proc_root=str(tmp_path))
    watch.tid = ENGINE_TID
    clock = watch._clock
    acc = PhaseAccumulator(clock, stalls=watch)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    watch.start()
    flagged, done = [], []
    try:
        for step in range(400):
            acc.enter_step(step)
            acc.begin()
            with acc.span("decode.fetch", awaited=Value(step % 2 == 0)):
                if step >= 16 and step % 20 == 0:
                    end = clock() + 0.06
                    while clock() < end:
                        pass
            acc.finish()
            acc.exit_step()
            _, f, d = watch.close_step(clock())
            flagged += f
            done += d
        deadline = clock() + stall.GRACE_S + 5
        while len(done) < len(flagged) and clock() < deadline:
            done += watch.close_step(clock())[2]
    finally:
        sys.setswitchinterval(old)
        watch.stop()
        watch.thread.join(10)
    assert not watch.thread.is_alive()
    assert len(flagged) >= 10
    assert sorted(map(id, done)) == sorted(map(id, flagged))
    assert all(r["held_by"] in HELD_BY for r in done)
    assert list(watch.ring) == done[-stall.RING:]
