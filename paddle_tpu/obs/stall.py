"""The stall record: what held a blocking wait of the engine's thread.

A *blocking span* of ``obs/attribution.py`` is one whose name ends in
``.fetch``, ``.upload`` or ``.dispatch``: the engine's one fetch, its one
launch, and the upload sites. One to four times a minute such a wait lasts
ten to a hundred times its norm with the thread using no CPU; the span's
seconds say WHERE, this module says WHAT HELD IT. Three parts, one object
(:class:`StallWatch`, one an enabled ``PhaseAccumulator``):

**Flagging, on the engine's thread, on the engine's clock.** A name's
NORM is what nine in ten of its last :data:`HISTORY` spans stayed under
(taken anew at every span of a young name, every :data:`REFRESH` of an
old one). A blocking span that outlasts its name's norm by more than
``max(FLOOR_S, norm)`` is a stall; its excess over the norm is its stall
seconds. A span with no norm yet (the first :data:`REFRESH` of a name) is
never one. The issue asked for the median; the chip showed why nine in
ten: a window's first step admits 13 prompts of 4,096 tokens (cell M) and
each ``prefill.upload`` then waits 100 ms for room in the device's queue,
behind a median that the few fast ones before had set, so 29 waits that
are the device's backpressure read as stalls; what nine in ten stayed
under follows the change within a span or two. The cost is a tuple and two
attribute stores a blocking span, and one float compare.

**Evidence, on a sampler thread, in real time.** At its start the span
writes ``(name, t0, sample_at, awaited, step)`` into :attr:`waiting` and at
its end clears it; ``awaited`` is the device value the wait is for (a
fetch: the array about to be copied; an upload or a dispatch: the newest
launch's output, which is not ready exactly while the device still has
work queued). The sampler (:meth:`StallWatch.poll`, a pass every
:data:`IDLE_PERIOD_S` on a daemon thread that references nothing of the
engine) notes at every pass how late the pass itself came and the
process's and the engine thread's CPU clocks (two clock reads). A pass
that finds the slot still set past ``sample_at`` (the name's norm +
:data:`SAMPLE_AFTER_S`) takes a first sample of the process and the
machine, then passes every :data:`POLL_PERIOD_S` asking
``awaited.is_ready()`` (non-blocking: no device sync) until the slot
clears, and takes a last sample. A sample (:func:`read_sample`) is this
process's threads from ``/proc/self/task/*/{stat,schedstat}`` (state, CPU
time, time runnable and waiting for a core, the core last run on, major
faults), the engine thread's voluntary and involuntary switches from its
``status``, ``/proc/pressure/{cpu,memory,io}`` (``some total``),
``/proc/loadavg``, the first line of ``/proc/stat`` and the
``pgmajfault``, ``allocstall*`` and ``compact_stall`` lines of
``/proc/vmstat``. A file that is absent gives ``None``, never an error
(the chip's sandboxed host has ``stat`` in 10 ms ticks and ``status``
without the switches, and none of the rest); the ``/proc`` root is a
parameter. While a profiler session is on, the sampled part is a
``serve.stall`` event (``span=``, ``step=``) on the sampler's own thread
in the host plane, beside the device's ``XLA Modules`` line.

**How late the sampler's own pass came is the first piece of evidence.**
Its wait is a timed one that needs nothing of the device, the runtime or
the engine's thread (which waits with the interpreter lock released). If
the pass that should have come 20 ms into a stalled wait comes only
with the wait's release, every thread of the process stood still: no
affair of the blocking call or of the device. A pass over
:data:`FLOOR_S` late is kept in :attr:`StallWatch.pauses` whether or not
the engine's thread was in a blocking span then.

Nothing is read on the engine's thread: a reading at the start of every
blocking span (the only way to a difference over the WHOLE span) would
be three system calls a span, 5 to 100 us each on the chip's host. The
engine thread's CPU comes from its pthread CPU clock as the sampler reads
it (the counter ``time.thread_time()`` reads), its switches and major
faults from its ``/proc/self/task/<tid>`` files (those ``RUSAGE_THREAD``
reads). The CPU clocks cover the wait from the sampler's last pass
BEFORE the one that found it overdue; the two samples' differences cover
the SAMPLED PART: from the first sample, about the name's norm + 20 to
40 ms into the wait where the sampler ran on time, to the last, taken
within 2 ms of the release.

**The record,** completed on the engine's thread at a step's close
(:meth:`StallWatch.close_step`) from the flagged span and the evidence the
sampler left for it:

=========================  ============================================
field                      what
=========================  ============================================
``step``, ``span``         the engine step and the span's name
``at_s``                   the span's start, engine clock
``ms``, ``excess_ms``,     the span's length, its excess over the
``norm_ms``                name's norm, that norm
``sampler_late_ms``,       the most that a pass of the sampler came
``late_cpu_ms``            late, from the one that found the span
                           overdue to the release (for a span never
                           sampled: of the passes that overlap it),
                           and the CPU the process used meanwhile
``sampled_from_ms``,       where in the span the first sample was
``sampled_ms``             taken, and how long the sampled part is
``awaited``,               whether a device value was awaited, and ms
``device_ready_after_ms``  into the span at which it was first seen
                           ready (at the first sample: an upper
                           bound); ``None``: never
``thread_cpu_ms``,         CPU of the engine's thread, and of the whole
``process_cpu_ms``         process less the sampler's own, from the
                           sampler's last pass before the wait came
                           overdue to the release
``nvcsw``, ``nivcsw``,     the engine thread's voluntary / involuntary
``majflt``,                switches and major faults, and every
``process_majflt``         thread's major faults, over the sampled
                           part
``threads``                the five threads with most CPU and the five
                           with most run-queue wait over it: ``tid``,
                           ``comm``, ``state``, ``cpu_ms``,
                           ``runq_wait_ms``, ``core``
``psi``                    ``{cpu, memory, io}``: ms by which each
                           ``some total`` grew
``loadavg``                ``/proc/loadavg`` at the release
``vmstat``                 growth of ``pgmajfault``, ``allocstall*``,
                           ``compact_stall``
``machine_cpu_ms``,        CPU that every core of the machine spent
``steal_ms``               busy, and that the hypervisor ran in the
                           machine's place (``/proc/stat``, 10 ms ticks
                           summed over the cores)
``held_by``                one word, below
=========================  ============================================

``held_by`` by fixed rules in this order (:func:`held_by`), decided at
the step's close where the wait's exact length is known. First: where a
pass of the sampler came late by over half of the stall's excess,
``frozen`` (the process as a whole stood still: held from outside it, by
the machine or the sandbox it runs in), unless the process used over
:data:`BUSY_SHARE` of that lateness in CPU: ``runtime_busy`` (somebody ran
and kept the rest from running: the interpreter's lock in a collection or
a trace). Then,
over the sampled part, of length D: ``device`` (a value was awaited and
was not ready until within :data:`READY_SLACK_S` of the release),
``cpu_queue`` (a thread of the process, or PSI cpu, spent over D/2
runnable and waiting for a core), ``memory`` (PSI memory over D/2, or the
kernel stalled an allocation or a compaction), ``io`` (PSI io over D/2, or
a thread of the process took a major fault), ``runtime_busy`` (the
process's other threads used over D/2 of CPU), else ``asleep`` (the
device done, nobody running, nobody waiting for a core: the wake-up
itself). ``unsampled``: the span was flagged, the sampler left no evidence
within :data:`GRACE_S` and none of its passes came late over it (an engine
on a virtual clock has no sampler thread). The raw fields stay in the
record, so a reader who distrusts a rule applies their own.

Imports nothing from ``paddle_tpu.serving`` and touches no device state:
``is_ready()`` is a non-blocking query.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

__all__ = ["BLOCKING", "HELD_BY", "StallWatch", "held_by", "read_sample",
           "stall_table"]

#: what a blocking span's name ends in
BLOCKING = (".fetch", ".upload", ".dispatch")
#: the label set of ``serving_stalls_total{held_by=}``, in rule order
HELD_BY = ("frozen", "device", "cpu_queue", "memory", "io", "runtime_busy",
           "asleep", "unsampled")

#: spans of a name that its norm is taken over: a window's first step of
#: 256 prefills must not set it for long, a minute of steps must
HISTORY = 64
#: an old name's norm is taken anew every so many spans, and a name has
#: none before as many: sorting 64 floats is 3 us and a step has four to
#: seven blocking spans
REFRESH = 8
#: a stall outlasts its norm by more than max(this, the norm): under
#: 50 ms a wait is a late wake-up among many; cell M's 100 ms
#: ``prefill.fetch`` and cell K's 256 first-step uploads are their norm
FLOOR_S = 0.050
#: the sampler starts on a span this long past its norm: early enough
#: to hold most of a 120 ms stall, late enough that a plain span never
#: costs a sample
SAMPLE_AFTER_S = 0.020
#: the sampler's period with nothing overdue, and while it samples (the
#: ``device`` rule resolves 5 ms, so readiness is asked more finely)
IDLE_PERIOD_S = 0.020
POLL_PERIOD_S = 0.002
#: after this many idle passes with no span open the sampler sleeps
#: RESTING_PERIOD_S a pass: an engine that nobody steps costs 5 wake-ups a
#: second, not 50
RESTING_AFTER = 100
RESTING_PERIOD_S = 0.2
#: a late pass of the sampler is somebody's doing where the process used
#: over this share of the lateness in CPU: on the chip a hole in which the
#: process was not run reads 0 to 0.6 (10 ms ticks: the burst of threads
#: that wake with it), a held interpreter lock 1.0 to 1.2
BUSY_SHARE = 0.75
#: ``device``: the awaited value became ready this close to the release
READY_SLACK_S = 0.005
#: a flagged span waits this long for its evidence before it is closed
#: ``unsampled``
GRACE_S = 1.0
#: the newest records kept (``engine.stalls``)
RING = 64
#: threads listed by CPU and by run-queue wait
TOP_THREADS = 5

_NEVER = float("inf")
_TICK_S = 1.0 / os.sysconf("SC_CLK_TCK")
_VMSTAT = ("pgmajfault", "allocstall", "compact_stall")
_log = logging.getLogger(__name__)


# ------------------------------------------------------------ /proc readers
def _read(path: str) -> str | None:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def _threads(root: str, but: int | None = None) -> dict:
    """{tid: (comm, state, cpu_s, runq_wait_s | None, core, majflt)} of
    this process's threads but the reader's own; a thread that ends under
    the reader is left out."""
    task = os.path.join(root, "self", "task")
    try:
        tids = os.listdir(task)
    except OSError:
        return {}
    out = {}
    for tid in tids:
        if tid == str(but):
            continue
        stat = _read(os.path.join(task, tid, "stat"))
        if not stat:
            continue
        close = stat.rfind(")")
        f = stat[close + 2:].split()
        if len(f) < 37:
            continue
        sched = (_read(os.path.join(task, tid, "schedstat")) or "").split()
        if len(sched) >= 2:  # on-CPU and run-queue ns, exact
            cpu_s, runq_s = 1e-9 * int(sched[0]), 1e-9 * int(sched[1])
        else:                # utime + stime in ticks
            cpu_s, runq_s = _TICK_S * (int(f[11]) + int(f[12])), None
        out[int(tid)] = (stat[stat.find("(") + 1:close], f[0], cpu_s,
                         runq_s, int(f[36]), int(f[9]))
    return out


def _psi_s(root: str, what: str) -> float | None:
    """Seconds that some task has stalled on ``what`` since boot."""
    for line in (_read(os.path.join(root, "pressure", what)) or
                 "").splitlines():
        if line.startswith("some"):
            return 1e-6 * int(line.rsplit("total=", 1)[1])
    return None


def _vmstat(root: str) -> dict | None:
    text = _read(os.path.join(root, "vmstat"))
    if text is None:
        return None
    out = {}
    for line in text.splitlines():
        if line.startswith(_VMSTAT):
            k, v = line.split()
            out[k] = int(v)
    return out


def _switches(root: str, tid) -> tuple:
    """(voluntary, involuntary) context switches of one thread."""
    vol = invol = None
    for line in (_read(os.path.join(root, "self", "task", str(tid),
                                    "status")) or "").splitlines():
        if line.startswith("voluntary_ctxt_switches"):
            vol = int(line.split()[1])
        elif line.startswith("nonvoluntary_ctxt_switches"):
            invol = int(line.split()[1])
    return vol, invol


def _machine(root: str) -> tuple:
    """(busy, stolen) CPU seconds of the whole machine since boot, from
    the first line of ``stat``: user + nice + system + irq + softirq, and
    ``steal``: what the hypervisor ran in this machine's place."""
    try:
        with open(os.path.join(root, "stat")) as f:
            v = [int(x) for x in f.readline().split()[1:9]]
    except (OSError, ValueError):
        return None, None
    if len(v) < 8:
        return None, None
    return _TICK_S * (v[0] + v[1] + v[2] + v[5] + v[6]), _TICK_S * v[7]


def read_sample(root: str, tid, now: float, own_tid=None) -> dict:
    """One reading of the process and the machine at ``now``; ``tid`` is
    the engine's thread, ``own_tid`` the sampler's, left out of the
    threads."""
    return {"t": now, "process_cpu_s": time.process_time(),
            "threads": _threads(root, own_tid),
            "switches": _switches(root, tid),
            "psi": {k: _psi_s(root, k) for k in ("cpu", "memory", "io")},
            "machine": _machine(root),
            "loadavg": (_read(os.path.join(root, "loadavg")) or "").strip()
            or None,
            "vmstat": _vmstat(root)}


# ---------------------------------------------------------------- the rules
def _grew(first, last):
    return None if first is None or last is None else last - first


def held_by(rec: dict) -> str:
    """The one word for a stall's record (the module docstring's rules,
    in their order)."""
    late = rec["sampler_late_ms"]
    if late is not None and late > 0.5 * rec["excess_ms"]:
        # the sampler's own timed wake-up stood still with the wait: it
        # was no affair of this call or of the device
        return "runtime_busy" if rec["late_cpu_ms"] > BUSY_SHARE * late \
            else "frozen"
    if rec["sampled_ms"] is None:
        return "unsampled"
    half = 0.5 * rec["sampled_ms"]
    ready = rec["device_ready_after_ms"]
    if rec["awaited"] and (ready is None or
                           rec["ms"] - ready <= 1e3 * READY_SLACK_S):
        return "device"
    psi = rec["psi"]
    if any((t["runq_wait_ms"] or 0.0) > half for t in rec["threads"]) \
            or (psi["cpu"] or 0.0) > half:
        return "cpu_queue"
    if (psi["memory"] or 0.0) > half or any(
            v > 0 for k, v in (rec["vmstat"] or {}).items()
            if k != "pgmajfault"):
        return "memory"
    if (psi["io"] or 0.0) > half or rec["process_majflt"] > 0:
        return "io"
    if rec["process_cpu_ms"] - (rec["thread_cpu_ms"] or 0.0) > half:
        return "runtime_busy"
    return "asleep"


def _evidence(cur: tuple, before: tuple, first: dict, last: dict, cpu: tuple,
              late: tuple, ready_s, tid) -> dict:
    """A stall's evidence. ``before`` and ``cpu``: the sampler's ``(t,
    process CPU, engine thread CPU)`` at its last pass BEFORE the one that
    found the span overdue and at the release; ``first`` and ``last``:
    the two samples; ``late``: ``(seconds, process CPU seconds)`` of the
    latest that a pass of the sampler came between the two."""
    name, t0, _, awaited, _ = cur
    rows = []
    for t, (comm, state, cpu_s, runq, core, majflt) in \
            last["threads"].items():
        was = first["threads"].get(t, (comm, state, 0.0, 0.0 if runq is not
                                       None else None, core, 0))
        rows.append({"tid": t, "comm": comm, "state": state,
                     "cpu_ms": 1e3 * (cpu_s - was[2]),
                     "runq_wait_ms": None if runq is None or was[3] is None
                     else 1e3 * (runq - was[3]),
                     "core": core, "majflt": majflt - was[5]})
    by_cpu = sorted(rows, key=lambda r: -r["cpu_ms"])[:TOP_THREADS]
    by_wait = sorted(rows, key=lambda r: -(r["runq_wait_ms"] or 0.0))[
        :TOP_THREADS]
    mine = next((r for r in rows if r["tid"] == tid), None)
    psi = {k: None if (g := _grew(first["psi"][k], last["psi"][k])) is None
           else 1e3 * g for k in first["psi"]}
    vm = None if first["vmstat"] is None or last["vmstat"] is None else {
        k: v - first["vmstat"].get(k, 0) for k, v in last["vmstat"].items()}
    thread_cpu = _grew(before[2], cpu[2])
    threads = by_cpu + [r for r in by_wait if r not in by_cpu]
    return {"sampler_late_ms": 1e3 * late[0], "late_cpu_ms": 1e3 * late[1],
            "sampled_from_ms": 1e3 * (first["t"] - t0),
            "sampled_ms": 1e3 * (last["t"] - first["t"]),
            "awaited": awaited is not None,
            "device_ready_after_ms": None if ready_s is None
            else 1e3 * (ready_s - t0),
            "thread_cpu_ms": (mine and mine["cpu_ms"]) if thread_cpu is None
            else 1e3 * thread_cpu,
            "process_cpu_ms": 1e3 * (cpu[1] - before[1]),
            "nvcsw": _grew(first["switches"][0], last["switches"][0]),
            "nivcsw": _grew(first["switches"][1], last["switches"][1]),
            "majflt": mine and mine["majflt"],
            "process_majflt": sum(r["majflt"] for r in rows),
            "threads": [{k: v for k, v in r.items() if k != "majflt"}
                        for r in threads],
            "psi": psi, "loadavg": last["loadavg"], "vmstat": vm,
            "machine_cpu_ms": None if (g := _grew(
                first["machine"][0], last["machine"][0])) is None
            else 1e3 * g,
            "steal_ms": None if (g := _grew(
                first["machine"][1], last["machine"][1])) is None
            else 1e3 * g}


_NO_EVIDENCE = dict.fromkeys(
    ("sampler_late_ms", "late_cpu_ms", "sampled_from_ms", "sampled_ms",
     "awaited", "device_ready_after_ms", "thread_cpu_ms", "process_cpu_ms",
     "nvcsw", "nivcsw", "majflt", "process_majflt", "threads", "psi",
     "loadavg", "vmstat", "machine_cpu_ms", "steal_ms"), None)


def _is_ready(awaited) -> bool:
    try:
        return bool(awaited.is_ready())
    except Exception:  # noqa: BLE001 — a deleted or foreign value: not known
        return False


# ----------------------------------------------------------------- the watch
class _Norm:
    """What is normal for one span name: its last durations, what nine
    in ten of them stayed under, and the two marks derived from it."""

    __slots__ = ("seen", "n", "norm", "limit", "sample_after")

    def __init__(self):
        self.seen = deque(maxlen=HISTORY)
        self.n = 0
        self.norm = None
        self.limit = self.sample_after = _NEVER

    def add(self, dt: float) -> None:
        self.seen.append(dt)
        n = self.n = self.n + 1
        # a young name follows a change of regime at once (a window's
        # first step of many prefills); an old one every REFRESH spans
        if n >= REFRESH and (n < HISTORY or n % REFRESH == 0):
            ranked = sorted(self.seen)
            m = self.norm = ranked[-(-9 * len(ranked) // 10) - 1]
            self.limit = m + max(FLOOR_S, m)
            self.sample_after = m + SAMPLE_AFTER_S


class StallWatch:
    """Flags the blocking spans that outlast their norm, samples what
    held them, and completes their records (module docstring). The
    engine's thread calls :meth:`enter` / :meth:`exit` (through a blocking
    ``_Span``) and :meth:`close_step`; the sampler thread (:meth:`start`),
    or a test by hand, calls :meth:`poll`. ``clock`` is the SAMPLER's and
    must run with the clock that stamps the spans: ``time.monotonic``
    beside an engine on its default clock. An engine on a clock of its
    own starts no thread."""

    def __init__(self, proc_root: str = "/proc", clock=time.monotonic,
                 cpu=None):
        self.proc_root = proc_root
        self._clock = clock
        self._cpu = cpu or self._read_cpu  # a test hands its own
        #: the open blocking span: (name, t0, sample_at, awaited, step)
        self.waiting = None
        self.tid = None    # the engine's thread, as /proc names it
        self.ident = None  # ... and as pthreads does (its CPU clock)
        self.ring: deque = deque(maxlen=RING)
        #: the sampler's own passes that came over FLOOR_S late, as
        #: (when it was due, seconds late, CPU seconds the process used
        #: meanwhile): with next to no CPU the process as a whole stood
        #: still then, inside a blocking span or not; with about as much
        #: a thread kept the interpreter's lock (a collection, a trace)
        self.pauses: deque = deque(maxlen=RING)
        self._norms: dict[str, _Norm] = {}
        self._stall_s = 0.0     # this step's
        self._flagged = []      # this step's records, still without evidence
        self._pending = {}      # (name, t0) -> flagged record
        self._evidence = deque()  # sampler -> engine: ((name, t0), dict)
        # the sampler's own
        self._cur = self._first = self._ready_s = self._ann = None
        self._pass = None      # (t, process CPU, engine CPU) at this pass
        self._from = None      # ... at the pass before a span came overdue
        self._due = None       # when this pass was due
        self._late = (0.0, 0.0)  # the latest a pass came, this span
        self._cpu_clock = None
        self._idle = 0
        self.thread = None    # the sampler thread, where one was started
        self._own_tid = None  # ... and its tid, once it runs
        self._stop = threading.Event()

    # ------------------------------------------- the engine's thread: spans
    def enter(self, name: str, t0: float, step: int, awaited) -> None:
        if self.tid is None:
            self.tid = threading.current_thread().native_id
            self.ident = threading.get_ident()
        norm = self._norms.get(name)
        self.waiting = (name, t0, t0 + (norm.sample_after if norm
                                        else _NEVER), awaited, step)

    def exit(self, name: str, t0: float, dt: float, step: int) -> None:
        self.waiting = None
        norm = self._norms.get(name)
        if norm is None:
            norm = self._norms[name] = _Norm()
        if dt > norm.limit:
            excess = dt - norm.norm
            self._stall_s += excess
            rec = {"step": step, "span": name, "at_s": t0, "ms": 1e3 * dt,
                   "excess_ms": 1e3 * excess, "norm_ms": 1e3 * norm.norm,
                   "held_by": None}
            self._flagged.append(rec)
            self._pending[(name, t0)] = rec
        norm.add(dt)

    def close_step(self, now: float) -> tuple:
        """At a step's close: ``(the step's stall seconds, the records of
        the spans flagged in it, the records completed now)``. A flagged
        record goes into its step's ``StepRecord.extra`` at once and is
        completed IN PLACE when the sampler's evidence for it arrives,
        this step or one of the next; one left without for
        :data:`GRACE_S` is closed ``unsampled``."""
        if not (self._flagged or self._pending or self._evidence
                or self._cur):
            return 0.0, (), ()
        if self.thread is None and self._cur is not None:
            self.poll()  # no sampler thread: the last sample is taken here
        stall_s, self._stall_s = self._stall_s, 0.0
        flagged, self._flagged = self._flagged, []
        done = []
        while self._evidence:
            key, ev = self._evidence.popleft()
            rec = self._pending.pop(key, None)
            if rec is not None:  # else: sampled, and no stall by the rule
                rec.update(ev)
                done.append(rec)
        for key, rec in list(self._pending.items()):
            end = rec["at_s"] + 1e-3 * rec["ms"]
            if end + GRACE_S < now:
                # never sampled: the sampler may have stood still with
                # the wait and come back with its release
                del self._pending[key]
                late = max(((late, cpu) for due, late, cpu
                            in tuple(self.pauses)  # the sampler appends
                            if due < end and due + late > rec["at_s"]),
                           default=(None, None))
                rec.update(_NO_EVIDENCE, sampler_late_ms=late[0] and
                           1e3 * late[0], late_cpu_ms=late[1] and
                           1e3 * late[1])
                done.append(rec)
        for rec in done:
            rec["held_by"] = held_by(rec)
        self.ring.extend(done)
        return stall_s, flagged, done

    # ------------------------------------------------------------ the sampler
    def _read_cpu(self) -> tuple:
        """(process CPU less the calling sampler's own, the engine
        thread's CPU or None), seconds."""
        if self._cpu_clock is None and self.ident is not None:
            try:
                self._cpu_clock = time.pthread_getcpuclockid(self.ident)
            except (AttributeError, OSError):
                self._cpu_clock = False
        return (time.process_time() - time.thread_time(),
                time.clock_gettime(self._cpu_clock) if self._cpu_clock
                else None)

    def poll(self, now: float | None = None) -> float:
        """One pass of the sampler; returns the seconds to its next."""
        at = self._clock() if now is None else now
        before, self._pass = self._pass, (at, *self._cpu())
        # (the two CPU clocks tick 10 ms apart on the chip's host: their
        # difference can fall by one tick)
        late = (0.0, 0.0) if self._due is None else (
            max(0.0, at - self._due), max(0.0, self._pass[1] - before[1]))
        if late[0] > FLOOR_S:
            self.pauses.append((self._due, *late))
        wait = self._look(at, late, before or self._pass)
        # the next pass is due a wait after this one ENDS (a sample takes
        # tens of ms on the chip's host)
        self._due = (self._clock() if now is None else now) + wait
        return wait

    def _look(self, now: float, late: tuple, before: tuple) -> float:
        waiting, cur = self.waiting, self._cur
        if cur is None:
            if waiting is None:
                self._idle += 1
                return RESTING_PERIOD_S if self._idle > RESTING_AFTER \
                    else IDLE_PERIOD_S
            self._idle = 0
            if now < waiting[2]:
                return IDLE_PERIOD_S
            # overdue: the first sample, readiness asked before it
            self._cur, self._late, self._from = waiting, late, before
            self._ann = TraceAnnotation("serve.stall", span=waiting[0],
                                        step=waiting[4])
            self._ann.__enter__()
            ready = waiting[3] is not None and _is_ready(waiting[3])
            self._first = read_sample(self.proc_root, self.tid, now,
                                      self._own_tid)
            self._ready_s = now if ready else None
            return POLL_PERIOD_S
        self._late = max(self._late, late)
        if waiting is cur:
            if self._ready_s is None and cur[3] is not None \
                    and _is_ready(cur[3]):
                self._ready_s = now
            return POLL_PERIOD_S
        # released: the last sample
        last = read_sample(self.proc_root, self.tid, now, self._own_tid)
        self._ann.__exit__(None, None, None)
        self._evidence.append(((cur[0], cur[1]), _evidence(
            cur, self._from, self._first, last, self._pass, self._late,
            self._ready_s, self.tid)))
        self._cur = self._first = self._ready_s = self._ann = None
        return IDLE_PERIOD_S

    def start(self) -> None:
        """Start the sampler thread (a daemon; :meth:`stop` ends it)."""
        if self.thread is None:
            self.thread = threading.Thread(
                target=self._run, name="serve-stall-sampler", daemon=True)
            self.thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        self._own_tid = threading.get_native_id()
        wait = IDLE_PERIOD_S
        while not self._stop.wait(wait):
            try:
                wait = self.poll()
            except Exception:  # noqa: BLE001 — the sampler must keep running
                _log.exception("stall sampler: a pass failed")
                self._cur = self._first = self._ready_s = self._ann = None
                self._due, wait = None, IDLE_PERIOD_S


def stall_table(stalls) -> str:
    """The fixed-width table of stall records (``--stalls``)."""
    def ms(v):
        return "-" if v is None else f"{v:.1f}"

    lines = [f"{'step':>7} {'span':<17} {'ms':>8} {'excess':>8} "
             f"{'norm':>7} {'sampler late':>12} {'ready@':>7} "
             f"{'thr cpu':>7} {'proc cpu':>8} {'runq max':>8} "
             f"{'psi c/m/i':>14}  held_by"]
    for s in stalls:
        psi = s.get("psi") or {}
        waits = [t["runq_wait_ms"] for t in s.get("threads") or ()
                 if t["runq_wait_ms"] is not None]
        lines.append(
            f"{s['step']:>7} {s['span']:<17} {s['ms']:>8.1f} "
            f"{s['excess_ms']:>8.1f} {s['norm_ms']:>7.1f} "
            f"{ms(s.get('sampler_late_ms')):>12} "
            f"{ms(s.get('device_ready_after_ms')):>7} "
            f"{ms(s.get('thread_cpu_ms')):>7} "
            f"{ms(s.get('process_cpu_ms')):>8} "
            f"{ms(max(waits) if waits else None):>8} "
            f"{'/'.join(ms(psi.get(k)) for k in ('cpu', 'memory', 'io')):>14}"
            f"  {s.get('held_by')}")
    if not stalls:
        lines.append("  (none)")
    return "\n".join(lines)
