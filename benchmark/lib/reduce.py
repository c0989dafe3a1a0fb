"""From a profiler trace and the driver's own records to per-layer
metrics. The arithmetic works on plain event records, so it is tested on
hand-written ones; ``load`` fills them from an ``.xplane.pb``.

An event is ``Event(plane, line, name, start, end, stats)`` with times in
seconds on the trace's clock. Device planes are named ``/device:TPU:<n>``;
on each, the line ``XLA Ops`` holds one event per executed HLO operation
(a Pallas kernel shows under its kernel name) and ``XLA Modules`` one per
executed program. The host plane holds the ``bench.*`` annotations that
``run.py``'s drivers put around their calls.

A reader (``READERS``) takes ``(trace, facts, args, peaks)`` and returns a
number, or None when it finds nothing to read — never 0 for a share. A
metric's ``args.work`` names a function of ``facts["work"]``, the ``WORK``
of the configuration's family; its ``args.counter`` / ``args.over`` name
entries of ``facts["counters"]``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: operations that only hold others (their bodies' operations are events of
#: their own): counted in busy time once by the union, left out of the
#: list of operations by time
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float
    end: float
    stats: dict = field(default_factory=dict)


# --------------------------------------------------------------- arithmetic
def union_seconds(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def sum_by_pattern(events, pattern: str) -> tuple[float, int]:
    """(summed duration, count) of events whose name matches."""
    rx = re.compile(pattern)
    hit = [e for e in events if rx.search(e.name)]
    return sum(e.end - e.start for e in hit), len(hit)


def idle_gaps(intervals, lo: float, hi: float):
    """The gaps between the merged intervals inside ``[lo, hi]``."""
    gaps, at = [], lo
    for s, e in sorted(clip(intervals, lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def attribute_gaps(gaps, host_spans) -> dict[str, float]:
    """Each gap's seconds go to the host spans that overlap it, by overlap;
    what no span covers goes to ``(unattributed)``. Nested spans: the
    shortest span covering an instant wins, approximated by taking spans
    in order of length."""
    out = defaultdict(float)
    spans = sorted(host_spans, key=lambda e: e.end - e.start)
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for sp in spans:
            nxt = []
            for a, b in left:
                lo, hi = max(a, sp.start), min(b, sp.end)
                if hi > lo:
                    out[sp.name] += hi - lo
                    if lo > a:
                        nxt.append((a, lo))
                    if b > hi:
                        nxt.append((hi, b))
                else:
                    nxt.append((a, b))
            left = nxt
        out["(unattributed)"] += sum(b - a for a, b in left)
    return {k: v for k, v in out.items() if v > 0}


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0..100), nearest rank above."""
    if not values:
        return None
    v = sorted(values)
    k = min(len(v) - 1, max(0, -(-len(v) * q // 100) - 1))
    return float(v[int(k)])


# ------------------------------------------------------------------- trace
class Trace:
    def __init__(self, events: list[Event]):
        self.events = events
        self.devices = sorted({e.plane for e in events
                               if DEVICE_PLANE.match(e.plane)})
        self.host = [e for e in events if e.name.startswith("bench.")
                     and not DEVICE_PLANE.match(e.plane)]
        if self.host:
            self.lo = min(e.start for e in self.host)
            self.hi = max(e.end for e in self.host)
        else:
            self.lo = self.hi = 0.0

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def ops(self, plane: str | None = None) -> list[Event]:
        return [e for e in self.events if e.line == OPS_LINE
                and (e.plane == plane if plane else
                     DEVICE_PLANE.match(e.plane))
                and e.end > self.lo and e.start < self.hi]

    def modules(self, pattern: str) -> list[Event]:
        rx = re.compile(pattern)
        return [e for e in self.events if e.line == MODULES_LINE
                and DEVICE_PLANE.match(e.plane) and rx.search(e.name)
                and e.start >= self.lo and e.end <= self.hi]

    def module_calls(self, pattern: str) -> float:
        """How many executions of a program the window holds on the first
        chip, one cut by an end of the window counted by the share of it
        that lies inside. With steps dispatched ahead the device runs
        other steps than the host is dispatching, so the work of a window
        is counted here, from the same events and over the same ``[lo,
        hi]`` as its time, never from the host's count of dispatches."""
        rx = re.compile(pattern)
        n = 0.0
        for e in self.events:
            if e.line == MODULES_LINE and e.plane == self.devices[0] \
                    and rx.search(e.name) and e.end > e.start:
                inside = min(e.end, self.hi) - max(e.start, self.lo)
                if inside > 0:
                    n += inside / (e.end - e.start)
        return n

    def ops_inside(self, modules: list[Event]) -> list[Event]:
        """The operations that ran inside the given executions."""
        spans = [(m.plane, m.start, m.end) for m in modules]
        return [e for e in self.ops()
                if any(e.plane == p and e.start >= s and e.end <= t
                       for p, s, t in spans)]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        per = [union_seconds(clip([(e.start, e.end) for e in self.ops(p)],
                                  self.lo, self.hi)) for p in self.devices]
        return sum(per) / len(per)

    def busy_inside(self, spans) -> float:
        """Device-busy seconds (first chip) inside the given intervals."""
        ops = [(e.start, e.end) for e in self.ops(self.devices[0])]
        return sum(union_seconds(clip(ops, s, e)) for s, e in spans)

    def breakdown(self, top: int = 10) -> dict:
        by = defaultdict(float)
        for e in self.ops(self.devices[0] if self.devices else None):
            if not CONTAINERS.match(e.name):
                by[re.sub(r"[.\d]+$", "", e.name) or e.name] += \
                    e.end - e.start
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        gaps = idle_gaps([(e.start, e.end)
                          for e in self.ops(self.devices[0])],
                         self.lo, self.hi) if self.devices else []
        att = attribute_gaps(gaps, self.host)
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in
                              sorted(att.items(), key=lambda kv: -kv[1])[:top]]}


def op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%name.7 = f32[..] fusion(%operand, ..)``): keep the instruction's
    own name, so that a pattern never matches an operand."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` with nothing but JAX."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith("bench."):
                    continue
                s = ev.start_ns * 1e-9
                events.append(Event(plane.name, line.name, op_name(ev.name),
                                    s, s + ev.duration_ns * 1e-9))
    return Trace(events)


# ----------------------------------------------------------------- readers
def _traced(facts: dict) -> dict:
    return facts.get("traced") or {}


def read_module_ms_per_call(trace, facts, args, peaks):
    """Device-busy time inside the executions of one program, per call."""
    mods = trace.modules(args["module"])
    if not mods:
        return None
    busy = trace.busy_inside([(m.start, m.end) for m in mods])
    return 1e3 * busy / len(mods)


def _with_calls(facts: dict, calls: float) -> dict:
    """The driver's record of the traced window with the number of calls
    that the trace itself holds."""
    return dict(facts, traced=dict(_traced(facts), calls=calls))


def read_mfu(trace, facts, args, peaks):
    """Model FLOPs of the work done in the traced window over the window
    times the chip's peak. Where ``args`` names the step's ``module``, the
    work is that of the executions the window holds (``module_calls``);
    otherwise what the driver recorded of the steps inside it."""
    if "module" in args:
        if not trace.devices:
            return None
        facts = _with_calls(facts, trace.module_calls(args["module"]))
    flops = facts["work"][args["work"]](facts["model"],
                                        _traced(facts))["flops"]
    if not flops or trace.window_s <= 0:
        return None
    return 100.0 * flops / (trace.window_s * peaks["bf16_flops_per_s"])


def _least_seconds(facts, args, peaks) -> float:
    """The least time the chip could take for the work ``args`` names: the
    larger of FLOPs over peak and bytes over bandwidth."""
    w = facts["work"][args["work"]](facts["model"], _traced(facts))
    return max(w["flops"] / peaks["bf16_flops_per_s"],
               w["bytes"] / peaks["hbm_bytes_per_s"])


def read_kernel_roofline(trace, facts, args, peaks):
    """The least time the chip could take for a kernel's work (the larger
    of FLOPs over peak and bytes over bandwidth) over the summed device
    time of its calls. Where ``args`` names the ``module`` that calls the
    kernel, both are taken of the executions that lie whole inside the
    window: the kernel's calls inside them, and the work of as many."""
    ops = trace.ops()
    if "module" in args:
        mods = trace.modules(args["module"])
        ops = trace.ops_inside(mods)
        facts = _with_calls(facts, len(mods))
    spent, calls = sum_by_pattern(ops, args["op"])
    if not calls or spent <= 0:
        return None
    least = _least_seconds(facts, args, peaks)
    if least <= 0:
        return None
    return 100.0 * least / spent


def read_module_roofline(trace, facts, args, peaks):
    """As above for a whole program: its work over the device-busy time
    inside its executions."""
    mods = trace.modules(args["module"])
    if not mods:
        return None
    busy = trace.busy_inside([(m.start, m.end) for m in mods])
    least = _least_seconds(facts, args, peaks)
    if busy <= 0 or least <= 0:
        return None
    return 100.0 * least / busy


def read_idle_share(trace, facts, args, peaks):
    if not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)


def read_span_percentile(trace, facts, args, peaks):
    """A percentile of one of the driver's own lists of times (ms)."""
    values = (facts.get("spans") or {}).get(args["span"])
    if not values:
        return None
    return percentile(values, args["percentile"])


def read_counter_share(trace, facts, args, peaks):
    """One counter over another, window only, in percent."""
    c = facts.get("counters") or {}
    if args["over"] not in c or args["counter"] not in c or not c[args["over"]]:
        return None
    return 100.0 * c[args["counter"]] / c[args["over"]]


def read_host_ms_per_call(trace, facts, args, peaks):
    """Wall time of a host span minus the device-busy time inside it, per
    call: what the host adds around the device's work."""
    spans = [e for e in trace.host if e.name == args["span"]]
    if not spans or not trace.devices:
        return None
    wall = sum(e.end - e.start for e in spans)
    busy = trace.busy_inside([(e.start, e.end) for e in spans])
    return 1e3 * (wall - busy) / len(spans)


READERS = {
    "module_ms_per_call": read_module_ms_per_call,
    "mfu": read_mfu,
    "kernel_roofline": read_kernel_roofline,
    "module_roofline": read_module_roofline,
    "idle_share": read_idle_share,
    "span_percentile": read_span_percentile,
    "counter_share": read_counter_share,
    "host_ms_per_call": read_host_ms_per_call,
}
