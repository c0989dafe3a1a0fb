"""Goodput attribution: where does an engine step's wall time actually go.

One host-only instrument, :class:`PhaseAccumulator`, and one mechanism
with two records. Every boundary inside ``ServingEngine.step()`` is
marked at ONE call site (``with att.span("decode.fetch"):``), and that
site writes both:

- **seconds on the engine's clock** (pluggable, virtual in the tests),
  rolled onto each :class:`~paddle_tpu.obs.timeline.StepRecord` and into
  the ``serving_step_phase_s{phase=}`` histogram family, and
- **a** ``jax.profiler.TraceAnnotation`` — a TraceMe event in the host
  plane of the profiler's own trace (the xplane), on the same timeline as
  the ``/device:TPU:n`` planes — so a gap of the device can be put down to
  the part of the step that the host was in. With no profiler session the
  TraceMe records nothing (under a microsecond).

Every ``*.dispatch`` span is opened by the engine's one launch
(``ServingEngine._launch``) and every ``*.fetch`` span holds its one fetch
(``_fetch``), whichever program runs. The span tree (every span carries ``step=<engine step index>``, the
per-request ones ``rid=``; a span takes its attributes when it opens, so
counts known only at its end stay on the ``StepRecord`` and join by
``step``):

======================  ====================================  ==============
span                    extent                                attributes
======================  ====================================  ==============
``serve.step``          all of ``ServingEngine.step()``       step
``serve.admit``         deadline sweep, ``scheduler.admit``,  queue_depth
                        restore failures
``serve.prefill``       one per request prefilled whole       rid, bucket,
                                                              cached, tail
``serve.chunk_prefill``  the chunk loop                       chunks
``serve.prefill.upload``    inside either, one a launch of    rid, bytes
                            the one prefill path: the padded
                            ids and the five operands from
                            the host
``serve.prefill.dispatch``  the call of the jitted program    rid
``serve.prefill.fetch``     the first-token fetch of a        rid
                            launch that completed a prompt:
                            inside ``serve.decode``, behind
                            the step's launch (or inside
                            ``serve.drain``); inside the
                            prefill's own span where the
                            engine fetches at once (spec,
                            ``debug_checks``); blocks
``serve.evict``         fault sites, decode-page pressure,
                        preemption
``serve.decode``        the decode phase                      batch
``serve.decode.upload``     the six device operands           bytes
``serve.decode.dispatch``   the call of the jitted program
``serve.decode.fetch``      the token fetch of the PREVIOUS   of_step
                            step's launch (blocks)
``serve.decode.emit``       the per-slot loop, retirements
``serve.drain``         an early fetch + emit of what is in   reason
                        flight (first tokens, the decode)
``serve.verify``        the speculative verify phase          batch
``serve.verify.dispatch``   the call of the jitted program
``serve.verify.fetch``      the packed fetch of that same
                            launch (blocks)
``serve.account``       cache stats, gauges, the step record,
                        watchdogs, the SLO controller
``serve.add_request``   ``ServingEngine.add_request``         rid, prompt_len
``serve.cow_copy``      one copy-on-write page copy           pages
``serve.window_release``  window-group pages freed behind
                        their slots' windows (host only)
``serve.stall``         the sampled part of a blocking span   span
                        that outlasted its norm, on the
                        SAMPLER's thread (obs/stall.py)
======================  ====================================  ==============

A span whose name ends in ``.fetch``, ``.upload`` or ``.dispatch`` is a
BLOCKING span: it also reports its start (with ``awaited``, the device
value it waits for) and its end to the accumulator's stall watch
(obs/stall.py), which flags one that outlasts its name's norm
and has a sampler thread read what held it.

The seconds come in two kinds. A span named after one of :data:`PHASES`
is a **phase** of the top-level split: the interval since the previous
mark is charged to it when it closes, so the phases plus the residual
``"other"`` SUM EXACTLY to the step's wall time by construction — no
sampling, no double counting (``StepRecord.phase_s``). Every other span
(the dotted ``*.upload`` / ``*.dispatch`` / ``*.fetch`` / ``*.emit``,
``cow_copy``, ``window_release``, ``account``) measures its own extent, lies inside a phase
(not always the phase of its name: ``prefill.fetch`` lies in ``decode``)
and is no part of that sum (``StepRecord.span_s``).

ZERO device syncs either way (clock reads and TraceMe events only — the
SyncTally decode-loop certification is byte-identical with attribution
on). Whether tracing is on is decided HERE, once: the engine (and the
cache) always hold an accumulator and every site reads ``with
att.span(...):``. A disabled one (``PhaseAccumulator()``, what
``enable_tracing=False`` builds) hands back the shared :data:`NO_SPAN`
and does nothing else: no span object, no clock read, no record.

Imports nothing from ``paddle_tpu.serving`` (serving imports us) and
touches no device state.
"""
from __future__ import annotations

import contextlib

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .stall import BLOCKING

__all__ = ["PHASES", "SPANS", "SPAN_PREFIX", "NO_SPAN", "PhaseAccumulator"]

#: the phase vocabulary — the pre-seeded label set of the
#: ``serving_step_phase_s{phase=}`` histogram family. "admit" covers the
#: deadline sweep + scheduler admission (including host-tier restores),
#: "swap" the swap-resume re-entry, "evict" injected/real preemption and
#: decode-page eviction pressure, "other" the residual step bookkeeping.
PHASES = ("admit", "swap", "prefill", "chunk_prefill", "decode", "verify",
          "evict", "other")

#: the spans that are no phase (``StepRecord.span_s``): the pre-seeded
#: label set of ``serving_step_span_seconds_total{span=}``; one the engine
#: opens beside these joins the family at its first close
SPANS = ("prefill.upload", "prefill.dispatch", "prefill.fetch",
         "decode.upload", "decode.dispatch", "decode.fetch", "decode.emit",
         "verify.dispatch", "verify.fetch", "drain", "account",
         "window_release", "cow_copy")

#: what every span's name starts with in the profiler's trace
SPAN_PREFIX = "serve."

#: what a disabled accumulator's ``span()`` hands back: one shared
#: do-nothing context, so no span object is made
NO_SPAN = contextlib.nullcontext()


class _Span:
    """One open span: the TraceMe event, and where its seconds go."""

    __slots__ = ("_acc", "_name", "_phase", "_ann", "_t0", "_watch",
                 "_awaited")

    def __init__(self, acc, name: str, attrs: dict, awaited):
        self._acc = acc
        self._name = name
        self._phase = name in PHASES
        self._ann = TraceAnnotation(SPAN_PREFIX + name, **attrs)
        self._t0 = None
        # a blocking span reports to the stall watch (obs/stall.py): the
        # sampler thread sees what the engine's thread waits in, for what
        self._watch = acc.stalls if name.endswith(BLOCKING) else None
        self._awaited = awaited

    def __enter__(self):
        self._ann.__enter__()
        # a span that is no phase reads the clock for its own extent, and
        # only while a step's record is open to take it
        acc = self._acc
        if acc.open and not self._phase:
            t0 = self._t0 = acc._clock()
            if self._watch is not None:
                self._watch.enter(self._name, t0, acc.step, self._awaited)
        return self

    def __exit__(self, *exc):
        acc = self._acc
        if self._phase:
            acc.mark(self._name)
        elif self._t0 is not None:
            dt = acc._clock() - self._t0
            if acc.open:
                acc._spans[self._name] = \
                    acc._spans.get(self._name, 0.0) + dt
            if self._watch is not None:  # a fatal step's span too
                self._watch.exit(self._name, self._t0, dt, acc.step)
        self._ann.__exit__(*exc)
        return False


class PhaseAccumulator:
    """Wall-time splitter and span source for one engine step at a time.

    The seconds: ``begin(t)`` opens a step's record; each ``mark(phase)``
    charges the interval since the previous mark (or begin) to ``phase``
    and returns it; ``finish()`` charges the remainder to ``"other"`` and
    returns ``(t_end, {phase: seconds})``. Exactness contract: the
    returned phase dict's values are precisely the consecutive clock
    deltas, so on any clock they sum to ``t_end - t_begin`` up to float
    addition — and EXACTLY on the integer-valued virtual clocks the tests
    use.

    The spans: ``enter_step(step)`` opens ``serve.step`` in the
    profiler's trace and ``exit_step()`` closes it (and ``serve.account``
    with it); in between ``span(name, **attrs)`` is a context manager
    that opens ``serve.<name>`` there and, when it closes, writes the
    seconds: a name from :data:`PHASES` is a ``mark`` of that phase, any
    other name adds its own extent to ``span_s``. ``account()`` opens
    ``serve.account``, which outlives the record: what of it lies before
    ``finish()`` is in ``span_s["account"]``, the rest is in the
    profiler's trace only.

    Without a clock the accumulator is disabled (``enabled`` False):
    ``span()`` is :data:`NO_SPAN`, every other method returns at once, a
    record never opens.
    """

    __slots__ = ("_clock", "enabled", "open", "t0", "_last", "_acc",
                 "_spans", "step", "_step_ann", "_account", "stalls")

    def __init__(self, clock=None, stalls=None):
        self._clock = clock
        self.enabled = clock is not None
        #: the stall watch (obs/stall.py) that the blocking spans report
        #: to, or None
        self.stalls = stalls if self.enabled else None
        self.open = False
        self.t0 = 0.0
        self._last = 0.0
        self._acc: dict[str, float] = {}
        self._spans: dict[str, float] = {}
        self.step = 0
        self._step_ann = None
        self._account = None

    # -------------------------------------------------------------- spans
    def enter_step(self, step: int) -> None:
        """Open ``serve.step`` (a step event: the profiler groups what the
        device ran by it). No clock read: the record opens at ``begin``."""
        if not self.enabled:
            return
        self.step = step
        self._step_ann = StepTraceAnnotation(
            SPAN_PREFIX + "step", step_num=step, step=step)
        self._step_ann.__enter__()

    def exit_step(self) -> None:
        """Close ``serve.account`` if it is open, then ``serve.step``."""
        if self._account is not None:
            self._account.__exit__(None, None, None)
            self._account = None
        if self._step_ann is not None:
            self._step_ann.__exit__(None, None, None)
            self._step_ann = None

    def span(self, name: str, awaited=None, **attrs):
        """``with att.span("decode.fetch"):`` — see the class docstring.
        ``step`` is the open step's unless given. ``awaited``: the device
        value a blocking span waits for, for the stall watch."""
        if not self.enabled:
            return NO_SPAN
        attrs.setdefault("step", self.step)
        return _Span(self, name, attrs, awaited)

    def account(self) -> None:
        """Open ``serve.account``; ``exit_step`` closes it."""
        if self.enabled:
            self._account = self.span("account").__enter__()

    @property
    def span_s(self) -> dict:
        """{span: seconds} of the spans that are no phase, this step."""
        return self._spans

    # ------------------------------------------------------------ seconds
    def begin(self, t: float | None = None) -> float:
        if not self.enabled:
            return 0.0
        t = self._clock() if t is None else t
        self.open = True
        self.t0 = self._last = t
        self._acc = {}
        self._spans = {}
        return t

    def mark(self, phase: str, t: float | None = None) -> float:
        """Charge now - last_mark to ``phase``; returns the interval."""
        if not self.enabled:
            return 0.0
        t = self._clock() if t is None else t
        dt = t - self._last
        if dt:
            self._acc[phase] = self._acc.get(phase, 0.0) + dt
        self._last = t
        return dt

    def finish(self, t: float | None = None) -> tuple[float, dict]:
        """Close the step's record: residual time goes to ``"other"``;
        returns ``(t_end, phases)``."""
        if not self.enabled:
            return 0.0, {}
        t = self._clock() if t is None else t
        self.mark("other", t)
        if self._account is not None and self._account._t0 is not None:
            self._spans["account"] = t - self._account._t0
        self.open = False
        return t, self._acc
