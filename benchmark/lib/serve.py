"""The ``serve`` driver: the ``ServingEngine`` that the configuration's
family builds over the benchmark's weights, driven through ``add_request``
and ``step()`` by an open or a closed loop from one thread. The benchmark
times every request itself, from when it was due."""
from __future__ import annotations

import time
from collections import Counter

import numpy as np

from . import check, reference, traffic, weights
from .common import Run, TraceWindow, family_of, peak_bytes, release, say
from .reduce import percentile

TERMINAL = ("finished", "cancelled", "expired", "failed", "shed")
#: how long past the close the driver waits for what was due in the window
DRAIN_S = 60.0
#: requests to a block of the reference
REFERENCE_ROWS = 8
#: a step this long by the host's clock is a stall: the driver then keeps
#: where the engine's own spans say the time went
STALL_S = 0.1


#: the engine's counters that the driver itself checks
CHECKED_COUNTERS = ("serving_prefix_tokens_saved",
                    "serving_preemptions_total")
DRAINS = "serving_decode_drains_total{"


def pool_pages(sv: dict, page_bytes: int) -> int:
    """The pages of a configuration's ``serve`` group: their number, or
    those that fit its share of the device's limit at ``page_bytes`` a
    page."""
    import jax

    if "num_pages" in sv:
        return sv["num_pages"]
    limit = int(jax.devices()[0].memory_stats()["bytes_limit"])
    return int(limit * sv["pool_share_of_device_limit"]) // page_bytes


def build(run: Run):
    import jax

    family = family_of(run.config)
    t0 = time.perf_counter()
    engine = family.build_serving(
        run, weights.for_program(family, run.config, run.seed))
    say("serve.built", pool_pages=engine.config.num_pages,
        pool_bytes=sum(int(a.nbytes) for a in
                       jax.tree_util.tree_leaves(engine.cache.pools)),
        seconds=round(time.perf_counter() - t0, 2),
        since_process_start=round(time.time() - run.t_process, 2))
    return engine


class Loop:
    """Adds requests, steps the engine, and stamps what the caller sees:
    when each request was admitted and when each of its tokens came back
    from ``step()``."""

    def __init__(self, engine):
        self.engine = engine
        self.live = {}       # rid -> record
        self.records = []    # every request ever added
        self.steps = []      # one record per engine.step()
        self.refused = 0

    def add(self, req: traffic.ServeRequest, due: float, now) -> dict | None:
        rec = {"req": req, "due": due, "added": None, "admitted": None,
               "token_times": [], "state": "refused", "cached": None}
        self.records.append(rec)
        try:
            rid = self.engine.add_request(req.prompt, req.output_tokens)
        except Exception as e:  # refused: counts as failed
            rec["error"] = repr(e)
            self.refused += 1
            return None
        rec.update(rid=rid, added=now(), state="waiting",
                   obj=self.engine.request(rid))
        self.live[rid] = rec
        return rec

    def step(self, now) -> None:
        t0 = now()
        waiting = [r for r in self.live.values() if r["admitted"] is None]
        decoding = [(r, len(r["obj"].generated)) for r in self.live.values()
                    if r["admitted"] is not None]
        cpu0 = time.thread_time()
        self.engine.step()
        t1 = now()
        st = {"t0": t0, "t1": t1, "prefills": [], "decode_tokens": 0,
              "decode_ctx_tokens": 0}
        if t1 - t0 > STALL_S:
            # this thread's CPU seconds tell a host that computed (a
            # collection, a retrace) from one that waited (for the device,
            # a transfer, or its turn on a shared core)
            last = getattr(self.engine.timeline, "last", None)
            st["stall"] = {
                "at_s": round(t0, 2), "ms": round(1e3 * (t1 - t0), 1),
                "thread_cpu_ms": round(1e3 * (time.thread_time() - cpu0), 1),
                "spans_ms": {k: round(1e3 * v, 1) for k, v in
                             {**last.phase_s, **last.span_s}.items()
                             if v > 0.001} if last else None}
        for r in waiting:
            o = r["obj"]
            if o.state != "waiting":
                r["admitted"], r["cached"] = t0, int(o.cached_tokens)
                if o.generated:
                    st["prefills"].append(
                        (r["cached"], o.prompt_len - r["cached"]))
                    decoding.append((r, 1))
        for r, before in decoding:
            o = r["obj"]
            if len(o.generated) > before:
                # the decode step attended the prompt and what was
                # generated before it
                st["decode_tokens"] += 1
                st["decode_ctx_tokens"] += o.prompt_len + before
        for rid in list(self.live):
            r = self.live[rid]
            o = r["obj"]
            seen = len(r["token_times"])
            if len(o.generated) > seen:
                r["token_times"].extend([t1] * (len(o.generated) - seen))
            if o.state in TERMINAL:
                r["state"] = o.state
                r["tokens"] = np.asarray(o.generated, np.int32)
                del self.live[rid], r["obj"]
        self.steps.append(st)
        self.engine.pop_finished()
        self.engine.pop_retired()

    @property
    def busy(self) -> bool:
        return bool(self.live)


def warm_up(engine, run: Run) -> None:
    """Send the mix's warm-up requests one after another, so that each
    program the mix lists is compiled and run, and no other."""
    loop = Loop(engine)
    clock = time.perf_counter
    for req in traffic.warmup_requests(run.mix, run.config["model"]
                                       ["vocab_size"], run.seed):
        loop.add(req, 0.0, clock)
        while loop.busy:
            loop.step(clock)
    states = Counter(r["state"] for r in loop.records)
    if set(states) != {"finished"}:
        raise SystemExit(f"warm-up did not finish: {dict(states)}")
    used = sorted({_bucket(engine, p[1]) for s in loop.steps
                   for p in s["prefills"]})
    programs = [f"prefill[{b}]" for b in used] + ["decode"]
    say("serve.warmed", programs=programs,
        compile_counts=dict(engine.compile_counts))
    if sorted(programs) != sorted(run.mix["programs"]):
        raise SystemExit(f"warm-up ran {programs}, the mix lists "
                         f"{run.mix['programs']}")


def _bucket(engine, tail: int) -> int:
    return next(b for b in engine.prefill_buckets if b >= tail)


def counters(engine, names) -> dict:
    """The engine's counters now: those the driver checks, every one of
    ``names`` that the engine has (``run.counters``: what the cell's metric
    files read), and the drains by reason."""
    snap = engine.metrics.snapshot()
    out = {k: float(snap.get(k, 0)) for k in CHECKED_COUNTERS}
    out.update({k: float(v) for k, v in snap.items()
                if k in names or k.startswith(DRAINS)})
    return out


def drive(run: Run) -> dict:
    engine = build(run)
    t0 = time.perf_counter()
    warm_up(engine, run)
    say("serve.warm_up", seconds=round(time.perf_counter() - t0, 2))
    done = measure(run, engine)
    sample = pick_sample(done, run.seed, run.check["compared_requests"])
    # the reference follows once the engine and its pool are gone
    del engine, done
    release()
    return compare(run, sample)


def measure(run: Run, engine) -> list:
    """The window, and the wait past its close for what was due in it.
    Fills ``run.facts`` and returns the records of the finished
    requests."""
    import jax

    mix, vocab = run.mix, run.config["model"]["vocab_size"]
    requests = traffic.serve_requests(mix, vocab, run.seed, run.seconds)
    compiles0 = dict(engine.compile_counts)
    count0 = counters(engine, run.counters)
    loop, tw = Loop(engine), TraceWindow(run)
    open_loop = mix["kind"] == "open_loop"
    nxt = 0
    run.facts["setup_s"] = time.time() - run.t_process
    w0 = time.perf_counter()
    now = lambda: time.perf_counter() - w0  # noqa: E731

    def offer() -> None:
        nonlocal nxt
        if open_loop:
            with jax.profiler.TraceAnnotation("bench.add_request"):
                while nxt < len(requests) and requests[nxt].due_s <= now():
                    loop.add(requests[nxt], requests[nxt].due_s, now)
                    nxt += 1
        else:
            with jax.profiler.TraceAnnotation("bench.add_request"):
                while len(loop.live) < mix["clients"]:
                    if nxt == len(requests):    # the pool's next cycle
                        requests.extend(traffic.serve_requests(
                            mix, vocab, run.seed, run.seconds,
                            cycle=nxt // mix["pool"]))
                    loop.add(requests[nxt], now(), now)
                    nxt += 1

    backlog_mid = None
    while now() < run.seconds:
        if backlog_mid is None and now() >= run.seconds / 2:
            backlog_mid = len(loop.live)
        if tw.due(now()):
            tw.open(now())
        offer()
        if loop.busy:
            with jax.profiler.TraceAnnotation("bench.step"):
                loop.step(now)
        else:
            with jax.profiler.TraceAnnotation("bench.wait_due"):
                wait = requests[nxt].due_s - now() if nxt < len(requests) \
                    else run.seconds - now()
                time.sleep(max(0.0, min(wait, run.seconds - now(), 0.05)))
    window_s = now()
    tw.close()
    in_window_steps = len(loop.steps)
    count1 = counters(engine, run.counters)
    backlog = len(loop.live)

    # ---- past the close: nothing new is offered; what was due is awaited
    if not open_loop:
        for rid, r in list(loop.live.items()):
            if r["admitted"] is None:       # a client's next call, unserved
                engine.cancel(rid)
                r["state"] = "cut_at_close"
                del loop.live[rid]
    while loop.busy and now() < window_s + DRAIN_S:
        loop.step(now)
    drain_s = now() - window_s
    compiles1 = dict(engine.compile_counts)
    memory = peak_bytes()

    # ---- what the window saw
    recs = [r for r in loop.records if r["state"] != "cut_at_close"]
    done = [r for r in recs if r["state"] == "finished"]
    failed = len(recs) - len(done)
    late_end = now()
    gaps, out_tokens = [], 0
    for r in loop.records:
        tt = [t for t in r["token_times"] if t <= window_s]
        out_tokens += len(tt)
        gaps.extend(1e3 * (b - a) for a, b in zip(tt, tt[1:]))
    cached = Counter(r["cached"] for r in recs if r["cached"] is not None)
    counted = {k: v - count0.get(k, 0.0) for k, v in count1.items()}
    saved = counted["serving_prefix_tokens_saved"]
    preempted = counted["serving_preemptions_total"]
    say("serve.window", window_s=window_s, drain_s=drain_s,
        requests=len(recs), finished=len(done), failed=failed,
        refused=loop.refused, backlog_at_close=backlog,
        backlog_at_middle=backlog_mid,
        steps=in_window_steps, out_tokens=out_tokens,
        cached_tokens_per_request=dict(cached),
        prefix_tokens_saved=saved, preemptions=preempted,
        counters={k: v for k, v in counted.items()
                  if k not in CHECKED_COUNTERS},
        compile_counts_before=compiles0, compile_counts_after=compiles1,
        compiles_in_window=sum(compiles1.values()) - sum(compiles0.values()))
    # where the window's time went by the host's clock: a stall of the
    # host shows as a few long steps, a slower machine as a longer median
    took = np.array([1e3 * (s["t1"] - s["t0"])
                     for s in loop.steps[:in_window_steps]])
    plain = np.array([not s["prefills"]
                      for s in loop.steps[:in_window_steps]])
    if plain.any() and not plain.all():
        mid, mid_p = np.median(took[plain]), np.median(took[~plain])
        over = np.where(plain, took - 2 * mid, took - 2 * mid_p)
        say("serve.steps", decode_steps=int(plain.sum()),
            prefill_steps=int((~plain).sum()),
            decode_step_ms_median=float(mid),
            prefill_step_ms_median=float(mid_p),
            between_steps_ms_total=float(1e3 * window_s - took.sum()),
            longest_steps_ms=[round(float(x), 2)
                              for x in np.sort(took)[::-1][:6]],
            stalls=sorted((s["stall"] for s in loop.steps[:in_window_steps]
                           if "stall" in s), key=lambda x: -x["ms"])[:4],
            stalled_ms_total=float(over[over > 0].sum()))
    if compiles1 != compiles0:
        raise SystemExit("a program was compiled inside the window: "
                         f"{compiles0} -> {compiles1}")
    if mix.get("expect_no_prefix_hits") and saved:
        raise SystemExit(f"the prefix cache served {saved} tokens of a mix "
                         "that shares nothing")
    e2e = {"itl_p95_ms": percentile(gaps, 95),
           "serve_out_tokens_per_s": out_tokens / window_s}
    # starting the profiler stalls the host: the driver's own clock is
    # read only for what was due before it
    calm = [r for r in recs
            if tw.opened_s is None or r["due"] < tw.opened_s]
    spans = {"queue_wait_ms": [1e3 * (r["admitted"] - r["due"])
                               for r in calm if r["admitted"] is not None],
             "generator_late_ms": [1e3 * (r["added"] - r["due"])
                                   for r in calm if r["added"] is not None],
             "ttft_ms": [1e3 * ((r["token_times"][0] if r["token_times"]
                                 else late_end) - r["due"]) for r in calm]}
    prompt_tokens = sum(len(r["req"].prompt) for r in calm)
    saved_calm = sum(r["cached"] or 0 for r in calm)
    run.facts.update(
        memory_peak_bytes=memory, window_s=window_s, attempted=len(recs),
        failed=failed, end_to_end=e2e, spans=spans, trace=tw,
        # the driver's own two, of the calm requests; the engine's, over
        # the window
        counters=dict({k: v for k, v in counted.items()
                       if not k.startswith(DRAINS)},
                      prefix_tokens_saved=saved_calm,
                      prompt_tokens_offered=prompt_tokens),
        traced=traced_facts(loop.steps, tw, w0))
    return done


def traced_facts(steps: list, tw: TraceWindow, w0: float) -> dict:
    """What the traffic did inside the profiler's window, for ``work``."""
    if tw.opened is None:
        return {}
    lo, hi = tw.opened - w0, tw.closed - w0
    out = Counter()
    for s in steps:
        if s["t0"] < lo or s["t1"] > hi:
            continue
        out["steps"] += 1
        if s["decode_tokens"]:
            out["decode_steps"] += 1
            out["decode_tokens"] += s["decode_tokens"]
            out["decode_ctx_tokens"] += s["decode_ctx_tokens"]
        for cached_n, tail in s["prefills"]:
            out["prefills"] += 1
            out["prefill_tokens"] += tail
            out["prefill_kv_tokens"] += cached_n + tail
            out["prefill_ctx_tokens"] += tail * cached_n \
                + tail * (tail + 1) // 2
    return dict(out)


def pick_sample(done: list, seed: int, n: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among
    them, and where the mix shares prefixes at least one hit and one
    miss."""
    if not done:
        raise SystemExit("no request finished in the window")
    rng = traffic.rng_for(seed, 9)
    order = [done[i] for i in rng.permutation(len(done))]
    longest = max(done, key=lambda r: len(r["req"].prompt) + len(r["tokens"]))
    picked = [longest]
    for want_hit in (True, False):
        for r in order:
            if bool(r["cached"]) == want_hit and r not in picked:
                picked.append(r)
                break
    for r in order:
        if len(picked) >= n:
            break
        if r not in picked:
            picked.append(r)
    return picked


def compare(run: Run, sample: list) -> dict:
    """The reference's float32 logits over each sampled prompt with its
    served tokens, ``REFERENCE_ROWS`` requests to a block: how far every
    served token lies below the reference's best."""
    import jax.numpy as jnp

    family, m, rows = family_of(run.config), run.config["model"], \
        REFERENCE_ROWS
    t0 = time.perf_counter()
    leaves_of = weights.for_reference(family, run.config, run.seed)
    # the longest a request can be: its prompt and all its output
    n_out = run.mix["output_tokens"]["max"]
    length = run.config["serve"]["max_prompt_len"] + n_out
    low = run.config["precision"]["controls"][0]

    def gaps_fn(ids, pos, tok):
        """(the served tokens' gaps, the control's): the control is the
        token that the lower precision puts first at each position of the
        same prompts and served tokens, read under the same logits."""
        logits = family.logits_at(leaves_of, ids, pos, m)
        if not run.control:
            return reference.below_best(logits, tok), None
        return reference.below_best(logits, tok), reference.below_best(
            logits, reference.first_tokens(family.logits_at, leaves_of, ids,
                                           pos, m, low))

    gaps, control = [], []
    for at in range(0, len(sample), rows):
        block = sample[at:at + rows]
        ids = np.zeros((rows, length), np.int32)
        pos = np.zeros((rows, n_out), np.int32)
        tok = np.zeros((rows, n_out), np.int32)
        for i, r in enumerate(block):
            prompt, served = r["req"].prompt, r["tokens"]
            seq = np.concatenate([prompt, served])[:-1]
            ids[i, :len(seq)] = seq
            pos[i] = np.minimum(len(prompt) - 1 + np.arange(n_out),
                                len(seq) - 1)
            tok[i, :len(served)] = served
        g, c = gaps_fn(jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(tok))
        g, c = np.asarray(g), np.asarray(c) if run.control else None
        for i, r in enumerate(block):
            gaps.append(g[i, :len(r["tokens"])])
            if run.control:
                control.append(c[i, :len(r["tokens"])])
    gaps = np.concatenate(gaps)
    say("serve.reference", seconds=round(time.perf_counter() - t0, 2),
        requests=len(sample), hits=sum(bool(r["cached"]) for r in sample),
        **check.gap_stats(gaps))
    if run.control:
        # the control, judged as a run is: it has to come out not correct
        control = np.concatenate(control)
        ok, judged = check.judge(check.gap_numbers(control),
                                 run.check["limits"])
        say("serve.control_" + low, correct=ok, compared=judged,
            **check.gap_stats(control))
    return check.gap_numbers(gaps)
