"""Decode k+1 is launched before decode k is fetched (serving/engine.py,
"The order of a step").

- **Same tokens**: every request's output is token for token that of the
  same engine drained after every step (which is the engine that fetched
  every step), over finish by length and by EOS, admission into a slot
  just freed, cancel of a running request, preemption (recompute and
  swap), chunked prefill, int8 KV, tensor parallelism and sampling.
- **Order**: in the profiler's trace ``serve.decode.dispatch`` of step k
  opens and closes before ``serve.decode.fetch`` with ``of_step=k-1``
  opens; a step with nothing to launch only fetches.
- **Counters**: ``serving_decode_overlapped_total`` over
  ``serving_decode_steps``, one ``serving_decode_drains_total{reason=}``
  per site that needs the host's view whole, none in a plain run.
- **One program**: ``compile_counts`` stays ``{prefill: 1, decode: 1}``
  and the decode jit holds one executable whether a launch overrides all,
  some or no slots.
- A speculative engine never has a decode in flight; a finish by EOS
  leaves no surplus token in ``result()``, in ``tokens_emitted``, in
  ``serving_tokens_total`` or in the prefix index.
"""
import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.engine import DRAIN_REASONS
from paddle_tpu.serving.faults import FaultInjector
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

VOCAB = 97
#: (prompt length, max_new_tokens): more requests than slots, one that
#: finishes at its prefill, a short and a long one side by side
MIX = ((5, 6), (9, 4), (3, 9), (7, 1), (12, 7), (4, 5))


@pytest.fixture(scope="module")
def model():
    paddle.seed(31)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=48, dropout=0.0))
    m.eval()
    return m


def _engine(model, **overrides):
    kw = dict(max_batch=3, num_pages=40, page_size=4, max_prompt_len=16)
    kw.update(overrides)
    inj = kw.pop("fault_injector", None)
    clock = kw.pop("clock", None)
    return ServingEngine(model, ServingConfig(**kw), fault_injector=inj,
                         clock=clock)


def _prompts(mix=MIX, seed=5):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, VOCAB, (n,)).astype(np.int32), m)
            for n, m in mix]


def _counts(engine) -> dict:
    snap = engine.metrics.snapshot()
    out = {k: snap[f"serving_{k}"] for k in (
        "decode_steps", "decode_overlapped_total", "tokens_total")}
    out["drains"] = {r: snap[f"serving_decode_drains_total{{reason={r}}}"]
                     for r in DRAIN_REASONS}
    return out


def _delta(after: dict, before: dict) -> dict:
    out = {k: after[k] - before[k] for k in after if k != "drains"}
    out["drains"] = {r: n - before["drains"][r]
                     for r, n in after["drains"].items()
                     if n != before["drains"][r]}
    return out


def _serve(engine, prompts, drained: bool, rid0: int, cancel_at=None,
           max_steps=400):
    """Add every request under a fixed id, step to the end, and return
    {rid: (state, generated tokens)}. ``drained`` fetches what is in
    flight after every step: the engine that never overlaps.
    ``cancel_at`` = (step, index of the request to cancel then)."""
    rids = [engine.add_request(p, m, rid=rid0 + i)
            for i, (p, m) in enumerate(prompts)]
    reqs = {r: engine.request(r) for r in rids}
    seen = []
    for step in range(max_steps):
        if engine.scheduler.all_done:
            break
        if cancel_at is not None and step == cancel_at[0]:
            engine.cancel(rids[cancel_at[1]])
        seen += engine.step()
        if drained:
            engine._drain("run_end")
    engine._drain("run_end")
    seen += engine._take_drained()
    assert engine.scheduler.all_done
    engine.cache.check_invariants()
    assert engine.cache.allocator.pages_in_use == 0
    out = {r - rid0: (q.state, list(q.generated)) for r, q in reqs.items()}
    finished = sorted(i for i, (state, _) in out.items()
                      if state == "finished")
    assert sorted(r - rid0 for r in seen) == finished  # each id, once
    for r in rids:
        if reqs[r].state == "finished":
            assert engine.result(r).tolist() == \
                reqs[r].prompt.tolist() + reqs[r].generated
    return out


def _eos_token(model) -> int:
    """A token the toy model emits in the middle of an output."""
    engine = _engine(model)
    out = _serve(engine, _prompts(), drained=True, rid0=9000)
    mid = [t for _, toks in out.values() for t in toks[1:-1]]
    assert mid
    return max(set(mid), key=mid.count)


# ------------------------------------------------------------- same tokens
CASES = {
    "length_and_freed_slots": {},
    "eos": {"eos": True},
    "cancel_running": {"cancel_at": (3, 0)},
    "preempt_recompute": {"num_pages": 9},
    "preempt_swap": {"num_pages": 9, "preemption_mode": "swap"},
    "chunked_prefill": {"chunk_size": 4},
    "int8_kv": {"kv_dtype": "int8"},
    "tensor_parallel": {"tensor_parallel": 2},
    "sampling": {"do_sample": True, "temperature": 0.9, "top_k": 20,
                 "seed": 7},
    "sampling_eos_preempt": {"do_sample": True, "seed": 3, "eos": True,
                             "num_pages": 9},
}


@pytest.mark.parametrize("case", CASES)
def test_same_tokens_as_the_engine_drained_every_step(model, case):
    kw = dict(CASES[case])
    cancel_at = kw.pop("cancel_at", None)
    if kw.pop("eos", False):
        kw["eos_token_id"] = _eos_token(model)
    if kw.get("tensor_parallel", 1) > len(jax.devices()):
        pytest.skip("needs two devices")
    prompts = _prompts()
    rid0 = 10000 + 100 * list(CASES).index(case)
    plain = _engine(model, **kw)
    before = _counts(plain)
    got = _serve(plain, prompts, drained=False, rid0=rid0,
                 cancel_at=cancel_at)
    moved = _delta(_counts(plain), before)
    want = _serve(_engine(model, **kw), prompts, drained=True, rid0=rid0,
                  cancel_at=cancel_at)
    assert got == want
    assert plain.compile_counts["decode"] == 1
    # the mechanism ran: launches were made with a decode in flight
    assert moved["decode_overlapped_total"] >= 3
    if "eos_token_id" in kw:
        assert any(toks[-1] == kw["eos_token_id"] and len(toks) < m
                   for (_, toks), (_, m) in zip(got.values(), prompts))
    if "num_pages" in kw:
        # a drain comes before a victim is picked (and may free the pages
        # itself: a request finishes with the token that was in flight)
        assert moved["drains"].get("preempt", 0) >= 1
        assert plain.scheduler.preemption_count >= ("eos_token_id" not in kw)
    if cancel_at is not None:
        assert got[cancel_at[1]][0] == "cancelled"
        assert moved["drains"] == {"cancel": 1}


# ------------------------------------------------------------------- order
def _traced_spans(tmp, fn):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append((ev.name[len("serve."):], ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return spans


def test_dispatch_of_step_k_precedes_the_fetch_of_step_k_minus_1(
        model, tmp_path):
    engine = _engine(model)
    engine.add_request(_prompts()[0][0], 3)
    engine.run()  # compiled and idle again

    def run():
        engine.add_request(_prompts()[2][0], 6)
        engine.run()

    spans = _traced_spans(tmp_path, run)
    by_step = {}
    for name, start, end, stats in spans:
        if name.startswith("decode."):
            by_step.setdefault(stats["step"], {})[name] = (start, end, stats)
    steps = sorted(by_step)
    assert len(steps) == 6  # five launches and the last fetch
    first, last = by_step[steps[0]], by_step[steps[-1]]
    # from idle: a launch and nothing to fetch; at the end: only a fetch
    assert set(first) == {"decode.upload", "decode.dispatch"}
    assert set(last) == {"decode.fetch", "decode.emit"}
    for k in steps[1:-1]:
        parts = by_step[k]
        assert set(parts) == {"decode.upload", "decode.dispatch",
                              "decode.fetch", "decode.emit"}
        assert parts["decode.upload"][1] <= parts["decode.dispatch"][0]
        assert parts["decode.dispatch"][1] <= parts["decode.fetch"][0]
        assert parts["decode.fetch"][1] <= parts["decode.emit"][0]
    for k in steps[1:]:
        assert by_step[k]["decode.fetch"][2]["of_step"] == k - 1
    assert not [s for s in spans if s[0] == "drain"]
    # the record of a step has the fetch's seconds of the previous launch
    rec = {r.step: r for r in engine.timeline.records()}
    assert "decode.fetch" not in rec[steps[0]].span_s
    assert "decode.dispatch" not in rec[steps[-1]].span_s
    assert rec[steps[-1]].batch == 0 and rec[steps[1]].batch == 1


def test_a_token_is_handed_over_one_step_after_its_launch(model):
    engine = _engine(model)
    rid = engine.add_request(_prompts()[0][0], 4)
    req = engine.request(rid)
    seen = []
    for _ in range(5):
        finished = engine.step()
        seen.append((len(req.generated), req.tokens_in_flight, finished))
    # prefill hands over its token at once; decode k's token comes with
    # step k+1; the last launch is not made (finish by length)
    assert seen == [(1, 1, []), (2, 1, []), (3, 1, []), (4, 0, [rid]),
                    (4, 0, [])]
    assert engine._inflight is None


# ---------------------------------------------------------------- counters
def test_plain_run_overlaps_every_launch_but_the_first_and_never_drains(
        model):
    engine = _engine(model, max_batch=2)
    before = _counts(engine)
    for p, _ in _prompts()[:2]:
        engine.add_request(p, 8)
    engine.run()
    moved = _delta(_counts(engine), before)
    assert moved["decode_steps"] == 7
    assert moved["decode_overlapped_total"] == 6
    assert moved["drains"] == {}
    assert moved["tokens_total"] == 16


def _drain_by_preempt(model):
    engine = _engine(model, num_pages=9)
    _serve(engine, _prompts(), drained=False, rid0=20000)
    return engine


def _drain_by_cancel(model):
    engine = _engine(model)
    rid = engine.add_request(_prompts()[0][0], 8)
    for _ in range(3):
        engine.step()
    held = len(engine.request(rid).generated)
    assert engine.cancel(rid)
    # it left with the token that was in flight
    assert len(engine.request(rid).generated) == held + 1
    return engine


def _drain_by_deadline(model):
    t = [0.0]
    engine = _engine(model, clock=lambda: t[0])
    rid = engine.add_request(_prompts()[0][0], 8, deadline_s=5.0)
    for _ in range(3):
        engine.step()
    held = len(engine.request(rid).generated)
    t[0] = 6.0
    engine.step()
    assert engine.status(rid) == "expired"
    assert len(engine.request(rid).generated) == held + 1
    return engine


def _drain_by_fault(model):
    inj = FaultInjector().arm("decode_fail", step=3)
    engine = _engine(model, fault_injector=inj)
    rid = engine.add_request(_prompts()[0][0], 8)
    for _ in range(3):
        engine.step()
    held = len(engine.request(rid).generated)
    engine.step()
    assert engine.status(rid) == "failed"
    assert len(engine.request(rid).generated) == held + 1
    return engine


def _drain_by_debug_checks(model):
    engine = _engine(model, debug_checks=True)
    engine.add_request(_prompts()[0][0], 4)
    engine.step()
    assert engine._inflight is None  # never across a step boundary
    engine.run()
    return engine


def _drain_by_flight_record(model):
    engine = _engine(model)
    rid = engine.add_request(_prompts()[0][0], 8)
    for _ in range(3):
        engine.step()
    held = len(engine.request(rid).generated)
    rec = engine.flight_record()
    assert len(engine.request(rid).generated) == held + 1
    assert rec["steps"]
    engine.run()
    return engine


def _drain_by_fatal(model):
    engine = _engine(model)
    rid = engine.add_request(_prompts()[0][0], 8)
    for _ in range(3):
        engine.step()
    held = len(engine.request(rid).generated)

    def boom(*a, **k):
        raise RuntimeError("the launch failed")

    engine._decode_jit = boom
    with pytest.raises(RuntimeError, match="the launch failed"):
        engine.step()
    # the black box has every token that was computed
    assert len(engine.request(rid).generated) == held + 1
    assert engine.last_flight_record["reason"].startswith("engine-fatal")
    return engine


def _drain_by_run_end(model):
    # the last request to finish does so by EOS: its surplus launch is in
    # flight when the queue is empty
    prompt = _prompts()[1][0]
    first = _serve(_engine(model), [(prompt, 9)], drained=True, rid0=21000)
    toks = first[0][1]
    eos = next(t for t in toks[1:-1] if t != toks[0])  # a decode's token
    engine = _engine(model, eos_token_id=eos)
    rid = engine.add_request(prompt, 9)
    done = engine.run()
    assert engine._inflight is None
    assert done[rid].tolist()[-1] == eos and len(done[rid]) < len(prompt) + 9
    return engine


DRAIN_SITES = {
    "preempt": _drain_by_preempt, "cancel": _drain_by_cancel,
    "deadline": _drain_by_deadline, "fault": _drain_by_fault,
    "debug_checks": _drain_by_debug_checks,
    "flight_record": _drain_by_flight_record, "fatal": _drain_by_fatal,
    "run_end": _drain_by_run_end,
}


def test_every_drain_reason_has_its_site():
    assert set(DRAIN_SITES) == set(DRAIN_REASONS)


@pytest.mark.parametrize("reason", DRAIN_REASONS)
def test_drain_is_counted_by_its_site(model, reason):
    name = f"serving_decode_drains_total{{reason={reason}}}"
    probe = _engine(model)
    before = probe.metrics.snapshot()[name]
    engine = DRAIN_SITES[reason](model)
    assert engine.metrics.snapshot()[name] - before >= 1
    assert f'serving_decode_drains_total{{reason="{reason}"}}' in \
        engine.metrics.prometheus()


def test_a_device_error_surfaces_at_the_next_fetch_and_names_its_step(
        model):
    engine = _engine(model)
    engine.add_request(_prompts()[0][0], 8)
    for _ in range(3):
        engine.step()

    class Lost:
        def __array__(self, *a, **k):
            raise RuntimeError("device lost")

    toks, step, launched = engine._inflight
    engine._inflight = (Lost(), step, launched)
    with pytest.raises(RuntimeError, match="device lost") as ei:
        engine.step()
    assert f"step {step} launched" in "".join(ei.value.__notes__)
    last = engine.last_flight_record["steps"][-1]
    assert f"step {step} launched" in last["extra"]["fatal"]


# ------------------------------------------------------------- one program
@pytest.mark.parametrize("tp", [1, 2])
def test_one_decode_program_for_all_some_and_no_overrides(model, tp):
    if tp > len(jax.devices()):
        pytest.skip("needs two devices")
    engine = _engine(model, tensor_parallel=tp)
    overrides = []
    real = engine._decode_args

    def spy(active=None, override=None):
        overrides.append(int((override[active] >= 0).sum())
                         - int(active.sum()))
        return real(active, override)

    engine._decode_args = spy
    prompts = _prompts()
    engine.add_request(*prompts[0])   # (5, 6)
    engine.add_request(*prompts[2])   # (3, 9)
    engine.step()                     # every slot overridden
    engine.step()                     # none
    engine.add_request(*prompts[5])   # joins: one of three overridden
    engine.run()
    kinds = {"all" if d == 0 else "some" for d in overrides[:1]} | \
        {"none" if d == -n else "some"
         for d, n in zip(overrides[1:], (2, 3))}
    assert kinds == {"all", "none", "some"}
    assert engine.compile_counts == {"prefill": 1, "decode": 1}
    jitted = engine.guards["decode"]._jits[None]
    assert jitted._cache_size() == 1  # the placeholder compiled no twin


# ------------------------------------------------------- spec, EOS surplus
def test_spec_engine_never_has_a_decode_in_flight(model):
    engine = _engine(model, spec=SpecConfig(method="ngram", depth=2))
    before = _counts(engine)
    for p, m in _prompts():
        engine.add_request(p, m)
    while not engine.scheduler.all_done:
        engine.step()
        assert engine._inflight is None
    moved = _delta(_counts(engine), before)
    assert moved["decode_overlapped_total"] == 0 and moved["drains"] == {}


def test_eos_finish_leaves_no_surplus_token(model):
    eos = _eos_token(model)
    prompts = _prompts()
    plain, drained = (_engine(model, eos_token_id=eos) for _ in range(2))
    before = _counts(plain)
    got = _serve(plain, prompts, drained=False, rid0=30000)
    moved = _delta(_counts(plain), before)
    before = _counts(drained)
    want = _serve(drained, prompts, drained=True, rid0=30000)
    moved_drained = _delta(_counts(drained), before)
    assert got == want
    cut = [i for i, (_, toks) in got.items()
           if toks[-1] == eos and len(toks) < prompts[i][1]]
    assert cut
    for i, (_, toks) in got.items():
        assert toks.count(eos) == (1 if toks[-1] == eos else 0)
    # counted: what was handed over, and no more
    n_tokens = sum(len(toks) for _, toks in got.values())
    assert moved["tokens_total"] == moved_drained["tokens_total"] == n_tokens
    # computed and thrown away: one launch's worth per EOS finish at most
    assert moved["decode_steps"] >= moved_drained["decode_steps"]
    # indexed: the same chains as the engine that never had a surplus
    assert sorted(k[1] for k in plain.cache._key_to_page) == \
        sorted(k[1] for k in drained.cache._key_to_page)
