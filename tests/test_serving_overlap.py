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
- **A prefill's first token** (PR 36) stays on the device for the decode
  launch behind it and is fetched behind that launch: the span order of a
  step that completes a prefill, the same tokens (EOS as the first token,
  ``max_new_tokens`` 1 and 2, several prefills a step, a final chunk, a
  prefix-cache tail, the hybrid and the window model), handed over in the
  step that completed the prefill, whole through every drain site hit
  between its launch and its fetch; a speculative and a ``debug_checks``
  engine fetch it at once; ``serving_prefill_overlapped_total`` counts the
  one and not the others; the fetches of a step are as many as they were.
"""
import functools
import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import SyncTally
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


@functools.cache
def _hybrid_model():
    """Mamba-2 layers beside an attention layer: a state a slot beside
    pages a token (text/granite_hybrid.py)."""
    from paddle_tpu.text.granite_hybrid import (GraniteHybridConfig,
                                                GraniteHybridForCausalLM)

    paddle.seed(3)
    m = GraniteHybridForCausalLM(GraniteHybridConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        num_attention_heads=4, num_key_value_heads=2,
        shared_intermediate_size=48, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=8, mamba_chunk_size=8, max_position_embeddings=48,
        initializer_range=0.25, embedding_multiplier=1.0,
        logits_scaling=1.0, residual_multiplier=1.0))
    m.eval()
    return m


@functools.cache
def _window_model():
    """A window layer beside a full one: pages in two groups, the window
    group's freed behind the window (text/mellum.py)."""
    from paddle_tpu.text.mellum import MellumConfig, MellumForCausalLM

    paddle.seed(5)
    m = MellumForCausalLM(MellumConfig(
        vocab_size=VOCAB, hidden_size=32, moe_intermediate_size=16,
        num_hidden_layers=2, layer_types=["sliding_attention",
                                          "full_attention"],
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        num_experts=4, num_experts_per_tok=2, sliding_window=4,
        max_position_embeddings=48, initializer_range=0.1))
    m.eval()
    return m


#: what a model other than the module's GPT needs of its engine
OTHER_MODELS = {
    "hybrid": (_hybrid_model, {"enable_prefix_caching": False}),
    "window": (_window_model, {"enable_prefix_caching": False,
                               "group_pages": {"window": 24}}),
}


def _engine(model, **overrides):
    kw = dict(max_batch=3, num_pages=40, page_size=4, max_prompt_len=16)
    kw.update(overrides)
    other = kw.pop("other", None)
    if other is not None:
        make, needs = OTHER_MODELS[other]
        model = make()
        kw.update(needs)
    inj = kw.pop("fault_injector", None)
    clock = kw.pop("clock", None)
    return ServingEngine(model, ServingConfig(**kw), fault_injector=inj,
                         clock=clock)


def _prompts(mix=MIX, seed=5, shared=0):
    """``shared``: every prompt starts with the same ``shared`` tokens (a
    prefix the cache can serve to the requests admitted later)."""
    rng = np.random.RandomState(seed)
    head = rng.randint(0, VOCAB, (shared,)).astype(np.int32)
    return [(np.concatenate([head, rng.randint(0, VOCAB, (n,))])
             .astype(np.int32), m) for n, m in mix]


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
    # a prefill's first token reaches the decode behind it on the device
    # and is fetched behind that launch (``at_once``: also token for token
    # the engine that fetches a first token at once, as debug_checks does)
    "first_token_is_eos": {"eos_first": True, "at_once": True},
    "max_new_tokens_1_and_2": {
        "mix": ((5, 1), (9, 2), (3, 1), (7, 2), (12, 1), (4, 2), (6, 3)),
        "at_once": True},
    "four_prefills_in_one_step": {"max_batch": 4, "at_once": True},
    "final_chunk_first_token_is_eos": {"chunk_size": 4, "eos_first": True,
                                       "at_once": True},
    "prefix_cache_tail": {
        "shared": 8, "max_batch": 2, "at_once": True,
        "mix": ((3, 6), (5, 4), (2, 9), (7, 1), (4, 7), (1, 5))},
    "tensor_parallel_chunked": {"tensor_parallel": 2, "chunk_size": 8},
    "hybrid_model": {"other": "hybrid"},
    "hybrid_model_sampling_eos": {"other": "hybrid", "do_sample": True,
                                  "seed": 11, "eos_first": True},
    "window_model": {"other": "window"},
    "window_model_max_new_1_and_2": {
        "other": "window",
        "mix": ((5, 1), (9, 2), (13, 1), (7, 2), (12, 6), (4, 2))},
}


@pytest.mark.parametrize("case", CASES)
def test_same_tokens_as_the_engine_drained_every_step(model, case):
    kw = dict(CASES[case])
    cancel_at = kw.pop("cancel_at", None)
    at_once = kw.pop("at_once", False)
    prompts = _prompts(kw.pop("mix", MIX), shared=kw.pop("shared", 0))
    if kw.pop("eos", False):
        kw["eos_token_id"] = _eos_token(model)
    eos_first = kw.pop("eos_first", False)
    if eos_first:
        first = _serve(_engine(model, **kw), prompts, drained=True,
                       rid0=9500)
        kw["eos_token_id"] = next(toks[0] for _, toks in first.values()
                                  if len(toks) > 2)
    if kw.get("tensor_parallel", 1) > len(jax.devices()):
        pytest.skip("needs two devices")
    rid0 = 10000 + 100 * list(CASES).index(case)
    plain = _engine(model, **kw)
    before = _counts(plain)
    snap = plain.metrics.snapshot()
    got = _serve(plain, prompts, drained=False, rid0=rid0,
                 cancel_at=cancel_at)
    moved = _delta(_counts(plain), before)
    want = _serve(_engine(model, **kw), prompts, drained=True, rid0=rid0,
                  cancel_at=cancel_at)
    assert got == want
    if at_once:
        assert got == _serve(_engine(model, debug_checks=True, **kw),
                             prompts, drained=False, rid0=rid0)
    assert plain.compile_counts["decode"] == 1
    # the mechanism ran: launches were made with a decode in flight
    assert moved["decode_overlapped_total"] >= 3
    if eos_first:
        # a request of several tokens ended at its first: the decode
        # launched behind its prefill computed a surplus token, dropped
        assert any(toks == [kw["eos_token_id"]] and m > 1
                   for (_, toks), (_, m) in zip(got.values(), prompts))
    elif "eos_token_id" in kw:
        assert any(toks[-1] == kw["eos_token_id"] and len(toks) < m
                   for (_, toks), (_, m) in zip(got.values(), prompts))
    if case == "prefix_cache_tail":
        after = plain.metrics.snapshot()
        assert after["serving_prefix_hits"] > snap["serving_prefix_hits"]
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


@pytest.mark.parametrize("chunk_size", [0, 4])
def test_a_prefill_s_decode_is_launched_before_its_first_token_is_fetched(
        model, tmp_path, chunk_size):
    """A step that completes a prefill: the prefill's launch, the decode's
    launch, the fetch of the decode that the step BEFORE launched, and
    only then the prefill's own fetch; several prefills of one step are
    all launched before the decode and fetched after it, in admission
    order. A final chunk under ``chunk_size`` takes the same order."""
    engine = _engine(model, chunk_size=chunk_size)
    prompts = _prompts()
    engine.add_request(prompts[0][0], 3)
    engine.run()  # compiled and idle again
    rids = []

    def run():
        rids.append(engine.add_request(prompts[2][0], 12))  # 3 tokens
        for _ in range(3):
            engine.step()
        # two more join a running engine: their prefills complete in one
        # step (3 and 4 tokens: a chunk each)
        rids.extend(engine.add_request(prompts[i][0], 4) for i in (2, 5))
        engine.run()

    spans = _traced_spans(tmp_path, run)
    assert not [s for s in spans if s[0] == "drain"]
    by_step = {}
    for name, start, end, stats in spans:
        by_step.setdefault(stats["step"], []).append((name, start, end,
                                                      stats))
    joined = 0
    for step, parts in by_step.items():
        firsts = [p for p in parts if p[0] == "prefill.fetch"]
        if not firsts:
            continue
        launches = [p for p in parts if p[0] == "prefill.dispatch"
                    and p[3]["rid"] in {f[3]["rid"] for f in firsts}]
        decode = [p for p in parts if p[0] == "decode.dispatch"]
        assert len(decode) == 1
        # every prefill is launched before the decode, fetched after it
        assert max(p[2] for p in launches) <= decode[0][1]
        assert decode[0][2] <= min(f[1] for f in firsts)
        # in admission order
        assert [f[3]["rid"] for f in firsts] == \
            [p[3]["rid"] for p in launches] == \
            [r for r in rids if r in {f[3]["rid"] for f in firsts}]
        fetch = [p for p in parts if p[0] == "decode.fetch"]
        if len(firsts) == 2:
            # a decode was in flight: its tokens leave before the
            # prefills' do
            joined += 1
            assert fetch and fetch[0][3]["of_step"] == step - 1
            assert decode[0][2] <= fetch[0][1]
            assert fetch[0][2] <= firsts[0][1]
            emit = next(p for p in parts if p[0] == "decode.emit")
            assert emit[2] <= firsts[0][1]
    assert joined == 1


@pytest.mark.parametrize("chunk_size", [0, 4])
def test_a_first_token_is_handed_over_in_the_step_that_completed_its_prefill(
        model, chunk_size):
    engine = _engine(model, chunk_size=chunk_size)
    prompts = _prompts()
    a = engine.add_request(prompts[2][0], 12)   # 3 prompt tokens
    for _ in range(3):
        engine.step()
    b = engine.add_request(prompts[1][0], 5)    # 9: one pass or 3 chunks
    req = engine.request(b)
    seen = []
    for _ in range(4 if chunk_size else 2):
        finished = engine.step()
        seen.append((req.state, len(req.generated), req.tokens_in_flight,
                     finished))
    if chunk_size:
        assert seen[:2] == [("prefilling", 0, 0, [])] * 2
        seen = seen[2:]
    # the step that completed the prefill hands the first token over, with
    # the decode launched behind it in flight; the next step that decode's
    assert seen == [("running", 1, 1, []), ("running", 2, 1, [])]
    events = [e.name for e in engine.trace(b).events]
    assert events.index("prefill_end") < events.index("first_token")
    # the record of that step: a prefill completed, its fetch's seconds,
    # and both slots launched
    done = next(r for r in engine.timeline.records()
                if r.step > 2 and r.prefills)
    assert "prefill.fetch" in done.span_s and done.batch == 2
    engine.run()
    assert engine.status(a) == engine.status(b) == "finished"


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


# ------------------------- drains between a prefill's launch and its fetch
#: a request that runs throughout (3 tokens in, 10 out) and one that joins
#: it at step 3 (8 in: its first decode asks for a fresh page; 6 out)
RUNNING_RID, JOINING_RID = 40001, 40002


def _caught_by_cancel(model):
    engine = _engine(model)
    return engine, lambda: engine.cancel(JOINING_RID), "cancelled"


def _caught_by_deadline(model):
    t = [0.0]
    engine = _engine(model, clock=lambda: t[0])

    def expire():
        t[0] = 100.0
        engine._sweep_deadlines()

    return engine, expire, "expired"


def _caught_by_fault(model):
    # the injector's decode-phase hit, in the step that prefilled
    inj = FaultInjector().arm("decode_fail", step=3, rid=JOINING_RID)
    return _engine(model, fault_injector=inj), None, "failed"


def _caught_by_preempt(model):
    # the pool runs dry where the joiner's decode asks for its page: a
    # victim is picked among, and preempted with, what the host knows
    return _engine(model, num_pages=5), None, "finished"


CAUGHT = {"cancel": _caught_by_cancel, "deadline": _caught_by_deadline,
          "fault": _caught_by_fault, "preempt": _caught_by_preempt}


@pytest.mark.parametrize("reason", CAUGHT)
def test_a_drain_between_a_prefill_s_launch_and_its_fetch_brings_the_token_home(
        model, reason):
    engine, act, state = CAUGHT[reason](model)
    before = _counts(engine)
    overlapped0 = engine.metrics.snapshot()[
        "serving_prefill_overlapped_total"]
    (pa, ma), (pb, mb) = _prompts(((3, 10), (8, 6)))
    engine.add_request(pa, ma, rid=RUNNING_RID)
    for _ in range(3):
        engine.step()
    engine.add_request(pb, mb, rid=JOINING_RID, deadline_s=50.0)
    req = engine.request(JOINING_RID)
    drains, real_drain = [], engine._drain
    real_pages = engine.scheduler.ensure_decode_pages

    def spy(why):
        drains.append((why, [f.req.rid for f in engine._unfetched]))
        return real_drain(why)

    def mid_step():
        # inside serve.evict: behind the prefill's launch, before the
        # decode's, the first token unfetched
        if act is not None and engine._unfetched:
            act()
        return real_pages()

    engine._drain = spy
    engine.scheduler.ensure_decode_pages = mid_step
    engine.step()                               # step 3: the prefill's
    engine._drain = real_drain
    engine.scheduler.ensure_decode_pages = real_pages
    # the drain found the first token unfetched and brought it home: the
    # request has it, nothing of its is in flight, and it was counted
    assert drains[0] == (reason, [JOINING_RID])
    assert req.tokens_in_flight == (1 if req.state == "running" else 0)
    assert not engine._unfetched
    assert len(req.generated) == 1
    assert req.tokens_emitted == 1
    engine.cache.check_invariants()
    moved = _delta(_counts(engine), before)
    assert moved["drains"].get(reason, 0) >= 1
    if reason == "preempt":
        assert engine.scheduler.preemption_count == 1
    # a first token that a drain caught was not fetched behind its decode:
    # only the first request's counts
    assert engine.metrics.snapshot()["serving_prefill_overlapped_total"] \
        == overlapped0 + 1
    engine.run()
    assert engine.status(JOINING_RID) == state
    assert engine.status(RUNNING_RID) == "finished"
    engine.cache.check_invariants()
    assert engine.cache.allocator.pages_in_use == 0
    # the survivors' tokens are those of an engine that never overlapped
    ref = _engine(model)
    ra, rb = ref.add_request(pa, ma), ref.add_request(pb, mb)
    want = ref.run()
    assert want[ra].tolist() == engine.result(RUNNING_RID).tolist()
    if state == "finished":
        assert want[rb].tolist() == engine.result(JOINING_RID).tolist()
    else:
        assert req.generated == want[rb].tolist()[len(pb):len(pb) + 1]


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
    engine.step()                     # none: both first tokens in flight
    engine._drain("run_end")
    engine.step()                     # every slot overridden
    engine._drain("run_end")
    engine.add_request(*prompts[5])   # joins with its token in flight:
    engine.step()                     # two of three overridden
    engine.run()
    assert overrides[:3] == [-2, 0, -1]
    kinds = {"all" if d == 0 else "none" if d == -n else "some"
             for d, n in zip(overrides, (2, 2, 3))}
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


# ------------------------------ who fetches a first token at once, counts
def _first_token_fetches(engine, prompts):
    """Serve ``prompts`` and return, for every launch of a decode or a
    verify, how many first tokens were unfetched then, and the host syncs
    of every step beside what the step fetched: (prefills completed, a
    decode was in flight at its start)."""
    unfetched, steps = [], []
    real = engine._launch

    def spy(prog, *a, **k):
        if prog.phase != "prefill":
            unfetched.append(len(engine._unfetched))
        return real(prog, *a, **k)

    engine._launch = spy
    for p, m in prompts:
        engine.add_request(p, m)
    while not engine.scheduler.all_done:
        in_flight = engine._inflight is not None
        n0 = engine.metrics.snapshot()["serving_prefills_total"]
        with SyncTally() as tally:
            engine.step()
        done = engine.metrics.snapshot()["serving_prefills_total"] - n0
        steps.append((tally.count, int(done), in_flight))
    return unfetched, steps


@pytest.mark.parametrize("how", ["plain", "chunked", "spec", "debug_checks",
                                 "spec_chunked"])
def test_who_fetches_a_first_token_at_once_and_what_a_step_fetches(
        model, how):
    kw = {"plain": {}, "chunked": {"chunk_size": 4},
          "spec": {"spec": SpecConfig(method="ngram", depth=2)},
          "debug_checks": {"debug_checks": True},
          "spec_chunked": {"spec": SpecConfig(method="ngram", depth=2),
                           "chunk_size": 4}}[how]
    engine = _engine(model, **kw)
    snap = engine.metrics.snapshot()
    unfetched, steps = _first_token_fetches(engine, _prompts())
    after = engine.metrics.snapshot()
    prefills = after["serving_prefills_total"] - snap["serving_prefills_total"]
    overlapped = after["serving_prefill_overlapped_total"] \
        - snap["serving_prefill_overlapped_total"]
    assert prefills == len(MIX)
    if how in ("plain", "chunked"):
        # every prefill's decode was launched with the token still on the
        # device, and the counter says so
        assert sum(unfetched) == prefills == overlapped
        # one fetch a completed prefill + one for the decode in flight at
        # the step's start: what a step fetched before the order changed
        assert all(syncs == done + in_flight
                   for syncs, done, in_flight in steps)
    else:
        # speculation and debug_checks want the token on the host: no
        # launch ever finds one unfetched, and none counts as overlapped
        assert unfetched and not any(unfetched) and overlapped == 0
        # they never have a launch in flight across a step boundary
        assert not any(in_flight for _, _, in_flight in steps)
        assert all(syncs >= done for syncs, done, _ in steps)
        assert sum(s for s, _, _ in steps) == prefills + len(unfetched)


def test_prefill_overlapped_counter_is_in_the_exposition(model):
    engine = _engine(model)
    engine.add_request(_prompts()[0][0], 3)
    engine.run()
    text = engine.metrics.prometheus()
    assert "serving_prefill_overlapped_total" in text
    assert "serving_prefills_total" in text


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
