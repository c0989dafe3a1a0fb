"""The engine's phases and the programs' names in the profiler's own trace.

What ``obs/attribution.py`` promises, read back out of a real
``jax.profiler`` trace taken on the CPU (``ProfileData`` over the
``.xplane.pb``, as ``benchmark/tests/test_reduce.py`` takes one):

- the ``serve.*`` span tree: every span of the table under ``serve.step``
  with the right parent, ``step`` on every span and ``rid`` on the
  per-request ones; the fetch ends after the dispatch, inside the phase;
- one mechanism, two records: each sub-span's seconds on the
  ``StepRecord`` agree with the same span's duration in the xplane, the
  top-level phases still sum to the step, and with tracing off there is no
  span and no accumulator;
- zero added host syncs with the profiler recording;
- device names: one module name per compiled serving program (a name per
  prefill pad bucket), the patterns the benchmark's accepted metrics search
  with hit exactly what they hit before, and the named scopes reach the
  ``op_name`` metadata of the training step and of the serving programs
  while the optimized program stays the same.
"""
import contextlib
import glob
import os
import re
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import CompileGuard, SyncTally
from paddle_tpu.obs import PHASES, SPAN_PREFIX
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.spec import SpecConfig
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

pytestmark = pytest.mark.obs

Span = namedtuple("Span", "name start end stats")

#: the sub-spans: on the StepRecord under these names, in the trace with
#: the prefix
SUB_SPANS = ("prefill.upload", "prefill.dispatch", "prefill.fetch",
             "decode.upload", "decode.dispatch", "decode.fetch",
             "decode.emit")
SCOPES_TRAIN = ("embed", "block/attn", "block/mlp", "final_norm", "head_ce",
                "optimizer")
SCOPES_SERVE = ("embed", "block/attn", "block/mlp", "final_norm", "kv_write",
                "lm_head", "sample")


def _toy_model(seed=29):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=48, dropout=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _toy_model()


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 97, (n,)).astype(np.int32)


def _engine(model, **overrides):
    kw = dict(max_batch=2, num_pages=32, page_size=4, max_prompt_len=16)
    kw.update(overrides)
    return ServingEngine(model, ServingConfig(**kw))


def _traced(tmp, fn):
    """Run ``fn`` under a profiler session (Python tracer off, as the
    benchmark's traced runs) and return (the ``serve.*`` spans, every
    host event's name)."""
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
    spans, names = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name.startswith(SPAN_PREFIX):
                    s = ev.start_ns * 1e-9
                    spans.append(Span(ev.name[len(SPAN_PREFIX):], s,
                                      s + ev.duration_ns * 1e-9,
                                      dict(ev.stats)))
    return sorted(spans, key=lambda s: (s.start, -s.end)), names


def _parent(span, spans):
    """The shortest span that holds ``span`` (nesting on one thread)."""
    holders = [p for p in spans if p is not span
               and p.start <= span.start and span.end <= p.end]
    return min(holders, key=lambda p: p.end - p.start, default=None)


def _scenario(model, tmp, **overrides):
    """An engine warmed up on both prefill buckets, then one traced run of
    two requests (a bucket each)."""
    engine = _engine(model, **overrides)
    for n, seed in ((5, 0), (12, 1)):
        engine.add_request(_prompt(n, seed), 3)
    engine.run()
    first = len(engine.timeline)
    rids = []

    def run():
        rids.extend(engine.add_request(_prompt(n, seed), 4)
                    for n, seed in ((5, 2), (12, 3)))
        engine.run()

    spans, names = _traced(tmp, run)
    return {"engine": engine, "spans": spans, "names": names, "rids": rids,
            "records": engine.timeline.records()[first:]}


@pytest.fixture(scope="module")
def plain(model, tmp_path_factory):
    return _scenario(model, tmp_path_factory.mktemp("plain"))


@pytest.fixture(scope="module")
def chunked(model, tmp_path_factory):
    return _scenario(model, tmp_path_factory.mktemp("chunked"), chunk_size=4)


@pytest.fixture(scope="module")
def spec(model, tmp_path_factory):
    return _scenario(model, tmp_path_factory.mktemp("spec"),
                     spec=SpecConfig(method="ngram", depth=2))


@pytest.fixture(scope="module")
def cow(tmp_path_factory):
    """Two concurrent identical two-page prompts against a warm cache: the
    second admission copies the shared last page on write."""
    engine = _engine(_toy_model(seed=41), max_prompt_len=8)
    prompt = _prompt(8, seed=7)
    engine.add_request(prompt, 3)
    engine.run()

    def run():
        engine.add_request(prompt, 3)
        engine.add_request(prompt, 3)
        engine.run()

    spans, names = _traced(tmp_path_factory.mktemp("cow"), run)
    assert engine.metrics.snapshot()["serving_prefix_cow_copies"] >= 1
    return {"engine": engine, "spans": spans, "names": names}


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """A model of window layers beside a full one (text/mellum.py): a
    prompt of three windows and ten more tokens, so window pages go back
    behind the prefill's launch and behind decode steps."""
    from paddle_tpu.text.mellum import MellumConfig, MellumForCausalLM

    paddle.seed(5)
    m = MellumForCausalLM(MellumConfig(
        vocab_size=97, hidden_size=32, moe_intermediate_size=16,
        num_hidden_layers=2, layer_types=["sliding_attention",
                                          "full_attention"],
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        num_experts=4, num_experts_per_tok=2, sliding_window=4,
        max_position_embeddings=32, initializer_range=0.1))
    engine = _engine(m, group_pages={"window": 8},
                     enable_prefix_caching=False)
    engine.add_request(_prompt(12, 0), 3)
    engine.run()

    def run():
        engine.add_request(_prompt(12, 1), 10)
        engine.run()

    spans, names = _traced(tmp_path_factory.mktemp("window"), run)
    return {"engine": engine, "spans": spans, "names": names}


# ------------------------------------------------------------ the span tree
@pytest.mark.parametrize("scenario,child,parent", [
    ("plain", "admit", "step"),
    ("plain", "prefill", "step"),
    ("plain", "prefill.upload", "prefill"),
    ("plain", "prefill.dispatch", "prefill"),
    # the first-token fetch lies behind the step's decode launch
    ("plain", "prefill.fetch", "decode"),
    ("plain", "evict", "step"),
    ("plain", "decode", "step"),
    ("plain", "decode.upload", "decode"),
    ("plain", "decode.dispatch", "decode"),
    ("plain", "decode.fetch", "decode"),
    ("plain", "decode.emit", "decode"),
    ("plain", "account", "step"),
    ("chunked", "chunk_prefill", "step"),
    # a chunk goes through the one prefill path: the same parts
    ("chunked", "prefill.upload", "chunk_prefill"),
    ("chunked", "prefill.dispatch", "chunk_prefill"),
    ("chunked", "prefill.fetch", "decode"),
    ("spec", "verify", "step"),
    # a speculative engine fetches a first token at once
    ("spec", "prefill.fetch", "prefill"),
    # and a verify through the one launch and the one fetch
    ("spec", "verify.dispatch", "verify"),
    ("spec", "verify.fetch", "verify"),
    ("cow", "cow_copy", "admit"),
])
def test_span_nests_under_its_parent(request, scenario, child, parent):
    spans = request.getfixturevalue(scenario)["spans"]
    mine = [s for s in spans if s.name == child]
    assert mine, f"no serve.{child} in the trace"
    for s in mine:
        p = _parent(s, spans)
        assert p is not None and p.name == parent, (s, p)
        assert s.stats["step"] == p.stats["step"]
        if "rid" in p.stats:  # a request's parts carry its id
            assert s.stats["rid"] == p.stats["rid"]
        elif child == "prefill.fetch":  # in serve.decode: its own request's
            assert s.stats["rid"] in {
                q.stats["rid"] for q in spans if q.name in (
                    "prefill", "prefill.dispatch")
                and q.stats["step"] == s.stats["step"]}


def test_window_release_lies_behind_a_prefill_s_launch_and_in_evict(window):
    """``serve.window_release``: the host's freeing of window-group pages
    behind the window: inside ``serve.prefill`` (behind the launch that
    read them) and inside ``serve.evict`` (before a decode step grows its
    slots), each in its step; the counter counts what they freed."""
    spans = window["spans"]
    mine = [s for s in spans if s.name == "window_release"]
    parents = {_parent(s, spans).name for s in mine}
    assert parents == {"prefill", "evict"}
    for s in mine:
        assert s.stats["step"] == _parent(s, spans).stats["step"]
    freed = window["engine"].metrics.snapshot()[
        "serving_kv_window_pages_released_total"]
    # two prompts of 12 and 13 more tokens at a window of 4, pages of 4
    assert freed >= 2 * 2 + 2


@pytest.mark.parametrize("scenario", ["plain", "chunked", "spec", "cow",
                                      "window"])
def test_every_span_carries_step_and_lies_in_its_step(request, scenario):
    spans = request.getfixturevalue(scenario)["spans"]
    steps = {s.stats["step"]: s for s in spans if s.name == "step"}
    assert steps and all(s.stats["step_num"] == k for k, s in steps.items())
    for s in spans:
        assert "step" in s.stats, s
        if s.name in ("step", "add_request"):
            continue
        home = steps[s.stats["step"]]  # joins by the step index
        assert home.start <= s.start and s.end <= home.end, (s, home)


def test_request_spans_carry_rid_and_their_attributes(plain):
    spans, rids = plain["spans"], plain["rids"]
    added = [s for s in spans if s.name == "add_request"]
    assert [s.stats["rid"] for s in added] == rids
    assert [s.stats["prompt_len"] for s in added] == [5, 12]
    assert all(_parent(s, spans) is None for s in added)  # no step is open
    prefills = [s for s in spans if s.name == "prefill"]
    assert [(s.stats["rid"], s.stats["bucket"], s.stats["cached"],
             s.stats["tail"]) for s in prefills] == [
        (rids[0], 8, 0, 5), (rids[1], 16, 0, 12)]
    engine = plain["engine"]
    for s in prefills:
        # the request's own lifecycle joins by rid and engine step
        hops = {h["kind"]: h["step"]
                for h in engine.journey(s.stats["rid"]).hops}
        assert hops["prefill_start"] == s.stats["step"]
    up = [s for s in spans if s.name == "prefill.upload"]
    row = engine.cache.page_table[0].nbytes
    assert [s.stats["bytes"] for s in up] == [4 * 8 + row + 16,
                                              4 * 16 + row + 16]
    decodes = [s for s in spans if s.name == "decode"]
    # batch = the slots launched: none in the last, which only fetches
    assert [s.stats["batch"] for s in decodes][-1] == 0
    assert decodes[:-1] and all(s.stats["batch"] in (1, 2)
                                for s in decodes[:-1])
    assert {s.stats["bytes"] for s in spans if s.name == "decode.upload"} \
        == {engine._decode_upload_bytes}
    assert [s.stats["queue_depth"] for s in spans
            if s.name == "admit"][0] == 2


def test_fetch_ends_after_dispatch_inside_the_phase(plain):
    spans = plain["spans"]
    prefills = [s for s in spans if s.name == "prefill"]
    for p in prefills:
        inside = {s.name: s for s in spans if _parent(s, spans) is p}
        # a prefill launches and does not wait: no fetch inside
        assert set(inside) == {"prefill.upload", "prefill.dispatch"}
        up, disp = inside["prefill.upload"], inside["prefill.dispatch"]
        assert p.start <= up.start and up.end <= disp.start
        assert disp.end <= p.end
    # a decode phase launches, then fetches the launch of the step before,
    # then the first tokens of its own step's prefills in admission order:
    # the first has no decode to fetch, the last nothing to launch
    decodes = [s for s in spans if s.name == "decode"]
    for i, p in enumerate(decodes):
        mine = [s for s in spans if _parent(s, spans) is p]
        firsts = [s for s in mine if s.name == "prefill.fetch"]
        assert [s.stats["rid"] for s in firsts] == [
            q.stats["rid"] for q in prefills
            if q.stats["step"] == p.stats["step"]]
        inside = {s.name: s for s in mine if s.name != "prefill.fetch"}
        assert set(inside) == \
            ({"decode.upload", "decode.dispatch"} if i < len(decodes) - 1
             else set()) | ({"decode.fetch", "decode.emit"} if i else set())
        order = [inside[f"decode.{k}"]
                 for k in ("upload", "dispatch", "fetch", "emit")
                 if f"decode.{k}" in inside] + firsts
        assert p.start <= order[0].start and order[-1].end <= p.end
        assert all(a.end <= b.start for a, b in zip(order, order[1:]))
        if i:
            assert inside["decode.fetch"].stats["of_step"] == \
                decodes[i - 1].stats["step"]


def test_attribute_counts(chunked, spec, cow):
    chunks = [s for s in chunked["spans"] if s.name == "chunk_prefill"]
    assert all(s.stats["chunks"] >= 1 for s in chunks)
    # 5 and 12 prompt tokens in chunks of 4: 2 + 3 chunks over the steps
    assert sum(s.stats["chunks"] for s in chunks) == 5
    assert not [s for s in chunked["spans"] if s.name == "prefill"]
    # every chunk uploads and dispatches; only the chunk that completes a
    # prompt fetches (its first token), after its own dispatch
    parts = {k: [s for s in chunked["spans"] if s.name == "prefill." + k]
             for k in ("upload", "dispatch", "fetch")}
    by_rid = lambda k: sorted(s.stats["rid"] for s in parts[k])  # noqa: E731
    small, big = chunked["rids"]
    assert by_rid("upload") == by_rid("dispatch") == [small] * 2 + [big] * 3
    assert by_rid("fetch") == [small, big]
    for fetch in parts["fetch"]:
        assert max(s.end for s in parts["dispatch"]
                   if s.stats["rid"] == fetch.stats["rid"]) <= fetch.start
    # a chunk of 4 pads into the bucket of 8
    row = chunked["engine"].cache.page_table[0].nbytes
    assert {s.stats["bytes"] for s in parts["upload"]} == {4 * 8 + row + 16}
    verifies = [s for s in spec["spans"] if s.name == "verify"]
    assert verifies and all(s.stats["batch"] in (1, 2) for s in verifies)
    assert not [s for s in spec["spans"] if s.name.startswith("decode")]
    assert all(s.stats["pages"] == 1 for s in cow["spans"]
               if s.name == "cow_copy")


# ----------------------------------------------- one mechanism, two records
@pytest.mark.parametrize("name", SUB_SPANS)
def test_sub_span_seconds_agree_with_the_xplane(plain, name):
    by_step = {}
    for s in plain["spans"]:
        if s.name == name:
            by_step[s.stats["step"]] = by_step.get(s.stats["step"], 0.0) \
                + (s.end - s.start)
    assert by_step
    records = {r.step: r for r in plain["records"]}
    for step, traced in by_step.items():
        mine = records[step].span_s[name]
        # the clock reads lie just inside the TraceMe event
        assert mine <= traced + 1e-4
        assert abs(mine - traced) <= max(0.2 * traced, 5e-4), (step, name)


def test_phases_still_sum_and_spans_are_no_part_of_the_sum(plain):
    for rec in plain["records"]:
        assert set(rec.phase_s) <= set(PHASES)
        assert not set(rec.span_s) & set(PHASES)
        assert sum(rec.phase_s.values()) == pytest.approx(rec.duration,
                                                          rel=1e-9)
        # a part is no longer than its phase; a first token's fetch lies
        # in the decode phase, behind its launch
        in_decode = lambda k: k.startswith("decode.") \
            or k == "prefill.fetch"  # noqa: E731
        for phase, mine in (
                ("prefill", lambda k: k.startswith("prefill.")
                 and not in_decode(k)), ("decode", in_decode)):
            parts = sum(v for k, v in rec.span_s.items() if mine(k))
            assert parts <= rec.phase_s.get(phase, 0.0) + 1e-9
        assert 0 < rec.span_s["account"] <= rec.phase_s["other"] + 1e-9
    assert any("prefill.fetch" in r.span_s for r in plain["records"])
    # the flight record carries the spans beside the phases
    last = plain["engine"].flight_record()["steps"][-1]
    assert set(last["span_s"]) >= {"decode.fetch", "account"}


def test_tracing_off_leaves_no_span_and_no_accumulator(model, tmp_path):
    engine = _engine(model, enable_tracing=False)
    assert not engine._attr.enabled and engine.cache.spans is engine._attr

    def run():
        engine.add_request(_prompt(5), 3)
        engine.run()

    spans, names = _traced(tmp_path, run)
    assert spans == []
    # the programs ran (and are named) all the same
    assert "PjitFunction(serve_decode)" in names


def test_profiled_spans_add_zero_host_syncs(model, tmp_path):
    engine = _engine(model)
    engine.add_request(_prompt(5), 2)
    engine.run()
    pre = engine.metrics.snapshot()

    def run():
        for i in range(3):
            engine.add_request(_prompt(4 + i, seed=i), 4)
        with SyncTally() as tally:
            engine.run()
        run.tally = tally

    spans, _ = _traced(tmp_path, run)
    snap = engine.metrics.snapshot()
    fetches = int(snap["serving_decode_steps"] - pre["serving_decode_steps"]
                  + snap["serving_prefills_total"]
                  - pre["serving_prefills_total"])
    assert run.tally.count == fetches, (run.tally.events, fetches)
    # one fetch span per sanctioned sync, and nothing else syncs
    assert len([s for s in spans if s.name.endswith(".fetch")]) == fetches


def test_fatal_step_closes_its_spans(model, tmp_path):
    engine = _engine(model)
    engine.add_request(_prompt(5), 6)
    engine.step()

    def boom(*args, **kwargs):
        raise RuntimeError("induced decode failure")

    engine._decode_jit = boom

    def run():
        with pytest.raises(RuntimeError, match="induced"):
            engine.step()

    spans, _ = _traced(tmp_path, run)
    # the failing step's spans are closed (they are in the trace at all)
    # and nest as ever; nothing is left open for the next step
    assert {s.name for s in spans} >= {"step", "decode", "decode.dispatch"}
    assert engine._attr._account is None and engine._attr._step_ann is None
    fatal = engine.timeline.records()[-1]
    assert fatal.extra["fatal"].startswith("RuntimeError")
    assert "decode.dispatch" in fatal.span_s


# ------------------------------------------------------------- device names
def _module_name(jitted, *args) -> str:
    return re.search(r"module @(\S+)", jitted.lower(*args).as_text()).group(1)


def test_compile_guard_names_a_program_per_group():
    g = CompileGuard(lambda ids: ids * 2, "prefill", budget=2,
                     group_by=lambda ids: tuple(ids.shape),
                     program="serve_prefill")
    for n in (8, 16, 8):
        g(jnp.zeros((n,), jnp.int32))
    assert g.traces == 2 and g.retraces == 0 and set(g._jits) == {(8,),
                                                                  (16,)}
    names = [_module_name(g._jits[(n,)], jnp.zeros((n,), jnp.int32))
             for n in (8, 16)]
    assert names == ["jit_serve_prefill_8", "jit_serve_prefill_16"]
    # ungrouped: the label, unless a program name is given
    plain = CompileGuard(lambda x: x + 1, "cow_copy", budget=1)
    plain(jnp.zeros((2,)))
    assert _module_name(plain._jits[None], jnp.zeros((2,))) == "jit_cow_copy"


def test_one_module_name_per_compiled_serving_program(plain, spec):
    want = {"PjitFunction(serve_prefill_8)", "PjitFunction(serve_prefill_16)",
            "PjitFunction(serve_decode)"}
    assert want <= plain["names"]
    assert "PjitFunction(serve_verify)" in spec["names"]
    engine = plain["engine"]
    # compile_counts, guards and prefill_buckets keep keys and meaning
    assert engine.compile_counts == {"prefill": 2, "decode": 1}
    assert set(engine.guards) == {"prefill", "decode"}
    assert engine.prefill_buckets == [8, 16]
    assert set(engine.guards["prefill"]._jits) == {(8,), (16,)}


@pytest.fixture(scope="module")
def train_step():
    """A tiny training step, lowered: (lowered, its text with locations)."""
    from jax.sharding import Mesh

    from paddle_tpu.distributed.fleet.hybrid_train import build_hybrid_step

    def build():
        paddle.seed(3)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
            max_seq_len=16, dropout=0.0, loss_chunk_size=8))
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        init_fn, step, shard_batch = build_hybrid_step(
            m, opt, lambda loss: loss, mesh)
        batch = tuple(shard_batch([np.zeros((2, 16), np.int64),
                                   np.zeros((2, 16), np.int64)]))
        return step.lower(init_fn(), jax.random.key(0), np.float32(1e-3),
                          batch, ())

    return build


def test_accepted_patterns_hit_what_they_hit_before(plain, train_step):
    """``decode`` (3 accepted metrics) and ``jit_step`` (3) search module
    names; ``ragged`` and ``flash`` search kernel names."""
    engine = plain["engine"]
    modules = ["jit_" + g.program + suffix
               for g, suffix in ((engine.guards["prefill"], "_8"),
                                 (engine.guards["prefill"], "_16"),
                                 (engine.guards["decode"], ""))]
    modules += ["jit_serve_verify"]
    modules += ["jit_" + g.program for g in engine.cache.guards.values()]
    step_module = re.search(r"module @(\S+)",
                            train_step().as_text()).group(1)
    modules.append(step_module)
    assert [m for m in modules if re.search("decode", m)] \
        == ["jit_serve_decode"]
    assert [m for m in modules if re.search("jit_step", m)] == ["jit_step"]
    assert not [m for m in modules if re.search("ragged|flash", m)]
    assert len(set(modules)) == len(modules)
    # the pattern of prefill_step_device_ms hits the 512 bucket alone
    pat = "jit_serve_prefill_512"
    assert [m for m in modules + ["jit_serve_prefill_512(7)"]
            if re.search(pat, m)] == ["jit_serve_prefill_512(7)"]


@pytest.mark.parametrize("scope", SCOPES_TRAIN)
def test_training_step_carries_scope_in_op_name(train_step, scope):
    text = train_step().as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(step\)/[^"]*)"', text))
    assert [n for n in names if scope in n], scope
    if scope != "optimizer":  # forward and backward both carry it
        assert [n for n in names if f"transpose(jvp({scope}))" in n]


@pytest.mark.parametrize("scope", SCOPES_SERVE)
def test_serving_program_carries_scope_in_op_name(plain, scope):
    engine = plain["engine"]
    text = jax.jit(engine.guards["decode"].fn).lower(
        *engine._decode_args()).as_text(
        debug_info=True)
    assert re.search(r'loc\("jit\([^"]*/' + re.escape(scope) + "/", text)


def _program_text(compiled) -> str:
    """The optimized HLO without what names it: metadata, the tables of
    files and frames, and the numbers of instructions."""
    text = compiled.as_text()
    text = text[text.index("\n\n", text.index("StackFrames"))
                if "StackFrames" in text else 0:]
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.sub(r"\.\d+\b", "", text)


def test_scopes_are_metadata_only(train_step, monkeypatch):
    with_scopes = train_step().compile()
    assert 'op_name="jit(step)/optimizer/' in with_scopes.as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = train_step().compile()
    assert 'op_name="jit(step)/optimizer/' not in without.as_text()
    assert _program_text(with_scopes) == _program_text(without)
