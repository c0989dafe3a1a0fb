"""Continuous-batching serving demo: requests of different lengths share one
compiled decode step over a paged KV cache.

Run: JAX_PLATFORMS=cpu python examples/serving_demo.py

Queues a burst of staggered requests against a toy GPT, drives the engine to
completion, and asserts the serving invariants: per-request outputs identical
to single-request generate(), one compilation of the prefill step per pad
bucket and exactly one of the decode step despite requests joining/leaving,
and live serving metrics. Phase two replays the burst against the resilience
layer: a deadline blown by an injected stall, a cancellation, and swap-style
preemption — all deterministic (virtual clock, no sleeps). Phase three
serves a shared-system-prompt burst through the automatic prefix cache:
every request after the first maps the system prompt's pages by refcount
and prefills only its private tail, bit-identical to the cold path.

Observability (on by default): phase one prints every request's latency
decomposition — queue wait / TTFT / TPOT / e2e off the engine clock — and
writes the burst's Chrome trace_event JSON to
profiles/serving_demo_trace.json (load it at ui.perfetto.dev: one track
per request plus the engine loop). The analysis phase certifies the
decode loop is sync-free with tracing enabled.

The final phase serves a whale prompt through CHUNKED prefill: the prompt
streams 8 tokens per step through the same compiled prefill program, so a
newcomer queued behind it gets its first token while the whale is still
prefilling — then replays the whale under an SLO admission controller
with an unmeetable TTFT target, which deterministically throttles
chunks-per-step to the floor (virtual clock) with outputs bit-identical
and the sync-free certification unchanged.

The speculative-decoding phase replays a burst with K=4 n-gram-proposed
candidates verified per step in one batched ragged pass: outputs stay
bit-identical to plain decode, one verify program compiles, the host
still fetches once per step, and the per-request acceptance table prints.
"""
import json
import os

import _common  # noqa: F401
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.analysis import SyncTally
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.obs import latency_table
from paddle_tpu.serving import FaultInjector, ServingConfig, ServingEngine
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM


def main():
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=211, hidden_size=64, num_layers=2, num_heads=4,
                    max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()

    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 211, (n,)).astype("int32")
               for n in (4, 9, 6, 3, 11, 7, 5, 8)]
    budgets = [8, 12, 6, 15, 7, 10, 9, 5]

    engine = ServingEngine(model, ServingConfig(
        max_batch=3, num_pages=24, page_size=8, max_prompt_len=16))

    # stagger arrivals: half up front, half mid-stream
    rids = [engine.add_request(p, t)
            for p, t in zip(prompts[:4], budgets[:4])]
    for _ in range(4):
        engine.step()
    rids += [engine.add_request(p, t)
             for p, t in zip(prompts[4:], budgets[4:])]
    outputs = engine.run()

    for i, rid in enumerate(rids):
        ref = np.asarray(model.generate(
            Tensor(prompts[i][None]), max_new_tokens=budgets[i])._value)[0]
        assert np.array_equal(ref, outputs[rid]), f"request {i} diverged"
    # prompts span both pad buckets of max_prompt_len=16 ([8, 16]): the
    # bucket set is the only source of prefill compiles, decode traces once
    assert engine.compile_counts == {"prefill": 2, "decode": 1}, \
        engine.compile_counts
    snap = engine.metrics.snapshot()
    assert snap["serving_tokens_total"] == sum(budgets)

    print(f"served {len(rids)} requests, {snap['serving_tokens_total']} "
          f"tokens, {snap['serving_decode_steps']:.0f} decode steps, "
          f"{snap.get('serving_preemptions_total', 0):.0f} preemptions, "
          f"compiles={engine.compile_counts}")

    # ---- observability: per-request latency decomposition + Perfetto trace
    summaries = engine.latency_summaries()
    assert len(summaries) == len(rids)
    assert all(s["state"] == "finished" and s["ttft"] is not None
               and s["tpot"] is not None for s in summaries)
    print(latency_table(summaries))
    snap = engine.metrics.snapshot()
    assert snap["serving_ttft_s_count"] == len(rids)
    assert snap["serving_e2e_s_p99"] >= snap["serving_ttft_s_p50"] > 0
    trace_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              os.pardir, "profiles",
                              "serving_demo_trace.json")
    os.makedirs(os.path.dirname(trace_path), exist_ok=True)
    doc = engine.export_chrome_trace(trace_path)
    with open(trace_path) as f:  # Perfetto-loadable: real JSON, real spans
        loaded = json.load(f)
    assert loaded["traceEvents"] and loaded == json.loads(json.dumps(doc))
    span_names = {ev["name"] for ev in loaded["traceEvents"]
                  if ev["ph"] == "X"}
    assert {"queued", "prefill", "decode"} <= span_names
    print(f"observability: ttft p50/p99 = {snap['serving_ttft_s_p50']:.4f}/"
          f"{snap['serving_ttft_s_p99']:.4f}s, tpot p50 = "
          f"{snap['serving_tpot_s_p50']:.4f}s; chrome trace "
          f"({len(loaded['traceEvents'])} events, one track per request) "
          f"-> {os.path.relpath(trace_path)}")

    # ---- resilience: deadline + cancel + injected stall, swap preemption
    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    # a 3-usable-page pool: the two survivors need 4 pages at peak, so the
    # run MUST swap-preempt one of them and resume it with tokens intact
    inj = FaultInjector().arm("slow_step", step=2, delay_s=60.0)
    eng2 = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=4, page_size=8, max_prompt_len=16,
        max_waiting=4, shed_policy="shed-oldest", preemption_mode="swap"),
        clock=Clock(), fault_injector=inj)
    keep = eng2.add_request(prompts[0], budgets[0])
    dead = eng2.add_request(prompts[1], 8, deadline_s=30.0)  # blown at step 2
    gone = eng2.add_request(prompts[2], 8)
    keep2 = eng2.add_request(prompts[5], 10)
    assert eng2.cancel(gone)
    outs2 = eng2.run(budget_s=600.0)
    assert set(outs2) == {keep, keep2}
    for rid, i, b in ((keep, 0, budgets[0]), (keep2, 5, 10)):
        ref = np.asarray(model.generate(
            Tensor(prompts[i][None]), max_new_tokens=b)._value)[0]
        assert np.array_equal(ref, outs2[rid]), "survivor diverged"
    assert eng2.status(dead) == "expired" and eng2.status(gone) == "cancelled"
    assert eng2.cache.allocator.pages_in_use == 0
    snap2 = eng2.metrics.snapshot()
    assert snap2["serving_swap_outs"] >= 1, "demo pool must force a swap"
    assert snap2["serving_swap_ins"] == snap2["serving_swap_outs"]
    print(f"resilience: survivor parity OK; expired="
          f"{snap2['serving_expired']:.0f} cancelled="
          f"{snap2['serving_cancelled']:.0f} swaps="
          f"{snap2['serving_swap_outs']:.0f} after an injected 60s stall")

    # ---- automatic prefix caching: shared system prompt, tail-only prefill
    system = rng.randint(0, 211, (12,)).astype("int32")  # 1.5 pages of 8
    chat_prompts = [np.concatenate([system,
                                    rng.randint(0, 211, (3,)).astype("int32")])
                    for _ in range(6)]
    # debug_checks: strict CompileGuards + invariant sweep + sync tally at
    # every step boundary — the whole phase runs under the auditor
    eng3 = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=32, page_size=8, max_prompt_len=16,
        debug_checks=True))
    outs3 = {}
    for p in chat_prompts:  # sequential bursts so later ones hit the cache
        rid = eng3.add_request(p, 6)
        outs3[rid] = eng3.run()[rid]
    for rid, p in zip(outs3, chat_prompts):
        ref = np.asarray(model.generate(
            Tensor(p[None]), max_new_tokens=6)._value)[0]
        assert np.array_equal(ref, outs3[rid]), "prefix-cache hit diverged"
    snap3 = eng3.metrics.snapshot()
    assert snap3["serving_prefix_hits"] == len(chat_prompts) - 1
    # each hit reused the system prompt's whole page (8 of its 12 tokens)
    assert snap3["serving_prefix_tokens_saved"] >= 8 * (len(chat_prompts) - 1)
    assert eng3.cache.allocator.pages_in_use == 0
    print(f"prefix cache: {snap3['serving_prefix_hits']:.0f} hits, "
          f"{snap3['serving_prefix_tokens_saved']:.0f} prefill tokens saved "
          f"({snap3['serving_prefill_tokens_total']:.0f} prefilled), "
          f"outputs bit-identical to cold prefill")

    # ---- analysis: certify the decode loop sync-free — the ONLY
    # device->host traffic is one token fetch per step boundary (a decode
    # step's batch fetch or a prefill's first-token fetch)
    rid = eng3.add_request(chat_prompts[0], 6)
    with SyncTally() as tally:
        out4 = eng3.run()[rid]
    assert np.array_equal(out4, outs3[min(outs3)]), "replay diverged"
    snap4 = eng3.metrics.snapshot()
    fetches = int(snap4["serving_decode_steps"] - snap3["serving_decode_steps"]
                  + snap4["serving_prefills_total"]
                  - snap3["serving_prefills_total"])
    assert tally.count == fetches, (tally.events, fetches)
    assert snap4["serving_analysis_retraces_total"] == 0
    assert snap4["serving_analysis_host_syncs_total"] > 0  # debug tally live
    print(f"analysis: decode loop certified sync-free ({tally.count} token "
          f"fetches across {fetches} step boundaries, 0 retraces, compile "
          f"budgets held under debug_checks)")

    # ---- hlocheck: the same audited engine certified at the COMPILED
    # level — every program (each prefill bucket + decode) was AOT-lowered
    # at its first trace and its optimized HLO held to the single-chip
    # budget: zero collective ops, zero host-transfer/callback ops, and
    # XLA aliasing every donated KV pool (a copied donation would be a
    # silent 2x HBM cost)
    audits = eng3.hlo_audits
    assert set(audits) == {"prefill[16]", "prefill[8]", "decode"}, audits
    assert all(not r.collectives and not r.host_transfers
               for r in audits.values())
    assert all(r.aliased_leaves == r.donated_leaves and not r.unaliased
               for r in audits.values())
    assert snap4["serving_hlo_collective_ops"] == 0
    peak = max(r.peak_bytes for r in audits.values())
    print(f"hlocheck: {len(audits)} compiled programs audited — 0 "
          f"collectives, 0 host transfers, "
          f"{sum(r.donated_leaves for r in audits.values())} donated pool "
          f"buffers all aliased; peak step HBM {peak / 1024:.1f} KiB")

    # ---- goodput attribution: every step's wall time splits exactly
    # across its phases, the parts of the decode and prefill phases
    # (upload / dispatch / fetch / emit) ride beside them as sub-spans —
    # the same boundaries that land as serve.* events in a profiler
    # trace; the clean demo fires no watchdog alerts; and the flight
    # recorder bundles it all into one schema-validated black-box dump
    from paddle_tpu.obs import validate_flight_record

    for rec in eng3.timeline.records():
        assert abs(sum(rec.phase_s.values()) - rec.duration) < 1e-9, rec
    fetch_s = sum(rec.span_s.get("decode.fetch", 0.0)
                  for rec in eng3.timeline.records())
    assert fetch_s > 0, "no decode.fetch sub-span recorded"
    assert eng3.alerts() == [] and all(
        v == 0 for k, v in snap4.items()
        if k.startswith("serving_alerts_total")), \
        "watchdog alert fired on the clean demo run"
    flight = validate_flight_record(eng3.flight_record())
    assert flight["alerts"] == [] and flight["steps"][-1]["phase_s"]
    assert set(flight["programs"]) == set(audits)
    print(f"attribution: phase times sum exactly, {fetch_s * 1e3:.2f} ms "
          f"of the decode phases was the token fetch, "
          f"0 watchdog alerts, flight record validated "
          f"({len(flight['steps'])} steps, {len(flight['requests'])} "
          f"request summaries)")

    # ---- chunked prefill + SLO admission: a 40-token whale streams its
    # prompt 8 tokens per step through the SAME prefill program while the
    # 4-token newcomer (enqueued BEHIND it) prefills and decodes — the
    # newcomer's first token no longer queues behind the whale's prefill
    whale = rng.randint(0, 211, (40,)).astype("int32")
    newcomer = rng.randint(0, 211, (4,)).astype("int32")
    eng4 = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=32, page_size=8, max_prompt_len=48,
        chunk_size=8))
    w = eng4.add_request(whale, 6)
    nc = eng4.add_request(newcomer, 6)
    pre4 = eng4.metrics.snapshot()
    with SyncTally() as tally4:
        outs4 = eng4.run()
    for rid, p in ((w, whale), (nc, newcomer)):
        ref = np.asarray(model.generate(
            Tensor(p[None]), max_new_tokens=6)._value)[0]
        assert np.array_equal(ref, outs4[rid]), "chunked output diverged"
    tw, tn = eng4.trace(w), eng4.trace(nc)
    assert tn.first("first_token").t < tw.first("first_token").t, \
        "the newcomer must get its first token while the whale prefills"
    assert tw.summary()["prefill_chunks"] == 5  # ceil(40 / 8)
    # every chunk padded into bucket 8: ONE prefill program for the burst
    assert eng4.compile_counts == {"prefill": 1, "decode": 1}
    # the sync-free certification is UNCHANGED with chunking on: one
    # fetch per decode step + one per COMPLETED prefill (intermediate
    # chunks discard their token undelivered)
    snap5 = eng4.metrics.snapshot()
    fetches4 = int(snap5["serving_decode_steps"]
                   - pre4["serving_decode_steps"]
                   + snap5["serving_prefills_total"]
                   - pre4["serving_prefills_total"])
    assert tally4.count == fetches4, (tally4.events, fetches4)
    print(f"chunked prefill: whale streamed in "
          f"{tw.summary()['prefill_chunks']} chunks "
          f"({snap5['serving_prefill_chunks_total']:.0f} total); newcomer "
          f"first token at t={tn.first('first_token').t - tn.events[0].t:.4f}s "
          f"vs whale prefill_end t="
          f"{tw.first('prefill_end').t - tn.events[0].t:.4f}s — TTFT "
          f"bounded, decode loop still sync-free ({tally4.count} fetches)")

    # the SLO controller on a ticking virtual clock: an unmeetable TTFT
    # target throttles chunk admission to the floor — deterministically —
    # while outputs stay exact and the controller reads only host-side
    # histogram integers (the tally certifies: zero added syncs)
    from paddle_tpu.serving import SLOConfig

    class Tick:
        t = 0.0

        def __call__(self):
            Tick.t += 0.01
            return Tick.t

    eng5 = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=32, page_size=8, max_prompt_len=48,
        chunk_size=8, slo=SLOConfig(ttft_p99_s=1e-6, window_steps=2)),
        clock=Tick())
    w2 = eng5.add_request(whale, 6)
    pre5 = eng5.metrics.snapshot()
    assert pre5["serving_chunk_limit"] == 2  # published at construction
    with SyncTally() as tally5:
        outs5 = eng5.run()
    ref = np.asarray(model.generate(
        Tensor(whale[None]), max_new_tokens=6)._value)[0]
    assert np.array_equal(ref, outs5[w2]), "throttled output diverged"
    snap6 = eng5.metrics.snapshot()
    assert snap6["serving_chunk_limit"] == 1, "every window must breach"
    assert snap6["serving_slo_throttles_total"] >= 1
    fetches5 = int(snap6["serving_decode_steps"]
                   - pre5["serving_decode_steps"]
                   + snap6["serving_prefills_total"]
                   - pre5["serving_prefills_total"])
    assert tally5.count == fetches5, (tally5.events, fetches5)
    print(f"slo admission: unmeetable target throttled chunk_limit "
          f"2 -> {snap6['serving_chunk_limit']:.0f} "
          f"({snap6['serving_slo_throttles_total']:.0f} throttle(s)); "
          f"outputs exact, controller host-side only")

    # ---- tensor-parallel serving: the SAME burst served at TP=2 —
    # Megatron weight shards + heads-sharded paged KV pool via shard_map
    # — bit-identical to single-chip, with every sharded program
    # certified under debug_checks against its declared CollectiveBudget
    # (2 all-reduces per block + 1 for the logits) and the zero-budget
    # variant rejecting the artifact by name
    import jax

    if len(jax.devices()) >= 2:
        from paddle_tpu.analysis.hlocheck import (SINGLE_CHIP,
                                                  CollectiveBudgetError)

        eng7 = ServingEngine(model, ServingConfig(
            max_batch=2, num_pages=32, page_size=8, max_prompt_len=16,
            tensor_parallel=2, debug_checks=True))
        rids7 = [eng7.add_request(p, b)
                 for p, b in zip(prompts[:4], budgets[:4])]
        outs7 = eng7.run()
        for i, rid in enumerate(rids7):
            ref = np.asarray(model.generate(
                Tensor(prompts[i][None]),
                max_new_tokens=budgets[i])._value)[0]
            assert np.array_equal(ref, outs7[rid]), \
                f"TP=2 request {i} diverged from single-chip"
        audits7 = eng7.hlo_audits
        n_ar = 2 * cfg.num_layers + 1
        assert all(r.counts() == {"all-reduce": n_ar}
                   for r in audits7.values()), audits7
        try:
            audits7["decode"].enforce(SINGLE_CHIP)
            raise AssertionError("zero budget must reject a sharded step")
        except CollectiveBudgetError as e:
            assert "all-reduce(" in str(e)  # the instruction line XLA prints
        snap7 = eng7.metrics.snapshot()
        shard = eng7.cache.pools[0]["k_pool"].addressable_shards[0].data
        print(f"tensor parallel: TP=2 outputs bit-identical across "
              f"{len(rids7)} requests; {len(audits7)} sharded programs "
              f"certified at {n_ar} all-reduces/step "
              f"({snap7['serving_tp_collective_bytes_per_token']:.0f} "
              f"collective B/token), zero-budget variant rejected naming "
              f"%all-reduce; KV pool shard per device "
              f"{tuple(shard.shape)} (heads {cfg.num_heads} -> "
              f"{shard.shape[2]})")
    else:
        print("tensor parallel: skipped (1 visible device — run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=2 to see "
              "the TP=2 phase)")

    # ---- speculative decoding: each engine step proposes K=4 candidate
    # tokens per running request (n-gram lookup over the request's own
    # token history, in-jit) and verifies all 5 in ONE batched ragged
    # pass through the paged decode path — outputs bit-identical to
    # plain decode, one compiled verify program, still exactly one host
    # fetch per step
    from paddle_tpu.serving import SpecConfig

    eng8 = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=32, page_size=8, max_prompt_len=16,
        spec=SpecConfig(method="ngram", depth=4)))
    rids8 = [eng8.add_request(p, b)
             for p, b in zip(prompts[:4], budgets[:4])]
    pre8 = eng8.metrics.snapshot()
    with SyncTally() as tally8:
        outs8 = eng8.run()
    for i, rid in enumerate(rids8):
        ref = np.asarray(model.generate(
            Tensor(prompts[i][None]), max_new_tokens=budgets[i])._value)[0]
        assert np.array_equal(ref, outs8[rid]), \
            f"speculative request {i} diverged from plain decode"
    snap8 = eng8.metrics.snapshot()
    assert eng8.compile_counts == \
        {"prefill": 2, "decode": 0, "verify": 1}, eng8.compile_counts
    fetches8 = int(snap8["serving_decode_steps"]
                   - pre8["serving_decode_steps"]
                   + snap8["serving_prefills_total"]
                   - pre8["serving_prefills_total"])
    assert tally8.count == fetches8, (tally8.events, fetches8)
    print(f"speculative decoding: K=4, outputs bit-identical across "
          f"{len(rids8)} requests, one verify program, sync-free "
          f"({tally8.count} fetches); acceptance table:")
    for rid in rids8:
        evs = [e for e in eng8.trace(rid).events
               if e.name == "spec_verify"]
        prop = sum(e.arg("proposed") for e in evs)
        acc = sum(e.arg("accepted") for e in evs)
        print(f"  request {rid}: {len(evs)} verify steps, "
              f"{acc}/{prop} candidates accepted "
              f"({acc / max(1, prop):.0%})")
    print(f"  engine acceptance rate "
          f"{snap8['serving_spec_acceptance_rate']:.2%}, "
          f"{snap8['serving_spec_accepted_tokens_total']:.0f} decode "
          f"steps saved over {snap8['serving_decode_steps']:.0f} verify "
          f"steps")

    # ---- per-tenant SLO observability: an interactive + batch mix on
    # one engine, every retirement classified by the goodput ledger,
    # every request accruing a wire-exportable journey — with the
    # SyncTally certification formula pinned byte-identical with the
    # whole tenant layer (tenants + journeys + slo_burn watchdog) ON
    from paddle_tpu.obs import (tenant_table, validate_flight_record,
                                validate_journey)
    from paddle_tpu.serving import TenantSLO

    eng9 = ServingEngine(model, ServingConfig(
        max_batch=2, num_pages=32, page_size=8, max_prompt_len=16,
        tenants={"interactive": TenantSLO(ttft_p99_s=300.0,
                                          tpot_p99_s=300.0),
                 "batch": TenantSLO(ttft_p99_s=600.0,
                                    tpot_p99_s=600.0)}))
    rids9 = [eng9.add_request(p, b,
                              tenant="interactive" if i % 2 else "batch")
             for i, (p, b) in enumerate(zip(prompts[:4], budgets[:4]))]
    with SyncTally() as tally9:
        outs9 = eng9.run()
    for i, rid in enumerate(rids9):
        assert np.array_equal(outs8[rids8[i]], outs9[rid]), \
            "tenant labels must not change served outputs"
    snap9 = eng9.metrics.snapshot()
    fetches9 = int(snap9["serving_decode_steps"]
                   + snap9["serving_prefills_total"])
    assert tally9.count == fetches9, (tally9.events, fetches9)
    assert eng9.alerts() == [], eng9.alerts()
    report = eng9.tenant_report()
    ledger_tokens = sum(sum(e["tokens"].values()) for e in report.values())
    assert ledger_tokens == int(snap9["serving_tokens_total"]), \
        "ledger tokens must reconcile with the engine total"
    for rid in rids9:
        w = validate_journey(eng9.journey(rid).to_wire())
        assert w["state"] == "finished" and w["ttft_s"] is not None
    rec9 = validate_flight_record(eng9.flight_record())
    assert rec9["tenants"] and len(rec9["journeys"]) == len(rids9)
    print(f"tenants & journeys: {len(rids9)} requests across 2 SLO "
          f"classes, ledger reconciles ({ledger_tokens} tokens), "
          f"{len(rec9['journeys'])} wire journeys validated, 0 alerts, "
          f"sync-free ({tally9.count} fetches)")
    print(tenant_table(report))
    print("serving_demo OK")


if __name__ == "__main__":
    main()
