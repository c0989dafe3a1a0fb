"""The ``train`` driver: the compiled step of ``build_hybrid_step`` on a
one-device mesh (a copy of ``chip_smoke.build_train`` as PR 21 ran it)
over the model that the configuration's family builds with the benchmark's
weights, driven through its first checked steps in set-up and then through
the window by the same call and feed."""
from __future__ import annotations

import collections
import time

import numpy as np

from . import check, reference, traffic, weights
from .common import Run, TraceWindow, family_of, peak_bytes, release, say

#: the steps that set-up drives and the reference follows
CHECKED_STEPS = 3
#: rows of a batch to a block of the reference, so that one block's
#: activations fit beside the weights, the gradient and both moments
REFERENCE_ROWS = 1


def hybrid_step(run: Run, model):
    """(step, state, feed) over a family's model that holds the benchmark's
    weights: the jitted step, its state, and the feed that puts a host
    batch on the device. The step's state aliases the model's weights (and
    donates them), and the fp32 master starts as their exact copy."""
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.hybrid_train import build_hybrid_step

    cfg, tr = run.config, run.config["train"]
    if list(tr["mesh"].values()) != [1]:
        raise SystemExit("the train driver runs a one-device mesh")
    mesh = Mesh(np.array(jax.devices()[:1]), tuple(tr["mesh"]))
    model.to(dtype=cfg["precision"]["parameters"])
    o = cfg["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters(),
        multi_precision=o["multi_precision"])
    init_fn, step, shard_batch, _aux = build_hybrid_step(
        model, opt, lambda loss: loss, mesh, with_aux=True)
    state = init_fn()

    def feed(step_index: int):
        ids, labels = traffic.train_batch(run.mix, cfg["model"]["vocab_size"],
                                          run.seed, step_index)
        return tuple(shard_batch([ids, labels]))

    return step, state, feed


def start_f32(run: Run, family) -> dict:
    """The weights the run started from, as the reference takes them (what
    the program's fp32 master starts as). A following consumes them."""
    return weights.for_reference(family, run.config, run.seed)()


def drive(run: Run) -> dict:
    import jax

    family, o, mix = family_of(run.config), run.config["optimizer"], run.mix
    tokens_per_step = mix["batch"] * mix["seq"]
    t0 = time.perf_counter()
    step, state, feed = family.build_training(
        run, weights.for_program(family, run.config, run.seed))
    say("train.built", seconds=round(time.perf_counter() - t0, 2),
        since_process_start=round(time.time() - run.t_process, 2))
    key = jax.random.key(0)                      # dropout is 0: unused
    lr = np.float32(o["learning_rate"])
    t0 = time.perf_counter()
    compiled = step.lower(state, key, lr, feed(0), ()).compile()
    say("train.compiled", seconds=round(time.perf_counter() - t0, 2),
        flash_custom_calls=compiled.as_text().count("tpu_custom_call"))

    # ---- the first steps, through the window's own call and feed
    checked = CHECKED_STEPS
    prog = {"losses": []}
    for k in range(checked):
        loss, state = compiled(state, key, lr, feed(k), ())
        prog["losses"].append(float(np.asarray(loss)))
        if k == 0:
            moment = {n: s["moment1"]
                      for n, s in state["opt"]["slots"].items()}
            prog["grad_norm"] = {
                n: float(x) / (1.0 - o["beta1"])
                for n, x in
                reference.leaf_norms(moment, family.parts).items()}
            del moment
    # between two steps the chip has room for the weights the run started
    # from: made again from the seed, compared, dropped
    master = {n: s["master_weight"] for n, s in state["opt"]["slots"].items()}
    start = weights.for_program(family, run.config, run.seed)
    prog["change_norm"] = {
        n: float(x) for n, x in
        reference.delta_norms(master, start, family.parts).items()}
    del master, start
    say("train.checked_steps", losses=prog["losses"])

    # ---- the window: steps dispatched back to back, each loss fetched
    # ``steps_in_flight`` steps behind and checked finite
    depth = mix["steps_in_flight"]
    tw = TraceWindow(run)
    k, pending, steps, ticks = checked, collections.deque(), 0, []
    run.facts["setup_s"] = time.time() - run.t_process
    w0 = time.perf_counter()
    while True:
        now = time.perf_counter() - w0
        ticks.append(now)
        if tw.due(now):
            jax.block_until_ready(pending[-1])
            tw.open(now)
        batch = feed(k)
        with jax.profiler.TraceAnnotation("bench.step"):
            loss, state = compiled(state, key, lr, batch, ())
        pending.append(loss)
        if len(pending) > depth:
            with jax.profiler.TraceAnnotation("bench.fetch_loss"):
                if not np.isfinite(float(np.asarray(pending.popleft()))):
                    raise SystemExit(f"a loss before step {k} is not finite")
        k, steps = k + 1, steps + 1
        if time.perf_counter() - w0 >= run.seconds:
            break
    tail = [float(np.asarray(x)) for x in pending]
    last = tail[-1]
    jax.block_until_ready(state)
    window_s = time.perf_counter() - w0
    tw.close()
    if not np.all(np.isfinite(tail)):
        raise SystemExit("a loss at the end of the window is not finite")
    # where the host's loop waited longest: a stall of the host shows as
    # one long turn and then short ones (the queue catching up)
    turns = np.diff(ticks)
    slow = np.argsort(turns)[::-1][:4]
    cache_size = step._cache_size() if hasattr(step, "_cache_size") else None
    say("train.window", steps=steps, window_s=window_s, last_loss=last,
        jit_cache_size=cache_size, compiles_in_window=0,
        steps_in_flight=depth, median_turn_s=float(np.median(turns)),
        longest_turns={int(i): round(float(turns[i]), 4) for i in slow},
        turns_under_half_median=int((turns < np.median(turns) / 2).sum()))
    run.facts.update(
        memory_peak_bytes=peak_bytes(), window_s=window_s,
        attempted=steps, failed=0,
        # the steps of the traced window are counted from the trace: with
        # steps in flight the host dispatches other steps than the device
        # runs
        traced={"batch": mix["batch"], "seq": mix["seq"]},
        trace=tw,
        end_to_end={"train_tokens_per_s": steps * tokens_per_step
                    / window_s})

    # ---- the reference, once the program's state is gone
    del state, compiled, step, pending, loss, tail
    release()
    t0 = time.perf_counter()
    p0 = start_f32(run, family)
    batches = [traffic.train_batch(mix, run.config["model"]["vocab_size"],
                                   run.seed, i) for i in range(checked)]
    ref = reference.follow_training(family, p0, batches,
                                    run.config["model"], o,
                                    rows=REFERENCE_ROWS)
    say("train.reference", seconds=round(time.perf_counter() - t0, 2),
        losses=ref["losses"])
    numbers, notes = check.train_numbers(prog, ref)
    say("train.compared", **notes)
    if run.control:
        # the reference in the program's place, a precision lower; and the
        # reference with half of every batch left out: each judged as a
        # run is, and has to come out not correct
        for label, kw in (
                *(("control_" + c, {"policy": c})
                  for c in run.config["precision"]["controls"]),
                ("fault_half_batch",
                 {"keep_rows": slice(0, mix["batch"] // 2)})):
            p0 = start_f32(run, family)
            got = reference.follow_training(
                family, p0, batches, run.config["model"], o,
                rows=REFERENCE_ROWS, **kw)
            n, at = check.train_numbers(got, ref)
            ok, rows = check.judge(n, run.check["limits"])
            say("train." + label, correct=ok, compared=rows,
                losses=got["losses"], **at)
    return numbers
