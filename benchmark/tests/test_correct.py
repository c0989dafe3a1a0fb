"""``correct`` has to be able to fail. At a size the CPU holds, through
``run.execute`` (everything of a run after the look for a chip) and the
limits the cells are held to:

- the control (the reference in the precision below the configuration's,
  put in the program's place) comes out not correct;
- the timed path broken underneath comes out not correct, once for each
  fault the cell can have: a step that returns its state unchanged; half of
  the batch left out, the mean taken over the rest; a token altered where
  it is produced. (No cell here spans chips, so no exchange to leave out.)
- and the unbroken path comes out correct.
"""
import pytest
import tiny

TRAIN = "gpt3-350m-train.b8-s1024"
SHARED = "gpt3-1.3b-serve.sessions-shared"
UNSHARED = "gpt3-1.3b-serve.batch-unshared"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def limits(workload):
    return tiny.load("limits", workload + ".json")["limits"]


def execute(workload, seed=3, seconds=1.0, control=False):
    import run as runpy

    # the shared mix is kept for a later cell (PERF.md section 7): it runs
    # here, on the CPU, under the unshared cell's limits
    run, bench = tiny.tiny_run(workload, seed=seed, seconds=seconds,
                               limits_of=UNSHARED if workload == SHARED
                               else "")
    run.control = control
    return runpy.execute(run, bench, DEVICE)


@pytest.fixture(autouse=True)
def _path(monkeypatch):
    monkeypatch.syspath_prepend(tiny.BENCH)


@pytest.mark.parametrize("workload", [TRAIN, SHARED, UNSHARED])
def test_sound_run_is_correct(workload):
    res = execute(workload)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"
    # (the kept mix is no cell yet: BENCHMARK.json gives it setup_s alone)
    assert "setup_s" in res["metrics"]
    assert len(res["metrics"]) >= 2 or workload == SHARED


def _break_step(monkeypatch, how):
    """Wrap what ``build_hybrid_step`` returns, underneath the driver."""
    from paddle_tpu.distributed.fleet import hybrid_train

    real = hybrid_train.build_hybrid_step

    def broken(*a, **k):
        init_fn, step, shard_batch, aux = real(*a, **dict(k, donate=False))

        class Compiled:
            def __call__(self, state, key, lr, batch, labels):
                if how == "state_unchanged":
                    loss, _ = step(state, key, lr, batch, labels)
                    return loss, state
                half = batch[0].shape[0] // 2
                return step(state, key, lr,
                            tuple(x[:half] for x in batch), labels)

            def as_text(self):
                return ""

        class Step:
            def lower(self, *a, **k):
                return self

            def compile(self):
                return Compiled()

        return init_fn, Step(), shard_batch, aux

    monkeypatch.setattr(hybrid_train, "build_hybrid_step", broken)


@pytest.mark.parametrize("how", ["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(monkeypatch, how):
    tiny.register_presets()
    _break_step(monkeypatch, how)
    res = execute(TRAIN)
    assert res["correct"] is False, res["compared"]
    if how == "state_unchanged":
        c = res["compared"]["change_norm_gap"]
        assert c["value"] == pytest.approx(1.0) and c["value"] > c["limit"]


@pytest.mark.parametrize("workload", [SHARED, UNSHARED])
def test_altered_token_is_not_correct(monkeypatch, workload):
    tiny.register_presets()
    from benchmark.lib import serve

    real = serve.build

    def build(run):
        engine = real(run)
        decode, vocab = engine._decode_jit, run.config["model"]["vocab_size"]

        def altered(*a, **k):
            pools, toks = decode(*a, **k)
            return pools, (toks + 1) % vocab

        engine._decode_jit = altered
        return engine

    monkeypatch.setattr(serve, "build", build)
    res = execute(workload)
    assert res["correct"] is False, res["compared"]


def test_train_controls_through_the_harness_are_not_correct(capfd):
    """``--control 1``: after the reference, the driver puts each control
    of the configuration (float8 operands; bfloat16 optimizer state) and
    half a batch in the program's place and judges it as ``run.execute``
    judges the run: each comes out not correct, beside a run that is."""
    import json

    res = execute(TRAIN, control=True)
    assert res["correct"] is True, res["compared"]
    judged = {}
    for line in capfd.readouterr().out.splitlines():
        head, _, body = line.partition(": ")
        if head.startswith("[bench] train.control_") \
                or head == "[bench] train.fault_half_batch":
            judged[head.split("train.")[1]] = json.loads(body)
    assert set(judged) == {"control_fp8", "control_bf16_master",
                           "fault_half_batch"}
    for name, j in judged.items():
        assert j["correct"] is False, (name, j["compared"])
        assert any(r["value"] > r["limit"] for r in j["compared"].values())


def test_control_bf16_master_training_is_not_correct():
    """The reference with the optimizer's float32 state kept in bfloat16,
    in the program's place."""
    import numpy as np

    from benchmark.families import gpt3
    from benchmark.lib import check, reference, traffic, weights

    run, _ = tiny.tiny_run(TRAIN, seed=5)
    m, o = run.config["model"], run.config["optimizer"]
    assert run.config["precision"]["controls"][0] == "bf16_master"
    batches = [traffic.train_batch(run.mix, m["vocab_size"], run.seed, i)
               for i in range(3)]
    table = gpt3.leaf_table(m)
    ref = reference.follow_training(
        gpt3, weights.make_weights(table, run.seed), batches, m, o)
    ctl = reference.follow_training(
        gpt3, weights.make_weights(table, run.seed), batches, m, o,
        policy="bf16_master")
    numbers, _ = check.train_numbers(ctl, ref)
    ok, rows = check.judge(numbers, limits(TRAIN))
    assert not ok, rows
    same, _ = check.train_numbers(ref, ref)
    assert check.judge(same, limits(TRAIN))[0]
    assert np.isfinite(list(numbers.values())).all()


def test_control_bf16_serving_is_not_correct():
    """The token that bfloat16 puts first, read against the float32
    reference at each position of the same sequences."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import gpt3
    from benchmark.lib import check, reference, weights

    run, _ = tiny.tiny_run(UNSHARED, seed=5)
    m = run.config["model"]
    assert run.config["precision"]["controls"] == ["bf16"]
    # two layers round far less than twenty-four, and their logits are a
    # third as wide as the real model's: matrices five times larger bring
    # the logits' spread (1.6) near the real model's, so that the limit set
    # on the chip means something here. (Through the harness at this size
    # the bf16 control reads under the limit; at the cell's own size
    # ``--control 1`` judges it on the chip: PERF.md section 6.)
    p = {n: v * 5 if v.ndim == 2 else v
         for n, v in weights.make_weights(gpt3.leaf_table(m),
                                          run.seed).items()}

    def token_gaps(tokens):
        """``below_best`` under the reference's own logits."""
        return reference.below_best(
            gpt3.logits_at(lambda: p, ids, pos, m), tokens)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, m["vocab_size"], (6, 96)), jnp.int32)
    pos = jnp.tile(jnp.arange(40, 90), (6, 1))
    low = reference.first_tokens(gpt3.logits_at, lambda: p, ids, pos, m,
                                 "bf16")
    gaps = np.asarray(token_gaps(low))
    best = reference.first_tokens(gpt3.logits_at, lambda: p, ids, pos, m,
                                  "f32")
    assert float(jnp.max(token_gaps(best))) == 0.0
    ok, rows = check.judge(check.gap_numbers(gaps), limits(UNSHARED))
    assert not ok, rows
