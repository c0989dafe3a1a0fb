"""``engine_host_work_share`` and ``step_unstalled_share`` (PR 37): each
metric file loads, agrees with its entry in ``BENCHMARK.json``, names
counters that an engine with tracing on seeds, reads one of the engine's
seconds counters over another through the reader the benchmark has, over
the differences of a window, and stays silent on a program that lacks the
counters (the parent of the PR that brought them)."""
import pytest

import tiny
from test_prefill_overlapped_share import SERVING_CELLS, _window

OVER = "serving_step_seconds_total"
METRICS = {
    "engine_host_work_share": ("serving_step_host_seconds_total", "lower"),
    "step_unstalled_share": ("serving_step_unstalled_seconds_total",
                             "higher"),
}


@pytest.mark.parametrize("name", list(METRICS))
def test_file_and_entry_agree(name):
    from benchmark.lib import reduce

    counter, better = METRICS[name]
    spec = tiny.load("metrics", name + ".json")
    entry = next(m for m in tiny.bench_json()["per_layer"]
                 if m["name"] == name)
    assert spec["reader"] == "counter_share" and \
        spec["reader"] in reduce.READERS
    assert spec["args"] == {"counter": counter, "over": OVER}
    assert (entry["layer"], entry["unit"], entry["moves"],
            entry["source"], entry["better"]) == (
        spec["layer"], spec["unit"], spec["moves"], spec["source"],
        better) == ("engine step", "%", "serve_out_tokens_per_s",
                    "program_counter", better)
    assert entry["workloads"] == SERVING_CELLS
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_the_two_entries_are_the_last_of_the_list():
    # this PR may only add: what the benchmark had stands before them
    names = [m["name"] for m in tiny.bench_json()["per_layer"]]
    assert names[-2:] == list(METRICS) and len(set(names)) == len(names)


@pytest.mark.parametrize("name,program,value", [
    # cell 3 by the issue's reading: 51 s of steps, 36 s of them in a
    # fetch
    ("engine_host_work_share",
     {"serving_step_host_seconds_total": (1.25, 16.25),
      OVER: (4.0, 55.0)}, 100.0 * 15.0 / 51.0),
    # a calm window, one with a 150 ms stall, one with a 3.4 s one
    ("step_unstalled_share",
     {"serving_step_unstalled_seconds_total": (4.0, 55.0),
      OVER: (4.0, 55.0)}, 100.0),
    ("step_unstalled_share",
     {"serving_step_unstalled_seconds_total": (4.0, 54.85),
      OVER: (4.0, 55.0)}, 100.0 * 50.85 / 51.0),
    ("step_unstalled_share",
     {"serving_step_unstalled_seconds_total": (4.0, 51.6),
      OVER: (4.0, 55.0)}, 100.0 * 47.6 / 51.0),
    # a program without the counters (the parent): silent, no error
    ("engine_host_work_share", {}, None),
    ("step_unstalled_share", {}, None),
    # ... or with the total alone
    ("engine_host_work_share", {OVER: (4.0, 55.0)}, None),
    # no step in the window: nothing to divide by
    ("step_unstalled_share",
     {"serving_step_unstalled_seconds_total": (4.0, 4.0),
      OVER: (4.0, 4.0)}, None),
])
def test_reads_the_share_of_a_window_and_is_silent_without(name, program,
                                                           value):
    from benchmark.lib import reduce

    spec = tiny.load("metrics", name + ".json")
    base = {"serving_prefix_tokens_saved": 0.0,
            "serving_preemptions_total": 0.0}
    before = dict(base, **{k: v[0] for k, v in program.items()})
    after = dict(base, **{k: v[1] for k, v in program.items()})
    counted = _window(before, after, frozenset(spec["args"].values()))
    got = reduce.READERS[spec["reader"]](
        None, {"counters": counted}, spec["args"], {})
    assert got == (None if value is None else pytest.approx(value))


def test_an_engine_with_tracing_seeds_the_counters_and_one_without_none():
    """The program's side of the names: at 0 in a snapshot taken before
    the first step of an engine with tracing on (``seed_step_seconds``,
    which the engine calls at construction), and absent otherwise, so a
    program run with ``enable_tracing=False`` reads silence and not 0."""
    from paddle_tpu.obs import HELD_BY, SPANS
    from paddle_tpu.serving.metrics import ServingMetrics

    names = {OVER} | {c for c, _ in METRICS.values()}
    metrics = ServingMetrics()
    assert not names & set(metrics.snapshot())
    metrics.seed_step_seconds(SPANS, HELD_BY)
    snap = metrics.snapshot()
    assert all(snap[n] == 0 for n in names)
    metrics.on_step_seconds(0.5, {"decode.fetch": 0.3, "account": 0.01},
                            0.125)
    snap = metrics.snapshot()
    assert (snap[OVER], snap["serving_step_host_seconds_total"],
            snap["serving_step_unstalled_seconds_total"]) == (0.5, 0.2,
                                                               0.375)
