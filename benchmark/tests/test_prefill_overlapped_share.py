"""``prefill_overlapped_share`` (PR 36): the metric file loads, agrees with
its entry in ``BENCHMARK.json``, reads one engine counter over another
through the reader the benchmark has, and stays silent on a program that
lacks the counter (the parent of the PR that brought it)."""
import pytest

import tiny

NAME = "prefill_overlapped_share"
COUNTER = "serving_prefill_overlapped_total"
OVER = "serving_prefills_total"
SERVING_CELLS = ["gpt3-1.3b-serve.batch-unshared",
                 "kimi-k2-ep32-serve.code-batch-256",
                 "granite-4.0-h-micro-serve.chat-batch-64",
                 "mellum2-12b-a2.5b-serve.code-context-48"]


class _Engine:
    """What ``serve.counters`` asks of an engine: a snapshot."""

    def __init__(self, snap):
        self.metrics = self
        self._snap = snap

    def snapshot(self):
        return dict(self._snap)


def _window(before: dict, after: dict, names) -> dict:
    """The counters of a window, as ``serve.measure`` hands them to the
    readers: the difference of two snapshots of the names a metric file
    of the cell reads."""
    from benchmark.lib import serve

    c0 = serve.counters(_Engine(before), names)
    c1 = serve.counters(_Engine(after), names)
    return {k: v - c0.get(k, 0.0) for k, v in c1.items()}


def test_file_and_entry_agree():
    from benchmark.lib import reduce

    spec = tiny.load("metrics", NAME + ".json")
    entry = next(m for m in tiny.bench_json()["per_layer"]
                 if m["name"] == NAME)
    assert spec["reader"] == "counter_share" and \
        spec["reader"] in reduce.READERS
    assert spec["args"] == {"counter": COUNTER, "over": OVER}
    assert (entry["layer"], entry["unit"], entry["moves"],
            entry["source"], entry["better"]) == (
        spec["layer"], spec["unit"], spec["moves"], spec["source"],
        "higher") == ("engine step", "%", "itl_p95_ms", "program_counter",
                      "higher")
    assert entry["workloads"] == SERVING_CELLS


@pytest.mark.parametrize("program,value", [
    # the change: every prefill of the window but one that a drain caught
    ({COUNTER: (3.0, 122.0), OVER: (4.0, 124.0)}, 100.0 * 119 / 120),
    # a program without the counter (the parent): silent, no error
    ({OVER: (4.0, 124.0)}, None),
    # no prefill completed in the window: nothing to divide by
    ({COUNTER: (3.0, 3.0), OVER: (4.0, 4.0)}, None),
])
def test_reads_the_share_and_is_silent_without_the_counter(program, value):
    from benchmark.lib import reduce

    spec = tiny.load("metrics", NAME + ".json")
    base = {"serving_prefix_tokens_saved": 0.0,
            "serving_preemptions_total": 0.0}
    before = dict(base, **{k: v[0] for k, v in program.items()})
    after = dict(base, **{k: v[1] for k, v in program.items()})
    counted = _window(before, after, frozenset(spec["args"].values()))
    got = reduce.READERS[spec["reader"]](
        None, {"counters": counted}, spec["args"], {})
    assert got == (None if value is None else pytest.approx(value))


def test_the_engine_has_the_counter_seeded():
    """The program's side of the name: present at 0 in a snapshot taken
    before the first prefill, so a window in which every prefill was
    caught by a drain reads 0 and not silence."""
    from paddle_tpu.serving.metrics import ServingMetrics

    snap = ServingMetrics().snapshot()
    assert COUNTER in snap and OVER in snap
