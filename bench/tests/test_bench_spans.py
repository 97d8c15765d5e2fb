"""The host spans' reduction (``bench/spans.py``) on constructed traces and
on a traced run of the harness, and the readers of the span metrics."""
from __future__ import annotations

import math

import pytest

from bench import run as R
from bench import spans as bspans
from bench import trace as btrace
from bench.tests import tiny

S = 1e9                                        # ns per second


def _span(line, name, start, end):
    return bspans.Span(line, bspans.span_name(name), start * S,
                       (end - start) * S)


def _op(start, end, line=btrace.OPS_LINE, name="%fusion.1 fusion f32[8]"):
    return btrace.Event("/device:TPU:0", line, name, start * S,
                        (end - start) * S)


# the batcher worker on line 0, another thread on line 1
SPANS = [
    _span(0, "serve.wait", 0.0, 0.9),
    _span(0, "serve.form", 0.9, 1.0),
    _span(0, "serve.group#group=3,size=2,first_seq=7#", 1.0, 3.0),
    _span(0, "store.hop#group=3,hop=0#", 1.0, 1.5),
    _span(0, "store.plan#group=3,hop=0#", 1.1, 1.2),
    _span(0, "engine.run#group=3#", 2.0, 2.8),
    _span(0, "serve.wait", 3.2, 4.0),
    _span(1, "serve.reply", 1.6, 1.9),
    _span(1, "serve.wait", 2.0, 2.1),
]
DEVICE = [
    _op(2.2, 2.4), _op(2.3, 2.6),
    _op(2.1, 2.7, btrace.MODULES_LINE, "jit__program(3)"),
    _op(2.5, 2.8005, btrace.MODULES_LINE, "jit__program(3)"),
    _op(3.05, 3.1, btrace.MODULES_LINE, "jit__program(3)"),
    _op(3.05, 3.1, btrace.MODULES_LINE, "jit_other"),
]


def test_annotation_arguments_are_stripped_from_the_name():
    assert bspans.span_name("store.hop#group=3,hop=0#") == "store.hop"
    assert bspans.span_name("serve.wait") == "serve.wait"
    assert [s.name for s in SPANS][:4] == ["serve.wait", "serve.form",
                                           "serve.group", "store.hop"]


def test_idle_time_goes_to_the_innermost_span_of_the_worker():
    assert bspans.worker_line(SPANS) == 0
    got = bspans.attribute(SPANS, DEVICE, window=(0.0, 4.0 * S))
    # idle: [0, 2.2] and [2.6, 4.0]
    assert got["idle_s"] == pytest.approx(3.6)
    assert got["window_s"] == pytest.approx(4.0)
    want = {"serve.wait": 1.7, "serve.group": 0.7, "store.hop": 0.4,
            "store.plan": 0.1, "engine.run": 0.4, "serve.form": 0.1,
            "unattributed": 0.2}
    assert got["by_span"] == pytest.approx(want)
    assert list(got["by_span"])[0] == "serve.wait"         # largest first
    assert got["by_root"] == pytest.approx(
        {"serve.wait": 1.7, "serve.group": 1.6, "serve.form": 0.1,
         "unattributed": 0.2})
    # the window defaults to the first and last event
    assert bspans.attribute(SPANS, DEVICE)["window_s"] == pytest.approx(4.0)


def test_a_trace_without_the_worker_attributes_nothing():
    assert bspans.attribute([_span(0, "engine.run", 0.0, 1.0),
                             _span(0, "serve.wait", 1.0, 2.0)],
                            DEVICE) is None
    assert bspans.worker_line([]) is None


def test_an_idle_device_leaves_the_whole_window_to_the_spans():
    got = bspans.attribute(SPANS, [], window=(0.0, 4.0 * S))
    assert got["idle_s"] == pytest.approx(4.0)
    assert sum(got["by_span"].values()) == pytest.approx(4.0)
    assert got["by_span"]["unattributed"] == pytest.approx(0.2)


def test_self_time_leaves_out_the_children():
    got = bspans.self_times(SPANS)
    assert got["serve.group"]["n"] == 1
    assert got["serve.group"]["s"] == pytest.approx(2.0)
    assert got["serve.group"]["self_s"] == pytest.approx(0.7)
    assert got["store.hop"]["self_s"] == pytest.approx(0.4)
    assert got["serve.wait"] == {"n": 2, "s": pytest.approx(1.7),
                                 "self_s": pytest.approx(1.7)}
    assert "serve.reply" not in got                  # another thread


def test_a_child_that_outlasts_its_parent_is_cut_to_it():
    spans = [_span(0, "serve.form", 0.0, 0.1),
             _span(0, "serve.group", 1.0, 2.0),
             _span(0, "engine.run", 1.5, 2.5)]
    got = bspans.self_times(spans)
    assert got["engine.run"]["self_s"] == pytest.approx(0.5)
    assert got["serve.group"]["self_s"] == pytest.approx(0.5)


def test_programs_are_counted_inside_the_engine_span_within_the_slack():
    # the second program ends 0.5 ms after the span; the third lies
    # 250 ms after it; jit_other is not the engine's program
    assert bspans.programs_inside(SPANS, DEVICE) == (2, 3)
    assert bspans.programs_inside(SPANS, DEVICE, slack_ns=0.0) == (1, 3)
    assert bspans.programs_inside([], DEVICE) == (0, 3)


def test_program_offsets_say_which_end_sticks_out_and_by_how_much():
    got = bspans.program_offsets(SPANS, DEVICE)
    assert [t for t, _ in got] == pytest.approx([0.0, 0.4, 0.95])
    assert [o for _, o in got] == pytest.approx([0.0, 0.0005 * S, 0.3 * S])
    early = [_op(1.9985, 2.1, btrace.MODULES_LINE, "jit__program(1)")]
    assert bspans.program_offsets(SPANS, early) == [
        (0.0, pytest.approx(-0.0015 * S))]
    assert bspans.programs_inside(SPANS, early) == (0, 1)
    assert bspans.program_offsets([], early) == [(0.0, float("inf"))]


# ---------------------------------------------------------------- readers
def _ctx(before, after):
    return {"before": {"spans": before}, "after": {"spans": after}}


BEFORE = {"serve.group": {"n": 10, "s": 50.0},
          "serve.queue_wait": {"n": 40, "s": 4.0},
          "store.hop": {"n": 30, "s": 30.0},
          "store.embed": {"n": 10, "s": 2.0},
          "serve.pad": {"n": 10, "s": 1.0},
          "serve.h2d": {"n": 10, "s": 0.5},
          "engine.run": {"n": 50, "s": 9.0},
          "serve.rows_real": {"n": 1000, "s": 0.0},
          "serve.rows_padded": {"n": 1500, "s": 0.0}}
AFTER = {"serve.group": {"n": 14, "s": 58.0},
         "serve.queue_wait": {"n": 104, "s": 10.4},
         "store.hop": {"n": 42, "s": 37.2},
         "store.embed": {"n": 14, "s": 2.4},
         "serve.pad": {"n": 14, "s": 1.2},
         "serve.h2d": {"n": 14, "s": 0.7},
         "engine.run": {"n": 54, "s": 9.2},
         "serve.rows_real": {"n": 3000, "s": 0.0},
         "serve.rows_padded": {"n": 4100, "s": 0.0}}
READINGS = {"queue_wait_ms": 100.0, "sample_ms": 1800.0,
            "embed_ms": 100.0, "feed_ms": 100.0, "engine_ms": 50.0,
            "pad_ratio": 1.3}


@pytest.mark.parametrize("metric", sorted(READINGS))
@pytest.mark.parametrize("cell", ["online", "closed"])
def test_span_readers_read_changes_across_the_window(metric, cell):
    reader = R.Bench(tiny.REPO).reader(f"{metric}.{cell}")
    assert reader.read(_ctx(BEFORE, AFTER)) == \
        pytest.approx(READINGS[metric])
    # a program without the spans block: nothing, and no error
    assert reader.read({"before": {}, "after": {}}) is None
    # a window in which nothing ran
    assert reader.read(_ctx(BEFORE, BEFORE)) is None


def test_a_span_first_seen_in_the_window_counts_from_zero():
    before = {k: v for k, v in BEFORE.items() if k != "store.embed"}
    reader = R.Bench(tiny.REPO).reader("embed_ms.online")
    assert reader.read(_ctx(before, AFTER)) == pytest.approx(600.0)


# ---------------------------------------------------------------- traced
@pytest.fixture
def quick(monkeypatch):
    monkeypatch.setattr(R, "WARM_S", 0.5)
    monkeypatch.setattr(R, "SHAPE_REQUESTS", 12)
    monkeypatch.setattr(R, "SHAPE_GROUPS", 20)


def test_a_traced_run_reports_the_span_metrics_and_its_spans(tmp_path,
                                                              quick):
    root = tiny.make_root(tmp_path)
    res = R.run(tiny.args("tiny.closed", trace=1), root=root,
                require_chip=False, use_cache=False)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    for metric in READINGS:
        v = m[f"{metric}.closed"]["value"]
        assert math.isfinite(v) and v > 0, metric
    assert m["pad_ratio.closed"]["value"] >= 1.0
    assert m["group_size.closed"]["value"] >= 1.0
    spans, device = bspans.load(str(root / "bench" / "out" / "trace"))
    line = bspans.worker_line(spans)
    assert line is not None
    names = {s.name for s in spans if s.line == line}
    assert {"serve.form", "serve.group", "store.hop",
            "store.plan", "store.read", "store.select", "store.embed",
            "serve.pad", "serve.h2d", "engine.run", "serve.slice",
            "serve.reply"} <= names
    times = bspans.self_times(spans)
    assert times["store.hop"]["n"] == 2 * times["serve.group"]["n"], times
    assert times["serve.group"]["self_s"] < times["serve.group"]["s"]
    # the CPU has no device plane: the whole window is idle.  Between
    # spans the worker drains the submission queues; on a loaded CPU its
    # wait for the interpreter lock there is unattributed, so the bound
    # is loose here (the chip's readings are in PERF.md)
    assert device == []
    idle = bspans.attribute(spans, device)
    assert sum(idle["by_span"].values()) == pytest.approx(idle["idle_s"])
    assert sum(idle["by_root"].values()) == pytest.approx(idle["idle_s"])
    assert set(idle["by_root"]) <= {"serve.group", "serve.form",
                                    "serve.wait", "unattributed"}
    assert idle["by_root"]["serve.group"] > 0
    assert idle["by_span"]["unattributed"] < 0.5 * idle["idle_s"], idle
