"""Generator, traffic, trace reduction and operation counts of the
benchmark, on the CPU."""
from __future__ import annotations

import json
import statistics

import numpy as np
import pytest

from bench import graphgen, reference
from bench import run as R
from bench import trace as btrace
from bench.models import gcn
from bench.tests import tiny
from bench.tests.tiny import REPO

CONFIGS = [c["name"] for c in
           json.loads((REPO / "BENCHMARK.json").read_text())["configs"]]


def _config(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


# ---------------------------------------------------------------- graphs
def test_graph_is_seeded_and_hits_its_counts():
    a = graphgen.power_law_graph(np.random.default_rng(5), 5000, 40000, 2.5)
    b = graphgen.power_law_graph(np.random.default_rng(5), 5000, 40000, 2.5)
    assert a.num_edges == 40000 and a.n == 5000
    assert np.array_equal(a.edge_array(), b.edge_array())
    e = a.edge_array()
    assert (e[:, 0] != e[:, 1]).all()
    key = np.minimum(e[:, 0], e[:, 1]) * 5000 + np.maximum(e[:, 0], e[:, 1])
    assert len(np.unique(key)) == 40000


@pytest.mark.parametrize("n,edges", [(34493, 247962), (20000, 505000)])
def test_no_vertex_holds_a_large_share_of_the_endpoints(n, edges):
    g = graphgen.power_law_graph(np.random.default_rng(1), n, edges, 2.5)
    stats = graphgen.describe(g, 64)
    # a zipf(1.35) % n source would put ~29% of all endpoints on one vertex
    assert stats["largest_endpoint_share"] < 0.01
    assert stats["max_degree"] <= 2 * np.sqrt(2 * edges)
    assert stats["mean_degree"] == pytest.approx(2 * edges / n + 1)


def test_neighbors_match_the_edge_list():
    g = graphgen.power_law_graph(np.random.default_rng(2), 400, 2000, 2.5)
    adj = {v: {v} for v in range(400)}
    for u, v in g.edge_array().tolist():
        adj[u].add(v)
        adj[v].add(u)
    for v in range(400):
        assert g.neighbors(v).tolist() == sorted(adj[v])


# ---------------------------------------------------------------- traffic
def _traffic(name):
    import importlib.util
    path = REPO / "bench" / "traffic" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"t_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_open_loop_schedule_is_seeded_and_keeps_its_rate():
    t = _traffic("open_poisson")
    spec = {"rate_rps": 25.0, "targets_per_request": 8,
            "targets": {"law": "zipf", "s": 0.99}}
    pop = np.random.default_rng
    a = t.build(spec, np.random.default_rng(9), 30.0, 1000, pop(1))
    b = t.build(spec, np.random.default_rng(9), 30.0, 1000, pop(1))
    c = t.build(spec, np.random.default_rng(10), 30.0, 1000, pop(1))
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.targets, b.targets)
    assert np.array_equal(a.seeds, b.seeds)
    assert not np.array_equal(a.due, c.due)
    assert len(a.due) == len(c.due) == 750
    assert 0 < a.due[0] and a.due[-1] < 30.0
    assert (np.diff(a.due) > 0).all()
    gaps = np.diff(a.due)
    assert statistics.mean(gaps) == pytest.approx(1 / 25.0, rel=0.02)
    # exponential gaps: the standard deviation is near the mean
    assert statistics.stdev(gaps) == pytest.approx(1 / 25.0, rel=0.15)
    # the same gaps in another order
    assert np.allclose(np.sort(np.diff(a.due)), np.sort(np.diff(c.due)),
                       rtol=0.05, atol=1e-3)
    counts = np.bincount(a.targets.ravel(), minlength=1000)
    assert counts.max() > 20 * np.median(counts)         # skewed
    assert a.targets.shape == (750, 8) and a.targets.max() < 1000
    # the same popularity ranks the same vertices hottest
    hot = np.bincount(c.targets.ravel(), minlength=1000)
    assert counts.argmax() == hot.argmax()


def test_closed_loop_source_never_repeats_a_target():
    t = _traffic("closed")
    spec = {"callers": 4, "targets_per_request": 8,
            "targets": {"law": "permutation"}}
    src = t.build(spec, np.random.default_rng(4), 10.0, 800, None)
    again = t.build(spec, np.random.default_rng(4), 10.0, 800, None)
    got = [src.take() for _ in range(100)]
    assert got == [again.take() for _ in range(100)]
    ids = [v for _, targets, _ in got for v in targets]
    assert sorted(ids) == list(range(800))


# ---------------------------------------------------------------- counts
@pytest.mark.parametrize("name", CONFIGS)
def test_operation_and_byte_counts_match_a_hand_count(name):
    cfg = _config(name)
    widths, fanouts = cfg["widths"], cfg["fanouts"]
    g = graphgen.power_law_graph(np.random.default_rng(0), 500, 3000, 2.5)
    _, blocks = reference.sample(g.neighbors, [3, 17, 3], 11, fanouts)
    flops = nbytes = 0
    rows, slots = [], []
    for (fi, fo), (nbr, mask) in zip(zip(widths[:-1], widths[1:]), blocks):
        rows.append(mask.shape[0])
        slots.append(int(mask.sum()))
        for i in range(mask.shape[0]):
            live = int(mask[i].sum())
            flops += live * fi            # adds of the mean
            flops += fi                   # its scale
            flops += fo * 2 * fi          # combine: multiply-add per input
            flops += fo * 2               # bias and ReLU
            nbytes += live * (fi * 4 + 8) + fo * 4
    work = gcn.layer_work(widths, rows, slots)
    assert sum(w["flops"] for w in work) == flops
    assert sum(w["bytes"] for w in work) == nbytes
    assert gcn.weight_bytes(widths) == [
        (fi * fo + fo) * 4.0 for fi, fo in zip(widths[:-1], widths[1:])]


# ---------------------------------------------------------------- rates
def test_rate_is_taken_to_the_last_answer_in_the_window():
    win = R.Window("closed", 100.0, 10.0)
    # three groups of four sent within the window, the last answered
    # 3.5 s after the close; a failed request counts for nothing
    win.records = ([{"ok": True, "t_done": 104.5}] * 4
                   + [{"ok": True, "t_done": 109.0}] * 4
                   + [{"ok": True, "t_done": 113.5}] * 4
                   + [{"ok": False, "t_done": 105.0}])
    win.t_end = 113.5
    assert R.answered_rps(win) == pytest.approx(12 / 13.5)
    win.records = [{"ok": False, "t_done": 101.0}]
    assert R.answered_rps(win) == 0.0


def test_a_stall_at_the_end_of_the_window_lowers_the_rate():
    win = R.Window("closed", 100.0, 10.0)
    win.records = [{"ok": True, "t_done": 104.0}] * 8
    win.t_end = 104.0
    steady = R.answered_rps(win)
    win.records += [{"ok": True, "t_done": 116.0}] * 4   # stalled from 9 s
    win.t_end = 116.0
    assert R.answered_rps(win) == pytest.approx(12 / 16.0)
    assert R.answered_rps(win) < steady


# ---------------------------------------------------------------- set-up
def test_graph_is_kept_in_the_checkout_and_loaded_the_same(tmp_path):
    cell = R.Cell(R.Bench(tiny.make_root(tmp_path)), "tiny.closed")
    first = R.Served(cell, 5)
    first.make_data()
    path = first.graph_file()
    assert path.is_file() and path.is_relative_to(tmp_path)
    again = R.Served(cell, 6)
    again.make_data()
    assert again.graph.n == first.graph.n
    assert np.array_equal(again.graph.keys, first.graph.keys)
    assert np.array_equal(again.graph.label, first.graph.label)
    assert not np.array_equal(again.table, first.table)   # from the seed
    cell.cfg["graph"]["edges"] += 1
    assert R.Served(cell, 5).graph_file() != path


# ---------------------------------------------------------------- trace
def _ev(dev, name, start, dur, line=btrace.OPS_LINE):
    return btrace.Event(dev, line, name, start * 1e9, dur * 1e9)


KERNEL_OP = ("%_agg_combine.2 = f32[1536,256]{1,0:T(8,128)S(1)} custom-call("
             "s32[1536,10]{1,0:T(8,128)S(1)} %copy.1), custom_call_target="
             '"tpu_custom_call"')
PAD_OP = ("%pad.4 = f32[12288,8448]{1,0:T(8,128)} pad(f32[12288,8415]"
          "{1,0:T(8,128)} %copy, f32[]{:T(128)} %constant.2)")


def test_trace_reduction():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    events = [
        _ev(d0, "jit__program(1)", 0.0, 0.5, line=btrace.MODULES_LINE),
        _ev(d0, PAD_OP, 0.0, 0.2),
        _ev(d0, KERNEL_OP, 0.1, 0.2),
        _ev(d0, PAD_OP, 0.9, 0.1),
        _ev(d0, KERNEL_OP, 1.5, 0.25),
        _ev(d1, "fusion.2", 0.0, 0.4),
        _ev(d0, "jit_other", 2.0, 0.3, line=btrace.MODULES_LINE),
    ]
    tr = btrace.reduce(events, 2.0, module="_program", kernel=R.KERNEL)
    # device 0: [0, 0.3] + [0.9, 1.0] + [1.5, 1.75] = 0.65 s; device 1: 0.4
    assert tr["busy_s"] == pytest.approx((0.65 + 0.4) / 2)
    assert tr["idle_share"] == pytest.approx(1 - 0.525 / 2.0)
    assert tr["devices"] == 2
    assert tr["module_calls"] == 1
    assert tr["module_s"] == pytest.approx(0.5)
    assert tr["kernel_calls"] == 2
    assert tr["kernel_s"] == pytest.approx(0.45)
    kern = "%_agg_combine.2 custom-call f32[1536,256]"
    assert tr["device_ops"][0] == [kern, pytest.approx(0.45)]
    assert tr["device_ops"][1:] == [["fusion.2", pytest.approx(0.4)],
                                    ["%pad.4 pad f32[12288,8448]",
                                     pytest.approx(0.3)]]
    assert tr["idle_gaps"][0] == [f"{kern} -> %pad.4 pad f32[12288,8448]",
                                  pytest.approx(0.6)]
    assert len(tr["idle_gaps"]) == 2


def test_trace_reduction_of_an_empty_trace():
    tr = btrace.reduce([], 1.0, module="_program", kernel="agg_combine")
    assert tr["busy_s"] == 0.0 and tr["kernel_calls"] == 0
    assert tr["device_ops"] == [] and tr["idle_gaps"] == []
