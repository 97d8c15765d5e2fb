"""The span recorder (``repro.spans``) and the spans of the served path."""
import sys
import threading
import time

import numpy as np
import pytest

from repro import spans
from repro.core import gnn
from repro.core.dfg import Engine
from repro.core.service import HolisticGNNService, make_service_dfg
from repro.serve import ServingRuntime


def _delta(before: dict, after: dict, name: str) -> dict:
    b = before.get(name, {"n": 0, "s": 0.0})
    a = after.get(name, {"n": 0, "s": 0.0})
    return {"n": a["n"] - b["n"], "s": a["s"] - b["s"]}


# ------------------------------------------------------------- the recorder
def test_counts_and_seconds_accumulate():
    before = spans.snapshot()
    for _ in range(3):
        with spans.span("test.accumulate"):
            time.sleep(0.01)
    spans.count("test.accumulate.counter", 5, 0.25)
    spans.count("test.accumulate.counter", 2)
    after = spans.snapshot()
    got = _delta(before, after, "test.accumulate")
    assert got["n"] == 3 and 0.03 <= got["s"] < 3.0
    assert _delta(before, after, "test.accumulate.counter") == \
        {"n": 7, "s": pytest.approx(0.25)}


def test_nested_spans_keep_their_own_counters():
    before = spans.snapshot()
    with spans.span("test.outer", group=1):
        time.sleep(0.01)
        for hop in range(2):
            with spans.span("test.inner", hop=hop):
                time.sleep(0.01)
    after = spans.snapshot()
    outer = _delta(before, after, "test.outer")
    inner = _delta(before, after, "test.inner")
    assert outer["n"] == 1 and inner["n"] == 2
    assert outer["s"] >= inner["s"] + 0.01
    assert inner["s"] >= 0.02


def test_counters_stay_exact_from_many_threads():
    workers, rounds = 16, 400
    before = spans.snapshot()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(rounds):
                spans.count("test.threads.count", 1, 0.5)
                with spans.span("test.threads.span"):
                    pass
        threads = [threading.Thread(target=work, name=f"spans-{i}")
                   for i in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    after = spans.snapshot()
    got = _delta(before, after, "test.threads.count")
    assert got["n"] == workers * rounds
    assert got["s"] == pytest.approx(0.5 * workers * rounds)
    assert _delta(before, after, "test.threads.span")["n"] == \
        workers * rounds


def test_snapshot_is_a_copy():
    rec = spans.Recorder()
    rec.add("a", 2, 1.0)
    snap = rec.snapshot()
    snap["a"]["n"] = 99
    snap["b"] = {"n": 1, "s": 1.0}
    rec.add("a", 1, 0.5)
    assert snap["a"]["n"] == 99
    assert rec.snapshot() == {"a": {"n": 3, "s": 1.5}}


def test_group_and_hop_pass_to_inner_spans_on_the_trace(tmp_path):
    """The annotations land on the host plane with their ids; ``group``
    and ``hop`` reach the spans opened inside, other ids do not."""
    import glob
    import os

    import jax
    from jax._src.lib import _profile_data

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span("test.group", group=7, size=3):
            with spans.span("test.hop", hop=1):
                with spans.span("test.read", tag="graph"):
                    pass
            with spans.span("test.after"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    got = {}
    for plane in _profile_data.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("test."):
                    got[ev.name.split("#")[0]] = dict(ev.stats)
    assert got["test.group"] == {"group": 7, "size": 3}
    assert got["test.hop"] == {"group": 7, "hop": 1}
    assert got["test.read"] == {"group": 7, "hop": 1, "tag": "graph"}
    assert got["test.after"] == {"group": 7}


def test_eager_engine_records_a_span_per_op():
    svc = HolisticGNNService(h_threshold=16, pad_to=32)
    eng = svc.engine
    assert not hasattr(eng, "timings")
    params = gnn.init_params("gcn", [8, 4], seed=0)
    dfg = gnn.BUILD_DFG["gcn"](1)
    rng = np.random.default_rng(0)
    h = rng.standard_normal((32, 8)).astype(np.float32)
    nbr = rng.integers(0, 32, (32, 3)).astype(np.int32)
    mask = np.ones((32, 3), np.float32)
    feeds = gnn.dfg_feeds("gcn", params, h, [(nbr, mask)])
    before = spans.snapshot()
    eng.run(dfg, feeds, fuse=False)
    after = spans.snapshot()
    assert _delta(before, after, "engine.run")["n"] == 1
    ops = [op for op, _ in eng.trace]
    for op in set(ops):
        assert _delta(before, after, f"engine.{op}")["n"] == ops.count(op)
    node_s = sum(_delta(before, after, f"engine.{op}")["s"]
                 for op in set(ops))
    assert node_s <= _delta(before, after, "engine.run")["s"]


# ----------------------------------------------------------- served path
def _service(seed=0, n=600, e=5000, feat=32):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, e), rng.zipf(1.4, e) % n],
                     axis=1).astype(np.int64)
    emb = rng.standard_normal((n, feat)).astype(np.float32)
    svc = HolisticGNNService(h_threshold=16, pad_to=32, cache_pages=2048)
    svc.store.update_graph(edges, emb)
    return svc, n


STAGES = ("store.hop", "store.embed", "serve.pad", "serve.h2d",
          "engine.run", "serve.slice", "serve.reply")


def test_served_path_spans_match_the_scheduler_counters():
    svc, n = _service()
    fanouts = [5, 5]
    params = gnn.init_params("gcn", [32, 16, 8], seed=1)
    dfg = make_service_dfg("gcn", 2, fanouts).save()
    weights = {k: v for k, v in
               gnn.dfg_feeds("gcn", params, None, []).items() if k != "H"}
    rt = ServingRuntime(svc, n_queues=2, max_group=2)
    c = rt.client()
    sid = c.submit("stats")
    rt.pump()
    before = c.result(sid)
    rng = np.random.default_rng(4)
    cmds = [c.submit("run", dfg=dfg, batch=rng.integers(0, n, 4).tolist(),
                     weights=weights, seed=i) for i in range(5)]
    assert rt.pump() == 5
    for cid in cmds:
        assert c.result(cid)["Result"].shape[0] >= 4
    sid = c.submit("stats")
    rt.pump()
    after = c.result(sid)
    d = {k: _delta(before["spans"], after["spans"], k)
         for k in after["spans"]}
    groups = after["qos"]["groups"] - before["qos"]["groups"]
    answered = after["qos"]["completed"] - before["qos"]["completed"]
    assert groups == 3 and answered == 5
    assert d["serve.group"]["n"] == groups
    assert d["serve.form"]["n"] >= groups
    assert d["store.hop"]["n"] == groups * len(fanouts)
    assert d["store.plan"]["n"] == d["store.select"]["n"] == \
        groups * len(fanouts)
    assert d["store.embed"]["n"] == groups
    assert d["serve.queue_wait"]["n"] == answered
    assert d["serve.queue_wait"]["s"] > 0
    assert d["serve.reply"]["n"] == answered
    assert d["serve.rows_padded"]["n"] >= d["serve.rows_real"]["n"] > 0
    for name in STAGES:
        assert d[name]["n"] >= groups, name
    assert sum(d[name]["s"] for name in STAGES) <= d["serve.group"]["s"]
    for name in ("store.plan", "store.select"):
        assert d[name]["s"] <= d["store.hop"]["s"]


def test_h2d_bytes_count_the_padded_table_and_blocks(monkeypatch):
    """``serve.h2d_bytes`` grows by the bytes of the padded feature table
    and of every layer's neighbour and mask block of the group."""
    from repro.serve import batcher
    svc, n = _service()
    fanouts = [5, 5]
    params = gnn.init_params("gcn", [32, 16, 8], seed=1)
    dfg = make_service_dfg("gcn", 2, fanouts).save()
    weights = {k: v for k, v in
               gnn.dfg_feeds("gcn", params, None, []).items() if k != "H"}
    padded = []
    pad_group = batcher.pad_group

    def keep(batch, base):
        padded.append(pad_group(batch, base))
        return padded[-1]
    monkeypatch.setattr(batcher, "pad_group", keep)
    rng = np.random.default_rng(5)
    requests = [{"targets": rng.integers(0, n, 7).tolist(), "seed": i}
                for i in range(3)]
    before = spans.snapshot()
    svc.run_batch(dfg, requests, weights=weights)
    after = spans.snapshot()
    (batch,) = padded
    rows = len(batch.node_vids)
    assert rows > _delta(before, after, "serve.rows_real")["n"]  # padded
    want = rows * 32 * 4 + sum(blk.nbr.shape[0] * blk.nbr.shape[1] * 8
                               for blk in batch.layers)
    assert _delta(before, after, "serve.h2d_bytes") == {"n": want, "s": 0.0}
    svc.close()
