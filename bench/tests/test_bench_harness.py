"""The harness end to end on the CPU at a tiny size: sound runs are
correct, and each fault planted under the timed path makes ``correct``
false.  The chip check is skipped (``require_chip=False``)."""
from __future__ import annotations

import numpy as np
import pytest

from bench import run as R
from bench.tests import tiny


@pytest.fixture
def quick(monkeypatch):
    monkeypatch.setattr(R, "WARM_S", 0.5)
    monkeypatch.setattr(R, "SHAPE_REQUESTS", 12)
    monkeypatch.setattr(R, "SHAPE_GROUPS", 20)


def _run(tmp_path, workload, **kw):
    root = tiny.make_root(tmp_path, extra_metric="added_metric")
    trace = kw.pop("trace", 0)
    return R.run(tiny.args(workload, trace=trace, **kw), root=root,
                 require_chip=False, use_cache=False)


def test_online_run_is_correct_and_reports_an_added_metric(tmp_path, quick):
    res = _run(tmp_path, "tiny.online", trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    m = res["metrics"]
    # a metric added as a file and an entry, with no harness edit
    assert m["added_metric"]["value"] == res["attempted"] + 0.5
    assert m["group_size.online"]["value"] >= 1.0
    assert m["compiles_in_window.online"]["value"] == 0.0
    assert "p50_ms" not in m                     # end to end: trace 0 only
    assert res["device"]["window_s"] > 0
    assert list(res)[-1] == "checks"


def test_closed_run_reports_end_to_end_metrics(tmp_path, quick):
    res = _run(tmp_path, "tiny.closed")
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"rps", "setup_s"}
    assert res["metrics"]["rps"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


def test_half_the_neighbours_left_out_is_not_correct(tmp_path, quick,
                                                      monkeypatch):
    from repro.serve import batcher
    pad_group = batcher.pad_group

    def half(batch, base):
        out = pad_group(batch, base)
        for blk in out.layers:                 # mean over the rest
            k = blk.mask.shape[1]
            blk.mask[:, (k + 1) // 2:] = 0.0
        return out
    monkeypatch.setattr(batcher, "pad_group", half)
    res = _run(tmp_path, "tiny.online")
    assert res["correct"] is False
    assert res["checks"]["rel_l2_worst"]["value"] > 1e-3


def test_an_altered_answer_is_not_correct(tmp_path, quick, monkeypatch):
    from repro.core.service import HolisticGNNService
    run_batch = HolisticGNNService.run_batch

    def altered(self, *a, **kw):
        outs = run_batch(self, *a, **kw)
        for o in outs:
            o["Result"] = o["Result"].copy()
            o["Result"][0, 0] += 1.0
        return outs
    monkeypatch.setattr(HolisticGNNService, "run_batch", altered)
    res = _run(tmp_path, "tiny.closed")
    assert res["correct"] is False


@pytest.mark.parametrize("answers", ["program", "control"])
def test_control_in_the_programs_place_is_not_correct_at_config_widths(
        tmp_path, quick, monkeypatch, answers):
    """Each configuration's model and widths over a small graph.  With
    ``control`` every served answer is replaced, where the service
    produces it, by the reference at ``high`` precision (bfloat16 head and
    tail); the harness's own comparison then fails it."""
    import jax.numpy as jnp
    from bench import control, reference
    from repro.core.service import HolisticGNNService

    cfg = tiny.at_config_widths(R.Bench(tiny.REPO).spec["configs"][0]["name"])
    served = []
    prepare = R.Served.prepare

    def keep(self):
        served.append(self)
        prepare(self)

    run_batch = HolisticGNNService.run_batch
    high = reference.matmul_bf16x3(jnp)

    def in_place(self, dfg, requests, *a, **kw):
        outs = run_batch(self, dfg, requests, *a, **kw)
        for o, r in zip(outs, requests):
            o["Result"] = control.control_rows(served[-1], r, high)
        return outs
    monkeypatch.setattr(R.Served, "prepare", keep)
    if answers == "control":
        monkeypatch.setattr(HolisticGNNService, "run_batch", in_place)
    root = tiny.make_root(tmp_path, cfg=cfg)
    res = R.run(tiny.args("tiny.closed"), root=root, require_chip=False,
                use_cache=False)
    worst = res["checks"]["rel_l2_worst"]
    assert res["failed"] == 0 and res["attempted"] > 0
    if answers == "control":
        assert res["correct"] is False
        assert worst["value"] > 2 * worst["limit"]
    else:
        assert res["correct"] is True, res["checks"]
        assert worst["value"] < worst["limit"] / 2


def test_control_precision_fails_the_limit_and_fp32_passes():
    """The reference at ``high`` precision (bfloat16 head and tail) reads
    above the configuration's limit; float32 at ``highest`` reads below."""
    import jax.numpy as jnp
    from bench import graphgen, reference
    from bench.models import gcn

    cfg = tiny.TINY
    rng = np.random.default_rng(3)
    g = graphgen.power_law_graph(rng, 300, 1200, 2.5)
    table = graphgen.features(rng, 300, 24)
    widths = [256, 256, 256]
    table = graphgen.features(rng, 300, widths[0])
    params = gcn.init_weights(rng, widths)
    jparams = [{k: jnp.asarray(v) for k, v in p.items()} for p in params]
    limit = cfg["correct"]["rel_l2_limit"]
    worst = {"high": 0.0, "highest": 0.0}
    for seed in range(4):
        targets = rng.integers(0, 300, 3).tolist()
        levels, blocks = reference.sample(g.neighbors, targets, seed,
                                          cfg["fanouts"])
        emb = table[np.asarray(levels[-1])]
        ref = gcn.forward(emb, blocks, params)
        jb = [(jnp.asarray(n), jnp.asarray(m)) for n, m in blocks]
        for name, mm in (("high", reference.matmul_bf16x3(jnp)),
                         ("highest", lambda a, b: jnp.matmul(
                             a, b, precision="highest"))):
            rows = gcn.forward(jnp.asarray(emb), jb, jparams, xp=jnp,
                               matmul=mm)
            worst[name] = max(worst[name], reference.rel_l2(rows, ref))
    assert worst["high"] > 2 * limit
    assert worst["highest"] < limit / 2
