"""``gcn-physics`` on the CPU: the served path at the configuration's
widths [8415, 64, 5] and fanouts [10, 10] over a small graph, and the
readers of the bytes a group moves."""
from __future__ import annotations

import pytest

from bench import run as R
from bench.tests import tiny


# ---------------------------------------------------------------- readers
def _ctx(before, after):
    return {"before": {"spans": before}, "after": {"spans": after}}


BEFORE = {"serve.group": {"n": 10, "s": 5.0},
          "store.embed_bytes": {"n": 1_000_000, "s": 0.0},
          "serve.h2d_bytes": {"n": 3_000_000, "s": 0.0}}
AFTER = {"serve.group": {"n": 14, "s": 7.0},
         "store.embed_bytes": {"n": 1_000_000 + 4 * 300_000_000, "s": 0.0},
         "serve.h2d_bytes": {"n": 3_000_000 + 4 * 551_000_000, "s": 0.0}}


@pytest.mark.parametrize("metric,counter,mb", [
    ("embed_mb.physics", "store.embed_bytes", 300.0),
    ("h2d_mb.physics", "serve.h2d_bytes", 551.0)])
def test_byte_readers_give_mb_per_group_across_the_window(metric, counter,
                                                          mb):
    reader = R.Bench(tiny.REPO).reader(metric)
    assert reader.read(_ctx(BEFORE, AFTER)) == pytest.approx(mb)
    # no group in the window
    assert reader.read(_ctx(BEFORE, BEFORE)) is None
    # a program without the spans block, or without this counter
    assert reader.read({"before": {}, "after": {}}) is None
    without = {k: v for k, v in AFTER.items() if k != counter}
    assert reader.read(_ctx({}, without)) is None
    # a counter first seen in the window counts from zero
    first = {k: v for k, v in BEFORE.items() if k != counter}
    assert reader.read(_ctx(first, AFTER)) == \
        pytest.approx(AFTER[counter]["n"] / 1e6 / 4)


# ------------------------------------------------------------ served path
def control_in_place(monkeypatch) -> None:
    """Replace every served answer, where the service produces it, by the
    reference at ``high`` precision (bfloat16 head and tail)."""
    import jax.numpy as jnp
    from bench import control, reference
    from repro.core.service import HolisticGNNService

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
    monkeypatch.setattr(HolisticGNNService, "run_batch", in_place)


@pytest.fixture
def quick(monkeypatch):
    monkeypatch.setattr(R, "WARM_S", 0.5)
    monkeypatch.setattr(R, "SHAPE_REQUESTS", 8)
    monkeypatch.setattr(R, "SHAPE_GROUPS", 4)


@pytest.mark.parametrize("answers", ["program", "control"])
def test_physics_widths_are_correct_and_the_control_is_not(
        tmp_path, quick, monkeypatch, answers):
    """Every row is 8,415 wide, so it spans 9 or 10 pages of the store and
    layer 0's AggCombine gathers at 8,448 lanes.  With ``control`` each
    served answer is replaced, where the service produces it, by the
    reference at ``high`` precision (bfloat16 head and tail); the
    harness's own comparison then fails it."""
    cfg = tiny.at_config_widths("gcn-physics", vertices=400, edges=1600)
    assert cfg["widths"] == [8415, 64, 5] and cfg["fanouts"] == [10, 10]
    if answers == "control":
        control_in_place(monkeypatch)
    root = tiny.make_root(tmp_path, cfg=cfg)
    res = R.run(tiny.args("tiny.closed", seconds=1.0), root=root,
                require_chip=False, use_cache=False)
    worst = res["checks"]["rel_l2_worst"]
    assert res["failed"] == 0 and res["attempted"] > 0
    if answers == "control":
        assert res["correct"] is False
        assert worst["value"] > 2 * worst["limit"]
    else:
        assert res["correct"] is True, res["checks"]
        assert worst["value"] < worst["limit"] / 2
