"""Run one cell of the benchmark once, on the chip.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything it
needs is found by name, so a cell, a configuration or a metric is added by
adding files:

  * ``bench/workloads/<cell>.json``: the traffic's parameters and the name
    of its generator;
  * ``bench/configs/<config>.json``: graph, model, widths, fanouts,
    precision, service settings and the limit of the correctness check;
  * ``bench/traffic/<generator>.py``: builds the arrivals from the seed;
  * ``bench/models/<model>.py``: weights, the plain forward, operation and
    byte counts;
  * ``bench/metrics/<metric>.py``: one reader per per-layer metric
    (``x.online`` falls back to ``x.py``);
  * ``bench/peaks.json``: the chip's peaks by ``device_kind``.

A run makes the graph from its configuration's own seed (kept under
``bench/out/graphs/`` after the first run in a checkout) and the features,
weights and traffic from ``--seed``, ingests them
into a ``HolisticGNNService`` behind a ``ServingRuntime``, compiles every
bucket shape the traffic reaches and serves a few seconds of it (set-up),
then serves ``--seconds`` of the cell's traffic through the RoP clients
(the window).  With ``--trace 1`` the profiler records the whole window.
After the window the program is stopped and a seeded sample of the
answers is compared with the plain reference.

The last line of stdout is the result object; the last lines of stderr are
the numbers compared, each beside its limit.  Without a TPU whose
``device_kind`` is in the peak table, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if (ROOT / "src").is_dir() and str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import graphgen, reference  # noqa: E402
from bench import trace as btrace  # noqa: E402

WARM_S = 5.0              # seconds of the cell's traffic served in set-up
SHAPE_REQUESTS = 256      # requests sampled alone to find bucket shapes
SHAPE_GROUPS = 300        # random groups of each size formed from them
DRAIN_S = 60.0            # how long replies are awaited after the window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
KERNEL = r"^%_agg_combine[.\d]* .*custom-call"   # the fused layer's kernel
MODULE = "_program"       # the engine's jitted program in the trace
STREAMS = {"graph": 0, "features": 1, "weights": 2, "traffic": 3,
           "warm": 4, "sample": 5, "popularity": 6, "shapes": 7}


class NoChip(RuntimeError):
    """No accelerator this benchmark can measure."""


def log(msg: str) -> None:
    print(msg, flush=True)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The benchmark's files under ``root``: ``BENCHMARK.json`` and
    ``bench/``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, *parts) -> dict:
        return json.loads(self.dir.joinpath(*parts).read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        return self._json("workloads", f"{name}.json")

    def config(self, name: str) -> dict:
        return self._json("configs", f"{name}.json")

    def peaks(self) -> dict:
        return self._json("peaks.json")

    def traffic(self, kind: str):
        return _load(self.dir / "traffic" / f"{kind}.py",
                     f"bench_traffic_{kind}")

    def model(self, name: str):
        return _load(self.dir / "models" / f"{name}.py", f"bench_model_{name}")

    def reader(self, metric: str):
        path = self.dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            path = self.dir / "metrics" / f"{metric.split('.')[0]}.py"
        return _load(path, "bench_metric_" + metric.replace(".", "_"))

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def check_chip(chips: int, peaks: dict) -> dict:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no backend: {e}") from e
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX platform is {d.platform!r}, not a TPU")
    if d.device_kind not in peaks:
        raise NoChip(f"device kind {d.device_kind!r} is not in the peak "
                     "table")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), STREAMS[stream]])


class Compiles:
    """Times and durations of JAX's compile-or-load events."""

    def __init__(self):
        self.events: list[tuple[float, float]] = []
        self._lock = threading.Lock()

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.events.append((time.perf_counter(), float(secs)))

    def since(self, t: float) -> list[tuple[float, float]]:
        with self._lock:
            return [e for e in self.events if e[0] >= t]


class Cell:
    """One cell's files, resolved."""

    def __init__(self, bench: Bench, name: str):
        self.bench = bench
        self.name = name
        self.entry = bench.cell(name)
        self.spec = bench.workload(name)
        self.cfg = bench.config(self.entry["config"])
        self.traffic = bench.traffic(self.spec["traffic"])
        self.model = bench.model(self.cfg["model"])
        self.widths = list(self.cfg["widths"])
        self.fanouts = list(self.cfg["fanouts"])
        self.svc_cfg = dict(self.cfg["service"])


class Served:
    """The system under test, loaded with the cell's data from one seed."""

    def __init__(self, cell: Cell, seed: int):
        self.cell = cell
        self.seed = seed
        self.setup_log: dict[str, float] = {}

    # ------------------------------------------------------------ set-up
    def prepare(self) -> None:
        """Set-up: the data, the service loaded with it, every bucket shape
        the traffic reaches compiled or loaded, and ``WARM_S`` seconds of
        the cell's traffic served."""
        self.make_data()
        self.start()
        t = time.perf_counter()
        self.shapes = self.signatures(self.shape_plan())
        self.setup_log["signature_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.compile_signatures(self.shapes)
        self.setup_log["compile_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.serve(self.requests("warm", WARM_S), WARM_S)
        self.setup_log["warm_traffic_s"] = time.perf_counter() - t

    def graph_file(self) -> Path:
        """Where this checkout keeps the configuration's graph: named by
        its parameters and the generator's source, so a change to either
        makes a new one."""
        key = hashlib.sha256(
            json.dumps(self.cell.cfg["graph"], sort_keys=True).encode()
            + Path(graphgen.__file__).read_bytes()).hexdigest()[:16]
        return (self.cell.bench.dir / "out" / "graphs"
                / f"{self.cell.cfg['name']}-{key}.npz")

    def make_data(self) -> None:
        """The graph is the configuration's own, from its ``graph.seed``:
        a deployment serves one graph, and a graph drawn anew per run
        would change the work from seed to seed.  The first run in a
        checkout draws it and keeps it; later runs load it.  Features,
        weights and traffic come from the run's seed."""
        cfg, t = self.cell.cfg, time.perf_counter()
        g = cfg["graph"]
        path = self.graph_file()
        if path.is_file():
            with np.load(path) as z:
                self.graph = graphgen.Graph(n=int(z["n"]), label=z["label"],
                                            keys=z["keys"])
        else:
            self.graph = graphgen.power_law_graph(
                rng_for(g["seed"], "graph"), int(g["vertices"]),
                int(g["edges"]), float(g["gamma"]))
            path.parent.mkdir(parents=True, exist_ok=True)
            part = path.with_suffix(".part.npz")
            np.savez(part, n=self.graph.n, label=self.graph.label,
                     keys=self.graph.keys)
            os.replace(part, path)
        self.setup_log["graph_s"] = time.perf_counter() - t
        log(f"graph: {json.dumps(graphgen.describe(self.graph, self.cell.svc_cfg['h_threshold']))}")
        t = time.perf_counter()
        self.table = graphgen.features(rng_for(self.seed, "features"),
                                       self.graph.n, int(g["features"]))
        self.setup_log["features_s"] = time.perf_counter() - t
        self.params = self.cell.model.init_weights(
            rng_for(self.seed, "weights"), self.cell.widths)

    def start(self) -> None:
        from repro.core.service import HolisticGNNService, make_service_dfg
        from repro.kernels.ops import program_config
        from repro.serve import ServingRuntime

        s = self.cell.svc_cfg
        t = time.perf_counter()
        self.svc = HolisticGNNService(
            h_threshold=int(s["h_threshold"]), pad_to=int(s["pad_to"]),
            cache_pages=int(s["cache_pages"]),
            jit_cache_size=int(s["jit_cache_size"]))
        self.runtime = ServingRuntime(
            self.svc, n_queues=int(s["n_queues"]),
            max_group=int(s["max_group"]), max_pending=int(s["max_pending"]))
        self.runtime.start()
        self.boot = self.runtime.client()
        self.boot.call("update_graph", edge_array=self.graph.edge_array(),
                       embeddings=self.table, timeout=1200)
        self.setup_log["ingest_s"] = time.perf_counter() - t
        program_config(self.svc.xbuilder, s["bitstream"])
        self.weights_name = self.cell.cfg["model"]
        t = time.perf_counter()
        self.boot.call("put_weights", name=self.weights_name,
                       weights=self.cell.model.service_weights(self.params),
                       timeout=600)
        self.setup_log["weights_s"] = time.perf_counter() - t
        self.dfg = make_service_dfg(self.cell.cfg["model"],
                                    len(self.cell.fanouts),
                                    self.cell.fanouts).save()

    def requests(self, stream: str, seconds: float):
        return self.cell.traffic.build(
            self.cell.spec, rng_for(self.seed, stream), seconds,
            self.graph.n, self.popularity())

    def popularity(self) -> np.random.Generator:
        """Which vertices a skewed law makes hot: the graph's, like the
        graph itself."""
        return rng_for(self.cell.cfg["graph"]["seed"], "popularity")

    def draw(self, plan, count: int):
        """The first ``count`` requests ``(targets, seed)`` of a plan."""
        if self.cell.traffic.KIND == "open":
            n = min(count, len(plan.seeds))
            return ([plan.targets[i].tolist() for i in range(n)],
                    [int(plan.seeds[i]) for i in range(n)])
        got = [plan.take() for _ in range(count)]
        return [g[1] for g in got], [g[2] for g in got]

    def shape_plan(self):
        """Traffic of the set-up's stream with ``SHAPE_REQUESTS`` in it."""
        rate = float(self.cell.spec.get("rate_rps", SHAPE_REQUESTS / WARM_S))
        return self.requests("warm", max(WARM_S, SHAPE_REQUESTS / rate))

    def signatures(self, plan) -> set:
        """Bucket shapes of fused groups of every size.

        A group samples each request on its own and stacks the results, so
        its level sizes are the sums of its requests' sizes.  Requests of
        the plan are sampled alone through the batcher (no embeddings);
        random groups of each size formed from them, and the groups of the
        smallest and the largest, are padded by the batcher's own rule."""
        from repro.serve.batcher import pad_group, sample_group
        from repro.store.sampler import LayerBlock, SampledBatch

        targets, seeds = self.draw(plan, SHAPE_REQUESTS)
        sizes = []
        for t, sd in zip(targets, seeds):
            b, _ = sample_group(self.svc.store, [t], [sd], self.cell.fanouts,
                                fetch_embeddings=False)
            sizes.append((b.num_nodes,) + tuple(blk.num_dst
                                                for blk in b.layers))
        sizes = np.array(sizes, np.int64)
        ks = self.cell.fanouts[::-1]
        base = int(self.cell.svc_cfg["pad_to"])
        rng = rng_for(self.seed, "shapes")
        def padded(tot) -> tuple:
            layers = [LayerBlock(nbr=np.zeros((d, k), np.int32),
                                 mask=np.zeros((d, k), np.float32),
                                 num_dst=int(d))
                      for d, k in zip(tot[1:], ks)]
            out = pad_group(SampledBatch(
                layers=layers, node_vids=np.zeros(tot[0], np.int64),
                embeddings=None, num_targets=0), base)
            return (len(out.node_vids),) + tuple(blk.nbr.shape[0]
                                                 for blk in out.layers)

        sigs = set()
        ordered = np.sort(sizes, axis=0)
        for g in range(1, min(int(self.cell.svc_cfg["max_group"]),
                              len(sizes)) + 1):
            picks = [rng.choice(len(sizes), g, replace=False)
                     for _ in range(SHAPE_GROUPS)]
            seen = {padded(tuple(sizes[p].sum(axis=0))) for p in picks}
            # each size at its least and its most, then every mix of the
            # buckets that each size reached
            seen |= {padded(tuple(ordered[:g].sum(axis=0))),
                     padded(tuple(ordered[-g:].sum(axis=0)))}
            for combo in itertools.product(*map(set, zip(*seen))):
                sigs.add((combo[0], tuple(combo[1:])))
        return sigs

    def compile_signatures(self, sigs: set) -> None:
        """Run the engine's program once at each bucket shape on zeros."""
        import jax.numpy as jnp
        from repro.core.dfg import DFG
        from repro.serve.batcher import split_service_dfg

        prog = split_service_dfg(DFG.load(self.dfg))
        weights = {k: jnp.asarray(v) for k, v in
                   self.cell.model.service_weights(self.params).items()}
        f_in = self.cell.widths[0]
        for n_pad, dpads in sorted(sigs):
            feeds = dict(weights)
            feeds[prog.feed_refs[0]] = jnp.zeros((n_pad, f_in), jnp.float32)
            for l, (d, k) in enumerate(zip(dpads, self.cell.fanouts[::-1])):
                feeds[prog.feed_refs[1 + 2 * l]] = jnp.zeros((d, k),
                                                             jnp.int32)
                feeds[prog.feed_refs[2 + 2 * l]] = jnp.zeros((d, k),
                                                             jnp.float32)
            self.svc.engine.run(prog.model, feeds, jit=True)

    def stats(self) -> dict:
        return self.boot.call("stats", timeout=600)

    def stop(self) -> None:
        self.runtime.stop()
        self.svc.close()
        del self.svc, self.runtime, self.boot
        gc.collect()

    # ------------------------------------------------------------ serving
    def serve(self, plan, seconds: float) -> "Window":
        if self.cell.traffic.KIND == "open":
            return open_loop(self, plan, seconds)
        return closed_loop(self, plan, seconds,
                           int(self.cell.spec["callers"]))


class Window:
    """What the clients saw: one record per request of the population."""

    def __init__(self, kind: str, t_open: float, seconds: float):
        self.kind = kind
        self.t_open = t_open
        self.seconds = seconds
        self.t_close = t_open + seconds
        self.records: list[dict] = []
        self.t_end = t_open

    @property
    def ok(self) -> list[dict]:
        return [r for r in self.records if r["ok"]]

    @property
    def failed(self) -> list[dict]:
        return [r for r in self.records if not r["ok"]]


def _call(client, served: Served, targets, seed):
    return client.submit("run", dfg=served.dfg, batch=targets,
                         weights_ref=served.weights_name, seed=seed)


def open_loop(served: Served, plan, seconds: float) -> Window:
    """Submit each request at its due time; reap replies on a pool."""
    from repro.rpc.queues import QueueFullError

    clients = [served.runtime.client()
               for _ in range(int(served.cell.svc_cfg["n_queues"]))]
    t_open = time.perf_counter()
    win = Window("open", t_open, seconds)
    n = len(plan.due)
    recs = [None] * n
    deadline = t_open + seconds + DRAIN_S

    def reap(i: int, client, cmd: int, due: float, late: float) -> None:
        rec = {"i": i, "targets": plan.targets[i].tolist(),
               "seed": int(plan.seeds[i]), "due": due, "late": late}
        try:
            out = client.result(cmd, timeout=max(deadline
                                                 - time.perf_counter(), 0.0))
            rec.update(ok=True, t_done=time.perf_counter(),
                       rows=np.asarray(out["Result"]))
        except Exception as e:  # noqa: BLE001 — a failed request is counted
            rec.update(ok=False, t_done=time.perf_counter(), error=repr(e))
        recs[i] = rec

    with ThreadPoolExecutor(max_workers=64,
                            thread_name_prefix="bench-reap") as pool:
        for i in range(n):
            due = t_open + float(plan.due[i])
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late = time.perf_counter() - due
            client = clients[i % len(clients)]
            try:
                cmd = _call(client, served, plan.targets[i].tolist(),
                            int(plan.seeds[i]))
            except QueueFullError as e:
                recs[i] = {"i": i, "targets": plan.targets[i].tolist(),
                           "seed": int(plan.seeds[i]), "due": due,
                           "late": late, "ok": False,
                           "t_done": time.perf_counter(), "error": repr(e)}
                continue
            pool.submit(reap, i, client, cmd, due, late)
    win.records = recs
    win.t_end = time.perf_counter()
    return win


def closed_loop(served: Served, source, seconds: float,
                callers: int) -> Window:
    """``callers`` threads, each sending its next request on a reply."""
    t_open = time.perf_counter()
    win = Window("closed", t_open, seconds)
    lock = threading.Lock()

    def caller() -> None:
        client = served.runtime.client()
        while True:
            t_sub = time.perf_counter()
            if t_sub >= win.t_close:
                return
            i, targets, seed = source.take()
            rec = {"i": i, "targets": targets, "seed": seed, "due": t_sub,
                   "late": 0.0}
            try:
                cmd = _call(client, served, targets, seed)
                out = client.result(cmd, timeout=DRAIN_S)
                rec.update(ok=True, t_done=time.perf_counter(),
                           rows=np.asarray(out["Result"]))
            except Exception as e:  # noqa: BLE001 — counted as failed
                rec.update(ok=False, t_done=time.perf_counter(),
                           error=repr(e))
            with lock:
                win.records.append(rec)

    threads = [threading.Thread(target=caller, name=f"bench-caller-{c}")
               for c in range(callers)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=seconds + 2 * DRAIN_S)
        if th.is_alive():
            raise RuntimeError(f"{th.name} did not finish")
    win.t_end = time.perf_counter()
    return win


def latency_ms(win: Window) -> np.ndarray:
    """Per request of the population, from its due time (open loop) or its
    submission (closed loop) to its reply; a failed request counts as
    waiting until the run stopped waiting."""
    return np.array([((r["t_done"] if r["ok"] else max(r["t_done"],
                                                       win.t_end))
                      - r["due"]) * 1e3 for r in win.records])


def answered_rps(win: Window) -> float:
    """Requests sent within the window and answered, over the time from
    its opening until the last request sent in it was done with.

    Every request of the population counts with all of its time: a stall
    anywhere, also at the end, lowers the rate.  A closed loop sends
    nothing after the close and drains what is in flight, so the groups
    that straddle the close count whole, with their work and their time."""
    span = win.t_end - win.t_open
    return len(win.ok) / span if span > 0 else 0.0


def end_to_end(win: Window, setup_s: float) -> dict:
    lat = latency_ms(win)
    return {"p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "p90_ms": float(np.percentile(lat, 90)) if len(lat) else None,
            "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
            "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
            "rps": answered_rps(win),
            "setup_s": setup_s}


def check_answers(served: Served, records: list[dict]):
    """Largest relative error of the sampled answers against the
    reference, and the request that gave it."""
    model = served.cell.model
    worst, worst_i = 0.0, None
    for r in records:
        ref = reference.answer(model, served.graph.neighbors, served.table,
                               served.params, r["targets"], r["seed"],
                               served.cell.fanouts)
        err = reference.rel_l2(r["rows"], ref)
        if not err <= worst:
            worst, worst_i = err, r["i"]
    return worst, worst_i


def work_counts(served: Served, records: list[dict], tr: dict | None):
    """Operations and bytes the served requests needed, from their real
    sampled rows and live slots (the reference's sampler)."""
    if tr is None or not records:
        return None
    L = len(served.cell.fanouts)
    rows, slots = [0] * L, [0] * L
    for r in records:
        _, blocks = reference.sample(served.graph.neighbors, r["targets"],
                                     r["seed"], served.cell.fanouts)
        for l, (_, mask) in enumerate(blocks):
            rows[l] += mask.shape[0]
            slots[l] += int(mask.sum())
    layers = served.cell.model.layer_work(served.cell.widths, rows, slots)
    flops = sum(x["flops"] for x in layers)
    calls = tr["module_calls"]
    nbytes = sum(x["bytes"] for x in layers) + calls * sum(
        served.cell.model.weight_bytes(served.cell.widths))
    return {"flops": flops, "bytes": nbytes, "calls": calls}


def run(args, *, root: Path = ROOT, require_chip: bool = True,
        use_cache: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    bench = Bench(root)
    cell = Cell(bench, args.workload)
    peaks_all = bench.peaks()
    import jax
    if require_chip:
        device = check_chip(int(cell.entry["chips"]), peaks_all)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    peaks = peaks_all.get(device["kind"])
    if use_cache:
        from repro.compile_cache import use_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"compile cache: {use_compile_cache()}")
    # process-wide, so that the serving threads compute at it too
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      cell.cfg["matmul_precision"])
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(args, bench, cell, device, peaks, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        jax.config.update("jax_default_matmul_precision", precision)


def _run(args, bench: Bench, cell: Cell, device: dict, peaks: dict | None,
         compiles: Compiles) -> dict:
    import jax
    log(f"device: {device}; cell {cell.name}: config {cell.cfg['name']}, "
        f"traffic {cell.spec['traffic']}, seed {args.seed}")

    served = Served(cell, args.seed)
    n0 = len(compiles.events)
    served.prepare()
    warm = compiles.events[n0:]
    log(f"set-up: {json.dumps({k: round(v, 3) for k, v in served.setup_log.items()})}; "
        f"{len(served.shapes)} bucket shapes; {len(warm)} compiles or cache "
        f"loads in set-up, {sum(s for _, s in warm):.3f} s")

    plan = served.requests("traffic", args.seconds)
    before = served.stats()
    trace_dir = bench.dir / "out" / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    jax.config.update("jax_log_compiles", True)   # names any in the window
    t_trace = time.perf_counter()
    setup_s = t_trace - T_START
    win = served.serve(plan, float(args.seconds))
    jax.config.update("jax_log_compiles", False)
    window_s = win.t_end - t_trace
    if args.trace:
        jax.profiler.stop_trace()
    after = served.stats()
    in_window = compiles.since(win.t_open)
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    device["memory_peak_bytes"] = int(mem) if mem is not None else None
    e2e = end_to_end(win, setup_s)
    late = np.array([r["late"] for r in win.records]) * 1e3
    log(f"window: {len(win.records)} requests, {len(win.ok)} answered, "
        f"{len(win.failed)} failed; latency ms p50 {e2e['p50_ms']} p90 "
        f"{e2e['p90_ms']} p95 {e2e['p95_ms']} p99 {e2e['p99_ms']}; "
        f"{sum(r['t_done'] <= win.t_close for r in win.ok)} answered "
        f"before the close, the last {win.t_end - win.t_open:.3f} s after "
        f"the opening: {e2e['rps']} req/s; {len(in_window)} compiles in "
        f"the window")
    if len(win.records) >= 3:
        lat = latency_ms(win)
        order = np.argsort([r["due"] for r in win.records])
        log("p50 ms by third of the window: " + ", ".join(
            f"{np.median(lat[part]):.3f}" for part in np.array_split(order, 3)))
    if win.kind == "open" and len(late):
        log(f"generator lateness: p50 {np.percentile(late, 50):.3f} ms, "
            f"p99 {np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms")
    for r in win.failed[:3]:
        log(f"failed request {r['i']}: {r['error'][:300]}")
    served.stop()

    t = time.perf_counter()
    ok = win.ok
    want = int(cell.cfg["correct"]["sample"])
    pick = rng_for(args.seed, "sample").permutation(len(ok))[:want]
    sample = [ok[i] for i in sorted(pick)]
    worst, worst_i = check_answers(served, sample)
    limit = float(cell.cfg["correct"]["rel_l2_limit"])
    checks = {"failed_requests": {"value": len(win.failed), "limit": 0},
              "answers_compared": {"value": len(sample),
                                   "limit": min(want, len(win.records))},
              "rel_l2_worst": {"value": worst, "limit": limit}}
    correct = (len(win.failed) == 0 and len(sample) >= min(want,
                                                           len(win.records))
               and len(win.records) > 0 and worst <= limit)
    log(f"reference: {len(sample)} answers in {time.perf_counter() - t:.3f} s;"
        f" worst relative error {worst:.6g} (request {worst_i})")

    result = {"correct": bool(correct), "attempted": len(win.records),
              "failed": len(win.failed)}
    metrics: dict = {}
    if not args.trace:
        for m in bench.end_to_end(cell.name):
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        events = btrace.load(str(trace_dir))
        tr = btrace.reduce(events, window_s, module=MODULE, kernel=KERNEL)
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = window_s
        log(f"trace: {len(events)} device events; busy {tr['busy_s']:.6f} s "
            f"of {window_s:.6f} s; program {tr['module_calls']} calls "
            f"{tr['module_s']:.6f} s; kernel {tr['kernel_calls']} calls "
            f"{tr['kernel_s']:.6f} s")
        log(f"top device ops: {json.dumps(tr['device_ops'])}")
        work = work_counts(served, ok, tr)
        if work is not None and peaks is not None:
            t_flop = work["flops"] / peaks["bf16_flops_per_s"]
            t_byte = work["bytes"] / peaks["hbm_bytes_per_s"]
            work["kernel_min_s"] = max(t_flop, t_byte)
            log(f"work: {work['flops']:.6g} flop, {work['bytes']:.6g} B over "
                f"{work['calls']} program calls; the roofline is "
                f"{'compute' if t_flop >= t_byte else 'memory'}-bound")
        ctx = {"before": before, "after": after, "completed": len(ok),
               "compiles": in_window, "trace": tr, "work": work,
               "peaks": peaks, "cell": cell.name}
        for m in bench.per_layer(cell.name):
            v = bench.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs only on a TPU",
              file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
