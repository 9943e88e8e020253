"""The benchmark harness: one run of one cell.

Everything a cell is made of is found by name under the checkout, so a
later change adds a configuration, a mix or a metric as new files:

* ``BENCHMARK.json`` names the cell, its configuration and its traffic;
* ``perfbench/configs/<config>.json`` holds the deployment (data set,
  sizes, guarantees);
* ``perfbench/traffic/<traffic>.json`` holds the mix, read by
  ``traffic/gen.py``;
* ``perfbench/metrics/<metric>.py`` reads one metric from a finished
  ``Run``; the harness asks each metric that the cell reports.

A run: check the device, build the index from ``--seed`` (set-up),
pre-fill the write tiers where the mix asks for it, warm every shape the
mix uses, drive the front end for ``--seconds`` (the window) in the
mix's closed or open loop, drain, read the metrics, free the index, and only then
replay the window's batches through the plain reference
(``reference.py``) to decide ``correct``.  With ``--trace 1`` a few
seconds of the window are profiled and the per-layer metrics are
reported instead of the end-to-end ones.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from perfbench.reference import Batch, Reference, Verdict, record
from perfbench.traffic.gen import RequestStream, arrival_times, load_mix
from perfbench.traffic.keys import make_keys, split_half

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# host spans the benchmark writes around its calls into each layer
SPANS = ("gen", "fe.step", "index.lookup_async", "index.finish",
         "index.scan", "index.insert")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ discovery
@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic: str
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def discover(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, its mix and the metrics it reports."""
    bench = _bench(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    return _cell(bench, workload, w["config"], w["traffic"], int(w["chips"]),
                 root)


def _bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _cell(bench, name, config_name, traffic, chips, root) -> Cell:
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[config_name]["file"])) as f:
        config = json.load(f)
    mix = load_mix(traffic, os.path.join(root, "perfbench", "traffic"))
    return Cell(
        name=name, config_name=config_name, config=config, traffic=traffic,
        mix=mix, chips=chips,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_reader(metric: str, root: str = ROOT):
    """``read(run)`` of ``perfbench/metrics/<metric>.py``."""
    path = os.path.join(root, "perfbench", "metrics", f"{metric}.py")
    mod_name = "perfbench_metric_" + "".join(
        c if c.isalnum() else "_" for c in metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------- instrumentation
def span(on: bool, name: str):
    """A host span in the profiler's trace while one is being taken."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Backend compiles and persistent-cache hits in this process
    (``jax.monitoring`` events)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class SpannedIndex:
    """The index as the front end sees it, with a host span around each
    call into it while a trace is being taken, and a record of what the
    traced calls asked for (the per-layer readers divide by it).

    ``insert_calls``, where a list, gets the clock and the length of
    each insert call; with ``watch_fold`` set, ``fold_started`` becomes
    the clock of the first insert call after which a fold is in
    flight."""

    def __init__(self, index):
        self.index = index
        self.tracing = False
        self.traced = {"point_keys": [], "range_lo": [], "range_hi": [],
                       "insert_calls": 0}
        self.insert_calls = None
        self.watch_fold = False
        self.fold_started = None

    def _span(self, name):
        return span(self.tracing, name)

    def lookup_batch_async(self, keys):
        if self.tracing:
            self.traced["point_keys"].append(np.array(keys, np.float64))
        with self._span("index.lookup_async"):
            fin = self.index.lookup_batch_async(keys)

        def finish():
            with self._span("index.finish"):
                return fin()

        return finish

    def scan_batch(self, lo, hi):
        if self.tracing:
            self.traced["range_lo"].append(np.array(lo, np.float64))
            self.traced["range_hi"].append(np.array(hi, np.float64))
        with self._span("index.scan"):
            return self.index.scan_batch(lo, hi)

    def insert_batch(self, keys, payloads):
        if self.tracing:
            self.traced["insert_calls"] += 1
        t = time.perf_counter()
        with self._span("index.insert"):
            out = self.index.insert_batch(keys, payloads)
        if self.insert_calls is not None:
            self.insert_calls.append((t, time.perf_counter() - t))
        if self.watch_fold and write_path(self.index).get("fold_active"):
            self.fold_started = t
            self.watch_fold = False
        return out


# --------------------------------------------------------------- driving
@dataclasses.dataclass
class Served:
    """What a stretch of the loop served: requests sent, each one's
    latency and the clock when it came back."""

    sent: int
    latency_s: np.ndarray
    t_back: np.ndarray
    longest_step: tuple   # (seconds, clock at its end) of the loop's longest pass
    backlog: int = 0      # requests outstanding when ``until`` came


class Driver:
    """Drives the front end with a cell's mix and keeps the client's
    clock: a closed loop times each request from its submit, an open loop
    from its scheduled arrival, to the moment the loop hands its answer
    back.

    Every batch the front end dispatches goes into ``log`` in dispatch
    order, as a compact ``reference.Batch`` once it is answered; the
    requests themselves are dropped then, so the run holds no more
    request objects than are outstanding."""

    def __init__(self, fe, stream: RequestStream, mix: dict, clock,
                 request_cls, tracer=None):
        self.fe = fe
        self.stream = stream
        self.clock = clock
        self.Req = request_cls
        self.tracer = tracer
        self.deadline = float(mix["deadline_s"])
        self.rid = 0
        self.pending: list = []  # (log slot, op, batch), not yet answered
        self.log: list = []      # a Batch per dispatched batch, in order
        self.undispatched = 0    # shed or expired before a dispatch
        fe.on_batch_dispatched = self._on_dispatch

    def _on_dispatch(self, op, batch) -> None:
        self.pending.append((len(self.log), op, batch))
        self.log.append(None)

    def _span(self, name):
        return span(self.tracer is not None and self.tracer.tracing, name)

    def _submit(self, live: dict, t_sub: float | None = None) -> None:
        """The stream's next request, timed from ``t_sub`` (default: the
        clock as it is submitted)."""
        op, key, hi, pay = self.stream.next()
        r = self.Req(self.rid, op, key, hi=hi, payload=pay,
                     deadline_s=self.deadline)
        self.rid += 1
        r.t_sub = self.clock() if t_sub is None else t_sub
        live[r.rid] = r
        self.fe.submit(r)

    def _harvest(self, now: float, live: dict, lat: list, back: list) -> int:
        """Record the answered batches of ``pending``, stamped ``now``,
        and drop their requests from ``live``."""
        n = 0
        keep = []
        for slot, op, batch in self.pending:
            if batch[-1].state == "queued":
                keep.append((slot, op, batch))
                continue
            self.log[slot] = record(op, batch)
            t_sub = np.fromiter((live.pop(r.rid).t_sub for r in batch),
                                np.float64, len(batch))
            lat.append(now - t_sub)
            back.append(np.full(len(batch), now))
            n += len(batch)
        self.pending = keep
        return n

    def _sweep_undispatched(self, now: float, live: dict, lat: list,
                            back: list) -> int:
        """Requests the front end resolved without dispatching them
        (shed or expired in the queue); they count as unanswered."""
        gone = [r for r in live.values() if r.state != "queued"]
        for r in gone:
            del live[r.rid]
        lat.append(now - np.array([r.t_sub for r in gone]))
        back.append(np.full(len(gone), now))
        self.undispatched += len(gone)
        return len(gone)

    def _resolved(self) -> int:
        c = self.fe.counters
        return c["completed"] + c["shed"] + c["expired"]

    def closed(self, clients: int, until: float | None = None,
               n_requests: int | None = None) -> Served:
        """Keep ``clients`` requests outstanding until ``until`` (clock)
        or until ``n_requests`` were sent; then drain."""
        sent = 0

        def feed(live):
            nonlocal sent
            while len(live) < clients:
                self._submit(live)
                sent += 1
                if n_requests is not None and sent >= n_requests:
                    return True
            return False

        return self._loop(feed, until, lambda: sent)

    def open(self, arrivals: np.ndarray, until: float) -> Served:
        """Submit request ``i`` once the clock reaches ``arrivals[i]``,
        whether or not earlier ones were answered, for the arrivals
        before ``until``; then drain.  Each request is timed from its
        scheduled arrival, so a pass that blocks the loop counts in the
        latency of every request due during it.  With nothing to do the
        loop waits for the next arrival in sleeps of at most 0.1 ms."""
        n = int(np.searchsorted(arrivals, until, side="left"))
        i = 0

        def feed(live):
            nonlocal i
            now = self.clock()
            while i < n and arrivals[i] <= now:
                self._submit(live, float(arrivals[i]))
                i += 1
            return i >= n

        def wait(now):
            if i < n and arrivals[i] > now:
                time.sleep(min(arrivals[i] - now, 1e-4))

        return self._loop(feed, until, lambda: i, wait)

    def _loop(self, feed, until, n_sent, wait=None) -> Served:
        """The loop both drivers share: ``feed(live)`` submits what is
        due and says whether the last request was sent; then one
        ``fe.step``; answers are harvested; from ``until`` on nothing more
        is sent, and the loop drains.  ``wait(now)`` is called after a
        pass in which the front end had nothing to do."""
        fe = self.fe
        live: dict = {}            # rid -> request, outstanding
        lat: list = []
        back: list = []
        answered = 0
        backlog = None
        resolved0 = self._resolved()
        stop = False
        longest, prev = (0.0, 0.0), self.clock()
        while True:
            if not stop:
                with self._span("gen"):
                    stop = feed(live)
            with self._span("fe.step"):
                busy = fe.step(drain=stop)
            now = self.clock()
            if now - prev > longest[0]:
                longest = (now - prev, now)
            prev = now
            answered += self._harvest(now, live, lat, back)
            if self._resolved() - resolved0 != answered:
                answered += self._sweep_undispatched(now, live, lat, back)
            if until is not None and now >= until and backlog is None:
                stop = True
                backlog = len(live)
            if stop and not live:
                return Served(n_sent(), np.concatenate(lat or [np.zeros(0)]),
                              np.concatenate(back or [np.zeros(0)]), longest,
                              backlog or 0)
            if wait is not None and not busy and not stop:
                with self._span("gen"):
                    wait(now)


# ------------------------------------------------------------------ run
@dataclasses.dataclass
class Run:
    """What the metric readers read.  Times are seconds of the host
    clock unless named otherwise."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0            # requests sent in the window
    latency_s: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0))   # one per request sent
    completed_in_window: int = 0
    fe_window: dict = dataclasses.field(default_factory=dict)
    fe_traced: dict = dataclasses.field(default_factory=dict)  # while traced
    build: dict = dataclasses.field(default_factory=dict)
    use_flow: bool = False
    trace: object = None          # trace.Reduced, with --trace 1
    traced: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)
    point_bytes: float | None = None
    range_bytes: float | None = None


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    peak = 0
    for d in jax.devices():
        try:
            st = d.memory_stats() or {}
        except Exception:  # a backend without memory stats
            st = {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def load_peaks(kind: str, root: str = ROOT) -> dict:
    """The published peaks of ``device_kind`` (``perfbench/peaks.json``);
    a kind missing from the table is an error."""
    with open(os.path.join(root, "perfbench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"device_kind {kind!r} is not in perfbench/peaks.json")
    return table["devices"][kind]


def build_nfl(cell: Cell, load_keys, load_payloads):
    """The system under test: the flat NFL of the configuration, bulk
    loaded."""
    from repro.core.nfl import NFL, NFLConfig

    idx = cell.config["index"]
    nfl = NFL(NFLConfig(backend=idx["backend"], shards=int(idx["shards"]),
                        force_flow=idx.get("force_flow")))
    nfl.bulkload(load_keys, load_payloads)
    return nfl


def _pow2_buckets(max_batch: int) -> list:
    out, b = [], 64
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return sorted(set(out))


def warm_up(nfl, cell: Cell, stream: RequestStream, load_keys, log_: list,
            max_batch: int) -> None:
    """Every shape the mix's ops take: one insert batch, then each
    power-of-two batch bucket of each read op, with the write tiers as
    the window will find them.  What is sent goes into ``log_`` for the
    reference."""
    mix = cell.mix["mix"]
    rng = np.random.default_rng(0)
    if mix.get("insert"):
        k, p = stream.take_inserts(1)
        nfl.insert_batch(k, p)
        log_.append(Batch("insert", k, pays=p))
    for b in _pow2_buckets(max_batch):
        if mix.get("point"):
            q = rng.choice(load_keys, b, replace=False)
            got = np.asarray(nfl.lookup_batch(q), np.int64)
            log_.append(Batch("point", q, got=got))
        if mix.get("range"):
            s = rng.integers(0, load_keys.shape[0] - 101, b)
            nfl.scan_batch(load_keys[s], load_keys[s + 50])


def prefill_sizes(mix: dict) -> list:
    """The batch sizes of the mix's pre-fill: ``prefill_inserts`` keys in
    batches of ``prefill_batch``, the last ``prefill_warm_sizes`` (k)
    batches holding k, k - 1, ..., 1 keys, so that every insert batch
    size up to k has run before the window."""
    n = int(mix.get("prefill_inserts", 0))
    warm = list(range(int(mix.get("prefill_warm_sizes", 0)), 0, -1))
    rest = n - sum(warm)
    if rest < 0:
        raise ValueError("prefill_inserts is smaller than the warm sizes")
    size = int(mix.get("prefill_batch", rest or 1))
    return [size] * (rest // size) + ([rest % size] if rest % size else []) \
        + warm


def prefill(nfl, mix: dict, stream: RequestStream, log_: list) -> None:
    """Insert the mix's pre-fill (``prefill_sizes``) from the unloaded
    half through the index's public ``insert_batch``, and log each batch
    for the reference."""
    sizes = prefill_sizes(mix)
    if not sizes:
        return
    t = time.perf_counter()
    for size in sizes:
        k, p = stream.take_inserts(size)
        nfl.insert_batch(k, p)
        log_.append(Batch("insert", k, pays=p))
    log(f"prefill: {sum(sizes)} inserts in {len(sizes)} batches "
        f"(sizes {sizes[0]}, then {sorted(set(sizes[1:]), reverse=True)}), "
        f"{time.perf_counter() - t:.2f} s; write path {write_path(nfl)}")


def write_path(nfl) -> dict:
    """The write tiers and the fold as ``FlatAFLI.stats()`` gives them."""
    idx = getattr(nfl, "index", None)
    st = idx.stats() if idx is not None and hasattr(idx, "stats") else {}
    return {k: st[k] for k in ("n_keys", "delta_len", "run_len",
                                "fold_active", "n_rebuilds", "n_reflows")
            if k in st}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, t_start: float | None = None,
             require_chip: bool = True, system=None, out=None,
             keep_trace: str | None = None) -> int:
    """One run of ``workload``; prints the result line on ``out``.

    ``system(cell, load_keys, load_payloads)`` builds what is measured
    (default ``build_nfl``); the control puts the reference there.
    ``require_chip=False`` lets a test drive the rest of a run on the
    CPU."""
    out = out or sys.stdout
    t_start = time.perf_counter() if t_start is None else t_start
    clock = time.perf_counter
    cell = discover(workload, root)

    import jax

    dev = device_info(jax)
    log(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"device_count={dev['count']} cell={cell.name} seed={seed} "
        f"seconds={seconds} trace={int(trace)}")
    if require_chip and (dev["platform"] != "tpu" or dev["count"] < cell.chips):
        log(f"FAIL: the cell needs {cell.chips} TPU chip(s); JAX found "
            f"{dev['count']} {dev['platform']} device(s)")
        return 2
    peaks = load_peaks(dev["kind"], root) if require_chip else {}

    from repro.kernels.backend import enable_compile_cache
    from repro.serve.frontend import FrontEnd, FrontEndConfig, ServiceRequest

    cache_dir = enable_compile_cache()
    counter = CompileCounter()

    # ---- set-up: data from the seed, the index, warm-up
    cfg = cell.config
    ss_keys, ss_traffic, ss_arrivals = np.random.SeedSequence(seed).spawn(3)
    rng = np.random.default_rng(ss_keys)
    keys = make_keys(cfg["dataset"], int(cfg["n_keys"]), rng)
    load_k, load_p, ins_k, ins_p = split_half(keys, rng)
    del keys
    build = system or build_nfl
    nfl = build(cell, load_k, load_p)
    run = Run(cell=cell, seed=seed, seconds=seconds, peaks=peaks)
    run.use_flow = bool(getattr(nfl, "use_flow", False))
    run.build = dict(getattr(nfl, "metrics", {}) or {})
    log(f"use_flow={run.use_flow} build={json.dumps(run.build)} "
        f"compile_cache={cache_dir}")
    stream = RequestStream(cell.mix, load_k, ins_k, ins_p,
                           np.random.default_rng(ss_traffic))
    setup_log: list = []
    fecfg = FrontEndConfig()
    prefill(nfl, cell.mix, stream, setup_log)
    warm_up(nfl, cell, stream, load_k, setup_log, fecfg.max_batch)
    spanned = SpannedIndex(nfl)
    fe = FrontEnd(spanned, fecfg)
    drv = Driver(fe, stream, cell.mix, clock, ServiceRequest, spanned)
    drv.log = setup_log
    is_open = cell.mix["loop"] == "open"
    clients = int(cell.mix["warmup_clients" if is_open else "clients"])
    drv.closed(clients, n_requests=int(cell.mix["warmup_requests"]))
    if is_open:
        # one block of gaps lasts the window: every seed is offered the
        # same number of requests in it
        rate = float(cell.mix["rate_per_s"])
        block = max(round(rate * seconds), 1)
        arrivals = arrival_times(rate, 2 * block,
                                 np.random.default_rng(ss_arrivals), block)
    write0 = write_path(nfl)
    spanned.watch_fold = bool(cell.mix.get("prefill_inserts")
                              and not write0.get("fold_active"))
    statics0 = _serving_statics(nfl)
    spanned.insert_calls = []
    fe0 = dict(fe.counters)
    compiles0, hits0 = counter.compiles, counter.cache_hits

    # ---- the window.  The set-up heap is frozen out of the cyclic
    # collector's passes; the collector itself stays on, as it is in a
    # deployment, and sees the front end's own allocations.
    tmp = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    gc.collect()
    gc.freeze()
    gc_log = _GcLog(clock)
    jax.config.update("jax_log_compiles", True)  # names any compile
    t0 = clock()
    run.setup_s = t0 - t_start
    if trace:
        trace_window = _TraceWindow(jax, tmp, spanned, fe,
                                    t0 + min(1.0, seconds / 4),
                                    min(3.0, seconds / 2), clock)
        fe_step = fe.step

        def step(drain: bool = False):
            trace_window.poll()
            return fe_step(drain)

        fe.step = step
    if is_open:
        served = drv.open(t0 + arrivals, until=t0 + seconds)
    else:
        served = drv.closed(clients, until=t0 + seconds)
    t_end = t0 + seconds
    gc_log.close()
    jax.config.update("jax_log_compiles", False)
    if trace:
        trace_window.finish()
        fe.step = fe_step
        run.fe_traced = trace_window.fe_counts()
    run.window_s = seconds
    run.attempted = served.sent
    run.latency_s = served.latency_s
    run.completed_in_window = int((served.t_back <= t_end).sum())
    window_compiles = counter.compiles - compiles0
    window_loads = counter.cache_hits - hits0
    run.fe_window = {k: fe.counters[k] - fe0[k] for k in fe0}
    n_sl = max(int(seconds), 1)
    slices = np.histogram(served.t_back - t0, bins=n_sl,
                          range=(0.0, seconds))[0] * n_sl / seconds
    log(f"ops/s per second of the window: {slices.tolist()}")
    log(f"longest loop pass: {served.longest_step[0] * 1e3:.1f} ms, ending "
        f"{served.longest_step[1] - t0:.2f} s into the window; "
        f"{gc_log.summary(t0)}")
    log(f"write path at the window's start {json.dumps(write0)}, at its "
        f"end {json.dumps(write_path(nfl))}; "
        + ("a fold was in flight at the start" if write0.get("fold_active")
           else "no fold started in the window"
           if spanned.fold_started is None else
           f"the fold started {spanned.fold_started - t0:.3f} s into the "
           "window"))
    log(_insert_calls_summary(spanned.insert_calls, t0))
    if is_open:
        log(f"open loop: offered {rate} /s, sent {served.sent}, backlog at "
            f"the window's end {served.backlog} "
            f"({served.backlog / rate:.3f} s of arrivals)")
    statics1 = _serving_statics(nfl)
    if statics1 != statics0:
        log(f"serving statics moved in the window: {statics0} -> "
            f"{statics1}")
    log(f"window: sent={served.sent} completed_in_window="
        f"{run.completed_in_window} compiles_in_window={window_compiles} "
        f"cache_loads_in_window={window_loads} setup_compiles={compiles0} "
        f"setup_cache_loads={hits0} "
        f"gc_collections={[g['collections'] for g in gc.get_stats()]} "
        f"fe={json.dumps(run.fe_window)}")
    if hasattr(nfl, "dispatch_stats"):
        d = nfl.dispatch_stats()["dispatch"]
        log("dispatch: " + json.dumps(
            {k: v for k, v in d.items() if k != "fallback_reasons"},
            sort_keys=True))

    # ---- per-layer inputs that need the live index
    if trace:
        from perfbench.trace import reduce_trace

        t_red = clock()
        run.trace = reduce_trace(tmp, SPANS)
        log(f"trace: stop {trace_window.stop_s:.1f} s, read and reduce "
            f"{clock() - t_red:.1f} s")
        if keep_trace:
            shutil.copytree(tmp, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tmp, ignore_errors=True)
        run.traced = spanned.traced
        from perfbench import roofline

        run.point_bytes = roofline.traced_point_bytes(nfl, run.traced)
        run.range_bytes = roofline.traced_range_bytes(
            nfl, run.traced, load_k)

    # ---- metrics
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in names:
        v = load_reader(m["name"], root)(run)
        if v is None:
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(dev)
    device["memory_peak_bytes"] = memory_peak(jax)
    breakdown = None
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()

    # ---- correctness, after the index is freed
    scan_cap = int(getattr(getattr(getattr(nfl, "cfg", None),
                                   "flat_index", None), "scan_cap", 128))
    use_flow = run.use_flow
    del nfl, spanned, fe
    t_ref = clock()
    verdict = check(drv.log, load_k, load_p, scan_cap, use_flow)
    log(f"reference replay: {clock() - t_ref:.3f} s over "
        f"{sum(verdict.checked.values())} answers; truncated ranges "
        f"{verdict.truncated}; examples {verdict.examples[:3]}")
    n_unanswered = verdict.unanswered + drv.undispatched
    checks = {
        "wrong_answers": {"value": verdict.n_wrong, "limit": 0},
        "unanswered": {"value": n_unanswered, "limit": 0},
    }
    if getattr(verdict, "flow_ranges", 0):
        checks["ranges_under_flow"] = {"value": verdict.flow_ranges,
                                       "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for k, c in checks.items():
        log(f"check {k}={c['value']} limit={c['limit']}")
    result = {"correct": correct, "attempted": run.attempted,
              "failed": verdict.n_wrong + n_unanswered,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), file=out, flush=True)
    return 0


def _insert_calls_summary(calls: list, t0: float) -> str:
    """The insert calls of the window and its drain: count, median,
    90th percentile and longest, and the five longest with their start
    (seconds into the window)."""
    if not calls:
        return "insert calls: none"
    ms = np.array([d for _, d in calls]) * 1e3
    top = sorted(calls, key=lambda c: -c[1])[:5]
    return (f"insert calls: {len(calls)}, median {np.median(ms):.2f} ms, "
            f"p90 {np.percentile(ms, 90):.2f} ms, longest "
            + ", ".join(f"{d * 1e3:.1f} ms at {t - t0:.2f} s"
                        for t, d in top))


def check(log_: list, load_k, load_p, scan_cap: int,
          use_flow: bool) -> Verdict:
    """Replay every batch sent to the index, in dispatch order, through
    the reference.  Ranges under a flow are ordered by the flow's
    positioning key, which the reference does not compute: such a range
    is counted apart and fails the run."""
    ref = Reference(load_k, load_p, np.float64, scan_cap)
    if use_flow:
        v = ref.replay([b for b in log_ if b.op != "range"])
        v.flow_ranges = sum(len(b.keys) for b in log_ if b.op == "range")
        return v
    return ref.replay(log_)


def _serving_statics(nfl) -> dict:
    """The index's serving statics (``FlatAFLI.stats()``): a move of one
    in the window compiles."""
    idx = getattr(nfl, "index", None)
    st = idx.stats() if idx is not None and hasattr(idx, "stats") else {}
    return {k: v for k, v in st.get("serving", {}).items() if k in (
        "static_max_depth", "static_dense_window", "run_window",
        "delta_window", "scan_window", "run_capacity", "delta_capacity",
        "scan_capacity")}


class _GcLog:
    """The cyclic collector's passes while it is open: count, total and
    longest time per generation."""

    def __init__(self, clock):
        self.clock = clock
        self.t = 0.0
        self.passes: list = []
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info) -> None:
        if phase == "start":
            self.t = self.clock()
        else:
            self.passes.append((info["generation"], self.clock() - self.t,
                                self.t))

    def close(self) -> None:
        gc.callbacks.remove(self._cb)

    def summary(self, t0: float) -> str:
        out = []
        for g in range(3):
            d = [(s, t) for gen, s, t in self.passes if gen == g]
            if d:
                s, t = max(d)
                out.append(f"gen{g} {len(d)} passes {sum(x for x, _ in d) * 1e3:.1f}"
                           f" ms, longest {s * 1e3:.2f} ms at {t - t0:.2f} s")
        return "gc: " + ("; ".join(out) or "no passes")


class _TraceWindow:
    """Starts the profiler at the first poll from ``t_start`` on and
    stops it ``length`` seconds after it started (polled from the loop),
    switching the benchmark's spans on inside.  Where the run awaits a
    fold (``SpannedIndex.watch_fold``), the start also waits for the
    insert call that starts it, up to ``t_start + length``, so the trace
    covers the fold's ticks and not, in some seeds, its start.  The
    front end's counters are read as the trace starts and just before it
    is stopped, so the stop's stall is not counted."""

    def __init__(self, jax, path, spanned, fe, t_start, length, clock):
        self.jax, self.path, self.spanned = jax, path, spanned
        self.fe = fe
        self.t_start, self.length, self.clock = t_start, length, clock
        self.t_stop = None
        self.state = 0
        self.stop_s = 0.0
        self.fe0: dict = {}
        self.fe1: dict = {}

    def fe_counts(self) -> dict:
        """The front end's counters over the traced stretch."""
        return {k: self.fe1[k] - self.fe0[k] for k in self.fe1}

    def poll(self) -> None:
        now = self.clock()
        if self.state == 0 and now >= self.t_start and (
                not self.spanned.watch_fold
                or now >= self.t_start + self.length):
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # the benchmark's spans only
            opts.host_tracer_level = 1
            self.jax.profiler.start_trace(self.path, profiler_options=opts)
            self.fe0 = dict(self.fe.counters)
            self.spanned.tracing = True
            self.t_stop = now + self.length
            self.state = 1
        elif self.state == 1 and now >= self.t_stop:
            self.stop()

    def stop(self) -> None:
        self.spanned.tracing = False
        self.fe1 = dict(self.fe.counters)
        t = self.clock()
        self.jax.profiler.stop_trace()
        self.stop_s = self.clock() - t
        self.state = 2

    def finish(self) -> None:
        if self.state == 1:
            self.stop()


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once; the last stdout line "
                    "is the result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profile of a --trace 1 run here")
    a = ap.parse_args(argv)
    try:
        return run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                        t_start=t_start, keep_trace=a.keep_trace)
    except ImportError as e:
        log(f"FAIL: cannot import the system under test ({e}); run from "
            "the root of a checkout")
        return 2
