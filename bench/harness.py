"""One run of one cell: set up the server, drive it from the load
generator for the window, check the replies, reduce the metrics.

Everything that differs between cells comes from the files the cell
names (``BENCHMARK.json``'s ``workloads`` entry): the configuration
(``bench/configs/<config>.json``: model factory, serving dtype, corpus,
tenants, mesh, scorer, frontend and server options, comparison limits),
the model module the configuration names (``bench/models/<m>.py``:
layout, weights, ids, float64 reference, work counts, kernel name), the
traffic mix (``bench/traffic/<traffic>.json``: loop, rate or clients, K
law, context law, tenant law, bursts, writer) and one reader per metric
(``bench/metrics/<name>.py``).  There is no branch on a cell's name or a
model's.

The path driven is the one users are served through: the load generator
(a child process that never imports JAX) -> loopback TCP -> ``RpcServer``
-> ``QueryFrontend`` (pumped by the server) -> ``CorpusState`` on a
``ScorerRuntime`` -> the model's kernel.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import io
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from bench import reference, traffic, work

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


# -- specification ----------------------------------------------------------

def load_cell(workload: str):
    """(benchmark, cell, configuration, mix) for a workload name."""
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json")))
    return bm, cell, config, mix


def metrics_of(bm: dict, workload: str, trace: bool) -> list[dict]:
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def reader(name: str):
    """The ``read(run)`` function of a metric: ``bench/metrics/<name>.py``,
    else the file named by the part before the first dot (one reader
    serves ``device_idle.auction`` and ``device_idle.retrieval``)."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader bench/metrics/{name}.py")


def model_module(name: str):
    """The module a configuration names by ``model.bench_model``:
    ``bench/models/<name>.py`` (a relative path from there may name one
    elsewhere under ``bench``, as the tests' modules do), loaded once."""
    path = os.path.normpath(os.path.join(BENCH, "models", name + ".py"))
    key = "bench_model_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH))
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


# -- what a metric reader sees ----------------------------------------------

@dataclasses.dataclass
class Run:
    seconds: float
    setup_s: float
    latency_ms: np.ndarray        # every answered request of the window
    replies_in_window: int        # replies received inside the window
    write_ms: np.ndarray          # catalogue writes called in the window
    frontend: dict                # QueryFrontend.stats change over window
    request_flops: float          # the model's FLOPs of one request
    launch_flops: float           # kernel FLOPs of a launch at mean rows
    launch_bytes: float           # kernel HBM bytes of a launch at mean rows
    peak: dict                    # work.peaks of the device kind
    trace: dict | None            # devtrace.reduce of the traced window

    def pct(self, q: float) -> float | None:
        if len(self.latency_ms) == 0:
            return None
        return float(np.percentile(self.latency_ms, q))


# -- the server side --------------------------------------------------------

class Compiles:
    """Times (monotonic) at which JAX traced or compiled anything."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.times.append(time.monotonic())

    def within(self, lo, hi) -> int:
        return sum(lo <= t <= hi for t in self.times)


class Writer:
    """The catalogue writer of a mix with a ``writer`` block: at its rate,
    cycling remove / add / update of ``items`` slots each, taken from
    the hot slots (those served at the top for the most popular
    contexts, where churn changes replies), and one model refresh at
    ``refresh_at`` of the window; an update writes ``item_rows(n, g)``.
    Logs every call into the tenant's ``reference.Catalogue``; write
    latency is call to return."""

    def __init__(self, fe, spec, item_rows, hot, slab, cat, params1, tenant,
                 seed):
        self.fe, self.spec, self.hot, self.slab = fe, spec, hot, slab
        self.item_rows = item_rows
        self.cat, self.params1, self.tenant = cat, params1, tenant
        self.g = traffic.rng(seed, traffic.WRITER)
        self.n = int(spec["items"])
        del hot[len(hot) - len(hot) % self.n:]
        self.p = 0
        self.log: list[tuple[float, float, str]] = []
        self.error: BaseException | None = None
        self.thread: threading.Thread | None = None

    def start(self, t0: float, seconds: float) -> None:
        """Write through the window ``[t0, t0 + seconds)`` on a thread."""
        times = traffic.writer_times(self.spec, seconds)
        ops = self.spec["ops"]
        plan = [(t0 + t, ops[i % len(ops)]) for i, t in enumerate(times)]
        if self.spec.get("refresh_at") is not None:
            plan.append((t0 + float(self.spec["refresh_at"]) * seconds,
                         "refresh"))
            plan.sort()
        self.thread = threading.Thread(target=self._run, args=(plan,),
                                       name="bench-writer", daemon=True)
        self.thread.start()

    def join(self) -> None:
        self.thread.join()
        if self.error is not None:
            raise self.error

    def _pair(self):
        a = self.hot[self.p:self.p + self.n]
        self.p = (self.p + self.n) % len(self.hot)
        return np.array(a, np.int32)

    def step(self, op: str) -> None:
        import jax
        kw = {"tenant": self.tenant}
        t = time.monotonic()
        with jax.profiler.TraceAnnotation(f"bench.write.{op}"):
            if op == "remove":
                slots = self._pair()
                self._removed = (slots, self.slab[slots].copy())
                self.fe.remove_items(slots, **kw)
                done = time.monotonic()
                self.cat.remove(t, done, slots)
            elif op == "add":
                old, ids = self._removed
                slots = np.asarray(self.fe.add_items(ids, **kw))
                done = time.monotonic()
                self.slab[slots] = ids
                self.cat.write(t, done, slots, ids)
                for o, s in zip(old, slots):
                    self.hot[self.hot.index(int(o))] = int(s)
            elif op == "update":
                slots = self._pair()
                ids = self.item_rows(self.n, self.g)
                self.fe.update_items(slots, ids, **kw)
                done = time.monotonic()
                self.slab[slots] = ids
                self.cat.write(t, done, slots, ids)
            else:
                self.fe.refresh(self.params1, step=1, **kw)
                done = time.monotonic()
                self.cat.refresh(t, done, 1)
        self.log.append((t, done, op))

    def _run(self, plan) -> None:
        try:
            for when, op in plan:
                wait = when - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                self.step(op)
        except BaseException as exc:   # noqa: BLE001 — reported by the run
            self.error = exc


def _factory(path: str):
    mod, fn = path.split(":")
    return getattr(importlib.import_module(mod), fn)


def _start_loadgen(header: dict, pool: np.ndarray):
    proc = subprocess.Popen([sys.executable,
                             os.path.join(BENCH, "loadgen.py")],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    data = np.ascontiguousarray(pool, np.int32).tobytes()
    header = dict(header, pool_bytes=len(data))
    proc.stdin.write(json.dumps(header).encode() + b"\n" + data)
    proc.stdin.flush()
    return proc


def _expect(proc, word: bytes) -> bytes:
    line = proc.stdout.readline()
    if not line.startswith(word):
        proc.kill()
        proc.wait()
        raise RuntimeError(f"load generator said {line!r}, not {word!r}")
    return line


def _collect(proc) -> dict:
    n = int(_expect(proc, b"RESULT").split()[1])
    blob = proc.stdout.read(n)
    if proc.wait(timeout=120) != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}")
    with np.load(io.BytesIO(blob)) as z:
        return {k: z[k] for k in z.files}


class Served:
    """The server side of a run, set up from a configuration and a mix:
    weights, corpora, engine states, frontend, RPC server, warmed to the
    shapes the mix reaches."""

    def __init__(self, config: dict, mix: dict, seed: int, *, chips: int,
                 require_tpu: bool, control: str | None, log):
        import jax

        from repro.launch.compile_cache import (cache_counts,
                                                enable_compile_cache)
        from repro.launch.mesh import make_host_mesh
        from repro.serving import (CorpusState, QueryFrontend,
                                   ScorerRuntime, serve_in_thread)

        cache_dir = enable_compile_cache()
        self.devs = jax.devices()[:chips]
        dev = self.devs[0]
        log(f"device: platform={dev.platform} kind={dev.device_kind} "
            f"count={len(jax.devices())}")
        if require_tpu and (dev.platform != "tpu"
                            or len(jax.devices()) < chips):
            raise SystemExit(f"bench: this cell needs {chips} TPU chip(s); "
                             f"JAX found {len(jax.devices())} "
                             f"{dev.platform} device(s)")
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.peak = work.peaks(dev.device_kind) if dev.platform == "tpu" \
            else {}
        self.compiles = Compiles()
        self.config, self.seed = config, seed

        model, corpus = config["model"], config["corpus"]
        self.model = mod = model_module(model["bench_model"])
        self.lay = lay = mod.Layout(model)
        cfg = _factory(model["factory"])()
        mod.check(cfg, lay)
        cfg = dataclasses.replace(
            cfg, dtype=jax.numpy.dtype(control or model["dtype"]))
        zipf_a = float(corpus["id_zipf_a"])
        self.n_items = n_items = int(corpus["n_items"])
        self.capacity = capacity = int(corpus["capacity"])
        n_tenants = int(corpus["tenants"])
        self.names = names = ([f"t{i}" for i in range(n_tenants)]
                              if n_tenants > 1 else [""])

        # -- build: weights, corpora, engine states, frontend -------------
        t = time.monotonic()
        self.snaps = mod.draw(lay, seed)
        served = self.snaps
        if control:
            served = tuple(jax.tree.map(lambda a: a.astype(cfg.dtype), s)
                           for s in self.snaps)
        g_items = traffic.rng(seed, traffic.ITEMS)
        items = {nm: mod.item_rows(model, n_items, g_items, zipf_a)
                 for nm in names}
        self.pool = mod.query_rows(model, int(corpus["context_pool"]),
                                   traffic.rng(seed, traffic.POOL), zipf_a)
        mesh = (make_host_mesh(model=int(config["mesh"]["model"]))
                if config.get("mesh") else None)
        self.runtime = ScorerRuntime(cfg, mesh=mesh, **config["scorer"])
        self.states = {nm: CorpusState(cfg, items[nm], capacity=capacity,
                                       runtime=self.runtime)
                       for nm in names}
        for st in self.states.values():
            st.refresh(served[0], step=0)
        jax.block_until_ready([st.cache for st in self.states.values()])
        build_s = time.monotonic() - t
        fe_opts = config["frontend"]
        self.fe = fe = QueryFrontend(
            self.states if n_tenants > 1 else self.states[""],
            auto_pump=False, **fe_opts)

        # -- warm the shapes this mix reaches, and nothing else -----------
        t = time.monotonic()
        hits0, misses0 = cache_counts()
        self.max_k = max_k = int(fe_opts["max_k"])
        max_batch = int(fe_opts["max_batch"])
        bqs = [1 << i for i in range(max_batch.bit_length())]
        ks = [k for k in traffic.k_buckets(mix["k"]) if k <= max_k]
        first = self.states[names[0]]
        width = lay.query_width
        for bq in bqs:
            ids = np.ascontiguousarray(np.broadcast_to(self.pool[0],
                                                       (bq, width)))
            w = np.ones((bq, width), np.float32)
            for k in ks:
                jax.block_until_ready(first.topk(ids, k, w))
        self.cats = {nm: reference.Catalogue(items[nm], capacity)
                     for nm in names}
        self.writer = None
        if mix.get("writer"):
            spec = mix["writer"]
            nm = names[0]
            hot_ids = np.ascontiguousarray(self.pool[:max_batch])
            _, hot = first.topk(hot_ids, max_k,
                                np.ones(hot_ids.shape, np.float32))
            order = list(dict.fromkeys(np.asarray(hot).reshape(-1).tolist()))
            self.writer = Writer(
                fe, spec, lambda n, g: mod.item_rows(model, n, g, zipf_a),
                order, items[nm].copy(), self.cats[nm], served[1],
                nm or None, seed)
            # one cycle, and a refresh there and back, before the window:
            # the writes' programs compile now, not inside it
            for op in spec["ops"]:
                self.writer.step(op)
            fe.refresh(served[1], step=1, tenant=nm or None)
            fe.refresh(served[0], step=0, tenant=nm or None)
            jax.block_until_ready(first.cache)
            self.writer.log.clear()
            self.cats[nm].rebase()
        hits, misses = (a - b for a, b in zip(cache_counts(),
                                              (hits0, misses0)))
        self.traces = self.runtime.trace_count
        log(f"setup: build {build_s:.3f} s ({n_items} items in a slab of "
            f"{capacity} x {n_tenants} tenant(s)), warm "
            f"{time.monotonic() - t:.3f} s over Bq {bqs} x K {ks}, "
            f"compilation cache {cache_dir}: {hits} hits, {misses} misses, "
            f"{self.traces} scorer traces")
        self.server = serve_in_thread(fe, **config.get("server", {}))

    def window(self, mix: dict, seconds: float, t_start: float,
               trace: bool = False):
        """Serve one window of ``mix`` from the load generator; returns
        (replies, t0, t_end, frontend stats change, trace directory)."""
        import jax

        header = {"host": "127.0.0.1", "port": self.server.port,
                  "seed": int(self.seed), "seconds": float(seconds),
                  "warmup_s": float(mix["warmup_s"]), "mix": mix,
                  "pool": len(self.pool), "n_ctx": self.lay.query_width,
                  "tenants": self.names, "max_k": self.max_k}
        proc = _start_loadgen(header, self.pool)
        trace_dir = None
        try:
            _expect(proc, b"READY")
            # set-up's garbage is collected now and never scanned again,
            # so no collector pause of the harness's lands in the window
            gc.collect()
            gc.freeze()
            if trace:
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t0 = time.monotonic() + 0.1
            t_end = t0 + seconds
            self.setup_s = t0 - t_start
            if self.writer is not None:
                self.writer.start(t0, seconds)
            proc.stdin.write(b"GO %r\n" % t0)
            proc.stdin.flush()
            time.sleep(max(t0 - time.monotonic(), 0))
            stats0 = dict(self.fe.stats)
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(max(t_end - time.monotonic(), 0))
            stats1 = dict(self.fe.stats)
            if trace:
                jax.profiler.stop_trace()
            replies = _collect(proc)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if self.writer is not None:
            self.writer.join()
        delta = {k: stats1[k] - stats0[k] for k in stats1}
        return replies, t0, t_end, delta, trace_dir

    def close(self) -> dict:
        """Stop the server; returns the facts read before it went."""
        facts = {
            "retraces": self.runtime.trace_count - self.traces,
            "degraded": any(st.kernel_degraded
                            for st in self.states.values()),
            "mem_peak": max((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0) for d in self.devs),
        }
        self.server.stop()
        self.fe = self.server = self.states = self.runtime = None
        self.writer = None
        gc.collect()
        return facts


def _window_stats(replies, t0, t_end):
    sched, recv, status = replies["sched"], replies["recv"], replies["status"]
    answered = status == 0
    lat_ms = (recv[answered] - sched[answered]) * 1e3
    late = (replies["sent"] - sched) * 1e3
    in_win = int(((recv >= t0) & (recv <= t_end) & answered).sum())
    return answered, lat_ms, late, in_win


def _sweep(served: Served, mix: dict, seconds: float, rates):
    """Offered rate against served rate and latency, one window per rate
    after one set-up, the mix's writer included (no reference check):
    how a mix's rate is chosen."""
    if mix["loop"] != "open":
        raise SystemExit("a sweep offers rates: it needs an open loop")
    t_start = time.monotonic()
    for rate in rates:
        replies, t0, t_end, delta, _ = served.window(
            dict(mix, rate=rate), seconds, t_start)
        answered, lat_ms, late, in_win = _window_stats(replies, t0, t_end)
        order = np.argsort(replies["sched"][answered])
        tenth = max(len(order) // 10, 1)
        writes = [(d - t) * 1e3 for t, d, op in
                  (served.writer.log if served.writer else [])
                  if op != "refresh"]
        if served.writer:
            served.writer.log.clear()
        yield {"offered_per_s": rate, "served_per_s": in_win / seconds,
               "answered": int(answered.sum()),
               "attempted": len(answered),
               "p50_ms": float(np.percentile(lat_ms, 50)),
               "p99_ms": float(np.percentile(lat_ms, 99)),
               "first_tenth_p50_ms": float(np.median(lat_ms[order[:tenth]])),
               "last_tenth_p50_ms": float(np.median(lat_ms[order[-tenth:]])),
               "late_p99_ms": float(np.nanpercentile(late, 99)),
               "rows_per_dispatch": delta["completed"]
               / max(delta["dispatches"], 1),
               "write_p99_ms": float(np.percentile(writes, 99))
               if writes else None}


def run_cell(config: dict, mix: dict, metric_specs: list,
             seed: int, seconds: float, trace: bool, t_start: float, *,
             chips: int = 1, require_tpu: bool = True,
             control: str | None = None, faults=None,
             keep_trace: str | None = None, sweep=None, log=print):
    """One run; returns the result line's object.  ``control`` names a
    lower serving dtype (the comparison's control); ``faults`` is a
    callable the tests use to break the timed path after set-up;
    ``sweep`` (rates) makes it a rate sweep instead, which yields one
    line per rate.  Every run sets up from this one call site: the
    kernels' compilation-cache keys carry the Python call stack that
    traced them, so a second path would compile them again."""
    sv = Served(config, mix, seed, chips=chips, require_tpu=require_tpu,
                control=control, log=log)
    if sweep:
        yield from _sweep(sv, mix, seconds, sweep)
        sv.close()
        return
    if faults is not None:
        faults(sv)
    replies, t0, t_end, delta, trace_dir = sv.window(mix, seconds, t_start,
                                                     trace)
    compiles = sv.compiles.within(t0, t_end)
    writes = sv.writer.log if sv.writer is not None else []
    facts = sv.close()

    # -- the window's numbers ---------------------------------------------
    answered, lat_ms, late, in_win = _window_stats(replies, t0, t_end)
    attempted = len(answered)
    failed = int(attempted - answered.sum())
    unanswered = int((replies["status"] < 0).sum())
    rows = delta["completed"] / max(delta["dispatches"], 1)
    write_ms = np.array([(d - t) * 1e3 for t, d, op in writes
                         if op != "refresh"])
    at = replies["sched"] - t0
    worst_late = np.argsort(-np.nan_to_num(late))[:3]
    worst_lat = np.flatnonzero(answered)[np.argsort(-lat_ms)[:3]]
    log(f"window: {attempted} requests, {int(answered.sum())} answered, "
        f"{failed} failed ({unanswered} never answered), {in_win} replies "
        f"inside the window; generator lateness p50 "
        f"{np.nanpercentile(late, 50):.4f} ms, p99 "
        f"{np.nanpercentile(late, 99):.4f} ms, max {np.nanmax(late):.4f} "
        f"ms, {int((late > 10).sum())} sent over 10 ms late; latency "
        f"p50/p90/p99/max "
        f"{[round(float(np.percentile(lat_ms, q)), 3) for q in (50, 90, 99, 100)] if len(lat_ms) else None}"
        f" ms; (s into the "
        f"window, ms) of the latest sends "
        f"{[(round(at[i], 3), round(late[i], 1)) for i in worst_late]}, "
        f"of the slowest replies "
        f"{[(round(at[i], 3), round((replies['recv'][i] - replies['sched'][i]) * 1e3, 1)) for i in worst_lat]}; "
        f"frontend {delta}")
    if writes:
        refresh = [(d - t) * 1e3 for t, d, op in writes if op == "refresh"]
        log(f"writes: {len(write_ms)} catalogue writes, p50 "
            f"{np.percentile(write_ms, 50):.4f} ms, max "
            f"{write_ms.max():.4f} ms; refresh {refresh} ms")
    log(f"compiles inside the window: {compiles}; scorer retraces after "
        f"warm-up: {facts['retraces']}; kernel_degraded: "
        f"{facts['degraded']}; device memory peak {facts['mem_peak']} bytes")

    summary = None
    if trace:
        from bench import devtrace
        raw = devtrace.load(trace_dir)
        if keep_trace:
            _keep(raw, keep_trace)
        summary = devtrace.reduce(raw, sv.model.KERNEL_EVENT)
        shutil.rmtree(trace_dir, ignore_errors=True)
        _, bound = work.roofline_seconds(
            sv.model.launch_flops(sv.lay, sv.n_items, rows),
            sv.model.launch_bytes(sv.lay, sv.capacity, rows, sv.max_k),
            sv.peak)
        log(f"trace: window {summary['window_s']:.6f} s, busy "
            f"{summary['busy_s']:.6f} s, kernel {summary['kernel_s']:.6f} s "
            f"in {summary['kernel_launches']} events against "
            f"{delta['dispatches']} dispatches; the kernel's roofline is "
            f"set by its {bound}; device ops {summary['device_ops']}; idle "
            f"gaps {summary['idle_gaps']}")

    # -- correctness --------------------------------------------------------
    t_ref = time.monotonic()
    checks, n_checked, n_after = compare(sv, replies, answered, t0, t_end)
    checks.update({
        "unanswered": (unanswered, 0),
        "window_compiles": (compiles + facts["retraces"], 0),
        "kernel_degraded": (int(facts["degraded"]), 0),
    })
    log(f"reference: {n_checked} replies checked ({n_after} after a "
        f"write or refresh) in {time.monotonic() - t_ref:.3f} s")
    correct = all(v <= lim for v, lim in checks.values())

    mod, lay = sv.model, sv.lay
    run = Run(seconds=seconds, setup_s=sv.setup_s, latency_ms=lat_ms,
              replies_in_window=in_win, write_ms=write_ms, frontend=delta,
              request_flops=mod.request_flops(lay, sv.n_items),
              launch_flops=mod.launch_flops(lay, sv.n_items, rows),
              launch_bytes=mod.launch_bytes(lay, sv.capacity, rows,
                                            sv.max_k),
              peak=sv.peak, trace=summary)
    metrics = {}
    for m in metric_specs:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(sv.device, memory_peak_bytes=int(facts["mem_peak"]))
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    yield out


def _keep(raw: dict, path: str, span_ns: int = 200_000_000) -> None:
    """Write the first ``span_ns`` of the traced window as a small JSON
    extract (the recorded trace the reduction's test reads)."""
    lo = raw["window"][0]
    hi = lo + span_ns
    keep = {"window": [lo, hi],
            "device": {p: [ev for ev in evs if lo <= ev[1] < hi]
                       for p, evs in raw["device"].items()},
            "host": [ev for ev in raw["host"] if lo <= ev[2] < hi]}
    with open(path, "w") as f:
        json.dump(keep, f)


def compare(sv: Served, replies, answered, t0, t_end):
    """The reference's verdict on a sample of the window's answered
    replies (drawn from the seed): each reply is judged at every catalogue
    version that may have served it and keeps its best reading.  Returns
    (checks, replies checked, of them served after a write or refresh)."""
    lim = sv.config["checks"]
    pool = sv.pool
    idx = np.flatnonzero(answered & (replies["sched"] >= t0)
                         & (replies["sched"] <= t_end))
    n = min(int(lim["sample"]), len(idx))
    idx = np.sort(traffic.rng(sv.seed, traffic.SAMPLE).choice(
        idx, n, replace=False))
    refs = sv.model.Reference.of(sv.lay, sv.snaps)
    sv.snaps = None
    worst = {"score_err": 0.0, "topk_short": 0.0, "dead_slots": 0,
             "bad_rows": 0}
    by_version = {}
    for ti, nm in enumerate(sv.names):
        cat = sv.cats[nm]
        mine = [i for i in idx if replies["tenant"][i] == ti]
        cands = {i: cat.candidates(replies["sent"][i], replies["recv"][i])
                 for i in mine}
        records = cat.all_records()
        for p, ref in enumerate(refs):
            ctxs = sorted({int(replies["ctx"][i]) for i in mine
                           for v in cands[i] if cat.params[v] == p})
            if not ctxs:
                continue
            S, A = ref.scores(pool[ctxs], records)
            row = {c: j for j, c in enumerate(ctxs)}
            for i in mine:
                for v in cands[i]:
                    if cat.params[v] != p:
                        continue
                    slot = cat.states[v]
                    live = slot >= 0
                    r = row[int(replies["ctx"][i])]
                    K = int(replies["k"][i])
                    served = int(replies["served"][i])
                    got = reference.judge(
                        replies["scores"][i, :served].astype(np.float64),
                        replies["slots"][i, :served], K,
                        np.where(live, S[r, slot], 0.0),
                        np.where(live, A[r, slot], 1.0), live)
                    key = (got[2], got[3], max(got[0], got[1]))
                    if i not in by_version or key < by_version[i][0]:
                        by_version[i] = (key, got, v)
    for key, got, v in by_version.values():
        worst["score_err"] = max(worst["score_err"], got[0])
        worst["topk_short"] = max(worst["topk_short"], got[1])
        worst["dead_slots"] += got[2]
        worst["bad_rows"] += got[3]
    after_writes = sum(v > 0 for _, _, v in by_version.values())
    checks = {
        "score_err": (worst["score_err"], float(lim["score_err"])),
        "topk_short": (worst["topk_short"], float(lim["topk_short"])),
        "dead_slots": (worst["dead_slots"], 0),
        "bad_rows": (worst["bad_rows"], 0),
        "unchecked": (int(len(by_version) == 0), 0),
    }
    return checks, len(by_version), after_writes
