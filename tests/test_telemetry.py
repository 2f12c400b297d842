"""Serving telemetry: the stage histograms agree with numpy, every
answered request is observed once per stage with stamps that add up, and
the spans land in the profiler's own trace and join by ``req`` and
``batch``."""
import glob
import time

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core.fields import uniform_layout
from repro.data.synthetic_ctr import SyntheticCTR
from repro.models.recsys import fwfm
from repro.serving import (CorpusState, QueryFrontend, RpcClient,
                           ScorerRuntime, serve_in_thread)
from repro.serving.telemetry import (LO, N_BUCKETS, PER_OCTAVE, STAGES,
                                     Stages, bucket)

K = 4
REQUEST_STAGES = ("rpc", "queue", "inflight", "server")


@pytest.mark.parametrize("law", ["lognormal", "exponential", "uniform"])
def test_stages_quantiles_match_numpy_within_one_bucket(law):
    g = np.random.default_rng(7)
    x = {"lognormal": lambda: g.lognormal(np.log(3e-3), 1.0, 5000),
         "exponential": lambda: g.exponential(2e-4, 5000),
         "uniform": lambda: g.uniform(1e-3, 2e-2, 5000)}[law]()
    st = Stages()
    for v in x:
        st.observe("queue", float(v))
    counts = st.snapshot()["queue"]
    assert sum(counts) == len(x)
    width = 2.0 ** (1.0 / PER_OCTAVE)
    for q in (0.1, 0.5, 0.9, 0.99):
        got = Stages.quantile(counts, q)
        want = float(np.percentile(x, 100 * q))
        assert want / width <= got <= want * width, (q, got, want)


def test_buckets_span_1us_to_100s_at_width_2_to_the_eighth():
    assert LO * 2.0 ** (N_BUCKETS / PER_OCTAVE) >= 100.0
    assert LO * 2.0 ** ((N_BUCKETS - 1) / PER_OCTAVE) < 100.0
    assert bucket(0.0) == bucket(LO) == 0
    assert bucket(1e3) == N_BUCKETS - 1
    for i in (1, 57, N_BUCKETS - 2):
        edge = LO * 2.0 ** (i / PER_OCTAVE)
        assert bucket(edge * 1.0001) == i
        assert bucket(edge * 0.9999) == i - 1
    assert Stages.quantile([0] * N_BUCKETS, 0.5) is None
    assert set(Stages().snapshot()) == set(STAGES)


# -- a served stack on the CPU (jnp scorer) ---------------------------------

def _frontend():
    layout = uniform_layout(5, 4, 50)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=8, interaction="dplr",
                          rank=2)
    params = fwfm.init(jax.random.PRNGKey(0), cfg)
    data = SyntheticCTR(layout, embed_dim=4, seed=0)
    q = data.ranking_query(20, 100)
    state = CorpusState(cfg, q["item_ids"][0], q["item_weights"][0],
                        capacity=32, runtime=ScorerRuntime(cfg))
    state.refresh(params, step=0)
    fe = QueryFrontend(state, max_batch=4, max_k=K, max_wait=1e-3,
                       auto_pump=False)
    fe.warmup(data.context_query(0)["context_ids"])
    return fe, data, params, q


@pytest.fixture(scope="module")
def served():
    fe, data, params, q = _frontend()
    server = serve_in_thread(fe)
    yield {"fe": fe, "server": server, "data": data, "params": params,
           "items": q["item_ids"][0]}
    server.stop()


def _total(fe, stage):
    return sum(fe.telemetry.snapshot()[stage])


def _wait_for(cond, timeout=10.0):
    """The server observes ``rpc``/``server`` after it wrote the reply,
    so a client can hold the reply a moment before the observation."""
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, "observation never landed"
        time.sleep(1e-3)


def test_each_reply_observes_each_stage_once_and_stages_add_up(served):
    fe, data = served["fe"], served["data"]
    seen, pendings = [], []
    observe, submit = fe.telemetry.observe, fe.submit

    def record(stage, seconds, n=1):
        seen.append((stage, seconds, n))
        observe(stage, seconds, n)

    def capture(*args, **kwargs):
        pendings.append(submit(*args, **kwargs))
        return pendings[-1]

    fe.telemetry.observe, fe.submit = record, capture
    try:
        with RpcClient("127.0.0.1", served["server"].port) as cli:
            for s in range(6):
                before = {st: _total(fe, st) for st in REQUEST_STAGES}
                del seen[:]
                cli.rank(data.context_query(s)["context_ids"], k=1 + s % K)
                _wait_for(lambda: _total(fe, "server") > before["server"])
                after = {st: _total(fe, st) for st in REQUEST_STAGES}
                assert {st: after[st] - before[st]
                        for st in REQUEST_STAGES} == dict.fromkeys(
                            REQUEST_STAGES, 1)
                assert [(st, n) for st, _, n in seen] == [
                    ("read_block", 1), ("inflight", 1), ("queue", 1),
                    ("rpc", 1), ("server", 1)]
                ns = {st: round(v * 1e9) for st, v, _ in seen}
                p = pendings[-1]
                assert p.batch is not None
                assert min(ns["rpc"], ns["queue"], ns["inflight"]) >= 0
                assert ns["queue"] + ns["inflight"] == \
                    p.t_finish_ns - p.t_submit_ns
                assert ns["rpc"] + ns["queue"] + ns["inflight"] == \
                    ns["server"]
    finally:
        del fe.telemetry.observe, fe.submit


def _events(log_dir):
    """(name, start_ns, end_ns, stats) of every host event in the
    profiler's ``.xplane.pb`` (the file the benchmark's trace reader
    loads)."""
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                {k: v for k, v in ev.stats}))
    return out


def test_spans_land_in_the_profiler_trace_and_join(served, tmp_path):
    fe, data = served["fe"], served["data"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with RpcClient("127.0.0.1", served["server"].port) as cli:
            for s in range(4):
                cli.rank(data.context_query(s)["context_ids"], k=K)
        fe.update_items([0], served["items"][:1])
        fe.refresh(served["params"], step=0)
        _wait_for(lambda: fe.queue_depth == 0 and fe.inflight_depth == 0)
        time.sleep(0.05)           # the last reply's span closes
    finally:
        jax.profiler.stop_trace()
    events = _events(tmp_path)
    by = {}
    for name, a, b, stats in events:
        by.setdefault(name, []).append((a, b, stats))
    for name in ("rpc.decode", "rpc.submit", "rpc.sweep", "rpc.reply",
                 "frontend.dispatch", "frontend.resolve",
                 "frontend.barrier", "engine.write", "engine.refresh"):
        assert name in by, (name, sorted(by))
    replies = [e for e in by["rpc.reply"] if "batch" in e[2]]
    assert len(replies) == 4
    for a, _, ids in replies:
        req, batch = ids["req"], ids["batch"]
        decode = [e for e in by["rpc.decode"] if e[2]["req"] == req]
        submit = [e for e in by["rpc.submit"] if e[2]["req"] == req]
        dispatch = [e for e in by["frontend.dispatch"]
                    if e[2]["batch"] == batch]
        resolve = [e for e in by["frontend.resolve"]
                   if e[2]["batch"] == batch]
        assert len(decode) == len(submit) == len(dispatch) == 1
        assert len(resolve) == 1
        assert dispatch[0][2]["rows"] >= 1 and dispatch[0][2]["k"] >= 1
        assert decode[0][0] <= submit[0][0] <= dispatch[0][0] \
            <= resolve[0][0] <= a
    assert {e[2]["op"] for e in by["engine.write"]} == {"update"}
    assert by["frontend.barrier"][0][2]["tenant"] == "default"


@pytest.mark.parametrize("op", ["add", "remove", "update", "refresh"])
def test_frontend_write_observes_write_stages_once(op):
    fe, data, params, q = _frontend()
    for s in range(3):             # queued reads the barrier must drain
        fe.submit(data.context_query(s)["context_ids"], k=2)
    before = fe.telemetry.snapshot()
    {"add": lambda: fe.add_items(q["item_ids"][0][:2]),
     "remove": lambda: fe.remove_items([0, 1]),
     "update": lambda: fe.update_items([0], q["item_ids"][0][:1]),
     "refresh": lambda: fe.refresh(params, step=1)}[op]()
    after = fe.telemetry.snapshot()
    grew = {st: sum(after[st]) - sum(before[st]) for st in STAGES}
    assert grew == {"write.lock": 1, "write.barrier": 1, "write.apply": 1,
                    "queue": 3, "inflight": 3, "read_block": 1,
                    "rpc": 0, "server": 0}
    barrier = Stages.quantile(
        [x - y for x, y in zip(after["write.barrier"],
                               before["write.barrier"])], 0.5)
    assert barrier > LO                  # the drain read a batch back
    fe.close()


def test_health_reports_stage_quantiles(served):
    fe = served["fe"]
    n = _total(fe, "server")
    with RpcClient("127.0.0.1", served["server"].port) as cli:
        cli.rank(served["data"].context_query(0)["context_ids"], k=K)
    _wait_for(lambda: _total(fe, "server") > n)
    stages = fe.health()["stages"]
    assert set(stages) == set(STAGES)
    for name in REQUEST_STAGES:
        s = stages[name]
        assert s["count"] >= 1
        assert 0 < s["p50"] <= s["p90"] <= s["p99"] < 100
    empty = [s for s in stages.values() if s["count"] == 0]
    assert all(s["p50"] is None for s in empty)
