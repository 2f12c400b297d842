"""RPC wire-protocol contract: framing, typed error frames, and adversarial
client behavior against a live server on a real socket.

The invariants under test, from docs/network.md:

  * codecs round-trip losslessly (requests and both reply shapes);
  * socket replies are BIT-EXACT vs direct in-process ``fe.submit``;
  * pipelined requests may complete out of order and correlate by id;
  * per-REQUEST garbage (bad opcode, undecodable payload) answers with a
    typed ``RpcProtocolError`` frame and the connection keeps serving;
  * per-STREAM garbage (unparseable length prefix, mid-frame death)
    closes only THAT connection — a neighbor's in-flight replies land
    untouched and the server keeps accepting;
  * serving errors cross the wire as their taxonomy class (a remote
    ``DeadlineExceeded`` is ``except DeadlineExceeded`` client-side).

One module-scoped server (3 tenants, one shared runtime, auto_pump off —
the server's event loop pumps) backs every test; stats are asserted as
DELTAS so the tests compose.
"""
import socket
import struct
import time

import numpy as np
import pytest

import jax

from hypothesis import given, settings, strategies as st
from repro.core.fields import uniform_layout
from repro.data.synthetic_ctr import SyntheticCTR
from repro.models.recsys import fwfm
from repro.serving import (CorpusState, DeadlineExceeded, Overloaded,
                           QueryFrontend, RpcClient, RpcProtocolError,
                           ScorerRuntime, ServingError, serve_in_thread)
from repro.serving.rpc import (MAX_FRAME, WIRE_ERRORS, decode_rank_request,
                               decode_reply, encode_error_reply,
                               encode_ok_reply, encode_rank_request,
                               error_code_of, frame)

MAX_K = 8


@pytest.fixture(scope="module")
def stack():
    layout = uniform_layout(5, 4, 50)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=8, interaction="dplr",
                          rank=2)
    params = fwfm.init(jax.random.PRNGKey(0), cfg)
    data = SyntheticCTR(layout, embed_dim=4, seed=0)
    runtime = ScorerRuntime(cfg)
    states = {}
    for i, name in enumerate(["a", "b", "c"]):
        q = data.ranking_query(20, 100 + i)
        states[name] = CorpusState(cfg, q["item_ids"][0],
                                   q["item_weights"][0], capacity=32,
                                   runtime=runtime)
        states[name].refresh(params, step=0)
    fe = QueryFrontend(states, max_batch=4, max_k=MAX_K, max_wait=1e-3,
                       auto_pump=False)
    fe.warmup(data.context_query(0)["context_ids"], tenant="a")
    server = serve_in_thread(fe)
    yield {"fe": fe, "server": server, "data": data, "states": states,
           "runtime": runtime}
    server.stop()


def _ctx(data, s):
    return data.context_query(s)["context_ids"]


def _client(stack) -> RpcClient:
    return RpcClient("127.0.0.1", stack["server"].port, timeout=30.0)


# ---------------------------------------------------------------------------
# Codecs round-trip losslessly
# ---------------------------------------------------------------------------

def test_request_codec_roundtrip():
    ctx = np.array([3, 1, 4, 1, 5], np.int32)
    w = np.array([0.5, 0.25, 1.0, 2.0, 0.125], np.float32)
    rq = decode_rank_request(encode_rank_request(
        7, ctx, w, k=5, deadline_rel=0.25, tenant="ads-eu"))
    assert rq.request_id == 7 and rq.k == 5 and rq.tenant == "ads-eu"
    assert rq.deadline_rel == 0.25
    np.testing.assert_array_equal(rq.ctx, ctx)
    np.testing.assert_array_equal(rq.w, w)
    # defaults: no weights, no tenant, no deadline
    rq2 = decode_rank_request(encode_rank_request(8, ctx, k=1))
    assert rq2.tenant is None and rq2.deadline_rel is None and rq2.w is None


def test_reply_codec_roundtrip_ok_and_error():
    scores = np.array([2.5, 1.5, 0.5], np.float32)
    slots = np.array([9, 4, 31], np.int32)
    rep = decode_reply(encode_ok_reply(11, scores, slots, True))
    assert rep.ok and rep.request_id == 11 and rep.degraded
    np.testing.assert_array_equal(rep.scores, scores)
    np.testing.assert_array_equal(rep.slots, slots)

    err = decode_reply(encode_error_reply(
        12, Overloaded("queue full", tenant="b")))
    assert not err.ok and err.code == WIRE_ERRORS["Overloaded"]
    assert isinstance(err.error, Overloaded) and err.error.tenant == "b"
    with pytest.raises(Overloaded, match="queue full"):
        err.raise_for_status()


def test_error_codes_cover_taxonomy_and_walk_mro():
    class Custom(Overloaded):
        pass

    # an unlisted subclass maps to its nearest listed ancestor
    assert error_code_of(Custom("x")) == WIRE_ERRORS["Overloaded"]
    assert error_code_of(ServingError("x")) == WIRE_ERRORS["ServingError"]
    assert error_code_of(RpcProtocolError("x")) == \
        WIRE_ERRORS["RpcProtocolError"]


# ---------------------------------------------------------------------------
# Live-socket parity and pipelining
# ---------------------------------------------------------------------------

def test_socket_replies_bitexact_vs_direct_submit(stack):
    fe, data = stack["fe"], stack["data"]
    rng = np.random.default_rng(0)
    with _client(stack) as cli:
        for s in range(12):
            tenant = ["a", "b", "c"][s % 3]
            k = int(rng.integers(1, MAX_K + 1))
            sc, sl = cli.rank(_ctx(data, s), k=k, tenant=tenant)
            wv, wi = fe.submit(_ctx(data, s), k=k, tenant=tenant).result()
            np.testing.assert_array_equal(sc, np.asarray(wv))
            np.testing.assert_array_equal(sl, np.asarray(wi))
            assert stack["states"][tenant].is_live(sl).all()


def test_pipelined_requests_correlate_out_of_order(stack):
    data = stack["data"]
    with _client(stack) as cli:
        rids = [cli.send_rank(_ctx(data, s), k=(s % MAX_K) + 1, tenant="b")
                for s in range(8)]
        for s, rid in reversed(list(enumerate(rids))):
            reply = cli.recv_for(rid)          # strays buffer the rest
            reply.raise_for_status()
            assert reply.request_id == rid
            assert reply.scores.shape == ((s % MAX_K) + 1,)


def test_zero_retraces_across_wire_traffic(stack):
    runtime, data = stack["runtime"], stack["data"]
    before = runtime.trace_count
    with _client(stack) as cli:
        for s in range(10):
            cli.rank(_ctx(data, 40 + s), k=(s % MAX_K) + 1,
                     tenant=["a", "b", "c"][s % 3])
    assert runtime.trace_count == before


# ---------------------------------------------------------------------------
# Typed error frames: requests fail typed, the connection keeps serving
# ---------------------------------------------------------------------------

def test_bad_request_and_unknown_tenant_answer_typed(stack):
    data = stack["data"]
    with _client(stack) as cli:
        with pytest.raises(ValueError, match="outside"):
            cli.rank(_ctx(data, 0), k=MAX_K + 50, tenant="a")
        with pytest.raises(ValueError, match="unknown tenant"):
            cli.rank(_ctx(data, 0), k=1, tenant="zzz")
        # the SAME connection still serves real requests
        sc, _ = cli.rank(_ctx(data, 0), k=2, tenant="a")
        assert sc.shape == (2,)


def test_deadline_crosses_wire_as_taxonomy_class(stack):
    data = stack["data"]
    with _client(stack) as cli:
        rid = cli.send_rank(_ctx(data, 1), k=1, tenant="a",
                            deadline_rel=1e-9)
        reply = cli.recv_for(rid)
        assert isinstance(reply.error, DeadlineExceeded)
        assert reply.error.tenant == "a"
        with pytest.raises(DeadlineExceeded):
            reply.raise_for_status()


def test_unknown_opcode_and_garbage_payload_keep_conn_alive(stack):
    data = stack["data"]
    with _client(stack) as cli:
        # unknown opcode: typed RpcProtocolError frame, conn survives
        cli.send_raw(frame(bytes([0x7F]) + struct.pack("<I", 501) + b"xx"))
        reply = cli.recv()
        assert isinstance(reply.error, RpcProtocolError)
        assert reply.request_id == 501
        # valid opcode, undecodable body: same contract
        cli.send_raw(frame(bytes([0x01]) + struct.pack("<I", 502) + b"\x01"))
        reply = cli.recv()
        assert isinstance(reply.error, RpcProtocolError)
        assert reply.request_id == 502
        sc, _ = cli.rank(_ctx(data, 2), k=1, tenant="a")
        assert sc.shape == (1,)


# ---------------------------------------------------------------------------
# Stream-level garbage: only the offending connection dies
# ---------------------------------------------------------------------------

def test_oversized_declared_length_closes_only_that_conn(stack):
    data = stack["data"]
    before = stack["server"].stats["protocol_errors"]
    with _client(stack) as neighbor:
        nrid = neighbor.send_rank(_ctx(data, 3), k=3, tenant="b")
        with _client(stack) as bad:
            bad.send_raw(struct.pack("<I", MAX_FRAME + 1) + b"junk")
            with pytest.raises((ConnectionError, RpcProtocolError)):
                bad.recv()                 # server closed the stream
        # the neighbor's in-flight reply lands untouched
        reply = neighbor.recv_for(nrid)
        reply.raise_for_status()
        assert reply.scores.shape == (3,)
    assert stack["server"].stats["protocol_errors"] >= before + 1


def test_truncated_prefix_and_midframe_disconnect_spare_neighbors(stack):
    data = stack["data"]
    srv = stack["server"]
    before = srv.stats["disconnects"]
    with _client(stack) as neighbor:
        nrid = neighbor.send_rank(_ctx(data, 4), k=2, tenant="c")
        # truncated length prefix: 2 of 4 header bytes, then death
        t = socket.create_connection(("127.0.0.1", srv.port))
        t.sendall(b"\x10\x00")
        t.close()
        # mid-frame death: full header, half the declared payload
        m = socket.create_connection(("127.0.0.1", srv.port))
        m.sendall(struct.pack("<I", 100) + b"\x01" * 10)
        m.close()
        reply = neighbor.recv_for(nrid)
        reply.raise_for_status()
        assert reply.scores.shape == (2,)
        # both deaths were accounted as disconnects, then a NEW client
        # is accepted and served — the listener never wobbled
        with _client(stack) as fresh:
            assert fresh.rank(_ctx(data, 5), k=1, tenant="a")[0].shape \
                == (1,)
    deadline = time.monotonic() + 5.0
    while (srv.stats["disconnects"] < before + 2
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert srv.stats["disconnects"] >= before + 2


# ---------------------------------------------------------------------------
# Fuzz: seeded garbage frames never kill the server
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_fuzzed_frames_never_crash_server(stack, seed):
    data = stack["data"]
    rng = np.random.default_rng(seed)
    with _client(stack) as cli:
        for _ in range(3):
            n = int(rng.integers(1, 64))
            cli.send_raw(frame(rng.bytes(n)))
        # every garbage frame was answered with SOME reply frame (typed
        # protocol error or, for byte soup that happens to decode, a
        # serving reply) — then a real request still round-trips
        for _ in range(3):
            cli.recv()
        sc, _ = cli.rank(_ctx(data, 6), k=1, tenant="a")
        assert sc.shape == (1,)
