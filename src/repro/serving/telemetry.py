"""Where a served request spends its time: profiler spans and stage
histograms.

Spans.  ``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` (a
TraceMe) while a profiler session runs, recorded on the same clock as
the device's "XLA Ops" events, so a trace says which host layer held
each gap between kernel launches.  With no session it is a shared no-op
context; there is no switch of its own.  ``tracing()`` is the TraceMe's
own enabled check: the per-request spans on the RPC server's threads
test it first and skip the span, because on the auction cells each
microsecond of per-request host work costs a visible share of the
median latency.  The ids land as the event's metadata: ``req`` (the
RPC server's request sequence number) joins a request's spans to its
``batch`` (the frontend's dispatch sequence number).  A model whose
launch has work of its own on the host side carries a span around its
enqueue: ``engine.mind_launch`` (MIND: the user tower and the corpus
kernel enqueued as one program), labelled with the ``batch``.

Counters.  ``QueryFrontend.stats`` counts, per launch, the query
vectors the kernel scans (``scan_vectors``: DPLR-FwFM one a row, MIND
K = 4 a row, padding rows included) and those that carry a live
interest (``live_vectors``: MIND's K'_u of each row), from
``CorpusState.query_vectors``.

Stages.  ``Stages`` keeps one histogram per stage of ``STAGES``: counts
in fixed log-spaced buckets of relative width 2**(1/8), from 1 us to
100 s.  The serving layers observe into one ``Stages`` (the frontend's
``telemetry``) with durations between stamps of ``time.perf_counter_ns``
taken at the layer boundaries; adjacent stages of one request share a
stamp, so per request ``rpc + queue + inflight == server`` exactly:

    rpc       frame fully read -> submit entered, plus finish -> reply
              written (decode, executor hop, sweep, loop wake-up, encode)
    queue     submit entered -> the launch holding the request returns
    inflight  launch returns -> the request is finished (device time,
              the blocking read and any wait for the tick)
    server    frame fully read -> reply written
    read_block     per batch: time the resolve blocked in its device read
    write.lock     a catalogue write or refresh: call -> frontend lock held
    write.barrier  the tenant's drain before the write
    write.apply    the engine's write or rebuild
"""
from __future__ import annotations

import bisect
import contextlib
import itertools
import math

import jax

STAGES = ("rpc", "queue", "inflight", "server", "read_block",
          "write.lock", "write.barrier", "write.apply")
LO = 1e-6                    # seconds: lower edge of bucket 0
PER_OCTAVE = 8               # buckets per doubling: width 2**(1/8)
N_BUCKETS = math.ceil(PER_OCTAVE * math.log2(100.0 / LO))   # up to 100 s
OFF = contextlib.nullcontext()       # the shared no-op span
tracing = jax.profiler.TraceAnnotation.is_enabled   # a session records


def span(name: str, **ids):
    """A profiler span over the ``with`` body, carrying ``ids``; a no-op
    unless a profiler session is recording."""
    if tracing():
        return jax.profiler.TraceAnnotation(name, **ids)
    return OFF


def bucket(seconds: float) -> int:
    """The bucket of a duration: [LO * 2**(i/8), LO * 2**((i+1)/8)),
    with shorter durations in bucket 0 and longer ones in the last."""
    if seconds <= LO:
        return 0
    return min(int(PER_OCTAVE * math.log2(seconds / LO)), N_BUCKETS - 1)


class Stages:
    """Named latency histograms.

    ``observe`` takes no lock, so it stays off the serving threads'
    critical path: each stage must have one writer at a time.  In the
    server that holds by construction — ``rpc`` and ``server`` are
    observed on the frontend's executor thread of the RPC server, every
    other stage by the holder of the frontend's lock.  ``snapshot`` may
    run on any thread: it copies each stage's counts in one step under
    the GIL."""

    def __init__(self):
        self._counts = {s: [0] * N_BUCKETS for s in STAGES}

    def observe(self, stage: str, seconds: float, n: int = 1) -> None:
        """``n`` observations of one duration."""
        self._counts[stage][bucket(seconds)] += n

    def snapshot(self) -> dict[str, list[int]]:
        """Plain per-stage bucket counts (the difference of two snapshots
        is the histogram of what was observed between them)."""
        return {s: list(c) for s, c in self._counts.items()}

    @staticmethod
    def quantile(counts, q: float) -> float | None:
        """The ``q`` quantile (0..1) of bucket ``counts``: the geometric
        middle of the bucket holding the order statistic nearest
        ``q * (n - 1)``; None when nothing was observed."""
        cum = list(itertools.accumulate(counts))
        if not cum or cum[-1] == 0:
            return None
        i = bisect.bisect_right(cum, int(q * (cum[-1] - 1) + 0.5))
        return LO * 2.0 ** ((i + 0.5) / PER_OCTAVE)

    def summary(self) -> dict[str, dict]:
        """Per stage: ``count`` and the ``p50``/``p90``/``p99`` seconds."""
        out = {}
        for stage, counts in self.snapshot().items():
            out[stage] = {"count": sum(counts)}
            for name, q in (("p50", 0.5), ("p90", 0.9), ("p99", 0.99)):
                out[stage][name] = self.quantile(counts, q)
        return out
