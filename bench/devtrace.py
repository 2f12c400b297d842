"""From a profiler trace to the device's busy time, a kernel's time and
the breakdown of a traced window.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists (so the reduction below runs on a recorded extract as well, and is
tested on one: ``bench/tests/data``):

* ``device``: per TPU, the ``(name, start_ns, dur_ns)`` events of its
  "XLA Ops" line — one event per operation the chip ran;
* ``host``: ``(thread, name, start_ns, dur_ns)`` of every host-thread
  event (the runtime's dispatch events and the benchmark's own
  annotations);
* ``window``: the span of the benchmark's ``bench.window`` annotation,
  which the harness holds open exactly over the measured window.

``reduce`` then gives, over that window: busy seconds (the union of the
op intervals, averaged over the chips), the summed time and count of the
ops whose name matches a kernel pattern, the ten ops that took most
time, and the ten longest idle gaps, each named by the host event that
overlapped it most and the share of the gap that event covers (a gap
the host spent in no traced event is mostly the host's own Python: the
program has no spans yet).
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
TPU_PLANE = re.compile(r"^/device:TPU:\d+$")


def load(log_dir: str) -> dict:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    device, host, window = {}, [], None
    for plane in pd.planes:
        if TPU_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    item = (line.name, ev.name, int(ev.start_ns),
                            int(ev.duration_ns))
                    if ev.name == WINDOW:
                        window = (item[2], item[2] + item[3])
                    else:
                        host.append(item)
    if window is None:
        raise RuntimeError(f"trace {path} holds no {WINDOW!r} annotation")
    if not device:
        raise RuntimeError(f"trace {path} holds no TPU '{OPS_LINE}' line")
    return {"device": device, "host": host, "window": window}


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _union(intervals):
    """Merged, sorted (start, end) of possibly overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


_DIGITS = re.compile(r"[.:/]?\d+$")
_HLO = re.compile(r"^%?([\w.-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])")


def _label(name: str) -> str:
    """A short name for an event: a device op's HLO text ``%name.3 =
    (f32[16,16]...`` becomes ``name f32[16,16]`` (its result shape); any
    other name loses its trailing instance number."""
    m = _HLO.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return _DIGITS.sub("", name)


def reduce(trace: dict, kernel: re.Pattern, top: int = 10) -> dict:
    lo, hi = trace["window"]
    window_s = (hi - lo) / 1e9
    busy, kernel_s, launches, by_op, gaps = [], 0.0, 0, {}, []
    for _, events in sorted(trace["device"].items()):
        clipped = list(_clip(events, lo, hi))
        merged = _union((a, b) for _, a, b in clipped)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, a, b in clipped:
            by_op[_label(name)] = by_op.get(_label(name), 0.0) + (b - a) / 1e9
            if kernel.search(name):
                kernel_s += (b - a) / 1e9
                launches += 1
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(trace["device"]), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_host_label(trace["host"], a, b), (b - a) / 1e9]
             for a, b in gaps[:top]]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "kernel_s": kernel_s / n_dev,
        "kernel_launches": launches / n_dev,
        "device_ops": [[name, s / n_dev] for name, s in ops],
        "idle_gaps": named,
    }


def _host_label(host, a, b) -> str:
    """The host event that overlaps [a, b] most, named by its thread, with
    the share of the gap it covers."""
    best, name = 0, None
    for thread, ev, s, d in host:
        over = min(s + d, b) - max(s, a)
        if over > best:
            best, name = over, f"{_label(thread) or 'host'}: {_label(ev)}"
    if name is None:
        return "no host event"
    return f"{name} ({100 * best / (b - a):.0f}% of the gap)"
