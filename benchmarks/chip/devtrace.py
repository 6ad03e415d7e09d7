"""The profiler's trace of a run, reduced to what the per-layer metrics read.

``capture`` records a window with ``jax.profiler`` into a fixed directory
inside the checkout, marking it with a host annotation (``bench.traced``)
so that the window's bounds are on the same clock as the device's events.
``load`` keeps, for each TPU, the events of its ops line and its modules
line, and the host's annotations. The reductions (busy union, time per
name, collective time that no compute overlaps) work on plain interval
lists, so a test can feed them a synthetic trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.traced"
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"ppermute|send|recv", re.I)


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns), by device plane and line."""

    devices: dict            # plane name -> {line name -> [events]}
    host: list               # host annotations and spans
    window: tuple            # (start_ns, end_ns) of the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def line(self, plane: str, line: str) -> list:
        return self.devices[plane].get(line, [])

    def clipped(self, events) -> list:
        lo, hi = self.window
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events
                if e > lo and s < hi]


@contextlib.contextmanager
def capture(directory: str):
    """Trace the body into ``directory`` (emptied first)."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {directory}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            devices[plane.name] = {
                ln.name: [(_op(e.name), e.start_ns,
                           e.start_ns + e.duration_ns) for e in ln.events]
                for ln in plane.lines if ln.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in ln.events)
    marks = [(s, e) for n, s, e in host if n == WINDOW]
    if not marks:
        raise ValueError("trace has no window annotation")
    return Trace(devices=devices, host=host, window=marks[0])


def _op(name: str) -> str:
    """An op's name without its HLO text: ``%topk_mips.1 = (...) ...`` ->
    ``%topk_mips.1``."""
    return name.split(" = ", 1)[0]


# ------------------------------------------------------------- reductions
def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def time_ns(events, pattern: str) -> tuple[float, int]:
    """Summed duration and count of events whose name matches."""
    rx = re.compile(pattern)
    hits = [e - s for n, s, e in events if rx.search(n)]
    return float(sum(hits)), len(hits)


def exposed_ns(events) -> float:
    """Time in which a collective runs and no other op does."""
    coll = [x for x in events if COLLECTIVE.search(x[0])]
    comp = [x for x in events if not COLLECTIVE.search(x[0])]
    return union_ns(coll) - _overlap_ns(coll, comp)


def _overlap_ns(a, b) -> float:
    """Length of union(a) intersected with union(b)."""
    def merged(evs):
        out = []
        for _, s, e in sorted(evs, key=lambda x: x[1]):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out
    ma, mb = merged(a), merged(b)
    i = j = 0
    total = 0.0
    while i < len(ma) and j < len(mb):
        lo = max(ma[i][0], mb[j][0])
        hi = min(ma[i][1], mb[j][1])
        if hi > lo:
            total += hi - lo
        if ma[i][1] < mb[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share(tr: Trace) -> float | None:
    """Percent of the window in which no op runs, averaged over devices."""
    if not tr.devices or tr.window_s <= 0:
        return None
    busy = [union_ns(tr.clipped(tr.line(p, OPS_LINE))) for p in tr.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (tr.window_s * 1e9))


def busy_s(tr: Trace) -> float:
    busy = [union_ns(tr.clipped(tr.line(p, OPS_LINE))) for p in tr.devices]
    return sum(busy) / max(len(busy), 1) / 1e9


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device ops that took most time (summed over devices), and the
    longest idle gaps of the first device, named by the host annotation
    that was open when each began."""
    per = {}
    for p in tr.devices:
        for n, s, e in tr.clipped(tr.line(p, OPS_LINE)):
            per[n] = per.get(n, 0.0) + (e - s) / 1e9
    ops = sorted(per.items(), key=lambda x: -x[1])[:top]
    gaps = []
    if tr.devices:
        p = sorted(tr.devices)[0]
        end = tr.window[0]
        for _, s, e in sorted(tr.clipped(tr.line(p, OPS_LINE)),
                              key=lambda x: x[1]):
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if tr.window[1] > end:
            gaps.append((end, tr.window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [h for h in tr.host if h[0] != WINDOW and h[0].startswith("bench.")]

    def doing(t):
        open_ = [n for n, s, e in spans if s <= t < e]
        return open_[-1] if open_ else "host: other"
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[doing(s), (e - s) / 1e9] for s, e in gaps[:top]]}
