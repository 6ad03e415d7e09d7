"""Percent of the traced window in which no op runs on the device while
the serving store works: an ``embed_store.*`` span of the program is open.

The program writes its spans into the profiler's trace, on the device's
clock, so they are read from the same trace as the device's ops. This file
holds the split that ``idle_batcher_share.serve`` and
``idle_untraced_share.serve`` load too: the device's idle time, computed as
``devtrace.idle_share`` computes it (ops-line events clipped to the window,
averaged over devices), cut by the program span open over it. The three
parts add up to ``device_idle_share.serve``.
"""
import devtrace

STORE = "embed_store."
BATCHER = "batcher."


def _spans(tr, prefix):
    return tr.clipped([h for h in tr.host if h[0].startswith(prefix)])


def _idle_under(spans, busy) -> float:
    """ns covered by ``spans`` in which no op of ``busy`` runs."""
    return devtrace.union_ns(spans) - devtrace._overlap_ns(spans, busy)


def split(tr) -> dict:
    """Percent of the window in which the device is idle while a store span
    is open (``store``), while a batcher span and no store span is open
    (``batcher``), and while neither is (``untraced``). A part is None where
    the trace holds none of its spans: a program without them reads
    nothing rather than zero."""
    has = {part: any(h[0].startswith(prefix) for h in tr.host)
           for part, prefix in (("store", STORE), ("batcher", BATCHER))}
    has["untraced"] = has["store"] or has["batcher"]
    if not tr.devices or tr.window_s <= 0 or not has["untraced"]:
        return dict.fromkeys(has)
    store = _spans(tr, STORE)
    either = store + _spans(tr, BATCHER)
    w = tr.window[1] - tr.window[0]
    ns = dict.fromkeys(has, 0.0)
    for p in tr.devices:
        busy = tr.clipped(tr.line(p, devtrace.OPS_LINE))
        idle_store = _idle_under(store, busy)
        idle_either = _idle_under(either, busy)
        ns["store"] += idle_store
        ns["batcher"] += idle_either - idle_store
        ns["untraced"] += w - devtrace.union_ns(busy) - idle_either
    return {part: 100.0 * ns[part] / len(tr.devices) / w if has[part]
            else None for part in has}


def read(run):
    if run["kind"] != "serve":
        return None
    return split(run["trace"])["store"]
