"""The top-k scan's share of its roofline: one read of the table per
backend call (flops_bytes.topk_scan) over peak HBM bytes/s, over the
device time of the scan kernel's events of those calls.

Both sides count the same calls: the backend calls (the benchmark's
``bench.store_topk`` span) that lie wholly inside the traced window, each
with the kernel events that start inside its span, unclipped. A call's
scan starts and ends inside its span, since ``store.topk`` waits for its
result, so a call cut by either edge of the window counts on neither side.
"""
import re

import devtrace

KERNEL = r"topk_mips"
CALL = "bench.store_topk"


def per_call(tr):
    """[(host wall ns, scan kernel ns)] of each call wholly inside the
    traced window."""
    lo, hi = tr.window
    calls = sorted((s, e) for n, s, e in tr.host
                   if n == CALL and lo <= s and e <= hi)
    kern = [(s, e) for p in tr.devices
            for n, s, e in tr.line(p, devtrace.OPS_LINE)
            if re.search(KERNEL, n)]
    return [(e - s, float(sum(ke - ks for ks, ke in kern if s <= ks < e)))
            for s, e in calls]


def read(run):
    if run["kind"] != "serve":
        return None
    c = per_call(run["trace"])
    k = sum(t for _, t in c) / 1e9
    if not c or not k:
        return None
    return 100.0 * len(c) * run["table_bytes"] / run["peaks"]["hbm_bw"] / k
