"""Host wall of ``store.topk`` per call (the benchmark's span around it)
minus the scan kernel's device time in that call: what the store's
dispatch, thread hand-offs and merge add. Counted over the calls wholly
inside the traced window, each with the kernel events that start inside
its span (``topk_scan_roofline.per_call``), so both sides count the same
calls."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_topk_scan", os.path.join(os.path.dirname(__file__),
                                        "topk_scan_roofline.py"))
_scan = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_scan)


def read(run):
    if run["kind"] != "serve":
        return None
    c = _scan.per_call(run["trace"])
    if not c or not any(k for _, k in c):
        return None
    return sum(w - k for w, k in c) / len(c) / 1e6
