"""The fused SGNS update's share of its roofline: the traced episodes'
algorithmic bytes (flops_bytes.py) over peak HBM bytes/s, over the summed
device time of the kernel's events on all devices."""
import devtrace

KERNEL = r"sgns"


def read(run):
    if run["kind"] != "train":
        return None
    tr = run["trace"]
    t = n = 0
    for p in tr.devices:
        dt, dn = devtrace.time_ns(tr.clipped(tr.line(p, devtrace.OPS_LINE)),
                                  KERNEL)
        t, n = t + dt, n + dn
    if not n:
        return None
    return 100.0 * run["bytes"] / run["peaks"]["hbm_bw"] / (t / 1e9)
