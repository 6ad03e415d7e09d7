"""Percent of the traced window in which no op runs on the device,
averaged over the cell's devices (training cells)."""
import devtrace


def read(run):
    if run["kind"] != "train":
        return None
    return devtrace.idle_share(run["trace"])
