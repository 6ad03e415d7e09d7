"""Percent of the traced window in which no op runs on the device
(serving cells)."""
import devtrace


def read(run):
    if run["kind"] != "serve":
        return None
    return devtrace.idle_share(run["trace"])
