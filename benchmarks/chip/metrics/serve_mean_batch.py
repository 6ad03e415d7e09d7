"""Real (unpadded) queries per backend call over the window, from the
micro-batcher's own counters."""


def read(run):
    if run["kind"] != "serve" or not run["batches"]:
        return None
    return run["requests"] / run["batches"]
