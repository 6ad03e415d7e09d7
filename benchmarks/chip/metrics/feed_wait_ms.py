"""Host time per episode that the training loop waited for the next
staged episode (the benchmark's own span around the feed)."""


def read(run):
    if run["kind"] != "train" or not run["feed_wait_s"]:
        return None
    w = run["feed_wait_s"]
    return 1e3 * sum(w) / len(w)
