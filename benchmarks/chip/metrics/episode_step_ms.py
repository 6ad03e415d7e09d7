"""Device time of the jitted episode step per episode: the summed
duration of its module's executions in the traced window, over the traced
episodes, averaged over devices."""
import devtrace

MODULE = r"episode_device_fn"


def step_s(run):
    """Seconds of episode-step device time per device, or None."""
    tr = run["trace"]
    per = [devtrace.time_ns(tr.clipped(tr.line(p, devtrace.MODULES_LINE)),
                            MODULE) for p in tr.devices]
    if not per or not all(n for _, n in per):
        return None
    return sum(t for t, _ in per) / len(per) / 1e9


def read(run):
    if run["kind"] != "train":
        return None
    s = step_s(run)
    return None if s is None else 1e3 * s / run["episodes"]
