"""What every part of the chip benchmark shares: where things are, how a
cell is found from its name, seeds, the compile cache, and the result line.

A cell is found by name alone. ``BENCHMARK.json`` names its configuration
and its traffic mix; the configuration's file is the one the entry gives,
the traffic mix is ``traffic/<traffic>.json``, the code that drives a mix
is ``cells/<kind>.py`` for the ``kind`` the mix names, and a per-layer
metric is ``metrics/<metric>.py``, all beside this file. Adding a cell, a
configuration, a mix, a kind of cell or a metric therefore means adding
files and entries, never editing the harness.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))       # the checkout
HARNESS = os.path.relpath(HERE, ROOT)               # benchmarks/chip


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: str = ROOT) -> dict:
    """The cell called ``name`` with its configuration, traffic and metrics.

    Returns a dict: ``cell`` (the workloads entry), ``config`` (the
    configuration file's contents), ``traffic`` (the mix's parameters),
    ``end_to_end`` and ``per_layer`` (the metric entries this cell
    reports), and ``root``.
    """
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _read_json(os.path.join(root, HARNESS, "traffic",
                                      cell["traffic"] + ".json"))

    def mine(metric):
        return name in metric.get("workloads", [name])
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "root": root}


def load_module(part: str, name: str, root: str = ROOT):
    """The module in ``<part>/<name>.py`` of the harness under ``root``."""
    path = os.path.join(root, HARNESS, part, name + ".py")
    key = f"chipbench_{part}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def cell_driver(ctx: dict):
    """The module that drives the cell's kind of traffic: ``cells/<kind>.py``
    with ``run(ctx, args, devices, clock, t_start) -> (result, checks)`` and
    ``calibrate(ctx, seed, devices, seconds) -> dict``."""
    return load_module("cells", ctx["traffic"]["kind"], ctx["root"])


def metric_reader(name: str, root: str = ROOT):
    """``read(run) -> float | None`` of ``metrics/<name>.py``."""
    return load_module("metrics", name, root).read


def read_metrics(ctx: dict, run_info: dict) -> dict:
    """The cell's per-layer metrics that find something to read."""
    out = {}
    for m in ctx["per_layer"]:
        value = metric_reader(m["name"], ctx["root"])(run_info)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def seed_words(seed: int, stream: int, n: int = 2) -> list[int]:
    """``n`` 32-bit words for one stream of one run's seed. Any whole
    number is a seed, negative or past 64 bits alike."""
    import numpy as np
    ss = np.random.SeedSequence([seed % (1 << 64), stream])
    return [int(w) for w in ss.generate_state(n)]


def seed31(seed: int, stream: int) -> int:
    """A non-negative int32 drawn from one stream of ``seed``."""
    return seed_words(seed, stream, 1)[0] & 0x7FFFFFFF


def rng(seed: int, stream: int):
    import numpy as np
    return np.random.default_rng(seed_words(seed, stream, 4))


# streams of a run's seed: each input is drawn from its own
STREAM_WALKS, STREAM_TABLES, STREAM_POOL, STREAM_TRAINER = 1, 2, 3, 4
STREAM_QUERIES, STREAM_SAMPLE = 5, 6


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` (the path the program's launchers use), or where
    ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program of the window, however quick to compile, is cached, so
    # a later run's set-up loads instead of compiling
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def import_program(root: str = ROOT) -> None:
    """Put the system under test (``src/``) on the import path."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class CompileClock:
    """Seconds and count of XLA backend compiles, read as deltas."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def device_info(devices, chips: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def emit(result: dict, checks: list[dict]) -> None:
    """Print the compared numbers beside their limits as the last lines of
    standard error, and the result as the last line of standard output,
    with the checks under a key of their own that comes last."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    print(json.dumps(out), flush=True)
