#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, the driver of the mix's kind
(``cells/<kind>.py``) and its metrics are found by name from
``BENCHMARK.json`` (see ``common.py``). The run needs a TPU with
as many chips as the cell asks for: anywhere else it exits 2 and prints
no result. It warms every shape the window uses, measures for
``--seconds`` seconds, checks what the timed path produced against the
plain reference, prints each compared number beside its limit as the last
lines of standard error, and prints one JSON result as the last line of
standard output: the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics, read from a profiler trace, with ``--trace 1``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str = common.ROOT, require_tpu: bool = True,
         t_start: float = T_START) -> int:
    args = parse(argv)
    ctx = common.find_cell(args.workload, root)
    chips = ctx["cell"]["chips"]
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"run.py: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    common.import_program(root)
    import repro  # noqa: F401 -- the system under test must be there
    if require_tpu:
        common.enable_compile_cache(root)
    clock = common.CompileClock()
    cell = common.cell_driver(ctx)
    result, checks = cell.run(ctx, args, devices, clock, t_start)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
