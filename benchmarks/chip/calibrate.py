#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not part of a run).

    python3 benchmarks/chip/calibrate.py --workload <name> --seeds 1,2,3

For each seed, in one process on the chip, at the cell's own size, the
driver of the cell's kind (``cells/<kind>.py``, its ``calibrate``) reads
the control, and for training the program and the planted faults, against
the plain reference. Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="serving: the window whose requests are sampled")
    args = ap.parse_args(argv)
    ctx = common.find_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    common.import_program(common.ROOT)
    common.enable_compile_cache()
    cell = common.cell_driver(ctx)
    for s in (int(x) for x in args.seeds.split(",")):
        t = time.perf_counter()
        out = cell.calibrate(ctx, s, devices, args.seconds)
        out.update(seed=s, seconds=time.perf_counter() - t)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
