"""The whole episode step's share of the chip's peak: the least time the
traced episodes' algorithmic work could take on the cell's chips (the
larger of FLOPs over peak FLOP/s and bytes over peak HBM bytes/s, from
flops_bytes.py) over the step's device time (episode_step_ms's reading)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_episode_step", os.path.join(os.path.dirname(__file__),
                                           "episode_step_ms.py"))
_step = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_step)


def read(run):
    if run["kind"] != "train":
        return None
    s = _step.step_s(run)
    if not s:
        return None
    pk, P = run["peaks"], run["chips"]
    least = max(run["flops"] / (P * pk["bf16_flops"]),
                run["bytes"] / (P * pk["hbm_bw"]))
    return 100.0 * least / s
