"""Percent of the traced window in which no op runs on the device and no
span of the serving program is open: neither a ``batcher.*`` nor an
``embed_store.*`` span (the split is in ``idle_store_share.serve.py``)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_idle_split", os.path.join(os.path.dirname(__file__),
                                         "idle_store_share.serve.py"))
_split = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_split)


def read(run):
    if run["kind"] != "serve":
        return None
    return _split.split(run["trace"])["untraced"]
