"""Percent of the traced window in which no op runs on the device while
the micro-batcher works and the store does not: a ``batcher.*`` span of the
program is open and no ``embed_store.*`` span is (the split is in
``idle_store_share.serve.py``)."""
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
    return _split.split(run["trace"])["batcher"]
