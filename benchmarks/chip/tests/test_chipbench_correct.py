"""Runs driven on the CPU at a tiny size: sound runs come out correct, and
each fault a cell can have, planted in the timed path, comes out not
correct; so does the control (the reference in the precision below the
configuration's), put in the program's place."""
import numpy as np
import pytest

from chipbench_helpers import drive, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


def test_sound_training_run_is_correct(root, capsys):
    out = drive(root, "tiny.replay", capsys)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_step_returning_state_unchanged_is_not_correct(root, capsys,
                                                      monkeypatch):
    from repro.core import HybridEmbeddingTrainer

    def unchanged(self, eb, *, lr=None):
        return 0.0
    monkeypatch.setattr(HybridEmbeddingTrainer, "train_episode", unchanged)
    out = drive(root, "tiny.replay", capsys)
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_not_correct(root, capsys, monkeypatch):
    import jax.numpy as jnp
    from repro.kernels import ops
    step = ops.sgns_step

    def half(vert, ctx, iv, ic, inn, mask, lr, **kw):
        keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
        return step(vert, ctx, iv, ic, inn, mask * keep, lr, **kw)
    monkeypatch.setattr(ops, "sgns_step", half)
    out = drive(root, "tiny.replay", capsys)
    assert out["correct"] is False


def test_sound_serving_run_is_correct(root, capsys):
    out = drive(root, "tiny.serve", capsys)
    assert out["correct"] is True
    assert out["failed"] == 0
    assert out["attempted"] == 400
    assert set(out["metrics"]) == {"serve_p99_ms", "serve_p50_ms",
                                   "setup_s"}


def test_burst_mix_added_as_a_data_file_runs_correct(root, capsys):
    """An on/off mix of 4-query requests is a data file and an entry."""
    import json
    import os
    h = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(h, "traffic", "serve.json")) as f:
        mix = json.load(f)
    mix.update(arrival="onoff", on_s=0.25, off_s=0.25, rate_qps=100,
               queries_per_request=4)
    with open(os.path.join(h, "traffic", "serve-burst.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["name"] != "tiny.burst"]
    bench["workloads"] = cells + [{"name": "tiny.burst", "config": "tiny",
                                   "traffic": "serve-burst", "chips": 1,
                                   "why": "test"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = drive(root, "tiny.burst", capsys)
    assert out["correct"] is True
    assert out["attempted"] == 100 and out["failed"] == 0


def test_altered_answer_is_not_correct(root, capsys, monkeypatch):
    from repro.embed_serve import ShardedEmbeddingStore
    topk = ShardedEmbeddingStore.topk

    def altered(self, queries, k, **kw):
        vals, ids = topk(self, queries, k, **kw)
        ids = np.array(ids)
        ids[:, -1] = (ids[:, -1] + 1) % self.num_nodes
        return vals, ids
    monkeypatch.setattr(ShardedEmbeddingStore, "topk", altered)
    out = drive(root, "tiny.serve", capsys)
    assert out["correct"] is False


def test_half_the_rows_scanned_is_not_correct(root, capsys, monkeypatch):
    from repro.embed_serve import ShardedEmbeddingStore
    topk = ShardedEmbeddingStore.topk

    def half(self, queries, k, **kw):
        valid = self.valid
        self.valid = tuple(v // 2 for v in valid)
        try:
            return topk(self, queries, k, **kw)
        finally:
            self.valid = valid
    monkeypatch.setattr(ShardedEmbeddingStore, "topk", half)
    out = drive(root, "tiny.serve", capsys)
    assert out["correct"] is False


@pytest.mark.parametrize("base", ["tencent-a-f32", "tencent-a-bf16"])
def test_training_control_fails_the_limits(tmp_path, base):
    """The reference in the precision below the configuration's, put in
    the program's place, fails that configuration's limits."""
    import common
    import compare
    import graphgen
    root = tiny_root(tmp_path, base=base)
    graphgen.CACHE = root + "/graph-cache"
    ctx = common.find_cell("tiny.replay", root)
    train_cell = common.cell_driver(ctx)
    S = train_cell.prepare(ctx, 17)
    ref = train_cell.reference_steps(S)
    ctl = ctx["config"]["control"]
    control = train_cell.reference_steps(S, dtype=ctl["dtype"],
                                         precision=ctl["precision"])
    checks = compare.checks(compare.train_numbers(control, ref),
                            ctx["config"]["limits"]["train"])
    assert not all(c["ok"] for c in checks), checks


def test_serving_control_fails_the_limits(root):
    import common
    import compare
    import reference
    import jax.numpy as jnp
    ctx = common.find_cell("tiny.serve", root)
    cfg = ctx["config"]
    rng = np.random.default_rng(3)
    table = rng.standard_normal((cfg["num_nodes"], cfg["dim"]))
    table = jnp.asarray(table / np.linalg.norm(table, axis=1, keepdims=True),
                        jnp.float32)
    q = np.asarray(table[rng.integers(0, cfg["num_nodes"], 64)])
    ref_v, _ = reference.topk_exact(table, q, 10, cfg["num_nodes"])
    ctl = cfg["control"]
    cv, ci = reference.topk_exact(table, q, 10, cfg["num_nodes"],
                                  precision=ctl["precision"],
                                  dtype=ctl["dtype"])
    numbers = compare.serve_numbers(ci, cv, ref_v,
                                    reference.scores(table, q, ci),
                                    cfg["num_nodes"])
    checks = compare.checks(numbers, cfg["limits"]["serve"])
    assert not all(c["ok"] for c in checks), checks
