"""chip_smoke.py off the chip: its phases at a tiny size on the CPU (Pallas
in interpret mode), its refusal to run without a TPU, and the compile-cache
placement it and the launchers share."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(nodes=300, epochs=2, episodes=1, block_cap=8192, impl="ref",
            min_auc=0.0)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_exits_nonzero_off_tpu(capsys):
    assert _chip_smoke().main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("alone", [False, True], ids=["repo", "alone"])
def test_script_exits_nonzero_off_tpu(tmp_path, alone):
    """As a script: on the CPU, and copied into a directory without the
    rest of the repo, it fails and prints no result."""
    cwd = REPO
    if alone:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_phases_tiny_on_cpu(tmp_path):
    cs = _chip_smoke()
    devices = jax.devices()
    out = cs.phase_train(devices, str(tmp_path / "train"), **TINY)
    assert os.path.exists(out["ckpt"])
    for dtype in ("float32", "bfloat16"):
        p = cs.phase_parity(devices[0], rows=512, dtype=dtype, pairs=512)
        assert p["dropped"] == 0 and p["rows"] == 512
    assert cs.phase_serve(devices, out["ckpt"], queries=16) == {
        "recall_exact": 1.0, "recall_int8": 1.0}


def test_ring_phase_on_four_cpu_devices(tmp_path):
    """Phase d on four virtual CPU devices: the table lands 1/4 per device
    (the AUC tolerance is left to the chip run's graph size)."""
    code = (
        "import sys, jax; sys.path.insert(0, %r)\n"
        "import importlib.util as u\n"
        "s = u.spec_from_file_location('cs', %r); cs = u.module_from_spec(s)\n"
        "s.loader.exec_module(cs)\n"
        "r = cs.phase_ring(jax.devices()[:4], %r, auc_tol=1.0, **%r)\n"
        "print(r)\n"
        "assert len(set(r['rows_per_device'].values())) == 1\n"
        "assert len(r['rows_per_device']) == 4\n"
        % (os.path.join(REPO, "src"), os.path.join(REPO, "chip_smoke.py"),
           str(tmp_path), TINY))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr


def test_compile_cache_placement(monkeypatch):
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cc.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before   # not overridden
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.enable_compile_cache() is None       # the CPU backend: off
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        path = cc.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_imports_start_no_backend():
    """Importing the kernels and the store starts no JAX backend (so it
    takes no chip), and the walk producers' modules import no JAX at all."""
    code = ("import sys\n"
            "import repro.walk.remote\n"
            "assert not any(m == 'jax' or m.startswith('jax.')\n"
            "               for m in sys.modules), 'walk producers import jax'\n"
            "import repro.kernels.ops, repro.embed_serve.store\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, 'a backend was started'\n")
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ,
                                PYTHONPATH=os.path.join(REPO, "src")),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
