"""CLI launchers (launch/train.py, launch/serve.py) run end to end."""

import os
import subprocess
import sys

ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
       "HOME": "/root",
       # without an explicit platform jax probes for accelerator plugins,
       # which hangs (network timeouts) in the offline container
       "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}


def test_train_launcher():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "max-sentiment", "--steps", "8", "--seq-len", "32",
         "--global-batch", "4"],
        capture_output=True, text=True, timeout=300, env=ENV)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "[train] done" in proc.stdout
    assert "loss=" in proc.stdout


def test_serve_launcher():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--port", "0", "--deploy", "max-sentiment", "--duration", "0.5"],
        capture_output=True, text=True, timeout=300, env=ENV)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "deployed max-sentiment" in proc.stdout
    assert "12 assets registered" in proc.stdout


def test_compile_cache_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to one fixed, gitignored directory of the checkout."""
    import pathlib

    import jax
    from repro.launch.compile_cache import use_compile_cache

    repo = pathlib.Path(__file__).resolve().parents[1]
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == str(repo / ".jax_cache") == use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
