"""The serving entry point: published widths by default, the platform's
compute dtype and kernel, pool accounting, the compile-cache directory, and
``chip_smoke.py`` refusing to report a run that had no TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache, serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_serve_config_published_widths_and_platform_dtype():
    cfg = serve.serve_config("qwen2.5-3b")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (36, 2048, 16, 2, 128, 151936)
    want = "bfloat16" if jax.default_backend() == "tpu" else "float32"
    assert cfg.dtype == want
    small = serve.serve_config("qwen2.5-3b", smoke=True)
    assert small.num_layers < cfg.num_layers and small.dtype == want


def test_platform_picks_kernel_and_pool_fit_counts_bytes():
    cfg = serve.serve_config("smollm-135m", smoke=True)
    eng = serve.build_engine(cfg, max_batch=2, max_seq=64)
    want = "pallas" if jax.default_backend() == "tpu" else "reference"
    assert eng.backend == "paged" and eng.kernel == want
    fit = serve.pool_fit(eng)
    assert fit["weights_bytes"] == sum(
        a.nbytes for a in jax.tree.leaves(eng.params))
    assert fit["pool_bytes"] == eng.kv.k.nbytes + eng.kv.v.nbytes


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.DEFAULT_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable_compile_cache() == want
        if env_dir is None:
            assert jax.config.jax_compilation_cache_dir == want
            assert compile_cache.DEFAULT_DIR.parent == Path(REPO).resolve()
        else:  # JAX reads the variable itself; nothing else is set
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    """On the CPU, and away from the repo's package, the script exits
    non-zero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = str(shutil.copy(script, tmp_path))
    r = subprocess.run([sys.executable, script], cwd=os.path.dirname(script),
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0, r.stdout + r.stderr
    assert "FAIL" in r.stderr and '"ok"' not in r.stdout
