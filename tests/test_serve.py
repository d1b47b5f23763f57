"""The served entry point on CPU: `repro.launch.serve --real --smoke`, the
refusal of published widths off the chip, and compile-cache placement."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache, serve

# JAX-compile-heavy (jits real kernels/models); deselect with -m "not slow"
pytestmark = pytest.mark.slow


@pytest.fixture
def cache_in(tmp_path, monkeypatch):
    """Point the compile cache at ``tmp_path`` for one test, then restore."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_serve_real_smoke_pipeline_end_to_end(cache_in):
    n = 40
    run = serve.main([
        "--arch", "smollm-360m,gemma3-1b", "--real", "--smoke", "--pipeline",
        "--requests", str(n), "--seq", "16", "--rate", "50",
    ])
    res = run.result
    assert len(res.e2e_latencies) + res.shed + res.dropped == res.offered == n
    for m, bs in serve.plan_batches(run.plan).items():
        ex = run.executors[m]
        for b in bs:
            assert (m, b) in run.live.measured
            assert b in ex.compile_s
            logits = ex(b)
            assert logits.shape == (b, 16, ex.cfg.vocab_size)
            assert bool(jnp.isfinite(logits).all())


def test_serve_real_without_smoke_refuses_cpu(cache_in):
    with pytest.raises(SystemExit) as e:
        serve.main(["--arch", "smollm-360m", "--real", "--requests", "4"])
    assert e.value.code not in (0, None)
    assert "TPU" in str(e.value.code)


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.compile_cache_dir() == os.path.join(repo, ".jax_cache")


def test_compile_cache_entries_land_only_in_env_dir(cache_in):
    def listing():
        repo = compile_cache.REPO_CACHE
        return set(os.listdir(repo)) if repo.exists() else set()

    before = listing()
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        assert compile_cache.enable_compile_cache() == str(cache_in)
        assert jax.config.jax_compilation_cache_dir == str(cache_in)
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 17.0)(jnp.ones(7)).block_until_ready()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was_min)
    assert os.listdir(cache_in)
    assert listing() == before
