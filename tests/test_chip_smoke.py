"""``chip_smoke.py`` at tiny sizes on the CPU, and the compile-cache
helper its entry point calls.

The phases are the ones the chip runs, through the same entry points;
off-TPU the codec lowers to its jnp twins, so these tests check control
flow, accounting and the phases' own checks, not the Mosaic kernels
(``tests/test_tpu_compile.py`` compiles those for a v5e).
"""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.utils import compile_cache  # noqa: E402


def test_fl_round_phase_tiny():
    out = chip_smoke.fl_round(rounds=2, n_samples=40, n_clients=4,
                              clients_per_round=2, rank=4, batch=8)
    hist = out["history"]
    assert [h["round"] for h in hist] == [1, 2]
    assert all(np.isfinite(h["client_loss"]) for h in hist)
    # off-TPU the codec lowers to its jnp twins: no Mosaic kernel
    assert not any(out["mosaic"].values())


def test_serve_phase_tiny():
    out = chip_smoke.serve(n_adapters=8, d_model=64, rows=8, slab_slots=8)
    assert out["max_rel_err"] < 1e-4


def test_sharded_reduction_phase_tiny():
    out = chip_smoke.sharded_reduction(k=8, rank=4, iters=1)
    assert set(out["times"]) == {"single", "sharded"}


def test_main_refuses_a_host_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    assert "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""         # no result line


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_env_var(monkeypatch, tmp_path,
                                       cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
