"""The launch entry points: the training and serving CLIs run end to end on
the installed JAX, and the compile-cache helper they share puts the cache
where ``JAX_COMPILATION_CACHE_DIR`` says, or at one fixed in-checkout
path."""
import math
from pathlib import Path

import jax
import pytest

from repro.launch import cache, serve, train

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def no_cache_env(monkeypatch):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_dir_obeys_env(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.compile_cache_dir() == str(tmp_path)
    assert cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the program sets no directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_fixed_in_checkout(no_cache_env):
    first, second = cache.compile_cache_dir(), cache.compile_cache_dir()
    assert first == second == str(REPO / ".jax_cache")
    assert cache.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first


@pytest.fixture
def cli_env(monkeypatch, tmp_path):
    # the entry points enable the persistent cache; keep it out of the
    # checkout and off for the rest of this test process
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))


def test_train_cli_runs(cli_env):
    args = train.parse_args(["--arch", "smollm-360m", "--smoke", "--steps",
                             "3", "--seq", "64", "--batch", "2",
                             "--log-every", "1"])
    recs = train.train(args)
    assert [r["step"] for r in recs] == [0, 1, 2]
    assert all(math.isfinite(r["loss"]) and not r["skipped"] for r in recs)
    assert all(r["seconds"] > 0 for r in recs)


def test_serve_cli_runs(cli_env, capsys):
    assert serve.main(["--arch", "smollm-360m", "--smoke", "--prompt-len",
                       "32", "--gen", "4", "--batch", "2"]) == 0
    out = capsys.readouterr().out
    assert "generated=4 tokens" in out and "failed=0" in out
