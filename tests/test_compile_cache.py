"""Compile cache placed from outside: ``JAX_COMPILATION_CACHE_DIR`` is
used verbatim and never rewritten; unset, the cache sits at a fixed path
inside the checkout (never ``$HOME``, a temp name, a pid or the time)."""

import os
import subprocess
import sys

import jax

import sntc_tpu.utils.compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_is_used_verbatim_and_never_rewritten(tmp_path, monkeypatch):
    base = str(tmp_path / "xla")
    monkeypatch.delenv("SNTC_NO_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", base)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cc.resolve_cache_dir() == base
        assert cc.enable_persistent_cache() == base
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == base
        assert jax.config.jax_compilation_cache_dir == base
        assert os.listdir(base) == []  # no partition level beneath it
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_default_is_a_fixed_path_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("SNTC_NO_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cc.resolve_cache_dir() == os.path.join(REPO, ".jax_cache")
    # derived from the package location alone: HOME, TMPDIR and the cwd
    # move nothing, and resolving does not set the variable
    monkeypatch.setenv("HOME", "/nonexistent-home")
    monkeypatch.setenv("TMPDIR", "/nonexistent-tmp")
    monkeypatch.chdir("/")
    assert cc.resolve_cache_dir() == os.path.join(REPO, ".jax_cache")
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_disable(monkeypatch):
    monkeypatch.setenv("SNTC_NO_COMPILE_CACHE", "1")
    assert cc.resolve_cache_dir() is None
    assert cc.enable_persistent_cache() is None


def test_main_leaves_env_alone_and_entries_land_directly_under_it(tmp_path):
    """The CLI contract end to end, in a fresh process: after ``main()``
    the variable still names the directory it was given, jax's config
    names the same one, and the entries sit directly under it."""
    cache = tmp_path / "x"
    code = (
        "import os, sys, jax, jax.numpy as jnp\n"
        "from sntc_tpu.app import main\n"
        "main(['fsck', sys.argv[1]])\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n"
        "assert os.environ['JAX_COMPILATION_CACHE_DIR'] == sys.argv[2]\n"
        "assert jax.config.jax_compilation_cache_dir == sys.argv[2]\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("SNTC_NO_COMPILE_CACHE", None)
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "ckpt"), str(cache)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    entries = os.listdir(cache)
    assert entries and all(
        os.path.isfile(cache / name) for name in entries
    ), entries
