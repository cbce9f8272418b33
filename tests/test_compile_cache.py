"""The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
else one fixed, git-ignored directory inside the checkout."""
import os
import subprocess
import sys

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_unset_env_points_the_cache_into_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert os.path.dirname(REPO_CACHE_DIR) == _REPO
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_env_dir_wins_and_receives_the_entries(tmp_path):
    """With the variable set, the helper sets nothing and a compiled
    program lands in that directory (run in a child: the cache directory
    is fixed for the life of a process once used)."""
    script = ("from repro.launch.compile_cache import enable_compile_cache\n"
              "print(enable_compile_cache())\n"
              "import jax, jax.numpy as jnp\n"
              "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
              "print(jax.config.jax_compilation_cache_dir)\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=_REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path)] * 2
    assert any(name.endswith("-cache") for name in os.listdir(tmp_path))


def test_a_program_whose_scopes_changed_is_compiled_anew(tmp_path):
    """Named scopes are part of the cache key: the same computation under
    another scope name gets an entry of its own (it would otherwise load an
    executable carrying the old op names), and the same scope hits."""
    script = ("import sys\n"
              "from repro.launch.compile_cache import enable_compile_cache\n"
              "enable_compile_cache()\n"
              "import jax, jax.numpy as jnp\n"
              "def scoped(x):\n"
              "    with jax.named_scope(sys.argv[1]):\n"
              "        return x * 2 + 1\n"
              "jax.jit(scoped)(jnp.ones(3)).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    entries = []
    for scope in ("ants.sense", "ants.move", "ants.sense"):
        r = subprocess.run([sys.executable, "-c", script, scope], env=env,
                           cwd=_REPO, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode == 0, r.stderr
        entries.append(sorted(n for n in os.listdir(tmp_path)
                              if n.startswith("jit_scoped")))
    assert [len(e) for e in entries] == [1, 2, 2]
