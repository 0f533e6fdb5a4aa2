"""Where the entry points keep JAX's persistent compilation cache
(``repro.launch.compile_cache``): ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``.  Each case compiles in a fresh process."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")

COMPILE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: x * {k} + 1)(jnp.arange(7.0)).block_until_ready()
""")


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def _compile(env_dir, k):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", COMPILE.format(k=k)], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip().splitlines()[-1]


def test_env_dir_holds_every_entry(tmp_path):
    before = _entries(DEFAULT_DIR)
    where = tmp_path / "cache"
    assert _compile(where, k=3.0) == str(where)
    assert _entries(where), "nothing was cached in $JAX_COMPILATION_CACHE_DIR"
    assert _entries(DEFAULT_DIR) == before


def test_default_dir_is_in_the_checkout():
    before = _entries(DEFAULT_DIR)
    assert _compile(None, k=5.0) == DEFAULT_DIR
    new = _entries(DEFAULT_DIR) - before
    for name in new:  # leave the checkout's cache as it was
        os.remove(os.path.join(DEFAULT_DIR, name))
    assert any(n.startswith("jit__lambda") for n in new), (
        f"the program's entry was not cached in <checkout>/.jax_cache: {sorted(new)}"
    )
