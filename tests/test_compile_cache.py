"""The one compile-cache rule every entry point follows
(``repro.launch.cache.enable_compile_cache``)."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# enable the cache, then compile one program slow enough to be kept
PROBE = textwrap.dedent("""
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.launch.cache import enable_compile_cache
    print("DIR", enable_compile_cache())
    print("CONF", jax.config.jax_compilation_cache_dir)
    if len(sys.argv) > 1:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) @ x.T + 1)(jnp.ones((8, 8)))
""")


def _probe(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    args = [sys.executable, "-c", PROBE] + (["compile"] if compile_ else [])
    r = subprocess.run(args, capture_output=True, text=True, cwd=ROOT,
                       env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = dict(line.split(" ", 1) for line in r.stdout.splitlines()
               if line.startswith(("DIR ", "CONF ")))
    return out["DIR"], out["CONF"]


def test_env_var_directory_is_left_to_jax(tmp_path):
    cache = tmp_path / "cache"
    got, conf = _probe(cache, compile_=True)
    assert got == conf == str(cache)
    assert cache.is_dir() and any(cache.iterdir())


def test_default_is_the_checkout_cache_and_git_ignores_it():
    got, conf = _probe(None, compile_=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert got == conf == want
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
