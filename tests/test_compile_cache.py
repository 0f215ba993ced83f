"""Where the entry points keep JAX's persistent compilation cache.

Subprocesses: the cache directory is process-global JAX configuration."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, **env) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"} | {"JAX_PLATFORMS": "cpu",
                                                   **env}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_env_dir_is_used_as_set(tmp_path):
    got = _run("""
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert got == [str(tmp_path)]
    assert os.listdir(tmp_path), "nothing was cached where the env says"


def test_default_dir_is_fixed_in_the_checkout():
    got = _run("""
        import sys; sys.path.insert(0, "src")
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
    """)
    want = os.path.join(REPO, ".jax_cache")
    assert got == [want, want]
