"""The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, and to ``<checkout>/.jax_cache`` otherwise. Each case runs in a
subprocess so the test process itself never turns the cache on."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SNIPPET = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    print(jax.config.jax_compilation_cache_dir)
    if jax.config.jax_compilation_cache_dir.startswith({tmp!r}):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
""")


def _run(tmp_path, env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", SNIPPET.format(tmp=str(tmp_path))],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_env_dir_is_used_and_left_alone(tmp_path):
    where = tmp_path / "cache"
    returned, configured = _run(tmp_path, where)
    assert returned == configured == str(where)
    assert any(where.iterdir())            # the cache was written there


def test_default_is_fixed_checkout_path(tmp_path):
    returned, configured = _run(tmp_path, None)
    assert returned == configured == os.path.join(os.path.realpath(REPO),
                                                  ".jax_cache")
