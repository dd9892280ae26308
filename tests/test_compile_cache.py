"""Where compiled programs persist (repro.launch.cache). Each case runs in
a subprocess, so the cache setting never leaks into the suite."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_WORKER = textwrap.dedent("""
    import sys
    import jax
    from repro.launch.cache import use_compile_cache
    path = use_compile_cache()
    if sys.argv[1] == "compile":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(4.0)).block_until_ready()
    print("CACHE:" + path)
    print("CONFIG:" + str(jax.config.jax_compilation_cache_dir))
""")


def _run(env_dir):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    # the no-variable case would write into the checkout: only read the
    # setting there, compile nothing
    mode = "read" if env_dir is None else "compile"
    proc = subprocess.run([sys.executable, "-c", _WORKER, mode], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = dict(l.split(":", 1) for l in proc.stdout.splitlines()
               if l.startswith(("CACHE:", "CONFIG:")))
    return out["CACHE"], out["CONFIG"]


def test_env_variable_places_the_cache(tmp_path):
    path, config = _run(tmp_path)
    assert path == config == str(tmp_path)
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_default_cache_is_in_the_checkout():
    path, config = _run(None)
    assert path == config == str(ROOT / ".jax_cache")
