"""What `repro.env` observes instead of asking for: interpret mode from
the platform, asked lazily, and where the compile cache goes."""
import os
import pathlib
import subprocess
import sys

import jax

from repro import env

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, **extra) -> str:
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(extra)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=e)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_importing_the_kernels_initializes_no_backend():
    out = _run("import repro.kernels.ops, repro.core.boundary\n"
               "from jax._src import xla_bridge as xb\n"
               "print(xb.backends_are_initialized())")
    assert out.strip() == "False"


def test_interpret_mode_follows_the_platform():
    assert env.pallas_interpret() == (jax.default_backend() != "tpu")


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro import env\n"
            "print(env.use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
            ".block_until_ready()")
    out = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert out.split() == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir()), "nothing was cached"


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout():
    out = _run("import jax\nfrom repro import env\n"
               "print(env.use_compile_cache())\n"
               "print(jax.config.jax_compilation_cache_dir)")
    want = str(ROOT / ".jax_cache")
    assert out.split() == [want, want]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
