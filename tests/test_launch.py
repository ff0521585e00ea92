"""Entry-point plumbing: the compilation-cache rule, the depth cut, and a
mesh that refuses to shrink."""

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import train
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR
from repro.launch.mesh import make_host_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _python(code, *args, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu", **env)
    r = subprocess.run([sys.executable, "-c", code, *args], capture_output=True,
                       text=True, timeout=120, env=full)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


_PROBE = """
import sys, jax
from repro.launch.compile_cache import enable_compile_cache
got = enable_compile_cache()
if sys.argv[1:] == ["compile"]:
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0)).block_until_ready()
print(got, jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_uses_env_dir_and_nothing_else(tmp_path):
    d = str(tmp_path / "cc")
    got, cfg = _python(_PROBE, "compile", JAX_COMPILATION_CACHE_DIR=d,
                       JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                       JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert got == cfg == d
    assert os.listdir(d), "no cache entry written where the variable points"


def test_compile_cache_defaults_to_fixed_checkout_dir():
    # nothing is compiled, so nothing is written into the checkout
    got, cfg = _python(_PROBE)
    assert got == cfg == CHECKOUT_CACHE_DIR
    assert CHECKOUT_CACHE_DIR == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def test_make_host_mesh_refuses_more_devices_than_exist():
    n = len(jax.devices())
    assert make_host_mesh(data=n).devices.size == n
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(data=n, model=2)


def test_depth_cut_must_be_a_multiple_of_the_pattern(tmp_path, capsys):
    # gemma2 alternates local and global attention: a 2-layer pattern
    with pytest.raises(SystemExit) as e:
        train.main(["--arch", "gemma2-9b", "--reduced", "--n-layers", "3",
                    "--workdir", str(tmp_path)])
    assert e.value.code == 2
    assert "not a multiple" in capsys.readouterr().err
