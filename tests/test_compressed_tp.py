"""Compressed TP-reduce numerics (§Perf iteration 7 — kept as a flagged
variant; see EXPERIMENTS.md for why it is not the default)."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.slow
def test_compressed_rowparallel_numerics():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", """
import jax, jax.numpy as jnp
from repro.parallel.actctx import activation_context
from repro.parallel.compressed import rowparallel_einsum_compressed
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=2, model=4)
y = jax.random.normal(jax.random.key(0), (4, 16, 32), jnp.float32).astype(jnp.bfloat16)
w = jax.random.normal(jax.random.key(1), (32, 24), jnp.float32) * 0.2
ref = jnp.einsum("bse,ed->bsd", y.astype(jnp.float32), w)
with mesh, activation_context(mesh):
    out = jax.jit(lambda y, w: rowparallel_einsum_compressed(y, w))(y, w)
rel = float(jnp.linalg.norm(out.astype(jnp.float32) - ref) / jnp.linalg.norm(ref))
assert rel < 0.02, rel
print("REL", rel)
"""], capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "REL" in out.stdout


def test_fallback_without_context():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.parallel.compressed import rowparallel_einsum_compressed
    y = jax.random.normal(jax.random.key(0), (2, 8, 16))
    w = jax.random.normal(jax.random.key(1), (16, 12))
    out = rowparallel_einsum_compressed(y, w)
    ref = jnp.einsum("bse,ed->bsd", y, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=1e-3)
