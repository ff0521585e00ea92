"""ParamSpec: one param definition -> init / abstract / sharding.

Every model parameter is declared once as a ``ParamSpec(shape, axes)`` where
``axes`` names each dimension with a *logical* axis ("embed", "heads",
"ff", "vocab", "experts", ...).  From that single declaration we derive:

* ``init_params``      — real arrays (smoke tests, examples)
* ``abstract_params``  — ShapeDtypeStructs, no allocation (dry-run)
* ``map_logical``      — PartitionSpec per param via the divisibility-aware
                         rule engine in ``repro.parallel.sharding``
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ParamSpec", "init_params", "abstract_params", "map_logical", "tree_paths"]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                 # logical axis name (or None) per dim
    init: str = "normal"        # normal | zeros | ones | s4d_real | dt_bias
    scale: float = 1.0          # stddev multiplier (normal) / fan-in override
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_paths(tree, prefix=""):
    """Flatten a nested-dict spec tree to {dotted.path: leaf}."""
    out = {}
    if _is_spec(tree) or not isinstance(tree, dict):
        out[prefix.rstrip(".")] = tree
        return out
    for k, v in tree.items():
        out.update(tree_paths(v, f"{prefix}{k}."))
    return out


def init_params(spec_tree, key, param_dtype=None):
    """Materialize real arrays from a spec tree (used by smoke tests)."""
    flat = tree_paths(spec_tree)
    keys = jax.random.split(key, max(len(flat), 1))
    out_flat = {}
    for (path, spec), k in zip(sorted(flat.items()), keys):
        dtype = param_dtype or spec.dtype
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, dtype)
        elif spec.init == "ones":
            arr = jnp.full(spec.shape, spec.scale, dtype)  # "ones" = constant ``scale``
        elif spec.init == "s4d_real":     # Mamba's A_log: log(1..n) in every row
            n = spec.shape[-1]
            arr = jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                                   spec.shape).astype(dtype)
        elif spec.init == "dt_bias":      # Mamba's: softplus^-1(dt), dt log-uniform
            arr = _dt_bias(k, spec.shape).astype(dtype)
        else:
            fan_in = spec.shape[0] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
            std = spec.scale / np.sqrt(max(fan_in, 1))
            arr = (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dtype)
        out_flat[path] = arr
    return _unflatten(out_flat)


def _dt_bias(key, shape):
    """Mamba's dt bias: the inverse softplus of dt drawn log-uniformly from
    [1e-3, 1e-1], so that softplus(bias) starts at such a dt."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def abstract_params(spec_tree, param_dtype=None):
    """ShapeDtypeStruct tree — the dry-run stand-in (no allocation)."""
    flat = tree_paths(spec_tree)
    out = {p: jax.ShapeDtypeStruct(s.shape, param_dtype or s.dtype)
           for p, s in flat.items()}
    return _unflatten(out)


def map_logical(spec_tree, fn: Callable[[ParamSpec], Any]):
    """Apply ``fn(spec)`` per leaf, preserving structure (sharding derivation)."""
    flat = tree_paths(spec_tree)
    return _unflatten({p: fn(s) for p, s in flat.items()})


def _unflatten(flat: dict):
    tree: dict = {}
    for path, v in flat.items():
        parts = path.split(".")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree
