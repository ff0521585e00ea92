"""Logical-axis -> mesh-axis sharding rules.

Every ParamSpec names its dims with logical axes; one rule table maps those
to mesh axes.  The rule engine is divisibility-aware: a rule only applies
when the dim is divisible by the mesh axis size (GSPMD would pad otherwise;
we allow padding ONLY for kv_heads, where 8-way KV on a 16-way model axis
is the intended production layout — see DESIGN.md §7).

Default layout (v5e (data=16, model=16), multi-pod adds a leading "pod" DP
axis):

  TP ("model"):   heads, kv_heads, ff, vocab, mamba d_inner, rwkv fused
                  heads, expert d_ff
  DP ("pod","data"): batch dim of every activation / input
  ZeRO-3 ("data"): MoE expert dim E (weights FSDP-gathered per layer) and,
                  when ``zero3=True``, any largest-dim of dense params
  SP:             KV-cache seq dim stays unsharded by default (hillclimb
                  variant shards it with flash-decode combine)
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.specs import ParamSpec, map_logical, tree_paths

__all__ = ["ParallelismConfig", "abstract_mesh", "logical_to_pspec",
           "param_shardings", "batch_shardings", "cache_shardings",
           "opt_shardings"]


def abstract_mesh(axis_sizes, axis_names) -> "jax.sharding.AbstractMesh":
    """Device-free mesh of the given shape.  Rules only read mesh shape, so
    an abstract mesh is all the engine ever needs."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))


@dataclasses.dataclass(frozen=True)
class ParallelismConfig:
    """Per-run parallelism policy (independent of the model config)."""
    zero3: bool = False          # FSDP dense params over "data"
    zero1_moments: bool = True   # shard optimizer moments over "data" too
    shard_kv_cache_time: bool = True  # time-shard decode caches when kv%model!=0
    experts_fsdp: bool = True    # MoE expert dim over "data" (ZeRO-3 style)
    compressed_dp: bool = False  # int8 compressed DP grad reduction (beyond-paper)


# rule table: logical axis -> preferred mesh axis (in priority order)
_TP_RULES = {
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "inner": "model",       # mamba d_inner
    "inner2": "model",      # mamba in_proj fused (2*d_inner)
    "heads_d": "model",     # rwkv fused H*D
    "experts_r": None,      # router output: small, replicated
    "embed": None,          # activations replicated over model between layers
    "embed_o": None,
    "layers": None,         # scan dim
}


def _divisible(dim: int, mesh: Mesh, axis: str) -> bool:
    return dim % mesh.shape[axis] == 0


def logical_to_pspec(spec: ParamSpec, mesh: Mesh, pcfg: ParallelismConfig) -> P:
    """One ParamSpec -> PartitionSpec under the rule table."""
    entries: list = []
    used = set()
    for dim, ax in zip(spec.shape, spec.axes):
        target: Optional[str] = None
        if ax == "experts" and pcfg.experts_fsdp and "data" in mesh.axis_names:
            target = "data"
        else:
            rule = _TP_RULES.get(ax)
            if rule and rule in mesh.axis_names and rule not in used:
                # strict divisibility: pjit rejects padded in_shardings, so
                # e.g. kv=8 heads or H=40 on a 16-way model axis fall back to
                # replication (decode caches re-shard over time instead; the
                # seq-parallel attention variant is the hillclimb lever).
                if _divisible(dim, mesh, rule):
                    target = rule
        if target:
            used.add(target)
        entries.append(target)
    # optional ZeRO-3 for dense params: shard the largest unsharded dim
    # over "data" (divisible only — padding a ZeRO gather wastes real bytes)
    if pcfg.zero3 and "data" in mesh.axis_names and "data" not in used \
            and "experts" not in spec.axes and len(spec.shape) >= 2:
        cands = sorted(
            (i for i, e in enumerate(entries)
             if e is None and _divisible(spec.shape[i], mesh, "data")
             and spec.axes[i] != "layers"),
            key=lambda i: -spec.shape[i])
        if cands:
            entries[cands[0]] = "data"
    return P(*entries)


def _ns(mesh, pspec):
    return NamedSharding(mesh, pspec)


def param_shardings(model, mesh: Mesh, pcfg: ParallelismConfig):
    """NamedSharding tree matching model.param_specs()."""
    return map_logical(model.param_specs(),
                       lambda s: _ns(mesh, logical_to_pspec(s, mesh, pcfg)))


def dp_spec(mesh: Mesh, dim: int):
    """The DP axes if ``dim`` divides evenly over them, else None (replicate
    — e.g. global_batch=1 long-context decode)."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    if dim % size:
        return None
    return dp if len(dp) > 1 else dp[0]


def batch_shardings(mesh: Mesh, batch_tree):
    """Shard the leading (batch) dim of every input over all DP axes."""
    def one(x):
        ndim = len(x.shape)
        if not ndim:
            return _ns(mesh, P())
        return _ns(mesh, P(dp_spec(mesh, x.shape[0]), *([None] * (ndim - 1))))

    return jax.tree.map(one, batch_tree)


def cache_shardings(model, mesh: Mesh, pcfg: ParallelismConfig, cache_tree):
    """Decode-state shardings, keyed on the cache tree's own structure.

    * attention kv ("self"/"cross" -> k/v (G,B,T,KV,Dh)): batch over DP;
      kv_heads over model when divisible, otherwise the TIME dim is
      sharded over model — GSPMD then emits the flash-decode pattern
      (partial softmax + tiny all-reduces; verified, DESIGN.md §7) and the
      dynamic cache update stays sharded.
    * mamba ("ssm_state" -> conv (G,B,K-1,di) / ssm (G,B,di,n)): d_inner
      over model.
    * rwkv ("tm_state" (G,B,H,Dk,Dv)): heads over model;
      shift states (G,B,d): d over model.
    Divisibility-gated except kv_heads (see above)."""
    msize = mesh.shape["model"]

    def shard_dim(shape, i):
        return "model" if shape[i] % msize == 0 else None

    def one(path, leaf):
        keys = [getattr(k, "key", str(k)) for k in path]
        shape = leaf.shape
        dp = dp_spec(mesh, shape[1])   # dim 1 = batch (dim 0 = scan groups)
        if "self" in keys or "cross" in keys:      # (G,B,T,KV,Dh)
            if shape[3] % msize == 0:              # kv heads shard evenly
                return _ns(mesh, P(None, dp, None, "model", None))
            if pcfg.shard_kv_cache_time and shape[2] % msize == 0:
                return _ns(mesh, P(None, dp, "model", None, None))
            return _ns(mesh, P(None, dp, None, None, None))
        if "conv" in keys:                          # (G,B,K-1,di)
            return _ns(mesh, P(None, dp, None, shard_dim(shape, 3)))
        if "ssm" in keys:                           # (G,B,di,n)
            return _ns(mesh, P(None, dp, shard_dim(shape, 2), None))
        if "tm_state" in keys:                      # (G,B,H,Dk,Dv)
            return _ns(mesh, P(None, dp, shard_dim(shape, 2), None, None))
        if len(shape) == 3:                         # shifts (G,B,d)
            return _ns(mesh, P(None, dp, shard_dim(shape, 2)))
        return _ns(mesh, P(*([None] * len(shape))))

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def opt_shardings(model, mesh: Mesh, pcfg: ParallelismConfig):
    """Adam moments: like params, plus ZeRO-1 sharding of the largest
    still-unsharded divisible dim over "data"."""
    def one(spec: ParamSpec):
        ps = logical_to_pspec(spec, mesh, pcfg)
        entries = list(ps) + [None] * (len(spec.shape) - len(ps))
        if pcfg.zero1_moments and "data" in mesh.axis_names \
                and "data" not in [e for e in entries if e]:
            cands = sorted(
                (i for i, e in enumerate(entries)
                 if e is None and spec.shape[i] % mesh.shape["data"] == 0),
                key=lambda i: -spec.shape[i])
            if cands:
                entries[cands[0]] = "data"
        return _ns(mesh, P(*entries))

    return map_logical(model.param_specs(), one)
