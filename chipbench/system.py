"""The system under test, bound once for every driver: the program's model,
its donated train step compiled for the cell's mesh, and its train state
made from the benchmark's weights.

``make_step`` and ``open_pipeline`` are the two calls into the program that
a window drives; tests replace them to plant faults underneath a run.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from chipbench import bench


def make_step(model, opt: dict):
    from repro.train import make_train_step
    return make_train_step(model, peak_lr=opt["peak_lr"], warmup=opt["warmup"],
                           total_steps=opt["total_steps"],
                           clip_norm=opt["clip_norm"],
                           weight_decay=opt["weight_decay"])


def open_pipeline(paths, batch: int, seq_len: int, seed: int):
    from repro.data import TokenPipeline
    return TokenPipeline(paths, batch=batch, seq_len=seq_len, seed=seed)


def build_batch(model, raw: dict):
    from repro.launch.train import build_batch as program_build_batch
    return program_build_batch(model.cfg, raw, 1)


class TrainSystem:
    """The cell's model, mesh, train state and compiled step."""

    def __init__(self, cell: dict, seed: int, devices):
        from repro.train import TrainState
        from repro.train.optim import adamw_init

        cfg, opt = cell["config"], cell["traffic"]["optimizer"]
        self.fam = bench.family(cell)
        self.model = self.fam.program_model(cfg)
        data, mdl = cfg["mesh"]["data"], cfg["mesh"]["model"]
        if data * mdl > 1:
            from repro.launch.mesh import make_host_mesh
            from repro.parallel import (ParallelismConfig, opt_shardings,
                                        param_shardings)
            from repro.parallel.actctx import activation_context
            self.mesh = make_host_mesh(data=data, model=mdl)
            pcfg = ParallelismConfig(zero3=cfg["zero3"])
            psh = param_shardings(self.model, self.mesh, pcfg)
            osh = opt_shardings(self.model, self.mesh, pcfg)
            rep = NamedSharding(self.mesh, P())
            self.batch_sharding = NamedSharding(self.mesh, P(("data",), None))
            self.context = lambda: _both(self.mesh, activation_context(self.mesh))
        else:
            one = SingleDeviceSharding(devices[0])
            psh = osh = jax.tree.map(lambda _: one, self.model.abstract())
            rep = self.batch_sharding = one
            self.mesh = None
            self.context = contextlib.nullcontext
        self.param_shardings, self.replicated = psh, rep
        self.state_shardings = TrainState(
            params=psh, opt={"m": osh, "v": osh, "count": rep}, step=rep, err=None)
        self.init = self.fam.make_init(cfg, psh)
        self.key = self.fam.seed_key(seed)
        params = self.init(self.key)
        moments = jax.jit(adamw_init, out_shardings={"m": osh, "v": osh,
                                                     "count": rep})(params)
        self.state = TrainState(params=params, opt=moments,
                                step=jax.device_put(jnp.zeros((), jnp.int32), rep),
                                err=None)
        self._step = make_step(self.model, opt)
        self.step = None
        self.memory_analysis = None

    def compile(self, batch_shape: tuple):
        """Compile the donated step for this batch shape."""
        tok = jax.ShapeDtypeStruct(batch_shape, jnp.int32,
                                   sharding=self.batch_sharding)
        with self.context():
            self.step = jax.jit(
                self._step, donate_argnums=0,
                in_shardings=(self.state_shardings, self.batch_sharding),
                out_shardings=(self.state_shardings, self.replicated),
            ).lower(self.state, {"tokens": tok, "targets": tok}).compile()
        self.memory_analysis = self.step.memory_analysis()

    def put_batch(self, raw: dict):
        return jax.device_put(build_batch(self.model, raw), self.batch_sharding)

    def run_step(self, batch):
        self.state, metrics = self.step(self.state, batch)
        return metrics

    def footprint_bytes(self) -> int:
        """Per-device bytes the compiled step holds: arguments, outputs
        not aliased to them, and temporaries."""
        ma = self.memory_analysis
        if ma is None:
            return 0
        return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                   - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


@contextlib.contextmanager
def _both(mesh, actctx):
    with mesh, actctx:
        yield
