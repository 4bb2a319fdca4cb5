"""TrainSession: the one-call facade over model build + trainer + host loop.

Examples, benchmarks and the train CLI go through this instead of reaching
into trainer internals::

    from repro.launch.session import TrainSession

    sess = TrainSession.from_config("paper-350m", strategy="acesync")
    sess.run(100)
    print(sess.losses[-1], sess.comm_bytes)

Any registered strategy name (see ``repro.strategies.list_strategies()``)
or a :class:`~repro.strategies.SyncStrategy` instance works.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax

from repro.configs import ARCHS, SMOKE_ARCHS
from repro.configs.base import RunConfig, ShapeConfig
from repro.data.pipeline import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import TrainLoop
from repro.models.registry import build_model
from repro.strategies import SyncStrategy


class TrainSession:
    """Owns (model, run, loop, pipeline, state) for one training run."""

    def __init__(self, model, run: RunConfig, mesh=None,
                 strategy: Union[str, SyncStrategy] = "acesync",
                 n_edge_devices: int = 8, seed: int = 0,
                 fault_schedule=None, elastic: bool = True,
                 blocking_replans: bool = False):
        enable_compile_cache()
        self.model = model
        self.run_config = run
        self.mesh = mesh
        self.loop = TrainLoop(model, run, mesh=mesh, strategy=strategy,
                              n_edge_devices=n_edge_devices, seed=seed,
                              fault_schedule=fault_schedule,
                              elastic=elastic,
                              blocking_replans=blocking_replans)
        self.pipeline = TokenPipeline(model, run.shape, seed=seed)
        self._rng = jax.random.PRNGKey(run.seed)
        self.state = None

    @classmethod
    def from_config(cls, arch: str,
                    strategy: Union[str, SyncStrategy] = "acesync",
                    mesh=None, *, smoke: bool = True, seq_len: int = 256,
                    batch: int = 8, steps: int = 100,
                    n_layers: Optional[int] = None,
                    n_edge_devices: int = 8, seed: int = 0,
                    fault_schedule=None, elastic: bool = True,
                    blocking_replans: bool = False,
                    **run_kw) -> "TrainSession":
        """Build a session from an architecture name + strategy spec.
        ``n_layers`` cuts the model's depth and keeps its widths: how a
        full-width configuration is fitted to one chip's memory."""
        cfg = (SMOKE_ARCHS if smoke else ARCHS)[arch]
        if n_layers is not None:
            cfg = dataclasses.replace(cfg, n_layers=n_layers)
        shape = ShapeConfig("session", seq_len, batch, "train")
        run_kw.setdefault("warmup_steps", max(2, steps // 10))
        run = RunConfig(model=cfg, shape=shape, total_steps=steps, **run_kw)
        model = build_model(cfg, run)
        return cls(model, run, mesh=mesh, strategy=strategy,
                   n_edge_devices=n_edge_devices, seed=seed,
                   fault_schedule=fault_schedule, elastic=elastic,
                   blocking_replans=blocking_replans)

    # ---- lifecycle ------------------------------------------------------
    @property
    def trainer(self):
        return self.loop.trainer

    @property
    def strategy(self) -> SyncStrategy:
        return self.loop.strategy

    def init(self):
        """Restore the latest checkpoint or initialize fresh state."""
        if self.state is None:
            self.state = self.loop.restore_or_init(self._rng, self.pipeline)
        return self.state

    def run(self, n_steps: Optional[int] = None,
            log_every: int = 10) -> "TrainSession":
        """Run n_steps (default: the RunConfig total) of the control loop."""
        self.init()
        self.state = self.loop.run_steps(
            self.state, self.pipeline,
            n_steps if n_steps is not None else self.run_config.total_steps,
            log_every=log_every)
        return self

    def finish(self):
        """Flush pending checkpoint writes (re-raises a failed write)."""
        self.loop.ckpt.wait()

    def save_now(self):
        """Force a full-state checkpoint at the current step (blocking)."""
        import jax as _jax
        step = int(_jax.device_get(
            _jax.tree.leaves(self.state["step"])[0].reshape(-1)[0]))
        if self.loop._pipeline is None:
            self.loop._pipeline = self.pipeline
        self.loop.ckpt.save(step, self.state,
                            extras=self.loop.ckpt_extras(), blocking=True)
        return step

    # ---- results --------------------------------------------------------
    @property
    def history(self):
        return self.loop.history

    @property
    def losses(self):
        return [h["loss"] for h in self.loop.history if "loss" in h]

    @property
    def comm_bytes(self) -> float:
        """Cumulative pod-tier wire bytes (strategy-priced, per device)."""
        return self.loop.comm_bytes
