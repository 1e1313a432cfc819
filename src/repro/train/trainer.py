"""Trainer: jit'd train_step loop + checkpoint/restart + straggler monitor
+ preemption-safe shutdown.  Works on one CPU device (tests/examples) and
on the production mesh (launch/train.py) through the same code path.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig
from repro.launch import steps as steps_lib
from repro.models import ffn
from repro.optim.adamw import OptimizerConfig
from repro.train import checkpoint
from repro.train import state as S
from repro.train.straggler import StepTimeMonitor, StragglerConfig


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_interval: int = 50
    keep_checkpoints: int = 3
    log_interval: int = 10
    loss_chunk: int = 512


def _span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(f"spt.train.{name}", **args)


class Trainer:
    def __init__(self, cfg: ModelConfig, ocfg: OptimizerConfig,
                 tcfg: TrainerConfig, mesh=None, rules=None,
                 seed: int = 0):
        self.cfg = cfg
        self.ocfg = ocfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.rules = rules
        self.monitor = StepTimeMonitor()
        self.metrics_log: list = []
        self._stop = False

        step_fn = steps_lib.build_train_step(cfg, ocfg,
                                             loss_chunk=tcfg.loss_chunk)
        # On a mesh, the state lives where state_specs puts it (each
        # device holds its share) and stays there across steps; batches
        # are placed by batch_specs as they arrive.
        self._state_sh = None
        out_sh = None
        if mesh is not None and rules is not None:
            self._state_sh = steps_lib._map_specs(
                mesh, S.state_specs(cfg, rules))
            out_sh = (self._state_sh, steps_lib._ns(mesh, PartitionSpec()))
        self._step = jax.jit(step_fn, out_shardings=out_sh,
                             donate_argnums=(0,))

        # resume-or-init
        start = None
        if tcfg.ckpt_dir:
            start = checkpoint.latest_step(tcfg.ckpt_dir)
        if start is not None:
            self.state = checkpoint.restore(tcfg.ckpt_dir, start)
            if self._state_sh is not None:
                self.state = jax.device_put(self.state, self._state_sh)
            self.start_step = int(start)
        else:
            init = S.init_state
            if self._state_sh is not None:    # born sharded, never whole
                init = jax.jit(init, static_argnums=0,
                               out_shardings=self._state_sh)
            self.state = init(cfg, jax.random.PRNGKey(seed))
            self.start_step = 0

        # preemption-safe: SIGTERM triggers an emergency checkpoint
        try:
            signal.signal(signal.SIGTERM, self._on_sigterm)
        except ValueError:      # not in main thread (tests)
            pass

    def _on_sigterm(self, *_):
        self._stop = True

    def _save(self, step: int) -> None:
        if self.tcfg.ckpt_dir:
            with _span("checkpoint"):
                checkpoint.save(self.state, step, self.tcfg.ckpt_dir,
                                keep=self.tcfg.keep_checkpoints)

    def _count(self, batch: Dict[str, jax.Array], metrics: dict) -> None:
        """The step's routed-FFN slot counts as a zero-length profiler
        event, from shapes and the ``dropped`` already on the host."""
        b, s = np.shape(batch["tokens"])
        if self.cfg.frontend_tokens and "frontend_embeds" in batch:
            s += np.shape(batch["frontend_embeds"])[1]
        counts = ffn.slot_counts(self.cfg, b, s, metrics["dropped"])
        if counts is not None:
            with _span("counters", **counts):
                pass

    def _place(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        if self._state_sh is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        specs = steps_lib.batch_specs(
            self.cfg, {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
                       for k, v in batch.items()}, self.rules)
        return jax.device_put(batch, steps_lib._map_specs(self.mesh, specs))

    def run(self, data: Iterator[Dict[str, np.ndarray]],
            step_hook: Optional[Callable[[int, dict], None]] = None) -> dict:
        """Train until ``total_steps``, the data's end or SIGTERM.

        Each step is a profiler step span ``spt.train`` (``step_num`` the
        step's index) with children ``spt.train.next_batch``, ``.place``,
        ``.dispatch``, ``.fetch``, ``.hook`` and, when a save runs,
        ``.checkpoint``; after the fetch, a zero-length
        ``spt.train.counters`` carries the routed FFN's ``slot_counts``
        where the model routes. Without a running profiler the spans cost
        a few microseconds a step."""
        step = self.start_step
        data = iter(data)
        while step < self.tcfg.total_steps and not self._stop:
            with jax.profiler.StepTraceAnnotation("spt.train", step_num=step):
                with _span("next_batch"):
                    batch = next(data, None)
                if batch is None:
                    break
                with _span("place"):
                    batch = self._place(batch)
                self.monitor.start()
                with _span("dispatch"):
                    self.state, metrics = self._step(self.state, batch)
                with _span("fetch"):
                    metrics = {k: float(v) for k, v in metrics.items()}
                self.monitor.stop(step)
                self._count(batch, metrics)
                step += 1
                if step % self.tcfg.log_interval == 0 or step == 1:
                    self.metrics_log.append({"step": step, **metrics})
                if step_hook:
                    with _span("hook"):
                        step_hook(step, metrics)
                if self.tcfg.ckpt_dir and step % self.tcfg.ckpt_interval == 0:
                    self._save(step)
                if self.monitor.should_act():
                    # straggler density high: checkpoint eagerly so a
                    # scheduler can replace the slow host with bounded
                    # lost work
                    self._save(step)
                    self.monitor.events.append(
                        {"step": step, "action": "eager_checkpoint"})
        self._save(step)
        return {"final_step": step,
                "metrics": self.metrics_log,
                "straggler": self.monitor.summary(),
                "interrupted": self._stop}
