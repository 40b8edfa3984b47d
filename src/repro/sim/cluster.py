"""Cluster assembly helpers: wire up loop + metadata store + repository +
master + workers and register the assigned architecture zoo."""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from repro.configs.base import ArchConfig
from repro.core.api import INFaaS
from repro.core.master import Master, MasterConfig
from repro.core.metadata import MetadataStore
from repro.core.repository import ModelRepository
from repro.sim.clock import Clock, EventLoop, RealClock


def serving_archs() -> List[ArchConfig]:
    """Archs with at least one variant on standard worker hardware
    (cpu-host / tpu-v5e-1); the giants that only fit multi-chip slices are
    exercised through the multi-pod dry-run instead."""
    from repro.configs.registry import ARCHS
    from repro.core import profiler as prof
    out = []
    for cfg in ARCHS.values():
        vs = prof.generate_variants(cfg)
        if any(v.hardware in ("cpu-host", "tpu-v5e-1") for v in vs):
            out.append(cfg)
    return out


@dataclasses.dataclass
class Cluster:
    loop: Clock
    store: MetadataStore
    repo: ModelRepository
    master: Master
    api: INFaaS
    # real-backend executors created so far (one per worker): the wall-
    # clock runtime walks these to stop stepper threads at shutdown
    executors: List = dataclasses.field(default_factory=list)

    def run_until(self, t: float) -> None:
        self.loop.run_until(t)


def make_cluster(n_accel: int = 1, n_cpu: int = 0,
                 archs: Optional[Sequence[ArchConfig]] = None,
                 autoscale: bool = True,
                 cfg: Optional[MasterConfig] = None,
                 backend: str = "sim",
                 engine_cfg=None,
                 clock: str = "virtual") -> Cluster:
    """Assemble a cluster.

    ``backend="sim"`` (default): workers answer from profiled t(b) models —
    any scale, no JAX execution.

    ``backend="real"``: every worker gets an
    ``repro.serving.executor.EngineExecutor`` — jobs run for real on
    continuous-batching engines built from ``archs`` exactly as given
    (pass ``cfg.reduced()`` for host-CPU sizes, the published config for
    the chip), measured service times drive the virtual clock, and variant
    profiles are re-fit from the measurements as they accumulate. Pass a
    small ``archs`` list (each arch builds real model params) and
    optionally an ``EngineExecutorConfig`` as ``engine_cfg``.

    ``clock="wall"`` (requires ``backend="real"``): the control plane runs
    against ``RealClock`` — callbacks fire on a scheduler thread as wall
    time passes — and every worker gets a ``ThreadedEngineExecutor``
    stepped by its own background thread, with token streaming enabled.
    Wrap the result in ``repro.serving.runtime.ServingRuntime`` for
    thread-safe submission and drain-on-shutdown.
    """
    if backend not in ("sim", "real"):
        raise ValueError(f"unknown backend {backend!r} (sim|real)")
    if clock not in ("virtual", "wall"):
        raise ValueError(f"unknown clock {clock!r} (virtual|wall)")
    if clock == "wall" and backend != "real":
        raise ValueError("clock='wall' requires backend='real': the sim "
                         "executor has no work to do in real time")
    loop: Clock = RealClock() if clock == "wall" else EventLoop()
    store = MetadataStore()
    repo = ModelRepository()
    use_archs = list(archs if archs is not None else serving_archs())
    executor_factory = None
    executors: List = []
    if backend == "real":
        from repro.serving.executor import (EngineExecutor,
                                            EngineExecutorConfig)
        arch_cfgs = {a.name: a for a in use_archs}
        ecfg = engine_cfg or EngineExecutorConfig()
        model_cache: dict = {}   # share built params across workers

        if clock == "wall":
            from repro.serving.runtime import ThreadedEngineExecutor
            ecfg = dataclasses.replace(ecfg, stream=True)

            def executor_factory():
                ex = ThreadedEngineExecutor(arch_cfgs, ecfg,
                                            model_cache=model_cache)
                executors.append(ex)
                return ex
        else:
            def executor_factory():
                ex = EngineExecutor(arch_cfgs, ecfg,
                                    model_cache=model_cache)
                executors.append(ex)
                return ex
    master = Master(store, repo, loop, cfg or MasterConfig(),
                    autoscale=autoscale, executor_factory=executor_factory)
    api = INFaaS(master)
    for cfgA in use_archs:
        master.register_model(cfgA)
    for _ in range(n_accel):
        master.add_worker("accel")
    for _ in range(n_cpu):
        master.add_worker("cpu")
    return Cluster(loop, store, repo, master, api, executors=executors)
