"""ElasticTrainer: the live JAX realization of Dorm's checkpoint-based
resource-adjustment protocol (§III-C.2).

One ElasticTrainer = one distributed-ML *application* running on its Dorm
partition. The partition's containers map to a JAX device group; training is
data-parallel over a ('data',) mesh built from exactly those devices. When
the DormMaster resizes the partition:

    save_state()  -> checkpoint (params, opt state, data cursor, step)
    kill()        -> drop the jitted step + device buffers
    resume(n')    -> rebuild the mesh over the new device group, restore the
                     checkpoint WITH RESHARDING, re-shard the data pipeline
                     to n' shards at the same global step, continue training

`ElasticJaxProtocol` adapts this to the `core.adjustment.AdjustmentProtocol`
interface so a DormMaster can drive real training jobs end-to-end.
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..checkpoint import load_checkpoint, save_checkpoint
from ..core.adjustment import CheckpointHandle
from ..core.types import ApplicationSpec
from ..data import DataConfig, TokenPipeline
from ..models.config import ModelConfig
from .optimizer import OptimizerSpec
from .train_loop import init_train_state, make_train_step


@dataclasses.dataclass
class ElasticConfig:
    model: ModelConfig
    optimizer: OptimizerSpec
    data: DataConfig
    ckpt_dir: str = ""
    microbatches: int = 1
    remat: bool = True
    remat_policy: str = "full"
    # tensor-parallel width per partition: the device group becomes a
    # (data = n/model_parallel, model = model_parallel) sub-mesh and params
    # shard over "model" with the same rules as the production launcher.
    model_parallel: int = 1
    seed: int = 0

    def __post_init__(self):
        if not self.ckpt_dir:
            self.ckpt_dir = tempfile.mkdtemp(prefix="dorm-ckpt-")


class ElasticTrainer:
    """Data-parallel trainer that can be killed and resumed at a different
    device count without losing progress."""

    def __init__(self, cfg: ElasticConfig, app_id: str = "app"):
        self.cfg = cfg
        self.app_id = app_id
        self.devices: List[jax.Device] = []
        self.mesh: Optional[Mesh] = None
        self.state: Optional[Dict[str, Any]] = None
        self.pipeline: Optional[TokenPipeline] = None
        self._step_fn = None
        self.global_step = 0
        self.history: List[Dict[str, float]] = []

    # ------------------------------------------------------------ lifecycle

    def start(self, devices: Sequence[jax.Device]) -> None:
        """Fresh start on a device group (one data shard per device)."""
        self._build(devices)
        key = jax.random.PRNGKey(self.cfg.seed)
        with jax.default_device(self.devices[0]):
            state = init_train_state(key, self.cfg.model, self.cfg.optimizer)
        self.state = jax.device_put(state, self._state_sharding(state))
        self.pipeline = TokenPipeline(self.cfg.data,
                                      num_shards=1, shard_id=0,
                                      start_step=0)
        self.global_step = 0

    def save_state(self) -> CheckpointHandle:
        """Step 1 of the protocol: write to 'reliable storage'."""
        host_state = jax.device_get(self.state)
        meta = {"global_step": self.global_step,
                "data": self.pipeline.state_dict()}
        path = save_checkpoint(self.cfg.ckpt_dir, self.app_id, host_state,
                               meta=meta)
        return CheckpointHandle(self.app_id, path, step=self.global_step,
                                meta=meta)

    def kill(self) -> None:
        """Step 2: release compute (containers are being destroyed)."""
        self.state = None
        self._step_fn = None
        self.mesh = None
        self.devices = []

    def resume(self, devices: Sequence[jax.Device],
               ckpt: Optional[CheckpointHandle] = None) -> None:
        """Step 3: rebuild at the new size and restore with resharding."""
        self._build(devices)
        like = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(self.cfg.seed),
                                     self.cfg.model, self.cfg.optimizer))
        shardings = self._state_sharding(like)
        self.state = load_checkpoint(self.cfg.ckpt_dir, self.app_id, like,
                                     shardings=shardings)
        meta = ckpt.meta if ckpt is not None else {}
        self.global_step = int(meta.get("global_step", self.global_step))
        data_state = meta.get("data", {"step": self.global_step,
                                       "seed": self.cfg.data.seed})
        self.pipeline = TokenPipeline.restore(self.cfg.data, data_state,
                                              num_shards=1, shard_id=0)

    def resize(self, devices: Sequence[jax.Device]) -> CheckpointHandle:
        """The full save -> kill -> resume cycle in one call."""
        ckpt = self.save_state()
        self.kill()
        self.resume(devices, ckpt)
        return ckpt

    # ------------------------------------------------------------- training

    def train_steps(self, n: int) -> Dict[str, float]:
        assert self.state is not None, "trainer not started/resumed"
        last: Dict[str, float] = {}
        for _ in range(n):
            batch_np = self.pipeline.next_batch()
            batch = jax.device_put(batch_np, self._batch_sharding(batch_np))
            self.state, metrics = self._step_fn(self.state, batch)
            self.global_step += 1
            last = {k: float(v) for k, v in metrics.items()}
            last["step"] = self.global_step
            self.history.append(last)
        return last

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    # ------------------------------------------------------------ internals

    def _build(self, devices: Sequence[jax.Device]) -> None:
        devices = list(devices)
        if not devices:
            raise ValueError("need at least one device")
        mp = self.cfg.model_parallel
        if len(devices) % mp:
            raise ValueError(f"device count {len(devices)} must divide "
                             f"model_parallel {mp}")
        dp = len(devices) // mp
        if self.cfg.data.global_batch % max(dp, 1):
            raise ValueError(
                f"global_batch {self.cfg.data.global_batch} must divide "
                f"data-parallel width {dp}")
        self.devices = devices
        if mp > 1:
            self.mesh = Mesh(np.array(devices).reshape(dp, mp),
                             ("data", "model"))
        else:
            self.mesh = Mesh(np.array(devices), ("data",))
        step = make_train_step(self.cfg.model, self.cfg.optimizer,
                               microbatches=self.cfg.microbatches,
                               remat=self.cfg.remat,
                               remat_policy=self.cfg.remat_policy)
        self._step_fn = jax.jit(step, donate_argnums=(0,))

    def _state_sharding(self, state) -> Any:
        if "model" in self.mesh.axis_names:
            from ..launch.shardings import param_specs, to_named
            return to_named(param_specs(state, self.mesh), self.mesh)
        repl = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda _: repl, state)

    def _batch_sharding(self, batch) -> Any:
        def spec(v):
            if v.ndim >= 3 and v.shape[0] == 3:          # mrope positions
                return NamedSharding(self.mesh, P(None, "data"))
            return NamedSharding(self.mesh, P("data"))
        return {k: spec(v) for k, v in batch.items()}


class ElasticJaxProtocol:
    """core.adjustment.AdjustmentProtocol backed by real ElasticTrainers.

    `device_pool`: all devices Dorm manages. Each container = a fixed-size
    device group; an app with n containers trains on n * devices_per_container
    devices. Trainers are registered per app_id before submission."""

    def __init__(self, device_pool: Sequence[jax.Device],
                 devices_per_container: int = 1,
                 oversubscribe: bool = False):
        """`oversubscribe`: allow containers to share physical devices
        (CPU demo mode -- a production pool has one device per container
        slot; the trainer then runs on the deduplicated device set)."""
        self.pool = list(device_pool)
        self.dpc = devices_per_container
        self.oversubscribe = oversubscribe
        self.trainers: Dict[str, ElasticTrainer] = {}
        self.assignments: Dict[str, List[jax.Device]] = {}
        self.pending_ckpt: Dict[str, CheckpointHandle] = {}

    def register(self, app_id: str, trainer: ElasticTrainer) -> None:
        self.trainers[app_id] = trainer

    def _allocate(self, app_id: str, n_containers: int) -> List[jax.Device]:
        need = n_containers * self.dpc
        if self.oversubscribe:
            chosen = [self.pool[i % len(self.pool)] for i in range(need)]
            uniq = list(dict.fromkeys(chosen))
            self.assignments[app_id] = uniq
            return uniq
        used = {d for ds in self.assignments.values() for d in ds}
        free = [d for d in self.pool if d not in used]
        if len(free) < need:
            raise RuntimeError(
                f"{app_id}: need {need} devices, only {len(free)} free")
        chosen = free[:need]
        self.assignments[app_id] = chosen
        return chosen

    # ---- AdjustmentProtocol interface

    def save_state(self, app: ApplicationSpec) -> CheckpointHandle:
        ckpt = self.trainers[app.app_id].save_state()
        self.pending_ckpt[app.app_id] = ckpt
        return ckpt

    def kill(self, app: ApplicationSpec) -> None:
        self.trainers[app.app_id].kill()
        self.assignments.pop(app.app_id, None)

    def resume(self, app: ApplicationSpec, n_containers: int,
               ckpt: Optional[CheckpointHandle]) -> None:
        devs = self._allocate(app.app_id, n_containers)
        self.trainers[app.app_id].resume(
            devs, ckpt or self.pending_ckpt.get(app.app_id))

    def start(self, app: ApplicationSpec, n_containers: int) -> None:
        devs = self._allocate(app.app_id, n_containers)
        self.trainers[app.app_id].start(devs)


class RuntimeTrainingBridge:
    """Drives REAL ElasticTrainers from the shared `core.runtime` event loop.

    Attach to a `ClusterRuntime`'s bus: after every applied reallocation
    (`Reallocated` event) the bridge runs `steps_per_event` optimizer steps
    on every live trainer. A DormMaster whose protocol is an
    `ElasticJaxProtocol`, driven by that runtime, then exercises the full
    loop end-to-end: arrivals/completions/injected `Resize` events ->
    optimizer -> save/kill/resume with resharding -> continued training --
    i.e. runtime-driven resizes of real JAX jobs."""

    def __init__(self, protocol: ElasticJaxProtocol,
                 steps_per_event: int = 1):
        self.protocol = protocol
        self.steps_per_event = steps_per_event
        self.n_events = 0

    def attach(self, bus) -> None:
        from ..core.runtime import Reallocated
        bus.subscribe(Reallocated, self._on_reallocated)

    def _on_reallocated(self, ev) -> None:
        self.n_events += 1
        for tr in self.protocol.trainers.values():
            if tr.state is not None:
                tr.train_steps(self.steps_per_event)
