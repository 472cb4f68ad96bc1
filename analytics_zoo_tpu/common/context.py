"""Cluster/device context — the ``init_nncontext`` equivalent.

The reference boots a Spark cluster and injects MKL threading env vars
(/root/reference/pyzoo/zoo/common/nncontext.py:180-315); here the "cluster" is a JAX
device mesh. ``init_zoo_context`` discovers devices (optionally initializing
``jax.distributed`` for multi-host pods — the RayOnSpark/barrier bootstrap parity,
/root/reference/pyzoo/zoo/ray/raycontext.py:190-332), builds the global
:class:`jax.sharding.Mesh` over the configured logical axes, and returns a
:class:`ZooContext` that every other subsystem hangs off.

Axis convention (framework-wide):
  ``dp``   data parallel          (gradient psum rides ICI — AllReduceParameter parity,
                                   zoo/.../keras/models/Topology.scala:1129-1131)
  ``fsdp`` param/optstate sharding within a replica (ZeRO-style slice-owner parity)
  ``tp``   tensor parallel        (2D matmul/embedding sharding)
  ``sp``   sequence/context parallel (ring attention)
  ``pp``   pipeline parallel
  ``ep``   expert parallel
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional, Sequence

import numpy as np

from .compile_cache import enable_compile_cache
from .config import MeshConfig, RuntimeConfig, apply_env_overrides
from .locks import traced_lock

logger = logging.getLogger("analytics_zoo_tpu")

# NOT a leaf: the runtime witness shows context init acquiring
# module._POLICY_LOCK (nn precision policy) while holding this — a leaf
# declaration here would fail the chaos-suite witness gate
_CONTEXT_LOCK = traced_lock("context._CONTEXT_LOCK")
_CURRENT: Optional["ZooContext"] = None


class ZooContext:
    """Holds the global mesh + runtime config. One per process."""

    def __init__(self, config: RuntimeConfig):
        import jax

        enable_compile_cache()
        self.config = config
        if config.coordinator_address is not None:
            jax.distributed.initialize(
                coordinator_address=config.coordinator_address,
                num_processes=config.num_processes,
                process_id=config.process_id,
            )
        if config.platform is not None:
            devices = jax.devices(config.platform)
        else:
            devices = jax.devices()
        self.devices = devices
        # engage the precision policy (params fp32, compute bf16 on TPU by config)
        from ..nn.module import set_policy

        set_policy(param_dtype=config.precision.param_dtype,
                   compute_dtype=config.precision.compute_dtype)
        self.mesh = build_mesh(config.mesh, devices)
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    @property
    def local_devices(self):
        import jax

        return jax.local_devices()

    def __enter__(self):
        self._mesh_ctx = self.mesh.__enter__()
        return self

    def __exit__(self, *exc):
        return self.mesh.__exit__(*exc)


def build_mesh(mesh_config: MeshConfig, devices: Optional[Sequence] = None):
    """Build a :class:`jax.sharding.Mesh` with the framework's canonical axis names."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    sizes = mesh_config.sizes(len(devices))
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, axis_names=mesh_config.axis_names)


def init_zoo_context(
    config: Optional[RuntimeConfig] = None,
    *,
    set_current: bool = True,
    **overrides,
) -> ZooContext:
    """Create (and register) the global :class:`ZooContext`.

    Parity: ``init_nncontext`` (/root/reference/pyzoo/zoo/common/nncontext.py:180).
    Keyword overrides are applied on top of ``config`` then ``ZOO_TPU_*`` env vars.
    """
    global _CURRENT
    import dataclasses

    cfg = config or RuntimeConfig()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    cfg = apply_env_overrides(cfg)
    if cfg.num_virtual_devices:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={cfg.num_virtual_devices}")
    ctx = ZooContext(cfg)
    if set_current:
        with _CONTEXT_LOCK:
            _CURRENT = ctx
    logger.info(
        "initialized ZooContext: %d devices, mesh=%s, process %d/%d",
        ctx.num_devices, dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape)),
        ctx.process_index, ctx.process_count)
    return ctx


def get_zoo_context(auto_init: bool = True) -> ZooContext:
    """Return the process-wide context, lazily creating a default one."""
    global _CURRENT
    with _CONTEXT_LOCK:
        if _CURRENT is None:
            if not auto_init:
                raise RuntimeError("no ZooContext; call init_zoo_context() first")
            _CURRENT = ZooContext(apply_env_overrides(RuntimeConfig()))
        return _CURRENT


def reset_zoo_context() -> None:
    """Drop the current context AND restore the default precision policy —
    ZooContext.__init__ engages the config's policy globally (set_policy), so
    leaving it behind would leak e.g. bfloat16 compute into later f32 code."""
    global _CURRENT
    from ..nn.module import set_policy

    with _CONTEXT_LOCK:
        _CURRENT = None
    set_policy(param_dtype="float32", compute_dtype="float32")
