"""Device mesh construction — the distributed communication backend.

The reference has *no* communication layer (SURVEY.md section 5.8: no
NCCL/MPI/Gloo anywhere; vLLM's internals are invisible to it).  On TPU the
comm backend is declarative: a ``jax.sharding.Mesh`` over the slice, sharded
``jit`` programs, and XLA-emitted collectives (psum/all-gather/all-to-all)
riding ICI within a slice and DCN across slices.  This module is that
backend's front door:

* ``initialize_distributed`` wires ``jax.distributed`` for multi-host pods
  (call once inside server startup, mirroring the reference's lifespan-init
  lesson, main.py:48-66);
* ``build_mesh`` turns the ``tpu`` config section into a named mesh with the
  canonical serving axes: ``("dp", "ep", "sp", "tp")`` — data, expert,
  sequence and tensor parallelism, ordered so that tp (the
  highest-bandwidth-demand axis) lands on the innermost, fastest ICI ring.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from vgate_tpu.logging_config import get_logger

logger = get_logger(__name__)

AXIS_DP = "dp"
AXIS_PP = "pp"
AXIS_EP = "ep"
AXIS_SP = "sp"
AXIS_TP = "tp"
# pp outermost after dp (stage boundary crossings are the rarest, smallest
# transfers: one [mb, D] activation per microbatch step); tp innermost on
# the fastest ICI loops
MESH_AXES = (AXIS_DP, AXIS_PP, AXIS_EP, AXIS_SP, AXIS_TP)

_distributed_initialized = False


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join the multi-host process group when running on a pod slice.

    Single-host runs (and CPU test meshes) skip this; on a real multi-host
    slice the TPU runtime env vars make the no-arg form work.  Safe to call
    more than once.
    """
    global _distributed_initialized
    if _distributed_initialized:
        return
    multi_host = (
        coordinator_address is not None
        or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
    )
    if multi_host:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        logger.info(
            "jax.distributed initialized",
            extra={
                "extra_data": {
                    "process_index": jax.process_index(),
                    "process_count": jax.process_count(),
                }
            },
        )
    _distributed_initialized = True


@dataclass(frozen=True)
class MeshPlan:
    """Resolved mesh geometry."""

    dp: int
    pp: int
    ep: int
    sp: int
    tp: int

    @property
    def shape(self) -> Tuple[int, int, int, int, int]:
        return (self.dp, self.pp, self.ep, self.sp, self.tp)

    @property
    def num_devices(self) -> int:
        return self.dp * self.pp * self.ep * self.sp * self.tp


def resolve_plan(tpu_config, num_devices: Optional[int] = None) -> MeshPlan:
    """Resolve config axis sizes (0 = absorb remaining devices) against the
    visible device count."""
    n = num_devices if num_devices is not None else jax.device_count()
    dp, pp, ep, sp, tp = (
        tpu_config.dp,
        getattr(tpu_config, "pp", 1),
        tpu_config.ep,
        tpu_config.sp,
        tpu_config.tp,
    )
    fixed = [x for x in (dp, pp, ep, sp, tp) if x > 0]
    free = [x for x in (dp, pp, ep, sp, tp) if x == 0]
    used = int(np.prod(fixed)) if fixed else 1
    if len(free) > 1:
        raise ValueError("at most one mesh axis may be 0 (auto)")
    if free:
        if n % used:
            raise ValueError(
                f"devices ({n}) not divisible by fixed axes product ({used})"
            )
        auto = n // used
        dp, pp, ep, sp, tp = [
            x if x > 0 else auto for x in (dp, pp, ep, sp, tp)
        ]
    plan = MeshPlan(dp=dp, pp=pp, ep=ep, sp=sp, tp=tp)
    if plan.num_devices != n:
        raise ValueError(
            f"mesh {plan.shape} covers {plan.num_devices} devices but "
            f"{n} are visible"
        )
    return plan


def build_mesh(tpu_config=None, devices=None) -> Mesh:
    """Create the named device mesh for the engine.

    ``jax.experimental.mesh_utils`` picks a device order that keeps the
    innermost axes on physically adjacent chips, so tp collectives ride the
    fastest ICI loops.
    """
    if tpu_config is None:
        from vgate_tpu.config import get_config

        tpu_config = get_config().tpu
    devices = devices if devices is not None else jax.devices()
    limit = getattr(tpu_config, "num_devices", 0)
    if limit and limit < len(devices):
        devices = devices[:limit]
    plan = resolve_plan(tpu_config, len(devices))
    try:
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(
            plan.shape, devices=devices
        )
        order = "create_device_mesh"
    except (ValueError, AssertionError) as exc:
        # enumeration order, NOT topology-aware: tp may not sit on
        # adjacent chips — said out loud below
        device_array = np.asarray(devices).reshape(plan.shape)
        order = f"plain reshape (create_device_mesh refused: {exc})"
    mesh = Mesh(device_array, MESH_AXES)
    logger.info(
        "mesh built",
        extra={
            "extra_data": {
                "shape": dict(zip(MESH_AXES, plan.shape)),
                "device_order": order,
            }
        },
    )
    return mesh


def single_device_mesh(device=None) -> Mesh:
    """A trivial all-ones mesh so single-chip and multi-chip share one code
    path."""
    device = device if device is not None else jax.devices()[0]
    return Mesh(
        np.asarray([device]).reshape((1,) * len(MESH_AXES)), MESH_AXES
    )
