"""Mesh construction and axis conventions.

Axis names (fixed across the framework so PartitionSpecs compose):

- ``dp``: data parallel — batch axis; gradients all-reduced over it.
- ``pp``: pipeline/stage axis — the stacked-layer axis of scanned
  decoder params is sharded over it (XLA turns the layer scan over a
  sharded leading axis into per-stage execution with collective
  permutes of the activations between stages).
- ``tp``: tensor parallel — attention heads and MLP hidden dim.
- ``sp``: sequence/context parallel — long-context prefill shards the
  sequence axis and runs ring attention over ``sp`` (ppermute over ICI).
- ``ep``: expert parallel — reserved for MoE model families; meshes are
  always built with the axis present (size 1 unless requested) so
  PartitionSpecs mentioning it are valid everywhere.

On real hardware ``jax.devices()`` for a TPU slice enumerates chips so
that adjacent devices are ICI neighbours; we put ``sp``/``tp`` innermost
so their collectives ride ICI, and ``dp`` outermost so it can span DCN
(multi-host data parallelism), mirroring how the reference fleet scales
pods over the datacenter network while NCCL stays intra-pod
(reference: vllm-setup-helm topology; scaling-book mesh recipe).
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DP = "dp"
AXIS_PP = "pp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_EP = "ep"

# Outermost-to-innermost device ordering; see module docstring.
AXIS_ORDER: Tuple[str, ...] = (AXIS_DP, AXIS_PP, AXIS_EP, AXIS_SP, AXIS_TP)


@dataclass
class MeshPlan:
    """Requested parallelism degrees; -1 on ``dp`` means "absorb the
    remaining devices" (the common fleet configuration)."""

    dp: int = -1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {
            AXIS_DP: self.dp,
            AXIS_PP: self.pp,
            AXIS_TP: self.tp,
            AXIS_SP: self.sp,
            AXIS_EP: self.ep,
        }
        fixed = 1
        free_axes = [a for a, s in sizes.items() if s == -1]
        for a, s in sizes.items():
            if s != -1:
                if s <= 0:
                    raise ValueError(f"axis {a} has invalid size {s}")
                fixed *= s
        if len(free_axes) > 1:
            raise ValueError("at most one axis may be -1")
        if free_axes:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed product {fixed}"
                )
            sizes[free_axes[0]] = n_devices // fixed
        else:
            if fixed != n_devices:
                raise ValueError(
                    f"mesh plan wants {fixed} devices, have {n_devices}"
                )
        return sizes


@contextmanager
def activate(mesh: Mesh) -> Iterator[Mesh]:
    """Enter a mesh context so bare PartitionSpecs resolve (e.g. in
    ``lax.with_sharding_constraint``).

    ``jax.set_mesh`` also sets the abstract mesh, which ``with mesh:``
    does not.  All framework entry points route through here so the
    choice lives in one place.
    """
    with jax.set_mesh(mesh):
        yield mesh


def mesh_is_active() -> bool:
    """Whether a PartitionSpec can currently resolve to mesh axes:
    either a ``jax.set_mesh`` scope (abstract mesh) or a legacy
    ``with mesh:`` context (thread-resources env).

    Model code uses this to make sharding constraints a deterministic
    no-op outside any mesh (single-device serving paths) instead of
    try/except-ing ``with_sharding_constraint``, which would silently
    bake a constraint-free trace into the jit cache under a mesh.
    """
    if not jax.sharding.get_abstract_mesh().empty:
        return True
    # ``with mesh:`` still routes through the thread-resources env
    # (get_abstract_mesh() only sees jax.set_mesh), and callers enter
    # it.  The attribute works but warns; keep the probe quiet.
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from jax.interpreters import pxla

        return not pxla.thread_resources.env.physical_mesh.empty


def make_mesh(
    plan: Optional[MeshPlan] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh with the framework's canonical axis order."""
    devices = list(devices if devices is not None else jax.devices())
    plan = plan or MeshPlan()
    sizes = plan.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def make_hybrid_mesh(
    ici_plan: Optional[MeshPlan] = None,
    dcn_plan: Optional[MeshPlan] = None,
) -> Mesh:
    """Multi-host mesh: per-axis ICI (intra-slice) x DCN (cross-host)
    degrees, same canonical axis names.

    The fleet-scaling recipe: call ``jax.distributed.initialize()`` on
    every host of a multi-slice/multi-host deployment, then build the
    mesh here.  ``mesh_utils.create_hybrid_device_mesh`` orders devices
    so each axis's DCN factor crosses slice boundaries while its ICI
    factor stays inside a slice — collectives for ``tp``/``sp`` ride
    ICI, while ``dp`` (gradient all-reduce, the bandwidth-tolerant one)
    crosses DCN, mirroring how the reference's fleet keeps NCCL
    intra-pod and scales pods over the datacenter network.

    ``dcn_plan`` defaults to data-parallel over the process count
    (dp=n_processes) — the standard multi-host serving/training fleet.
    """
    from jax.experimental import mesh_utils

    devices = jax.devices()
    n_processes = max(d.process_index for d in devices) + 1
    per_slice = len(devices) // n_processes
    ici_plan = ici_plan or MeshPlan(dp=1, tp=per_slice)
    dcn_plan = dcn_plan or MeshPlan(dp=n_processes)
    ici_sizes = ici_plan.resolve(per_slice)
    dcn_sizes = dcn_plan.resolve(n_processes)
    if n_processes == 1:
        # Single host: hybrid degenerates to the flat ICI mesh.
        merged = MeshPlan(
            **{
                a: ici_sizes[a] * dcn_sizes[a]
                for a in (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP, AXIS_EP)
            }
        )
        return make_mesh(merged, devices)
    # Granule = process: dcn degrees count hosts, matching this
    # function's contract on every backend (jax's default granule is
    # the TPU slice, which breaks single-slice multi-host deployments
    # and CPU clusters whose devices have no slice_index).
    dev_array = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=tuple(ici_sizes[a] for a in AXIS_ORDER),
        dcn_mesh_shape=tuple(dcn_sizes[a] for a in AXIS_ORDER),
        devices=devices,
        process_is_granule=True,
    )
    return Mesh(dev_array, AXIS_ORDER)
