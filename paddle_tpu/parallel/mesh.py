"""Device-mesh management — the TPU-native replacement for the reference's
NCCL context plumbing (``platform/nccl_helper.h:75-300``,
``platform/collective_helper.h:50``).

Where the reference builds NCCL rings per place (flat + hierarchical
inter/intra-node), here a single ``jax.sharding.Mesh`` carries every
parallelism axis and XLA lays collectives onto ICI/DCN:

- ``dp``  — data parallel (≈ AllReduceSSAGraphBuilder / c_allreduce ring)
- ``mp``  — tensor/model parallel (capability the reference lacks; SURVEY §2.5)
- ``sp``  — sequence/context parallel (ring attention axis)
- ``pp``  — pipeline stages (≈ PipelineTrainer sections)
- ``ep``  — expert parallel (MoE)
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..device import is_tpu

AXES = ("dp", "mp", "sp", "pp", "ep")

_current_mesh: Optional[Mesh] = None


def make_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Build a Mesh from {axis_name: size}; sizes must multiply to #devices.

    Axis order follows AXES so dp is outermost (DCN-friendly) and mp/sp
    innermost (ICI-friendly) — the hierarchical-allreduce layout the
    reference approximates with inter/intra-node NCCL rings
    (nccl_helper.h:246 InitHierarchicalCtxs).
    """
    devices = list(devices if devices is not None else jax.devices())
    names = [a for a in AXES if a in axes] + \
        [a for a in axes if a not in AXES]
    sizes = [axes[a] for a in names]
    if int(np.prod(sizes)) != len(devices):
        raise ValueError(f"mesh {axes} needs {int(np.prod(sizes))} devices, "
                         f"have {len(devices)}")
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, axis_names=tuple(names))


def data_parallel_mesh(n: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    if n is not None:
        devs = devs[:n]
    return make_mesh({"dp": len(devs)}, devs)


def set_mesh(mesh: Optional[Mesh]):
    global _current_mesh
    _current_mesh = mesh


def current_mesh() -> Optional[Mesh]:
    return _current_mesh


def sharding_for(mesh: Mesh, spec) -> NamedSharding:
    """spec: None (replicated) or a tuple of axis-names/None per dim, with
    axes absent from the mesh silently dropped (so a tp-annotated model runs
    unchanged on a dp-only mesh)."""
    if spec is None:
        return NamedSharding(mesh, P())
    clean = tuple(
        (a if (a is not None and _axis_in(mesh, a)) else None)
        for a in spec)
    return NamedSharding(mesh, P(*clean))


def _axis_in(mesh: Mesh, axis) -> bool:
    if isinstance(axis, (tuple, list)):
        return all(a in mesh.axis_names for a in axis)
    return axis in mesh.axis_names


def make_topology_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Hardware-topology-aware Mesh from {axis_name: size} — the GSPMD
    partitioner's mesh constructor (SNIPPETS.md [2]:
    ``mesh_utils.create_device_mesh`` / ``create_hybrid_device_mesh``).

    Unlike :func:`make_mesh`'s row-major reshape, ``mesh_utils`` orders
    devices so the innermost (mp/sp) axes land on physically adjacent
    chips — ICI rings for the model-parallel collectives, DCN only
    across the outermost (dp) axis.  Multi-host meshes go through the
    hybrid constructor (one slow axis per granule, fast axes inside).
    Virtual CPU devices have no topology, so the CPU test mesh is
    :func:`make_mesh`'s row-major reshape, chosen by platform; on TPU a
    shape mesh_utils cannot map raises instead of quietly losing the
    physical adjacency."""
    devices = list(devices if devices is not None else jax.devices())
    names = [a for a in AXES if a in axes] + \
        [a for a in axes if a not in AXES]
    sizes = [int(axes[a]) for a in names]
    if int(np.prod(sizes)) != len(devices):
        raise ValueError(
            f"mesh {axes} needs {int(np.prod(sizes))} devices, "
            f"have {len(devices)}")
    if not is_tpu(devices[0]):
        return make_mesh(axes, devices)
    from jax.experimental import mesh_utils
    n_hosts = len({getattr(d, "process_index", 0) for d in devices})
    if n_hosts > 1 and len(sizes) > 1:
        per_host = len(devices) // n_hosts
        # split each axis between the DCN (host) and ICI (chip)
        # levels, outermost axes absorbing the host factor first
        dcn, ici, hosts_left = [], [], n_hosts
        for s in sizes:
            g = np.gcd(s, hosts_left)
            dcn.append(int(g))
            ici.append(s // int(g))
            hosts_left //= int(g)
        if hosts_left == 1 and int(np.prod(ici)) == per_host:
            arr = mesh_utils.create_hybrid_device_mesh(
                ici, dcn, devices=devices)
            return Mesh(arr, axis_names=tuple(names))
    arr = mesh_utils.create_device_mesh(sizes, devices=devices)
    return Mesh(arr, axis_names=tuple(names))


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """{axis_name: size} of a Mesh — the partitioner's planner input."""
    return {str(a): int(s)
            for a, s in zip(mesh.axis_names, mesh.devices.shape)}


def make_hierarchical_mesh(inter: int, intra: int, devices=None) -> Mesh:
    """2-level data-parallel mesh (ref SURVEY §2.5 hierarchical allreduce:
    ``NCCLCommunicator::InitHierarchicalCtxs`` inter/intra-node rings).

    On TPU the two levels are DCN (between slices/hosts) and ICI (inside a
    slice): build a ``("dcn", "ici")`` mesh and shard the batch over BOTH
    axes; XLA lowers the gradient psum into an ICI-local reduce followed by
    a DCN exchange — the exact hierarchical-allreduce structure the
    reference hand-builds, chosen automatically from the mesh topology.
    ``hierarchical_allreduce`` exposes the explicit two-stage form for
    shard_map code."""
    return make_mesh({"dcn": inter, "ici": intra}, devices)


def hierarchical_allreduce(x, inter_axis: str = "dcn",
                           intra_axis: str = "ici"):
    """Explicit two-stage allreduce over a hierarchical mesh (inside
    shard_map): reduce over the fast intra axis first, then the slow inter
    axis — same result as one psum over both, with the collective order
    pinned (ref nccl_helper.h:246 hierarchical inter/exter comms)."""
    from jax import lax
    return lax.psum(lax.psum(x, intra_axis), inter_axis)
