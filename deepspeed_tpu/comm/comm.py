"""Communication façade over XLA collectives.

TPU-native re-design of ``deepspeed/comm/comm.py`` (reference comm.py:145-427:
the torch.distributed-mirror API ``init_distributed`` / ``all_reduce`` /
``all_gather_base`` / ``reduce_scatter_base`` / ``all_to_all_single`` /
``broadcast`` / ``barrier`` / ``new_group``). Differences forced — and
exploited — by the TPU model:

  * There is no NCCL rendezvous; multi-host identity comes from
    ``jax.distributed.initialize`` and collectives ride ICI/DCN as XLA ops.
  * Hot-loop collectives (grad reduce-scatter, ZeRO all-gather) do NOT go
    through this module: they are emitted by the compiler from sharding
    annotations inside the jitted train step. This façade provides the
    *eager* surface the rest of the framework needs (checkpoint-time gathers,
    loss aggregation, tests, 1-bit compression experiments) plus the group
    bookkeeping API that ZeRO / pipeline / MoE code addresses.

Eager collectives use the *stacked global view*: a "distributed tensor held
per-rank" is represented as ONE global jax.Array whose leading axis indexes
the group ranks and is sharded over the group's mesh axis. ``all_reduce`` on
a ``[G, ...]`` array returns the ``[...]`` elementwise sum; ``all_gather``
returns the replicated stack; ``reduce_scatter`` on ``[G, N]`` returns
``[G, N/G]`` owner slices, etc. On a single process this emulates G ranks on
G devices, which is exactly how the test suite runs (8 virtual CPU devices).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..parallel import mesh as mesh_lib
from ..utils.logging import logger

_INITIALIZED = False

ReduceOp = type("ReduceOp", (), {"SUM": "sum", "AVG": "avg", "MAX": "max",
                                 "MIN": "min", "PROD": "prod"})


@dataclasses.dataclass(frozen=True)
class CommGroup:
    """A collective group = one (or a tuple of) mesh axis(es)."""
    axes: tuple
    mesh: Mesh

    @property
    def size(self) -> int:
        n = 1
        for a in self.axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]


def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     init_method: Optional[str] = None,
                     rank: int = -1,
                     world_size: int = -1,
                     mesh_shape: Optional[mesh_lib.MeshShape] = None) -> None:
    """Initialize multi-host JAX (if launched distributed) and the global mesh.

    Reference analogue: ``init_distributed`` (comm/comm.py:376-540) including
    its launcher-env discovery; here the env contract is the one our launcher
    (launcher/launch.py) writes: COORDINATOR_ADDRESS, PROCESS_ID, NUM_PROCESSES.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    coord = os.environ.get("COORDINATOR_ADDRESS")
    nproc = int(os.environ.get("NUM_PROCESSES", "1"))
    pid = int(os.environ.get("PROCESS_ID", "0"))
    if auto_mpi_discovery and not coord and "OMPI_COMM_WORLD_SIZE" in os.environ:
        # launched under mpirun (OpenMPIRunner): take identity from the OMPI
        # env (reference mpi_discovery, comm/comm.py:399-427); rank 0's host
        # coordinates
        nproc = int(os.environ["OMPI_COMM_WORLD_SIZE"])
        pid = int(os.environ["OMPI_COMM_WORLD_RANK"])
        coord = os.environ.get("MASTER_ADDR", "127.0.0.1") + ":" + \
            os.environ.get("MASTER_PORT", "29500")
        os.environ.setdefault(
            "LOCAL_RANK", os.environ.get("OMPI_COMM_WORLD_LOCAL_RANK", "0"))
    elif auto_mpi_discovery and not coord \
            and "MV2_COMM_WORLD_SIZE" in os.environ:
        # launched under mpirun_rsh (MVAPICHRunner): MVAPICH2 spells the
        # same identity MV2_* (reference mpi_discovery covers both)
        nproc = int(os.environ["MV2_COMM_WORLD_SIZE"])
        pid = int(os.environ["MV2_COMM_WORLD_RANK"])
        coord = os.environ.get("MASTER_ADDR", "127.0.0.1") + ":" + \
            os.environ.get("MASTER_PORT", "29500")
        os.environ.setdefault(
            "LOCAL_RANK", os.environ.get("MV2_COMM_WORLD_LOCAL_RANK", "0"))
    if coord and nproc > 1:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=nproc,
            process_id=pid,
        )
        logger.info(f"jax.distributed initialized: process {jax.process_index()}"
                    f"/{jax.process_count()}")
    if mesh_shape is None:
        mesh_shape = mesh_lib.MeshShape.infer(len(jax.devices()))
    mesh_lib.set_global_mesh(mesh_lib.build_mesh(mesh_shape), mesh_shape)
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED


def get_rank() -> int:
    return jax.process_index()


def get_world_size(group: Optional[CommGroup] = None) -> int:
    """Total ranks. Reference semantics: one rank per accelerator, so the
    no-group form counts *devices* (processes x local devices), matching the
    size of a group spanning the whole mesh."""
    if group is not None:
        return group.size
    return len(jax.devices())


def get_local_rank() -> int:
    return int(os.environ.get("LOCAL_RANK", "0"))


def device_count() -> int:
    return len(jax.devices())


def barrier() -> None:
    """Cross-process sync (no-op in single-process runs)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("deepspeed_tpu_barrier")


def new_group(axes: Sequence[str] | str, mesh: Optional[Mesh] = None) -> CommGroup:
    """Reference `new_group(ranks)` becomes mesh-axis subsetting: a group is
    named by the mesh axes its members span."""
    if isinstance(axes, str):
        axes = (axes,)
    mesh = mesh or mesh_lib.get_global_mesh()
    for a in axes:
        if a not in mesh.shape:
            raise ValueError(f"unknown mesh axis {a!r}; mesh has {dict(mesh.shape)}")
    return CommGroup(axes=tuple(axes), mesh=mesh)


def get_data_parallel_group() -> CommGroup:
    return new_group("dp")


def get_model_parallel_group() -> CommGroup:
    return new_group("tp")


def get_expert_parallel_group() -> CommGroup:
    return new_group("ep")


# ---------------------------------------------------------------------------
# Eager collectives over the stacked global view.
# ---------------------------------------------------------------------------

def _default_group(group: Optional[CommGroup]) -> CommGroup:
    return group if group is not None else new_group("dp")


def _stacked(x, group: CommGroup):
    """Commit x as a global array with axis 0 sharded over the group axis."""
    x = jnp.asarray(x)
    if x.shape[0] != group.size:
        raise ValueError(
            f"stacked collective input must have leading dim == group size "
            f"({group.size}), got shape {x.shape}")
    spec = P(group.axis_name, *([None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(group.mesh, spec))


def _reduce_local(x, op: str, axis_name):
    if op in ("sum", "avg"):
        r = jax.lax.psum(x, axis_name)
        if op == "avg":
            r = r / jax.lax.psum(jnp.ones((), x.dtype), axis_name)
        return r
    if op == "max":
        return jax.lax.pmax(x, axis_name)
    if op == "min":
        return jax.lax.pmin(x, axis_name)
    raise ValueError(f"unsupported reduce op {op}")


def all_reduce(x, op: str = "sum", group: Optional[CommGroup] = None):
    """x: [G, ...] stacked per-rank tensors -> [...] reduced, replicated."""
    group = _default_group(group)
    x = _stacked(x, group)
    ax = group.axis_name
    spec_in = P(ax, *([None] * (x.ndim - 1)))

    def f(local):
        return _reduce_local(jnp.sum(local, axis=0) if op in ("sum", "avg")
                             else local.max(axis=0) if op == "max"
                             else local.min(axis=0), op, ax)

    out = shard_map(f, mesh=group.mesh, in_specs=(spec_in,),
                    out_specs=P(*([None] * (x.ndim - 1))))(x)
    return out


def all_gather(x, group: Optional[CommGroup] = None):
    """x: [G, ...] sharded stack -> [G, ...] replicated (the gather)."""
    group = _default_group(group)
    x = _stacked(x, group)
    return jax.device_put(x, NamedSharding(group.mesh, P(*([None] * x.ndim))))


def all_gather_base(x, group: Optional[CommGroup] = None):
    """Flat all-gather: [G, n] per-rank chunks -> [G*n] replicated."""
    group = _default_group(group)
    g = all_gather(x, group)
    return g.reshape((-1,) + tuple(g.shape[2:]))


def reduce_scatter_base(x, op: str = "sum", group: Optional[CommGroup] = None):
    """x: [G, N] stacked per-rank tensors (N divisible by G) ->
    [G, N/G] where out[r] = reduce_r'(x[r', r-th chunk]). psum_scatter."""
    if op not in ("sum", "avg"):
        raise ValueError(f"reduce_scatter supports sum/avg, got {op!r}")
    group = _default_group(group)
    x = _stacked(x, group)
    ax = group.axis_name
    if x.shape[1] % group.size:
        raise ValueError(f"reduce_scatter needs N % G == 0, got {x.shape}")

    def f(local):  # local: [1, N]
        chunk = jax.lax.psum_scatter(local[0], ax, scatter_dimension=0,
                                     tiled=True)
        if op == "avg":
            chunk = chunk / group.size
        return chunk[None]

    return shard_map(f, mesh=group.mesh, in_specs=(P(ax, None),),
                     out_specs=P(ax, None))(x)


def all_to_all_single(x, group: Optional[CommGroup] = None):
    """x: [G, G, ...]; out[r] = stack of x[r'][r] for all r' — i.e. a
    transpose of the first two axes across ranks."""
    group = _default_group(group)
    x = _stacked(x, group)
    ax = group.axis_name

    def f(local):  # [1, G, ...]
        return jax.lax.all_to_all(local, ax, split_axis=1, concat_axis=0,
                                  tiled=False).reshape(local.shape)

    return shard_map(f, mesh=group.mesh,
                     in_specs=(P(ax, *([None] * (x.ndim - 1))),),
                     out_specs=P(ax, *([None] * (x.ndim - 1))))(x)


def broadcast(x, src: int = 0, group: Optional[CommGroup] = None):
    """x: [G, ...] stacked; returns x[src] replicated to every rank."""
    group = _default_group(group)
    if not 0 <= src < group.size:
        raise ValueError(f"src {src} out of range for group of size {group.size}")
    x = _stacked(x, group)
    out = jax.device_put(x[src], NamedSharding(group.mesh, P(*([None] * (x.ndim - 1)))))
    return out


def ppermute(x, perm, group: Optional[CommGroup] = None):
    """Stacked p2p: out[dst] = x[src] for each (src, dst) in perm; ranks not
    a destination get zeros. This is the pipeline send/recv primitive
    (reference p2p.py:21-86) expressed as one collective permute."""
    group = _default_group(group)
    x = _stacked(x, group)
    ax = group.axis_name

    def f(local):
        return jax.lax.ppermute(local, ax, perm)

    spec = P(ax, *([None] * (x.ndim - 1)))
    return shard_map(f, mesh=group.mesh, in_specs=(spec,), out_specs=spec)(x)


def send(x, dst: int, src: Optional[int] = None,
         group: Optional[CommGroup] = None):
    """Stacked p2p send (reference comm.py send / pipe p2p.py:48): moves
    x[src] to rank dst; other rows are zeros in the result. ``src``
    defaults to every rank sending to ``dst``'s left neighbor semantics —
    pass it explicitly for a single directed edge. Composes with ``recv``
    as one ppermute under the hood (on TPU a directed pair IS a permute)."""
    if src is None:
        src = (dst - 1) % _default_group(group).size
    return ppermute(x, [(src, dst)], group=group)


def recv(x, src: int, dst: Optional[int] = None,
         group: Optional[CommGroup] = None):
    """Stacked p2p receive: returns the stack where row dst holds rank
    src's tensor (zeros elsewhere). With ``dst=None`` receives into
    ``src+1`` (pipeline neighbor order)."""
    group_ = _default_group(group)
    if dst is None:
        dst = (src + 1) % group_.size
    return ppermute(x, [(src, dst)], group=group)


# Capability shims kept for API parity with the reference (comm.py:165-216).
allgather_fn = all_gather_base
reduce_scatter_fn = reduce_scatter_base
