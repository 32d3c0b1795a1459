"""Device-mesh construction + Megatron-style ``mpu`` grid facade.

This replaces the reference's ``PipelineParallelGrid`` (``topology.py:252-455``),
which eagerly constructed NCCL process groups for every dp/pp/mp slice.  Here
the single artifact is a ``jax.sharding.Mesh`` with named axes; collectives
reference axes by name and XLA routes them over ICI/DCN.

Canonical axis names (outermost → innermost): ``pipe``, ``data``, ``seq``,
``model``.  ``data`` is the ZeRO axis; ``model`` is tensor parallelism;
``seq`` is sequence/context parallelism (ring attention) — absent in the
2020 reference (SURVEY §2.5) but first-class here; ``pipe`` is pipeline
stages.  Any axis of size 1 can be omitted from the mesh.
"""

from typing import Optional

import numpy as np

from .topology import ProcessTopology

PIPE_AXIS = "pipe"
DATA_AXIS = "data"


def data_parallel_process_info(mesh):
    """(world, rank) for per-process batch slicing: how many process groups
    the ``data`` mesh axis spans, and which group this process is in.

    If the data axis does not cross process boundaries (e.g. multi-host
    model/pipe parallelism with a local data axis), every process must feed
    the SAME global batch — world is 1.  Otherwise processes own contiguous
    equal blocks of data coordinates (the standard mesh layout).
    """
    import jax

    axes = list(mesh.axis_names)
    if DATA_AXIS not in axes:
        return 1, 0
    di = axes.index(DATA_AXIS)
    devs = mesh.devices
    ncoord = devs.shape[di]
    if ncoord <= 1:
        return 1, 0
    me = jax.process_index()
    mine = sorted({i for i in range(ncoord)
                   if any(d.process_index == me
                          for d in np.take(devs, i, axis=di).flat)})
    if not mine or len(mine) == ncoord:
        # this process sees every data coordinate (or none — not a
        # participant): feed the full batch
        return 1, 0
    assert ncoord % len(mine) == 0 and mine == list(
        range(mine[0], mine[0] + len(mine))), (
        f"data axis coords owned by process {me} are not a contiguous "
        f"equal block: {mine} of {ncoord}")
    return ncoord // len(mine), mine[0] // len(mine)
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
EXPERT_AXIS = "expert"

CANONICAL_AXES = (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS, EXPERT_AXIS)

# Process-wide current mesh, set by the engine at init so mesh-aware ops
# (ring attention's shard_map) can find it at trace time without plumbing a
# mesh argument through every model layer.  Static trace-time state, not
# runtime state.
_CURRENT_MESH = None


def set_current_mesh(mesh):
    global _CURRENT_MESH
    _CURRENT_MESH = mesh
    return mesh


def get_current_mesh():
    return _CURRENT_MESH


def mesh_axis_sizes(mesh, keep_trivial=False):
    """{axis_name: size} for a Mesh — the communication context the comm
    ledger stamps into every ``comm`` program event (a reader can tell a
    dp=8 receipt from a dp=2 one without the engine config).  Size-1
    axes are dropped unless ``keep_trivial``: they carry no
    collectives."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if keep_trivial:
        return sizes
    return {ax: n for ax, n in sizes.items() if n > 1}


def current_platform():
    """Platform the traced program is built for: the engine-registered
    mesh's devices when there is one (so a mesh of described TPU devices
    compiles the TPU path from a CPU-only host, and a CPU test mesh takes
    the CPU path on a TPU host), else the default backend."""
    import jax

    if _CURRENT_MESH is not None:
        return _CURRENT_MESH.devices.flat[0].platform
    return jax.default_backend()


def available_devices(n_devices: Optional[int] = None, platform: Optional[str] = None):
    """The first ``n_devices`` devices of ``platform`` (default: the
    default backend).  Too few is an error — never a quiet switch to
    another platform's devices: a four-chip mesh asked for on a one-chip
    host must not "pass" on virtual CPU devices.  Multi-chip runs on a CPU
    box set ``JAX_PLATFORMS=cpu`` and
    ``--xla_force_host_platform_device_count`` so that the default
    backend itself has the devices."""
    import jax

    devs = jax.devices(platform)
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"need {n_devices} {devs[0].platform} devices, only "
                f"{len(devs)} available")
        devs = devs[:n_devices]
    return devs


def make_mesh(axis_dims: dict, devices=None, allow_split_physical_axes: bool = True):
    """Build a ``jax.sharding.Mesh`` with the canonical axis ordering.

    ``axis_dims`` maps axis name → size; axes default to 1 and size-1 axes are
    kept (harmless, simplifies PartitionSpecs).  A ``-1`` size is inferred
    from the device count.
    """
    import jax
    from jax.sharding import Mesh

    dims = {ax: int(axis_dims.get(ax, 1)) for ax in CANONICAL_AXES}
    for ax in axis_dims:
        if ax not in CANONICAL_AXES:
            raise ValueError(f"unknown mesh axis {ax!r}; canonical axes are {CANONICAL_AXES}")

    known = 1
    infer_ax = None
    for ax, d in dims.items():
        if d == -1:
            assert infer_ax is None, "only one axis size may be -1"
            infer_ax = ax
        else:
            known *= d

    if devices is None:
        total = known if infer_ax is None else None
        devices = available_devices(total)
    n = len(devices)
    if infer_ax is not None:
        assert n % known == 0, f"{n} devices not divisible by {known}"
        dims[infer_ax] = n // known
    else:
        assert known == n, f"mesh dims {dims} need {known} devices, got {n}"

    shape = tuple(dims[ax] for ax in CANONICAL_AXES)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, CANONICAL_AXES)


class MeshGrid:
    """Megatron-``mpu``-compatible facade over a Mesh + ProcessTopology.

    The reference engine consumes a user ``mpu`` object through the interface
    ``get_{model,data}_parallel_{rank,group,world_size}()``
    (``deepspeed/__init__.py:79-80``, ``engine.py:527-538``).  We provide the
    same surface so user code ports over; "group" accessors return the mesh
    axis *name*, which is what our collectives take in place of a process
    group handle.
    """

    def __init__(self, mesh, topology: Optional[ProcessTopology] = None, process_rank: int = 0):
        self.mesh = mesh
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.data_parallel_size = shape.get(DATA_AXIS, 1)
        self.model_parallel_size = shape.get(MODEL_AXIS, 1)
        self.seq_parallel_size = shape.get(SEQ_AXIS, 1)
        self.pipe_parallel_size = shape.get(PIPE_AXIS, 1)
        if topology is None:
            topology = ProcessTopology(axes=list(mesh.axis_names), dims=list(mesh.devices.shape))
        self._topo = topology
        self.global_rank = process_rank
        self.world_size = topology.world_size()

    @property
    def topology(self):
        return self._topo

    def _coord(self):
        return self._topo.get_coord(self.global_rank)

    # ---- Megatron mpu interface (reference topology.py:405-455) ----
    def get_global_rank(self):
        return self.global_rank

    def get_model_parallel_rank(self):
        return getattr(self._coord(), MODEL_AXIS, 0) if MODEL_AXIS in self._topo.axes else 0

    def get_model_parallel_world_size(self):
        return self.model_parallel_size

    def get_model_parallel_group(self):
        return MODEL_AXIS

    def get_data_parallel_rank(self):
        return getattr(self._coord(), DATA_AXIS, 0) if DATA_AXIS in self._topo.axes else 0

    def get_data_parallel_world_size(self):
        return self.data_parallel_size

    def get_data_parallel_group(self):
        return DATA_AXIS

    # ---- pipeline extras (reference PipelineParallelGrid) ----
    def get_pipe_parallel_rank(self):
        return getattr(self._coord(), PIPE_AXIS, 0) if PIPE_AXIS in self._topo.axes else 0

    def get_pipe_parallel_world_size(self):
        return self.pipe_parallel_size

    def get_pipe_parallel_group(self):
        return PIPE_AXIS

    def get_stage_id(self):
        return self.get_pipe_parallel_rank()

    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self.pipe_parallel_size - 1
