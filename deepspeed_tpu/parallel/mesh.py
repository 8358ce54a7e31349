"""Device mesh construction and axis bookkeeping.

This is the TPU-native replacement for the reference's process-group layer
(``deepspeed/utils/groups.py`` + ``runtime/pipe/topology.py``): instead of
building NCCL process groups per parallel dimension, we build ONE
``jax.sharding.Mesh`` with named axes and express every "group" as a mesh
axis (or tuple of axes). XLA then lowers collectives onto ICI/DCN along
those axes.

Axes (sizes from ``MeshConfig``):
- ``data``    — pure data parallelism (replica groups)
- ``fsdp``    — ZeRO param/optimizer sharding axis (stage>0). When ZeRO is
                on and ``fsdp == 1``, the engine folds ``data`` into the
                sharding axis, matching the reference's "ZeRO over the DP
                group" semantics.
- ``tensor``  — tensor (megatron-style) model parallelism
- ``pipe``    — pipeline stages
- ``expert``  — MoE expert parallelism (reference ``groups.py:114``)
- ``seq``     — Ulysses sequence parallelism (reference ``groups.py:464``)
- ``context`` — ring-attention context parallelism (superset of reference)
"""

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..runtime.config import MeshConfig
from ..utils.logging import logger
from .topology import ProcessTopology

ALL_AXES = ("pipe", "data", "fsdp", "expert", "seq", "context", "tensor")


def _resolve_axis_sizes(cfg: MeshConfig, n_devices: int) -> Dict[str, int]:
    sizes = {a: getattr(cfg, a) for a in ALL_AXES}
    wildcard = [a for a, s in sizes.items() if s == -1]
    if len(wildcard) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {wildcard}")
    fixed = 1
    for a, s in sizes.items():
        if s != -1:
            if s < 1:
                raise ValueError(f"Mesh axis {a} must be >=1 or -1, got {s}")
            fixed *= s
    if wildcard:
        if n_devices % fixed != 0:
            raise ValueError(f"{n_devices} devices not divisible by fixed axes product {fixed}")
        sizes[wildcard[0]] = n_devices // fixed
    else:
        total = fixed
        if total != n_devices:
            raise ValueError(f"Mesh axes product {total} != device count {n_devices}")
    return sizes


class MeshTopology:
    """Owns the global ``jax.sharding.Mesh`` and answers axis-rank queries."""

    def __init__(self, config: Optional[MeshConfig] = None, devices: Optional[Sequence] = None):
        self.config = config or MeshConfig()
        devices = list(devices if devices is not None else jax.devices())
        self.n_devices = len(devices)
        self.axis_sizes = _resolve_axis_sizes(self.config, self.n_devices)
        order = list(self.config.axis_order)
        if sorted(order) != sorted(ALL_AXES):
            raise ValueError(f"axis_order must be a permutation of {ALL_AXES}, got {order}")
        self.axis_order = order
        shape = [self.axis_sizes[a] for a in order]
        device_grid = self._arrange_devices(devices, shape)
        self.mesh = Mesh(device_grid, axis_names=tuple(order))
        # Pure-rank topology mirror for coordinate math without devices.
        self.topology = ProcessTopology(order, shape)
        logger.info(f"MeshTopology: axes={dict(zip(order, shape))} over {self.n_devices} devices")

    @staticmethod
    def _arrange_devices(devices, shape):
        if len(devices) > 1 and devices[0].platform == "tpu":
            # Respect ICI physical topology on real TPU slices; a shape the
            # slice cannot host is an error, never a silent reshape.
            from jax.experimental import mesh_utils

            return mesh_utils.create_device_mesh(shape, devices=devices)
        return np.array(devices).reshape(shape)

    # ---- axis sizes ----
    def axis_size(self, axis: str) -> int:
        return self.axis_sizes.get(axis, 1)

    @property
    def data_parallel_size(self) -> int:
        # ZeRO shards live on fsdp but each fsdp shard still sees distinct data.
        return self.axis_size("data") * self.axis_size("fsdp")

    @property
    def sharding_size(self) -> int:
        return self.axis_size("fsdp")

    @property
    def model_parallel_size(self) -> int:
        return self.axis_size("tensor")

    @property
    def pipe_parallel_size(self) -> int:
        return self.axis_size("pipe")

    @property
    def expert_parallel_size(self) -> int:
        return self.axis_size("expert")

    @property
    def sequence_parallel_size(self) -> int:
        return self.axis_size("seq")

    @property
    def context_parallel_size(self) -> int:
        return self.axis_size("context")

    # ---- shardings ----
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    @property
    def batch_axes(self):
        """Mesh axes over which the global batch is split."""
        axes = tuple(a for a in ("data", "fsdp") if self.axis_size(a) > 1)
        return axes if axes else ("data",)

    def batch_sharding(self) -> NamedSharding:
        return self.sharding(self.batch_axes)

    def __repr__(self):
        return f"MeshTopology({self.axis_sizes})"


# ------------------------------------------------------------------
# Module-level singleton + getters, mirroring reference utils/groups.py
# ------------------------------------------------------------------
_TOPOLOGY: Optional[MeshTopology] = None


def initialize_mesh(config: Optional[MeshConfig] = None, devices=None, force: bool = False) -> MeshTopology:
    """Build (or return) the global mesh. Reference: ``groups.initialize`` (``groups.py:52``).

    Rebuilds if the requested axis sizes differ from the current mesh —
    a new engine with a different parallel layout must not silently
    inherit the old one.
    """
    global _TOPOLOGY
    if _TOPOLOGY is not None and not force and config is not None:
        n = len(devices) if devices is not None else _TOPOLOGY.n_devices
        requested = _resolve_axis_sizes(config, n)
        if requested != _TOPOLOGY.axis_sizes:
            logger.info(f"initialize_mesh: rebuilding mesh {_TOPOLOGY.axis_sizes} -> {requested}")
            force = True
    if _TOPOLOGY is None or force:
        _TOPOLOGY = MeshTopology(config, devices)
    return _TOPOLOGY


def serving_mesh(tp: int = 1, devices=None) -> MeshTopology:
    """The inference-serving mesh: ``tensor=tp`` with every remaining local
    device on ``data``. One process drives N local devices — CPU CI forces
    N host devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (tests/conftest.py does this for the whole suite)."""
    return initialize_mesh(MeshConfig.from_dict({"data": -1, "tensor": int(tp)}),
                           devices=devices)


def mesh_signature(topo: Optional[MeshTopology] = None) -> str:
    """Compact topology identity for program-cache keys and journal/profile
    fingerprints: non-trivial axes in mesh order (``mesh[data2,tensor4]``),
    ``mesh[1]`` for a trivial mesh, ``mesh[none]`` with no mesh at all."""
    topo = topo if topo is not None else _TOPOLOGY
    if topo is None:
        return "mesh[none]"
    axes = ",".join(f"{a}{topo.axis_sizes[a]}" for a in topo.axis_order
                    if topo.axis_sizes[a] > 1)
    return f"mesh[{axes or '1'}]"


def get_mesh_topology(required: bool = True) -> Optional[MeshTopology]:
    if _TOPOLOGY is None and required:
        raise RuntimeError("Mesh not initialized — call deepspeed_tpu.initialize() or initialize_mesh() first")
    return _TOPOLOGY


def reset_mesh():
    global _TOPOLOGY
    _TOPOLOGY = None


# A spec against a topology, pure functions of both: the ZeRO planner's (``runtime/zero/partition.py``) and the kernels'
# placement's (``ops/placement.py``)
def prune_spec(spec: Optional[PartitionSpec], topo) -> Optional[PartitionSpec]:
    """Drop axes of size 1 from a spec (they're no-ops that would block
    further sharding of the dim by the ZeRO planner)."""
    if spec is None:
        return None

    def keep(entry):
        if entry is None:
            return None
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        names = tuple(a for a in names if topo.axis_size(a) > 1)
        if not names:
            return None
        return names if len(names) > 1 else names[0]

    return _norm([keep(e) for e in spec])


def fit_spec(spec: Optional[PartitionSpec], shape: Tuple[int, ...], topo) -> Optional[PartitionSpec]:
    """Drop from ``spec`` every entry whose axes do not divide the dimension
    they shard: that dimension stays whole (replicated over those axes)
    rather than failing placement. GPT-2's vocabulary of 50257 is the case:
    no tensor degree divides it, so its embedding cannot shard over vocab."""
    if spec is None:
        return None

    def fits(entry, dim):
        if entry is None:
            return None
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        return entry if dim % int(np.prod([topo.axis_size(a) for a in names])) == 0 else None

    entries = list(spec)[:len(shape)]
    return _norm([fits(e, d) for e, d in zip(entries, shape)])


def _norm(entries) -> PartitionSpec:
    """Strip trailing Nones so equal specs compare equal (P(None,None)==P())."""
    entries = list(entries)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)
