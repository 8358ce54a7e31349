"""Parallel-group getters.

API parity with the reference ``deepspeed/utils/groups.py`` (getters at
``groups.py:397-515``): callers ask for the world size / rank along each
parallel dimension. On TPU a "group" is a mesh axis; "rank in group" is the
host-process coordinate along that axis (meaningful on multi-host, always 0
for the in-jit SPMD view where XLA owns per-device identity).
"""

from typing import Optional

from ..parallel.mesh import get_mesh_topology, initialize_mesh, reset_mesh  # noqa: F401 (re-export)


def _topo():
    return get_mesh_topology(required=True)


def initialize(ep_size: int = 1, mpu=None):
    """Reference-compat entry (``groups.py:52``): expert-parallel size is a
    mesh axis here, so this validates rather than constructs groups."""
    topo = get_mesh_topology(required=False)
    if topo is not None and ep_size not in (1, topo.expert_parallel_size):
        raise ValueError(
            f"ep_size {ep_size} conflicts with mesh expert axis {topo.expert_parallel_size}; set mesh.expert in config")
    return topo


# -- world sizes --
def get_data_parallel_world_size() -> int:
    return _topo().data_parallel_size


def get_model_parallel_world_size() -> int:
    return _topo().model_parallel_size


def get_tensor_model_parallel_world_size() -> int:
    return _topo().model_parallel_size


def get_expert_parallel_world_size(group_name: str = "", held: Optional[int] = None) -> int:
    """The ``expert`` axis' size; with ``held``, the count of experts a routed layer holds: the chips THOSE are spread
    over, by the layer's own rule (``ops/placement.py::held_axes``: ``expert`` and ``fsdp`` where they divide the count,
    else 1), so that the getter and ``moe/layer.py::_over_expert_axis`` cannot disagree. A model with no routed layer
    has no such count, and a ZeRO axis alone is no expert parallelism."""
    if held is None:
        return _topo().expert_parallel_size
    from ..ops.placement import held_axes

    chips = 1
    for axis in held_axes((held, 1, 1)):
        chips *= _topo().axis_size(axis)
    return chips


def get_expert_data_parallel_world_size(group_name: str = "", held: Optional[int] = None) -> int:
    return max(1, get_data_parallel_world_size() // get_expert_parallel_world_size(group_name, held))


def get_sequence_parallel_world_size() -> int:
    return _topo().sequence_parallel_size


def get_pipe_parallel_world_size() -> int:
    return _topo().pipe_parallel_size


def get_context_parallel_world_size() -> int:
    return _topo().context_parallel_size


def get_zero_param_shard_size() -> int:
    return _topo().sharding_size


# -- axis names for in-jit collectives --
def get_data_parallel_axis():
    return _topo().batch_axes


def get_model_parallel_axis() -> str:
    return "tensor"


def get_expert_parallel_axis() -> str:
    """The axis over which every chip routes the SAME tokens and the experts' parts are summed. A routed layer's held
    experts also lie by expert over ``get_fsdp_axis()``, over which the rows are split too and travel to their experts
    (``moe/layer.py::_over_expert_axis``); ``get_expert_parallel_world_size(held=...)`` counts both."""
    return "expert"


def get_sequence_parallel_axis() -> str:
    return "seq"


def get_context_parallel_axis() -> str:
    return "context"


def get_fsdp_axis() -> str:
    return "fsdp"


# -- ranks (host-process view; 0 on single-host) --
def _process_coord(axis: str) -> int:
    import jax

    topo = _topo()
    # Host index -> first device it owns -> coordinate along axis.
    try:
        local0 = jax.local_devices()[0]
        flat = list(topo.mesh.devices.flat)
        rank = flat.index(local0)
        coord = topo.topology.get_coord(rank)
        return getattr(coord, axis, 0)
    except Exception:
        return 0


def get_data_parallel_rank() -> int:
    return _process_coord("data")


def get_model_parallel_rank() -> int:
    return _process_coord("tensor")


def get_tensor_model_parallel_rank() -> int:
    return _process_coord("tensor")


def get_expert_parallel_rank(group_name: str = "") -> int:
    return _process_coord("expert")


def get_sequence_parallel_rank() -> int:
    return _process_coord("seq")


def get_pipe_parallel_rank() -> int:
    return _process_coord("pipe")


# group objects do not exist on TPU; return axis names for compatibility
def get_data_parallel_group():
    return get_data_parallel_axis()


def get_model_parallel_group():
    return get_model_parallel_axis()


def get_expert_parallel_group(group_name: str = ""):
    return get_expert_parallel_axis()


def get_sequence_parallel_group():
    return get_sequence_parallel_axis()


def get_context_parallel_group():
    return get_context_parallel_axis()
