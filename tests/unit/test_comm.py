"""Communication tests. Reference coverage model: ``tests/unit/comm/test_dist.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

import deepspeed_tpu.comm as dist
from deepspeed_tpu.comm import collectives
from deepspeed_tpu.parallel.mesh import MeshTopology
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.utils.comms_logging import calc_bw_log


@pytest.fixture
def data_mesh():
    return MeshTopology(MeshConfig.from_dict({"data": 8}))


def _smap(mesh, fn, in_specs, out_specs):
    return jax.jit(shard_map(fn, mesh=mesh.mesh, in_specs=in_specs, out_specs=out_specs))


def test_injit_all_reduce(data_mesh):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    f = _smap(data_mesh, lambda v: collectives.all_reduce(v, group="data"), (P("data", None),), P("data", None))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.full((8, 1), x.sum()))


def test_injit_all_reduce_max(data_mesh):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    f = _smap(data_mesh, lambda v: collectives.all_reduce(v, op=dist.ReduceOp.MAX, group="data"),
              (P("data", None),), P("data", None))
    assert np.asarray(f(x)).max() == 7.0


def test_injit_all_gather(data_mesh):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    f = _smap(data_mesh, lambda v: collectives.all_gather_into_tensor(v, group="data"),
              (P("data", None),), P("data", None))
    out = np.asarray(f(x))  # each member gathers all 8 values -> global (64, 1)
    assert out.shape == (64, 1)
    np.testing.assert_allclose(out[:8, 0], np.arange(8))


def test_injit_reduce_scatter(data_mesh):
    # every member holds the full vector 0..7; reduce-scatter sums and splits
    x = np.tile(np.arange(8, dtype=np.float32), (8, 1))
    f = _smap(data_mesh, lambda v: collectives.reduce_scatter_tensor(v.reshape(-1), group="data"),
              (P("data", None),), P("data"))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.arange(8) * 8.0)


def test_injit_all_to_all(data_mesh):
    # member i sends value 10*i+j to member j
    x = np.array([[10 * i + j for j in range(8)] for i in range(8)], dtype=np.float32)
    f = _smap(data_mesh, lambda v: collectives.all_to_all_single(v.reshape(-1), group="data"),
              (P("data", None),), P("data"))
    out = np.asarray(f(x)).reshape(8, 8)
    np.testing.assert_allclose(out, x.T)


def test_injit_broadcast(data_mesh):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    f = _smap(data_mesh, lambda v: collectives.broadcast(v, src=3, group="data"), (P("data", None),), P("data", None))
    np.testing.assert_allclose(np.asarray(f(x)), np.full((8, 1), 3.0))


def test_eager_all_reduce():
    x = jnp.arange(8.0).reshape(8, 1)
    out = dist.all_reduce(x)
    np.testing.assert_allclose(np.asarray(out), [28.0])


def test_eager_all_to_all():
    x = jnp.arange(16.0).reshape(4, 4)
    out = dist.all_to_all_single(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).T)


def test_eager_broadcast():
    x = jnp.stack([jnp.full((2,), float(i)) for i in range(4)])
    out = dist.broadcast(x, src=2)
    np.testing.assert_allclose(np.asarray(out), np.full((4, 2), 2.0))


def test_init_distributed_single_process():
    dist.init_distributed()
    assert dist.is_initialized()
    assert dist.get_world_size() == 8  # devices
    assert dist.get_rank() == 0
    dist.barrier()


def test_comms_logger_records():
    dist.configure(enabled=True, verbose=False)
    try:
        x = jnp.ones((8, 4))
        dist.all_reduce(x)
        assert "all_reduce" in dist.comms_logger.comms_dict
        summary = dist.log_summary()
        assert "all_reduce" in summary
    finally:
        dist.configure(enabled=False)


def test_bw_calc_all_reduce():
    tput, busbw = calc_bw_log("all_reduce", size_bytes=1_000_000, duration_s=0.001, n=8)
    assert tput == pytest.approx(2 * 1_000_000 / 0.001 * 8 / 1e9)
    assert busbw == pytest.approx((1_000_000 / 0.001) * (2 * 7 / 8) * 8 / 1e9)
