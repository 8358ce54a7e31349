"""Communication microbenchmarks (``ds_tpu_bench``).

Capability parity: reference ``bin/ds_bench`` -> ``benchmarks/communication``
(all_reduce / all_gather / all_to_all / broadcast / pt2pt sweeps with
algorithm- and bus-bandwidth reporting). TPU-native stance: the collectives
are XLA ops over mesh axes compiled with ``shard_map`` (the production
comm path, ``comm/collectives.py``), so the benchmark measures exactly
what training runs — ICI on real multichip, shared-memory on the virtual
host mesh.

Bandwidth accounting (matches the reference's ``utils.py``):
- algbw = payload_bytes / time
- busbw: all_reduce x 2(n-1)/n, all_gather / reduce_scatter / all_to_all
  x (n-1)/n — the per-link traffic of ring algorithms.
"""

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..comm import collectives
from ..parallel.mesh import get_mesh_topology


_OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all", "ppermute", "broadcast")


def _bus_factor(op: str, n: int) -> float:
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    return 1.0


def _build(op: str, axis: str):
    if op == "all_reduce":
        return lambda x: collectives.all_reduce(x, group=axis)
    if op == "all_gather":
        return lambda x: collectives.all_gather_into_tensor(x, group=axis)
    if op == "reduce_scatter":
        return lambda x: collectives.reduce_scatter_tensor(x, group=axis)
    if op == "all_to_all":
        return lambda x: collectives.all_to_all_single(x, group=axis)
    if op == "ppermute":
        return lambda x: collectives.send_recv_ring(x, group=axis, shift=1)
    if op == "broadcast":
        return lambda x: collectives.broadcast(x, src=0, group=axis)
    raise ValueError(f"unknown op {op!r} (have {_OPS})")


def run_comm_bench(ops: Optional[List[str]] = None, axis: str = "data", sizes_mb: Optional[List[float]] = None,
                   dtype=jnp.bfloat16, trials: int = 20, warmups: int = 3, topo=None) -> List[Dict]:
    """Sweep collectives over ``axis``; returns one record per (op, size):
    {op, size_bytes, time_us, algbw_gbps, busbw_gbps}."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    topo = topo if topo is not None else get_mesh_topology()
    n = topo.axis_sizes[axis]
    if n <= 1:
        raise ValueError(f"mesh axis {axis!r} has size {n}; nothing to benchmark")
    ops = ops or ["all_reduce", "all_gather", "all_to_all"]
    sizes_mb = sizes_mb or [1, 4, 16, 64]
    itemsize = jnp.dtype(dtype).itemsize
    mesh = topo.mesh
    other = tuple(a for a in mesh.axis_names if a != axis)
    results = []
    for op in ops:
        fn = _build(op, axis)
        for mb in sizes_mb:
            per_dev = max(128, int(mb * 2**20 / itemsize / n) // 128 * 128)
            shape = (n * per_dev,)
            x = jax.device_put(
                jnp.ones(shape, dtype),
                jax.sharding.NamedSharding(mesh, P(axis)))
            sharded = shard_map(fn, mesh=mesh, in_specs=P(axis),
                                out_specs=_out_spec(op, axis), check_vma=False)
            run = jax.jit(sharded)
            for _ in range(warmups):
                out = run(x)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(trials):
                out = run(x)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / trials
            payload = shape[0] * itemsize
            algbw = payload / dt
            results.append({
                "op": op, "axis": axis, "world": n, "size_bytes": payload,
                "time_us": round(dt * 1e6, 1),
                "algbw_gbps": round(algbw / 1e9, 3),
                "busbw_gbps": round(algbw * _bus_factor(op, n) / 1e9, 3),
            })
    return results


def _out_spec(op: str, axis: str):
    from jax.sharding import PartitionSpec as P

    # inside shard_map each rank holds its block; output layouts differ per op
    if op in ("all_gather", "broadcast"):
        return P()  # replicated full tensor
    if op == "all_reduce":
        return P()  # replicated reduction
    return P(axis)  # reduce_scatter / all_to_all / ppermute keep a shard


def format_table(results: List[Dict]) -> str:
    lines = [f"{'op':<16}{'world':>6}{'size':>12}{'time(us)':>12}{'algbw(GB/s)':>14}{'busbw(GB/s)':>14}"]
    for r in results:
        size = f"{r['size_bytes'] / 2**20:.1f}MB"
        lines.append(f"{r['op']:<16}{r['world']:>6}{size:>12}{r['time_us']:>12}"
                     f"{r['algbw_gbps']:>14}{r['busbw_gbps']:>14}")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    import json as _json

    ap = argparse.ArgumentParser("ds_tpu_bench", description="collective communication sweep over a mesh axis")
    ap.add_argument("--ops", nargs="+", default=["all_reduce", "all_gather", "all_to_all"], choices=_OPS)
    ap.add_argument("--axis", default="data")
    ap.add_argument("--sizes-mb", nargs="+", type=float, default=[1, 4, 16])
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "fp32"])
    ap.add_argument("--trials", type=int, default=20)
    ap.add_argument("--mesh", default=None, help='JSON mesh layout, e.g. \'{"data": 8}\' (defaults to all devices on data)')
    ap.add_argument("--json", action="store_true", help="emit JSON records instead of the table")
    args = ap.parse_args(argv)

    from ..parallel.mesh import initialize_mesh
    from ..runtime.config import MeshConfig

    layout = _json.loads(args.mesh) if args.mesh else {"data": jax.device_count()}
    topo = initialize_mesh(MeshConfig.from_dict(layout), force=True)
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    res = run_comm_bench(ops=args.ops, axis=args.axis, sizes_mb=args.sizes_mb, dtype=dtype,
                         trials=args.trials, topo=topo)
    print(_json.dumps(res) if args.json else format_table(res))
    return 0
