"""Dist tier round 5: the paths SPMD dryruns structurally cannot cover.

``MULTICHIP_r*.json`` legs run single-process on a virtual mesh, so they
prove compilation + single-process execution of the sharded programs —
but not cross-process rendezvous, collective transport between address
spaces, worker death, or launcher env plumbing. These tests close that
gap (VERDICT r4 weak #4): every case forks REAL processes.

Reference analogues: ``tests/unit/common.py:113`` (forked harness),
``deepspeed/elasticity/elastic_agent.py:125`` (kill -> restart ->
resume contract), launcher runner end-to-end.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dist_utils import REPO, free_port, run_distributed

pytestmark = pytest.mark.dist


# ------------------------------------------------------------------ collectives
def test_collectives_ladder_two_procs():
    """all_gather / reduce_scatter / all_to_all / broadcast /
    send_recv_ring with operands that MUST cross the process boundary
    (rank-dependent values; 2 procs x 2 devices)."""
    out = run_distributed("""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
_SM_KW = {"check_vma": False}
from functools import partial
from jax.experimental import multihost_utils
from deepspeed_tpu.comm import collectives as C

G = lambda a: np.asarray(multihost_utils.process_allgather(a, tiled=True))

mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
x = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")),
    np.arange(2, dtype=np.float32).reshape(2, 1) + RANK * 2, (4, 1))

sm = partial(shard_map, mesh=mesh, in_specs=P("data", None), **_SM_KW)

ag = jax.jit(sm(lambda a: C.all_gather_into_tensor(a, group="data"),
                out_specs=P(None, None)))(x)
np.testing.assert_array_equal(G(ag).ravel(), [0, 1, 2, 3])

rs = jax.jit(sm(lambda a: C.reduce_scatter_tensor(jnp.tile(a.sum(keepdims=True), (4, 1)),
                                                  group="data"),
                out_specs=P("data", None)))(x)
# each shard contributes its own value to every slot; slot i sums all shards
assert float(G(rs).sum()) == 4 * (0 + 1 + 2 + 3), G(rs)

a2a = jax.jit(sm(lambda a: C.all_to_all_single(jnp.tile(a, (4, 1)), group="data"),
                 out_specs=P("data", None)))(x)
assert G(a2a).shape == (16, 1)

bc = jax.jit(sm(lambda a: C.broadcast(a, src=3, group="data"),
                out_specs=P("data", None)))(x)
np.testing.assert_array_equal(G(bc).ravel(), [3, 3, 3, 3])

ring = jax.jit(sm(lambda a: C.send_recv_ring(a, group="data", shift=1),
                  out_specs=P("data", None)))(x)
np.testing.assert_array_equal(G(ring).ravel(), [3, 0, 1, 2])
print("COLL_OK", RANK)
""")
    assert all("COLL_OK" in o for o in out)


def test_ulysses_attention_two_procs():
    """Ulysses head-scatter/seq-gather a2a spanning processes; every rank
    checks its local output shard against the replicated dense oracle."""
    out = run_distributed("""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from deepspeed_tpu.sequence.layer import ulysses_sharded_attention

B, S, H, D = 2, 16, 4, 8
rng = np.random.RandomState(0)  # same on both ranks
q, k, v = (rng.randn(B, S, H, D).astype(np.float32) for _ in range(3))
mesh = Mesh(np.array(jax.devices()).reshape(4), ("seq",))
sh = NamedSharding(mesh, P(None, "seq", None, None))
def put(a):
    return jax.make_array_from_process_local_data(
        sh, a[:, (S // 2) * RANK:(S // 2) * (RANK + 1)], (B, S, H, D))
o = ulysses_sharded_attention(put(q), put(k), put(v), mesh, causal=True)

# dense oracle (replicated math)
qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
logits = np.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(D)
mask = np.tril(np.ones((S, S), bool))
logits = np.where(mask, logits, -1e30)
p = np.exp(logits - logits.max(-1, keepdims=True))
p /= p.sum(-1, keepdims=True)
ref = np.einsum("bhqk,bhkd->bqhd", p, vt)

from jax.experimental import multihost_utils
local = np.asarray(multihost_utils.process_allgather(o, tiled=True))
np.testing.assert_allclose(local, ref, rtol=2e-4, atol=2e-5)
print("ULYSSES_OK", RANK)
""", timeout=560)
    assert all("ULYSSES_OK" in o for o in out)


def test_ring_attention_two_procs():
    """Ring CP: KV blocks ppermute around a ring that crosses the process
    boundary; numerics must match full softmax attention."""
    out = run_distributed("""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from deepspeed_tpu.sequence.ring import ring_sharded_attention

B, S, H, D = 1, 16, 4, 8
KVH = 2  # GQA stays collapsed through the cross-proc ring
rng = np.random.RandomState(1)
q = rng.randn(B, S, H, D).astype(np.float32)
k = rng.randn(B, S, KVH, D).astype(np.float32)
v = rng.randn(B, S, KVH, D).astype(np.float32)
mesh = Mesh(np.array(jax.devices()).reshape(4), ("context",))
sh = NamedSharding(mesh, P(None, "context", None, None))
def put(a):
    c = a.shape[1] // 4
    lo = c * (RANK * 2)
    return jax.make_array_from_process_local_data(sh, a[:, lo:lo + 2 * c], a.shape)
o = ring_sharded_attention(put(q), put(k), put(v), mesh, causal=True)

kr = np.repeat(k, H // KVH, axis=2)
vr = np.repeat(v, H // KVH, axis=2)
qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, kr, vr))
logits = np.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(D)
logits = np.where(np.tril(np.ones((S, S), bool)), logits, -1e30)
p = np.exp(logits - logits.max(-1, keepdims=True)); p /= p.sum(-1, keepdims=True)
ref = np.einsum("bhqk,bhkd->bqhd", p, vt)

from jax.experimental import multihost_utils
full = np.asarray(multihost_utils.process_allgather(o, tiled=True))
np.testing.assert_allclose(full, ref, rtol=2e-4, atol=2e-5)
print("RING_OK", RANK)
""", timeout=560)
    assert all("RING_OK" in o for o in out)


# ------------------------------------------------------------------ engines
def test_pipeline_engine_two_procs():
    """The compiled 1F1B pipeline with its CollectivePermute stage
    transfers crossing the process boundary (pipe=2 x data=2 over 2
    procs); both ranks must agree on the loss and complete a step."""
    out = run_distributed("""
import numpy as np
import jax
import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, TransformerConfig

model = CausalLM(TransformerConfig(vocab_size=256, n_layers=4, n_heads=2, d_model=32,
                                   max_seq_len=32, norm="rmsnorm", activation="swiglu",
                                   pos_emb="rope", tie_embeddings=False))
params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
    "train_micro_batch_size_per_gpu": 1,
    "gradient_accumulation_steps": 4,
    "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 1},
    "mesh": {"pipe": 2, "data": 2},
    "steps_per_print": 10**9,
})
g = engine.topology.data_parallel_size
batch = {"input_ids": np.ones((engine.num_microbatches, g, 16), np.int32)}
loss = engine.forward(batch)
engine.backward(loss)
engine.step()
jax.block_until_ready(engine.params)
assert engine.global_steps == 1
print("PIPE_OK", RANK, round(float(loss), 6))
""", timeout=560)
    assert all("PIPE_OK" in o for o in out)
    # both ranks computed the SAME loss for the same global step
    losses = {o.split("PIPE_OK")[1].split()[1] for o in out}
    assert len(losses) == 1, losses


def test_moe_engine_two_procs():
    """MoE expert-parallel a2a dispatch with experts living in different
    processes (expert=4 over 2 procs)."""
    out = run_distributed("""
import numpy as np
import jax
import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, TransformerConfig

model = CausalLM(TransformerConfig(vocab_size=256, n_layers=2, n_heads=2, d_model=32,
                                   max_seq_len=32, moe_num_experts=4, moe_top_k=1,
                                   moe_capacity_factor=4.0))
params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
    "train_micro_batch_size_per_gpu": 4,
    "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 0},
    "mesh": {"data": 1, "expert": 4},
    "steps_per_print": 10**9,
})
rng = np.random.RandomState(0)
batch = {"input_ids": rng.randint(0, 256, size=(4, 16)).astype(np.int32)}
loss = engine.forward(batch); engine.backward(loss); engine.step()
jax.block_until_ready(engine.params)
assert np.isfinite(float(loss))
print("MOE_OK", RANK, round(float(loss), 6))
""", timeout=560)
    assert all("MOE_OK" in o for o in out)


def test_per_host_data_loading_two_procs():
    """deepspeed_io(per_host=True): each process collates ONLY the rows its
    devices own — enforced by a dataset that raises on foreign access —
    and the training step still sees the correct global batch."""
    out = run_distributed("""
import numpy as np
import jax
import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, llama_tiny

model = CausalLM(llama_tiny())
params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 2}, "mesh": {"data": 4}, "steps_per_print": 10**9,
})

rng = np.random.RandomState(0)
rows = [{"input_ids": rng.randint(0, 1024, size=(16,)).astype(np.int32)} for _ in range(16)]

class OwnedOnly:
    # global batch 8: process 0 owns rows [i%8 < 4], process 1 the rest
    def __len__(self):
        return len(rows)
    def __getitem__(self, i):
        assert (i % 8) // 4 == RANK, f"process {RANK} touched foreign row {i}"
        return rows[i]

it = iter(engine.deepspeed_io(OwnedOnly(), per_host=True))
losses = [float(engine.train_batch(it)) for _ in range(2)]
assert all(np.isfinite(losses)), losses

# oracle: full-batch path on a fresh engine must see the same trajectory
model2 = CausalLM(llama_tiny())
params2 = model2.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 16), np.int32)})
oracle, _, _, _ = deepspeed_tpu.initialize(model=model2, model_parameters=params2, config={
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 2}, "mesh": {"data": 4}, "steps_per_print": 10**9,
})
it2 = iter(oracle.deepspeed_io(rows))
base = [float(oracle.train_batch(it2)) for _ in range(2)]
np.testing.assert_allclose(losses, base, rtol=1e-5)
print("PERHOST_OK", RANK, losses)
""", timeout=560)
    assert all("PERHOST_OK" in o for o in out)


# ------------------------------------------------------------------ elasticity
def test_elastic_agent_kill_and_resume(tmp_path):
    """The reference's elasticity contract end-to-end: a worker is
    SIGKILLed mid-training, the agent restarts it, it resumes from the
    universal checkpoint, and the post-restart losses EQUAL an
    uninterrupted run's tail — the loss curve continues, not restarts."""
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent, ElasticAgentConfig

    work = tmp_path / "work"
    work.mkdir()
    worker = tmp_path / "worker.py"
    worker.write_text(f"""
import json, os, signal, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, gpt2_tiny

WORK = {str(work)!r}
TOTAL = 6
KILL_AT = 3  # first round dies mid-run, AFTER step 3's checkpoint

model = CausalLM(gpt2_tiny())
params = model.init(jax.random.PRNGKey(0), {{"input_ids": np.zeros((1, 16), np.int32)}})
engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={{
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {{"type": "adam", "params": {{"lr": 1e-2}}}},
    "zero_optimization": {{"stage": 2}},
    "mesh": {{"data": -1}},
    "steps_per_print": 10**9,
}})
ckpt = os.path.join(WORK, "uckpt")
if os.path.isdir(ckpt):
    engine.load_universal_checkpoint(ckpt)

def batch(i):
    rng = np.random.RandomState(1000 + i)
    dp = engine.topology.data_parallel_size
    return {{"input_ids": rng.randint(0, 1024, size=(2 * dp, 16)).astype(np.int32)}}

log = os.path.join(WORK, "losses.jsonl")
while engine.global_steps < TOTAL:
    step = engine.global_steps
    loss = engine.forward(batch(step)); engine.backward(loss); engine.step()
    with open(log, "a") as f:
        f.write(json.dumps({{"step": step, "loss": float(loss),
                             "round": os.environ.get("DS_TPU_ELASTIC_RESTART")}}) + "\\n")
    engine.save_universal_checkpoint(ckpt)
    if os.environ.get("DS_TPU_ELASTIC_RESTART") == "0" and engine.global_steps == KILL_AT:
        os.kill(os.getpid(), signal.SIGKILL)  # the failure the agent exists for
""")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    agent = DSElasticAgent([sys.executable, str(worker)],
                           ElasticAgentConfig(max_restarts=2, restart_backoff_s=0.2),
                           env=env)
    assert agent.run() == 0
    assert agent.restarts == 1  # exactly one death, one successful resume

    rows = [json.loads(l) for l in (work / "losses.jsonl").read_text().splitlines()]
    by_step = {}
    for r in rows:
        by_step.setdefault(r["step"], r)
    assert sorted(by_step) == list(range(6))
    assert {r["round"] for r in rows} == {"0", "1"}

    # uninterrupted oracle: same data schedule, straight 6 steps
    oracle = tmp_path / "oracle.py"
    oracle.write_text(f"""
import json
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, gpt2_tiny

model = CausalLM(gpt2_tiny())
params = model.init(jax.random.PRNGKey(0), {{"input_ids": np.zeros((1, 16), np.int32)}})
engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config={{
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {{"type": "adam", "params": {{"lr": 1e-2}}}},
    "zero_optimization": {{"stage": 2}},
    "mesh": {{"data": -1}},
    "steps_per_print": 10**9,
}})
out = []
for i in range(6):
    rng = np.random.RandomState(1000 + i)
    dp = engine.topology.data_parallel_size
    b = {{"input_ids": rng.randint(0, 1024, size=(2 * dp, 16)).astype(np.int32)}}
    loss = engine.forward(b); engine.backward(loss); engine.step()
    out.append(float(loss))
print("ORACLE " + json.dumps(out))
""")
    r = subprocess.run([sys.executable, str(oracle)], env=env, capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    base = json.loads(r.stdout.split("ORACLE ")[1])
    got = [by_step[i]["loss"] for i in range(6)]
    np.testing.assert_allclose(got, base, rtol=1e-4, atol=1e-5,
                               err_msg="post-restart loss curve detached from uninterrupted run")


# ------------------------------------------------------------------ launcher
def test_launcher_end_to_end_localhost(tmp_path):
    """The per-host launcher end-to-end on a 2-"node" localhost world:
    launch.py builds each child's rendezvous env (MASTER_*/RANK/
    DS_TPU_*), the children bring up jax.distributed through the comm
    facade, and a cross-process collective agrees."""
    import base64

    script = tmp_path / "train_stub.py"
    script.write_text("""
import os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import deepspeed_tpu.comm as dist
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

dist.init_distributed(verbose=False)
assert dist.get_world_size() == 2
assert int(os.environ["DS_TPU_NODE_RANK"]) == dist.get_rank()
mesh = Mesh(np.array(jax.devices()).reshape(2), ("data",))
x = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")), np.full((1,), dist.get_rank() + 1.0, np.float32), (2,))
total = jax.jit(lambda a: a.sum(), out_shardings=NamedSharding(mesh, P()))(x)
assert float(total) == 3.0, float(total)
print("LAUNCH_OK", dist.get_rank())
""")
    world_info = base64.urlsafe_b64encode(
        json.dumps({"node-a": [0], "node-b": [1]}).encode()).decode()
    port = free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        env["XLA_FLAGS"] = " ".join(flags + ["--xla_force_host_platform_device_count=1"])
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
             "--world_info", world_info, "--node_rank", str(rank),
             "--master_addr", "127.0.0.1", "--master_port", str(port),
             str(script)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=420)[0].decode(errors="replace") for p in procs]
    for p, o in zip(procs, outs):
        assert p.returncode == 0, o[-3000:]
    assert all("LAUNCH_OK" in o for o in outs)
