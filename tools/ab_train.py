"""Single-chip training A/B harness: times the GPT-2-125M fwd+bwd step
under flash-block / CE-chunk variants. Run one variant per process (the
env knobs are read at import):
  python tools/ab_train.py <FLASH_BQ> <FLASH_BK> [CE_CHUNK]
Optional DS_AB_BS sets the micro-batch (default 16). Prints one line:
  VARIANT bq=..,bk=..,ce=..,bs=..: X ms/step (Y tok/s)."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root
bq, bk = sys.argv[1], sys.argv[2]
os.environ["DS_TPU_FLASH_BQ"] = bq
os.environ["DS_TPU_FLASH_BK"] = bk
if len(sys.argv) > 3:
    os.environ["DS_TPU_CE_CHUNK"] = sys.argv[3]
import jax, jax.numpy as jnp, numpy as np
from deepspeed_tpu.models import CausalLM, TransformerConfig

cfg = TransformerConfig(vocab_size=50257, n_layers=12, n_heads=12, d_model=768, max_seq_len=1024, dtype=jnp.bfloat16)
model = CausalLM(cfg)
params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1,1024), np.int32)})
bparams = jax.tree.map(lambda x: x.astype(jnp.bfloat16) if x.dtype==jnp.float32 else x, params)
bs = int(os.environ.get("DS_AB_BS", 16))
batch = {"input_ids": np.random.RandomState(0).randint(0, 50257, size=(bs,1024)).astype(np.int32)}
vg = jax.jit(jax.value_and_grad(lambda p,b: model.loss_fn(p,b)))
t0=time.perf_counter(); l,_ = vg(bparams, batch); float(l)
comp = time.perf_counter()-t0
n = 10
t0=time.perf_counter()
for _ in range(n): l,g = vg(bparams, batch)
float(l)
dt=(time.perf_counter()-t0)/n
print(f"VARIANT bq={bq},bk={bk},ce={os.environ.get('DS_TPU_CE_CHUNK','auto')},bs={bs}: "
      f"{dt*1e3:.1f} ms/step ({bs*1024/dt:.0f} tok/s) [compile {comp:.0f}s]", flush=True)
