"""Flash-attention block-size sweep on the real chip: times ONLY the
framework train step for the flagship 750M config under
RAY_TPU_FLASH_BLOCKS / RAY_TPU_FLASH_BWD_BLOCKS overrides.

Usage: python benchmarks/tune_flash.py "512,512" "1024,512" ...
       (each arg = "fwd_bq,fwd_bk[:bwd_bq,bwd_bk]")
"""
from __future__ import annotations

import os
import subprocess
import sys

CHILD = r"""
import os
import time
import types
import jax
import jax.numpy as jnp
from chipbench.peaks import peaks_for
from chipbench.work import train_flops_per_token
from ray_tpu.models import transformer as tf
from ray_tpu.parallel import MeshPlan, build_mesh, make_train_state, make_train_step
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.train_step import make_optimizer

BATCH = int(os.environ.get("TUNE_BATCH", "8"))
D = int(os.environ.get("TUNE_D", "1536"))
L = int(os.environ.get("TUNE_L", "24"))
FF = int(os.environ.get("TUNE_FF", "4096"))
H = int(os.environ.get("TUNE_H", "12"))
cfg = tf.TransformerConfig(
    vocab_size=32000, d_model=D, n_layers=L, n_heads=H, n_kv_heads=H,
    d_ff=FF, max_seq_len=2048, dtype=jnp.bfloat16,
    remat=os.environ.get("TUNE_REMAT", "1") == "1",
    remat_policy=os.environ.get("TUNE_REMAT_POLICY", "full"),
    logits_chunk=int(os.environ.get("TUNE_LOGITS_CHUNK", "0")),
    scan_unroll=int(os.environ.get("TUNE_UNROLL", "1")),
)
plan = MeshPlan(dp=jax.device_count())
mesh = build_mesh(plan)
opt = make_optimizer(lr=3e-4, warmup=10)
params, opt_state, _ = make_train_state(cfg, plan, mesh, opt)
step = make_train_step(cfg, plan, mesh, opt)
tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, 2049), 0, cfg.vocab_size)
batch = {"tokens": jax.device_put(tokens, mesh_lib.batch_sharding(mesh, plan))}
for _ in range(2):
    params, opt_state, m = step(params, opt_state, batch)
    print("warmup loss", float(m["loss"]), flush=True)
t0 = time.perf_counter()
N = 6
for _ in range(N):
    params, opt_state, m = step(params, opt_state, batch)
_ = float(m["loss"])  # materialize: forces the whole chain
dt = (time.perf_counter() - t0) / N
# The benchmark's count of work and table of peaks (one yardstick: an MFU
# here reads on the ledger's scale; an unknown device kind raises).
dims = types.SimpleNamespace(vocab=cfg.vocab_size, hidden=D, layers=L, heads=H,
                             kv_heads=H, head_dim=cfg.head_dim, ffn=FF)
flops_tok = train_flops_per_token(dims, 2048)
n_params = sum(int(x.size) for x in jax.tree.leaves(params))
peak = peaks_for(jax.devices()[0].device_kind)["bf16_flops"]
mfu = (flops_tok * BATCH * 2048 / dt) / (peak * jax.device_count())
tps = BATCH * 2048 / dt
print(f"RESULT {dt*1e3:.1f} ms/step  MFU {mfu:.2%}  {tps:.0f} tok/s  params {n_params/1e6:.0f}M", flush=True)
"""


def main():
    configs = sys.argv[1:] or ["512,512"]
    for spec in configs:
        if ":" in spec:
            fwd, bwd = spec.split(":")
        else:
            fwd, bwd = spec, ""
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["RAY_TPU_FLASH_BLOCKS"] = fwd
        if bwd:
            env["RAY_TPU_FLASH_BWD_BLOCKS"] = bwd
        else:
            env.pop("RAY_TPU_FLASH_BWD_BLOCKS", None)
        out = subprocess.run(
            [sys.executable, "-c", CHILD], env=env, capture_output=True, text=True,
            timeout=900,
        )
        line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
        print(f"fwd={fwd} bwd={bwd or fwd}: {line[0][7:] if line else 'FAILED'}",
              flush=True)
        if not line:
            print(out.stderr[-500:], flush=True)


if __name__ == "__main__":
    main()
