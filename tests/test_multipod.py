"""Multi-pod features that need >1 device, exercised in a subprocess with 8
forced host devices so the main test session keeps 1 device:

* int8-compressed cross-pod gradient psum: numerics vs the f32 all-reduce
  (the full-train-step integration of `_pod_compressed_grads` mixes manual
  'pod' with auto in-pod axes, which the current XLA SPMD partitioner only
  supports with involuntary remat — it is wired behind
  ParallelConfig.grad_compression and documented as experimental until
  Shardy lands; the payload math is what this test pins down).
* the (pod, data, model) production mesh slicing a train step.
"""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compression import quantize_int8

mesh = jax.make_mesh((8,), ("pod",))

def compressed_psum(g):
    from repro.optim.compression import block_absmax, quantize_int8_with_scale
    absmax = block_absmax(g.astype(jnp.float32), 64)
    scale = jax.lax.pmax(absmax, "pod") / 127.0
    q = quantize_int8_with_scale(g.astype(jnp.float32), scale, 64)
    qsum = jax.lax.psum(q.astype(jnp.int32), "pod")
    npods = jax.lax.psum(jnp.ones((), jnp.float32), "pod")
    deq = (qsum.astype(jnp.float32) * scale[:, None]).reshape(-1)
    return (deq[: g.size].reshape(g.shape) / npods)

def exact_psum(g):
    return jax.lax.pmean(g, "pod")

rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
run_c = jax.jit(jax.shard_map(compressed_psum, mesh=mesh,
                              in_specs=P("pod"), out_specs=P("pod")))
run_e = jax.jit(jax.shard_map(exact_psum, mesh=mesh,
                              in_specs=P("pod"), out_specs=P("pod")))
got, want = np.asarray(run_c(g)), np.asarray(run_e(g))
# error bounded by one int8 step of the max per-block scale
bound = np.abs(g).max() / 127.0 + 1e-6
err = np.abs(got - want).max()
assert err < bound, (err, bound)
# compressed payload is 4x smaller than f32 (int8 + scales)
payload_f32 = g.size * 4
payload_int8 = g.size * 1 + (g.size // 64) * 4
assert payload_int8 < 0.3 * payload_f32
print("COMPRESS_ERR", float(err), "BOUND", float(bound))

# (pod, data, model) mesh slices a real train step
from repro.config import OptimizerConfig, ParallelConfig
from repro.configs import get_smoke_config
from repro.models import model as M
from repro.optim.adamw import abstract_opt_state, init_opt_state
from repro.train.steps import make_train_step

cfg = get_smoke_config("llama3.2-1b")
mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
batch_abs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((8, 32), jnp.float32)}
ocfg = OptimizerConfig(total_steps=4, warmup_steps=1)
step, _ = make_train_step(cfg, ocfg, ParallelConfig(), mesh3, batch_abs,
                          donate=False)
params = M.init_params(cfg, jax.random.PRNGKey(0))
opt = init_opt_state(params, ocfg)
batch = {"tokens": jnp.zeros((8, 32), jnp.int32) + 5,
         "loss_mask": jnp.ones((8, 32), jnp.float32)}
with mesh3:
    p2, o2, m = step(params, opt, batch)
assert np.isfinite(float(m["loss"]))
print("MULTIPOD_OK")
"""


def test_multipod_compression_and_mesh():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MULTIPOD_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
