"""Multi-device distribution tests (subprocess with 8 host devices)."""

import pytest

from helpers import SIM_DEVICE_SNIPPET, run_subprocess


def test_seq_sharded_decode_matches_ref():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.dist.seq_decode import seq_decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref

mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
b, s, kv, rep, hd = 4, 64, 2, 3, 16
h = kv * rep
q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
kn = jnp.asarray(rng.standard_normal((b, kv, hd)), jnp.float32)
vn = jnp.asarray(rng.standard_normal((b, kv, hd)), jnp.float32)
ck = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
cv = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
pos = jnp.int32(37)
with jax.set_mesh(mesh):
    ck_d = jax.device_put(ck, NamedSharding(mesh, P("data", "model", None, None)))
    cv_d = jax.device_put(cv, NamedSharding(mesh, P("data", "model", None, None)))
    out, ck2, cv2 = jax.jit(lambda *a: seq_decode_attention(
        *a, mesh=mesh, seq_axes=("model",), batch_axes=("data",)))(
        q, kn, vn, ck_d, cv_d, pos)
# reference: update then attend over pos+1
ck_ref = ck.at[:, 37].set(kn)
cv_ref = cv.at[:, 37].set(vn)
want = decode_attention_ref(q, ck_ref, cv_ref, length=38)
np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-3, rtol=2e-3)
np.testing.assert_allclose(np.asarray(ck2), np.asarray(ck_ref), atol=1e-6)
print("SEQ_DECODE_OK")
""")
    assert "SEQ_DECODE_OK" in out


def test_sharded_train_step_matches_single_device():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch.train import train_loop
from repro.launch.mesh import make_host_mesh
from repro.dist.sharding import ShardingConfig

cfg = configs.get("qwen2.5-3b").smoke()
mesh8 = make_host_mesh(8, ("data",))
out8 = train_loop(cfg, steps_total=6, batch=8, seq_len=32, mesh=mesh8,
                  log_every=0,
                  scfg=ShardingConfig(data_axes=("data",), model_axes=(),
                                      fsdp_axes=("data",), remat=False))
mesh1 = make_host_mesh(1, ("data",))
out1 = train_loop(cfg, steps_total=6, batch=8, seq_len=32, mesh=mesh1,
                  log_every=0,
                  scfg=ShardingConfig(data_axes=("data",), model_axes=(),
                                      fsdp_axes=(), remat=False))
np.testing.assert_allclose(out8["losses"], out1["losses"], rtol=2e-4, atol=2e-4)
print("DP_MATCH_OK")
""")
    assert "DP_MATCH_OK" in out


def test_tensor_parallel_train_step():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch.mesh import make_mesh
from repro.launch.train import train_loop
from repro.dist.sharding import ShardingConfig

cfg = configs.get("phi3.5-moe-42b-a6.6b").smoke()
mesh = make_mesh((2, 4), ("data", "model"))
out = train_loop(cfg, steps_total=4, batch=4, seq_len=32, mesh=mesh,
                 log_every=0,
                 scfg=ShardingConfig(data_axes=("data",),
                                     model_axes=("model",),
                                     fsdp_axes=("data",), microbatches=2,
                                     seq_parallel=True, remat=True))
assert all(np.isfinite(l) for l in out["losses"])
assert out["losses"][-1] < out["losses"][0] + 0.5
print("TP_OK", out["losses"][0], out["losses"][-1])
""")
    assert "TP_OK" in out


def test_elastic_remesh_restore_continues_identically():
    out = run_subprocess("""
import tempfile, jax, numpy as np
from repro import configs
from repro.launch.train import train_loop
from repro.launch.mesh import make_host_mesh
from repro.dist.sharding import ShardingConfig

cfg = configs.get("qwen2.5-3b").smoke()
d = tempfile.mkdtemp()
scfg8 = ShardingConfig(data_axes=("data",), model_axes=(), fsdp_axes=("data",),
                       remat=False)
# train 8 steps on 8 devices, checkpoint at 4
out8 = train_loop(cfg, steps_total=8, batch=8, seq_len=32, ckpt_dir=d,
                  ckpt_every=4, mesh=make_host_mesh(8), log_every=0,
                  scfg=scfg8)
# resume the step-8 checkpoint on FOUR devices (elastic shrink) and
# continue to step 12; compare with a straight 12-step 8-device run
out12a = train_loop(cfg, steps_total=12, batch=8, seq_len=32, ckpt_dir=d,
                    ckpt_every=100, mesh=make_host_mesh(4), log_every=0,
                    scfg=scfg8)
assert out12a["resumed_from"] == 8
d2 = tempfile.mkdtemp()
out12b = train_loop(cfg, steps_total=12, batch=8, seq_len=32, ckpt_dir=d2,
                    ckpt_every=100, mesh=make_host_mesh(8), log_every=0,
                    scfg=scfg8)
np.testing.assert_allclose(out12a["losses"], out12b["losses"][8:],
                           rtol=2e-4, atol=2e-4)
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


def test_hetero_runner_rebalances_straggler():
    # Forced host devices share one CPU thread pool, so a compute-based
    # straggler would contend its way back to equal wall times; the slow
    # group is instead an emulated async device (dispatch returns at once,
    # the result becomes ready after a per-row latency), which exercises
    # the split / overlap / E = max(T_a, T_b) / rebalance path for real.
    out = run_subprocess(SIM_DEVICE_SNIPPET + """
import jax, jax.numpy as jnp, numpy as np
from repro.core.hetero import DeviceGroup, HeterogeneousRunner
from jax.sharding import NamedSharding, PartitionSpec as P

devs = jax.devices()
ga = DeviceGroup("fast", devs[:4])
gb = DeviceGroup("slow", devs[4:], work_multiplier=4)

def builder(group):
    mesh = group.mesh()
    mult = group.work_multiplier
    per_row_s = 0.004 * mult / len(group.devices)
    def fn(batch):
        x = batch["x"]
        sh = NamedSharding(mesh, P("data"))
        y = jax.jit(lambda v: v.sum(), in_shardings=sh)(jax.device_put(x, sh))
        return SimReady(y, per_row_s * x.shape[0])
    return fn

runner = HeterogeneousRunner(builder, ga, gb, fraction=0.5, clock=SIM_CLOCK)
batch = {"x": np.random.default_rng(0).standard_normal((64, 256)).astype(np.float32)}
runner.step(batch)  # compile warmup both
runner.step(batch)
for _ in range(12):
    rec = runner.step(batch)
# group B is ~4x slower per row: the tuned fraction should give A much more
assert runner.fraction > 0.6, runner.fraction
first, last = runner.history[2], runner.history[-1]
assert last["t_step"] < first["t_step"], (first, last)
print("HETERO_OK", runner.fraction, first["t_step"], last["t_step"])
""")
    assert "HETERO_OK" in out


def test_param_specs_tolerate_overlapping_axis_roles():
    # fsdp over the same mesh axis as TP: the axis may shard only one dim
    # of a leaf, never appear twice in its PartitionSpec
    out = run_subprocess("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.launch.mesh import make_mesh
from repro.dist.sharding import ShardingConfig, param_specs
mesh = make_mesh((2, 4), ("data", "model"))
scfg = ShardingConfig(data_axes=("data",), model_axes=("model",),
                      fsdp_axes=("model",))
shapes = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32),
          "b": jax.ShapeDtypeStruct((3,), jnp.float32)}
specs = param_specs(shapes, mesh, scfg)
w = jax.device_put(jnp.zeros((8, 16)), NamedSharding(mesh, specs["w"]))
b = jax.device_put(jnp.zeros((3,)), NamedSharding(mesh, specs["b"]))
print("OVERLAP_OK", specs)
""")
    assert "OVERLAP_OK" in out


def test_compressed_allreduce_matches_mean():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.dist.compression import compressed_allreduce_mean
mesh = make_mesh((8,), ("data",))
x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)), jnp.float32)
with jax.set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    # each shard holds one row; all-reduce-mean over rows
    got = jax.jit(lambda x: compressed_allreduce_mean(
        x, mesh, "data", scheme="int8"))(xs)
want = jnp.broadcast_to(x.mean(axis=0), x.shape)
err = float(jnp.abs(got - want).max())
assert err < float(jnp.abs(x).max()) / 100, err
print("COMPRESS_AR_OK", err)
""")
    assert "COMPRESS_AR_OK" in out
