"""Multi-device semantics via subprocess (forced host devices): compressed
cross-pod KV transfer, compressed gradient sync, mini dry-run.

These must run in fresh processes because jax locks the device count at
first init.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).parent.parent
SRC = str(ROOT / "src")


def _run(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_kv_transfer_roundtrip_and_compression():
    """ppermute KV migration: pods swap caches; int8 payload ~matches bf16."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.distribution.kv_transfer import make_kv_transfer, transfer_wire_bytes

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rng = np.random.default_rng(0)
cache = {"layer0": {"k": jnp.asarray(rng.standard_normal((4, 32, 2, 64)), jnp.bfloat16),
                    "v": jnp.asarray(rng.standard_normal((4, 32, 2, 64)), jnp.bfloat16)}}
with mesh:
    fn16, specs = make_kv_transfer(mesh, cache, bits=16)
    fn8, _ = make_kv_transfer(mesh, cache, bits=8)
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), cache, specs,
        is_leaf=lambda x: hasattr(x, "shape"))
    out16 = fn16(sharded)
    out8 = fn8(sharded)

# pod axis is the leading batch factor: batch 4 over pod=2,data=2 -> batch
# sharded (pod,data). ppermute swaps pod shards: rows [0,1] <-> [2,3].
k = np.asarray(cache["layer0"]["k"], np.float32)
got16 = np.asarray(out16["layer0"]["k"], np.float32)
expected = np.concatenate([k[2:], k[:2]], axis=0)
assert np.allclose(got16, expected, atol=1e-2), "bf16 permute mismatch"
got8 = np.asarray(out8["layer0"]["k"], np.float32)
err = np.abs(got8 - expected).max()
assert err < 0.06, f"int8 transfer error too large: {err}"
w16 = transfer_wire_bytes(cache, 16); w8 = transfer_wire_bytes(cache, 8); w4 = transfer_wire_bytes(cache, 4)
assert w8 < 0.6 * w16 and w4 < 0.35 * w16, (w16, w8, w4)
print("ok", w16, w8, w4)
""")
    assert "ok" in out


def test_collective_bytes_drop_with_compression():
    """The roofline's collective term shrinks ~16/bits for the transfer."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.launch.mesh import make_mesh
from repro.distribution.kv_transfer import make_kv_transfer
from repro.launch.hlo_cost import analyze_hlo_text

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cache = {"k": jnp.zeros((4, 256, 2, 64), jnp.bfloat16)}
with mesh:
    res = {}
    for bits in (16, 8, 4):
        fn, specs = make_kv_transfer(mesh, cache, bits=bits)
        comp = fn.lower(jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype), cache, specs)).compile()
        res[bits] = analyze_hlo_text(comp.as_text()).coll_bytes
assert res[8] < 0.62 * res[16], res
assert res[4] < 0.40 * res[16], res
print("ok", res)
""")
    assert "ok" in out


def test_cross_pod_grad_sync():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.distribution.grad_compress import make_cross_pod_grad_sync

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rng = np.random.default_rng(1)
g = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
# different grads per pod: shard over pod on axis 0
specs = {"w": P("pod", None)}
with mesh:
    fn = make_cross_pod_grad_sync(mesh, {"w": g}, specs, bits=8)
    gs = jax.device_put(g, NamedSharding(mesh, specs["w"]))
    out = fn({"w": gs})["w"]
got = np.asarray(out)
# every pod's shard becomes the average of the two pod shards
gn = np.asarray(g)
avg = (gn[:4] + gn[4:]) / 2
assert np.abs(got[:4] - avg).max() < 0.02, np.abs(got[:4] - avg).max()
assert np.abs(got[4:] - avg).max() < 0.02
print("ok")
""")
    assert "ok" in out


@pytest.mark.slow
def test_dryrun_tiny_both_meshes():
    """The dry-run machinery end-to-end on the 512-device production meshes
    (tiny arch so it compiles in seconds)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "tiny-lm",
         "--shape", "train_4k,decode_32k", "--mesh", "both"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=str(ROOT))
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert r.stdout.count("[ok]") == 4


@pytest.mark.parametrize("paged", [False, True])
def test_pd_cluster_one_worker_per_device(paged):
    """A 2x2 PD ClusterRuntime with one worker per (forced) device: each
    worker's params, arena and page pools stay on its own device, the
    hand-off crosses devices, and greedy tokens match a 1x1 run on the
    default device.  The paged variant injects the wire-restored KV, so
    the injection path writes across devices too."""
    out = _run(f"""
import jax, numpy as np
import repro.serving.cluster as C
from repro.configs import get_config
from repro.core.profiles import Profile
from repro.core.strategy import StrategyConfig
from repro.models import init_params
from repro.serving.scheduler import SchedulerConfig
from repro.serving.workers import RuntimeConfig

cfg = get_config("tiny-lm")
params, _ = init_params(cfg, seed=3)
C.get_reference_model = lambda: (cfg, params)   # seeded weights, no training
profile = Profile(StrategyConfig(quantizer="uniform", key_bits=8,
                                 value_bits=8, granularity="per_channel"),
                  cr=2.0, s_enc=5e8, s_dec=5e8)
devs = jax.devices()
assert len(devs) == 4

def serve(n, pdev, ddev):
    rt = C.ClusterRuntime(
        static_profile=profile, router="round_robin", n_prefill=n,
        n_decode=n, prefill_devices=pdev, decode_devices=ddev,
        config=RuntimeConfig(seq=32, decode_tokens=6, mode="pd",
                             paged={paged}, page_size=8,
                             pd_inject_restored={paged}),
        scheduler=SchedulerConfig(max_slots=4, max_prefills_per_step=2))
    for i in range(8):
        rt.submit(("qalike", "codelike", "mathlike", "summlike")[i % 4],
                  prompt_seed=100 + i)
    rt.run()
    assert len(rt.completed) == 8
    return rt, {{r.rid: r.tokens.tolist() for r in rt.completed}}

_, one = serve(1, None, None)
rt, two = serve(2, devs[:2], devs[2:])
assert two == one, (one, two)
assert {{r.route for r in rt.completed}} == {{"p0->d0", "p0->d1", "p1->d0",
                                              "p1->d1"}}
on = lambda tree, d: all(x.devices() == {{d}}
                         for x in jax.tree_util.tree_leaves(tree))
for w, d in zip(rt.prefill_workers, devs[:2]):
    assert on(w.params, d), w.name
for w, d in zip(rt.decode_workers, devs[2:]):
    assert on((w.params,) + w.layout.arrays, d), w.name
    assert (len(w.layout.arrays) == 3) == {paged}   # + the quant pools
print("ok")
""", devices=4)
    assert "ok" in out


def test_pd_identity_handoff_is_cast_on_each_decode_device():
    """The identity hand-off restored as fp16 and cast on the device lands
    in each decode worker's arena on that worker's device, and a 2x2
    cluster serves the 1x1 cluster's greedy tokens."""
    out = _run("""
import jax, numpy as np
import repro.serving.cluster as C
from repro.configs import get_config
from repro.core.profiles import IDENTITY_PROFILE
from repro.core.quality import _scatter_kv
from repro.models import init_params
from repro.serving.scheduler import SchedulerConfig
from repro.serving.workers import RuntimeConfig

cfg = get_config("tiny-lm")
params, _ = init_params(cfg, seed=3)
C.get_reference_model = lambda: (cfg, params)   # seeded weights, no training
devs = jax.devices()

def serve(n, pdev, ddev):
    rt = C.ClusterRuntime(
        static_profile=IDENTITY_PROFILE, router="round_robin", n_prefill=n,
        n_decode=n, prefill_devices=pdev, decode_devices=ddev,
        config=RuntimeConfig(seq=32, decode_tokens=6, mode="pd",
                             pd_inject_restored=True),
        scheduler=SchedulerConfig(max_slots=4, max_prefills_per_step=2))
    for i in range(8):
        rt.submit(("qalike", "codelike", "mathlike", "summlike")[i % 4],
                  prompt_seed=100 + i)
    rt.run()
    assert len(rt.completed) == 8
    return rt, {r.rid: r.tokens.tolist() for r in rt.completed}

_, one = serve(1, None, None)
rt, two = serve(2, devs[:2], devs[2:])
assert two == one, (one, two)
assert _scatter_kv._cache_size() >= 1
for w, d in zip(rt.decode_workers, devs[2:]):
    assert all(x.devices() == {d}
               for x in jax.tree_util.tree_leaves(w.layout.cache)), w.name
print("ok")
""", devices=4)
    assert "ok" in out
