"""Multi-device tests (subprocess with 8 fake CPU devices — the main test
process must keep seeing exactly 1 device, DESIGN.md §6)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd="/tmp",
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_device_isolation():
    """This process sees exactly the device count IT was launched with
    (1 by default; CI runs the fast split with 2 for the hetero offload
    path) — a subprocess's XLA_FLAGS never leak back; subprocesses see 8."""
    import re
    import jax
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    assert jax.device_count() == (int(m.group(1)) if m else 1)
    out = _run("import jax; print(jax.device_count())")
    assert out.strip() == "8"


@pytest.mark.slow
def test_distributed_topk_and_decode_exact():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,4), ("data","model"))
from repro.distributed.topk import distributed_relevancy_topk, distributed_sparse_decode
from repro.kernels import ref
rng = np.random.default_rng(0)
B,Hq,dk,S,k = 2,4,32,256,16
q = jnp.asarray(rng.standard_normal((B,Hq,dk)), jnp.float32)
keys = jnp.asarray(rng.standard_normal((B,S,dk)), jnp.float32)
w = jnp.abs(jnp.asarray(rng.standard_normal((B,Hq)), jnp.float32))
v1,i1 = distributed_relevancy_topk(q, keys, w, k, mesh, "model", block=64)
v2,i2 = ref.relevancy_topk(q, keys, w, k)
assert np.allclose(np.asarray(v1), np.asarray(v2), atol=1e-5)
v3,_ = distributed_relevancy_topk(q, keys, w, k, mesh, ("data","model"), block=32)
assert np.allclose(np.asarray(v3), np.asarray(v2), atol=1e-5)
KV,G,dh,ps = 2,2,32,8
q2 = jnp.asarray(rng.standard_normal((B,KV*G,dh)), jnp.float32)
kc = jnp.asarray(rng.standard_normal((B,S,KV,dh)), jnp.float32)
vc = jnp.asarray(rng.standard_normal((B,S,KV,dh)), jnp.float32)
pages = jnp.asarray(np.stack([rng.choice(S//ps,8,replace=False) for _ in range(B)]), jnp.int32)
length = jnp.asarray([S, S//2], jnp.int32)
o1 = distributed_sparse_decode(q2, kc, vc, pages, length, mesh, "model", page_size=ps)
o2,_ = ref.paged_decode_attention(q2, kc, vc, pages, ps, length)
assert np.abs(np.asarray(o1)-np.asarray(o2)).max() < 1e-4
# batch sharded over data (decode_32k layout)
o3 = distributed_sparse_decode(q2, kc, vc, pages, length, mesh, "model", page_size=ps, batch_axis="data")
assert np.abs(np.asarray(o3)-np.asarray(o2)).max() < 1e-4
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """One train step on a (2,4) mesh == the same step on 1 device."""
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.configs import get_arch
from repro.models import init_params
from repro.train import make_train_step, init_opt_state, TrainConfig
from repro.distributed import sharding as sh
from repro.data import TokenStream

cfg = get_arch("llama3.2-1b").smoke()
params = init_params(cfg, jax.random.PRNGKey(0), tp=4)
b = {k: jnp.asarray(v) for k, v in TokenStream(cfg.vocab_size, 32, 4, seed=0).next_batch().items()}
tc = TrainConfig(tp=4)
step = make_train_step(cfg, tc)

mesh = make_mesh((2,4), ("data","model"))
specs = sh.param_specs(params, cfg, mesh)
shards = sh.make_shardings(specs, mesh)
params_sh = jax.device_put(params, shards)
opt_sh = init_opt_state(params_sh)
opt_ref = init_opt_state(params)
# run the sharded step FIRST: device_put may alias replicated leaves, and
# the single-device step donates (deletes) its inputs.
with jax.set_mesh(mesh):
    p2, _, st2 = jax.jit(step)(params_sh, opt_sh, b)
p_ref, _, st_ref = step(params, opt_ref, b)
assert abs(float(st_ref["loss"]) - float(st2["loss"])) < 2e-3, (st_ref["loss"], st2["loss"])
for a, c in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(c, np.float32), rtol=3e-2, atol=3e-3)
print("OK")
""")
    assert "OK" in out


def test_gpipe_pipeline_parallel():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.distributed.pipeline_parallel import gpipe_forward, bubble_fraction
mesh = make_mesh((4,), ("pod",))
n_stages, M, mb, d = 4, 8, 2, 16
ws = jnp.asarray(np.random.default_rng(0).standard_normal((n_stages, d, d)) / 4, jnp.float32)
xs = jnp.asarray(np.random.default_rng(1).standard_normal((M, mb, d)), jnp.float32)
def group(w, x): return jnp.tanh(x @ w)
fn = gpipe_forward(group, mesh, "pod")
out = fn(ws, xs)
ref = xs
for s in range(n_stages):
    ref = jnp.tanh(ref @ ws[s])
assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 1e-5
assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
print("OK")
""")
    assert "OK" in out


def test_checkpoint_elastic_reshard():
    """Checkpoint written from an 8-device mesh restores onto 4 devices."""
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.distributed import checkpoint as ckpt
mesh8 = make_mesh((2,4), ("data","model"))
w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                   NamedSharding(mesh8, P("data","model")))
d = tempfile.mkdtemp()
ckpt.save(d, 1, {"w": w})
mesh4 = make_mesh((4,), ("model",))
tgt = NamedSharding(mesh4, P(None, "model"))
back = ckpt.restore(d, 1, {"w": jnp.zeros((8,8))}, shardings={"w": tgt})
assert back["w"].sharding == tgt
np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(w))
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_cell_executes():
    """The real dry-run entrypoint (512 placeholder devices) compiles a cell."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "llama3.2-1b",
         "--shape", "decode_32k", "--force"],
        env=env, cwd=os.path.join(SRC, "..") , capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "1 ok, 0 failed" in out.stdout
    rec = json.load(open(os.path.join(
        SRC, "..", "experiments", "dryrun",
        "llama3.2-1b__decode_32k__16x16__baseline.json")))
    assert rec["ok"] and rec["roofline"]["bottleneck"] in (
        "compute", "memory", "collective")


@pytest.mark.slow
def test_cached_index_decode_matches_stateless():
    """§Perf iteration 3 correctness: the incremental index cache path
    (prepare-once) must produce the same attention output as the stateless
    distributed path that re-projects the whole context every step."""
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.core.methods import dsa
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,4), ("data","model"))
cfg = get_arch("llama3.2-1b").smoke()
mem = cfg.memory.replace(top_k=32, index_heads=4, index_dim=32)
page = 8
rng = np.random.default_rng(0)
B, S = 2, 64
KV, hd, HP = cfg.n_kv_heads, cfg.hd, cfg.padded_heads(4)
kc = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
vc = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
q = jnp.asarray(rng.standard_normal((B, 1, HP, hd)), jnp.float32)
sp = jax.tree.map(lambda a: a[0], dsa.dsa_init(jax.random.PRNGKey(1), cfg, mem))
length = jnp.asarray(S, jnp.int32)
k_new = kc[:, S-1][:, None]  # the key written this step

stateless = dsa.make_sparse_fn_distributed(cfg, mem, mesh, axis="model", tp=4, page=page)
out_d = stateless(q, kc, vc, length, sp)

# prebuild the index cache from all but the newest key
k_idx = (kc.reshape(B, S, -1) @ sp["wk_idx"]).astype(jnp.float32)
k_idx = k_idx.at[:, S-1].set(0.0)
kidx_sum = k_idx.reshape(B, S // page, page, -1).sum(axis=2)
cached = dsa.make_sparse_fn_cached(cfg, mem, mesh, axis="model", tp=4, page=page)
out_c, sp_new = cached(q, kc, vc, length, {"p": sp, "kidx_sum": kidx_sum}, k_new=k_new)

err = np.abs(np.asarray(out_c, np.float32) - np.asarray(out_d, np.float32)).max()
assert err < 1e-4, err
# the update landed in exactly the right page
full = (kc.reshape(B, S, -1) @ sp["wk_idx"]).astype(jnp.float32)
full_sum = full.reshape(B, S // page, page, -1).sum(axis=2)
assert np.abs(np.asarray(sp_new["kidx_sum"]) - np.asarray(full_sum)).max() < 1e-3
print("OK")
""")
    assert "OK" in out
