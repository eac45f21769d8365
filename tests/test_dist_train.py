"""Distributed-path tests: run in subprocesses with forced host device
counts so the pjit/shard_map code executes on a real (fake-)multi-device
mesh without polluting this process's single-device jax state."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """Loss on a 2x4 mesh must equal the unsharded loss (same params/batch)."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.models import model, Runtime
from repro.core.grpo import GRPOConfig, grpo_loss
from repro.dist.sharding import param_shardings
from repro.launch.specs import train_specs

cfg = dataclasses.replace(get_config('deepseek-moe-16b', reduced=True),
                          dtype='float32', vocab_size=256)
mesh = auto_mesh((2, 4), ('data', 'model'))
params = model.init_params(jax.random.PRNGKey(0), cfg)
B, S = 4, 32
batch = {
    'tokens': jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 256),
    'mask': jnp.ones((B, S), jnp.float32),
    'advantages': jnp.asarray([1., -1., 0.5, -0.5]),
    'old_logps': jnp.zeros((B, S)), 'ref_logps': jnp.zeros((B, S)),
}
gcfg = GRPOConfig()
rt0 = Runtime(mesh=None, attn_chunk=16, logit_chunk=16, remat='none',
              capacity_factor=8.0)
l0, _ = grpo_loss(params, batch, cfg, rt0, gcfg)

rt1 = Runtime(mesh=mesh, attn_chunk=16, logit_chunk=16, remat='none',
              capacity_factor=8.0)
pshard = param_shardings(jax.eval_shape(lambda: params), mesh)
with mesh:
    sharded_params = jax.device_put(params, pshard)
    l1, _ = jax.jit(lambda p, b: grpo_loss(p, b, cfg, rt1, gcfg))(
        sharded_params, batch)
print('single:', float(l0), 'sharded:', float(l1))
assert abs(float(l0) - float(l1)) < 5e-3, (float(l0), float(l1))
print('OK')
""")
    assert "OK" in out


def test_moe_shard_map_matches_local():
    """EP shard_map MoE == local dispatch (fp32, high capacity)."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.models import moe as moe_lib

cfg = dataclasses.replace(get_config('dbrx-132b', reduced=True),
                          dtype='float32')
mesh = auto_mesh((2, 4), ('data', 'model'))
p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)
out_local, aux_local = moe_lib.apply_moe(p, x, cfg, mesh=None,
                                         capacity_factor=8.0)
with mesh:
    out_ep, aux_ep = jax.jit(lambda p, x: moe_lib.apply_moe(
        p, x, cfg, mesh=mesh, dp_axes=('data',), capacity_factor=8.0))(p, x)
d = float(jnp.max(jnp.abs(out_local - out_ep)))
print('maxdiff', d)
assert d < 1e-4, d
print('OK')
""")
    assert "OK" in out


def test_train_driver_runs_distributed():
    out = _run("""
import sys
sys.argv = ['train', '--arch', 'crinn-policy-100m', '--reduced',
            '--steps', '4', '--seq', '64', '--global-batch', '4',
            '--debug-mesh', '2x4', '--ckpt-dir', '/tmp/test_dist_ckpt']
from repro.launch.train import main
main()
print('OK')
""")
    assert "OK" in out and "done: 4 steps" in out


def test_elastic_reshard_checkpoint():
    """Save on a 2x4 mesh, restore on 4x2 — mesh-agnostic checkpoints."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, tempfile, os
from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.models import model
from repro.dist.sharding import param_shardings
from repro.ckpt import save_checkpoint, load_checkpoint

cfg = get_config('stablelm-1.6b', reduced=True)
params = model.init_params(jax.random.PRNGKey(0), cfg)

mesh1 = auto_mesh((2, 4), ('data', 'model'))
sh1 = param_shardings(jax.eval_shape(lambda: params), mesh1)
p1 = jax.device_put(params, sh1)

with tempfile.TemporaryDirectory() as d:
    save_checkpoint(os.path.join(d, 'ck'), p1, step=3)
    mesh2 = auto_mesh((4, 2), ('data', 'model'))
    sh2 = param_shardings(jax.eval_shape(lambda: params), mesh2)
    tree, step, _ = load_checkpoint(os.path.join(d, 'ck'), params)
    p2 = jax.device_put(tree, sh2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert jnp.allclose(a.astype(jnp.float32), b.astype(jnp.float32))
print('OK')
""")
    assert "OK" in out


def test_seq_sharded_decode_correct():
    """KV cache sharded over seq (the long-context layout) must give the
    same decode logits as unsharded."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp
from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.models import model, Runtime
from repro.dist.sharding import param_shardings, cache_shardings

cfg = dataclasses.replace(get_config('glm4-9b', reduced=True), dtype='float32')
rt0 = Runtime(mesh=None, attn_chunk=16, logit_chunk=16, remat='none')
params = model.init_params(jax.random.PRNGKey(0), cfg)
B, S = 2, 32
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
caches = model.init_cache(cfg, B, S + 8)
_, caches, clen = model.prefill(params, {'tokens': toks[:, :-1]}, cfg, rt0, caches)
want, _, _ = model.decode_step(params, {'tokens': toks[:, -1:]}, cfg, rt0, caches, clen)

mesh = auto_mesh((2, 4), ('data', 'model'))
rt1 = Runtime(mesh=mesh, attn_chunk=16, logit_chunk=16, remat='none')
pshard = param_shardings(jax.eval_shape(lambda: params), mesh)
cshard = cache_shardings(jax.eval_shape(lambda: caches), mesh)
with mesh:
    sp = jax.device_put(params, pshard)
    sc = jax.device_put(caches, cshard)
    got, _, _ = jax.jit(lambda p, b, c, l: model.decode_step(p, b, cfg, rt1, c, l))(
        sp, {'tokens': toks[:, -1:]}, sc, clen)
d = float(jnp.max(jnp.abs(got - want)))
print('maxdiff', d)
assert d < 1e-3, d
print('OK')
""")
    assert "OK" in out


def test_flash_decode_combine_matches_unsharded():
    """seq_shard_decode (shard_map partial-softmax combine) == plain decode."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp
from repro.launch.mesh import auto_mesh
from repro.configs import get_config
from repro.models import model, Runtime
from repro.dist.sharding import param_shardings, cache_shardings

cfg = dataclasses.replace(get_config('glm4-9b', reduced=True), dtype='float32')
rt0 = Runtime(mesh=None, attn_chunk=16, logit_chunk=16, remat='none')
params = model.init_params(jax.random.PRNGKey(0), cfg)
B, S = 2, 32
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
caches = model.init_cache(cfg, B, S + 8)
_, caches, clen = model.prefill(params, {'tokens': toks[:, :-1]}, cfg, rt0, caches)
want, _, _ = model.decode_step(params, {'tokens': toks[:, -1:]}, cfg, rt0, caches, clen)

mesh = auto_mesh((2, 4), ('data', 'model'))
rt1 = Runtime(mesh=mesh, attn_chunk=16, logit_chunk=16, remat='none',
              seq_shard_decode=True)
pshard = param_shardings(jax.eval_shape(lambda: params), mesh)
cshard = cache_shardings(jax.eval_shape(lambda: caches), mesh)
with mesh:
    sp = jax.device_put(params, pshard)
    sc = jax.device_put(caches, cshard)
    got, _, _ = jax.jit(lambda p, b, c, l: model.decode_step(p, b, cfg, rt1, c, l))(
        sp, {'tokens': toks[:, -1:]}, sc, clen)
d = float(jnp.max(jnp.abs(got - want)))
print('maxdiff', d)
assert d < 1e-3, d
print('OK')
""")
    assert "OK" in out
