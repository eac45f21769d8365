"""HLO collective parser + launch spec construction tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.hlo import collective_bytes, _shape_bytes
from repro.configs import SHAPES, get_config
from repro.core import prompting
from repro.core.variant_space import MODULES


def test_shape_bytes():
    assert _shape_bytes("f32[16,512]{1,0}") == 16 * 512 * 4
    assert _shape_bytes("bf16[8,128]") == 8 * 128 * 2
    assert _shape_bytes("(f32[4,4]{1,0}, s32[2])") == 64 + 8
    assert _shape_bytes("pred[10]") == 10


def test_collective_parse():
    hlo = """
  %ar = f32[16,512]{1,0} all-reduce(f32[16,512]{1,0} %x), replica_groups={}
  %ag.1 = bf16[32,128]{1,0} all-gather(bf16[16,128]{1,0} %y), dimensions={0}
  %cp = f32[8]{0} collective-permute(f32[8]{0} %z)
  %ar2 = f32[4]{0} all-reduce-start(f32[4]{0} %w)
  %ar2d = f32[4]{0} all-reduce-done(f32[4]{0} %ar2)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"]["count"] == 2           # ar + ar2-start
    assert out["all-reduce"]["bytes"] == 16 * 512 * 4 + 16
    assert out["all-gather"]["bytes"] == 32 * 128 * 2
    assert out["collective-permute"]["count"] == 1
    assert out["total_bytes"] > 0


def test_real_hlo_collectives_detected():
    """A psum under jit on a fake 2-device mesh must show in the parser."""
    import subprocess, sys, os
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.dist.hlo import collective_bytes
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2,), ("x",))
def f(a):
    return jax.lax.psum(a, "x")
fn = jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P())
lowered = jax.jit(fn).lower(jax.ShapeDtypeStruct((8, 128), jnp.float32))
c = lowered.compile()
out = collective_bytes(c.as_text())
assert out["total_bytes"] > 0, out
print("OK", out["total_bytes"])
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=src)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_all_cells_enumerated():
    from repro.configs import dryrun_cells, ASSIGNED_ARCHS
    cells = dryrun_cells()
    assert len(cells) == 34                      # 40 - 6 long_500k skips
    archs = {c[0] for c in cells}
    assert archs == set(ASSIGNED_ARCHS)
    # sub-quadratic archs have long_500k, others don't
    long_archs = {a for a, s in cells if s == "long_500k"}
    assert long_archs == {"rwkv6-1.6b", "jamba-v0.1-52b",
                          "h2o-danube-1.8b", "gemma2-27b"}


def test_policy_vocab_covers_grammar():
    cfg = get_config("crinn-policy-100m")
    assert cfg.padded_vocab >= prompting.VOCAB_SIZE
    # every knob token fits in the vocab
    for module, knobs in MODULES.items():
        for pos, (name, choices) in enumerate(knobs):
            for c in range(len(choices)):
                t = prompting.knob_token(module, name, c)
                assert 0 <= t < prompting.VOCAB_SIZE


def test_all_cell_shardings_construct():
    """Construct every cell's input/param/cache shardings on the real
    512-device grid (no compile — catches divisibility bugs in seconds)."""
    import subprocess, sys, os
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = """
import jax
from repro.configs import SHAPES, dryrun_cells, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import decode_specs, prefill_specs, train_specs
from repro.dist.sharding import param_shardings, zero_shardings
from repro.models import model as model_lib

for mp in (False, True):
    mesh = make_production_mesh(multi_pod=mp)
    for arch, shape_name in dryrun_cells():
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        pshape = jax.eval_shape(lambda c=cfg: model_lib.init_params(
            jax.random.PRNGKey(0), c))
        ps = param_shardings(pshape, mesh)
        zs = zero_shardings(ps, pshape, mesh)
        if shape.kind == "train":
            train_specs(cfg, shape, mesh)
        elif shape.kind == "prefill":
            prefill_specs(cfg, shape, mesh)
        else:
            decode_specs(cfg, shape, mesh)
print("OK all", len(dryrun_cells()), "cells x 2 meshes")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=512",
               PYTHONPATH=src)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK all 34" in r.stdout
