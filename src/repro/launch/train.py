"""End-to-end distributed GRPO training driver.

    PYTHONPATH=src python -m repro.launch.train --arch crinn-policy-100m \
        --steps 50 --debug-mesh 2x4       # CPU: 8 forced host devices
    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --production

On real hardware ``--production`` builds the 16x16 pod mesh; on this
container ``--debug-mesh`` forces host devices so the full pjit path
(sharded params, DP gradient reduction, shard_map MoE) executes for real
at reduced scale.  The data path is the deterministic PromptPipeline —
resume/elastic semantics are exercised by tests/test_dist_train.py.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="crinn-policy-100m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test reduction of the arch")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--debug-mesh", default=None,
                    help="DxM (e.g. 2x4): force host devices, CPU testing")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    if args.debug_mesh:
        d, m = (int(x) for x in args.debug_mesh.split("x"))
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={d * m}")

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.grpo import GRPOConfig
    from repro.data import PromptPipeline
    from repro.dist.sharding import param_shardings
    from repro.launch.mesh import make_debug_mesh, make_production_mesh
    from repro.models import model as model_lib
    from repro.models.runtime import Runtime
    from repro.runtime import Trainer, TrainerConfig

    cfg = get_config(args.arch, reduced=args.reduced)

    mesh = None
    if args.production:
        mesh = make_production_mesh()
    elif args.debug_mesh:
        d, m = (int(x) for x in args.debug_mesh.split("x"))
        mesh = make_debug_mesh(d, m)

    rt = Runtime(mesh=mesh, attn_chunk=min(512, args.seq),
                 logit_chunk=min(512, args.seq), remat="block")

    if mesh is not None:
        pshape = jax.eval_shape(
            lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
        pshard = param_shardings(pshape, mesh)
        with mesh:
            params = jax.jit(
                lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg),
                out_shardings=pshard)()
    else:
        params = model_lib.init_params(jax.random.PRNGKey(0), cfg)

    pipe = PromptPipeline(seq_len=args.seq, global_batch=args.global_batch)
    tcfg = TrainerConfig(total_steps=args.steps, warmup_steps=max(1, args.steps // 10),
                         ckpt_every=max(5, args.steps // 4),
                         ckpt_dir=args.ckpt_dir, log_every=5)
    trainer = Trainer(cfg, rt, params, tcfg=tcfg, gcfg=GRPOConfig())
    if args.resume and trainer.try_restore():
        print(f"resumed from step {trainer.step}")

    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        log = trainer.run(pipe.batch, verbose=True)
    losses = [r["loss"] for r in log]
    print(f"done: {len(log)} steps, loss {losses[0]:.3f} -> {losses[-1]:.3f}")


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
