"""End-to-end training driver.

Example (CPU, reduced config)::

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --reduced \
        --steps 50 --global-batch 8 --seq 64 --strategy ca-das

On a real fleet the same entry point runs the full config against the
production mesh (``--mesh 16x16`` / ``--mesh 2x16x16``).
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import get_config
from repro.core import execution
from repro.core.asymmetric import AsymmetricMesh, DeviceClass, biglittle_classes
from repro.distributed import sharding as SH
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim.adamw import AdamWConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--strategy", default="ca-das",
                    choices=["sss", "sas", "ca-sas", "das", "ca-das", "none"])
    ap.add_argument("--heterogeneous", action="store_true",
                    help="simulate a big+little two-pod fleet for the scheduler")
    ap.add_argument("--mesh", default="host", choices=["host", "16x16", "2x16x16"])
    ap.add_argument("--class-sharded", default="auto", choices=["auto", "on", "off"],
                    help="per-class programs in one SPMD step (shard_map over "
                         "the pod axis); auto = on when the mesh has >1 class "
                         "and enough devices for a pod axis")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    asym = None
    if args.strategy != "none":
        classes = (
            biglittle_classes(chips_per_pod=1)
            if args.heterogeneous
            else [DeviceClass("pod0", chips_per_pod=1), DeviceClass("pod1", chips_per_pod=1)]
        )
        asym = AsymmetricMesh(classes, strategy=args.strategy, batch_tile=2)

    if args.mesh == "host":
        # The class-sharded step needs a pod axis: carve one out of the
        # host devices when the run wants it and the host has enough.
        want_pods = (
            args.class_sharded != "off"
            and asym is not None
            and len(asym.classes) > 1
            and jax.device_count() >= asym.n_pods
        )
        mesh = make_host_mesh(pod=asym.n_pods if want_pods else 0)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "2x16x16")
    SH.use_mesh_for_activations(mesh, seq_shard=False)

    # Class-routed execution: the asymmetric mesh's primary control tree
    # governs every matmul in the step; homogeneous runs get the default
    # single-class context (behavior-neutral without a tuning cache).
    exec_ctx = (
        asym.execution_context() if asym is not None else execution.default_context()
    )

    tcfg = TrainerConfig(
        steps=args.steps,
        global_batch=args.global_batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        n_micro=args.n_micro,
        class_sharded={"auto": None, "on": True, "off": False}[args.class_sharded],
    )
    trainer = Trainer(
        cfg,
        mesh,
        tcfg=tcfg,
        opt_cfg=AdamWConfig(lr=args.lr, total_steps=args.steps),
        asym=asym,
        exec_ctx=exec_ctx,
    )
    t0 = time.time()
    history = trainer.run()
    dt = time.time() - t0
    shard_classes = (
        [(p.pod, p.device_class, p.block_source, p.backend)
         for p in trainer.class_sharded_step.provenance]
        if trainer.class_sharded_step is not None
        else None
    )
    print(
        json.dumps(
            {
                "arch": cfg.name,
                "device_class": exec_ctx.device_class,
                "exec_backend": exec_ctx.backend(),
                "class_sharded": trainer.class_sharded_enabled(),
                "shard_classes": shard_classes,
                "steps": len(history),
                "first_loss": history[0]["loss"],
                "last_loss": history[-1]["loss"],
                "restarts": trainer.restarts,
                "wall_s": round(dt, 2),
                "chunk_sizes": trainer.asym.batch_layout(args.global_batch).sizes
                if trainer.asym
                else None,
            },
            indent=1,
        )
    )


if __name__ == "__main__":
    main()
