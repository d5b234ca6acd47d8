"""Production training launcher: ``python -m repro.launch.train --arch <id>``.

Single-host it runs on the local device(s); on a pod slice each host runs
this same entrypoint (jax.distributed-style) with its host index -- the
data pipeline shards by host, the mesh shards by device.  For this
container, --devices N forces N virtual host devices (set before jax
import, which is why it's parsed from argv manually below).
"""
import os
import sys

if "--devices" in sys.argv:                       # pre-jax-import device count
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n}")

import argparse
import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_arch, reduced
from ..core.dispatch import DEFAULT_DISPATCHER
from ..core.intensity import KernelTraits
from ..data.pipeline import TokenPipeline
from ..models import lm
from ..obs.log import LOG
from ..optim.adamw import AdamW, cosine_schedule
from ..runtime.train_loop import (StragglerWatchdog, TrainLoopConfig, run)
from ..sharding import rules
from . import steps as steps_mod
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1",
                    help="data x model, e.g. 2x4 (requires --devices 8)")
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-compress", default=None,
                    choices=(None, "bf16", "int8"))
    args = ap.parse_args()
    LOG.configure(level="info")   # launcher mains narrate by default
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    d_mesh, m_mesh = map(int, args.mesh.split("x"))
    mesh = jax.make_mesh((d_mesh, m_mesh), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    # dispatch layer: a train step is ~6*P flops/token against ~16*P bytes
    # of params+grads+optimizer state -- compute-bound at any real batch,
    # the mirror image of the decode path serve.py classifies.
    tokens = args.batch * args.seq
    traits = KernelTraits(f"train_step@{cfg.name}",
                          6.0 * cfg.param_count() * tokens,
                          16.0 * cfg.param_count())
    LOG.info("advisor", arch=cfg.name,
             advice=DEFAULT_DISPATCHER.advise_traits(traits))

    opt = AdamW(lr=cosine_schedule(args.lr, 10, args.steps))
    pipe = TokenPipeline(cfg, global_batch=args.batch, seq=args.seq)
    step = steps_mod.make_train_step(cfg, opt, dtype=jnp.float32,
                                     grad_compress=args.grad_compress)

    def init_state():
        params = lm.init_params(cfg, jax.random.key(0))
        ps = rules.to_shardings(mesh, rules.param_pspecs(params, mesh))
        params = jax.device_put(params, ps)
        return params, opt.init(params)

    jit_step = jax.jit(step)
    with jax.set_mesh(mesh):
        loop = TrainLoopConfig(
            total_steps=args.steps, ckpt_every=max(args.steps // 2, 1),
            ckpt_dir=args.ckpt_dir or f"ckpts/{cfg.name}",
            log_every=max(args.steps // 10, 1))
        _, _, metrics = run(loop, init_state=init_state, step_fn=jit_step,
                            batch_fn=pipe.batch,
                            watchdog=StragglerWatchdog())
    print(f"done: loss={float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
