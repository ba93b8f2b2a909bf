"""Training driver: data pipeline -> jitted train_step -> checkpoint/restart.

Works at every scale knob: the e2e example trains a ~100M model on this
container's CPU devices; the same driver with ``--dryrun-mesh`` lowers
against the production mesh.  Fault tolerance: checkpoints every
``ckpt_every`` steps (async, atomic), auto-resumes from the latest
complete checkpoint, and the data pipeline regenerates its stream from the
step counter (bitwise-identical restart, tested).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
        --steps 50 --batch 8 --seq-len 128
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from .. import configs
from ..ckpt.manager import CheckpointManager
from ..data.pipeline import DataConfig, SyntheticPipeline
from ..dist.api import use_rules
from ..dist.sharding import ShardingConfig
from ..models import build_model
from ..obs import get_logger
from ..optim.adamw import AdamWConfig, init_opt_state
from ..optim.schedule import warmup_cosine
from . import shapes, steps
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh

log = get_logger("repro.train")


def make_data_cfg(cfg, batch: int, seq_len: int, seed: int = 0) -> DataConfig:
    return DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len, global_batch=batch,
        seed=seed, frontend=cfg.frontend, d_model=cfg.d_model,
        n_patches=cfg.n_patches, decoder_len=cfg.decoder_len)


def train_loop(cfg, *, steps_total: int, batch: int, seq_len: int,
               ckpt_dir: str | Path | None = None, ckpt_every: int = 50,
               scfg: ShardingConfig | None = None,
               opt_cfg: AdamWConfig | None = None,
               mesh=None, log_every: int = 10, seed: int = 0,
               fail_at_step: int | None = None) -> dict:
    """Returns {"losses": [...], "resumed_from": step|None, ...}."""
    mesh = mesh or make_host_mesh()
    scfg = scfg or ShardingConfig(
        data_axes=mesh.axis_names[:1], model_axes=(), fsdp_axes=(),
        microbatches=1, remat=False)
    opt_cfg = opt_cfg or AdamWConfig(
        learning_rate=warmup_cosine(3e-4, 20, steps_total))
    model = build_model(cfg)
    data = SyntheticPipeline(make_data_cfg(cfg, batch, seq_len, seed))
    cell = shapes.ShapeCell("custom", "train", seq_len, batch)
    batch_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), data.batch_at(0))

    with jax.set_mesh(mesh):
        bundle = steps.make_train_step(cfg, scfg, mesh, opt_cfg, batch_shapes)
        step_fn = bundle.jit()

        mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        start_step = 0
        resumed_from = None
        restored = False
        if mgr and mgr.latest_step() is not None:
            try:
                with use_rules(bundle.rules):
                    start_step, state, extra = mgr.restore(
                        shardings=bundle.in_shardings[0])
                resumed_from = start_step
                restored = True
            except Exception as e:  # noqa: BLE001 — incompatible checkpoint
                log.warning(f"WARNING: checkpoint in {ckpt_dir} is "
                            f"incompatible with this model "
                            f"({type(e).__name__}); starting fresh",
                            ckpt_dir=str(ckpt_dir), error=type(e).__name__)
        if not restored:
            with use_rules(bundle.rules):
                params = jax.jit(
                    model.init,
                    out_shardings=bundle.in_shardings[0]["params"],
                )(jax.random.PRNGKey(seed))
                opt = jax.jit(
                    lambda p: init_opt_state(p, opt_cfg),
                    out_shardings=bundle.in_shardings[0]["opt"],
                )(params)
            state = {"params": params, "opt": opt,
                     "step": jax.numpy.zeros((), jax.numpy.int32)}
            if scfg.grad_compression != "none":
                from ..dist.compression import init_error_state
                state["err"] = jax.jit(
                    init_error_state,
                    out_shardings=bundle.in_shardings[0]["params"],
                )(params)

        losses: list[float] = []
        t0 = time.time()
        try:
            with use_rules(bundle.rules):
                for step, host_batch in data.iterate(start_step):
                    if step >= steps_total:
                        break
                    if fail_at_step is not None and step == fail_at_step:
                        raise RuntimeError(
                            f"injected failure at step {step}")
                    dev_batch = jax.tree.map(
                        lambda a, s: jax.device_put(a, s), host_batch,
                        bundle.in_shardings[1])
                    state, metrics = step_fn(state, dev_batch)
                    loss = float(metrics["loss"])
                    losses.append(loss)
                    if log_every and step % log_every == 0:
                        dt = time.time() - t0
                        log.info(f"step {step:5d}  loss {loss:7.4f}  "
                                 f"gnorm {float(metrics['gnorm']):7.3f}  "
                                 f"{dt:6.1f}s",
                                 step=step, loss=loss,
                                 gnorm=float(metrics["gnorm"]))
                    if mgr and ckpt_every and (step + 1) % ckpt_every == 0:
                        mgr.save(step + 1, state, extra={"loss": loss})
        except BaseException:
            # flush in-flight async saves so a supervised restart
            # (dist.fault.run_with_restarts) sees every completed
            # checkpoint — otherwise resume races the writer thread
            if mgr:
                mgr.wait()
            raise
        if mgr:
            mgr.save(steps_total, state, extra={"final": True})
            mgr.wait()
    return {"losses": losses, "resumed_from": resumed_from,
            "final_loss": losses[-1] if losses else None, "state": state}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b", choices=configs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    out = train_loop(cfg, steps_total=args.steps, batch=args.batch,
                     seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every, seed=args.seed)
    log.info(f"final loss: {out['final_loss']:.4f} "
             f"(first: {out['losses'][0]:.4f})",
             final_loss=out["final_loss"], first_loss=out["losses"][0])


if __name__ == "__main__":
    main()
