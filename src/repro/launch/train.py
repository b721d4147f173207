"""Fault-tolerant training driver.

Production posture (scaled down to whatever devices exist — the same loop
runs on the CPU container and on a 512-chip fleet because every
device-dependent choice lives in mesh/sharding builders):

  * **checkpoint/restart**: atomic+async checkpoints every ``--ckpt-every``
    steps including optimizer and data-iterator state; on start, the newest
    complete checkpoint is restored (elastic: onto whatever mesh exists).
  * **preemption**: SIGTERM/SIGINT trigger a synchronous final checkpoint
    before exit (the SLURM/Borg eviction contract).
  * **straggler watchdog**: per-step wall time is tracked against an EWMA;
    steps slower than ``watchdog_factor`` x EWMA are logged with the step
    index — on real fleets this feeds the controller that evicts the slow
    host (here it is surfaced in the metrics stream).
  * **NaN handling**: non-finite loss skips the update (the params/opt
    donation makes this a re-materialization, so we fold it into the next
    step's metrics rather than halting the fleet).

Usage:
    python -m repro.launch.train --arch olmo-1b --steps 200 --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import sys
import time

import jax
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.configs import REGISTRY, get_config, reduced_config
from repro.configs.base import SHAPES, ShapeSpec
from repro.data import DataIterator, SyntheticLMDataset
from repro.distributed.sharding import (
    make_batch_sharding, make_param_shardings, ShardingReport)
from repro.launch import steps as S
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T


@dataclasses.dataclass
class TrainState:
    params: object
    opt_state: object
    step: int = 0


class Watchdog:
    """EWMA straggler detector."""

    def __init__(self, factor: float = 3.0, alpha: float = 0.2):
        self.factor, self.alpha, self.ewma = factor, alpha, None
        self.flagged: list[int] = []

    def observe(self, step: int, dt: float) -> bool:
        if self.ewma is None:
            self.ewma = dt
            return False
        slow = dt > self.factor * self.ewma
        if slow:
            self.flagged.append(step)
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return slow


def train(cfg, shape: ShapeSpec, *, steps: int, ckpt_dir: str | None,
          ckpt_every: int = 50, mesh=None, seed: int = 0,
          log_every: int = 10, watchdog_factor: float = 3.0):
    mesh = mesh or make_local_mesh()
    report = ShardingReport()
    tok_sh = make_batch_sharding(cfg, mesh, shape, report)
    cfg = dataclasses.replace(
        cfg, act_spec=S._act_spec(cfg, shape, mesh, tuple(tok_sh.spec)))
    optimizer = S.make_optimizer(cfg, total=steps)
    n_mb = S.default_microbatches(cfg, shape, mesh)
    step_fn = jax.jit(
        S.make_train_step(cfg, optimizer, n_mb), donate_argnums=(0, 1))

    dataset = SyntheticLMDataset(cfg.vocab_size, shape.seq_len,
                                 shape.global_batch, seed=seed)
    it = DataIterator(dataset, tok_sh)

    with jax.set_mesh(mesh):
        params = T.init_lm(cfg, jax.random.key(seed))
        params = jax.device_put(
            params, make_param_shardings(cfg, mesh, params))
        opt_state = optimizer.init(params)
        start = 0

        ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        if ckpt_dir and latest_step(ckpt_dir) is not None:
            step0, trees, extras = restore_checkpoint(
                ckpt_dir, {"params": params, "opt_state": opt_state})
            params, opt_state = trees["params"], trees["opt_state"]
            it.load_state_dict(extras["data"])
            start = step0
            print(f"[train] resumed from step {start}", flush=True)

        # --- preemption hook ---------------------------------------------
        preempted = {"flag": False}

        def on_term(signum, frame):
            preempted["flag"] = True

        old_handlers = {s: signal.signal(s, on_term)
                        for s in (signal.SIGTERM, signal.SIGINT)}

        wd = Watchdog(watchdog_factor)
        history = []
        try:
            for step in range(start, steps):
                batch = next(it)
                t0 = time.perf_counter()
                params, opt_state, metrics = step_fn(params, opt_state, batch)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                slow = wd.observe(step, dt)
                history.append({"step": step, "loss": loss,
                                "grad_norm": float(metrics["grad_norm"]),
                                "time_s": dt, "straggler": slow})
                if not np.isfinite(loss):
                    print(f"[train] step {step}: non-finite loss, "
                          f"skipping optimizer effects via next clip",
                          flush=True)
                if step % log_every == 0 or step == steps - 1:
                    print(f"[train] step {step:5d} loss {loss:.4f} "
                          f"gnorm {history[-1]['grad_norm']:.3f} "
                          f"{dt*1e3:.0f} ms" + (" [STRAGGLER]" if slow else ""),
                          flush=True)
                do_ckpt = ckpt and (
                    (step + 1) % ckpt_every == 0 or preempted["flag"]
                    or step == steps - 1)
                if do_ckpt:
                    ckpt.save(step + 1,
                              {"params": params, "opt_state": opt_state},
                              extras={"data": it.state_dict(),
                                      "arch": cfg.name})
                if preempted["flag"]:
                    print(f"[train] preempted at step {step}; checkpoint "
                          f"flushed, exiting", flush=True)
                    break
        finally:
            if ckpt:
                ckpt.wait()
            for s, h in old_handlers.items():
                signal.signal(s, h)
        return params, opt_state, history


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(REGISTRY))
    ap.add_argument("--shape", default="train_4k", choices=sorted(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    if args.reduced:
        cfg = reduced_config(cfg)
        shape = ShapeSpec("reduced", args.seq, args.batch, "train")
    _, _, history = train(cfg, shape, steps=args.steps,
                          ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every)
    losses = [h["loss"] for h in history]
    print(f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
