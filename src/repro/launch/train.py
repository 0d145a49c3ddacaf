"""End-to-end training driver.

Runs a real (CPU-scale or pod-scale) training loop with the full SAGE
substrate engaged: data pipeline from the object store, streaming /
window / collective checkpointing with transactional commits, preemption
handling (SIGTERM -> flush -> exit), HA monitoring, ADDB telemetry, and
optional gradient compression.  Restart resumes from the latest
checkpoint (mesh-elastic).

Usage (CPU example — ~100M-class model a few hundred steps):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-32b \
        --smoke --steps 50 --root /tmp/sage_run
"""
from __future__ import annotations

import argparse
import signal
import sys
import time
from functools import partial
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.base import RunConfig
from repro.core import Clovis, HAMonitor
from repro.core.addb import span
from repro.data.pipeline import TokenLoader, build_synthetic_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, mesh_context
from repro.launch.steps import make_train_step
from repro.models import model as mdl
from repro.models.common import axis_rules
from repro.distributed.sharding import (default_axis_rules, make_batch_specs,
                                        make_param_specs, named)
from repro.optim import (AdamWState, compress_grads, init_error_feedback,
                         init_opt_state)


class Trainer:
    def __init__(self, cfg, run: RunConfig, root: Path, *,
                 data_mesh: int = 1, model_mesh: int = 1,
                 param_dtype=jnp.float32, clovis: Optional[Clovis] = None):
        self.cfg = cfg
        self.run = run
        self.clovis = clovis if clovis is not None else Clovis(root)
        self.ha = HAMonitor(self.clovis.store)
        self.ckpt = CheckpointManager(self.clovis,
                                      strategy=run.checkpoint_strategy)
        self.mesh = make_host_mesh(data_mesh, model_mesh)
        self.rules = default_axis_rules(self.mesh,
                                        run.sequence_parallel)
        self._preempted = False
        self.param_dtype = param_dtype
        # the step consumes the parameters and optimizer state it is given
        # (their buffers hold the new ones): callers keep only the outputs
        self.train_step = jax.jit(make_train_step(cfg, run),
                                  donate_argnums=(0, 1))

    # -- preemption: SIGTERM triggers an immediate streamed checkpoint --
    def install_signal_handler(self, state_ref):
        def handler(signum, frame):
            self._preempted = True
        signal.signal(signal.SIGTERM, handler)

    def place(self, params, opt_state: AdamWState):
        """Lay parameters and optimizer moments out on the mesh by the
        sharding rules (TP over 'model', FSDP over 'data'); moments
        share their parameter's layout, the step counter is replicated."""
        specs = make_param_specs(params, self.cfg, self.mesh,
                                 fsdp=self.run.fsdp)
        shard = named(self.mesh, specs)
        put = lambda t: jax.device_put(t, shard)      # noqa: E731
        step = jax.device_put(opt_state.step,
                              NamedSharding(self.mesh, PartitionSpec()))
        return put(params), AdamWState(step, put(opt_state.m),
                                       put(opt_state.v))

    def place_batch(self, batch):
        """Host batch -> device arrays split over the data axis."""
        with span("sage.train.place"):
            return jax.device_put(
                batch, named(self.mesh, make_batch_specs(batch, self.mesh)))

    def init_state(self, seed: int = 0):
        params = mdl.init_params(jax.random.key(seed), self.cfg,
                                 dtype=self.param_dtype)
        return self.place(params, init_opt_state(params))

    def try_restore(self):
        step = self.ckpt.latest_step()
        if step is None:
            return None
        params_like = jax.eval_shape(
            lambda: mdl.init_params(jax.random.key(0), self.cfg,
                                    dtype=self.param_dtype))
        opt_like = jax.eval_shape(
            lambda: init_opt_state(
                jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             params_like)))
        state = self.ckpt.restore(step, like={"params": params_like,
                                              "opt": opt_like})
        params, opt = self.place(state["params"], state["opt"])
        return step, params, opt

    def train(self, steps: int, loader, *, start_step: int = 0,
              params=None, opt_state=None, log_every: int = 10):
        if params is None:
            params, opt_state = self.init_state(self.run.seed)
        self.install_signal_handler((params, opt_state))
        err_fb = (init_error_feedback(params)
                  if self.run.grad_compression == "int8" else None)
        history = []
        with mesh_context(self.mesh), axis_rules(self.rules):
            step = start_step
            t_last = time.time()
            while step < steps:
                with span("sage.train.step", step=step):
                    batch = self.place_batch(next(loader))
                    params, opt_state, metrics = self.train_step(
                        params, opt_state, batch)
                step += 1
                if step % log_every == 0 or step == steps:
                    loss = float(metrics["loss"])
                    dt = (time.time() - t_last) / log_every
                    t_last = time.time()
                    history.append((step, loss))
                    print(f"step {step:5d}  loss {loss:.4f}  "
                          f"{dt*1e3:7.1f} ms/step  "
                          f"gnorm {float(metrics['grad_norm']):.3f}")
                if (step % self.run.checkpoint_every == 0
                        or step == steps or self._preempted):
                    self.ckpt.save(step, {"params": params,
                                          "opt": opt_state},
                                   block=(step == steps or self._preempted))
                if self._preempted:
                    ok = self.ckpt.wait()
                    print(f"preempted at step {step}; checkpoint "
                          f"{'flushed' if ok else 'INCOMPLETE'}")
                    break
        self.ckpt.wait()
        return params, opt_state, history


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--root", default="/tmp/sage_train")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--checkpoint-strategy", default="stream",
                    choices=("collective", "window", "stream"))
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-compression", default="none",
                    choices=("none", "int8"))
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if jax.default_backend() == "cpu":
        cfg = cfg.scaled(dtype="float32")   # bf16 matmuls are slow on CPU
    run = RunConfig(arch=args.arch, learning_rate=args.lr,
                    total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
                    checkpoint_strategy=args.checkpoint_strategy,
                    checkpoint_every=args.checkpoint_every,
                    grad_compression=args.grad_compression,
                    remat="none", scan_layers=True)

    trainer = Trainer(cfg, run, Path(args.root))
    build_synthetic_corpus(trainer.clovis, vocab=cfg.vocab_real,
                           n_shards=4, tokens_per_shard=args.batch * (args.seq + 1) * 8)

    start, params, opt = 0, None, None
    if args.resume:
        got = trainer.try_restore()
        if got is not None:
            start, params, opt = got
            print(f"resumed from checkpoint at step {start}")

    loader = TokenLoader(trainer.clovis, batch=args.batch, seq=args.seq,
                         start_step=start)
    try:
        t0 = time.time()
        params, opt, hist = trainer.train(args.steps, loader,
                                          start_step=start, params=params,
                                          opt_state=opt)
        dt = time.time() - t0
        print(f"done: {args.steps - start} steps in {dt:.1f}s; "
              f"final loss {hist[-1][1]:.4f}" if hist else "done")
        print("ADDB report:", {k: f"{v['bytes']/1e6:.1f}MB"
                               for k, v in trainer.clovis.addb_report().items()
                               if v["bytes"]})
    finally:
        loader.close()
        trainer.ckpt.close()


if __name__ == "__main__":
    main()
