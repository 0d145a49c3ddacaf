"""Training driver: a language-model training job reading its corpus
from the store, steps back to back.

Set-up writes the corpus (``bench.gen.mamba2.make_corpus``) into the
store through ``Clovis.put_array``, builds the program's ``Trainer`` for
the configuration's ``arch``, makes the weights on the device from the
seed (``bench.gen.mamba2.init_weights``), and starts a ``TokenLoader``.
It then takes the first ``check_steps`` steps through the same call the
window makes (next batch from the loader, ``Trainer.place_batch``,
``Trainer.train_step``), the first of which compiles, and records what
the reference compares: each step's loss, the first clipped gradient as
Adam's first moment holds it after step one, and the parameters before
step one and after the last.  The same state goes on into the window,
which steps until ``--seconds`` have passed, keeps ``ahead_steps`` steps
dispatched beyond the one it waits for, so that the chip stays fed while
the host stands still, and ends when the last step is done on the device.

Afterwards, with the program's state freed, ``bench.ref.mamba2`` takes
the same steps from the same weights on the same batches (read from
the benchmark's own copy of the corpus) in float32, and the worst leaf
decides each compared gap.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List

import numpy as np

from bench.flops import mamba2_train_flops_per_token
from bench.gen import mamba2 as gen


def program_config(cfg: Dict):
    """The program's registered configuration, checked against the
    benchmark's file so the cell runs what the file states."""
    from repro.configs import get_config
    pc = get_config(cfg["arch"])
    pc = pc.scaled(n_layers=cfg["n_layers"], d_model=cfg["d_model"],
                   ssm_state=cfg["d_state"], ssm_expand=cfg["expand"],
                   ssm_headdim=cfg["headdim"], ssm_ngroups=cfg["ngroups"],
                   ssm_conv=cfg["d_conv"], ssm_chunk=cfg["chunk_size"],
                   vocab_size=gen.padded_vocab(cfg),
                   norm_eps=cfg["norm_eps"],
                   dtype=cfg["train"]["compute_dtype"])
    if not pc.tie_embeddings:
        raise ValueError("the reference ties the embeddings")
    return pc


def run_config(cfg: Dict):
    from repro.configs.base import RunConfig
    t = cfg["train"]
    return RunConfig(arch=cfg["arch"], remat=t["remat"],
                     scan_layers=t["scan_layers"],
                     microbatch=t["microbatch"],
                     learning_rate=t["learning_rate"],
                     weight_decay=t["weight_decay"], beta1=t["beta1"],
                     beta2=t["beta2"], grad_clip=t["grad_clip"],
                     warmup_steps=t["warmup_steps"],
                     total_steps=t["total_steps"], checkpoint_every=1 << 30)


def leaf_norms(tree) -> List[float]:
    import jax
    import jax.numpy as jnp
    return [float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
            for x in jax.tree.leaves(tree)]


def worst_gap(prog: Dict[str, float], ref: Dict[str, float],
              skip=()) -> float:
    """max over leaves of |prog - ref| / max(ref, median ref leaf)."""
    keys = [k for k in ref if k not in skip]
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def run(ctx) -> Dict:
    import jax
    from repro.data.pipeline import CORPUS_CONTAINER, TokenLoader
    from repro.core import layouts as lay
    from repro.launch.mesh import mesh_context
    from repro.launch.train import Trainer
    from repro.models import model as mdl
    from repro.models.common import axis_rules
    from repro.optim import init_opt_state

    from bench.drivers.query import make_clovis

    cfg, traffic = ctx.config, ctx.traffic
    t = cfg["train"]
    B, S = int(traffic["batch"]), int(traffic["seq"])
    n_check = int(traffic["check_steps"])
    ahead = int(traffic["ahead_steps"])
    pc = program_config(cfg)
    clovis = make_clovis(ctx.work / "store", int(traffic["devices_per_tier"]))
    need = B * (S + 1)
    with ctx.spans.span("bench.corpus"):
        shards = gen.make_corpus(cfg, ctx.seed, int(traffic["shards"]),
                                 int(traffic["shard_batches"]) * need)
        for i, toks in enumerate(shards):
            clovis.put_array(f"corpus/shard{i:04d}", toks,
                             container=CORPUS_CONTAINER,
                             layout=lay.DEFAULT_LAYOUTS["data"])
    trainer = Trainer(pc, run_config(cfg), ctx.work / "train", clovis=clovis)
    with ctx.spans.span("bench.weights"):
        w = gen.init_weights(cfg, ctx.seed)
        params = gen.to_program(w, t["scan_layers"])
        like = jax.eval_shape(lambda: mdl.init_params(
            jax.random.key(0), pc, scan_layers=t["scan_layers"]))
        if (jax.tree.structure(like) != jax.tree.structure(params)
                or any(a.shape != b.shape or a.dtype != b.dtype
                       for a, b in zip(jax.tree.leaves(like),
                                       jax.tree.leaves(params)))):
            raise ValueError("the weights do not fit the program's "
                             "parameter tree")
        names = gen.program_names(params)
        state = trainer.place(params, init_opt_state(params))
        del w, params
    loader = TokenLoader(clovis, batch=B, seq=S,
                         prefetch=int(traffic["prefetch"]),
                         seed=ctx.seed % (1 << 32))
    seen: List[Dict] = []

    def step(state, keep: bool):
        with ctx.spans.span("bench.next_batch"):
            batch = next(loader)
        if keep:
            seen.append(batch)
        with ctx.spans.span("bench.step"):
            placed = trainer.place_batch(batch)
            p, o, m = trainer.train_step(state[0], state[1], placed)
        return p, o, m

    b1 = t["beta1"]
    try:
        with mesh_context(trainer.mesh), axis_rules(trainer.rules):
            p0 = jax.device_get(state[0])
            losses = []
            for k in range(n_check):
                p, o, m = step(state, keep=True)
                losses.append(float(m["loss"]))
                if k == 0:
                    first_grad = [n / (1 - b1) for n in leaf_norms(o.m)]
                state = (p, o)
            p3 = jax.device_get(state[0])
            change = [float(np.linalg.norm((np.asarray(a, np.float64)
                                            - np.asarray(b, np.float64))))
                      for a, b in zip(jax.tree.leaves(p3),
                                      jax.tree.leaves(p0))]
            del p0, p3
            loader_wait0 = ctx.spans.total("bench.next_batch")

            with ctx.window():
                t_start = time.perf_counter()
                stop_at = t_start + ctx.seconds
                steps, pending = 0, collections.deque()
                while time.perf_counter() < stop_at:
                    p, o, m = step(state, keep=False)
                    state = (p, o)
                    steps += 1
                    pending.append(m["loss"])
                    if len(pending) > ahead:   # `ahead` steps queued
                        with ctx.spans.span("bench.sync"):
                            pending.popleft().block_until_ready()
                jax.block_until_ready(state)
                t_end = time.perf_counter()
            ctx.window_s = t_end - t_start
            last_loss = float(pending[-1]) if pending else float("nan")
            ctx.read_peak()
    finally:
        loader.close()
        trainer.ckpt.close()
    n_wait, s_wait = ctx.spans.total("bench.next_batch")
    del state, p, o, m, trainer
    tokens_per_s = steps * B * S / ctx.window_s
    ctx.notes.append(f"train: batch {B} x {S}; window steps {steps}, "
                     f"{ahead} queued ahead; losses of the checked steps "
                     f"{losses}, last window loss {last_loss}; device peak "
                     f"bytes {ctx.peak_bytes}")

    # the reference: same weights, same batches, float32
    from bench.ref import mamba2 as ref
    with ctx.spans.span("bench.reference"):
        index = gen.CorpusIndex(shards)
        batches = []
        for b in seen:
            flat = np.concatenate([b["tokens"], b["labels"][:, -1:]], 1)
            toks = index.locate(flat.reshape(-1)).reshape(B, S + 1)
            batches.append((toks[:, :-1], toks[:, 1:]))
        w = gen.init_weights(cfg, ctx.seed)
        r_losses, r_grad, r_w = ref.train(
            w, batches, cfg, rows_per_pass=int(traffic["ref_rows_per_pass"]))
        ref_grad, ref_change = ref_norms(jax.device_get(w), r_grad, r_w,
                                         names)
    gaps = readings(dict(zip(names, first_grad)), dict(zip(names, change)),
                    losses, ref_grad, ref_change, r_losses)
    ctx.notes.append(f"reference losses {r_losses}; leaves left out of "
                     f"the gradient and change gaps: {gaps.pop('skipped')}")
    limits = traffic["limits"]
    return {
        "attempted": steps, "failed": 0,
        "correct": bool(np.isfinite(last_loss)) and steps > 0,
        "metrics": {"train_tokens_per_s": tokens_per_s},
        "checks": {k: (gaps[k], limits[k]) for k in limits},
        "layer": {"tokens_per_s": tokens_per_s,
                  "flops_per_token": mamba2_train_flops_per_token(cfg),
                  "loader_wait": (n_wait - loader_wait0[0],
                                  s_wait - loader_wait0[1])},
    }


def ref_norms(w0: Dict, grad: Dict, w1: Dict, names: List[str]):
    """The reference's gradient and change norms of each program leaf."""
    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))
    return ({n: norm(gen.leaf_of(grad, n)) for n in names},
            {n: norm(np.asarray(gen.leaf_of(w1, n), np.float64)
                     - gen.leaf_of(w0, n)) for n in names})


def readings(prog_grad: Dict[str, float], prog_change: Dict[str, float],
             prog_losses: List[float], ref_grad: Dict[str, float],
             ref_change: Dict[str, float], ref_losses: List[float]) -> Dict:
    """The compared gaps.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left
    out of the gradient and change gaps."""
    med = float(np.median(list(ref_grad.values())))
    skip = sorted(k for k, v in ref_grad.items() if v < 1e-3 * med)
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog_losses,
                                                       ref_losses))
    return {"loss": loss_gap,
            "grad": worst_gap(prog_grad, ref_grad, skip),
            "change": worst_gap(prog_change, ref_change, skip),
            "skipped": skip}


def control(ctx) -> Dict:
    """The lower-precision control's readings at the cell's size: the
    reference with fp8 (e4m3) matrix-product inputs put in the program's
    place, against the float32 reference, over ``check_steps`` batches of
    the cell's shape drawn from the benchmark's corpus."""
    import jax
    from bench.ref import mamba2 as ref
    cfg, traffic = ctx.config, ctx.traffic
    B, S = int(traffic["batch"]), int(traffic["seq"])
    n = int(traffic["check_steps"])
    shard = gen.make_corpus(cfg, ctx.seed, 1, n * B * (S + 1))[0]
    shard = shard.reshape(n, B, S + 1)
    batches = [(b[:, :-1], b[:, 1:]) for b in shard]
    names = gen.program_names(jax.eval_shape(
        lambda: gen.to_program(gen.init_weights(cfg, 0),
                               cfg["train"]["scan_layers"])))
    out = {}
    for mm in ("float32", traffic["control_mm_dtype"]):
        w = gen.init_weights(cfg, ctx.seed)
        w0 = jax.device_get(w)
        losses, g, w1 = ref.train(
            w, batches, cfg, mm_dtype=mm,
            rows_per_pass=int(traffic["ref_rows_per_pass"]))
        out[mm] = (losses, *ref_norms(w0, g, w1, names))
        del w, w0, g, w1
    (r_l, r_g, r_c), (c_l, c_g, c_c) = out.values()
    return readings(c_g, c_c, c_l, r_g, r_c, r_l)
