"""Training driver of the Nemotron 3 Nano cell: the language-model
training job of ``bench.drivers.train`` for a hybrid Mamba-2 / attention
/ expert-layer model on one chip's expert share.

Set-up writes the corpus (``bench.gen.mamba2.make_corpus`` over the
configuration's vocabulary slice) into the store through
``Clovis.put_array``, builds the program's ``Trainer`` for
``nemotron3-nano-30b-a3b`` cut as the configuration file says, makes the
weights on the device from the seed (``bench.gen.nemotron3``), and
starts a ``TokenLoader``.  It takes the first ``check_steps`` steps
through the call the window makes (the first compiles) and records what
the reference compares, as the mamba2 driver does.  It then reads the
compiled step's HLO for the instructions that carry the expert layer's
``sage_moe`` scope and for the step's microbatch loop, which the per-layer
metric ``moe_expert_s_per_step`` finds in the trace.  The window steps
until ``--seconds`` have passed with ``ahead_steps`` dispatched beyond
the one it waits for, and sums the steps' ``moe_assigned`` and
``moe_dropped`` counters.

Afterwards, with the program's state freed, ``bench.ref.nemotron3``
takes the same steps from the same weights on the same batches in
float32, a row at a time, and the worst leaf decides each gap.
"""
from __future__ import annotations

import collections
import re
import time
from typing import Dict, List, Tuple

import numpy as np

from bench.drivers.train import leaf_norms, readings, run_config
from bench.flops_nemotron3 import train_flops_per_token
from bench.gen import mamba2 as corpus
from bench.gen import nemotron3 as gen

# what the program implements of the published config, checked so that
# the cell runs what its file states
FIXED = {"mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
         "tie_word_embeddings": False, "use_conv_bias": True,
         "mamba_proj_bias": False, "attention_bias": False,
         "mlp_bias": False, "norm_topk_prob": True, "n_group": 1,
         "topk_group": 1, "n_shared_experts": 1}


def program_config(cfg: Dict):
    """The program's registered configuration, cut as the file says."""
    from repro.configs import get_config
    from repro.configs.base import hybrid_pattern
    bad = {k: cfg[k] for k, v in FIXED.items() if cfg[k] != v}
    if bad:
        raise ValueError(f"the program does not implement {bad}")
    dep = cfg["deployment"]
    return get_config(cfg["arch"]).scaled(
        n_layers=cfg["num_hidden_layers"],
        attn_pattern=hybrid_pattern(cfg["hybrid_override_pattern"]),
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        d_shared_expert=cfg["moe_shared_expert_intermediate_size"],
        n_experts=dep["router_experts"],
        n_local_experts=cfg["n_routed_experts"],
        expert_offset=dep["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        moe_capacity_factor=cfg["moe_capacity_factor"],
        ssm_state=cfg["ssm_state_size"], ssm_nheads=cfg["mamba_num_heads"],
        ssm_headdim=cfg["mamba_head_dim"], ssm_ngroups=cfg["n_groups"],
        ssm_conv=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
        vocab_size=cfg["vocab_size"], norm_eps=cfg["norm_eps"],
        dtype=cfg["train"]["compute_dtype"])


_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_RUNS = re.compile(r"\b(?:condition|body|true_computation|false_computation)"
                   r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CALLED = re.compile(r"\bto_apply=%?([\w.\-]+)")
CONTROL = ("while", "call", "conditional")


def hlo_ops(hlo_text: str, scope: str) -> Tuple[List[str], List[str]]:
    """From a compiled module's text: the names of the instructions that
    run as device ops (those of the entry computation and of the loop,
    branch and call bodies it runs, not those inside fusions, reductions
    or sort comparators) whose metadata carries ``scope``, leaving out
    control flow (a loop's time is its body's); and the names of the
    entry computation's ``while`` instructions."""
    comps: Dict[str, List[Tuple[str, str, str]]] = {}
    entry, name = None, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            h = _HEADER.match(line)
            if h:
                name = h.group(2)
                comps[name] = []
                if h.group(1):
                    entry = name
            continue
        m = _INSTR.match(line)
        if m and name is not None:
            rest = line[m.end():]
            op = _OPCODE.search(rest)
            comps[name].append((m.group(1), op.group(1) if op else "", line))
    runs, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c in runs or c not in comps:
            continue
        runs.add(c)
        for _, op, line in comps[c]:
            todo += _RUNS.findall(line)
            for b in _BRANCHES.findall(line):
                todo += [x.strip().lstrip("%") for x in b.split(",")]
            if op == "call":
                todo += _CALLED.findall(line)
    scoped = sorted(n for c in runs for n, op, line in comps[c]
                    if op not in CONTROL and (f"/{scope}/" in line
                                              or f"({scope})/" in line))
    loops = [n for n, op, _ in comps.get(entry, ()) if op == "while"]
    return scoped, loops


def run(ctx) -> Dict:
    import jax
    pc = program_config(ctx.config)      # the parent's program fails here
    from repro.data.pipeline import CORPUS_CONTAINER, TokenLoader
    from repro.core import layouts as lay
    from repro.launch.mesh import mesh_context
    from repro.launch.train import Trainer
    from repro.models import model as mdl
    from repro.models import moe
    from repro.models.common import axis_rules
    from repro.optim import init_opt_state

    from bench.drivers.query import make_clovis

    cfg, traffic = ctx.config, ctx.traffic
    t = cfg["train"]
    B, S = int(traffic["batch"]), int(traffic["seq"])
    n_check = int(traffic["check_steps"])
    ahead = int(traffic["ahead_steps"])
    pattern = cfg["hybrid_override_pattern"]
    clovis = make_clovis(ctx.work / "store", int(traffic["devices_per_tier"]))
    need = B * (S + 1)
    with ctx.spans.span("bench.corpus"):
        shards = corpus.make_corpus(cfg, ctx.seed, int(traffic["shards"]),
                                    int(traffic["shard_batches"]) * need)
        for i, toks in enumerate(shards):
            clovis.put_array(f"corpus/shard{i:04d}", toks,
                             container=CORPUS_CONTAINER,
                             layout=lay.DEFAULT_LAYOUTS["data"])
    trainer = Trainer(pc, run_config(cfg), ctx.work / "train", clovis=clovis)
    with ctx.spans.span("bench.weights"):
        params = gen.to_program(gen.init_weights(cfg, ctx.seed), pattern)
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
        del params
    loader = TokenLoader(clovis, batch=B, seq=S,
                         prefetch=int(traffic["prefetch"]),
                         seed=ctx.seed % (1 << 32))
    seen: List[Dict] = []
    counters: List[Tuple] = []

    def step(state, keep: bool):
        with ctx.spans.span("bench.next_batch"):
            batch = next(loader)
        if keep:
            seen.append(batch)
        with ctx.spans.span("bench.step"):
            placed = trainer.place_batch(batch)
            return trainer.train_step(state[0], state[1], placed), placed

    b1 = t["beta1"]
    try:
        with mesh_context(trainer.mesh), axis_rules(trainer.rules):
            p0 = jax.device_get(state[0])
            losses = []
            for k in range(n_check):
                (p, o, m), placed = step(state, keep=True)
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
            # the step as it runs (read back from the compile cache)
            moe_ops, loops = hlo_ops(trainer.train_step.lower(
                state[0], state[1], placed).compile().as_text(), moe.SCOPE)
            loader_wait0 = ctx.spans.total("bench.next_batch")

            with ctx.window():
                t_start = time.perf_counter()
                stop_at = t_start + ctx.seconds
                steps, pending = 0, collections.deque()
                while time.perf_counter() < stop_at:
                    (p, o, m), _ = step(state, keep=False)
                    state = (p, o)
                    steps += 1
                    counters.append((m["moe_assigned"], m["moe_dropped"]))
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
    assigned, dropped = (int(sum(int(c[i]) for c in counters))
                         for i in (0, 1))
    del state, p, o, m, placed, trainer
    tokens_per_s = steps * B * S / ctx.window_s
    ctx.notes.append(f"train: batch {B} x {S}; window steps {steps}, "
                     f"{ahead} queued ahead; losses of the checked steps "
                     f"{losses}, last window loss {last_loss}; expert "
                     f"assignments {assigned}, dropped {dropped}; "
                     f"{len(moe_ops)} HLO ops in {moe.SCOPE}, step loops "
                     f"{loops}; device peak bytes {ctx.peak_bytes}")

    # the reference: same weights, same batches, float32
    from bench.ref import nemotron3 as ref
    with ctx.spans.span("bench.reference"):
        index = corpus.CorpusIndex(shards)
        batches = []
        for b in seen:
            flat = np.concatenate([b["tokens"], b["labels"][:, -1:]], 1)
            toks = index.locate(flat.reshape(-1)).reshape(B, S + 1)
            batches.append((toks[:, :-1], toks[:, 1:]))
        w = gen.init_weights(cfg, ctx.seed)
        w0 = jax.device_get(w)
        r_losses, r_grad, r_w = ref.train(
            w, batches, cfg, rows_per_pass=int(traffic["ref_rows_per_pass"]))
        ref_grad, ref_change = ref_norms(w0, r_grad, r_w, names)
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
                  "flops_per_token": train_flops_per_token(cfg, S),
                  "loader_wait": (n_wait - loader_wait0[0],
                                  s_wait - loader_wait0[1]),
                  "moe_assigned": assigned, "moe_dropped": dropped,
                  "moe_ops": moe_ops, "step_loops": loops},
    }


def ref_norms(w0: Dict, grad: Dict, w1: Dict, names: List[str]):
    """The reference's gradient and change norms of each program leaf."""
    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))
    return ({n: norm(gen.leaf_of(grad, n)) for n in names},
            {n: norm(np.asarray(gen.leaf_of(w1, n), np.float64)
                     - gen.leaf_of(w0, n)) for n in names})


def control(ctx) -> Dict:
    """The lower-precision control's readings at the cell's size: the
    reference with fp8 (e4m3) weight-product inputs put in the program's
    place, against the float32 reference, over ``check_steps`` batches of
    the cell's shape drawn from the benchmark's corpus."""
    import jax
    from bench.ref import nemotron3 as ref
    cfg, traffic = ctx.config, ctx.traffic
    B, S = int(traffic["batch"]), int(traffic["seq"])
    n = int(traffic["check_steps"])
    shard = corpus.make_corpus(cfg, ctx.seed, 1, n * B * (S + 1))[0]
    shard = shard.reshape(n, B, S + 1)
    batches = [(b[:, :-1], b[:, 1:]) for b in shard]
    names = gen.program_names(jax.eval_shape(
        lambda: gen.to_program(gen.init_weights(cfg, 0),
                               cfg["hybrid_override_pattern"])))
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
