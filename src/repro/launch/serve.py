"""Batched serving driver: prefill + greedy decode with KV/state caches.

Demonstrates the serving path the decode_* dry-run cells lower, at CPU
scale, with SAGE engaged: token streams are offloaded to a StreamContext
consumer that appends to Clovis (request logging / analytics feed), and
per-request latency telemetry lands in ADDB.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --smoke \
        --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import Clovis, StreamContext, clovis_appender
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as mdl


class Server:
    def __init__(self, cfg, *, root: Path, max_len: int = 256,
                 param_dtype=jnp.float32, log_tokens: bool = True):
        self.cfg = cfg
        self.max_len = max_len
        self.clovis = Clovis(root)
        self.params = mdl.init_params(jax.random.key(0), cfg,
                                      dtype=param_dtype)
        self._prefill = jax.jit(
            lambda p, b, c: mdl.prefill(p, b, cfg, c))
        self._decode = jax.jit(
            lambda p, t, pos, c: mdl.decode_step(p, t, pos, cfg, c))
        self._stream = None
        if log_tokens:
            self._stream = StreamContext(
                n_producers=1, consumer_ratio=15,
                attach=clovis_appender(self.clovis, container="servelog"))

    def generate(self, tokens: np.ndarray, gen: int, extra=None):
        """tokens: (batch, prompt_len) int32 -> (batch, gen) int32."""
        b, plen = tokens.shape
        cache = mdl.init_decode_state(
            self.cfg, b, self.max_len,
            dtype=jnp.float32 if self.cfg.dtype == "float32" else jnp.bfloat16)
        batch = {"tokens": jnp.asarray(tokens)}
        if extra:
            batch.update(extra)
        t0 = time.time()
        logits, cache = self._prefill(self.params, batch, cache)
        t_prefill = time.time() - t0

        out = np.zeros((b, gen), np.int32)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        t0 = time.time()
        for i in range(gen):
            out[:, i] = np.asarray(tok)[:, 0]
            if self._stream is not None:
                self._stream.push(0, "tokens", out[:, i])
            logits, cache = self._decode(self.params, tok,
                                         jnp.int32(plen + i), cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        t_decode = time.time() - t0
        self.clovis.addb.record("serve", "generate", "-",
                                b * gen, t_prefill + t_decode)
        return out, {"prefill_s": t_prefill, "decode_s": t_decode,
                     "tok_per_s": b * gen / max(t_decode, 1e-9)}

    def close(self):
        if self._stream is not None:
            self._stream.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--root", default="/tmp/sage_serve")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.scaled(dtype="float32")
    srv = Server(cfg, root=Path(args.root),
                 max_len=args.prompt_len + args.gen + 8)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_real,
                           (args.batch, args.prompt_len)).astype(np.int32)
    extra = {}
    if cfg.is_encoder_decoder:
        extra["frames"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    if cfg.cross_attn_period:
        extra["image_embeds"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_image_tokens, cfg.d_model)), jnp.float32)
    out, stats = srv.generate(prompts, args.gen, extra=extra)
    print(f"generated {out.shape} tokens; "
          f"prefill {stats['prefill_s']*1e3:.1f}ms, "
          f"decode {stats['tok_per_s']:.1f} tok/s")
    srv.close()


if __name__ == "__main__":
    main()
