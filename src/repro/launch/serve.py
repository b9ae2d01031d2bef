"""Batched serving driver: prefill a prompt batch, then greedy-decode tokens.

CPU-runnable with ``--reduced`` configs; the full-size configs are exercised
via the dry-run only.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --batch 4 --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, ShapeConfig, get_arch, reduced
from repro.data import SyntheticLM
from repro.launch.mesh import worker_mesh
from repro.launch.serving import build_serve_programs, serve_batch_specs


def serve_session(cfg, *, batch: int = 4, prompt_len: int = 32,
                  new_tokens: int = 16, seed: int = 0, mesh=None,
                  verbose: bool = True):
    """Returns (generated tokens (B, new_tokens), tokens/s)."""
    mesh = mesh or worker_mesh()
    cache_len = prompt_len + new_tokens
    shape = ShapeConfig(name="decode_32k", seq_len=cache_len,
                        global_batch=batch, kind="decode")
    with mesh:
        programs = build_serve_programs(cfg, shape, mesh)
        params = programs.init_fn(jax.random.PRNGKey(seed))
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=prompt_len,
                         n_workers=1, seed=seed)
        prompts = jnp.asarray(ds.worker_batch(0, 0, batch)["tokens"])

        # ---- prefill: run the prompt, then write its KV into a fresh cache
        pre_shape = ShapeConfig(name="prefill", seq_len=prompt_len,
                                global_batch=batch, kind="prefill")
        specs = serve_batch_specs(cfg, pre_shape)
        pre_batch = {"tokens": prompts}
        for k, v in specs["prefill"].items():
            if k != "tokens":
                pre_batch[k] = jnp.zeros(v.shape, v.dtype)
        logits, _ = programs.prefill(params, pre_batch)

        # decode continues from a zero cache replayed over the prompt —
        # simple and correct for every family (attention ring-buffer, SSM
        # recurrence, LSTM state all update via decode_step).
        from repro.launch.serving import decode_cache_specs
        cache = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, l.dtype),
            decode_cache_specs(cfg, shape))
        tok = prompts[:, :1]
        out = []
        t0 = time.time()
        for pos in range(cache_len - 1):
            if pos + 1 < prompt_len:
                nxt = prompts[:, pos + 1:pos + 2]            # teacher-forced
            else:
                nxt = None
            logits, cache = programs.decode_step(
                params, cache, tok.astype(jnp.int32),
                jnp.full((batch,), pos, jnp.int32))
            if nxt is None:
                nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
                out.append(np.asarray(nxt))
            tok = nxt
            if len(out) >= new_tokens:
                break
        dt = time.time() - t0
        gen = np.concatenate(out, axis=1) if out else np.zeros((batch, 0), np.int32)
        tps = batch * gen.shape[1] / max(dt, 1e-9)
        if verbose:
            print(f"generated {gen.shape} tokens in {dt:.2f}s "
                  f"({tps:.1f} tok/s incl. prompt replay)")
        return gen, tps


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-7b", help=f"one of {sorted(ARCHS)}")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    gen, tps = serve_session(cfg, batch=args.batch, prompt_len=args.prompt_len,
                             new_tokens=args.new_tokens, seed=args.seed)
    print("sample generations (token ids):")
    for row in gen[:4]:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
