"""Batched serving driver: prefill a batch of prompts, then decode N tokens
autoregressively with greedy/temperature sampling.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
        --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.steps import make_prefill_step, make_serve_step
from repro.models.transformer import init_lm_params


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--obs", default=None, metavar="SPEC",
        help="stream repro.obs timing records: jsonl:PATH, socket:ADDR, "
        "or a bare JSONL path",
    )
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    obs = None
    if args.obs:
        from repro.obs import Obs, sink_from_spec

        obs = Obs(sink=sink_from_spec(args.obs), run=f"serve-{args.arch}")
    key = jax.random.PRNGKey(args.seed)
    params, _ = init_lm_params(cfg, key)

    B, S, G = args.batch, args.prompt_len, args.gen
    prompts = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    memory = None
    if cfg.arch_type == "audio":
        s_enc = max(1, S // cfg.enc_seq_ratio)
        batch["enc_embeds"] = jax.random.normal(key, (B, s_enc, cfg.d_model), cfg.dtype)
        from repro.models.transformer import encoder_forward

        memory = encoder_forward(params, cfg, batch["enc_embeds"])
    if cfg.arch_type == "vlm":
        batch["memory"] = jax.random.normal(
            key, (B, cfg.num_patches, cfg.d_model), cfg.dtype
        )
        memory = batch["memory"]

    prefill = jax.jit(make_prefill_step(cfg, max_len=S + G))
    serve = jax.jit(make_serve_step(cfg))

    t0 = time.time()
    logits, caches = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0
    print(f"[serve] prefill {B}x{S} in {t_prefill*1e3:.1f} ms")
    if obs is not None:
        obs.timing(
            "prefill", t_prefill, engine="serve", batch=B, prompt_len=S,
        )

    def sample(logits, k):
        if args.temperature <= 0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.random.categorical(k, logits / args.temperature).astype(jnp.int32)

    tok = sample(logits, key)
    generated = [tok]
    t0 = time.time()
    for i in range(G - 1):
        key, k = jax.random.split(key)
        logits, caches = serve(params, tok, jnp.int32(S + i), caches, memory)
        tok = sample(logits, k)
        generated.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    toks = B * (G - 1)
    print(f"[serve] decoded {G-1} steps x {B} seqs in {dt:.2f}s "
          f"({toks/max(dt,1e-9):.1f} tok/s on CPU)")
    if obs is not None:
        obs.timing(
            "decode", dt, engine="serve", batch=B, gen=G - 1,
            tokens_per_s=toks / max(dt, 1e-9),
        )
        obs.close()
    out = jnp.stack(generated, axis=1)
    print("[serve] sample output ids:", np.asarray(out[0, :16]))
    return out


if __name__ == "__main__":
    main()
