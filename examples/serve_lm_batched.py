"""Batched *LM decode* serving demo: prefill a batch of prompts, then
greedy-decode continuation tokens through the rolling KV/state cache —
the same `prefill_step` / `decode_step` the dry-run lowers for
prefill_32k / decode_32k / long_500k, here executed for real on a
reduced config.

This serves language-model tokens, not bilevel jobs: for the batched
*bilevel solver* engine (vmapped DAGM job fleets, shape buckets,
compile cache, continuous batching — `repro.serve`), see
examples/serve_hyperopt.py.

Works for every architecture family (dense GQA / MoE / RWKV6 / hybrid):

    PYTHONPATH=src python examples/serve_lm_batched.py \
        --arch mixtral-8x7b [--prompt-len 48] [--new-tokens 16]
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import build_model
from repro.models.steps import (make_decode_step, make_prefill_step,
                                sample_greedy)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"[serve] {cfg.name}: {model.param_count()/1e6:.2f}M params, "
          f"batch={args.batch}, prompt={args.prompt_len}")

    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": prompts}
    if cfg.encoder_decoder:    # whisper: stubbed frame embeddings
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.encoder_frames, cfg.d_model))

    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))

    t0 = time.time()
    logits, cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0
    assert logits.shape == (args.batch, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    toks = sample_greedy(logits)[:, None]
    generated = [toks]
    t0 = time.time()
    for _ in range(args.new_tokens - 1):
        logits, cache = decode(params, toks, cache)
        toks = sample_greedy(logits)[:, None]
        generated.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.time() - t0
    gen = np.concatenate([np.asarray(g) for g in generated], axis=1)

    assert gen.shape == (args.batch, args.new_tokens)
    assert (gen >= 0).all() and (gen < cfg.vocab_size).all()
    per_tok = t_decode / max(args.new_tokens - 1, 1) * 1e3
    print(f"[serve] prefill {t_prefill*1e3:.0f}ms, "
          f"decode {per_tok:.1f}ms/token")
    print(f"[serve] sample continuation (seq 0): {gen[0][:12]}")
    print("OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
