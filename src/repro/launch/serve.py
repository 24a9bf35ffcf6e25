"""Serving launcher: batched prefill + decode with per-step latency stats.

  PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-9b \
      --batch 4 --prompt-len 64 --new-tokens 32 [--json]

Supervision contract: ``--json`` makes the final line one JSON status
object, and exit codes are typed per ``repro.orchestrator.contract``
(0 ok, 42 fault-injected, 43 stalled, 44 preempted) so a daemon or CI
lane can supervise this entrypoint without scraping the human text.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import all_archs, get_arch
from repro.models import build_model
from repro.train.step import make_train_step
from repro.utils.backend import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=sorted(all_archs()))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="final line is one machine-readable JSON status object")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    _, init_state, *_ = make_train_step(model)
    params = init_state(jax.random.key(0))["params"]

    B, S, N = args.batch, args.prompt_len, args.new_tokens
    batch = {"tokens": jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab)}
    if cfg.num_img_tokens:
        batch["image_embeds"] = jax.random.normal(
            jax.random.key(2), (B, cfg.num_img_tokens, cfg.d_model), jnp.float32)
    if cfg.encoder_layers:
        batch["frames"] = jax.random.normal(
            jax.random.key(3), (B, cfg.encoder_seq, cfg.d_model), jnp.float32)

    prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len=S + N))
    decode = jax.jit(lambda p, t, pos, c: model.decode(p, t, pos, c))

    t0 = time.perf_counter()
    logits, caches = prefill(params, batch)
    jax.block_until_ready(logits)
    t_pre = time.perf_counter() - t0
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    lat = []
    for i in range(N - 1):
        t0 = time.perf_counter()
        logits, caches = decode(params, tok, jnp.int32(S + i), caches)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        jax.block_until_ready(tok)
        lat.append(time.perf_counter() - t0)
    lat = np.array(lat[1:])  # drop the compile step
    if args.as_json:
        from repro.orchestrator.contract import EXIT_OK

        print(json.dumps({
            "status": "ok",
            "exit_code": EXIT_OK,
            "arch": args.arch,
            "prefill_s": round(float(t_pre), 6),
            "decode_p50_s": round(float(np.percentile(lat, 50)), 6),
            "decode_p99_s": round(float(np.percentile(lat, 99)), 6),
            "tokens_per_s": round(float(B / np.mean(lat)), 2),
        }))
    else:
        print(f"{args.arch}: prefill {B}x{S}: {t_pre*1e3:.1f} ms | decode p50 "
              f"{np.percentile(lat,50)*1e3:.2f} ms p99 {np.percentile(lat,99)*1e3:.2f} ms "
              f"| {B/np.mean(lat):.0f} tok/s")


if __name__ == "__main__":
    main()
