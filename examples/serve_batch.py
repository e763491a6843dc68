"""Batched serving: prefill a batch of prompts, decode new tokens with the
KV cache (GQA or MLA absorbed cache, per --arch smoke config).  The loop
itself lives in repro.launch.driver (shared with `python -m
repro.launch.serve`).

    PYTHONPATH=src python examples/serve_batch.py --arch qwen2-7b --new-tokens 16
"""
import argparse

from repro.configs.base import list_archs, smoke_config
from repro.launch.driver import serve_greedy
from repro import compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")
    res = serve_greedy(cfg, args.batch, args.prompt_len, args.new_tokens)

    print(f"arch={args.arch}  batch={args.batch}")
    print(f"prefill {args.prompt_len} toks: {res.prefill_s * 1e3:.1f} ms "
          f"(incl. compile)")
    print(f"decode  {args.new_tokens - 1} steps: "
          f"{res.ms_per_token:.1f} ms/tok")
    print("generated token ids:\n", res.tokens)


if __name__ == "__main__":
    compile_cache.enable()
    main()
