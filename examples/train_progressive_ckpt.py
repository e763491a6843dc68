"""End-to-end training driver: a llama-style LM trained with the full
substrate — AdamW, progressive MDR checkpoints (async, atomic), bit-exact
crash resume, error-feedback gradient compression, straggler detection.

Defaults are CPU-friendly (~33M params, 60 steps).  The production-size run
the deliverable describes is:

    PYTHONPATH=src python examples/train_progressive_ckpt.py \
        --d-model 768 --n-layers 12 --steps 300      # ~103M params

At the end the script demonstrates precision-on-demand restore: bit-exact for
resume vs ~half the read bytes at rel_error=1e-2 for evaluation warm-start.
"""
import argparse
import shutil
import time

from repro.configs.base import ModelConfig
from repro.ckpt import manager as ckpt_mgr
from repro.models.model import Model, count_params
from repro.optim import adamw
from repro.train.loop import Trainer, TrainerConfig, synthetic_data
from repro import compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--n-layers", type=int, default=8)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_demo")
    ap.add_argument("--grad-compress-planes", type=int, default=8)
    ap.add_argument("--simulate-crash", action="store_true")
    args = ap.parse_args()

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    cfg = ModelConfig(
        name="demo-lm", family="dense", n_layers=args.n_layers,
        d_model=args.d_model, n_heads=args.d_model // 64,
        n_kv_heads=max(args.d_model // 128, 1), d_ff=4 * args.d_model,
        vocab_size=8192, compute_dtype="float32", remat=False)
    model = Model(cfg)
    print(f"model: {count_params(cfg) / 1e6:.1f}M params")

    def make_trainer():
        return Trainer(
            model,
            adamw.AdamWConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps),
            TrainerConfig(total_steps=args.steps, ckpt_every=20,
                          ckpt_dir=args.ckpt_dir, log_every=10,
                          grad_compress_planes=args.grad_compress_planes),
            synthetic_data(cfg, args.batch, args.seq))

    if args.simulate_crash:
        try:
            make_trainer().run(crash_at=args.steps // 2)
        except RuntimeError as e:
            print(f"!! {e} — restarting…")

    t0 = time.time()
    res = make_trainer().run()
    for m in res["metrics"]:
        print(f"step {m['step']:4d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:8.3f}  {m['dt'] * 1e3:7.1f} ms")
    print(f"trained to step {res['final_step']} in {time.time() - t0:.1f}s "
          f"(stragglers flagged: {res['straggler_events']})")

    # precision-on-demand restore
    step = ckpt_mgr.latest_step(args.ckpt_dir)
    like = {"params": res["params"], "opt": res["opt_state"], "ef": res["ef"]}
    _, full = ckpt_mgr.load(args.ckpt_dir, step, like)
    _, part = ckpt_mgr.load(args.ckpt_dir, step, like, rel_error=1e-2)
    print(f"restore step {step}: bit-exact read {full['bytes_read'] / 1e6:.1f} MB; "
          f"eval-precision (1e-2) read {part['bytes_read'] / 1e6:.1f} MB "
          f"({part['read_fraction']:.0%} of the checkpoint)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
