"""Example: train an assigned-architecture LM end-to-end with the full
substrate — data pipeline, AdamW+WSD, checkpointing, straggler watch —
including a mid-run kill/restart to demonstrate fault tolerance.

  PYTHONPATH=src python examples/train_lm.py [--arch minicpm-2b] [--steps 200]

On this CPU container the reduced config trains a few hundred steps in
minutes; on real hardware the same Trainer drives the full config under
the dry-run-proven shardings.
"""
import argparse
import shutil
import tempfile

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ARCH_IDS, get_config
from repro.train.data import DataConfig
from repro.train.loop import TrainConfig, Trainer
from repro.train.optimizer import AdamWConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="minicpm-2b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    ckpt_dir = tempfile.mkdtemp(prefix="train_lm_ckpt_")
    mk = lambda: Trainer(
        cfg,
        TrainConfig(steps=args.steps, ckpt_every=50, ckpt_dir=ckpt_dir),
        AdamWConfig(lr=3e-3, schedule="wsd",
                    warmup_steps=args.steps // 10, total_steps=args.steps),
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch, seed=0))

    print(f"== training {args.arch} (reduced) for {args.steps} steps ==")
    t = mk()
    half = args.steps // 2
    t.run(half)
    mid_loss = t.metrics_log[-1]["loss"]
    print(f"step {t.step}: loss={mid_loss:.4f} — simulating a crash now")
    del t  # "node failure"

    t2 = mk()  # restores from the newest sealed checkpoint
    print(f"restarted at step {t2.step} "
          f"(data stream at batch {t2.data.next_index}) — resuming")
    last = t2.run(args.steps - t2.step)
    print(f"done: step {t2.step}, loss={last['loss']:.4f} "
          f"(grad_norm={last['grad_norm']:.3f}, lr={last['lr']:.2e})")
    shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
