"""End-to-end training driver with fault tolerance.

    PYTHONPATH=src python examples/train_lm.py --arch qwen2-0.5b --steps 60 \
        [--inject-failure 25]

Trains the reduced config on the synthetic bigram corpus with the full
runtime: AdamW + schedule, periodic checkpoints, restart-on-failure, and
straggler detection.  Loss must drop well below ln(vocab) as the model
learns the planted bigrams.
"""
import argparse
import tempfile

import numpy as np

from repro.data.pipeline import DataConfig
from repro.models import get_config
from repro.optim import adamw
from repro.runtime.trainer import Trainer, TrainerConfig, WorkerFailure


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--inject-failure", type=int, default=-1,
                    help="simulate a node failure at this step")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_train_")
    tcfg = TrainerConfig(total_steps=args.steps, checkpoint_every=10,
                         checkpoint_dir=ckpt)
    opt = adamw.AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                            total_steps=args.steps, weight_decay=0.01)
    fired = {"done": False}

    def maybe_fail(step):
        if step == args.inject_failure and not fired["done"]:
            fired["done"] = True
            print(f"!! injecting WorkerFailure at step {step}")
            raise WorkerFailure("simulated preemption")

    tr = Trainer(cfg, tcfg, opt_cfg=opt,
                 data_cfg=DataConfig(vocab_size=cfg.vocab_size,
                                     seq_len=args.seq,
                                     global_batch=args.batch),
                 failure_hook=maybe_fail if args.inject_failure >= 0 else None)
    state = tr.run_with_restarts()

    losses = [h["loss"] for h in tr.history if "loss" in h]
    restarts = [h for h in tr.history if "restart" in h]
    print(f"\narch={cfg.name} steps={args.steps} "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(ln V = {np.log(cfg.vocab_size):.2f}) restarts={len(restarts)}")
    if tr.detector.stragglers():
        print("stragglers:", tr.detector.stragglers())
    assert losses[-1] < losses[0], "loss did not decrease"

    print("OK")


if __name__ == "__main__":
    main()
