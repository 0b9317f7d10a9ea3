"""Production training launcher.

    # CPU-scale run (reduced config, real runtime):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
        --steps 50

    # Production lowering check for the full config on the target mesh:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --dry-run

On a real TPU cluster this module is invoked per-host under the standard
JAX distributed bootstrap; the mesh/sharding config is identical to what
the dry-run validates.
"""
import argparse
import os
import tempfile

from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import get_config
from repro.optim import adamw
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile the full config on the production "
                         "mesh instead of training")
    args = ap.parse_args()

    if args.dry_run:
        # delegate to the dry-run module (must own process startup for the
        # 512-device host platform flag); it compiles for fake CPU devices
        # only, so it is kept off any accelerator this host may hold
        import subprocess
        import sys
        raise SystemExit(subprocess.call(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", args.arch, "--shape", "train_4k", "--both",
             "--force"], env={**os.environ, "JAX_PLATFORMS": "cpu"}))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix=f"train_{args.arch}_")
    tcfg = TrainerConfig(total_steps=args.steps,
                         checkpoint_every=args.checkpoint_every,
                         checkpoint_dir=ckpt)
    opt = adamw.AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                            total_steps=args.steps)
    tr = Trainer(cfg, tcfg, opt_cfg=opt,
                 data_cfg=DataConfig(vocab_size=cfg.vocab_size,
                                     seq_len=args.seq,
                                     global_batch=args.global_batch))
    tr.run_with_restarts()
    losses = [h["loss"] for h in tr.history if "loss" in h]
    print(f"[train] {cfg.name}: {len(losses)} steps, "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}, ckpt={ckpt}")


if __name__ == "__main__":
    main()
