"""Train a language model on the synthetic pipeline with checkpoint/resume.

Default preset is CPU-sized; `--arch smollm-360m --full` uses the real
360M config (for actual hardware). Demonstrates the fault-tolerance path:
Ctrl-C mid-run, re-launch with the same command, training resumes from the
last checkpoint bitwise-exactly.

    PYTHONPATH=src python examples/train_lm.py --steps 20
"""

import argparse

from repro.config import TrainConfig, get_config, reduced_config
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--full", action="store_true",
                    help="use the full config (needs real hardware)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced_config(cfg, num_layers=4, d_model=128, head_dim=32,
                             d_ff=256 if cfg.d_ff else 0)
    tc = TrainConfig(
        total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
        checkpoint_every=10, checkpoint_dir=args.ckpt, learning_rate=1e-3,
    )
    tr = Trainer(cfg, tc, global_batch=args.batch, seq_len=args.seq)
    start = tr.init_or_resume(resume=True)
    print(f"training {cfg.name} from step {start} "
          f"({cfg.num_params() / 1e6:.1f}M params)")
    out = tr.run(args.steps - start)
    losses = out["losses"]
    if losses:
        print(f"steps {start}..{out['final_step']}: "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    if tr.watchdog.flagged:
        print("straggler steps:", tr.watchdog.flagged)


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
