"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps.

Uses the production substrates exactly as the full-scale launcher does:
deterministic data pipeline, microbatched train step, async atomic
checkpoints, crash-recovery supervisor — on a llama-family config sized
to ~100M params so it runs on this CPU container.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import dataclasses

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.data.pipeline import DataConfig, make_batch
from repro.models import LM
from repro.runtime.supervisor import FailureInjector, TrainSupervisor
from repro.training.optim import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step


def config_100m():
    """stablelm family scaled to ~100M params."""
    base = get_config("stablelm-12b")
    return dataclasses.replace(
        base, name="stablelm-100m", num_layers=6, d_model=512,
        num_heads=8, num_kv_heads=4, head_dim=64, d_ff=1536,
        vocab_size=32768, attn_q_block=128, attn_kv_block=128)


def main(argv=None):
    use_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--ckpt-dir", default="/tmp/repro_100m")
    args = p.parse_args(argv)

    cfg = config_100m()
    model = LM(cfg)
    print(f"{cfg.name}: {cfg.param_count()/1e6:.0f}M params")

    opt_cfg = AdamWConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    global_batch=args.batch)

    state = init_train_state(model, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(model, opt_cfg, num_microbatches=2,
                                      remat=True))
    losses = []

    def logged(state, batch):
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
        s = int(state["opt"]["step"])
        if s % 25 == 0:
            print(f"step {s:4d}  loss {losses[-1]:.4f}  "
                  f"lr {float(m['lr']):.2e}", flush=True)
        return state, m

    sup = TrainSupervisor(
        make_step=lambda n: logged, make_batch=lambda s: make_batch(dc, s),
        init_state=state, ckpt=CheckpointManager(args.ckpt_dir),
        ckpt_every=100, injector=FailureInjector([]))
    report = sup.run(args.steps)
    first, last = sum(losses[:10]) / 10, sum(losses[-10:]) / 10
    print(f"\n{report.steps_run} steps; loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'}); "
          f"{report.checkpoints_saved} checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
