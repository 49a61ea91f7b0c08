"""End-to-end training driver: annotative-index-backed data → transformer.

The full pipeline: ingest a corpus into the dynamic index, run the dedup +
segmentation annotation stages, then train an LM whose batches are hydrated
from 'seg:' extents — with periodic checkpoints, an injected crash, and a
restart that resumes the exact batch stream.

    PYTHONPATH=src python examples/train_lm.py --steps 60            # smoke
    PYTHONPATH=src python examples/train_lm.py --preset 100m ...     # big
"""

import argparse
import dataclasses
import os
import tempfile
import time

import jax

from repro.core import DynamicIndex, Warren
from repro.data.pipeline import (IndexedCorpusLoader, ingest,
                                 mark_duplicates, segment)
from repro.data.synth import doc_generator
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig, run_with_restarts

PRESETS = {
    "smoke": T.TransformerConfig(
        name="lm-smoke", n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
        head_dim=32, d_ff=256, vocab=4096, dtype="float32", remat=False),
    "20m": T.TransformerConfig(
        name="lm-20m", n_layers=6, d_model=384, n_heads=6, n_kv_heads=2,
        head_dim=64, d_ff=1024, vocab=8192, dtype="float32", remat=False),
    "100m": T.TransformerConfig(
        name="lm-100m", n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
        head_dim=64, d_ff=2048, vocab=16384, dtype="float32"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--docs", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--crash-at", type=int, default=None,
                    help="inject a failure to demo checkpoint/restart")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = PRESETS[args.preset]
    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                             f"lm_ckpt_{os.getpid()}")

    # ---- stage 1-3: index-backed data pipeline ------------------------- #
    warren = Warren(DynamicIndex())
    t0 = time.time()
    n = ingest(warren, doc_generator(0, args.docs, mean_len=120))
    dups = mark_duplicates(warren)
    segs = segment(warren, window=args.seq, stride=args.seq // 2)
    print(f"pipeline: {n} docs, {dups} dups, {segs} segments "
          f"({time.time() - t0:.1f}s)")

    params = T.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {n_params / 1e6:.1f}M params")

    def make_trainer():
        loader = IndexedCorpusLoader(warren, vocab=cfg.vocab,
                                     batch=args.batch, seq_len=args.seq)
        tc = TrainerConfig(
            total_steps=args.steps, ckpt_every=max(args.steps // 4, 1),
            ckpt_dir=ckpt_dir, log_every=max(args.steps // 10, 1),
            opt=AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps))
        return Trainer(lambda p, b: T.loss_fn(p, b, cfg),
                       T.init_params(cfg, jax.random.PRNGKey(0)), tc, loader,
                       data_state_fn=loader.state,
                       data_restore_fn=loader.restore)

    t0 = time.time()
    trainer = run_with_restarts(make_trainer, fail_at=args.crash_at)
    dt = time.time() - t0
    if not trainer.metrics_log:      # resumed at/after total_steps
        print(f"nothing to do: checkpoint already at step {trainer.step}")
        return
    first, last = trainer.metrics_log[0], trainer.metrics_log[-1]
    print(f"trained {trainer.step} steps in {dt:.1f}s "
          f"({trainer.step / dt:.2f} steps/s)")
    print(f"loss {first['loss']:.3f} (step {first['step']}) -> "
          f"{last['loss']:.3f} (step {last['step']})")
    if first["step"] <= args.steps // 2:  # fresh-enough run to judge trend
        assert last["loss"] < first["loss"], "loss did not improve"
    print(f"checkpoints in {ckpt_dir}")


if __name__ == "__main__":
    main()
