"""End-to-end driver (paper's kind: embedding training): a ~100M-parameter
Word2Vec model — 400k vocabulary × d=128 × two tables — trained for a few
hundred batches with checkpointing and the full host batching pipeline.

    PYTHONPATH=src python examples/train_100m_w2v.py [--batches 200]
"""
import argparse
import os
import tempfile
import time

import numpy as np

from repro.configs.w2v import W2VConfig
from repro.core.trainer import TrainSession
from repro.data.corpus import synthetic_zipf_corpus
from repro.data.prefetch import make_pipeline


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=200)
    ap.add_argument("--vocab", type=int, default=400_000)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--prefetch-workers", type=int, default=2,
                    help="async host batching workers (0 = synchronous)")
    args = ap.parse_args()

    cfg = W2VConfig(dim=128, window=5, negatives=5, epochs=1, min_count=1,
                    subsample_t=0.0, sentences_per_batch=512,
                    max_sentence_len=64,
                    prefetch_workers=args.prefetch_workers)
    print("building corpus...")
    corpus = synthetic_zipf_corpus(vocab_size=args.vocab,
                                   n_sentences=args.batches * 512,
                                   mean_len=24, zipf_a=1.1, seed=0)
    pipe = make_pipeline(corpus, cfg)   # async when prefetch_workers > 0
    n_params = 2 * pipe.vocab.size * cfg.dim
    print(f"vocab={pipe.vocab.size:,} params={n_params / 1e6:.1f}M")

    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                             "w2v_100m_ckpt")

    # TrainSession owns periodic checkpointing (atomic, pruned) and
    # resumes from the latest checkpoint in ckpt_dir automatically
    trainer = TrainSession(
        pipe, cfg, backend="auto", ckpt_dir=ckpt_dir, ckpt_every=50,
        on_metrics=lambda m: (m.batches_seen % 50 == 0) and print(
            f"  batch {m.batches_seen}: {m.words_seen:,} words "
            f"(checkpointed)"))
    if trainer.resumed_step is not None:
        print(f"resumed from checkpoint batch {trainer.resumed_step}")
    t0 = time.time()
    trainer.train(max_batches=args.batches)
    print(f"trained {trainer.state.words_seen:,} words in "
          f"{time.time() - t0:.0f}s -> {trainer.words_per_sec:,.0f} words/s "
          f"(device busy {trainer.device_busy_frac:.0%})")
    print("final checkpoint:", trainer.save_checkpoint())
    emb = trainer.embeddings()
    print("embedding norms: mean", float(np.linalg.norm(emb, axis=1).mean()))


if __name__ == "__main__":
    main()
