"""End-to-end launcher.

Two paths, per the paper's kind:
  * `w2v`  — the paper's workload: FULL-W2V embedding training (default).
  * `lm`   — any assigned architecture (reduced or full), synthetic tokens.

Examples:
  PYTHONPATH=src python -m repro.launch.train w2v --vocab 400000 --epochs 2
  PYTHONPATH=src python -m repro.launch.train lm --arch qwen3-8b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import logging
import sys

import jax
import numpy as np

from repro.kernels import registry


def _tables_shards(tables: str) -> int:
    """The ``shards=N`` clause of a ``--tables`` spec, parsed textually so
    :func:`main` can synthesize host devices *before* jax initializes its
    backends (the full parse lives in ``kernels.tables``, which imports
    jax)."""
    import re
    m = re.search(r"(?:^|,)\s*shards\s*=\s*(\d+)", tables or "")
    return int(m.group(1)) if m else 0


def run_w2v(args) -> int:
    import hashlib

    from repro import frontends
    from repro.configs.w2v import W2VConfig
    from repro.core.quality import evaluate
    from repro.core.trainer import TrainSession
    from repro.data.prefetch import AsyncBatchingPipeline, make_pipeline

    cfg = W2VConfig(dim=args.dim, epochs=args.epochs, min_count=1,
                    subsample_t=0.0, negatives=args.negatives,
                    window=args.window,
                    sentences_per_batch=args.sentences_per_batch,
                    max_sentence_len=args.max_sentence_len,
                    tile_windows=args.tile_windows,
                    tile_gemm_windows=args.tile_gemm_windows,
                    pad_len=args.pad_len,
                    prefetch_workers=args.prefetch_workers,
                    prefetch_depth=args.prefetch_depth,
                    prefetch_mode=args.prefetch_mode,
                    vocab_shard=bool(args.vocab_shard),
                    hot_vocab_frac=args.hot_vocab_frac,
                    tables=args.tables)
    # every workload rides the same engine: the frontend adapts a corpus
    # (words, graph walks, documents, subword bags) into the batch schema
    # and attaches its table extras to the pipeline (DESIGN.md §12)
    workload = frontends.get(args.workload).build(
        cfg, vocab=args.vocab, clusters=args.clusters,
        sentences=args.sentences,
        p=args.node2vec_p, q=args.node2vec_q,
        walk_length=args.walk_length, walks_per_node=args.walks_per_node,
        docs=args.docs, buckets=args.subword_buckets, seed=0)
    cfg, corpus = workload.cfg, workload.corpus
    pipe = make_pipeline(corpus, cfg)
    workload.attach(pipe)
    extras = (f" (+{pipe.extra_rows} {args.workload} rows)"
              if pipe.extra_rows else "")
    print(f"workload={args.workload} vocab={pipe.vocab.size}{extras} "
          f"params={2 * pipe.table_rows * cfg.dim / 1e6:.1f}M "
          f"words/epoch={pipe.epoch_words}")
    if isinstance(pipe, AsyncBatchingPipeline):
        print(f"pipeline=async(workers={pipe.workers} depth={pipe.depth} "
              f"mode={pipe.mode})")
    else:
        print("pipeline=sync")
    mesh = None
    n_shards = max(args.vocab_shard, _tables_shards(args.tables))
    if n_shards > 1:
        from repro.launch.mesh import make_host_mesh
        if jax.device_count() < n_shards:
            print(f"error: {n_shards}-shard tables need {n_shards} "
                  f"devices, have {jax.device_count()}", file=sys.stderr)
            return 2
        mesh = make_host_mesh(model=1)
    trainer = TrainSession(pipe, cfg, backend=args.backend, mesh=mesh,
                           ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every)
    print(f"backend={trainer.backend}")
    if trainer.spec.is_mixed:
        s = trainer.spec
        print(f"tables: hot={s.hot_dtype} cold={s.cold_dtype} "
              f"master_copy={s.master_copy}")
    if trainer.placement is not None:
        p = trainer.placement
        print(f"vocab_shard: hot={p.hot} cold={p.cold} shards={p.n_shards} "
              f"rows/device={p.rows_per_device} "
              f"(replicated would be {p.vocab_size})")
    if trainer.resumed_step is not None:
        print(f"resumed from checkpoint batch {trainer.resumed_step} "
              f"({trainer.state.words_seen:,} words seen)")
    batches0 = trainer.state.batches_seen
    resilient = (args.max_restarts > 0 or args.step_timeout > 0
                 or args.health_every > 0)
    if resilient:
        trainer.train_resilient(
            max_batches=args.max_batches,
            max_restarts=args.max_restarts or 3,
            step_timeout_s=args.step_timeout,
            health_every=args.health_every,
            reset_after=args.reset_after)
        r = trainer.last_report
        print(f"resilience: restarts={r.restarts} rollbacks={r.rollbacks} "
              f"health_failures={r.health_failures} timeouts={r.timeouts} "
              f"skipped={r.batches_skipped} "
              f"recovery_seconds={r.recovery_seconds:.3f}")
    else:
        trainer.train(max_batches=args.max_batches)
    if args.ckpt_dir:
        print("checkpoint:", trainer.save_checkpoint())
    host = trainer.host_report(trainer.state.batches_seen - batches0)
    print(f"throughput: {trainer.words_per_sec:,.0f} words/sec "
          f"({trainer.state.words_seen:,} words) "
          f"host_wait={host['host_wait']:.3f} "
          f"finalize_ms={host['finalize_ms']:.2f} "
          f"neg_draws_per_word={host['neg_draws_per_word']:.2f}")
    # bit-exactness witness: identical configs must print identical digests
    # regardless of prefetch_workers (CI's determinism smoke greps this).
    # Covers every table leaf — hot, cold, and int8 scales — so quantized
    # storage (keyed stochastic rounding included) is held to the same
    # bit-determinism bar as f32
    digest = hashlib.sha1()
    st = trainer.state
    for part in (st.w_in, st.w_out, st.cold_in, st.cold_out,
                 st.scale_in, st.scale_out):
        if part is not None:
            digest.update(np.asarray(part).tobytes())
    print(f"final_digest={digest.hexdigest()}")
    if corpus.clusters is not None:
        inv = np.zeros(pipe.vocab.size, dtype=int)
        for w, i in pipe.vocab.ids.items():
            inv[i] = corpus.clusters[w]
        # frontend extras (doc rows, n-gram buckets) sit past the
        # vocabulary — cluster quality is a word/node-vector property
        metrics = evaluate(trainer.embeddings()[:pipe.vocab.size], inv)
        print("quality:", {k: round(v, 4) for k, v in metrics.items()})
    return 0


def run_lm(args) -> int:
    import jax.numpy as jnp

    from repro.configs.base import get_arch, get_smoke
    from repro.train.loop import LoopConfig, Trainer
    from repro.train.optim import AdamWConfig

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    loop = LoopConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every,
                      microbatches=args.microbatches)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(args.steps // 20, 1))
    trainer = Trainer(cfg, opt, loop, batch=args.batch, seq=args.seq)
    out = trainer.train()
    losses = out["losses"]
    print(f"final step {out['final_step']}; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")
    return 0


def main() -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    w = sub.add_parser("w2v")
    from repro import frontends
    w.add_argument("--workload", default="w2v",
                   choices=frontends.names(),
                   help="workload frontend (DESIGN.md §12): plain w2v, "
                        "node2vec random walks, PV-DM doc2vec, or "
                        "fastText-style subword bags — all through the "
                        "same engine, batching, sharding, and serving")
    w.add_argument("--node2vec-p", type=float, default=1.0,
                   help="node2vec return parameter (1/p weight on "
                        "backtracking to the previous node)")
    w.add_argument("--node2vec-q", type=float, default=0.5,
                   help="node2vec in-out parameter (1/q weight on "
                        "exploring away; q<1 favors communities)")
    w.add_argument("--walk-length", type=int, default=40,
                   help="node2vec: nodes per walk")
    w.add_argument("--walks-per-node", type=int, default=10,
                   help="node2vec: walks started from each node")
    w.add_argument("--docs", type=int, default=64,
                   help="doc2vec: number of synthetic documents")
    w.add_argument("--subword-buckets", type=int, default=4096,
                   help="subword: hashed n-gram bucket rows appended "
                        "past the vocabulary")
    w.add_argument("--vocab", type=int, default=8192)
    w.add_argument("--clusters", type=int, default=64)
    w.add_argument("--sentences", type=int, default=20000)
    w.add_argument("--dim", type=int, default=128)
    w.add_argument("--window", type=int, default=5)
    w.add_argument("--negatives", type=int, default=5)
    w.add_argument("--epochs", type=int, default=2)
    w.add_argument("--sentences-per-batch", type=int, default=2048)
    w.add_argument("--max-sentence-len", type=int, default=64)
    w.add_argument("--max-batches", type=int, default=None)
    w.add_argument("--tile-windows", type=int, default=1,
                   help="T: windows fused per kernel step (DESIGN.md §4)")
    w.add_argument("--tile-gemm-windows", type=int, default=4,
                   help="G: windows per GEMM group inside a tile")
    w.add_argument("--pad-len", type=int, default=0,
                   help="padded batch length L (0: min(max-sentence-len, "
                        "1024))")
    w.add_argument("--prefetch-workers", type=int, default=0,
                   help="host pipeline workers; 0 = synchronous batching, "
                        ">0 overlaps batching with device updates "
                        "(bit-identical stream, DESIGN.md §4.1)")
    w.add_argument("--prefetch-depth", type=int, default=2,
                   help="bounded prefetch queue: finalized batches allowed "
                        "in flight ahead of the device")
    w.add_argument("--prefetch-mode", default="thread",
                   choices=("thread", "process"),
                   help="worker kind: threads (numpy finalize releases the "
                        "GIL) or processes (python-heavy encode)")
    w.add_argument("--vocab-shard", type=int, nargs="?", const=1, default=0,
                   metavar="N",
                   help="replicate the Zipf-hot vocabulary head and shard "
                        "the cold tail over the mesh data axis "
                        "(DESIGN.md §8); scales trainable vocabulary with "
                        "device count. With a value N > 1, runs over N "
                        "shards (on CPU, N fake host devices are "
                        "synthesized); bare flag = 1-shard layout")
    w.add_argument("--hot-vocab-frac", type=float, default=0.0,
                   help="replicated hot head as a fraction of V "
                        "(0: smallest prefix covering ~90%% of corpus "
                        "occurrences)")
    w.add_argument("--tables", default="",
                   help="table storage spec (DESIGN.md §11), e.g. "
                        "'hot=bf16:frac=0.1,cold=int8,shards=4': per-table "
                        "storage dtypes (f32/bf16 hot, f32/bf16/int8 cold "
                        "with per-row scales), shard count, exchange "
                        "flavor (exchange=exact|dense), and master=1 for "
                        "the f32 master-copy fallback. Subsumes "
                        "--vocab-shard/--hot-vocab-frac, which seed its "
                        "defaults; unsupported backend×dtype combinations "
                        "are rejected at resolve time")
    # choices come from the backend registry, so every registered kernel
    # variant — pipelined, tiled, interpret — is reachable from the CLI
    w.add_argument("--backend", default="auto",
                   choices=registry.cli_choices(),
                   help="kernel backend; 'auto' resolves per platform and "
                        "tile-windows against the registry descriptors")
    w.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory (resumes from the latest "
                        "checkpoint when one exists)")
    w.add_argument("--ckpt-every", type=int, default=0,
                   help="checkpoint every N batches (0: only at exit when "
                        "--ckpt-dir is set)")
    # resilience (DESIGN.md §9): any nonzero flag below drives the run
    # through TrainSupervisor (restore + bit-exact replay on failure)
    w.add_argument("--max-restarts", type=int, default=0,
                   help="supervised recovery: restore the latest good "
                        "checkpoint and replay on step failure, up to N "
                        "restarts per failure burst (0: supervision off "
                        "unless another resilience flag is set)")
    w.add_argument("--step-timeout", type=float, default=0.0,
                   help="watchdog: a batch exceeding this many seconds is "
                        "treated as a failed step (0: no timeout)")
    w.add_argument("--health-every", type=int, default=0,
                   help="probe the tables for NaN/divergence every N "
                        "batches, rolling back on failure (0: no probe)")
    w.add_argument("--reset-after", type=int, default=0,
                   help="refill the restart budget after N consecutive "
                        "good batches (0: budget is cumulative)")
    w.set_defaults(fn=run_w2v)

    l = sub.add_parser("lm")
    l.add_argument("--arch", required=True)
    l.add_argument("--smoke", action="store_true")
    l.add_argument("--steps", type=int, default=100)
    l.add_argument("--batch", type=int, default=8)
    l.add_argument("--seq", type=int, default=128)
    l.add_argument("--lr", type=float, default=3e-4)
    l.add_argument("--microbatches", type=int, default=1)
    l.add_argument("--ckpt-dir", default=None)
    l.add_argument("--ckpt-every", type=int, default=50)
    l.set_defaults(fn=run_lm)

    args = ap.parse_args()
    n_shards = max(getattr(args, "vocab_shard", 0),
                   _tables_shards(getattr(args, "tables", "")))
    if n_shards > 1:
        # synthesize the fake host devices the sharded run needs BEFORE
        # jax initializes its backends (first devices()/dispatch call);
        # import order alone has not initialized them yet
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={n_shards}")
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
