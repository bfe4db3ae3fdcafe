"""Embedding query server CLI (DESIGN.md §10).

Loads the newest checkpoint under ``--ckpt-dir`` into a sharded
:class:`~repro.serve.index.EmbeddingIndex`, stands up the batching
:class:`~repro.serve.server.EmbeddingServer` behind a
:class:`~repro.serve.snapshot.SnapshotWatcher`, answers a scripted query
load, and prints grep-able stats (the serve-smoke CI job's interface).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --ckpt-dir /tmp/ckpt \
      --queries 64 --check-oracle
  PYTHONPATH=src python -m repro.launch.serve --ckpt-dir /tmp/ckpt \
      --shards 2 --follow 10
"""
from __future__ import annotations

import argparse
import logging
import sys
import time


def main() -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoint directory to serve from (and follow)")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve over N vocab shards (on CPU, N fake host "
                         "devices are synthesized); 0/1 = single device — "
                         "still the sharded code path on a 1-shard layout")
    ap.add_argument("--batch-size", type=int, default=32,
                    help="padded device batch the request coalescer cuts at")
    ap.add_argument("--deadline-ms", type=float, default=2.0,
                    help="max wait for co-riders before a batch is cut "
                         "short")
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--queries", type=int, default=64,
                    help="scripted random queries to answer before exit")
    ap.add_argument("--mode", default="both",
                    choices=("nn", "analogy", "both"))
    ap.add_argument("--check-oracle", action="store_true",
                    help="recompute every response against the dense "
                         "single-host oracle for its snapshot step; "
                         "exit 1 on any mismatch")
    ap.add_argument("--follow", type=float, default=0.0,
                    help="after the scripted load, keep serving this many "
                         "seconds and report hot-swaps as they happen")
    ap.add_argument("--poll-s", type=float, default=0.25,
                    help="snapshot watcher poll cadence")
    ap.add_argument("--hot-frac", type=float, default=0.1,
                    help="serving hot-head fraction for replicated "
                         "(non-split) checkpoints")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.shards > 1:
        # fake host devices must exist BEFORE jax initializes its backends
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.shards}")

    import jax
    import numpy as np

    from repro.launch.cache import enable_compile_cache
    from repro.serve import EmbeddingIndex, EmbeddingServer, SnapshotWatcher
    enable_compile_cache()
    from repro.serve.query import dense_topk

    mesh = None
    if args.shards > 1:
        from repro.launch.mesh import make_host_mesh
        if jax.device_count() < args.shards:
            print(f"error: --shards {args.shards} needs {args.shards} "
                  f"devices, have {jax.device_count()}", file=sys.stderr)
            return 2
        mesh = make_host_mesh(model=1)

    def on_swap(old, new):
        print(f"swap: step {old.step if old else None} -> {new.step}",
              flush=True)

    watcher = SnapshotWatcher(args.ckpt_dir, mesh=mesh, poll_s=args.poll_s,
                              on_swap=on_swap)
    watcher.start()
    try:
        idx = watcher.wait_ready(timeout=60.0)
    except (TimeoutError, FileNotFoundError) as e:
        print(f"error: {e}", file=sys.stderr)
        watcher.stop()
        return 2
    print(f"serving: step={idx.step} vocab={idx.vocab_size} dim={idx.dim} "
          f"shards={idx.n_shards} hot={idx.placement.hot}")

    rng = np.random.default_rng(args.seed)
    server = EmbeddingServer(watcher, batch_size=args.batch_size,
                             deadline_ms=args.deadline_ms, k=args.k)
    kinds = {"nn": ("nn",), "analogy": ("analogy",),
             "both": ("nn", "analogy")}[args.mode]
    pending = []
    t0 = time.perf_counter()
    for i in range(args.queries):
        kind = kinds[i % len(kinds)]
        n = 1 + int(rng.integers(min(4, args.batch_size)))
        shape = (n,) if kind == "nn" else (n, 3)
        ids = rng.integers(idx.vocab_size, size=shape).astype(np.int32)
        pending.append((kind, ids, server.submit(kind, ids)))
    results = [(kind, ids, req.wait(60.0)) for kind, ids, req in pending]
    wall = time.perf_counter() - t0

    mismatches = 0
    if args.check_oracle:
        oracles = {}
        for kind, ids, res in results:
            step = res.snapshot_step
            if step not in oracles:
                oracles[step] = EmbeddingIndex.load(
                    args.ckpt_dir, step=step, mesh=mesh,
                    hot_frac=args.hot_frac).dense_embeddings()
            want_ids, want_sc = dense_topk(oracles[step], ids, k=args.k,
                                           mode=kind)
            if not (np.array_equal(res.ids, want_ids)
                    and np.allclose(res.scores, want_sc, atol=1e-5)):
                mismatches += 1
        print(f"oracle_parity={'ok' if mismatches == 0 else 'FAIL'} "
              f"checked={len(results)} mismatches={mismatches}")

    lat = np.asarray(server.latencies_us, np.float64)
    rows = sum(r.ids.shape[0] for _, _, r in results)
    print(f"serve_stats: queries={rows} batches={server.batches} "
          f"qps={rows / max(wall, 1e-9):,.0f} "
          f"p50_us={np.percentile(lat, 50):,.0f} "
          f"p99_us={np.percentile(lat, 99):,.0f}")

    if args.follow > 0:
        swaps0 = watcher.swaps
        print(f"following {args.ckpt_dir} for {args.follow:.0f}s "
              f"(poll every {args.poll_s}s)...")
        deadline = time.monotonic() + args.follow
        while time.monotonic() < deadline:
            time.sleep(min(0.2, args.poll_s))
        print(f"follow_done: swaps={watcher.swaps - swaps0} "
              f"now_serving_step={watcher.current().step}")

    server.close()
    watcher.stop()
    return 1 if mismatches else 0


if __name__ == "__main__":
    sys.exit(main())
