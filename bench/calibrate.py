#!/usr/bin/env python3
"""Readings that set the limits and the rate, on the chip, in one process.

    python3 bench/calibrate.py train --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3]
    python3 bench/calibrate.py serve --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 5]
    python3 bench/calibrate.py sweep --workload <cell> --rates 500,1000 \\
        [--seconds 10]

``train`` drives the cell's session through its first steps on each seed
(no window) and prints the compared numbers of the program (the lower
readings). On the control seeds it prints those of the control and of
the step's faults, each in the program's place: the reference one
precision step down (three bf16 passes), half of every batch left out,
and one token of every batch altered. On the fault seeds it plants each
of the host pipeline's faults in the program (``harness.faults``)
and prints the pipeline's numbers over as many batches as set-up hands
over, drawn from the pipeline alone. ``serve`` runs short windows at the
cell's rate and prints the program's numbers, and on the control seeds those of int8-
and fp8-scored dense top-k in the server's place. ``sweep`` runs the
serving cell at each rate and prints the latency percentiles and how
the backlog grew, to find the highest sustained rate. Each reading is
one JSON line. The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

from harness import device, faults, spec  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def train(cell, seeds, control_seeds, fault_seeds, fault_names) -> None:
    import numpy as np

    from harness import pipeline, reference, train as drv, zipf

    stream = cell.traffic["layout"] == "stream"
    t_sub = float(cell.config["w2v"]["subsample_t"])

    def pipeline_numbers(st, seed):
        return pipeline.check(st.pipe.handed, st.corpus, stream, st.counts,
                              t_sub, zipf.rng_for(seed, zipf.NEGATIVE_TAG))

    for seed in seeds:
        t = time.perf_counter()
        st = drv.start(cell.config, cell.traffic, seed)
        st.stream.close()
        st.sess = st.stream = None
        prog_s = time.perf_counter() - t
        t = time.perf_counter()
        v, cfg = int(cell.config["vocab_size"]), st.cfg
        lrs = [m.lr for m in st.warm]
        kept = st.kept()
        ref = drv.reference_states(cfg.seed, v, cfg.dim, cfg.fixed_window,
                                   kept, lrs)
        numbers = drv.compare(st.prog, ref, kept, cfg.fixed_window)
        numbers.update(pipeline_numbers(st, seed))
        emit(cell=cell.name, seed=seed, side="program", numbers=numbers,
             start_s=prog_s, check_s=time.perf_counter() - t,
             words=[b.words for b in st.pipe.handed])
        if seed not in control_seeds:
            continue
        st.prog = None
        half = [(t_, n, np.where(np.arange(len(ln)) % 2 == 1, 0, ln))
                for t_, n, ln in kept]
        altered = []
        for t_, n, ln in kept:
            t_ = t_.copy()
            t_[0, 1] = (t_[0, 1] + 1) % v
            altered.append((t_, n, ln))
        sides = {"control_bf16x3": (kept, reference.mm_bf16x3),
                 "fault_half_batch": (half, reference.mm_f32),
                 "fault_token_altered": (altered, reference.mm_f32)}
        for side, (batches, mm) in sides.items():
            got = drv.reference_states(cfg.seed, v, cfg.dim,
                                       cfg.fixed_window, batches, lrs, mm)
            emit(cell=cell.name, seed=seed, side=side,
                 numbers=drv.compare(got, ref, kept, cfg.fixed_window))
            del got
    for seed in fault_seeds:
        for side in fault_names:
            with faults.PIPELINE[side]():
                sess, pipe, cfg, corpus, counts = drv.make_session(
                    cell.config, cell.traffic, seed)
                it = pipe.batches(pad_len=cfg.resolved_pad_len, epoch=0)
                for _ in range(drv.CHECK_STEPS + 1):
                    next(it)
                it.close()
            st = drv.Started(None, pipe, cfg, None, None, None, seed, corpus,
                             counts)
            emit(cell=cell.name, seed=seed, side=side,
                 numbers=pipeline_numbers(st, seed))
            del sess, st


class _Answer:
    def __init__(self, ids, scores):
        self.ids, self.scores = ids[None], scores[None]


def serve(cell, seeds, control_seeds, seconds, devices) -> None:
    import jax

    from harness import reference, serve as drv, zipf

    for seed in seeds:
        out = drv.run(cell, seed, seconds, time.perf_counter(), devices,
                      log=lambda *a: None)
        emit(cell=cell.name, seed=seed, side="program",
             numbers=out["numbers"], e2e=out["e2e"],
             batches=out["rec"]["batches"], queries=out["rec"]["queries"])
        if seed not in control_seeds:
            continue
        v, d = int(cell.config["vocab_size"]), int(cell.config["w2v"]["dim"])
        table = reference.serve_table(
            jax.random.PRNGKey(zipf.program_seed(seed)), v, d)
        law = zipf.ZipfLaw(v, cell.config["zipf_exponent"])
        ids = law.draw(zipf.rng_for(seed, zipf.QUERY_TAG),
                       int(cell.traffic["check_sample"]))
        k = int(cell.traffic["k"])
        for side, mm in (("control_int8", reference.mm_int8),
                         ("control_fp8", reference.mm_fp8),
                         ("program_precision_bf16", reference.mm_bf16)):
            got_i, got_s = reference.dense_topk(table, ids, k, mm)
            answers = [_Answer(i, s) for i, s in zip(got_i, got_s)]
            emit(cell=cell.name, seed=seed, side=side,
                 numbers=drv.compare(table, ids, answers, k))


def sweep(cell, rates, seconds, devices, seed=20261017) -> None:
    from harness import openloop, serve as drv

    for rate in rates:
        t = time.perf_counter()
        out = drv.run(cell, seed, seconds, t, devices, log=lambda *a: None,
                      rate=rate)
        lat = out["latencies"]
        half = len(lat) // 2
        emit(cell=cell.name, rate=rate, e2e=out["e2e"],
             completed_per_s=out["rec"]["served"] / out["rec"]["window_s"],
             p95_first_half_ms=openloop.percentile(lat[:half], 95) * 1e3,
             p95_second_half_ms=openloop.percentile(lat[half:], 95) * 1e3,
             batches=out["rec"]["batches"], queries=out["rec"]["queries"],
             failed=out["failed"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("train", "serve", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    device.use_checkout_cache()
    try:
        devices, _ = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    if args.mode == "train":
        train(cell, _seeds(args.seeds), set(_seeds(args.control_seeds)),
              _seeds(args.fault_seeds),
              [f for f in args.faults.split(",") if f] or sorted(
                  faults.PIPELINE))
    elif args.mode == "serve":
        serve(cell, _seeds(args.seeds), set(_seeds(args.control_seeds)),
              args.seconds, devices)
    else:
        sweep(cell, [float(r) for r in args.rates.split(",")],
              args.seconds, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
