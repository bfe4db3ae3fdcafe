"""What the per-layer metric readers share: the record's counts, the
device time of named programs in the reduced trace, and the training
metrics that several cells read under names of their own (one per
end-to-end metric they move)."""
from __future__ import annotations

from typing import Iterable

from harness import counts

# ops.step's single-device jit wraps the backend's update in ``run``
STEP_PROGRAMS = ("jit_run",)


def trained_words(rec) -> int:
    return sum(s["words"] for s in rec["steps"])


def per_word(rec):
    """FLOPs and bytes of one trained word at the configuration's shapes
    (f32 rows, as the tables are stored)."""
    w2v = rec["config"]["w2v"]
    w_f = (w2v["window"] + 1) // 2
    return (counts.window_flops(w_f, w2v["negatives"], w2v["dim"]),
            counts.window_bytes(w_f, w2v["negatives"], w2v["dim"]))


def program_seconds(summary, names: Iterable[str]) -> float:
    """Device seconds of the XLA modules whose name (up to any
    parenthesised suffix) is one of ``names``."""
    names = set(names)
    return sum(sec for mod, sec in summary.program_s.items()
               if mod.split("(")[0] in names)


def idle_pct(rec, kind: str):
    tr = rec.get("trace")
    if rec["kind"] != kind or tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def host_wait_pct(rec):
    """Share of the window the session spent waiting on the host
    pipeline: the waits of the hand-overs inside the window, timed around
    each (``bench.fetch``), over the window (layer: session,
    ``core/trainer.py``). The session's own ``StepMetrics.fetch_seconds``
    charges each wait to the step after it, one wait off over a window."""
    if rec["kind"] != "train" or not rec["steps"]:
        return None
    return 100.0 * sum(s["fetch_s"] for s in rec["steps"]) / rec["window_s"]


def pad_fill_pct(rec):
    """Real words over padded positions of the batches the window
    trained, counted from each batch's own ``tokens`` shape (layer: host
    pipeline, ``data/batching.py``, ``data/prefetch.py``,
    ``data/negatives.py``)."""
    if rec["kind"] != "train" or not rec["steps"]:
        return None
    positions = sum(s["positions"] for s in rec["steps"])
    return 100.0 * trained_words(rec) / positions


def step_roofline_pct(rec):
    """The training step's share of its roofline: the least time the chip
    needs for the window's real words (the larger of FLOPs over the bf16
    peak and bytes over the HBM peak; bytes bind for SGNS) over the device
    time of the step programs in the traced window (layer: kernels,
    ``kernels/fullw2v.py`` via ``kernels/ops.py``)."""
    tr = rec.get("trace")
    if rec["kind"] != "train" or tr is None:
        return None
    device_s = program_seconds(tr, STEP_PROGRAMS)
    words = trained_words(rec)
    if device_s <= 0 or words <= 0:
        return None
    flops, nbytes = per_word(rec)
    least, _ = counts.least_seconds(words * flops, words * nbytes,
                                    rec["peak"])
    return 100.0 * least / device_s


def mfu_pct(rec):
    """Model FLOP utilization of training: FLOPs per real word times the
    real words trained in the traced window, over the traced window's
    seconds times chips times the bf16 peak (layer: whole step)."""
    tr = rec.get("trace")
    if rec["kind"] != "train" or tr is None or tr.window_s <= 0:
        return None
    flops, _ = per_word(rec)
    return 100.0 * trained_words(rec) * flops / (
        tr.window_s * rec["chips"] * rec["peak"].bf16_flops)
