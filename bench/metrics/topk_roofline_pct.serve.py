"""The top-k program's share of its roofline: per dispatched batch, the
least time of one sweep over the (V, d) f32 table (its bytes at the HBM
peak bind; the FLOPs of the real query rows are counted too) over the
device time of the top-k programs in the traced window (layer: serving
kernels, ``serve/query.py::make_topk_fn``)."""
from harness import counts, readers

# make_topk_fn jits the shard_map of its ``local`` body
TOPK_PROGRAMS = ("jit_local",)


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "serve" or tr is None or rec["batches"] <= 0:
        return None
    device_s = readers.program_seconds(tr, TOPK_PROGRAMS)
    if device_s <= 0:
        return None
    v, d = rec["config"]["vocab_size"], rec["config"]["w2v"]["dim"]
    least, _ = counts.least_seconds(
        rec["served"] * counts.query_flops(v, d),
        rec["batches"] * counts.sweep_bytes(v, d), rec["peak"])
    return 100.0 * least / device_s
