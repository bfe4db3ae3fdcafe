"""FLOP utilization of serving: the FLOPs of scoring every real query
served in the traced window against the whole table, over the traced
window's seconds times chips times the bf16 peak (layer: whole step)."""
from harness import counts


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "serve" or tr is None or tr.window_s <= 0:
        return None
    v, d = rec["config"]["vocab_size"], rec["config"]["w2v"]["dim"]
    work = rec["served"] * counts.query_flops(v, d)
    return 100.0 * work / (tr.window_s * rec["chips"]
                           * rec["peak"].bf16_flops)
