"""Real query rows over padded batch rows of the batches the server
dispatched in the window, from its own ``served`` and ``batches``
counters (layer: server, ``serve/server.py``)."""


def read(rec):
    if rec["kind"] != "serve" or rec["batches"] <= 0:
        return None
    return 100.0 * rec["served"] / (rec["batches"]
                                    * rec["traffic"]["batch_size"])
