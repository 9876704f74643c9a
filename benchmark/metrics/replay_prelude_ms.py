"""A catchup's work before replay: the node's ``catchup.phase.verify``
(history archive state, the header chain downloaded and verified, the
buckets and tx sets downloaded and decoded) plus ``catchup.phase.apply``
(bucket apply) timers in ``app.metrics``, in ms, averaged over the
traced window's catchups that reached replay."""
LAYER = "catchup history"
UNIT = "ms"
MOVES = "applied_tx_per_s"


def read(data):
    vals = [c["prelude_s"] for c in data.get("catchups") or []
            if c["prelude_s"] is not None]
    return sum(vals) / len(vals) * 1000.0 if vals else None
