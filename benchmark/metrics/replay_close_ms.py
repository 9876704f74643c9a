"""Wall time of one replayed ledger's close: the ``ledger.close`` span
of each close record in the node's flight recorder, in ms, averaged over
the ledgers the traced window's catchups replayed."""
LAYER = "ledger close"
UNIT = "ms"
MOVES = "applied_tx_per_s"


def read(data):
    vals = [ms for c in data.get("catchups") or [] for ms in c["close_ms"]]
    return sum(vals) / len(vals) if vals else None
