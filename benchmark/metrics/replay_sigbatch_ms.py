"""Wall time of one checkpoint's signature batch in catchup replay: the
node's ``catchup.replay.sigbatch`` span (collect every ledger's triples,
one device dispatch, wait, split the verdicts per ledger), read from its
timer in ``app.metrics``, in ms, averaged over the traced window's
checkpoints."""
LAYER = "catchup replay"
UNIT = "ms"
MOVES = "applied_tx_per_s"


def read(data):
    catchups = data.get("catchups") or []
    n = sum(c["sigbatch_s"][0] for c in catchups)
    if not n:
        return None
    return sum(c["sigbatch_s"][1] for c in catchups) / n * 1000.0
