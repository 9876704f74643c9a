"""Signatures per device verify dispatch in catchup replay: the node's
``catchup.replay.sigs_batched`` over ``catchup.replay.sig_dispatches``
counters, summed over the traced window's catchups (a checkpoint of 64
ledgers of 1000 txs reads 64,000; a dispatch per ledger would read
about 1,000)."""
LAYER = "catchup replay"
UNIT = "sigs"
MOVES = "applied_tx_per_s"


def read(data):
    catchups = data.get("catchups") or []
    n = sum(c["sig_dispatches"] for c in catchups)
    if not n:
        return None
    return sum(c["sigs_batched"] for c in catchups) / n
