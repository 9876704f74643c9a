"""Share of the parallel-apply planner's order-book rows that the node's
memo of resting offers served instead of decoding them: 100 x
``book_rows_reused`` / ``book_rows`` from each close's
``last_close_phases["parallel"]``, summed over the traced window's
closes.  A node without the memo reports neither count: nothing."""
LAYER = "ledger close"
UNIT = "%"
MOVES = "applied_tx_per_s"


def read(data):
    pars = [c["phases"].get("parallel") or {}
            for c in data.get("closes") or []]
    pars = [p for p in pars if "book_rows" in p and "book_rows_reused" in p]
    rows = sum(p["book_rows"] for p in pars)
    if not rows:
        return None
    return 100.0 * sum(p["book_rows_reused"] for p in pars) / rows
