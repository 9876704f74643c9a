#!/usr/bin/env python
"""Quorum-intersection tier shoot-out (VERDICT r4 task 9): native C++
enumerator vs Python+numpy enumerator vs Python+device-batch contractor
at growing SCC sizes.  One number decides which tier earns the default.

Topologies are flat majority cliques with a per-node twist (every node's
qset drops a different neighbour) so the org-collapse reduction cannot
fire and the enumerator genuinely runs.

Writes QUORUM_TIER_BENCH.json.  JAX pinned to CPU: the device tier's
number on this host is the XLA-on-CPU rate; on a real TPU chip the same
code path is the one the artifact's "device" row would re-measure.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ["JAX_PLATFORMS"] = "cpu"


def build_qmap(n):
    from stellar_core_tpu.scp.local_node import make_qset

    nodes = [b"%02d" % i + b"\x00" * 30 for i in range(n)]
    thr = 2 * n // 3 + 1
    qmap = {}
    for i, x in enumerate(nodes):
        members = [v for j, v in enumerate(nodes) if j != (i + 1) % n]
        qmap[x] = make_qset(min(thr, len(members)), members, [])
    return qmap


def main():
    from stellar_core_tpu.herder.quorum_intersection import (
        check_quorum_intersection,
    )

    sizes = [int(x) for x in (sys.argv[1:] or ["16", "24", "32", "48"])]
    rows = []
    for n in sizes:
        qmap = build_qmap(n)
        row = {"scc_size": n}
        for label, kw in (
                ("native", dict(use_native=True, use_device=False)),
                ("python_numpy", dict(use_native=False, use_device=False)),
                ("python_device", dict(use_native=False, use_device=True))):
            t0 = time.perf_counter()
            res = check_quorum_intersection(qmap, max_seconds=120, **kw)
            dt = time.perf_counter() - t0
            row[label] = {"seconds": round(dt, 3), "ok": res.ok,
                          "scanned": res.scanned,
                          "aborted": bool(res.aborted)}
            assert res.ok is True or res.aborted, (label, n, res.ok)
        rows.append(row)
        print(json.dumps(row), flush=True)

    out = {
        "jax_platform": "cpu",
        "note": ("device tier = XLA batch contractor on host CPU here; "
                 "the native C++ tier needs no device at all"),
        "rows": rows,
    }
    with open(os.path.join(REPO, "QUORUM_TIER_BENCH.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
