"""Batched SCP quorum-set math as boolean matrix reductions.

Reference seam: ``LocalNode::isQuorumSlice`` / ``isVBlocking`` / ``isQuorum``
(ref src/scp/LocalNode.h:58-78, LocalNode.cpp) — recursive walks over an
``SCPQuorumSet`` tree, called O(messages × qset size) per ballot-protocol
``advanceSlot`` (ref src/scp/BallotProtocol.cpp:1863).  The reference
evaluates one (qset, node-set) pair at a time on CPU.

TPU-first redesign (SURVEY.md §2.17 P6): quorum sets are *tensorised*.
The tensor form covers 2-level quorum sets (validators + inner sets) — the
shape every production stellar validator uses (org-grouped validators).
The wire format legally allows nesting to depth 4
(ref src/scp/QuorumSetUtils.cpp:16 MAXIMUM_QUORUM_NESTING_LEVEL); deeper
sets fall back to the exact host-side evaluation in ``scp.local_node``
(see ``scp.local_node.qset_to_plain``).  A 2-level qset is represented as:

  - ``top_mem``   (N,)   bool  — top-level validator membership
  - ``top_thr``   ()     int32 — top-level threshold
  - ``inner_mem`` (K, N) bool  — inner-set validator membership (zero-padded)
  - ``inner_thr`` (K,)   int32 — inner thresholds (0 ⇒ padding slot, never
                                  satisfied, never counts)

and every primitive becomes a masked matmul + threshold compare, batchable
over *all nodes and all candidate vote-vectors at once* — MXU work instead of
pointer chasing.  All dtypes int32/bool: bitwise deterministic.

A "node set" is a bool vector over the node universe (row of ``votes``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class QSetTensor(NamedTuple):
    """One quorum set (or a batch of them) in tensor form.

    Shapes (unbatched): top_mem (N,), top_thr (), inner_mem (K, N),
    inner_thr (K,).  A leading batch axis B (one qset per local node) is
    supported by every op below.
    """

    top_mem: jnp.ndarray    # bool  (..., N)
    top_thr: jnp.ndarray    # int32 (...,)
    inner_mem: jnp.ndarray  # bool  (..., K, N)
    inner_thr: jnp.ndarray  # int32 (..., K)


def _hits(qs: QSetTensor, sets: jnp.ndarray) -> jnp.ndarray:
    """#top-level members (validators + inner sets) satisfied by each set.

    sets: bool (..., S, N) — S candidate node-sets over an N-node universe.
    returns int32 (..., S).
    """
    s = sets.astype(jnp.int32)
    top = jnp.einsum("...n,...sn->...s", qs.top_mem.astype(jnp.int32), s)
    inner_ct = jnp.einsum(
        "...kn,...sn->...sk", qs.inner_mem.astype(jnp.int32), s
    )
    # padding slots have inner_thr == 0 and must never count
    inner_ok = (inner_ct >= qs.inner_thr[..., None, :]) & (
        qs.inner_thr[..., None, :] > 0
    )
    return top + inner_ok.sum(axis=-1, dtype=jnp.int32)


def is_quorum_slice(qs: QSetTensor, sets: jnp.ndarray) -> jnp.ndarray:
    """Does each node-set contain a slice of ``qs``?  bool (..., S).

    Mirrors LocalNode::isQuorumSlice (ref src/scp/LocalNode.cpp) on a
    2-level qset: satisfied iff #hit members >= threshold.
    """
    return _hits(qs, sets) >= qs.top_thr[..., None]


def is_v_blocking(qs: QSetTensor, sets: jnp.ndarray) -> jnp.ndarray:
    """Is each node-set v-blocking for ``qs``?  bool (..., S).

    Mirrors LocalNode::isVBlocking: S blocks iff the members still
    satisfiable *without* S cannot reach the threshold.  threshold == 0
    (empty qset) is never blocked (ref LocalNode.cpp isVBlockingInternal).
    """
    avail = _hits(qs, ~sets)
    return (avail < qs.top_thr[..., None]) & (qs.top_thr[..., None] > 0)


def contract_to_maximal_quorum(
    qsets: QSetTensor, members: jnp.ndarray
) -> jnp.ndarray:
    """Greatest fixpoint: contract ``members`` to its maximal quorum.

    qsets: batched QSetTensor with leading axis N (one qset per node).
    members: bool (N,) — candidate node set.
    returns bool (N,): the maximal quorum contained in ``members`` (all-False
    if none) — the tensorised equivalent of
    ``QuorumIntersectionChecker::contractToMaximalQuorum`` (ref
    src/herder/QuorumIntersectionCheckerImpl.cpp:407) and the engine behind
    ``LocalNode::isQuorum`` (ref src/scp/LocalNode.h:73): iteratively drop
    nodes whose slice isn't satisfied inside the current set.
    """

    def body(m):
        sat = is_quorum_slice(qsets, m[None, None, :].repeat(m.shape[0], 0))
        return m & sat[..., 0]

    def cond(state):
        m, changed = state
        return changed

    def step(state):
        m, _ = state
        m2 = body(m)
        return m2, jnp.any(m2 != m)

    out, _ = jax.lax.while_loop(cond, step, (members, jnp.asarray(True)))
    return out


def is_quorum(local_qs: QSetTensor, qsets: QSetTensor,
              members: jnp.ndarray) -> jnp.ndarray:
    """Does ``members`` contain a quorum w.r.t. the local node?

    Matches the host oracle ``scp.local_node.is_quorum``: contract to the
    maximal sub-quorum, then require it non-empty AND satisfying the local
    node's slice.  returns scalar bool.
    """
    q = contract_to_maximal_quorum(qsets, members)
    local_ok = is_quorum_slice(local_qs, q[None, :])[0]
    return jnp.any(q) & local_ok


def contract_batch(qsets: QSetTensor, members: jnp.ndarray) -> jnp.ndarray:
    """Batched greatest-fixpoint contraction: members (B, N) -> (B, N).

    The engine of the quorum-intersection scan (BASELINE config #3):
    thousands of candidate subsets contract in one device program.  A
    fixpoint is reached in <= N iterations (each iteration can only drop
    nodes), so a fixed-trip fori_loop keeps the program shape static
    (ref contractToMaximalQuorum,
    src/herder/QuorumIntersectionCheckerImpl.cpp:407)."""
    n = members.shape[-1]

    def step(_, m):
        # for each batch row: node i stays iff its slice is satisfied by
        # the row.  is_quorum_slice(qsets, sets) with qsets batched over N
        # and sets (B, N) needs per-node evaluation of every row:
        # hits (N_qsets) x (B rows) -> evaluate as (B, N): node i vs row b
        s = m.astype(jnp.int32)                       # (B, N)
        top = jnp.einsum("in,bn->bi", qsets.top_mem.astype(jnp.int32), s)
        inner_ct = jnp.einsum(
            "ikn,bn->bik", qsets.inner_mem.astype(jnp.int32), s)
        inner_ok = (inner_ct >= qsets.inner_thr[None, :, :]) & (
            qsets.inner_thr[None, :, :] > 0)
        hits = top + inner_ok.sum(axis=-1, dtype=jnp.int32)   # (B, N)
        sat = hits >= qsets.top_thr[None, :]
        return m & sat

    return jax.lax.fori_loop(0, n, step, members)


# ---------------------------------------------------------------------------
# federated-voting tallies (the BallotProtocol hot loop, batched)
# ---------------------------------------------------------------------------

def federated_accept(
    local_qs: QSetTensor,
    qsets: QSetTensor,
    voted: jnp.ndarray,
    accepted: jnp.ndarray,
    ratified: jnp.ndarray = None,
) -> jnp.ndarray:
    """Batched federated *accept* over C candidate statements.

    local_qs: unbatched QSetTensor (the local node's qset).
    qsets: per-node QSetTensor batch (N leading axis).
    voted/accepted: bool (C, N) — which of the N nodes voted-for/accepted
    each of C candidate statements.
    ratified: optional precomputed federated_ratify(local_qs, qsets,
    voted|accepted) — pass it when the caller also needs the ratify result,
    to avoid running the (expensive) contraction fixpoint twice.
    returns bool (C,).

    Mirrors ``Slot::federatedAccept`` (ref src/scp/Slot.h:188, Slot.cpp):
    accept iff (a) a v-blocking set has accepted, or (b) a quorum (w.r.t.
    the local node) has voted-or-accepted.
    """
    vblock = is_v_blocking(local_qs, accepted)          # (C,)
    if ratified is None:
        ratified = federated_ratify(local_qs, qsets, voted | accepted)
    return vblock | ratified


def federated_ratify(
    local_qs: QSetTensor, qsets: QSetTensor, voted: jnp.ndarray
) -> jnp.ndarray:
    """Batched federated *ratify*: a quorum voted for it.  bool (C,).

    The quorum must satisfy the LOCAL node's slice too (mirrors
    ``LocalNode::isQuorum`` with the local qset as the filter — a disjoint
    quorum among remote voters must NOT ratify; ref src/scp/LocalNode.h:73).
    """

    def one(s):
        q = contract_to_maximal_quorum(qsets, s)
        local_ok = is_quorum_slice(local_qs, q[None, :])[0]
        return jnp.any(q) & local_ok

    return jax.vmap(one)(voted)


# ---------------------------------------------------------------------------
# the live tallies (scp/tally.py): one program per process and shape
# ---------------------------------------------------------------------------
# Called eagerly, federated_ratify's while_loop closes over fresh
# functions on every call, so JAX traces and builds a new executable each
# time.  Jitted at module level, a program is keyed on the argument
# shapes alone: the qsets are arguments, never closure constants.

@jax.jit
def tally_accept(local_qs: QSetTensor, qsets: QSetTensor,
                 accepted: jnp.ndarray,
                 vote_or_accept: jnp.ndarray) -> jnp.ndarray:
    """``Slot::federatedAccept`` for one statement: accepted (1, N) is
    v-blocking, or vote_or_accept (1, N) holds a quorum.  bool ()."""
    return (is_v_blocking(local_qs, accepted)[0]
            | federated_ratify(local_qs, qsets, vote_or_accept)[0])


@jax.jit
def tally_ratify(local_qs: QSetTensor, qsets: QSetTensor,
                 voted: jnp.ndarray) -> jnp.ndarray:
    """``Slot::federatedRatify`` for one statement: voted (1, N) holds a
    quorum.  bool ()."""
    return federated_ratify(local_qs, qsets, voted)[0]


# ---------------------------------------------------------------------------
# host-side construction from python quorum-set descriptions
# ---------------------------------------------------------------------------

def build_qset_tensor(qsets, node_ids, max_inner=None) -> QSetTensor:
    """Pack python quorum sets into a batched QSetTensor.

    qsets: list over nodes; each is ``(threshold, validators, inner_sets)``
    with validators a list of node ids and inner_sets a list of
    ``(threshold, validators)`` (2-level, like the wire format
    ref src/protocol-curr/xdr/Stellar-SCP.x SCPQuorumSet).
    node_ids: ordered universe of node ids (index == tensor column).
    """
    idx = {n: i for i, n in enumerate(node_ids)}
    n = len(node_ids)
    k = max_inner or max((len(q[2]) for q in qsets), default=0) or 1
    b = len(qsets)
    top_mem = np.zeros((b, n), np.bool_)
    top_thr = np.zeros((b,), np.int32)
    inner_mem = np.zeros((b, k, n), np.bool_)
    inner_thr = np.zeros((b, k), np.int32)
    for i, (thr, vals, inners) in enumerate(qsets):
        top_thr[i] = thr
        for v in vals:
            top_mem[i, idx[v]] = True
        for j, (ithr, ivals) in enumerate(inners):
            inner_thr[i, j] = ithr
            for v in ivals:
                inner_mem[i, j, idx[v]] = True
    return QSetTensor(
        jnp.asarray(top_mem),
        jnp.asarray(top_thr),
        jnp.asarray(inner_mem),
        jnp.asarray(inner_thr),
    )
