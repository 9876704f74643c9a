"""TallyEngine: routes the live SCP federated-voting tallies through the
batched TPU kernels in ops/quorum.py (BASELINE config #5 — "pmapped ballot
tallies"; SURVEY.md §2.17 P6).

Per slot, the engine keeps a QSetTensor over the current envelope
universe, rebuilt only when the (node -> qset-hash) map changes.  Each
``Slot.federated_accept/ratify`` call evaluates its statement predicates
on host (cheap python over ≤N statements) and runs the threshold/fixpoint
math as one device program: ``ops.quorum.tally_accept``/``tally_ratify``,
jitted at module level, so a program is built once per process and
shape (N, K) and reused by every later slot.  Quorum sets deeper than 2
levels have no tensor form (ref MAXIMUM_QUORUM_NESTING_LEVEL=4,
src/scp/QuorumSetUtils.cpp:16) — those slots fall back to the exact host
evaluation in scp/local_node.py, which is also the differential oracle in
"both" mode.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import numpy as np

from . import local_node as LN

HOST = "host"
TENSOR = "tensor"
BOTH = "both"  # tensor path + host oracle, assert equal (sim tests)


# (entry name, local and batched inner_mem shapes) of every tally this
# process ran.  Process-wide like the jit cache it mirrors: a key not in
# it builds a device program on its first call
_SHAPES_SEEN: set = set()


class TallyMismatch(AssertionError):
    pass


class TallyEngine:
    def __init__(self, slot, backend: str):
        self.slot = slot
        self.backend = backend
        self._cache_key: Optional[Tuple] = None
        self._tensors = None  # (local_qs, qsets, node_order)
        self.tensor_tallies = 0
        self.programs_built = 0  # calls whose shape was new to the process
        self.host_fallbacks = 0

    # -- tensor (re)construction -------------------------------------------

    def _build(self, envelopes: Dict[bytes, object]):
        from ..ops.quorum import QSetTensor, build_qset_tensor
        import jax.numpy as jnp

        local = self.slot.local_node
        node_qsets: Dict[bytes, object] = {local.node_id: local.qset}
        # sorted iteration: the envelope map arrives keyed by node id
        # (bytes) — tensor construction must not depend on arrival or
        # hash order (detlint det-unsorted-iter)
        for n, env in sorted(envelopes.items()):
            q = self.slot.qset_from_statement(env.statement)
            if q is None:
                continue
            node_qsets[n] = q
        key = tuple(sorted(
            (n, LN.qset_hash(q)) for n, q in node_qsets.items()))
        if key == self._cache_key:
            return self._tensors
        for _, q in sorted(node_qsets.items()):
            if LN.qset_to_plain(q) is None:
                self._cache_key = key
                self._tensors = None  # >2-level qset: host only
                return None
        # the universe covers every node any qset references (not just
        # envelope senders) — columns must exist for yet-silent validators
        universe = set(node_qsets)
        for _, q in sorted(node_qsets.items()):
            universe |= LN.qset_nodes(q)
        node_order = sorted(universe)
        # unknown qset: threshold 1 with zero members is never satisfiable,
        # so the node can never stay in a contraction (threshold 0 would
        # be trivially satisfied — the opposite of what we need)
        empty = (1, [], [])
        plains = [LN.qset_to_plain(node_qsets[n])
                  if n in node_qsets else empty for n in node_order]
        qsets = build_qset_tensor(plains, node_order)
        local_plain = LN.qset_to_plain(local.qset)
        local_qs = build_qset_tensor([local_plain], node_order)
        local_qs = QSetTensor(local_qs.top_mem[0], local_qs.top_thr[0],
                              local_qs.inner_mem[0], local_qs.inner_thr[0])
        self._cache_key = key
        self._tensors = (local_qs, qsets, node_order)
        return self._tensors

    # -- tallies ------------------------------------------------------------

    def _run(self, entry, local_qs, qsets, *votes) -> bool:
        shape = (entry.__name__, local_qs.inner_mem.shape,
                 qsets.inner_mem.shape)
        if shape not in _SHAPES_SEEN:
            _SHAPES_SEEN.add(shape)
            self.programs_built += 1
        self.tensor_tallies += 1
        return bool(entry(local_qs, qsets, *votes))

    def federated_accept(self, voted_predicate: Callable,
                         accepted_predicate: Callable,
                         envelopes: Dict[bytes, object]) -> Optional[bool]:
        """Tensor-path verdict, or None to use the host path."""
        if self.backend == HOST:
            return None
        t = self._build(envelopes)
        if t is None:
            self.host_fallbacks += 1
            return None
        from ..ops import quorum as Q

        local_qs, qsets, order = t
        accepted = np.zeros((1, len(order)), np.bool_)
        vote_or_accept = np.zeros((1, len(order)), np.bool_)
        for i, n in enumerate(order):
            env = envelopes.get(n)
            if env is None:
                continue
            acc = accepted_predicate(env.statement)
            accepted[0, i] = acc
            vote_or_accept[0, i] = acc or voted_predicate(env.statement)
        verdict = self._run(Q.tally_accept, local_qs, qsets, accepted,
                            vote_or_accept)
        if self.backend == BOTH:
            host = self._host_accept(voted_predicate, accepted_predicate,
                                     envelopes)
            if host != verdict:
                raise TallyMismatch(
                    f"federated_accept tensor={verdict} host={host} "
                    f"slot={self.slot.slot_index}")
        return verdict

    def federated_ratify(self, voted_predicate: Callable,
                         envelopes: Dict[bytes, object]) -> Optional[bool]:
        if self.backend == HOST:
            return None
        t = self._build(envelopes)
        if t is None:
            self.host_fallbacks += 1
            return None
        from ..ops import quorum as Q

        local_qs, qsets, order = t
        voted = np.zeros((1, len(order)), np.bool_)
        for i, n in enumerate(order):
            env = envelopes.get(n)
            if env is not None and voted_predicate(env.statement):
                voted[0, i] = True
        verdict = self._run(Q.tally_ratify, local_qs, qsets, voted)
        if self.backend == BOTH:
            host = self._host_ratify(voted_predicate, envelopes)
            if host != verdict:
                raise TallyMismatch(
                    f"federated_ratify tensor={verdict} host={host} "
                    f"slot={self.slot.slot_index}")
        return verdict

    # -- host oracle ---------------------------------------------------------

    def _host_accept(self, voted_predicate, accepted_predicate,
                     envelopes) -> bool:
        accepted_nodes = {
            n for n, env in envelopes.items()
            if accepted_predicate(env.statement)}
        if LN.is_v_blocking(self.slot.local_node.qset, accepted_nodes):
            return True
        vote_or_accept = {
            n for n, env in envelopes.items()
            if accepted_predicate(env.statement)
            or voted_predicate(env.statement)}
        return self.slot._host_is_quorum(vote_or_accept, envelopes)

    def _host_ratify(self, voted_predicate, envelopes) -> bool:
        voted = {n for n, env in envelopes.items()
                 if voted_predicate(env.statement)}
        return self.slot._host_is_quorum(voted, envelopes)
