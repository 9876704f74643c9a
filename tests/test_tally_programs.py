"""The live SCP tally entries (ops.quorum.tally_accept / tally_ratify)
against the host oracle, and their programs kept across slots.

The differential cases lay quorum sets out as ``TallyEngine._build``
does (sorted universe, unknown qsets as threshold-1 empty columns, the
local qset as its own unbatched tensor) and hold each verdict to
``scp.local_node``'s ``is_v_blocking`` and ``is_quorum``, the functions
behind ``TallyEngine._host_accept`` / ``_host_ratify``.
"""
import numpy as np
import pytest

import jax.monitoring

from stellar_core_tpu.ops import quorum as Q
from stellar_core_tpu.scp import SCP, make_qset, qset_hash
from stellar_core_tpu.scp import local_node as LN

from tests.test_scp import TestDriver, V, X, prepare_env

BUILD = "/jax/core/compile/backend_compile_duration"


def node(i):
    return bytes([i + 1]) * 32


def random_qset(rng, ids, max_inner):
    """A sane 2-level qset over ``ids``: thresholds 1..members."""
    vals = [n for n in ids if rng.random() < 0.4]
    inner = []
    for _ in range(int(rng.integers(0, max_inner + 1))):
        ivals = [n for n in ids if rng.random() < 0.5] or [ids[0]]
        inner.append(make_qset(int(rng.integers(1, len(ivals) + 1)), ivals))
    members = len(vals) + len(inner)
    thr = int(rng.integers(1, members + 1)) if members else 1
    return make_qset(thr, vals, inner)


def random_case(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 17))
    k = int(rng.integers(1, 5))
    ids = [node(i) for i in range(n)]
    qsets = {ids[0]: random_qset(rng, ids, k)}
    for nid in ids[1:]:
        if rng.random() < 0.8:   # else unknown: an empty column
            qsets[nid] = random_qset(rng, ids, k)
    votes = [(set(), set()), (set(ids), set(ids))]
    for _ in range(6):
        acc = {nid for nid in ids if rng.random() < 0.3}
        vote = acc | {nid for nid in ids if rng.random() < 0.6}
        votes.append((acc, vote))
    return ids[0], qsets, votes


def named_case(name):
    a, b, c, d, e = (node(i) for i in range(5))
    if name == "empty_qset_columns":
        # c and d are referenced but never sent a qset: columns of
        # threshold 1 and no members, which never satisfy a slice
        qsets = {a: make_qset(2, [a, b, c, d]), b: make_qset(2, [a, b, c])}
        every = {a, b, c, d}
        votes = [(set(), every), ({c, d}, every), ({a, c, d}, {a, b}),
                 ({b}, {a, c, d})]
        return a, qsets, votes
    if name == "inner_padding":
        # the local qset has one inner set, a remote four: the local
        # tensor's K is 1, the batch's 4, and its missing slots are
        # threshold-0 padding that must never count
        orgs = [make_qset(2, [a, b]), make_qset(1, [c]), make_qset(2, [d, e]),
                make_qset(1, [a, e])]
        qsets = {a: make_qset(1, [b], [make_qset(2, [c, d])]),
                 b: make_qset(3, [], orgs),
                 c: make_qset(1, [a, b, c]),
                 d: make_qset(2, [c, d, e]),
                 e: make_qset(1, [d])}
        every = {a, b, c, d, e}
        votes = [(set(), set()), (set(), every), ({b}, {a, b, c, d}),
                 ({c, d}, {c, d, e}), ({a, b, c}, {a, b, c})]
        return a, qsets, votes
    if name == "disjoint_remote_quorum":
        # {d, e} is a quorum of its own that shares no node with the
        # local slices: it must neither ratify nor block
        qsets = {a: make_qset(2, [a, b, c]), b: make_qset(2, [a, b, c]),
                 c: make_qset(2, [a, b, c]), d: make_qset(2, [d, e]),
                 e: make_qset(2, [d, e])}
        votes = [({d, e}, {d, e}), ({c, d, e}, {c, d, e}),
                 (set(), {a, b, d, e}), ({b, c}, {a, b})]
        return a, qsets, votes
    if name == "single_node":
        qsets = {a: make_qset(1, [a])}
        return a, qsets, [(set(), set()), (set(), {a}), ({a}, {a})]
    raise KeyError(name)


def tensors(local, qsets):
    """The engine's layout: (local_qs, qsets tensor, node order)."""
    universe = set(qsets)
    for q in qsets.values():
        universe |= LN.qset_nodes(q)
    order = sorted(universe)
    plains = [LN.qset_to_plain(qsets[n]) if n in qsets else (1, [], [])
              for n in order]
    qt = Q.build_qset_tensor(plains, order)
    lq = Q.build_qset_tensor([LN.qset_to_plain(qsets[local])], order)
    lq = Q.QSetTensor(lq.top_mem[0], lq.top_thr[0], lq.inner_mem[0],
                      lq.inner_thr[0])
    return lq, qt, order


def row(nodes, order):
    return np.array([[n in nodes for n in order]], np.bool_)


NAMED = ["empty_qset_columns", "inner_padding", "disjoint_remote_quorum",
         "single_node"]


@pytest.mark.parametrize(
    "case", [f"seed{s}" for s in range(24)] + NAMED)
def test_tally_entries_match_host_oracle(case):
    local, qsets, votes = (random_case(int(case[4:]))
                           if case.startswith("seed") else named_case(case))
    lq, qt, order = tensors(local, qsets)
    local_qset = qsets[local]
    for accepted, vote_or_accept in votes:
        want_ratify = LN.is_quorum(vote_or_accept, qsets.get,
                                   local_qset=local_qset)
        want_accept = (LN.is_v_blocking(local_qset, accepted)
                       or want_ratify)
        got_accept = Q.tally_accept(lq, qt, row(accepted, order),
                                    row(vote_or_accept, order))
        got_ratify = Q.tally_ratify(lq, qt, row(vote_or_accept, order))
        assert got_accept.shape == () and got_ratify.shape == ()
        assert bool(got_accept) == want_accept, (accepted, vote_or_accept)
        assert bool(got_ratify) == want_ratify, vote_or_accept
    if case == "disjoint_remote_quorum":
        d, e = node(3), node(4)
        assert LN.is_quorum({d, e}, qsets.get)  # a quorum, but not ours
        assert not bool(Q.tally_ratify(lq, qt, row({d, e}, order)))


def test_tally_programs_kept_across_slots():
    """A tensor-backend node's fresh slots with an unchanged qset reuse
    the programs of the first: no engine counts a new shape after it,
    and no backend compile happens."""
    qset = make_qset(4, V)
    qh = qset_hash(qset)
    scp = SCP(TestDriver(qset), V[0], True, qset, tally_backend="tensor")

    def voted(st):
        return True

    def accepted(st):
        return st.pledges.value.prepared is not None

    def tally_slot(i):
        slot = scp.get_slot(i)
        envelopes = {v: prepare_env(v, i, qh, (1, X),
                                    prepared=(1, X) if j % 2 else None)
                     for j, v in enumerate(V)}
        verdicts = (slot.federated_accept(voted, accepted, envelopes),
                    slot.federated_ratify(voted, envelopes),
                    slot.federated_accept(voted, accepted, envelopes))
        assert slot.tally.tensor_tallies == 3
        assert slot.tally.host_fallbacks == 0
        return slot.tally.programs_built, verdicts

    first, verdicts = tally_slot(1)
    assert first <= 2 and verdicts == (True, True, True)
    compiles = []

    def listen(event, secs, **_):
        if event == BUILD:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        later = [tally_slot(i) for i in range(2, 7)]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert [built for built, _ in later] == [0] * 5
    assert all(v == verdicts for _, v in later)
    assert compiles == []
