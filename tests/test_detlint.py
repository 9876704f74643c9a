"""Tier-1 gate for the detlint static analyzer (ISSUE 3 tentpole).

Two jobs: (1) the repo itself must be CLEAN — zero unbaselined
findings, no stale baseline entries, no un-justified baseline entries —
so the gate self-enforces on every future PR; (2) the analyzer must
actually catch the bug classes it claims to (seeded injections into
real module source must go red), or a green gate means nothing.
"""
import subprocess
import sys

from tools.lint import (
    lint_repo, lint_sources, load_baseline, match_baseline,
)
from tools.lint.engine import REPO

TALLY = "stellar_core_tpu/scp/tally.py"
OPS = "stellar_core_tpu/ops/injected_kernel.py"
BUCKET = "stellar_core_tpu/bucket/injected.py"


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# the gate itself
# ---------------------------------------------------------------------------

def test_repo_has_zero_unbaselined_findings():
    findings = lint_repo()
    baseline = load_baseline()
    fresh, pinned, stale = match_baseline(findings, baseline)
    assert not fresh, "unbaselined detlint findings:\n" + "\n".join(
        f.render() for f in fresh)
    assert not stale, (
        "stale baseline entries (finding fixed? remove them):\n"
        + "\n".join(str(e) for e in stale))


def test_baseline_entries_are_justified():
    for entry in load_baseline():
        j = entry.get("justification", "")
        assert j and not j.startswith("TODO"), (
            f"baseline entry without a real justification: {entry}")


def test_baseline_shrank_after_sanctioned_tracing_api():
    """ISSUE 4 satellite: the flight recorder's sanctioned timing APIs
    (utils.tracing.span/stopwatch, Timer.time_scope) replaced every raw
    perf_counter read in consensus modules, so the 18 det-wallclock
    baseline entries of ISSUE 3 are gone.  The baseline must only ever
    shrink or stay equal from here."""
    assert len(load_baseline()) == 0


def test_sanctioned_instrumentation_needs_no_baseline():
    """Instrumenting a consensus module through the sanctioned APIs
    produces zero findings — adding a span must never require a new
    det-wallclock baseline entry."""
    src = '''
from stellar_core_tpu.utils.tracing import span, stopwatch


def close_ledger(tracer, metrics, stats):
    with tracer.span("ledger.close"):
        with metrics.timer("ledger.ledger.close").time_scope():
            pass
    with stopwatch() as sw:
        pass
    stats["spill_wait_s"] += sw.seconds
'''
    assert not lint_sources({TALLY: src})


def test_sanctioned_call_matcher():
    from tools.lint.determinism import is_sanctioned_timing_call

    assert is_sanctioned_timing_call(
        "stellar_core_tpu.utils.tracing.span")
    assert is_sanctioned_timing_call(
        "stellar_core_tpu.utils.tracing.stopwatch")
    assert is_sanctioned_timing_call("tracing.span")
    assert is_sanctioned_timing_call("self.metrics.timer.time_scope")
    assert not is_sanctioned_timing_call("time.perf_counter")
    assert not is_sanctioned_timing_call("time.time")
    assert not is_sanctioned_timing_call(None)


def test_strict_cli_exits_zero_on_clean_repo():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--strict"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# acceptance: a seeded nondeterminism bug in scp/tally.py goes red
# ---------------------------------------------------------------------------

def _tally_source():
    with open(f"{REPO}/{TALLY}", encoding="utf-8") as fh:
        return fh.read()


def test_injected_unsorted_items_feeding_hash_is_caught():
    src = _tally_source() + '''

def _fingerprint(envelopes):
    import hashlib
    h = hashlib.sha256()
    for n, env in envelopes.items():
        h.update(n)
    return h.digest()
'''
    findings = lint_sources({TALLY: src})
    hits = [f for f in findings if f.rule == "det-unsorted-iter"
            and f.context == "_fingerprint"]
    assert hits, [f.render() for f in findings]
    # and it is UNBASELINED (strict would exit nonzero)
    fresh, _, _ = match_baseline(findings, load_baseline())
    assert any(f.context == "_fingerprint" for f in fresh)


def test_injected_wallclock_read_is_caught():
    src = _tally_source() + '''

def _stamp(slot):
    import time
    return time.time()
'''
    findings = lint_sources({TALLY: src})
    assert any(f.rule == "det-wallclock" and f.context == "_stamp"
               for f in findings), [f.render() for f in findings]


def test_current_tally_module_is_clean():
    findings = lint_sources({TALLY: _tally_source()})
    assert not findings, [f.render() for f in findings]


def test_telemetry_readback_is_caught():
    """ISSUE 14: any data flow FROM the slot-timeline recorder INTO
    consensus code (reading its state, returning it, passing it on)
    breaks the telemetry-on/off bit-identity contract."""
    src = _tally_source() + '''

def _leak_state(slot):
    tl = slot.scp.timeline
    return tl.export()


def _leak_as_argument(slot, fn):
    fn(slot.scp.timeline)


def _leak_len(slot):
    return len(slot.scp.timeline._slots)
'''
    findings = lint_sources({TALLY: src})
    hits = {f.context for f in findings
            if f.rule == "det-telemetry-readback"}
    assert {"_leak_state", "_leak_as_argument", "_leak_len"} <= hits, \
        [f.render() for f in findings]
    # and they are UNBASELINED (strict would exit nonzero)
    fresh, _, _ = match_baseline(findings, load_baseline())
    assert any(f.rule == "det-telemetry-readback" for f in fresh)


def test_telemetry_writeonly_shapes_are_clean():
    """The instrumented call-site shapes — alias, .enabled / is-None
    guard, bare .record(...) statement, verdict write into the event
    dict — must NOT be flagged."""
    src = _tally_source() + '''

def _record_ok(slot, kind):
    tl = slot.scp.timeline
    if tl.enabled:
        ev = {"from": "aa"}
        tl.record(slot.slot_index, kind, ev)
        ev["ok"] = True
    if tl is not None:
        slot.scp.timeline.record(slot.slot_index, "env")
'''
    findings = lint_sources({TALLY: src})
    assert not any(f.rule == "det-telemetry-readback" for f in findings), \
        [f.render() for f in findings]


# ---------------------------------------------------------------------------
# determinism rules, unit-level
# ---------------------------------------------------------------------------

def test_pragma_suppresses_finding():
    src = '''
import time


def close_time():
    # detlint: allow(det-wallclock)
    return time.time()
'''
    assert not lint_sources({TALLY: src})


def test_float_on_fee_is_caught_and_floordiv_is_not():
    src = '''
def rate(fee_bid, ops):
    return fee_bid / ops
'''
    findings = lint_sources({TALLY: src})
    assert _rules(findings) == {"det-float-consensus"}
    src_ok = src.replace(" / ", " // ")
    assert not lint_sources({TALLY: src_ok})


def test_set_comprehension_and_sorted_consumer_are_exempt():
    src = '''
def tally(envelopes, pred):
    voted = {n for n, env in envelopes.items() if pred(env)}
    order = sorted(n for n in voted)
    total = sum(len(n) for n in voted)
    h = sha256(b"".join(order))
    return h, total
'''
    assert not lint_sources({TALLY: src})


def test_unsorted_iteration_without_sink_is_not_flagged():
    src = '''
def count(envelopes):
    n = 0
    for k, v in envelopes.items():
        n += 1
    return n
'''
    assert not lint_sources({TALLY: src})


def test_jit_host_effect_is_caught():
    src = '''
import os
from functools import partial

import jax


@partial(jax.jit, static_argnames=())
def kernel(x):
    if os.environ.get("DEBUG"):
        print("tracing", x)
    return x * 2


def host_helper(x):
    print(x)  # not jitted: fine
    return x
'''
    findings = lint_sources({OPS: src})
    assert all(f.rule == "det-jit-host-effect" for f in findings)
    assert {f.context for f in findings} == {"kernel"}
    assert len(findings) >= 2  # the environ read and the print


# ---------------------------------------------------------------------------
# lock-discipline rules
# ---------------------------------------------------------------------------

_LOCKED_MODULE = '''
import threading

_lock = threading.Lock()
_shared = set()  # guarded-by: _lock


def good():
    with _lock:
        _shared.add(1)


def bad():
    _shared.add(2)


class Pipeline:
    def __init__(self):
        self._mu = threading.Lock()
        self._outputs = set()  # guarded-by: _mu
        self._outputs.add(0)  # __init__ is construction: exempt

    def good(self):
        with self._mu:
            self._outputs.discard(1)

    def bad(self):
        self._outputs |= {2}
'''


def test_lock_unguarded_write_is_caught():
    findings = lint_sources({BUCKET: _LOCKED_MODULE})
    assert all(f.rule == "lock-unguarded-write" for f in findings)
    assert {(f.context, f.line_text) for f in findings} == {
        ("bad", "_shared.add(2)"),
        ("Pipeline.bad", "self._outputs |= {2}"),
    }, [f.render() for f in findings]


def test_lock_order_inversion_is_caught():
    src = '''
import threading

_a_lock = threading.Lock()
_b_lock = threading.Lock()


def forward():
    with _a_lock:
        with _b_lock:
            pass


def backward():
    with _b_lock:
        with _a_lock:
            pass
'''
    findings = lint_sources({BUCKET: src})
    assert any(f.rule == "lock-order" for f in findings), \
        [f.render() for f in findings]
    src_consistent = src.replace(
        "with _b_lock:\n        with _a_lock:",
        "with _a_lock:\n        with _b_lock:")
    assert not any(f.rule == "lock-order"
                   for f in lint_sources({BUCKET: src_consistent}))


def test_lock_unknown_guard_is_caught():
    src = '''
_shared = set()  # guarded-by: _phantom_lock


def touch():
    _shared.add(1)
'''
    findings = lint_sources({BUCKET: src})
    assert "lock-unknown-guard" in _rules(findings)


def test_repo_lock_annotations_are_honoured():
    """The real bucket pipeline / native loader / quorum bridge carry
    guarded-by annotations and every mutation is inside its lock."""
    findings = [f for f in lint_repo()
                if f.rule.startswith("lock-")]
    assert not findings, [f.render() for f in findings]


# ---------------------------------------------------------------------------
# detlint v2: interprocedural determinism taint (ISSUE 9 tentpole)
# ---------------------------------------------------------------------------

SCP_HELPER = "stellar_core_tpu/scp/injected_helpers.py"
SCP_SINK = "stellar_core_tpu/scp/injected_sink.py"
KERNEL = "stellar_core_tpu/native/apply_kernel.cpp"


def _kernel_source():
    with open(f"{REPO}/{KERNEL}", encoding="utf-8") as fh:
        return fh.read()


def test_interproc_taint_through_helper_is_caught_with_chain():
    """The acceptance shape: an unsorted-iteration helper WITHOUT a
    sink (invisible to every v1 rule) feeding a hash through one
    intermediate call in another module of scp/."""
    helper = '''
def collect(envelopes):
    out = []
    for node, env in envelopes.items():
        out.append(env)
    return out
'''
    sink = '''
from .injected_helpers import collect


def vote_hash(envelopes):
    import hashlib
    h = hashlib.sha256()
    for env in collect(envelopes):
        h.update(env)
    return h.digest()
'''
    # v1 alone is blind: the helper has no sink, the sink fn has no
    # unsorted iteration
    v1 = [f for f in lint_sources({SCP_HELPER: helper})
          if f.rule != "det-interproc-taint"]
    assert not v1, [f.render() for f in v1]

    findings = lint_sources({SCP_HELPER: helper, SCP_SINK: sink})
    hits = [f for f in findings if f.rule == "det-interproc-taint"]
    assert hits, [f.render() for f in findings]
    f = hits[0]
    assert f.file == SCP_SINK and f.context == "vote_hash"
    # the full source->sink chain is in the message
    assert "vote_hash -> collect" in f.message
    assert "unsorted .items() iteration" in f.message
    assert "injected_helpers.py:4" in f.message
    # ...and it is unbaselined (strict goes red)
    fresh, _, _ = match_baseline(findings, load_baseline())
    assert any(x.rule == "det-interproc-taint" for x in fresh)


def test_interproc_wallclock_chain_across_two_hops():
    helper = '''
import time


def jitter():
    return time.time() % 1.0


def mix(values):
    return [v + jitter() for v in values]
'''
    sink = '''
from .injected_helpers import mix


def emit(values, driver):
    driver.emit_envelope(mix(values))
'''
    findings = lint_sources({SCP_HELPER: helper, SCP_SINK: sink})
    hits = [f for f in findings if f.rule == "det-interproc-taint"]
    assert hits, [f.render() for f in findings]
    assert "emit -> mix -> jitter" in hits[0].message
    assert "wallclock time.time()" in hits[0].message


def test_interproc_source_pragma_kills_all_chains():
    helper = '''
import time


def jitter():
    # detlint: allow(det-wallclock)
    return time.time() % 1.0
'''
    sink = '''
from .injected_helpers import jitter


def emit(values, driver):
    driver.emit_envelope([jitter() for _ in values])
'''
    findings = [f for f in lint_sources({SCP_HELPER: helper,
                                         SCP_SINK: sink})
                if f.rule == "det-interproc-taint"]
    assert not findings, [f.render() for f in findings]


def test_interproc_sink_pragma_and_baseline_round_trip():
    helper = '''
import time


def jitter():
    return time.time() % 1.0
'''
    sink = '''
from .injected_helpers import jitter


def emit(values, driver):
    # detlint: allow(det-interproc-taint)
    driver.emit_envelope([jitter() for _ in values])
'''
    taint = [f for f in lint_sources({SCP_HELPER: helper,
                                      SCP_SINK: sink})
             if f.rule == "det-interproc-taint"]
    assert not taint
    # baseline round-trip: the same finding pinned by identity
    sink_nopragma = sink.replace(
        "    # detlint: allow(det-interproc-taint)\n", "")
    findings = lint_sources({SCP_HELPER: helper, SCP_SINK: sink_nopragma})
    taint = [f for f in findings if f.rule == "det-interproc-taint"]
    assert taint
    entry = {"rule": taint[0].rule, "file": taint[0].file,
             "context": taint[0].context,
             "line_text": taint[0].line_text, "justification": "test"}
    fresh, pinned, stale = match_baseline(taint, [entry])
    assert not fresh and pinned and not stale


def test_interproc_id_source_and_sanctioned_modules():
    helper = '''
def cache_key(obj):
    return id(obj)
'''
    sink = '''
from .injected_helpers import cache_key


def digest(objs):
    import hashlib
    return hashlib.sha256(bytes(cache_key(o) % 256 for o in objs)).digest()
'''
    findings = [f for f in lint_sources({SCP_HELPER: helper,
                                         SCP_SINK: sink})
                if f.rule == "det-interproc-taint"]
    assert findings and "id id()" in findings[0].message
    # sanctioned module: the same source in utils/tracing.py is exempt
    from tools.lint.callgraph import SANCTIONED_MODULES

    assert "stellar_core_tpu/utils/tracing.py" in SANCTIONED_MODULES


def test_interproc_depth_bound_is_enforced():
    """A chain longer than MAX_TAINT_DEPTH edges does not propagate —
    the documented blind spot, pinned so it changes consciously."""
    from tools.lint.callgraph import MAX_TAINT_DEPTH

    hops = MAX_TAINT_DEPTH + 1
    parts = ["import time", "", "",
             "def h0():", "    return time.time()", ""]
    for i in range(1, hops):
        parts += [f"def h{i}():", f"    return h{i - 1}()", ""]
    parts += ["def over(driver):",
              f"    driver.emit_envelope(h{hops - 1}())"]
    src = "\n".join(parts)
    findings = [f for f in lint_sources({SCP_HELPER: src})
                if f.rule == "det-interproc-taint"]
    assert not findings, [f.render() for f in findings]
    # one hop fewer: caught
    src_ok = src.replace(f"emit_envelope(h{hops - 1}())",
                         f"emit_envelope(h{hops - 2}())")
    findings = [f for f in lint_sources({SCP_HELPER: src_ok})
                if f.rule == "det-interproc-taint"]
    assert findings


# ---------------------------------------------------------------------------
# detlint v2: native-kernel auditor
# ---------------------------------------------------------------------------

def test_injected_constant_drift_is_caught():
    """Acceptance: a one-character drift in apply_kernel.cpp fails the
    gate (neither present in the shipped tree)."""
    drifted = _kernel_source().replace("MAX_OFFERS_TO_CROSS = 1000",
                                       "MAX_OFFERS_TO_CROSS = 1001")
    findings = lint_sources({KERNEL: drifted})
    hits = [f for f in findings if f.rule == "native-lockstep"]
    assert hits, [f.render() for f in findings]
    assert "max-offers-to-cross" in hits[0].message
    assert "1001 != 1000" in hits[0].message
    fresh, _, _ = match_baseline(findings, load_baseline())
    assert any(f.rule == "native-lockstep" for f in fresh)


def test_issue13_kernel_constant_drift_is_caught():
    """The ISSUE-13 constants (path hop cap, trustline flag masks,
    liability XDR tags) are lockstep-pinned: a one-character C++ edit
    on any of them is red."""
    for frm, to, name in (
            ("MAX_PATH_HOPS = 6", "MAX_PATH_HOPS = 7", "max-path-hops"),
            ("TL_CLAWBACK_FLAG = 4", "TL_CLAWBACK_FLAG = 5",
             "trustline-clawback-flag"),
            ("TL_V1_EXT_V2 = 2", "TL_V1_EXT_V2 = 3",
             "trustline-v1-ext-v2-tag"),
            ("OP_CHANGE_TRUST = 6", "OP_CHANGE_TRUST = 7",
             "op-change-trust")):
        drifted = _kernel_source().replace(frm, to)
        assert drifted != _kernel_source(), frm
        hits = [f for f in lint_sources({KERNEL: drifted})
                if f.rule == "native-lockstep"]
        assert hits, f"{name}: drift must fail the gate"
        assert any(name in f.message for f in hits), \
            [f.render() for f in hits]


def test_issue16_kernel_constant_drift_is_caught():
    """The ISSUE-16 constants (pool constant-product fee/rounding, the
    fee phase's op floor, seqnum account-ext tags, pool XDR tags) are
    lockstep-pinned: a one-character C++ edit on any of them is red."""
    for frm, to, name in (
            ("POOL_FEE_V18 = 30", "POOL_FEE_V18 = 31", "pool-fee-v18"),
            ("POOL_MAX_BPS = 10000", "POOL_MAX_BPS = 10001",
             "pool-max-bps"),
            ("FEE_OPS_FLOOR = 1", "FEE_OPS_FLOOR = 0", "fee-ops-floor"),
            ("ACC_EXT_V3 = 3", "ACC_EXT_V3 = 4", "account-v2-ext-v3-tag"),
            ("LE_LIQUIDITY_POOL = 5", "LE_LIQUIDITY_POOL = 6",
             "le-liquidity-pool"),
            ("w.u32(2); /* CLAIM_ATOM_TYPE_LIQUIDITY_POOL",
             "w.u32(3); /* CLAIM_ATOM_TYPE_LIQUIDITY_POOL",
             "claim-atom-liquidity-pool")):
        drifted = _kernel_source().replace(frm, to)
        assert drifted != _kernel_source(), frm
        hits = [f for f in lint_sources({KERNEL: drifted})
                if f.rule == "native-lockstep"]
        assert hits, f"{name}: drift must fail the gate"
        assert any(name in f.message for f in hits), \
            [f.render() for f in hits]


def test_issue16_python_pool_rounding_drift_is_caught():
    """The pool math's Python twin (liquidity_pool.py's basis-point
    denominator) is pinned too — the kernel quote must divide by the
    very same constant."""
    path = "stellar_core_tpu/transactions/liquidity_pool.py"
    with open(f"{REPO}/{path}", encoding="utf-8") as fh:
        src = fh.read()
    drifted = src.replace("f = 10000 - fee_bps", "f = 10001 - fee_bps")
    assert drifted != src
    findings = [f for f in lint_sources({path: drifted})
                if f.rule == "native-lockstep"]
    assert findings, "python-side pool drift must fail the gate"
    assert any("pool-max-bps" in f.message and f.file == path
               for f in findings), [f.render() for f in findings]


def test_python_side_constant_drift_is_caught():
    """The same entry fails when the PYTHON twin drifts instead."""
    path = "stellar_core_tpu/transactions/utils.py"
    with open(f"{REPO}/{path}", encoding="utf-8") as fh:
        src = fh.read()
    drifted = src.replace("MAX_OFFERS_TO_CROSS = 1000",
                          "MAX_OFFERS_TO_CROSS = 999")
    findings = [f for f in lint_sources({path: drifted})
                if f.rule == "native-lockstep"]
    assert findings, "python-side drift must fail the gate"
    assert any("999 != 1000" in f.message and f.file == path
               for f in findings)


def test_stale_lockstep_manifest_pattern_is_itself_a_finding():
    renamed = _kernel_source().replace("MAX_OFFERS_TO_CROSS",
                                       "MAX_OFFERS_CROSSED")
    findings = [f for f in lint_sources({KERNEL: renamed})
                if f.rule == "native-lockstep"]
    assert any("no longer matches" in f.message for f in findings)


def test_injected_py_call_inside_allow_threads_is_caught():
    """Acceptance: Py* under Py_BEGIN_ALLOW_THREADS fails the gate."""
    bad = _kernel_source().replace(
        "    try {\n        for (auto &kv : c.store)",
        "    PyErr_Clear();\n    try {\n        for (auto &kv : c.store)")
    findings = [f for f in lint_sources({KERNEL: bad})
                if f.rule == "native-gil-api"]
    assert findings, "Py* in an allow-threads region must be caught"
    assert "PyErr_Clear" in findings[0].message
    # ...and a // pragma suppresses a justified one
    ok = _kernel_source().replace(
        "    try {\n        for (auto &kv : c.store)",
        "    PyErr_Clear(); // detlint: allow(native-gil-api)\n"
        "    try {\n        for (auto &kv : c.store)")
    findings = [f for f in lint_sources({KERNEL: ok})
                if f.rule == "native-gil-api"]
    assert not findings, [f.render() for f in findings]


def test_block_threads_window_is_exempt():
    bad = _kernel_source().replace(
        "    try {\n        for (auto &kv : c.store)",
        "    Py_BLOCK_THREADS;\n    PyErr_Clear();\n"
        "    Py_UNBLOCK_THREADS;\n"
        "    try {\n        for (auto &kv : c.store)")
    findings = [f for f in lint_sources({KERNEL: bad})
                if f.rule == "native-gil-api"]
    assert not findings, [f.render() for f in findings]


def test_unchecked_allocator_is_caught_and_checked_is_not():
    bad = _kernel_source().replace(
        "    PyObject *deltas = PyList_New((Py_ssize_t)delta_keys.size());\n"
        "    if (!deltas)\n        return NULL;",
        "    PyObject *deltas = PyList_New((Py_ssize_t)delta_keys.size());")
    findings = [f for f in lint_sources({KERNEL: bad})
                if f.rule == "native-null-unchecked"]
    assert findings, "removing the NULL check must surface a finding"
    assert "deltas" in findings[0].message
    # the shipped kernel (checks intact) is clean
    clean = [f for f in lint_sources({KERNEL: _kernel_source()})
             if f.rule == "native-null-unchecked"]
    assert not clean, [f.render() for f in clean]


def test_comments_naming_py_functions_do_not_trip_the_auditor():
    src = '''
#include <Python.h>
/* PyBytes_AsStringAndSize would segfault on NULL — see glue below */
static PyObject *f(PyObject *s, PyObject *a) {
    Py_BEGIN_ALLOW_THREADS;
    // PyErr_SetString is NOT legal here
    int x = 1;
    Py_END_ALLOW_THREADS;
    return NULL;
}
'''
    findings = [f for f in lint_sources(
        {"stellar_core_tpu/native/injected.cpp": src})
        if f.rule in ("native-gil-api", "native-null-unchecked")]
    assert not findings, [f.render() for f in findings]


def test_srchash_sidecar_audit(tmp_path):
    from tools.lint.native import SO_SOURCES, check_srchash

    ndir = tmp_path / "stellar_core_tpu" / "native"
    ndir.mkdir(parents=True)
    for srcs in SO_SOURCES.values():
        for s in srcs:
            (ndir / s).write_text("int x;\n")
    (ndir / "_xdrpack.so").write_bytes(b"\x7fELF-fake")
    # missing sidecar -> finding
    findings = check_srchash(str(tmp_path))
    assert any(f.rule == "native-srchash" and "missing" in f.message
               for f in findings)
    # stale sidecar -> finding
    (ndir / "_xdrpack.so.srchash").write_text("0" * 64)
    findings = check_srchash(str(tmp_path))
    assert any(f.rule == "native-srchash" and "stale" in f.message
               for f in findings)
    # current sidecar -> clean
    import hashlib
    h = hashlib.sha256()
    h.update((ndir / "xdr_pack.c").read_bytes())
    (ndir / "_xdrpack.so.srchash").write_text(h.hexdigest())
    findings = check_srchash(str(tmp_path))
    assert not findings, [f.render() for f in findings]
    # unknown .so -> finding (no auditable contract)
    (ndir / "_mystery.so").write_bytes(b"??")
    findings = check_srchash(str(tmp_path))
    assert any("unknown native library" in f.message for f in findings)


def test_shipped_tree_sidecars_are_current():
    from tools.lint.native import check_srchash

    findings = check_srchash(REPO)
    assert not findings, [f.render() for f in findings]


# ---------------------------------------------------------------------------
# detlint v2: exception-safety & resource rules
# ---------------------------------------------------------------------------

def test_swallow_except_rules():
    src = '''
def bare(raw):
    try:
        return decode(raw)
    except:
        return None


def silent(raw):
    try:
        return decode(raw)
    except Exception:
        pass


def acts(raw):
    try:
        return decode(raw)
    except Exception:
        log.warning("bad value")
        return None


def narrow(raw):
    try:
        return decode(raw)
    except ValueError:
        pass
'''
    findings = lint_sources({TALLY: src})
    assert {(f.rule, f.context) for f in findings} == {
        ("safety-swallow-except", "bare"),
        ("safety-swallow-except", "silent"),
    }, [f.render() for f in findings]
    # pragma round-trip
    ok = src.replace("    except:",
                     "    # detlint: allow(safety-swallow-except)\n"
                     "    except:").replace(
        "    except Exception:\n        pass",
        "    # detlint: allow(safety-swallow-except)\n"
        "    except Exception:\n        pass", 1)
    assert not lint_sources({TALLY: ok}), \
        [f.render() for f in lint_sources({TALLY: ok})]


def test_resource_ctx_rule():
    src = '''
import os


def good(path):
    with open(path, "rb") as f:
        return f.read()


def bad(path):
    f = open(path, "rb")
    data = f.read()
    f.close()
    return data


class Cache:
    def keeps(self, path):
        fd = os.open(path, os.O_RDONLY)
        self._fd = fd
        return fd
'''
    findings = lint_sources({BUCKET: src})
    assert {(f.rule, f.context) for f in findings} == {
        ("safety-resource-ctx", "bad"),
    }, [f.render() for f in findings]


def test_mutable_default_rule():
    src = '''
def tally(votes, seen=set()):
    return votes


def fine(votes, seen=None):
    return votes
'''
    findings = lint_sources({TALLY: src})
    assert [f.rule for f in findings] == ["safety-mutable-default"]
    assert findings[0].context == "tally"


# ---------------------------------------------------------------------------
# detlint v2: --changed incremental mode
# ---------------------------------------------------------------------------

def test_changed_mode_reuses_cache_and_matches_cold_run(tmp_path):
    from tools.lint.cache import lint_changed

    pkg = tmp_path / "stellar_core_tpu" / "scp"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "x.py").write_text(
        "import time\n\n\ndef stamp():\n    return time.time()\n")
    cpath = str(tmp_path / "cache.json")
    f1, s1 = lint_changed(root=str(tmp_path), path=cpath)
    assert s1["reused"] == 0 and len(s1["changed"]) == 2
    f2, s2 = lint_changed(root=str(tmp_path), path=cpath)
    assert not s2["changed"] and s2["reused"] == 2
    # warm run is finding-identical to the cold run
    assert [f.render() for f in f1] == [f.render() for f in f2]
    assert any(f.rule == "det-wallclock" for f in f2)
    # edit the file: only it re-analyzes, the finding goes away
    (pkg / "x.py").write_text("def stamp(clock):\n    return clock.now()\n")
    f3, s3 = lint_changed(root=str(tmp_path), path=cpath)
    assert s3["changed"] == ["stellar_core_tpu/scp/x.py"]
    assert not any(f.rule == "det-wallclock" for f in f3)


def test_changed_mode_on_repo_matches_full_run(tmp_path):
    """--changed against the real tree reports exactly what the cold
    full run reports (zero, per the gate) — strict on --changed is
    sound."""
    from tools.lint.cache import lint_changed

    cpath = str(tmp_path / "cache.json")
    cold, _ = lint_changed(root=REPO, path=cpath)
    warm, stats = lint_changed(root=REPO, path=cpath)
    assert not stats["changed"]
    assert [f.render() for f in cold] == [f.render() for f in warm]
    full = lint_repo()
    assert [f.render() for f in full] == [f.render() for f in cold]


def test_verify_green_lint_only_gate():
    proc = subprocess.run(
        [sys.executable, "tools/verify_green.py", "--lint-only"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "LINT GREEN" in proc.stdout


# ---------------------------------------------------------------------------
# regressions for what the v2 first full run surfaced and this PR fixed
# ---------------------------------------------------------------------------

def test_value_tx_set_hashes_skips_malformed_but_propagates_bugs():
    """safety-swallow-except fix in herder.py: the decode guard eats
    XdrError (hostile/torn peer bytes) but no longer masks runtime
    bugs behind 'except Exception'."""
    from stellar_core_tpu.herder import herder as H
    from stellar_core_tpu.scp import statement as S
    from stellar_core_tpu.xdr import XdrError, types as T

    class FakeStatement:
        pass

    st = FakeStatement()
    orig_pt, orig_nv = S.pledge_type, S.nomination_values
    S.pledge_type = lambda s: S.ST_NOMINATE
    S.nomination_values = lambda s: [b"\x00garbage-not-xdr"]
    try:
        assert H._value_tx_set_hashes(st) == []
        # a NON-decode error must stay loud
        orig_decode = T.StellarValue.decode
        T.StellarValue.decode = staticmethod(
            lambda v: (_ for _ in ()).throw(RuntimeError("driver bug")))
        try:
            import pytest
            with pytest.raises(RuntimeError):
                H._value_tx_set_hashes(st)
        finally:
            T.StellarValue.decode = orig_decode
    finally:
        S.pledge_type, S.nomination_values = orig_pt, orig_nv
    assert issubclass(XdrError, Exception)


def test_unprotect_future_logs_instead_of_silent_swallow(caplog):
    """safety-swallow-except fix in bucket_list.py: a failed staged
    merge no longer disappears without a trace at GC-unprotect time."""
    import logging
    import threading
    from concurrent.futures import Future

    from stellar_core_tpu.bucket.bucket_list import BucketList

    bl = object.__new__(BucketList)
    bl._bg_lock = threading.Lock()
    bl._bg_outputs = {"aa"}
    fut = Future()
    fut.set_exception(RuntimeError("merge exploded"))
    with caplog.at_level(logging.DEBUG,
                         logger="stellar_core_tpu.Bucket"):
        bl._unprotect_future(fut)  # must not raise
    assert any("staged merge failed" in r.message for r in caplog.records)
    assert bl._bg_outputs == {"aa"}  # protection entry intact

    class BadBucket:
        def hash(self):
            raise RuntimeError("no hash")

        def __repr__(self):
            return "<BadBucket>"

    with caplog.at_level(logging.WARNING,
                         logger="stellar_core_tpu.Bucket"):
        bl._unprotect(BadBucket())  # must not raise
    assert any("has no hash" in r.message for r in caplog.records)


def test_merge_table_narrowed_guard(tmp_path, monkeypatch):
    """safety-swallow-except fix in disk_bucket.py: unreadable files
    still fall back to the Python tier; unexpected error types
    propagate instead of being silently converted into a fallback."""
    import pytest

    from stellar_core_tpu.bucket import disk_bucket as DB

    b = object.__new__(DB.DiskBucket)
    b.path = str(tmp_path / "nope.bucket")
    b.size_bytes = 123
    b.count = 1
    monkeypatch.setattr(DB, "_read_sidecar", lambda *a, **k: None)
    monkeypatch.setattr(DB, "_scan_tables",
                        lambda p: (_ for _ in ()).throw(OSError("gone")))
    assert b.merge_table() is None
    monkeypatch.setattr(DB, "_scan_tables",
                        lambda p: (_ for _ in ()).throw(TypeError("bug")))
    with pytest.raises(TypeError):
        b.merge_table()


# ---------------------------------------------------------------------------
# review-pass regressions: gate soundness of the cold run and the cache
# ---------------------------------------------------------------------------

def test_unparseable_file_goes_red_in_cold_run():
    """A SyntaxError'd consensus file must be a finding, not silence —
    the cold full run (the CI gate) and --changed agree on the verdict."""
    findings = lint_sources(
        {"stellar_core_tpu/scp/broken.py": "def f(:\n    pass\n"})
    assert [f.rule for f in findings] == ["parse-error"]


def test_cache_invalidated_when_lint_rules_change(tmp_path):
    """Cached findings were computed BY the rule sources — a cache
    stamped by different tools must be dropped wholesale, or --changed
    --strict could stay green where a cold run goes red."""
    import json

    from tools.lint.cache import lint_changed

    pkg = tmp_path / "stellar_core_tpu" / "scp"
    pkg.mkdir(parents=True)
    (pkg / "x.py").write_text("import time\n\n\ndef s():\n"
                              "    return time.time()\n")
    cpath = tmp_path / "cache.json"
    _, s1 = lint_changed(root=str(tmp_path), path=str(cpath))
    assert s1["reused"] == 0
    _, s2 = lint_changed(root=str(tmp_path), path=str(cpath))
    assert s2["reused"] == 1
    # simulate a pulled commit that changed a rule module: the recorded
    # tools fingerprint no longer matches
    data = json.loads(cpath.read_text())
    data["tools_sha256"] = "0" * 64
    cpath.write_text(json.dumps(data))
    _, s3 = lint_changed(root=str(tmp_path), path=str(cpath))
    assert s3["reused"] == 0, "stale-rules cache must be dropped"


def test_srchash_reverse_audit_catches_stale_source_map(tmp_path):
    from tools.lint.native import SO_SOURCES, check_srchash

    ndir = tmp_path / "stellar_core_tpu" / "native"
    ndir.mkdir(parents=True)
    for srcs in SO_SOURCES.values():
        for s in srcs:
            (ndir / s).write_text("int x;\n")
    assert not check_srchash(str(tmp_path))
    (ndir / "apply_kernel.cpp").unlink()
    findings = check_srchash(str(tmp_path))
    assert any("missing source apply_kernel.cpp" in f.message
               for f in findings)


def test_changed_mode_parity_on_a_tree_with_findings(tmp_path):
    """Cache/cold parity proven on a tree that actually HAS findings of
    several families (per-file, interproc, native, srchash) — a cache
    path that drops findings cannot pass this."""
    from tools.lint.cache import lint_changed
    from tools.lint.engine import lint_repo as cold_run

    pkg = tmp_path / "stellar_core_tpu"
    (pkg / "scp").mkdir(parents=True)
    (pkg / "native").mkdir()
    (pkg / "scp" / "helpers.py").write_text(
        "def collect(envelopes):\n"
        "    out = []\n"
        "    for node, env in envelopes.items():\n"
        "        out.append(env)\n"
        "    return out\n")
    (pkg / "scp" / "sink.py").write_text(
        "import time\n\n"
        "from .helpers import collect\n\n\n"
        "def vote_hash(envelopes, h):\n"
        "    try:\n"
        "        for env in collect(envelopes):\n"
        "            h.update(env)\n"
        "    except Exception:\n"
        "        pass\n"
        "    return h.digest()\n\n\n"
        "def stamp():\n"
        "    return time.time()\n")
    (pkg / "native" / "injected.cpp").write_text(
        "#include <Python.h>\n"
        "static PyObject *f(PyObject *s) {\n"
        "    Py_BEGIN_ALLOW_THREADS;\n"
        "    PyErr_Clear();\n"
        "    Py_END_ALLOW_THREADS;\n"
        "    return NULL;\n"
        "}\n")
    (pkg / "native" / "_xdrpack.so").write_bytes(b"fake")  # no sidecar

    cold = cold_run(root=str(tmp_path))
    warm_cold, _ = lint_changed(root=str(tmp_path),
                                path=str(tmp_path / "c.json"))
    warm, stats = lint_changed(root=str(tmp_path),
                               path=str(tmp_path / "c.json"))
    assert not stats["changed"], "second run must be all cache hits"
    rules = {f.rule for f in cold}
    assert {"det-interproc-taint", "safety-swallow-except",
            "det-wallclock", "native-gil-api",
            "native-srchash"} <= rules, sorted(rules)
    assert [f.render() for f in cold] \
        == [f.render() for f in warm_cold] \
        == [f.render() for f in warm]


def test_txtrace_vitals_sanctioned_observation_only():
    """ISSUE 12 satellite: utils/txtrace.py and utils/vitals.py hold
    the lifecycle/vitals wallclock reads and are sanctioned like
    tracing.py — not taint sources AND cut as carriers — while the
    identical helper inside a consensus dir still fires the taint rule
    (proving the sanction, not the depth bound, is load-bearing)."""
    from tools.lint.callgraph import SANCTIONED_MODULES

    TXTRACE = "stellar_core_tpu/utils/txtrace.py"
    VITALS = "stellar_core_tpu/utils/vitals.py"
    assert TXTRACE in SANCTIONED_MODULES
    assert VITALS in SANCTIONED_MODULES

    helper = '''
import time


def stamp():
    return time.time()
'''
    sink = '''
from ..utils.txtrace import stamp


def vote_hash(values):
    import hashlib
    h = hashlib.sha256()
    for v in values:
        h.update(v + bytes([int(stamp()) % 7]))
    return h.digest()
'''
    # a wallclock read INSIDE txtrace.py is observation-only: no chain
    findings = lint_sources({TXTRACE: helper, SCP_SINK: sink})
    assert not [f for f in findings
                if f.rule == "det-interproc-taint"], \
        [f.render() for f in findings]

    # the SAME helper in scp/ is a live source: the sanction cut it
    sink_scp = sink.replace("from ..utils.txtrace import stamp",
                            "from .injected_helpers import stamp")
    findings = lint_sources({SCP_HELPER: helper, SCP_SINK: sink_scp})
    hits = [f for f in findings if f.rule == "det-interproc-taint"]
    assert hits, [f.render() for f in findings]
    assert "wallclock time.time()" in hits[0].message

    # carrier laundering is cut too: a consensus source wrapped by a
    # txtrace function never reaches a consensus sink as a chain (the
    # documented sanctioned-module blind spot, now pinned for txtrace)
    carrier = '''
from ..scp.injected_helpers import stamp


def wrap():
    return stamp()
'''
    sink_carrier = sink.replace(
        "from ..utils.txtrace import stamp",
        "from ..utils.txtrace import wrap").replace("stamp()", "wrap()")
    findings = lint_sources({SCP_HELPER: helper, TXTRACE: carrier,
                             SCP_SINK: sink_carrier})
    assert not [f for f in findings
                if f.rule == "det-interproc-taint"], \
        [f.render() for f in findings]


# ---------------------------------------------------------------------------
# detlint v3: whole-program concurrency analysis (ISSUE 18 tentpole)
# ---------------------------------------------------------------------------

LEDGER_A = "stellar_core_tpu/ledger/injected_a.py"
LEDGER_B = "stellar_core_tpu/ledger/injected_b.py"

_ENGINE_SRC = '''
from concurrent.futures import ThreadPoolExecutor


class Engine:
    def __init__(self):
        self.pool = ThreadPoolExecutor(max_workers=1,
                                       thread_name_prefix="close-tail")
        self.counter = 0

    def work(self):
        self.counter += 1

    def kick(self):
        self.pool.submit(self.work)

    def tick(self):
        self.counter += 1
'''


def test_conc_unguarded_shared_from_submit_reached_function():
    """A field written both from a submit-reached function (the
    worker:close-tail context inferred through the executor's
    thread_name_prefix) and from a main-context method, with no
    '# guarded-by:' annotation, goes red — and the finding names both
    contexts."""
    hits = [f for f in lint_sources({LEDGER_A: _ENGINE_SRC})
            if f.rule == "conc-unguarded-shared"]
    assert hits, "no conc-unguarded-shared finding"
    assert any("worker:close-tail" in f.message and "main" in f.message
               for f in hits), [f.render() for f in hits]


def test_conc_unguarded_shared_guard_annotation_is_clean():
    src = _ENGINE_SRC.replace(
        "        self.counter = 0",
        "        self._lock = __import__('threading').Lock()\n"
        "        self.counter = 0  # guarded-by: _lock")
    hits = [f for f in lint_sources({LEDGER_A: src})
            if f.rule == "conc-unguarded-shared"]
    assert not hits, [f.render() for f in hits]


def test_conc_class_confinement_pragma_and_baseline_round_trip():
    # class-line pragma: the whole class's fields are exempt
    src = _ENGINE_SRC.replace(
        "class Engine:",
        "class Engine:  # detlint: allow(conc-unguarded-shared)")
    hits = [f for f in lint_sources({LEDGER_A: src})
            if f.rule == "conc-unguarded-shared"]
    assert not hits, [f.render() for f in hits]
    # baseline round-trip: the unpragma'd finding pins by identity
    hits = [f for f in lint_sources({LEDGER_A: _ENGINE_SRC})
            if f.rule == "conc-unguarded-shared"]
    entry = {"rule": hits[0].rule, "file": hits[0].file,
             "context": hits[0].context,
             "line_text": hits[0].line_text, "justification": "test"}
    fresh, pinned, stale = match_baseline([hits[0]], [entry])
    assert not fresh and pinned and not stale


def test_conc_shipped_baseline_is_empty():
    """ISSUE 18 satellite 1: conc-unguarded-shared ships with an EMPTY
    baseline — every hit in the tree was fixed or justified with a
    pragma, none parked.  Pinned here so it stays that way."""
    assert not [e for e in load_baseline()
                if str(e.get("rule", "")).startswith("conc-")]


def test_conc_thread_affine_sqlite_from_worker_context():
    src = '''
import sqlite3
from concurrent.futures import ThreadPoolExecutor


class Store:
    def __init__(self):
        self.conn = sqlite3.connect(":memory:")
        self.pool = ThreadPoolExecutor(max_workers=1,
                                       thread_name_prefix="bucket-merge")

    def flush(self):
        self.conn.execute("DELETE FROM t")

    def kick(self):
        self.pool.submit(self.flush)
'''
    hits = [f for f in lint_sources({LEDGER_A: src})
            if f.rule == "conc-thread-affine-call"]
    assert hits, "no conc-thread-affine-call finding"
    assert any("sqlite-conn" in f.message
               and "worker:bucket-merge" in f.message for f in hits), \
        [f.render() for f in hits]


def test_conc_cross_file_lock_cycle_with_chain():
    """Opposite-order acquisition split across two files, visible only
    interprocedurally (each file alone is clean): the conc-lock-cycle
    finding carries the full ring and per-edge witness chain."""
    src_a = '''
import threading


class Alpha:
    def __init__(self):
        self._alock = threading.Lock()

    def enter_alpha(self):
        with self._alock:
            pass

    def do_alpha(self, beta):
        with self._alock:
            beta.enter_beta()
'''
    src_b = '''
import threading


class Beta:
    def __init__(self):
        self._block = threading.Lock()

    def enter_beta(self):
        with self._block:
            pass

    def do_beta(self, alpha):
        with self._block:
            alpha.enter_alpha()
'''
    hits = [f for f in lint_sources({LEDGER_A: src_a, LEDGER_B: src_b})
            if f.rule == "conc-lock-cycle"]
    assert hits, "no conc-lock-cycle finding"
    msg = hits[0].message
    assert "_alock" in msg and "_block" in msg, msg
    assert "->" in msg       # the ring
    assert "injected" in msg  # per-edge witness carries file:line
    # each file alone is clean — the cycle exists only package-wide
    for solo in (src_a, src_b):
        assert not [f for f in lint_sources({LEDGER_A: solo})
                    if f.rule == "conc-lock-cycle"]


def test_conc_interproc_exoneration_of_v1_unguarded_write():
    """The v1 lexical rule flags a guarded write outside a with-lock
    block; the whole-program pass exonerates it when EVERY caller holds
    the declared lock at the call site (held-at-entry intersection)."""
    src = '''
import threading


class Tracker:
    def __init__(self):
        self._lock = threading.Lock()
        self.seen = 0  # guarded-by: _lock

    def stamp(self):
        with self._lock:
            self._finish()

    def _finish(self):
        self.seen += 1
'''
    from tools.lint import locks as locks_rule
    from tools.lint.engine import _parse_file

    info = _parse_file(LEDGER_A, src)
    assert any(f.rule == "lock-unguarded-write"
               for f in locks_rule.check([info])), \
        "lexical rule should flag the helper write"
    # ...but the whole-program run discharges it interprocedurally
    hits = [f for f in lint_sources({LEDGER_A: src})
            if f.rule == "lock-unguarded-write"]
    assert not hits, [f.render() for f in hits]


def test_conc_changed_cache_parity_on_findings_bearing_tree(tmp_path):
    """Cold vs --changed cache parity when concurrency findings EXIST:
    the conc summaries round-trip through the cache json and the
    global pass reproduces the same findings from cached per-file
    facts (the satellite-4 fingerprint/parity contract)."""
    from tools.lint.cache import lint_changed

    pkg = tmp_path / "stellar_core_tpu" / "ledger"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "eng.py").write_text(_ENGINE_SRC)
    cpath = str(tmp_path / "cache.json")
    cold, s1 = lint_changed(root=str(tmp_path), path=cpath)
    assert s1["reused"] == 0
    warm, s2 = lint_changed(root=str(tmp_path), path=cpath)
    assert not s2["changed"] and s2["reused"] == 2
    assert [f.render() for f in cold] == [f.render() for f in warm]
    assert any(f.rule == "conc-unguarded-shared" for f in warm)


def test_conc_threads_dump_cli():
    """--threads inventory: thread roots + runs-on histogram, and the
    real tree resolves the known worker pools."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--threads"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = proc.stdout
    assert "worker:close-tail" in out
    assert "worker:bucket-merge" in out
