#!/usr/bin/env python
"""The snapshot-must-be-green gate (VERDICT r5; ISSUE r7 satellite): run
the detlint static analyzer in --strict mode, then the tier-1 command
EXACTLY as ROADMAP.md states it, and exit nonzero on any unbaselined
lint finding, test failure OR collection error.

Lint findings are reported DISTINCTLY from test failures (separate
"verify_green: LINT RED" line) so a red gate immediately says which
discipline broke.  The tier-1 command is parsed out of ROADMAP.md
(single source of truth: the driver, the builder and this gate all run
the same line).  pytest's exit code already covers failures; collection
errors are additionally grepped out of the log because
`--continue-on-collection-errors` can leave a "green-looking" run that
silently skipped whole files.

After the default pass, a PARALLEL-APPLY SMOKE re-runs the tier-1 line
with ``PARALLEL_APPLY_WORKERS=2`` exported (flipping every test
Application onto the apply/ planner+executor path) and reports the
aborts observed across the suite (aggregated from the per-Application
stats lines written via ``PARALLEL_APPLY_STATS_FILE``).  Bit-identity
means the same suite must stay green either way.

Usage: python tools/verify_green.py            -> exit 0 iff green
       python tools/verify_green.py --timings  -> also print the 10
           slowest tier-1 test FILES (aggregated from pytest's own
           --durations accounting)
       python tools/verify_green.py --lint-only -> CI-style fast gate:
           ONLY detlint v2 --strict (determinism + interprocedural
           taint with source->sink chains + native-kernel auditor +
           safety rules), no pytest; exit code is the lint verdict.
       --skip-parallel-smoke / --parallel-smoke-only control the second
           pass; --skip-chaos-smoke skips the chaos scenario smoke (one
           core-4 partition+heal run incl. the same-seed determinism
           rerun, via tools/chaos_bench.py); --skip-pipeline-smoke
           skips the PIPELINED_CLOSE=1 tier-1 rerun + the on/off
           hash/meta parity mini-bench (tools/pipeline_bench.py);
           --skip-soak-smoke skips the ~30 s sustained-load soak
           (tools/soak_bench.py --smoke: vitals ring populated, memory
           slope under the SLO ceiling, zero breaches, telemetry
           disabled-cost <1% and on/off hash parity);
           --skip-catchup-smoke skips the cold-join catchup gate
           (tools/catchup_bench.py --smoke: a cold node joins a live
           core-2 net mid-traffic, catches up via verified bucket
           apply AND full replay, both ending bit-identical to the
           validators); --skip-lockdep-smoke skips the runtime
           lockdep-witness gate; --skip-netobs-smoke skips the
           network-observatory gate (tools/chaos_bench.py --netobs
           --tier core4: hop records nonzero, coverage percentiles
           present, crank attribution >= 90%, tracing overhead < 2%,
           on/off hash+meta inertness); --skip-fuzz-smoke skips the
           fault-schedule-fuzzer gate (tools/fuzz_bench.py --smoke:
           budget-capped seeded schedules on core-4 + one tiered net
           under the full oracle stack, plus the known-bad ->
           ddmin-minimize -> replay-identical proof).
       python tools/verify_green.py --netobs-smoke -> ONLY the
           network-observatory gate above.
       python tools/verify_green.py --fuzz-smoke -> ONLY the
           fault-schedule-fuzzer gate above.
       python tools/verify_green.py --lockdep-smoke -> ONLY the
           runtime witness gate: the threaded-subsystem tier-1 subset,
           one core-4 chaos scenario and one pipelined-close bench
           iteration all under LOCKDEP=1 (every registered lock
           order-witnessed, every # guarded-by: write assert-held
           checked), zero LockOrderInversion/GuardViolation required,
           plus the <1%-of-close-p50 witness-overhead micro-gate.
"""
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tier1_command() -> str:
    text = open(os.path.join(REPO, "ROADMAP.md")).read()
    m = re.search(r"\*\*Tier-1 verify:\*\* `(.+?)`", text, re.S)
    if not m:
        print("verify_green: no tier-1 command found in ROADMAP.md",
              file=sys.stderr)
        sys.exit(2)
    return m.group(1)


def run_detlint() -> int:
    """python -m tools.lint --strict; nonzero = unbaselined findings."""
    print("verify_green: python -m tools.lint --strict", flush=True)
    proc = subprocess.run([sys.executable, "-m", "tools.lint", "--strict"],
                          cwd=REPO)
    return proc.returncode


def print_timings(log: str, top_n: int = 10) -> None:
    """Aggregate pytest's --durations lines (``0.42s call path::test``)
    per test FILE and print the slowest."""
    totals = {}
    for m in re.finditer(
            r"^\s*([0-9.]+)s\s+(?:call|setup|teardown)\s+([^:\s]+)::",
            log, re.M):
        totals[m.group(2)] = totals.get(m.group(2), 0.0) + \
            float(m.group(1))
    if not totals:
        print("verify_green: no duration lines found in the tier-1 log",
              flush=True)
        return
    print(f"verify_green: {top_n} slowest test files:", flush=True)
    width = max(len(f) for f in totals)
    for f, s in sorted(totals.items(), key=lambda kv: -kv[1])[:top_n]:
        print(f"  {f:<{width}}  {s:8.2f}s", flush=True)


def run_parallel_smoke(cmd: str) -> "tuple":
    """The tier-1 line again with parallel apply forced on.  Returns
    (problems, passed, abort_summary)."""
    log_path = "/tmp/_t1p_parallel.log"
    smoke_cmd = cmd.replace("/tmp/_t1.log", log_path)
    stats_path = "/tmp/_t1p_parallel_apply_stats.jsonl"
    try:
        os.unlink(stats_path)
    except OSError:
        pass
    env = dict(os.environ)
    env["PARALLEL_APPLY_WORKERS"] = "2"
    env["PARALLEL_APPLY_STATS_FILE"] = stats_path
    print("verify_green: [parallel smoke] PARALLEL_APPLY_WORKERS=2 "
          f"{smoke_cmd}", flush=True)
    proc = subprocess.run(["bash", "-c", smoke_cmd], cwd=REPO, env=env)
    problems = []
    if proc.returncode != 0:
        problems.append(f"parallel smoke exited {proc.returncode}")
    try:
        with open(log_path, errors="replace") as f:
            log = f.read()
    except OSError:
        problems.append("parallel smoke log missing")
        log = ""
    tail = "\n".join(log.splitlines()[-30:])
    for pat, what in ((r"\b([1-9]\d*) failed\b", "failed tests"),
                      (r"\b([1-9]\d*) errors?\b", "collection errors")):
        m = re.search(pat, tail)
        if m:
            problems.append(f"parallel smoke: {m.group(1)} {what}")
    m = re.search(r"\b(\d+) passed\b", tail)
    passed = m.group(1) if m else "?"
    totals = {"parallel_closes": 0, "sequential_closes": 0, "aborts": 0,
              "unplanned": 0, "native_hits": 0, "native_declines": 0,
              "sessions": 0}
    reasons = []
    try:
        with open(stats_path, errors="replace") as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                totals["sessions"] += 1
                for k in ("parallel_closes", "sequential_closes",
                          "aborts", "unplanned", "native_hits",
                          "native_declines"):
                    totals[k] += int(row.get(k, 0))
                reasons.extend(row.get("escape_reasons", []))
    except OSError:
        pass
    summary = (f"{totals['parallel_closes']} parallel closes, "
               f"{totals['aborts']} aborts, "
               f"{totals['unplanned']} unplanned, "
               f"{totals['native_hits']} native hits, "
               f"{totals['native_declines']} declines, "
               f"{totals['sessions']} app sessions")
    if reasons:
        summary += f"; escapes: {reasons[:4]}"
    return problems, passed, summary


def run_pipelined_smoke(cmd: str) -> "tuple":
    """The tier-1 line again with PIPELINED_CLOSE=1 exported: every
    test Application closes through the pipelined engine (MANUAL_CLOSE
    rigs eager-drain per close, so post-close reads keep sequential
    semantics while the stage/tail/overlay machinery runs for real).
    Afterwards a miniature tools/pipeline_bench.py run checks the
    on/off hash+meta parity summary end to end.  Returns
    (problems, passed, summary)."""
    log_path = "/tmp/_t1p_pipeline.log"
    smoke_cmd = cmd.replace("/tmp/_t1.log", log_path)
    stats_path = "/tmp/_t1p_pipeline_stats.jsonl"
    try:
        os.unlink(stats_path)
    except OSError:
        pass
    env = dict(os.environ)
    env["PIPELINED_CLOSE"] = "1"
    env["PIPELINED_CLOSE_STATS_FILE"] = stats_path
    print(f"verify_green: [pipeline smoke] PIPELINED_CLOSE=1 "
          f"{smoke_cmd}", flush=True)
    proc = subprocess.run(["bash", "-c", smoke_cmd], cwd=REPO, env=env)
    problems = []
    if proc.returncode != 0:
        problems.append(f"pipeline smoke exited {proc.returncode}")
    try:
        with open(log_path, errors="replace") as f:
            log = f.read()
    except OSError:
        problems.append("pipeline smoke log missing")
        log = ""
    tail = "\n".join(log.splitlines()[-30:])
    for pat, what in ((r"\b([1-9]\d*) failed\b", "failed tests"),
                      (r"\b([1-9]\d*) errors?\b", "collection errors")):
        m = re.search(pat, tail)
        if m:
            problems.append(f"pipeline smoke: {m.group(1)} {what}")
    m = re.search(r"\b(\d+) passed\b", tail)
    passed = m.group(1) if m else "?"
    totals = {"sessions": 0, "tails": 0, "tail_failures": 0,
              "prefetch_adopted": 0}
    try:
        with open(stats_path, errors="replace") as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                totals["sessions"] += 1
                for k in ("tails", "tail_failures", "prefetch_adopted"):
                    totals[k] += int(row.get(k, 0))
    except OSError:
        pass
    if totals["tail_failures"]:
        problems.append(
            f"pipeline smoke: {totals['tail_failures']} tail failures")
    # the on/off parity summary: a miniature bench run (2 closes/arm of
    # 120 txs) whose parity pass compares per-close header/bucket
    # hashes AND meta bytes pipeline-on vs off
    bench_out = "/tmp/_t1p_pipeline_bench.json"
    bench_env = dict(os.environ)
    bench_env.update({"BENCH_CLOSES": "2", "BENCH_CLOSE_TXS": "120",
                      "JAX_PLATFORMS": "cpu",
                      "PIPELINE_BENCH_OUT": bench_out})
    bench = subprocess.run(
        [sys.executable, os.path.join("tools", "pipeline_bench.py")],
        cwd=REPO, env=bench_env, capture_output=True, text=True)
    parity = "unchecked"
    if bench.returncode != 0:
        problems.append("pipeline parity bench failed: "
                        + "\n".join(bench.stderr.splitlines()[-3:]))
        parity = "failed"
    else:
        try:
            with open(bench_out) as f:
                rep = json.load(f)["parity"]
            parity = ("identical" if rep.get("hashes_identical")
                      and rep.get("meta_bytes_identical") else "DIVERGED")
            if parity == "DIVERGED":
                problems.append("pipeline on/off hash parity DIVERGED")
        except (OSError, ValueError, KeyError) as e:
            problems.append(f"pipeline parity report unreadable: {e}")
            parity = "unreadable"
    summary = (f"{totals['tails']} tails over {totals['sessions']} app "
               f"sessions, {totals['tail_failures']} tail failures, "
               f"{totals['prefetch_adopted']} prefetched keys adopted, "
               f"on/off parity {parity}")
    return problems, passed, summary


def run_chaos_smoke() -> "tuple":
    """One small chaos scenario end-to-end (core-4 partition+heal, with
    the same-seed determinism rerun): the full fault-inject -> heal ->
    no-fork -> bit-identical-fingerprint contract in ~20s.  Returns
    (problems, summary)."""
    out = "/tmp/_t1_chaos_smoke.json"
    cmd = [sys.executable, "-m", "tools.chaos_bench", "--tier", "core4",
           "--scenario", "partition_heal", "--out", out]
    print(f"verify_green: [chaos smoke] {' '.join(cmd)}", flush=True)
    proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    problems = []
    if proc.returncode != 0:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-6:])
        return [f"chaos smoke exited {proc.returncode}: {tail}"], "failed"
    try:
        with open(out) as f:
            rep = json.load(f)["scenarios"][0]
    except (OSError, ValueError, KeyError, IndexError) as e:
        return [f"chaos smoke report unreadable: {e}"], "failed"
    if rep.get("fork_check") != "pass":
        problems.append("chaos smoke: fork check failed")
    if rep.get("rerun_identical") is not True:
        problems.append("chaos smoke: same-seed rerun not bit-identical")
    summary = (f"{rep.get('ledgers_closed')} ledgers, "
               f"heal={rep.get('time_to_heal_s')}s, "
               f"fork={rep.get('fork_check')}, "
               f"rerun_identical={rep.get('rerun_identical')}")
    return problems, summary


def run_forensics_smoke() -> "tuple":
    """The consensus-forensics gate (tools/scp_forensics_bench.py
    --smoke): a deliberately-unsafe core-4 net with a full Byzantine
    bridge MUST fork, the FORENSICS_*.json dump must attribute the
    first divergence to the Byzantine node via equivocation evidence,
    and a same-seed rerun must reproduce the dump byte-for-byte.  The
    recorder-overhead A/B rides along (informational at smoke scale;
    the <2% acceptance gate is the full 1000-tx bench artifact).
    Returns (problems, summary)."""
    out = "/tmp/_t1_forensics_smoke.json"
    cmd = [sys.executable, "-m", "tools.scp_forensics_bench",
           "--smoke", "--out", out]
    print(f"verify_green: [forensics smoke] {' '.join(cmd)}", flush=True)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)
    try:
        with open(out) as f:
            rep = json.load(f)
    except (OSError, ValueError) as e:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-6:])
        return [f"forensics smoke report unreadable: {e}: {tail}"], \
            "failed"
    problems = []
    probe = rep.get("fork_probe", {})
    if not probe.get("attributed_to_byzantine"):
        problems.append(
            f"forensics smoke: fork NOT attributed to the Byzantine "
            f"node (first_divergence={probe.get('first_divergence')}, "
            f"byzantine={probe.get('byzantine')})")
    if probe.get("rerun_dump_identical") is not True:
        problems.append(
            "forensics smoke: same-seed FORENSICS dump not "
            "byte-identical")
    overhead = rep.get("overhead", {}).get("overhead_pct_p50")
    summary = (f"fork at slot {probe.get('divergence_slot')} attributed "
               f"to {probe.get('first_divergence', {}).get('node')} "
               f"(byz={probe.get('byzantine')}), dump deterministic="
               f"{probe.get('rerun_dump_identical')}, recorder overhead "
               f"{overhead}% (smoke scale)")
    return problems, summary


def run_catchup_smoke() -> "tuple":
    """The fast-catchup gate (tools/catchup_bench.py --smoke): a small
    cold-join scenario — seed a core-2 net with traffic, publish
    checkpoints, then a minimal-mode joiner AND a complete-mode joiner
    each sync against the live network (closes keep arriving) and must
    end bit-identical (header hash + bucketListHash) to the validators.
    The 5x minimal-vs-complete speedup assertion is full-tier only; at
    smoke scale this checks correctness, not the ratio.  Returns
    (problems, summary)."""
    out = "/tmp/_t1_catchup_smoke.json"
    cmd = [sys.executable, os.path.join("tools", "catchup_bench.py"),
           "--smoke", "--out", out]
    print(f"verify_green: [catchup smoke] {' '.join(cmd)}", flush=True)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=900)
    if proc.returncode != 0:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-6:])
        return [f"catchup smoke exited {proc.returncode}: {tail}"], \
            "failed"
    try:
        with open(out) as f:
            rep = json.load(f)
    except (OSError, ValueError) as e:
        return [f"catchup smoke report unreadable: {e}"], "failed"
    problems = []
    mn, cp = rep.get("minimal", {}), rep.get("complete", {})
    for tag, row in (("minimal", mn), ("complete", cp)):
        if row.get("bit_identical") is not True:
            problems.append(f"catchup smoke: {tag} joiner NOT "
                            "bit-identical to the validators")
    if mn.get("bucket_applied_entries", 0) <= 0:
        problems.append("catchup smoke: minimal joiner applied no "
                        "bucket entries")
    if cp.get("ledgers_replayed", 0) <= 0:
        problems.append("catchup smoke: complete joiner replayed no "
                        "ledgers")
    summary = (f"minimal {mn.get('time_to_synced_s')}s "
               f"(trailing {mn.get('trailing_ledgers_at_join')}, "
               f"{mn.get('bucket_apply_mb_s')} MB/s apply, "
               f"{mn.get('chain_headers_verified')} headers verified), "
               f"complete {cp.get('time_to_synced_s')}s "
               f"({cp.get('ledgers_replayed')} ledgers replayed), "
               f"speedup {rep.get('minimal_speedup_vs_complete')}x, "
               f"both bit-identical")
    return problems, summary


def run_soak_smoke() -> "tuple":
    """A ~30-clock-second sustained-load soak (tools/soak_bench.py
    --smoke): rate-mode load on a disk-backed REAL_TIME node, then the
    vitals/SLO verdicts — the ring must be populated, the RSS slope
    must sit under the watchdog ceiling (zero SLO breaches), the
    telemetry disabled-cost must stay <1% of close p50, and the
    telemetry on/off hash+meta parity must hold.  Returns
    (problems, summary)."""
    out = "/tmp/_t1_soak_smoke.json"
    cmd = [sys.executable, os.path.join("tools", "soak_bench.py"),
           "--smoke", "--out", out]
    print(f"verify_green: [soak smoke] {' '.join(cmd)}", flush=True)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)
    if proc.returncode != 0:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-6:])
        return [f"soak smoke exited {proc.returncode}: {tail}"], "failed"
    try:
        with open(out) as f:
            rep = json.load(f)
    except (OSError, ValueError) as e:
        return [f"soak smoke report unreadable: {e}"], "failed"
    problems = []
    vit = rep.get("vitals", {})
    if vit.get("samples", 0) < 10:
        problems.append(
            f"soak smoke: vitals ring underpopulated "
            f"({vit.get('samples')} samples over a ~30 s run)")
    if not rep.get("slo", {}).get("watchdog_green"):
        problems.append(
            f"soak smoke: SLO breaches {rep.get('slo', {})}")
    cost = rep.get("disabled_cost", {})
    disabled_pct = cost.get("disabled_pct")
    if disabled_pct is None or disabled_pct >= 1.0:
        problems.append(
            f"soak smoke: telemetry disabled-cost {disabled_pct}% of "
            f"close p50 (gate: <1%)")
    par = rep.get("parity", {})
    if not (par.get("hashes_identical") and
            par.get("meta_bytes_identical")):
        problems.append("soak smoke: telemetry on/off parity DIVERGED")
    summary = (f"{rep.get('sustained', {}).get('applied_tx_s')} tx/s "
               f"applied over "
               f"{rep.get('sustained', {}).get('ledgers_closed')} "
               f"ledgers, rss slope "
               f"{vit.get('rss_slope_mb_s')} MB/s, "
               f"{vit.get('samples')} vitals samples, disabled-cost "
               f"{disabled_pct}% (enabled A/B "
               f"{cost.get('enabled_overhead_pct')}%), parity "
               f"{'ok' if par.get('hashes_identical') else 'FAILED'}")
    return problems, summary


def run_netobs_smoke() -> "tuple":
    """The network-observatory gate (tools/chaos_bench.py --netobs
    --tier core4): a core-4 sim under chaos + rate-mode loadgen with
    flood tracing ON, then the same run with tracing OFF — nonzero hop
    records, coverage percentiles present, crank wall attribution
    >= 90%, tracing disabled-cost < 2% of close p50, and on/off
    hash+meta inertness.  The tiered-50 tier is full-bench only
    (NET_OBS_r19.json).  Returns (problems, summary)."""
    out = "/tmp/_t1_netobs_smoke.json"
    cmd = [sys.executable, "-m", "tools.chaos_bench", "--netobs",
           "--tier", "core4", "--out", out]
    print(f"verify_green: [netobs smoke] {' '.join(cmd)}", flush=True)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)
    if proc.returncode != 0:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-6:])
        return [f"netobs smoke exited {proc.returncode}: {tail}"], \
            "failed"
    try:
        with open(out) as f:
            rep = json.load(f)["tiers"]["core4"]
    except (OSError, ValueError, KeyError) as e:
        return [f"netobs smoke report unreadable: {e}"], "failed"
    problems = []
    g = rep.get("gates", {})
    if not g.get("hop_records_nonzero"):
        problems.append("netobs smoke: no flood hop records")
    if not g.get("coverage_percentiles_present"):
        problems.append("netobs smoke: coverage percentiles missing")
    overhead = g.get("tracing_overhead_pct")
    if not g.get("tracing_overhead_ok"):
        problems.append(f"netobs smoke: tracing disabled-cost "
                        f"{overhead}% of close p50 (gate: <2%)")
    if not g.get("inert_hashes_and_meta"):
        problems.append("netobs smoke: tracing on/off hash/meta "
                        "parity DIVERGED")
    if not g.get("attribution_ok"):
        problems.append(f"netobs smoke: only {g.get('attributed_pct')}% "
                        f"of crank wall attributed (gate: >=90%)")
    prop = rep.get("on", {}).get("observatory", {}).get("propagation", {})
    t90 = (prop.get("time_to_90pct") or {}).get("p90")
    summary = (f"{rep.get('on', {}).get('hop_records_total')} hop "
               f"records, t90 p90={t90}s, disabled-cost {overhead}% "
               f"(enabled A/B {g.get('enabled_overhead_pct')}%), "
               f"attributed {g.get('attributed_pct')}%, "
               f"inert={'ok' if g.get('inert_hashes_and_meta') else 'FAILED'}")
    return problems, summary


#: threaded-subsystem tier-1 subset the lockdep witness re-runs: every
#: file that exercises the pipelined close, the bucket background
#: merge/GC, or a registered lock directly
LOCKDEP_T1_SUBSET = [
    "tests/test_lockdep.py",
    "tests/test_pipelined_close.py",
    "tests/test_bucket_list.py",
    "tests/test_metrics.py",
    "tests/test_txtrace.py",
    "tests/test_tracing.py",
]

def run_lockdep_smoke() -> "tuple":
    """The detlint-v3 runtime witness gate, everything under LOCKDEP=1:
    (a) the threaded-subsystem tier-1 subset, (b) one core-4
    partition+heal chaos scenario, (c) one pipelined-close bench
    iteration — all with every registered lock wrapped and every
    ``# guarded-by:`` field write assert-held-checked; ANY
    LockOrderInversion or GuardViolation is red.  A per-acquire
    micro-benchmark then bounds the enabled-witness cost at <1% of the
    close p50 the bench just measured.  Returns (problems, summary)."""
    problems = []
    env = dict(os.environ)
    env["LOCKDEP"] = "1"
    env.setdefault("JAX_PLATFORMS", "cpu")

    # (a) the threaded tier-1 subset
    log_path = "/tmp/_t1_lockdep.log"
    sub_cmd = (f"timeout -k 10 600 {sys.executable} -m pytest "
               f"{' '.join(LOCKDEP_T1_SUBSET)} -q -m 'not slow' "
               f"-p no:cacheprovider -p no:xdist -p no:randomly "
               f"> {log_path} 2>&1")
    print(f"verify_green: [lockdep smoke] LOCKDEP=1 {sub_cmd}",
          flush=True)
    proc = subprocess.run(["bash", "-c", sub_cmd], cwd=REPO, env=env)
    try:
        with open(log_path, errors="replace") as f:
            log = f.read()
    except OSError:
        log = ""
    if proc.returncode != 0:
        problems.append(f"lockdep smoke: subset exited {proc.returncode}")
    tail = "\n".join(log.splitlines()[-30:])
    m = re.search(r"\b([1-9]\d*) failed\b", tail)
    if m:
        problems.append(f"lockdep smoke: {m.group(1)} failed tests")
    m = re.search(r"\b(\d+) passed\b", tail)
    passed = m.group(1) if m else "?"

    # (b) one chaos scenario with the witness armed
    chaos_out = "/tmp/_t1_lockdep_chaos.json"
    chaos_cmd = [sys.executable, "-m", "tools.chaos_bench", "--tier",
                 "core4", "--scenario", "partition_heal", "--out",
                 chaos_out]
    print(f"verify_green: [lockdep smoke] LOCKDEP=1 "
          f"{' '.join(chaos_cmd)}", flush=True)
    chaos = subprocess.run(chaos_cmd, cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=600)
    chaos_note = "ok"
    if chaos.returncode != 0:
        tail2 = "\n".join((chaos.stdout + chaos.stderr).splitlines()[-6:])
        problems.append(
            f"lockdep smoke: chaos exited {chaos.returncode}: {tail2}")
        chaos_note = "failed"
    log += chaos.stdout + chaos.stderr

    # (c) one pipelined-close bench iteration with the witness armed:
    # the probe mode runs pay closes on one app and reports the
    # lockdep.stats() DELTA across the timed loop — the measured
    # acquires + guarded-field checks PER CLOSE, plus the close p50
    # those closes actually achieved under the witness
    bench_out = "/tmp/_t1_lockdep_pipeline.json"
    bench_env = dict(env)
    bench_env.update({"BENCH_CLOSES": "6", "BENCH_CLOSE_TXS": "120",
                      "PIPELINE_BENCH_OUT": bench_out})
    bench_cmd = [sys.executable, os.path.join("tools",
                                              "pipeline_bench.py"),
                 "--lockdep-probe"]
    print(f"verify_green: [lockdep smoke] LOCKDEP=1 "
          f"{' '.join(bench_cmd)}", flush=True)
    bench = subprocess.run(bench_cmd, cwd=REPO, env=bench_env,
                           capture_output=True, text=True, timeout=600)
    probe = None
    if bench.returncode != 0:
        tail3 = "\n".join((bench.stdout + bench.stderr).splitlines()[-6:])
        problems.append(
            f"lockdep smoke: pipeline probe exited {bench.returncode}: "
            f"{tail3}")
    else:
        try:
            with open(bench_out) as f:
                probe = json.load(f)
            if probe.get("inversions") or probe.get("guard_violations"):
                problems.append(
                    f"lockdep smoke: probe saw "
                    f"{probe.get('inversions')} inversions / "
                    f"{probe.get('guard_violations')} guard violations")
        except (OSError, ValueError) as e:
            problems.append(
                f"lockdep smoke: probe report unreadable: {e}")
    log += bench.stdout + bench.stderr

    # zero-violations gate: inversions/guard trips raise and fail their
    # run above, but scan the combined output too so a swallowed one
    # still reds the gate with its name attached
    for marker in ("LockOrderInversion", "GuardViolation"):
        n = log.count(marker)
        if n:
            problems.append(f"lockdep smoke: {n} {marker} in output")

    # overhead gate: A/B micro-bench of the enabled witness (wrapped vs
    # raw lock, plus one guarded-field check), scaled by the per-close
    # counts the probe just MEASURED, bounded at <1% of the probe's
    # close p50
    micro = subprocess.run(
        [sys.executable, "-c", (
            "import json, threading, time\n"
            "from stellar_core_tpu.utils import lockdep\n"
            "raw = threading.Lock()\n"
            "wit = lockdep.register_lock(threading.Lock(), 'bench')\n"
            "assert isinstance(wit, lockdep.WitnessLock)\n"
            "def per_acquire(lk, n=200000):\n"
            "    for _ in range(n // 10):\n"
            "        with lk:\n"
            "            pass\n"
            "    t0 = time.perf_counter()\n"
            "    for _ in range(n):\n"
            "        with lk:\n"
            "            pass\n"
            "    return (time.perf_counter() - t0) / n\n"
            "class B:\n"
            "    pass\n"
            "b = B()\n"
            "b.__dict__['_lock'] = wit\n"
            "b.__dict__['_lockdep_enforced'] = True\n"
            "desc = lockdep._GuardedField('val', '_lock')\n"
            "def per_check(n=200000):\n"
            "    with wit:\n"
            "        t0 = time.perf_counter()\n"
            "        for i in range(n):\n"
            "            desc.__set__(b, i)\n"
            "        return (time.perf_counter() - t0) / n\n"
            "print(json.dumps({'raw_us': per_acquire(raw) * 1e6,\n"
            "                  'wit_us': per_acquire(wit) * 1e6,\n"
            "                  'check_us': per_check() * 1e6}))\n")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    overhead_note = "unmeasured"
    if micro.returncode != 0:
        problems.append("lockdep smoke: overhead micro-bench failed: "
                        + "\n".join(micro.stderr.splitlines()[-3:]))
    elif probe is not None:
        try:
            row = json.loads(micro.stdout.strip().splitlines()[-1])
            acq_us = max(0.0, row["wit_us"] - row["raw_us"])
            chk_us = row["check_us"]
            per_close_ms = (
                acq_us * probe.get("acquires_per_close", 0.0)
                + chk_us * probe.get("guard_checks_per_close", 0.0)
            ) / 1000.0
            p50 = probe.get("close_p50_ms") or 20.0
            pct = per_close_ms / p50 * 100.0
            overhead_note = (
                f"{acq_us:.2f}us x {probe.get('acquires_per_close')} "
                f"acquires + {chk_us:.2f}us x "
                f"{probe.get('guard_checks_per_close')} checks = "
                f"{per_close_ms:.3f}ms/close = {pct:.2f}% of close "
                f"p50 {p50}ms")
            if pct >= 1.0:
                problems.append(
                    f"lockdep smoke: witness overhead {overhead_note} "
                    f"(gate: <1%)")
        except (ValueError, KeyError, IndexError) as e:
            problems.append(
                f"lockdep smoke: overhead report unreadable: {e}")
    summary = (f"subset passed={passed}, chaos {chaos_note}, "
               f"0 violations, witness overhead {overhead_note}")
    return problems, summary


def run_fuzz_smoke() -> "tuple":
    """The fault-schedule fuzzer gate (tools/fuzz_bench.py --smoke): a
    budget-capped campaign of seeded schedules on the smoke grid
    (core-4 + one tiered net) under the full oracle stack, plus the
    known-bad proof — the injected fork schedule must be found,
    ddmin-minimized to its essential events, and its persisted repro
    artifact must replay to the same failure fingerprint.  Red on any
    oracle failure and on a non-reproducing minimized artifact.
    Returns (problems, summary)."""
    out = "/tmp/_t1_fuzz_smoke.json"
    cmd = [sys.executable, "-m", "tools.fuzz_bench", "--smoke",
           "--out", out]
    print(f"verify_green: [fuzz smoke] {' '.join(cmd)}", flush=True)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=900)
    try:
        with open(out) as f:
            rep = json.load(f)
    except (OSError, ValueError) as e:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-6:])
        return [f"fuzz smoke report unreadable: {e}: {tail}"], "failed"
    problems = [f"fuzz smoke: {p}" for p in rep.get("problems", [])]
    if proc.returncode != 0 and not problems:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-6:])
        problems.append(f"fuzz smoke exited {proc.returncode}: {tail}")
    camp = rep.get("campaigns", {}).get("smoke", {})
    kb = rep.get("known_bad", {})
    ab = rep.get("slice_eval_ab", {}).get("50", {})
    summary = (f"{camp.get('schedules_executed')} schedules "
               f"({camp.get('failure_count')} failures, "
               f"{camp.get('unique_novelty')} novel), known-bad "
               f"{kb.get('events_before')}->{kb.get('events_after')} "
               f"events, replay={kb.get('replay_reproduced')}, "
               f"A/B@50 {ab.get('speedup')}x")
    return problems, summary


def main() -> int:
    timings = "--timings" in sys.argv
    if "--lint-only" in sys.argv:
        # the fast CI gate: the native auditor + interprocedural taint
        # pass (with source->sink chains in every finding) run inside
        # the same strict lint; a red exit here is a LINT RED verdict
        lint_rc = run_detlint()
        if lint_rc != 0:
            print(f"verify_green: LINT RED (detlint --strict exited "
                  f"{lint_rc})", flush=True)
            return 1
        print("verify_green: LINT GREEN (detlint --strict clean)",
              flush=True)
        return 0
    if "--netobs-smoke" in sys.argv:
        # standalone network-observatory gate: core-4 chaos+load with
        # flood tracing on/off, asserting the persisted r19 gates
        no_problems, no_summary = run_netobs_smoke()
        print(f"verify_green: netobs smoke: {no_summary}", flush=True)
        if no_problems:
            print(f"verify_green: RED ({'; '.join(no_problems)})",
                  flush=True)
            return 1
        print(f"verify_green: GREEN (netobs smoke: {no_summary})",
              flush=True)
        return 0
    if "--lockdep-smoke" in sys.argv:
        # standalone runtime-witness gate: everything under LOCKDEP=1
        ld_problems, ld_summary = run_lockdep_smoke()
        print(f"verify_green: lockdep smoke: {ld_summary}", flush=True)
        if ld_problems:
            print(f"verify_green: RED ({'; '.join(ld_problems)})",
                  flush=True)
            return 1
        print(f"verify_green: GREEN (lockdep smoke: {ld_summary})",
              flush=True)
        return 0
    if "--fuzz-smoke" in sys.argv:
        # standalone fault-schedule-fuzzer gate: budget-capped seeded
        # schedules + the known-bad minimize/replay proof
        fz_problems, fz_summary = run_fuzz_smoke()
        print(f"verify_green: fuzz smoke: {fz_summary}", flush=True)
        if fz_problems:
            print(f"verify_green: RED ({'; '.join(fz_problems)})",
                  flush=True)
            return 1
        print(f"verify_green: GREEN (fuzz smoke: {fz_summary})",
              flush=True)
        return 0
    smoke_only = "--parallel-smoke-only" in sys.argv
    skip_smoke = "--skip-parallel-smoke" in sys.argv
    skip_chaos = "--skip-chaos-smoke" in sys.argv
    skip_pipeline = "--skip-pipeline-smoke" in sys.argv
    skip_soak = "--skip-soak-smoke" in sys.argv
    skip_forensics = "--skip-forensics-smoke" in sys.argv
    skip_catchup = "--skip-catchup-smoke" in sys.argv
    skip_lockdep = "--skip-lockdep-smoke" in sys.argv
    skip_netobs = "--skip-netobs-smoke" in sys.argv
    skip_fuzz = "--skip-fuzz-smoke" in sys.argv
    if smoke_only:
        cmd = tier1_command()
        problems, passed, summary = run_parallel_smoke(cmd)
        print(f"verify_green: parallel-apply smoke: {summary}", flush=True)
        if problems:
            print(f"verify_green: RED ({'; '.join(problems)}); "
                  f"passed={passed}", flush=True)
            return 1
        print(f"verify_green: GREEN (parallel smoke passed={passed})",
              flush=True)
        return 0
    lint_rc = run_detlint()
    if lint_rc != 0:
        # distinct from test failures: the analyzer itself printed the
        # findings; still run the tests so one gate run reports both
        print(f"verify_green: LINT RED (detlint --strict exited "
              f"{lint_rc})", flush=True)
    cmd = tier1_command()
    if timings:
        # same tier-1 line, plus pytest's own per-test durations (all of
        # them: --durations=0) so the slow tail is attributable by file
        cmd = cmd.replace("-m pytest", "-m pytest --durations=0 -vv", 1)
    print(f"verify_green: {cmd}", flush=True)
    proc = subprocess.run(["bash", "-c", cmd], cwd=REPO)
    rc = proc.returncode
    problems = []
    if rc != 0:
        problems.append(f"tier-1 command exited {rc}")
    try:
        with open("/tmp/_t1.log", errors="replace") as f:
            log = f.read()
    except OSError:
        problems.append("tier-1 log /tmp/_t1.log missing")
        log = ""
    # the summary line: "N passed", "N failed", "N errors" — failures
    # and errors both break the gate even if the shell rc lied
    tail = "\n".join(log.splitlines()[-30:])
    for pat, what in ((r"\b([1-9]\d*) failed\b", "failed tests"),
                      (r"\b([1-9]\d*) errors?\b", "collection errors")):
        m = re.search(pat, tail)
        if m:
            problems.append(f"{m.group(1)} {what}")
    if re.search(r"^=+ ERRORS =+$", log, re.M):
        problems.append("ERRORS section in pytest output")
    m = re.search(r"\b(\d+) passed\b", tail)
    passed = m.group(1) if m else "?"
    if timings:
        print_timings(log)
    if lint_rc != 0:
        problems.append("unbaselined detlint findings (see LINT RED "
                        "above)")
    smoke_note = "parallel smoke skipped"
    if not skip_smoke:
        smoke_problems, smoke_passed, summary = run_parallel_smoke(cmd)
        print(f"verify_green: parallel-apply smoke: {summary}",
              flush=True)
        problems.extend(smoke_problems)
        smoke_note = f"parallel smoke passed={smoke_passed}"
    if not skip_pipeline:
        pl_problems, pl_passed, pl_summary = run_pipelined_smoke(cmd)
        print(f"verify_green: pipelined-close smoke: {pl_summary}",
              flush=True)
        problems.extend(pl_problems)
        smoke_note += f", pipeline smoke passed={pl_passed}"
    if not skip_chaos:
        chaos_problems, chaos_summary = run_chaos_smoke()
        print(f"verify_green: chaos smoke: {chaos_summary}", flush=True)
        problems.extend(chaos_problems)
        smoke_note += f", chaos smoke: {chaos_summary}"
    if not skip_soak:
        soak_problems, soak_summary = run_soak_smoke()
        print(f"verify_green: soak smoke: {soak_summary}", flush=True)
        problems.extend(soak_problems)
        smoke_note += f", soak smoke: {soak_summary}"
    if not skip_forensics:
        fo_problems, fo_summary = run_forensics_smoke()
        print(f"verify_green: forensics smoke: {fo_summary}", flush=True)
        problems.extend(fo_problems)
        smoke_note += f", forensics smoke: {fo_summary}"
    if not skip_catchup:
        cu_problems, cu_summary = run_catchup_smoke()
        print(f"verify_green: catchup smoke: {cu_summary}", flush=True)
        problems.extend(cu_problems)
        smoke_note += f", catchup smoke: {cu_summary}"
    if not skip_netobs:
        no_problems, no_summary = run_netobs_smoke()
        print(f"verify_green: netobs smoke: {no_summary}", flush=True)
        problems.extend(no_problems)
        smoke_note += f", netobs smoke: {no_summary}"
    if not skip_lockdep:
        ld_problems, ld_summary = run_lockdep_smoke()
        print(f"verify_green: lockdep smoke: {ld_summary}", flush=True)
        problems.extend(ld_problems)
        smoke_note += f", lockdep smoke: {ld_summary}"
    if not skip_fuzz:
        fz_problems, fz_summary = run_fuzz_smoke()
        print(f"verify_green: fuzz smoke: {fz_summary}", flush=True)
        problems.extend(fz_problems)
        smoke_note += f", fuzz smoke: {fz_summary}"
    if problems:
        print(f"verify_green: RED ({'; '.join(problems)}); "
              f"passed={passed}", flush=True)
        return 1
    print(f"verify_green: GREEN (passed={passed}, detlint clean, "
          f"{smoke_note})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
