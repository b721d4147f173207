"""Benchmark harness — one module per paper table/figure, plus kernel and
LM-architecture benches.  Prints ``name,us_per_call,derived`` CSV and dumps
the kernel/emulation rows to ``BENCH_kernels.json`` (a machine-readable
perf baseline: op, shape, wall-time, plane-count scaling).

Perf-regression gate: before refreshing the baseline, every new record is
diffed against the previous ``BENCH_kernels.json`` — any recorded op that
got more than ``REGRESSION_THRESHOLD`` x slower is re-timed (median of 3
via ``kernel_bench.RETIMERS``, rejecting transient host-load spikes like
the known ``kernel/f32_dot`` flap) and, if the slowdown survives, flagged
on stderr and listed under ``notes.regressions`` in the refreshed file,
so a later PR's run makes its own slowdowns visible.

``--quick`` runs only the subsecond subset — the ``kernel/*`` rows plus
the ``replay/quick_poisson`` traffic-replay smoke (PR 9) — through the
same diff-vs-baseline gate (no baseline rewrite, no slow-test gate) — a
CI pre-check; ``tests/test_bench_quick.py`` keeps it working.  ``--only
<record-prefix>`` narrows further: just the matching retimer-backed
records, median of 3, diffed against the baseline.  The gate output and
the refreshed baseline both carry a host fingerprint (cpu count,
platform, jax/jaxlib versions) so recorded wall times keep their
provenance.

Slow-test gate: tier-1 (`pytest -x -q`) deselects the ``slow``-,
``faults``- and ``backends``-marked tests (pytest.ini) — the end-to-end
reduced-Inception/serving runs, the fault-injection sweeps, and the
interpret-mode backend conformance sweeps; this harness runs them
(`pytest -m "slow or faults or backends"`) after the benches so they
stay exercised.  Set ``BENCH_SKIP_SLOW=1`` to skip the gate."""
from __future__ import annotations

import importlib
import json
import os
import pathlib
import subprocess
import sys
import traceback

MODULES = [
    "benchmarks.fig13_latency_by_layer",
    "benchmarks.fig14_breakdown",
    "benchmarks.fig15_total_latency",
    "benchmarks.fig16_throughput_batch",
    "benchmarks.sched_breakdown",
    "benchmarks.tab3_energy",
    "benchmarks.tab4_cache_scaling",
    "benchmarks.kernel_bench",
    "benchmarks.lm_neural_cache",
    "benchmarks.traffic_replay",
]

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_kernels.json"

REGRESSION_THRESHOLD = 1.3  # flag ops that got >1.3x slower than baseline

# Measured on the CI container (PR 2: packed-resident tiled layer pipeline
# vs PR 1's word-packed engine, vs the per-lane uint8 seed emulation;
# PR 3: batched slice-scheduler + decoded bucketed-jit engine body);
# kept as provenance next to the fresh numbers dumped on every run.
SPEEDUP_NOTES = {
    "emulation_engine": "packed-resident row-aligned words; schedule-planned "
                        "tiles ((image,pixel) rows x filters, geometry-"
                        "bounded) reusing packed window planes across filters "
                        "and packed filters across the batch; EIE-style "
                        "zero-operand word skipping; bucketed-jit engine "
                        "cache with decoded integer-lane kernel body",
    "batch4_reduced_forward": "nc_forward(batch=4) reduced_config(): "
                              "~0.4-1.0 s/img (jit default) vs ~1.8-2.0 s "
                              "at batch=1 (host) — §VI-C amortization",
    "sparsity": "PR 4: dense-vs-sparse pair "
                "(emulation/nc_forward_b4_pruned50_*): reduced_config at "
                "batch 4 with the last 50% of every conv's filters zeroed; "
                "the sparse schedule drops zero-filter passes (engine runs "
                "live columns only, logits byte-identical — asserted) and "
                "kernel_bench RAISES if sparse wall time exceeds dense; "
                "full-network modeled credit at 50% pruning is ~48% of "
                "compute cycles (sparsity/TOTAL row of sched_breakdown)",
    "compression": "PR 8: compressed-vs-dense pair "
                   "(emulation/nc_forward_b4_pruned50_densestore/_csr): "
                   "CSR bit-plane filter residency at 50% pruning keeps "
                   "<= 0.55x the dense filter bytes resident (gated), "
                   "logits byte-identical, wall no worse than dense; "
                   "emulation/csr_conv_smoke is the --quick smoke row; "
                   "the compressed staging rule lifts the full-network "
                   "stream_batch_limit 1 -> 2 (sched_breakdown gates it)",
    "host_noise": "this shared container shows >1.3x ambient cross-run "
                  "drift even at min-of-15 (PR 3: untouched ops incl. the "
                  "pure-XLA kernel/f32_dot flapped 1.3-2.7x between "
                  "back-to-back runs); treat notes.regressions entries as "
                  "real only when kernel/f32_dot (the load canary) is NOT "
                  "also flagged and the ratio reproduces across runs",
    "emulation_suite_seed_s": 14.45,   # pytest tests/test_nc_layers.py @ seed
    "emulation_suite_now_s": 2.5,      # same module, packed engine (PR 1)
    "emulation_speedup_vs_seed": 5.8,  # wall; per-op bodies are >20x
    "nc_conv2d_pr1_us": 168421.96,     # 14x14x8 * 3x3x8x16 @ PR 1 baseline
    "orchestrator": "PR 9: replay/* rows are fully seeded fake-clock "
                    "replays (traces + jitter), so their recorded mean "
                    "latencies are deterministic — a notes.regressions "
                    "entry there is a routing/admission behavior change, "
                    "never host noise; traffic_replay RAISES unless the "
                    "latency router beats round-robin on SLO hit rate on "
                    "both traces and completed logits stay byte-identical "
                    "to standalone nc_forward on the real-fleet segment",
}


def host_fingerprint() -> dict:
    """Provenance for the recorded wall times (PR 8): which host shape
    produced them.  Written under ``notes.host`` in BENCH_kernels.json and
    printed next to the regression gate, so a flagged slowdown can be told
    apart from a container change (cpu_count 1 vs N decides whether the
    overlap gates demand parity or no-loss — see
    ``benchmarks.common.overlap_wall_slack``)."""
    import platform

    fp = {
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    try:
        import jax
        import jaxlib
        fp["jax"] = jax.__version__
        fp["jaxlib"] = jaxlib.__version__
    except Exception:  # pragma: no cover - fingerprint best-effort
        pass
    return fp


def diff_records(old_payload: dict | None, records: list[dict],
                 threshold: float = REGRESSION_THRESHOLD) -> list[dict]:
    """Compare fresh records against a previous baseline payload; return
    the ops that regressed by more than ``threshold`` x."""
    if not old_payload:
        return []
    prev = {r["op"]: r.get("us_per_call", 0.0)
            for r in old_payload.get("records", [])}
    regressions = []
    for r in records:
        before = prev.get(r["op"], 0.0)
        if before > 0 and r["us_per_call"] > threshold * before:
            regressions.append({
                "op": r["op"],
                "before_us": before,
                "after_us": r["us_per_call"],
                "ratio": round(r["us_per_call"] / before, 2),
            })
    return regressions


def harden_regressions(regressions: list[dict], records: list[dict],
                       retimers: dict,
                       threshold: float = REGRESSION_THRESHOLD) -> list[dict]:
    """Re-time each flagged op (median of 3 fresh measurements) before
    recording it as a regression.

    The known flap: ``kernel/f32_dot`` (pure XLA, untouched across PRs)
    drifts >1.3x between back-to-back runs on this shared container
    (SPEEDUP_NOTES["host_noise"]) — a transient host-load spike during its
    original min-of-15 window.  A median re-measure moments later rejects
    the spike: the op keeps ``min(original, median)`` as its recorded
    time, and the regression survives only if that still clears the
    threshold (then it is stamped ``retimed: True`` so the baseline shows
    the flag was confirmed, not ambient).  Ops without a registered
    retimer (the multi-second emulation records) pass through unchanged —
    re-running those would double the bench wall time."""
    import statistics
    by_op = {r["op"]: r for r in records}
    confirmed = []
    for reg in regressions:
        retime = retimers.get(reg["op"])
        if retime is None:
            confirmed.append(reg)
            continue
        med = statistics.median([retime() for _ in range(3)])
        best = round(min(reg["after_us"], med), 2)
        rec = by_op.get(reg["op"])
        if rec is not None:
            rec["us_per_call"] = best
        if best > threshold * reg["before_us"]:
            confirmed.append(dict(reg, after_us=best,
                                  ratio=round(best / reg["before_us"], 2),
                                  retimed=True))
        else:
            print(f"# retime cleared {reg['op']}: flagged "
                  f"{reg['after_us']:.1f} us, median-of-3 {med:.1f} us "
                  f"(baseline {reg['before_us']:.1f} us)", file=sys.stderr)
    return confirmed


def _dump_kernel_records(ok: set | None = None) -> None:
    try:
        from benchmarks import kernel_bench
        records = list(kernel_bench.RECORDS)
        retimers = dict(kernel_bench.RETIMERS)
    except Exception:  # pragma: no cover - harness robustness
        return
    if not records:
        return
    # fold in the traffic-replay records (PR 9) only when that module ran
    # to completion — partial records must not masquerade as a baseline
    if ok is None or "benchmarks.traffic_replay" in ok:
        try:
            from benchmarks import traffic_replay
            records += traffic_replay.RECORDS
            retimers.update(traffic_replay.RETIMERS)
        except Exception:  # pragma: no cover - harness robustness
            pass
    try:
        previous = json.loads(BENCH_JSON.read_text())
    except Exception:
        previous = None
    regressions = harden_regressions(diff_records(previous, records),
                                     records, retimers)
    for reg in regressions:
        print(f"# PERF REGRESSION {reg['op']}: {reg['before_us']:.1f} us -> "
              f"{reg['after_us']:.1f} us ({reg['ratio']}x)", file=sys.stderr)
    host = host_fingerprint()
    notes = dict(SPEEDUP_NOTES, regressions=regressions, host=host)
    payload = {"records": records, "notes": notes}
    BENCH_JSON.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# host: {json.dumps(host, sort_keys=True)}", file=sys.stderr)
    print(f"# wrote {BENCH_JSON.name} ({len(records)} records, "
          f"{len(regressions)} regressions)", file=sys.stderr)


def _run_slow_gate() -> bool:
    """Exercise the `slow`-, `faults`- and `backends`-marked tests tier-1
    deselects."""
    if os.environ.get("BENCH_SKIP_SLOW"):
        print("# slow-test gate skipped (BENCH_SKIP_SLOW)", file=sys.stderr)
        return True
    repo = pathlib.Path(__file__).resolve().parent.parent
    cmd = [sys.executable, "-m", "pytest", "-q", "-m",
           "slow or faults or backends", "-o", "addopts=", "tests"]
    print(f"# slow-test gate: {' '.join(cmd[2:])}", file=sys.stderr)
    # CPU tests: the child must not reach for the chip this process holds
    res = subprocess.run(cmd, cwd=repo,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return res.returncode in (0, 5)  # 5: no slow tests collected


def _run_quick() -> int:
    """``--quick``: the subsecond ``kernel/*`` subset only, diffed against
    the committed ``BENCH_kernels.json`` with the same retime-hardened
    regression gate as a full run.  Never rewrites the baseline (a partial
    record set must not masquerade as one) and skips the slow-test gate —
    a CI pre-check that finishes in seconds."""
    from benchmarks import kernel_bench, traffic_replay
    print("name,us_per_call,derived")
    try:
        for line in kernel_bench.run_quick():
            print(line)
        # PR 9: the sub-second traffic-replay smoke rides along — it gates
        # the router-beats-round-robin claim and the accounting identities
        for line in traffic_replay.run_quick():
            print(line)
    except Exception:  # pragma: no cover - harness robustness
        traceback.print_exc(file=sys.stderr)
        return 1
    records = kernel_bench.RECORDS + traffic_replay.RECORDS
    retimers = dict(kernel_bench.RETIMERS, **traffic_replay.RETIMERS)
    try:
        previous = json.loads(BENCH_JSON.read_text())
    except Exception:
        previous = None
    regressions = harden_regressions(
        diff_records(previous, records), records, retimers)
    for reg in regressions:
        print(f"# PERF REGRESSION {reg['op']}: {reg['before_us']:.1f} us -> "
              f"{reg['after_us']:.1f} us ({reg['ratio']}x)", file=sys.stderr)
    print(f"# host: {json.dumps(host_fingerprint(), sort_keys=True)}",
          file=sys.stderr)
    print(f"# quick mode: {len(records)} records "
          f"diffed, {len(regressions)} regressions; baseline not "
          f"rewritten", file=sys.stderr)
    return 0


def _run_only(prefix: str) -> int:
    """``--only <record-prefix>``: re-time just the matching retimer-backed
    records (median of 3 fresh measurements through
    ``kernel_bench.RETIMERS``) and diff them against the committed
    baseline — the same retime-hardened gate semantics as ``--quick``,
    without the figure modules, the multi-second emulation records or the
    slow-test gate.  Never rewrites the baseline (a partial record set
    must not masquerade as one)."""
    import statistics

    from benchmarks import kernel_bench, traffic_replay
    from benchmarks.common import row
    try:
        # building the quick rows registers the retimers (and runs their
        # correctness gates); their first-pass timings are discarded —
        # only the fresh medians below are reported
        kernel_bench.run_quick()
        traffic_replay.run_quick()
    except Exception:  # pragma: no cover - harness robustness
        traceback.print_exc(file=sys.stderr)
        return 1
    retimers = dict(kernel_bench.RETIMERS, **traffic_replay.RETIMERS)
    matching = {op: rt for op, rt in retimers.items()
                if op.startswith(prefix)}
    if not matching:
        print(f"# --only {prefix!r} matches no retimer-backed record; "
              f"available: {', '.join(sorted(retimers))}",
              file=sys.stderr)
        return 1
    try:
        previous = json.loads(BENCH_JSON.read_text())
    except Exception:
        previous = None
    prev = {r["op"]: r.get("us_per_call", 0.0)
            for r in (previous or {}).get("records", [])}
    print("name,us_per_call,derived")
    records = []
    for op in sorted(matching):
        med = statistics.median([matching[op]() for _ in range(3)])
        records.append({"op": op, "us_per_call": round(med, 2)})
        base = prev.get(op, 0.0)
        print(row(op, med, f"baseline {base:.1f} us" if base
                 else "no baseline record"))
    regressions = diff_records(previous, records)
    for reg in regressions:
        print(f"# PERF REGRESSION {reg['op']}: {reg['before_us']:.1f} us -> "
              f"{reg['after_us']:.1f} us ({reg['ratio']}x)", file=sys.stderr)
    print(f"# host: {json.dumps(host_fingerprint(), sort_keys=True)}",
          file=sys.stderr)
    print(f"# only mode ({prefix!r}): {len(records)} records re-timed "
          f"(median of 3), {len(regressions)} regressions; baseline not "
          f"rewritten", file=sys.stderr)
    return 0


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="benchmark harness; see module docstring")
    ap.add_argument("--quick", action="store_true",
                    help="subsecond kernel/* subset with the same "
                         "diff-vs-baseline regression gate; no baseline "
                         "rewrite, no slow-test gate")
    ap.add_argument("--only", metavar="RECORD_PREFIX", default=None,
                    help="re-time just the records matching this prefix "
                         "(e.g. 'kernel/f32' or 'emulation/csr') through "
                         "kernel_bench.RETIMERS, median of 3, diffed "
                         "against the baseline; never rewrites it")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.quick and args.only:
        ap.error("--quick and --only are mutually exclusive")
    if args.only:
        sys.exit(_run_only(args.only))
    if args.quick:
        sys.exit(_run_quick())
    print("name,us_per_call,derived")
    failures = 0
    ok = set()
    for modname in MODULES:
        try:
            mod = importlib.import_module(modname)
            for line in mod.run():
                print(line)
            ok.add(modname)
        except Exception as e:  # pragma: no cover - harness robustness
            failures += 1
            print(f"{modname},0,ERROR", file=sys.stdout)
            if type(e).__name__ == "BenchBaselineError":
                # diagnosable baseline problem (sched_breakdown): the
                # message names the fix — and THIS run refreshes the
                # baseline below (kernel_bench runs after), so a rerun
                # passes; no traceback needed
                print(f"# {modname}: {e}", file=sys.stderr)
            else:
                traceback.print_exc(file=sys.stderr)
    # only persist a baseline from a complete kernel_bench run — a partial
    # RECORDS list would masquerade as a full perf baseline
    if "benchmarks.kernel_bench" in ok:
        _dump_kernel_records(ok)
    if not _run_slow_gate():
        print("# slow-test gate FAILED", file=sys.stderr)
        failures += 1
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
