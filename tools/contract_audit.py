"""Contract auditor CLI: the flag/lazy-import/observability/thread/
handoff/kernel invariants, machine-checked (ISSUE 12 + 13;
docs/ANALYSIS.md "Contract auditor").

    python tools/contract_audit.py                    # all six passes
    python tools/contract_audit.py --flags --imports  # a subset
    python tools/contract_audit.py --handoff          # transfer edges only
    python tools/contract_audit.py --pallas           # kernel budgets only
    python tools/contract_audit.py --json             # machine-readable
    python tools/contract_audit.py --record           # regen BOTH baselines
    python tools/contract_audit.py --list-rules       # rules + markers

Targets:

  flags         : analysis/flag_audit.py — orphan/undocumented flags,
                  conflicting defaults, structural flags missing from
                  _exec_key, hot-path flag re-reads
  imports       : analysis/import_graph.py — manifest-lazy modules must
                  be unreachable from the plain trainer/engine closure
  observability : analysis/obs_audit.py — metric/span inventory vs the
                  docs/OBSERVABILITY.md reference tables and the
                  tools/metrics_dump.py required-families lists
  threads       : source_lint unlocked-thread-shared-write over the
                  daemon-thread modules (THREAD_SHARED_MODULES). The
                  rule ALSO rides lint_source, so graph_lint --source
                  reports the same findings under its source_lint
                  target — deliberate overlap (each CLI is complete on
                  its own); exit codes key off "any error", so the
                  double view never flips a verdict
  handoff       : analysis/handoff_schema.py — every declared transfer
                  edge (disagg KV, pipeline stage, federated adapter,
                  checkpoint tree) extracted from source, producer/
                  consumer sites verified, fingerprints pinned against
                  tests/handoff_baseline.json (drift = error)
  pallas        : analysis/pallas_audit.py — every registered kernel's
                  grid/block divisibility, MXU/VPU alignment, static
                  VMEM budget, fp32-accumulator checks

Report format: the tools/graph_lint.py schema ({"tool", "passes",
"targets": {name: {"name","counts","findings"}}, "totals"}), so CI reads
every audit tool through one loader. Exit code 1 when any
error-severity finding exists. Warning counts are pinned by the tier-1
gate (tests/test_contract_gate.py) against tests/contract_baseline.json;
``--record`` regenerates it (AND tests/handoff_baseline.json) after an
INTENTIONAL change — errors are never baselined, they are fixed (the
one exception is handoff drift, where --record IS the act of moving
both sides of the edge together).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TARGETS = ("flags", "imports", "observability", "threads", "handoff",
           "pallas")
BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "contract_baseline.json")


def build_report(targets=TARGETS, handoff_baseline=None):
    """Run the requested contract passes; graph_lint-schema dict."""
    from paddle_tpu.analysis import contract_reports, contract_rules

    picked = contract_reports(targets=[n for n in TARGETS
                                       if n in targets],
                              handoff_baseline=handoff_baseline)
    totals = {"error": 0, "warning": 0, "info": 0}
    for rep in picked.values():
        for sev, n in rep.counts().items():
            totals[sev] = totals.get(sev, 0) + n
    return {
        "tool": "contract_audit",
        "passes": sorted(contract_rules()),
        "targets": {n: r.to_dict() for n, r in picked.items()},
        "totals": totals,
    }


def record_baseline(report, path=BASELINE_PATH):
    """Persist per-target warning/info counts (NEVER errors — those are
    fixed, not acknowledged)."""
    base = {"targets": {n: {"warning": r["counts"]["warning"],
                            "info": r["counts"]["info"]}
                        for n, r in report["targets"].items()}}
    with open(path, "w") as f:
        json.dump(base, f, indent=1)
        f.write("\n")
    return base


def list_rules():
    from paddle_tpu.analysis import rule_table

    print(rule_table())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--flags", action="store_true",
                    help="run the flag-contract pass only")
    ap.add_argument("--imports", action="store_true",
                    help="run the lazy-import closure pass only")
    ap.add_argument("--obs", "--observability", action="store_true",
                    dest="obs", help="run the observability-drift pass "
                    "only")
    ap.add_argument("--threads", action="store_true",
                    help="run the thread-discipline lint only")
    ap.add_argument("--handoff", action="store_true",
                    help="run the transfer-edge schema audit only")
    ap.add_argument("--pallas", action="store_true",
                    help="run the Pallas kernel budget audit only")
    ap.add_argument("--handoff-baseline", default=None,
                    dest="handoff_baseline", metavar="PATH",
                    help="override the handoff baseline path (the gate's "
                         "planted-drift smoke uses this)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the machine-readable report")
    ap.add_argument("--record", action="store_true",
                    help="regenerate tests/contract_baseline.json AND "
                         "tests/handoff_baseline.json (warning/info "
                         "counts + edge fingerprints; errors never "
                         "baseline)")
    ap.add_argument("--list-rules", action="store_true", dest="list_rules",
                    help="print every rule, severity and allow-marker "
                         "spelling")
    args = ap.parse_args(argv)

    if args.list_rules:
        list_rules()
        return 0

    picked = [n for n, on in (("flags", args.flags),
                              ("imports", args.imports),
                              ("observability", args.obs),
                              ("threads", args.threads),
                              ("handoff", args.handoff),
                              ("pallas", args.pallas)) if on] or TARGETS
    if args.record and tuple(picked) != TARGETS:
        # a partial baseline would KeyError the tier-1 gate on the
        # missing targets — recording is always the full battery
        picked = TARGETS
    if args.record:
        # stamp the edge fingerprints FIRST so the drift pass in the
        # battery below sees (and reports against) the fresh baseline
        from paddle_tpu.analysis import handoff_schema

        hb = handoff_schema.record_baseline(path=args.handoff_baseline)
        print(f"recorded -> "
              f"{args.handoff_baseline or handoff_schema.BASELINE_PATH} "
              f"({len(hb['edges'])} transfer edge(s))")
    report = build_report(picked, handoff_baseline=args.handoff_baseline)
    if args.record:
        base = record_baseline(report)
        print(f"recorded -> {BASELINE_PATH}")
        print(json.dumps(base, indent=1))
    if args.as_json:
        print(json.dumps(report, indent=1))
    elif not args.record:
        for name, rep in report["targets"].items():
            c = rep["counts"]
            print(f"{name}: {c['error']} error(s), {c['warning']} "
                  f"warning(s), {c['info']} info")
            for f in rep["findings"]:
                loc = f" @ {f['where']}" if f["where"] else ""
                print(f"  [{f['severity']}] {f['pass']}: "
                      f"{f['message']}{loc}")
        t = report["totals"]
        print(f"total: {t['error']} error(s), {t['warning']} warning(s), "
              f"{t['info']} info across {len(report['targets'])} "
              "target(s)")
    return 1 if report["totals"]["error"] else 0


if __name__ == "__main__":
    sys.exit(main())
