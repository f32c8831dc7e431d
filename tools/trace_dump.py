"""Trace dump CLI: run a traced workload, check its span families, export.

    python tools/trace_dump.py --model gpt --train        # traced train step
    python tools/trace_dump.py --serving                  # traced serving loop
    python tools/trace_dump.py --router                   # multi-engine tier
    python tools/trace_dump.py --serving --chrome out.json
    python tools/trace_dump.py --all --json               # machine report

Each target runs under FLAGS_trace=1 at CPU-shrunk shapes (the
metrics_dump runners), then the collected spans are audited: a target
missing a REQUIRED span family — train: train_step; serving: request /
queue_wait / prefill / decode sharing one trace_id per request — reports
an error-severity finding and the exit code is 1 (the acceptance
criterion in executable form). ``--chrome`` additionally writes the
merged chrome://tracing JSON (host RecordEvents + spans + flow links +
the step phases; open in chrome://tracing or Perfetto).

``--json`` emits the tools/graph_lint.py report schema ({"tool",
"passes", "targets": {name: {"name", "counts", "findings"}}, "totals"},
plus per-target "trace" summary and "cost_table"), so CI reads
graph_lint / op_coverage / metrics_dump / trace_dump through one loader.
"""
import argparse
import importlib.util
import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL_TARGETS = ("gpt", "bert", "ernie")

# span families that MUST appear in a target's trace
REQUIRED = {
    "train": ("train_step",),
    "serving": ("request", "queue_wait", "prefill", "decode"),
    # the multi-engine tier: route (Router placement) + kv_handoff
    # (disaggregated prefill->decode transfer) threading into the same
    # engine span families the monolithic loop emits
    "router": ("route", "kv_handoff", "request", "queue_wait", "decode"),
}


def _load_runners():
    """The metrics_dump workload runners — one source for both CLIs."""
    spec = importlib.util.spec_from_file_location(
        "._metrics_dump_runners",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "metrics_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_target(name):
    """Run one target under FLAGS_trace; returns (spans, findings)."""
    from paddle_tpu import trace
    from paddle_tpu.trace import costs

    md = _load_runners()
    trace.clear()
    costs.reset()   # each target reports ITS executables, not the
    trace.enable()  # accumulated table of every earlier target
    try:
        if name == "serving":
            md.run_serving_loop()
        elif name == "router":
            md.run_router_loop()
        else:
            md.run_train_step(name)
    finally:
        trace.disable()
    spans = trace.spans()
    kind = name if name in ("serving", "router") else "train"
    names = {s.name for s in spans}
    findings = []
    for fam in REQUIRED[kind]:
        if fam not in names:
            findings.append({
                "pass": "spans-present", "severity": "error",
                "message": f"required span family {fam!r} missing after "
                           f"the {name} run", "where": name})
    if kind == "serving":
        # every request's lifecycle spans must share its trace_id
        roots = [s for s in spans if s.name == "request"]
        if not roots:
            findings.append({"pass": "trace-linkage", "severity": "error",
                             "message": "no request root spans recorded",
                             "where": name})
        for root in roots:
            members = {s.name for s in spans if s.trace_id == root.trace_id}
            missing = {"queue_wait", "decode"} - members
            if missing:
                findings.append({
                    "pass": "trace-linkage", "severity": "error",
                    "message": f"request trace {root.trace_id} is missing "
                               f"span families {sorted(missing)}",
                    "where": name})
    if kind == "router":
        # placement and handoff spans must THREAD into engine traces:
        # a route/kv_handoff trace_id with no request/decode members
        # means the propagation chain (submit trace_id=/parent_span=)
        # broke somewhere
        for fam, need in (("route", {"request"}),
                          ("kv_handoff", {"request", "decode"})):
            for root in [s for s in spans if s.name == fam]:
                members = {s.name for s in spans
                           if s.trace_id == root.trace_id}
                missing = need - members
                if missing:
                    findings.append({
                        "pass": "trace-linkage", "severity": "error",
                        "message": f"{fam} trace {root.trace_id} is "
                                   f"missing span families "
                                   f"{sorted(missing)}",
                        "where": name})
    if kind == "train":
        steps = [s for s in spans if s.name == "train_step"]
        if steps and not any(
                costs.get("trainer", s.attrs.get("sig")) for s in steps):
            findings.append({
                "pass": "cost-join", "severity": "error",
                "message": "train_step spans have no matching cost-"
                           "registry entry (MFU join would be empty)",
                "where": name})
    for nm, total_ms, count in trace.top_spans(5):
        findings.append({"pass": "spans", "severity": "info",
                         "message": f"{nm}: {count} spans, "
                                    f"{total_ms:.3f} ms total",
                         "where": name})
    return spans, findings


def build_report(targets):
    from paddle_tpu.trace import costs

    report = {"tool": "trace_dump",
              "passes": ["spans-present", "trace-linkage", "cost-join"],
              "targets": {},
              "totals": {"error": 0, "warning": 0, "info": 0}}
    for name in targets:
        spans, findings = run_target(name)
        counts = {"error": 0, "warning": 0, "info": 0}
        for f in findings:
            counts[f["severity"]] += 1
        from paddle_tpu import trace

        report["targets"][name] = {
            "name": name, "counts": counts, "findings": findings,
            "trace": trace.snapshot_summary(5),
            "cost_table": costs.table(),
        }
        if name in ("serving", "router"):
            # with the flight recorder on, the serving/router targets
            # also carry the ring summary (span digests + byte tags) —
            # the same view a dump bundle would open with
            from paddle_tpu.monitor import blackbox

            if blackbox.is_enabled():
                report["targets"][name]["blackbox_ring"] = \
                    blackbox.ring_summary(5)
        for sev, n in counts.items():
            report["totals"][sev] += n
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", choices=MODEL_TARGETS, action="append",
                    default=[], help="trace one bundled model (use with "
                                     "--train; implied when given)")
    ap.add_argument("--train", action="store_true",
                    help="trace a train step for the chosen --model "
                         "(default gpt when no --model given)")
    ap.add_argument("--serving", action="store_true",
                    help="trace the ServingEngine decode loop")
    ap.add_argument("--router", action="store_true", dest="router",
                    help="trace the multi-engine tier (Router fan-out + "
                         "disaggregated handoff); exit 1 when the "
                         "route/kv_handoff span families are missing or "
                         "unlinked")
    ap.add_argument("--all", action="store_true",
                    help="all models + the serving loop + the router "
                         "tier")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the graph_lint-schema machine report")
    ap.add_argument("--chrome", metavar="OUT.json",
                    help="also write the merged chrome://tracing JSON of "
                         "the LAST target's spans")
    args = ap.parse_args(argv)

    targets = list(args.model)
    if args.train and not targets:
        targets = ["gpt"]
    if args.serving:
        targets.append("serving")
    if args.router:
        targets.append("router")
    if args.all:
        targets = list(MODEL_TARGETS) + ["serving", "router"]
    if not targets:
        ap.error("pick a target: --model NAME [--train], --serving, "
                 "--router or --all")

    report = build_report(targets)
    if args.chrome:
        from paddle_tpu import trace

        trace.export_chrome(args.chrome)
        report["chrome"] = args.chrome
    if args.as_json:
        print(json.dumps(report, indent=1))
    else:
        for name, t in report["targets"].items():
            print(f"# target: {name}")
            print(json.dumps({"trace": t["trace"],
                              "cost_entries": len(t["cost_table"])},
                             sort_keys=True))
            for f in t["findings"]:
                print(f"  [{f['severity']}] {f['pass']}: {f['message']}")
    return 1 if report["totals"]["error"] else 0


if __name__ == "__main__":
    sys.exit(main())
