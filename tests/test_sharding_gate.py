"""Tier-1 sharding-flow / transfer-edge / kernel-budget gate (ISSUE 13).

Contract (the acceptance criteria, in executable form):

 - the sharding-flow battery reports ZERO error-severity findings on a
   representative subset of the bundled distributed programs in-process
   (gpt dp8 train, the dp8 quantized step, the pp pipeline step, the
   disagg prefill program) — the full seven-target battery is the
   `python tools/graph_lint.py --sharding` CLI surface;
 - every transfer edge (disagg KV, pipeline stage, federated adapter,
   checkpoint tree) extracts from source, audits clean, and matches the
   recorded tests/handoff_baseline.json fingerprints; a doctored
   baseline makes the CLI exit 1 (the planted-drift subprocess smoke);
 - the Pallas kernel audit reports zero errors over every registered
   manifest (tpp + flash attention + NMS);
 - `ServingEngine.admit_prefilled` consumes the SAME disagg_kv
   declaration the static pass extracts: a good row round-trips, a
   drifted row raises naming the offending leaf — one source of truth,
   regression-tested both ways;
 - the new rules ride --list-rules on both CLIs.

Budget: in-process work is trace-only (~10 s); ONE subprocess pays a
fresh interpreter for the exit-code smoke (AST-only handoff target — no
model tracing in the child). Not slow-marked. The planted-violation
matrix lives in tests/test_analysis_passes.py.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GATED_SHARDING_TARGETS = ("gpt_train", "dp8_quantized", "pipeline",
                          "disagg")


@pytest.fixture(scope="module")
def sharding_reps():
    from paddle_tpu.analysis import sharding_reports

    return sharding_reports(targets=GATED_SHARDING_TARGETS)


@pytest.mark.parametrize("target", GATED_SHARDING_TARGETS)
def test_sharding_zero_errors(sharding_reps, target):
    rep = sharding_reps[target]
    assert rep.errors == [], (
        f"{target}: NEW sharding-flow error findings:\n" + "\n".join(
            f"  [{f.pass_name}] {f.message} @ {f.where}"
            for f in rep.errors))


@pytest.mark.parametrize("target", GATED_SHARDING_TARGETS)
def test_sharding_zero_warnings(sharding_reps, target):
    """The distributed programs stay warning-clean too (implicit
    replication / resharding churn are fixed or threshold-justified,
    never accumulated)."""
    rep = sharding_reps[target]
    assert rep.warnings == [], [repr(f) for f in rep.warnings]


def test_quantized_target_sees_the_wire_ops(sharding_reps):
    """The dp8 quantized target actually exercised the int8 exchange —
    the collective-count pass must name the quantized reduce family."""
    msgs = [f.message for f in sharding_reps["dp8_quantized"].findings
            if f.pass_name == "collective-count"]
    assert any("quantized reduce family" in m for m in msgs), msgs


def test_pipeline_target_sees_the_ring(sharding_reps):
    """The pipeline target carries the ppermute ring (the thing the
    bijectivity pass exists to police)."""
    msgs = [f.message for f in sharding_reps["pipeline"].findings
            if f.pass_name == "collective-count"]
    assert any("collective-permute" in m for m in msgs), msgs


# ---------------------------------------------------------------------------
# transfer edges
# ---------------------------------------------------------------------------


def test_handoff_audit_clean_and_baselined():
    from paddle_tpu.analysis import handoff_schema as hs

    findings = hs.audit_package()
    assert findings == [], [repr(f) for f in findings]
    base = json.load(open(hs.BASELINE_PATH))
    decls, errs = hs.load_declarations()
    assert errs == []
    assert set(base["edges"]) == set(decls) == set(hs.EDGES)
    for edge, decl in decls.items():
        assert base["edges"][edge] == hs.fingerprint(decl)


def test_pallas_audit_zero_errors():
    from paddle_tpu.analysis import pallas_audit

    errs = [f for f in pallas_audit.audit_package()
            if f.severity == "error"]
    assert errs == [], [repr(f) for f in errs]
    # the manifest actually covers all seven kernel families
    kerns = {e["kernel"].split(".")[0]
             for e in pallas_audit.collect_manifest()}
    assert kerns == {"tpp", "flash", "nms", "kv_store", "decode_attention",
                     "latent_decode_attention", "latent_prefill_attention"}


def test_list_rules_carries_the_new_vocabulary():
    from paddle_tpu.analysis import contract_rules, rule_table

    rules = contract_rules()
    for rule in ("implicit-replication", "resharding-churn",
                 "collective-axis-mismatch", "ppermute-malformed",
                 "branch-collective-mismatch", "handoff-schema-drift",
                 "kernel-vmem-over-budget",
                 "kernel-low-precision-accumulator"):
        assert rule in rules, rule
        assert rule in rule_table()


# ---------------------------------------------------------------------------
# runtime <-> static: one declaration, consumed from both sides
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine_and_row():
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.disagg import PrefillWorker

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    eng = ServingEngine(m, max_batch=1)
    worker = PrefillWorker(m, prompt_buckets=(16,))
    row, logits = worker.prefill(np.arange(5, dtype=np.int32))
    return m, eng, row, logits


def test_admit_prefilled_validates_against_the_declaration(
        tiny_engine_and_row):
    import jax.numpy as jnp

    from paddle_tpu.analysis.handoff_schema import HandoffMismatch
    from paddle_tpu.inference.serving import ServingEngine

    m, eng, row, logits = tiny_engine_and_row
    # the good row is admitted and serves (the bit-exactness half lives
    # in tests/test_serving_disagg.py)
    rid = eng.admit_prefilled(np.arange(5, dtype=np.int32), row, logits,
                              max_new_tokens=2)
    eng.run_until_complete()
    assert len(eng.get_request(rid).output_ids) == 2

    # drifted rows raise NAMING the leaf — before any slot is touched
    fresh = ServingEngine(m, max_batch=1)
    with pytest.raises(HandoffMismatch, match=r"\[disagg_kv\] kc: dtype"):
        fresh.admit_prefilled(np.arange(5, dtype=np.int32),
                              (row[0].astype(jnp.bfloat16), row[1]),
                              logits)
    with pytest.raises(HandoffMismatch, match="'T'"):
        fresh.admit_prefilled(np.arange(5, dtype=np.int32),
                              (row[0][:, :, :, :16], row[1]), logits)
    with pytest.raises(HandoffMismatch, match="logits"):
        fresh.admit_prefilled(np.arange(5, dtype=np.int32), row,
                              logits[:64])
    # nothing leaked into the engine's admission state
    assert fresh.stats()["requests"]["handoff"] == 0


def test_admit_prefilled_matches_static_extraction(tiny_engine_and_row):
    """The runtime validator and the static auditor read the SAME
    literal: the attribute the engine imports equals the AST-extracted
    declaration byte for byte."""
    from paddle_tpu.analysis import handoff_schema as hs
    from paddle_tpu.serving.disagg import HANDOFF_SCHEMA

    extracted = hs.extract_declaration(*hs.EDGES["disagg_kv"])
    assert extracted == HANDOFF_SCHEMA


def test_pipeline_declares_and_checks_its_edge():
    from paddle_tpu.analysis import handoff_schema as hs
    from paddle_tpu.distributed.pipeline import HANDOFF_SCHEMA

    assert hs.extract_declaration(
        *hs.EDGES["pipeline_stage"]) == HANDOFF_SCHEMA
    assert HANDOFF_SCHEMA["runtime_checked"]


# ---------------------------------------------------------------------------
# CLI exit codes (one subprocess; AST-only target, no tracing)
# ---------------------------------------------------------------------------


def test_cli_handoff_exit_codes(tmp_path):
    """contract_audit --handoff exits 0 against the recorded baseline
    and 1 against a doctored one (drift detection can actually fail)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    tool = os.path.join(REPO, "tools", "contract_audit.py")

    out = subprocess.run(
        [sys.executable, tool, "--handoff", "--json"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    rep = json.loads(out.stdout)
    assert set(rep["targets"]) == {"handoff"}
    assert rep["totals"]["error"] == 0

    # doctor the baseline: flip the KV dtype the decode engine expects
    base = json.load(open(os.path.join(REPO, "tests",
                                       "handoff_baseline.json")))
    base["edges"]["disagg_kv"]["payload"]["kc"]["dtype"] = "float64"
    doctored = tmp_path / "handoff_drifted.json"
    doctored.write_text(json.dumps(base))
    out = subprocess.run(
        [sys.executable, tool, "--handoff", "--json",
         "--handoff-baseline", str(doctored)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 1, out.stdout + out.stderr
    rep = json.loads(out.stdout)
    msgs = [f["message"] for f in rep["targets"]["handoff"]["findings"]
            if f["pass"] == "handoff-schema-drift"]
    assert msgs and "disagg_kv" in msgs[0] and "kc" in msgs[0], msgs


if __name__ == "__main__":
    print(__doc__)
