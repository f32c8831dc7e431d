"""An expert family's whole step as a share of the chip's peak: operations
(or bytes) the window's work needed, from the family's counts module and the
engine's own counts of the window, over the window's seconds, the chips and
the published peak. %.

params: "counts": the module under benchmark/ that has the family's
`serve_flops` and `serve_decode_bytes` (and `expert_layers(cfg)`, the layers
that have routed experts; a module without one has them in every layer);
"resource": "flops" | "hbm". Which cells it is read in is the metric's
`workloads` list's to say, not this reader's. Returns nothing where the
runner's counters lack the engine's expert counts (an older program)."""
import importlib


def read(ctx, params):
    c = ctx.counters
    if not c or c.get("window_s", 0) <= 0 or "moe_experts_touched" not in c \
            or not c.get("decode_steps") or not c.get("moe_assignments"):
        return None
    counts = importlib.import_module("benchmark." + params["counts"])
    if params["resource"] == "flops":
        # the prompts' tokens take held experts at the share the decoded
        # tokens were counted to, in the layers that have experts
        share = c["moe_assignments_held"] / c["moe_assignments"]
        layers = counts.expert_layers(ctx.cfg) \
            if hasattr(counts, "expert_layers") \
            else ctx.cfg["num_hidden_layers"]
        held = c["moe_assignments_held"] + share * c["prompt_tokens"] \
            * ctx.cfg["num_experts_per_tok"] * layers
        need = counts.serve_flops(
            ctx.cfg, c["prompt_tokens"], c["prompt_sq"], c["new_tokens"],
            c["ctx_tokens"], held)
        peak = ctx.peak["flops_per_s"]
    elif params["resource"] == "hbm":
        need = counts.serve_decode_bytes(
            ctx.cfg, c["decode_steps"], c["moe_experts_touched"],
            c["state_bytes_moved"])
        peak = ctx.peak["hbm_bytes_per_s"]
    else:
        raise ValueError(f"unknown resource {params['resource']!r}")
    return 100.0 * need / c["window_s"] / ctx.chips / peak
