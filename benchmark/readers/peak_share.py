"""The whole step's share of the chip's peak: operations (or bytes) that
the window's work needed, from benchmark/counts.py, over the window's
seconds, the chips and the published peak. %.

params: "resource": "flops" | "hbm"; "count": which work was counted."""
from benchmark import counts


def read(ctx, params):
    c = ctx.counters
    if not c or c["window_s"] <= 0:
        return None
    if params["count"] == "train_step":
        if not c.get("tokens"):
            return None
        need = counts.train_flops_per_token(ctx.cfg, c["seq_len"]) \
            * c["tokens"]
    elif params["count"] == "serve_step":
        if not c.get("new_tokens"):
            return None
        need = counts.serve_flops(ctx.cfg, c["prompt_tokens"],
                                  c["prompt_sq"], c["new_tokens"],
                                  c["ctx_tokens"])
    elif params["count"] == "serve_decode_bytes":
        if not c.get("decode_steps"):
            return None
        need = counts.serve_decode_bytes(ctx.cfg, c["decode_steps"],
                                         c["ctx_tokens"])
    else:
        raise ValueError(f"unknown count {params['count']!r}")
    peak = ctx.peak["flops_per_s" if params["resource"] == "flops"
                    else "hbm_bytes_per_s"]
    return 100.0 * need / c["window_s"] / ctx.chips / peak
