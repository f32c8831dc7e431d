"""Operations and bytes that serving the `axk1` family needs, worked out from
shapes and from what the engine counted (beside counts.py, whose functions are
GPT-2's, and hybrid_counts.py, the other family's).

`cfg` is the configuration file's dict. A count is what the mathematics of the
deployment's share requires: for a PROMPT token the naive form of the latent
attention (its projections, its own latent up-projected once, causal scores
and values over the prompt so far); for a ONE-TOKEN STEP the absorbed form
(`W_UK` into the query, `W_UV` onto the output, scores over r + dr channels
and a sum over r against the live context); the dense layer, the shared
expert, the router, the selected experts THIS SHARE HOLDS (never the 8 a token
selects, most of which lie on other chips), the held rows of the head. What a
chunk re-expands of the columns before it, the rotary channels the absorbed
output computes and drops, padded rows of the grouped products, re-read
experts, the second pass of an einsum pair over the cache and the columns past
a row's position are not counted. No share of a peak made from these can pass
100 (tests/benchmark/test_benchmark_latent.py works two cases by hand).
"""


def _shape(cfg):
    L = cfg["num_hidden_layers"]
    Ld = min(cfg["first_k_dense_replace"], L)
    return dict(d=cfg["hidden_size"], L=L, Ld=Ld, Le=L - Ld,
                H=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
                r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
                dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
                w=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
                fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                E=cfg.get("deployment", {}).get("n_routed_experts_published",
                                                cfg["n_routed_experts"]),
                count=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
                V=cfg["vocab_size"])


def attn_layer_params(cfg):
    """Matrix parameters of one latent-attention layer: q_a (d x rq), q_b
    (rq x H (dn + dr)), kv_a (d x (r + dr)), kv_b (r x H (dn + dv)), o
    (H dv x d). A token is multiplied by each once in either form: the
    naive form up-projects its latent through kv_b, the absorbed form puts
    kv_b's two halves on its query and its output."""
    s = _shape(cfg)
    return (s["d"] * s["rq"] + s["rq"] * s["H"] * (s["dn"] + s["dr"])
            + s["d"] * (s["r"] + s["dr"])
            + s["r"] * s["H"] * (s["dn"] + s["dv"])
            + s["H"] * s["dv"] * s["d"])


def dense_mlp_params(cfg):
    s = _shape(cfg)
    return 3 * s["d"] * s["w"]


def expert_params(cfg):
    s = _shape(cfg)
    return 3 * s["d"] * s["f"]


def expert_layers(cfg):
    """Layers that have routed experts: all but the leading dense ones."""
    return _shape(cfg)["Le"]


def expert_layer_params(cfg):
    """An expert layer outside its routed experts: shared expert, router."""
    s = _shape(cfg)
    return 3 * s["d"] * s["fs"] + s["d"] * s["E"]


def head_params(cfg):
    s = _shape(cfg)
    return s["d"] * s["V"]


def outside_experts_params(cfg):
    """Every matrix a token meets whatever the routing, the head aside."""
    s = _shape(cfg)
    return (s["L"] * attn_layer_params(cfg) + s["Ld"] * dense_mlp_params(cfg)
            + s["Le"] * expert_layer_params(cfg))


def n_params(cfg):
    """Every parameter this share holds (vectors too)."""
    s = _shape(cfg)
    norms = s["L"] * (2 * s["d"] + s["rq"] + s["r"]) + s["d"]
    return (outside_experts_params(cfg)
            + s["Le"] * s["count"] * expert_params(cfg)
            + 2 * head_params(cfg) + norms)


def latent_bytes_per_token(cfg, itemsize=2):
    """What one token costs the cache: r + dr values a layer."""
    s = _shape(cfg)
    return s["L"] * (s["r"] + s["dr"]) * itemsize


def naive_attention_flops(cfg, prompt_sq):
    """Causal scores over dn + dr channels and values over dv, every head,
    every layer: `prompt_sq` is the sum over prompts of length squared."""
    s = _shape(cfg)
    return s["L"] * 2 * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * prompt_sq / 2


def absorbed_attention_flops(cfg, ctx_tokens):
    """Scores over r + dr channels and a sum over r, every head, every
    layer: `ctx_tokens` is the sum over decoded tokens of their context."""
    s = _shape(cfg)
    return s["L"] * 2 * s["H"] * (2 * s["r"] + s["dr"]) * ctx_tokens


def absorbed_read_least_seconds(cfg, layer_columns, peak, itemsize=2):
    """Least time the chip could take for the absorbed attention's pass over
    `layer_columns` live columns (summed over rows, steps and layers): each
    column's r + dr latent values read once, scores over r + dr channels and
    a sum over r for every head. Returns (seconds, which bound): 121
    operations a byte, under the chip's ridge, so memory."""
    s = _shape(cfg)
    t_flops = layer_columns * 2 * s["H"] * (2 * s["r"] + s["dr"]) \
        / peak["flops_per_s"]
    t_bytes = layer_columns * (s["r"] + s["dr"]) * itemsize \
        / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def serve_flops(cfg, prompt_tokens, prompt_sq, new_tokens, ctx_tokens,
                held_assignments):
    """Operations a window of serving needs. prompt_tokens, prompt_sq,
    new_tokens, ctx_tokens as counts.serve_flops has them; held_assignments:
    the (token, expert) pairs of those tokens that land on held experts,
    over all layers."""
    tokens = prompt_tokens + new_tokens
    return (2 * tokens * outside_experts_params(cfg)
            + 2 * held_assignments * expert_params(cfg)
            + 2 * new_tokens * head_params(cfg)
            + naive_attention_flops(cfg, prompt_sq)
            + absorbed_attention_flops(cfg, ctx_tokens))


def decode_weight_bytes(cfg, itemsize=2):
    """Weights every decode step reads whatever the routing: the layers
    outside their experts and the head's held rows."""
    return (outside_experts_params(cfg) + head_params(cfg)) * itemsize


def serve_decode_bytes(cfg, decode_steps, experts_touched, state_bytes_moved,
                       itemsize=2):
    """Bytes the decode steps of a window have to move: the weights above
    once a step, every held expert a step TOUCHES once (summed over steps
    and layers), and the latent cache as the engine counted it (live columns
    read ONCE, a column a row written)."""
    return (decode_steps * decode_weight_bytes(cfg, itemsize)
            + experts_touched * expert_params(cfg) * itemsize
            + sum(state_bytes_moved.values()))


def work(cfg, c):
    """The window's work by part, for the run's notes (PERF.md section 5
    sets it beside the issue's reckoning)."""
    if not c or not c.get("decode_steps") or "moe_experts_touched" not in c:
        return None
    steps = c["decode_steps"]
    return {"decode_steps": steps,
            "prefill_chunks": c.get("prefill_chunks"),
            "weights_outside_experts_bytes_a_step": decode_weight_bytes(cfg),
            "expert_bytes_a_step": c["moe_experts_touched"]
            * expert_params(cfg) * 2 / steps,
            "experts_touched_a_step": c["moe_experts_touched"] / steps,
            "state_bytes_a_step": {k: v / steps for k, v in
                                   c["state_bytes_moved"].items()},
            "held_share_of_assignments": c["moe_assignments_held"]
            / max(c["moe_assignments"], 1),
            "requests_finished": c.get("requests_finished"),
            "prompt_tokens_admitted": c.get("prompt_tokens")}
