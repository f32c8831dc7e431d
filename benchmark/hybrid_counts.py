"""Operations and bytes that serving the `solar_open2` family needs, worked
out from shapes and from what the engine counted (beside counts.py, whose
functions are GPT-2's).

`cfg` is the configuration file's dict. A count is what the mathematics of
the deployment's share requires: the blocks outside the experts, the shared
expert, the selected experts THIS SHARE HOLDS (never the 8 a token selects,
seven of which lie on other chips), attention over the live context, the
state update, the held rows of the head. Padded rows of the grouped products,
re-read experts and the columns of the cache past a row's position are not
counted. No share of a peak made from these can pass 100 (tests/benchmark/
test_benchmark_hybrid.py works two cases by hand).
"""


def _shape(cfg):
    lin = cfg["linear_attn_config"]
    L = cfg["num_hidden_layers"]
    gqa = [l for l in cfg["gqa_layers"] if l < L]
    return dict(d=cfg["hidden_size"], L=L, Lg=len(gqa), Lk=L - len(gqa),
                H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], Hk=lin["num_heads"], dk=lin["head_dim"],
                K=lin["short_conv_kernel_size"],
                r=cfg["assumed"]["kda_low_rank"],
                f=cfg["moe_intermediate_size"],
                fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                E=cfg.get("deployment", {}).get("n_routed_experts_published",
                                                cfg["n_routed_experts"]),
                count=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
                V=cfg["vocab_size"])


def gqa_layer_params(cfg):
    """Matrix parameters of a GQA layer outside its experts: q, gate, o
    (d x H hd each), k and v (d x KVh hd each), shared expert, router."""
    s = _shape(cfg)
    return (3 * s["d"] * s["H"] * s["hd"] + 2 * s["d"] * s["KV"] * s["hd"]
            + 3 * s["d"] * s["fs"] + s["d"] * s["E"])


def kda_layer_params(cfg):
    """Matrix parameters of a KDA layer outside its experts: q, k, v, o
    (d x H dk each), the low-rank decay and gate (d x r and r x H dk each),
    beta (d x H), shared expert, router."""
    s = _shape(cfg)
    wide = s["Hk"] * s["dk"]
    return (4 * s["d"] * wide + 2 * (s["d"] * s["r"] + s["r"] * wide)
            + s["d"] * s["Hk"] + 3 * s["d"] * s["fs"] + s["d"] * s["E"])


def expert_params(cfg):
    s = _shape(cfg)
    return 3 * s["d"] * s["f"]


def head_params(cfg):
    s = _shape(cfg)
    return s["d"] * s["V"]


def n_params(cfg):
    """Every parameter this share holds (vectors too)."""
    s = _shape(cfg)
    d, wide = s["d"], s["Hk"] * s["dk"]
    gqa = gqa_layer_params(cfg) + 2 * d + s["E"]
    kda = kda_layer_params(cfg) + 2 * d + s["E"] + 3 * s["K"] * wide \
        + s["Hk"] + wide + s["dk"]
    return (s["Lg"] * gqa + s["Lk"] * kda
            + s["L"] * s["count"] * expert_params(cfg)
            + 2 * head_params(cfg) + d)


def kda_state_flops_per_token(cfg):
    """The recurrence a token a layer: decay (dk dv), k^T S, the rank-one
    update and q^T S (2 dk dv each), for every head; and the three
    convolutions (2 x taps a channel)."""
    s = _shape(cfg)
    return 7 * s["Hk"] * s["dk"] * s["dk"] \
        + 2 * s["K"] * 3 * s["Hk"] * s["dk"]


def serve_flops(cfg, prompt_tokens, prompt_sq, new_tokens, ctx_tokens,
                held_assignments):
    """Operations a window of serving needs. prompt_tokens, prompt_sq,
    new_tokens, ctx_tokens as counts.serve_flops has them; held_assignments:
    the (token, expert) pairs of those tokens that land on held experts,
    over all layers."""
    s = _shape(cfg)
    tokens = prompt_tokens + new_tokens
    dense = 2 * tokens * (s["Lg"] * gqa_layer_params(cfg)
                          + s["Lk"] * kda_layer_params(cfg))
    experts = 2 * held_assignments * expert_params(cfg)
    head = 2 * new_tokens * head_params(cfg)
    attn = 4 * s["H"] * s["hd"] * s["Lg"] * (prompt_sq / 2 + ctx_tokens)
    state = tokens * s["Lk"] * kda_state_flops_per_token(cfg)
    return dense + experts + head + attn + state


def decode_weight_bytes(cfg, itemsize=2):
    """Weights every decode step reads whatever the routing: the layers
    outside their experts and the head's held rows (the embedding's rows
    of the step's tokens are a few KB)."""
    s = _shape(cfg)
    return (s["Lg"] * gqa_layer_params(cfg) + s["Lk"] * kda_layer_params(cfg)
            + head_params(cfg)) * itemsize


def serve_decode_bytes(cfg, decode_steps, experts_touched, state_bytes_moved,
                       itemsize=2):
    """Bytes the decode steps of a window have to move: the weights above
    once a step, every held expert a step TOUCHES once (summed over steps
    and layers), and the state as the engine counted it by kind (live keys
    and values read, a column a row written, fixed-size state read and
    written whole)."""
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
            "weights_outside_experts_bytes_a_step": decode_weight_bytes(cfg),
            "expert_bytes_a_step": c["moe_experts_touched"]
            * expert_params(cfg) * 2 / steps,
            "experts_touched_a_step": c["moe_experts_touched"] / steps,
            "state_bytes_a_step": {k: v / steps for k, v in
                                   c["state_bytes_moved"].items()},
            "held_share_of_assignments": c["moe_assignments_held"]
            / max(c["moe_assignments"], 1),
            "requests_finished": c.get("attempted"),
            "prompt_tokens_admitted": c.get("prompt_tokens")}
