"""Operations and bytes that the algorithm needs, worked out from shapes.

The yardstick's arithmetic: nothing here is read from the program or from
XLA. A count is what the mathematics of the published model requires --
padded vocabulary rows, recomputation and masked-out halves of a causal
product are not counted (except where a kernel's own algorithm requires the
recomputation: see flash_train_flops).

`cfg` is a configuration file's dict (GPT-2 keys: n_embd, n_layer, n_head,
vocab_size, n_positions).
"""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The published peaks of one chip of `device_kind`; unknown = error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       "in benchmark/peaks.json")
    return table[device_kind]


def n_params(cfg):
    """All parameters of the published model (tied head counted once)."""
    h, L, v, p = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], \
        cfg["n_positions"]
    per_layer = 12 * h * h + 13 * h      # qkv, proj, fc1, fc2 (+biases), 2 LN
    return v * h + p * h + L * per_layer + 2 * h


def matmul_params(cfg):
    """Weights that a token is multiplied by: the blocks' matrices and the
    head (the tied table used as the output projection). The embedding
    *lookup* multiplies nothing."""
    h, L, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return L * 12 * h * h + v * h


def train_flops_per_token(cfg, seq_len):
    """Forward + backward of one token in a causal sequence of `seq_len`:
    6 x matmul parameters, plus attention: QK^T and PV are 2*s*h each per
    layer forward for a full square, half under the causal mask, and twice
    that again backward: 6*s*h a layer. No recomputation."""
    h, L = cfg["n_embd"], cfg["n_layer"]
    return 6 * matmul_params(cfg) + 6 * seq_len * h * L


def flash_fwd_flops(seq_len, head_dim, causal=True):
    """One head, forward: QK^T and PV, 2*S*S*D each."""
    full = 2 * 2 * seq_len * seq_len * head_dim
    return full // 2 if causal else full


def flash_train_flops(seq_len, head_dim, causal=True):
    """One head, forward + flash backward: 2 products forward, 5 backward
    (the flash algorithm recomputes QK^T by design, then dP, dV, dQ, dK)."""
    full = 7 * 2 * seq_len * seq_len * head_dim
    return full // 2 if causal else full


def flash_train_bytes(seq_len, head_dim, itemsize=2):
    """One head: q, k, v read and o written forward; q, k, v, o, do read
    and dq, dk, dv written backward."""
    return (4 + 8) * seq_len * head_dim * itemsize


def flash_train_least_seconds(cfg, batch, seq_len, peak):
    """Least time one training step's attention could take on the chip:
    the larger of operations over peak FLOP/s and bytes over peak bytes/s.
    Returns (seconds, which bound)."""
    heads = batch * cfg["n_head"] * cfg["n_layer"]
    d = cfg["n_embd"] // cfg["n_head"]
    t_flops = heads * flash_train_flops(seq_len, d) / peak["flops_per_s"]
    t_bytes = heads * flash_train_bytes(seq_len, d) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def kv_bytes_per_token(cfg, itemsize=2):
    """Keys and values of one token over all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize


def decode_weight_bytes(cfg, itemsize=2):
    """What one decode step has to read of the weights: every block, the
    final layer norm and the whole tied table (as the head)."""
    h, L, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return (L * (12 * h * h + 13 * h) + 2 * h + v * h) * itemsize


def serve_flops(cfg, prompt_tokens, prompt_sq, new_tokens, ctx_tokens):
    """Operations a window of serving needs. prompt_tokens: sum of prompt
    lengths prefilled; prompt_sq: sum of their squares (causal prefill
    attention); new_tokens: tokens emitted (a request's first comes out
    of its prefill, and is counted as one more pass of the blocks: under
    1 % of a reply); ctx_tokens: sum over decoded tokens of the context
    each attended to."""
    h, L, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    # every token passes the blocks; only a position whose next token is
    # wanted passes the head (a prompt's last one and every decoded one)
    dense = 2 * (L * 12 * h * h * (prompt_tokens + new_tokens)
                 + v * h * new_tokens)
    attn = 4 * h * L * (prompt_sq / 2 + ctx_tokens)
    return dense + attn


def serve_decode_bytes(cfg, decode_steps, ctx_tokens, itemsize=2):
    """Bytes the decode steps of a window have to read: the weights once a
    step, and the keys and values of every live context."""
    return (decode_steps * decode_weight_bytes(cfg, itemsize)
            + ctx_tokens * kv_bytes_per_token(cfg, itemsize))
