"""Kimi Delta Attention (arXiv:2510.26692): a gated delta rule with a decay
for every key channel, in the two forms a server needs.

Per head, with a float32 state S [dk, dv]:

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

`g = log(a) <= 0` comes in, never `a`: products of decays are sums of logs.

`recurrent_step` is the recurrence itself, one token a row: the decode step.
`chunked` is the chunkwise-parallel WY form for whole sequences (prefill):
inside a chunk of C tokens the delta rule's corrections u_i solve one
unit-lower-triangular system, (I + Diag(beta) A) U = Diag(beta) (V - K~ S_0),
A_ij = sum_c k_ic k_jc exp(G_ic - G_jc) for j < i, G the running sum of g in
the chunk; then O = Q~ S_0 + B U with B the same sum over q_i and j <= i, and
S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U. Every exponent is a
difference G_i - G_j with j <= i, so none is positive: no 1 / decay is ever
formed, whatever the decays (the [C, C, dk] tensor of them is the price: 134
MB a chunk for 64 heads of 128, inside the scan over chunks).

A position with g = 0 and beta = 0 leaves the state exactly as it was, which
is how padding past a sequence's true length is made invisible to it.

Products run at matmul precision "highest": the state is float32 and a
bfloat16 pass over it would round what the state exists to keep.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64


def recurrent_step(S, q, k, v, g, beta, scale):
    """One token a row. S [B, H, dk, dv] float32; q, k, g [B, H, dk];
    v [B, H, dv]; beta [B, H]. Returns (o [B, H, dv] float32, S).

    Two passes over the state, not three: with a = exp(g), both products
    with the decayed state are taken from the state as it came in,
    k^T (a S) = (a k)^T S and q^T (a S) = (a q)^T S, in one reading; the
    correction u follows, then o = q^T S_t = (a q)^T S + (q . k) u, and the
    second reading writes S_t = a S + k u^T."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    a = jnp.exp(g)
    both = jnp.einsum("bhck,bhkv->bhcv", jnp.stack([k * a, q * a], axis=2),
                      S, precision=HIGHEST)
    u = beta[..., None] * (v - both[:, :, 0])
    qk = jnp.sum(q * k, axis=-1, keepdims=True)
    o = (both[:, :, 1] + qk * u) * scale
    S = S * a[..., None] + k[..., None] * u[..., None, :]
    return o, S


def _chunk(S0, q, k, v, g, beta, scale):
    """One chunk. S0 [B, H, dk, dv]; q, k, g [B, C, H, dk]; v [B, C, H, dv];
    beta [B, C, H]; all float32. Returns (o [B, C, H, dv], S_C)."""
    C = q.shape[1]
    G = jnp.cumsum(g, axis=1)                                # [B, C, H, dk]
    Gh = jnp.moveaxis(G, 1, 2)                               # [B, H, C, dk]
    i = jnp.arange(C)
    incl = i[:, None] >= i[None, :]                          # j <= i
    # exp(G_i - G_j), j <= i: never a positive exponent
    diff = Gh[:, :, :, None, :] - Gh[:, :, None, :, :]       # [B,H,C,C,dk]
    E = jnp.where(incl[None, None, :, :, None], jnp.exp(
        jnp.minimum(diff, 0.0)), 0.0)
    kh, qh = jnp.moveaxis(k, 1, 2), jnp.moveaxis(q, 1, 2)    # [B, H, C, dk]
    vh = jnp.moveaxis(v, 1, 2)                               # [B, H, C, dv]
    bh = jnp.moveaxis(beta, 1, 2)                            # [B, H, C]
    kE = E * kh[:, :, None, :, :]                            # k_j exp(..)
    A = jnp.sum(kE * kh[:, :, :, None, :], axis=-1)          # [B, H, C, C]
    Bm = jnp.sum(kE * qh[:, :, :, None, :], axis=-1)
    A = jnp.where((i[:, None] > i[None, :])[None, None], A, 0.0)
    eG = jnp.exp(Gh)
    rhs = bh[..., None] * (vh - jnp.einsum(
        "bhck,bhkv->bhcv", kh * eG, S0, precision=HIGHEST))
    M = jnp.eye(C, dtype=A.dtype) + bh[..., None] * A
    U = jax.scipy.linalg.solve_triangular(M, rhs, lower=True,
                                          unit_diagonal=True)
    o = (jnp.einsum("bhck,bhkv->bhcv", qh * eG, S0, precision=HIGHEST)
         + jnp.einsum("bhij,bhjv->bhiv", Bm, U, precision=HIGHEST)) * scale
    tail = jnp.exp(Gh[:, :, -1:, :] - Gh)                    # exp(G_C - G_j)
    S = eG[:, :, -1, :, None] * S0 + jnp.einsum(
        "bhjk,bhjv->bhkv", kh * tail, U, precision=HIGHEST)
    return jnp.moveaxis(o, 2, 1), S


def chunked(S0, q, k, v, g, beta, scale, chunk=CHUNK):
    """A whole sequence. S0 [B, H, dk, dv] float32; q, k, g [B, t, H, dk];
    v [B, t, H, dv]; beta [B, t, H]. Any t: the tail of the last chunk is
    filled with positions that leave the state unchanged. Returns
    (o [B, t, H, dv] float32, the state after position t - 1)."""
    f32 = jnp.float32
    t = q.shape[1]
    n = -(-t // chunk)
    pad = n * chunk - t

    def cut(x):
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        # [n, B, C, ...]
        return jnp.moveaxis(
            x.reshape((x.shape[0], n, chunk) + x.shape[2:]), 1, 0)

    def body(S, xs):
        o, S = _chunk(S, *xs, scale)
        return S, o

    S, o = jax.lax.scan(body, S0.astype(f32),
                        tuple(cut(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                                # [B, n, C, H, dv]
    o = o.reshape((o.shape[0], n * chunk) + o.shape[3:])
    return o[:, :t], S
