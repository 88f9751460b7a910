"""The gated delta rule (Yang, Kautz, Hatamizadeh, "Gated Delta
Networks", ICLR 2025) — the recurrent mixer of linear-attention layers.

Per head, a state ``S`` in R^{dk x dv}, ``S_0 = 0``::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``alpha_t`` in (0, 1) (given as its log) and ``beta_t`` in (0, 2).
Three formulations, all float32 at ``Precision.HIGHEST``:

* :func:`gated_delta_step` — one token for a batch of states (decode);
  :func:`gated_delta_step_flat` is the same update on states stored
  with the heads along the lanes, ``(dk, H dv)``.
* :func:`gated_delta_chunked` — a whole sequence in chunks of ``chunk``
  tokens (prefill): inside a chunk the rule is solved in its WY form
  (one unit-lower-triangular solve a chunk, all chunks at once), and
  only the chunk-to-chunk state passes through a scan.
* :func:`gated_delta_recurrent` — the token-by-token scan the other two
  are tested against.

A token with ``beta = 0`` and ``log_alpha = 0`` leaves the state as it
is: that is how a padded chunk's tail is masked.

The chunked form, for one chunk that starts from ``S_0``. Write
``G_i = prod_{j<=i} alpha_j`` and ``u_i = beta_i (v_i - alpha_i k_i^T
S_{i-1})``, so that ``S_i = alpha_i S_{i-1} + k_i u_i^T`` and therefore
``S_i = G_i S_0 + sum_{j<=i} (G_i / G_j) k_j u_j^T``. Substituting,

    (I + L) U = diag(beta) (V - diag(G) K S_0),
    L_ij = beta_i (G_i / G_j) (k_i . k_j)  for j < i, else 0

and, ``S_0`` entering linearly, ``U = U~ - W S_0`` with ``U~`` and ``W``
the solutions for the right-hand sides ``diag(beta) V`` and
``diag(beta G) K``. Then ``O = diag(G) Q S_0 + ((Q K^T) * D) U`` with
``D_ij = G_i / G_j`` for ``j <= i``, and ``S_C = G_C S_0 + (diag(G_C /
G) K)^T U``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def gated_delta_step(S, q, k, v, log_alpha, beta):
    """One token. ``S`` (B, H, dk, dv) float32; ``q``, ``k`` (B, H, dk);
    ``v`` (B, H, dv); ``log_alpha``, ``beta`` (B, H). Returns
    ``(S', o)`` with ``o`` (B, H, dv)."""
    Sd = S * jnp.exp(log_alpha)[..., None, None]
    kS = jnp.einsum("bhk,bhkv->bhv", k, Sd, precision=_HI)
    u = beta[..., None] * (v - kS)
    S2 = Sd + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, S2, precision=_HI)
    return S2, o


def gated_delta_step_flat(S, q, k, v, log_alpha, beta):
    """One token on states stored ``(B, dk, H dv)``: the layout in
    which a float32 state tiles without padding on a TPU (``dv`` need
    not be a multiple of the 128 lanes, ``H dv`` is), and in which the
    whole update is elementwise work and reductions over ``dk`` — no
    per-head matrix is ever cut out of the tile. ``q``, ``k`` (B, H,
    dk); ``v`` (B, H, dv); ``log_alpha``, ``beta`` (B, H). Returns
    ``(S', o)`` with ``o`` (B, H, dv); the arithmetic of
    :func:`gated_delta_step` up to the order of the sums over ``dk``.

    A head's value is spread over its ``dv`` lanes by a product with
    the 0/1 matrix ``E[h, h dv + j] = 1`` at ``Precision.HIGHEST``
    (exact: a float32 is the sum of its three bfloat16 pieces, each
    times 1): one operation that lands in the state's own tiling,
    where a broadcast and a reshape would be re-laid as several."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    E = jnp.repeat(jnp.eye(h, dtype=jnp.float32), dv, axis=1)   # (H, H dv)

    def lanes(a):          # (B, n, H) -> (B, n, H dv)
        return jnp.einsum("bnh,hl->bnl", a, E, precision=_HI)

    k_x, q_x = jnp.split(jnp.einsum("bhk,hl->bkl",
                                    jnp.concatenate([k, q], axis=-1), E,
                                    precision=_HI), 2, axis=1)
    gates = lanes(jnp.stack([jnp.exp(log_alpha), beta], axis=1))
    Sd = S * gates[:, :1]
    kS = jnp.sum(k_x * Sd, axis=1, keepdims=True)          # (B, 1, H dv)
    u = gates[:, 1:] * (v.reshape(b, 1, h * dv) - kS)
    S2 = Sd + k_x * u
    o = jnp.sum(q_x * S2, axis=1)
    return S2, o.reshape(b, h, dv)


def heads_first(S_flat, heads: int):
    """(dk, H dv) -> (H, dk, dv)."""
    dk = S_flat.shape[0]
    return jnp.moveaxis(S_flat.reshape(dk, heads, -1), 1, 0)


def heads_on_lanes(S):
    """(H, dk, dv) -> (dk, H dv), the inverse of :func:`heads_first`."""
    h, dk, dv = S.shape
    return jnp.moveaxis(S, 0, 1).reshape(dk, h * dv)


def gated_delta_recurrent(S0, q, k, v, log_alpha, beta):
    """Token-by-token scan over one sequence. ``S0`` (H, dk, dv);
    ``q``, ``k`` (L, H, dk); ``v`` (L, H, dv); ``log_alpha``, ``beta``
    (L, H). Returns ``(S_L, O)`` with ``O`` (L, H, dv)."""

    def body(S, t):
        qt, kt, vt, gt, bt = t
        S2, o = gated_delta_step(S[None], qt[None], kt[None], vt[None],
                                 gt[None], bt[None])
        return S2[0], o[0]

    return jax.lax.scan(body, S0, (q, k, v, log_alpha, beta))


def gated_delta_chunked(S0, q, k, v, log_alpha, beta, chunk: int = 64):
    """One sequence in chunks (shapes as :func:`gated_delta_recurrent`;
    ``L`` a multiple of ``chunk``). Returns ``(S_L, O)``."""
    L, H, dk = q.shape
    dv = v.shape[-1]
    if L % chunk:
        raise ValueError(f"sequence length {L} is not a multiple of "
                         f"the chunk {chunk}")
    nc = L // chunk

    def chunks(a):  # (L, H, ...) -> (nc, H, chunk, ...)
        a = a.reshape((nc, chunk) + a.shape[1:])
        return jnp.moveaxis(a, 2, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    bc = chunks(beta)                                  # (nc, H, C)
    g = jnp.cumsum(chunks(log_alpha), axis=-1)         # log G_i
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    # D_ij = G_i / G_j on and below the diagonal, 0 above: the exponent
    # is masked before exp so that the upper triangle cannot overflow
    D = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                          -jnp.inf))
    kk = jnp.einsum("nhik,nhjk->nhij", kc, kc, precision=_HI)
    strict = idx[:, None] > idx[None, :]
    A = jnp.where(strict, bc[..., :, None] * D * kk, 0.0) \
        + jnp.eye(chunk, dtype=jnp.float32)
    rhs = jnp.concatenate(
        [(bc * jnp.exp(g))[..., None] * kc, bc[..., None] * vc], axis=-1)
    sol = jax.lax.linalg.triangular_solve(
        A, rhs, left_side=True, lower=True, unit_diagonal=True)
    W, Ut = sol[..., :dk], sol[..., dk:]
    qk = jnp.einsum("nhik,nhjk->nhij", qc, kc, precision=_HI) * D
    to_end = jnp.exp(g[..., -1:] - g)                  # G_C / G_i

    def body(S, t):
        q_c, k_c, W_c, Ut_c, qk_c, g_c, e_c = t
        U = Ut_c - jnp.einsum("hik,hkv->hiv", W_c, S, precision=_HI)
        O = jnp.exp(g_c)[..., None] * jnp.einsum(
            "hik,hkv->hiv", q_c, S, precision=_HI) \
            + jnp.einsum("hij,hjv->hiv", qk_c, U, precision=_HI)
        S2 = jnp.exp(g_c[:, -1])[:, None, None] * S + jnp.einsum(
            "hik,hiv->hkv", k_c * e_c[..., None], U, precision=_HI)
        return S2, O

    S_L, O = jax.lax.scan(body, S0, (qc, kc, W, Ut, qk, g, to_end))
    # (nc, H, C, dv) -> (L, H, dv)
    return S_L, jnp.moveaxis(O, 1, 2).reshape(L, H, dv)
