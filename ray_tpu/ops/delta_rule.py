"""The gated delta rule, once for every model that recurs by it: the state of a
head is a matrix ``S [dk, dv]`` float32 that each position decays and then
corrects by a rank-one step towards its value::

    S_t = (I - beta_t k_t k_t^T) Decay_t S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Two models differ in ``Decay_t`` alone. Kimi Delta Attention (``models/
kimi_linear.py``) forgets a CHANNEL at a time, ``Decay_t = Diag(e^{g_t})`` with
``g_t [dk]``; Gated DeltaNet (``models/olmo_hybrid.py``) a HEAD at a time,
``Decay_t = e^{g_t} I`` with ONE number ``g_t``. ``beta`` is the step's size: in
(0, 1) for KDA, up to 2 for a Gated DeltaNet that allows negative eigenvalues
(``I - beta k k^T`` then has one in (-1, 1)). ``dk`` and ``dv`` need not agree.

* :func:`kda_update`: the recurrence once, one token a slot (a decode step;
  over a whole slab of the state pool on a TPU the same four lines are ONE
  pass in ``ops/kda.py``). A gate a head is a gate ``[.., 1]`` broadcast.
* :func:`kda_chunked`: the chunked (WY) form for a gate a channel, the
  decays of a pair of positions ``[c, c, dk]`` exponentials and a reduction on
  the vector unit (``models/kimi_linear.py``'s docstring has the equations).
* :func:`gdn_chunked`: the same form for a gate a head. The decay of a pair
  of positions is then ONE number, ``Gamma_ts = e^{G_t - G_s}``, and

      A^kk = (K K^T) * Gamma,   A^qk = (Q K^T) * Gamma

  are matmuls: the published Gated DeltaNet algorithm, ``dk`` times fewer
  exponentials and no reduction over the channels. Equal to
  :func:`kda_chunked` at a gate broadcast over ``dk`` (``tests/
  test_delta_rule.py``).
* :func:`_unit_lower_inverse`, the inverse both forms share, and
  :func:`_wy`, everything of a sub-chunk behind ``A^kk`` and ``A^qk``.
* :func:`rows_of_slots`, :func:`slot_state`, :func:`write_slot_state`: a decode
  batch seen from the pool's slots, and a prefill chunk's one slot of it.

Everything float32 with the matmul's highest precision: the state is float32
and stays so.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_update(S, q, k, v, g, beta):
    """The recurrence, once: ``S [B, H, dk, dv]`` float32 and one token a
    slot (``q, k, g [B, H, dk]``, ``v [B, H, dv]``, ``beta [B, H]``) ->
    ``(S_t, o_t [B, H, dv])``. Sums on the vector unit in float32: nothing
    of the state goes through a bfloat16 product."""
    S = S * jnp.exp(g)[..., None]
    u = jnp.sum(S * k[..., None], axis=-2)
    S = S + (beta[..., None] * k)[..., None] * (v - u)[..., None, :]
    return S, jnp.sum(S * q[..., None], axis=-2)


#: the largest block :func:`_unit_lower_inverse` inverts by the product formula
_INVERSE_BLOCK = 16


def _unit_lower_inverse(L, mm):
    """``(I + L)^-1`` of a strictly lower triangular ``L [..., n, n]``
    (``mm``: the matmul). Diagonal blocks of at most 16 by the product
    formula ``(I - L)(I + L^2)(I + L^4)...`` (exact: ``L^16 = 0``), all blocks in
    one batched product; then pairs of neighbours merged, ``[[A, 0], [C,
    B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``, until one block is left. The
    formula over the whole sub-chunk of 64 is exact too, but its powers reach
    ``C(63, 32) |L|^32``: with keys of one head alike (cosine 0.8: a residual
    stream's common part does that in the deeper layers) and beta near 1 its
    float32 sum cancelled to nothing and a head's state came out 1e16 times
    too large, which the head norm hid from the logits (PR 35: the pool's
    reading of the check found it on the chip)."""
    n = L.shape[-1]
    m = 0
    while (n >> m) > _INVERSE_BLOCK and (n >> m) % 2 == 0:
        m += 1
    b = n >> m
    if b > _INVERSE_BLOCK:  # an odd size: zeros up to 16 x a power of two (its inverse: this one beside an identity)
        pad = _INVERSE_BLOCK * (1 << math.ceil(math.log2(-(-n // _INVERSE_BLOCK)))) - n
        padded = jnp.pad(L, ((0, 0),) * (L.ndim - 2) + ((0, pad), (0, pad)))
        return _unit_lower_inverse(padded, mm)[..., :n, :n]
    X = -jnp.stack([L[..., i * b : (i + 1) * b, i * b : (i + 1) * b] for i in range(1 << m)], axis=-3)
    inv, power = jnp.eye(b, dtype=L.dtype) + X, X
    for _ in range(max(0, math.ceil(math.log2(b)) - 1)):
        power = mm("...ij,...jk->...ik", power, power)
        inv = inv + mm("...ij,...jk->...ik", inv, power)
    while inv.shape[-3] > 1:  # [..., blocks, b, b] -> [..., blocks / 2, 2 b, 2 b]
        pairs = inv.shape[-3] // 2
        A, B = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        C = jnp.stack(
            [L[..., (2 * j + 1) * b : (2 * j + 2) * b, 2 * j * b : (2 * j + 1) * b] for j in range(pairs)],
            axis=-3,
        )
        low = -mm("...ij,...jk->...ik", mm("...ij,...jk->...ik", B, C), A)
        inv = jnp.concatenate([
            jnp.concatenate([A, jnp.zeros_like(A)], axis=-1), jnp.concatenate([low, B], axis=-1),
        ], axis=-2)
        b *= 2
    return inv[..., 0, :, :]


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=_HIGHEST, preferred_element_type=F32)


def _wy(S, q, k, v, beta, G, A_kk, A_qk, strict):
    """A sub-chunk behind its decays: ``S [B, H, dk, dv]`` before it, ``q, k
    [B, H, c, dk]``, ``v [B, H, c, dv]``, ``beta [B, H, c]``, ``G`` the running
    sum of the gate (``[B, H, c, dk]`` a channel, ``[B, H, c, 1]`` a head),
    ``A_kk``, ``A_qk [B, H, c, c]`` -> ``(S after it, o [B, H, c, dv])``."""
    inv = _unit_lower_inverse(beta[..., :, None] * jnp.where(strict, A_kk, 0.0), _mm)
    Tb = inv * beta[..., None, :]
    e_G = jnp.exp(G)
    W = _mm("...ts,...sv->...tv", Tb, v - _mm("...sk,...kv->...sv", k * e_G, S))
    o = _mm("...tk,...kv->...tv", q * e_G, S) + _mm("...ts,...sv->...tv", A_qk, W)
    last = G[..., -1:, :]
    S = jnp.swapaxes(jnp.exp(last), -1, -2) * S + _mm(
        "...sk,...sv->...kv", k * jnp.exp(last - G), W
    )
    return S, o


def _in_sub_chunks(body, S, arrays, chunk: int):
    """``body(S, xs) -> (S, o [B, H, chunk, dv])`` over the sub-chunks of
    ``arrays`` (each ``[B, T, H, .]``, ``T`` a multiple of ``chunk``) in turn,
    the state passed from one to the next: ``(S, o [B, T, H, dv])``."""
    B, T, H = arrays[0].shape[:3]
    n = T // chunk

    def split(a):  # [B, T, H, .] -> [n, B, H, chunk, .]
        return jnp.moveaxis(a.reshape(B, n, chunk, H, -1), (1, 3), (0, 2))

    S, o = jax.lax.scan(body, S, tuple(split(a) for a in arrays))
    return S, jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, T, H, -1)


def kda_chunked(S, q, k, v, g, beta, chunk: int):
    """The chunked (WY) form of the recurrence with a gate a CHANNEL over
    ``T`` positions in sub-chunks of ``chunk`` (``models/kimi_linear.py``'s
    docstring has the equations): ``S [B, H, dk, dv]`` float32 before the
    first position, ``q, k, g [B, T, H, dk]``, ``v [B, T, H, dv]``, ``beta [B,
    T, H]`` float32, ``T`` a multiple of ``chunk`` -> ``(S after the last
    position, o [B, T, H, dv])``. The sub-chunks run in turn (the state passes
    from one to the next); inside one, every position at once. Products at the
    matmul's highest precision: the state is float32 and stays so."""
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def body(S, xs):
        q, k, v, g, beta = xs
        beta = beta[..., 0]                                    # [B, H, c]
        G = jnp.cumsum(g, axis=-2)                             # [B, H, c, dk], <= 0
        # A_ts = sum_c x_tc k_sc e^(G_tc - G_sc) for x = k and x = q, s <= t:
        # ONE reduction over the channels for both (the decays are shared)
        rows = jnp.concatenate([k, q], axis=-2)                # [B, H, 2c, dk]
        diff = jnp.concatenate([G, G], axis=-2)[..., :, None, :] - G[..., None, :, :]
        seen = jnp.concatenate([lower, lower], axis=0)[..., None]
        decay = jnp.exp(jnp.where(seen, diff, -jnp.inf))
        A = jnp.sum(rows[..., :, None, :] * decay * k[..., None, :, :], axis=-1)
        return _wy(S, q, k, v, beta, G, A[..., :chunk, :], A[..., chunk:, :], strict)

    return _in_sub_chunks(body, S, (q, k, v, g, beta[..., None]), chunk)


def gdn_chunked(S, q, k, v, g, beta, chunk: int):
    """The chunked (WY) form with a gate a HEAD, ``g [B, T, H]`` (everything
    else as :func:`kda_chunked`; ``dk`` and ``dv`` need not agree, ``beta`` may
    reach 2). With ``G_t`` the running sum of ``g`` inside a sub-chunk the decay
    between two of its positions is one number, ``Gamma_ts = e^(G_t - G_s)`` for
    ``s <= t`` (the exponent non-positive: nothing overflows), so ``A^kk = (K
    K^T) * Gamma`` and ``A^qk = (Q K^T) * Gamma`` are ONE matmul of ``[K; Q]`` against ``K^T`` and ``c x c``
    exponentials a head where the gate a channel takes ``2 c x c x dk``."""
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def body(S, xs):
        q, k, v, g, beta = xs
        G = jnp.cumsum(g, axis=-2)                             # [B, H, c, 1], <= 0
        decay = jnp.exp(jnp.where(lower, G - jnp.swapaxes(G, -1, -2), -jnp.inf))
        A = _mm("...tk,...sk->...ts", jnp.concatenate([k, q], axis=-2), k)  # ONE product for both
        return _wy(S, q, k, v, beta[..., 0], G, A[..., :chunk, :] * decay, A[..., chunk:, :] * decay, strict)

    return _in_sub_chunks(body, S, (q, k, v, g[..., None], beta[..., None]), chunk)


def rows_of_slots(slots, real, n_slots: int):
    """A decode batch seen from the pool: for each of the pool's ``n_slots``
    slots the batch row that holds it, and whether a REAL row does (never
    the null slot 0). By comparison, ``[n_slots, B]`` booleans: no scatter."""
    hit = (slots[None, :] == jnp.arange(n_slots, dtype=slots.dtype)[:, None]) & real[None, :]
    hit = hit & (jnp.arange(n_slots) > 0)[:, None]
    return jnp.argmax(hit, axis=1), hit.any(axis=1)


def slot_state(state, names, layer: int, slot, fresh):
    """A prefill chunk's view of the state pool: one layer's rows of ONE slot
    in each array of ``names``, ``[1, *shape]`` by a dynamic slice, zeros where
    ``fresh`` (the sequence starts in this window: whatever the slot's last
    holder left is not read)."""
    out = []
    for a in (state[name] for name in names):
        rows = jax.lax.dynamic_slice_in_dim(a[layer], slot, 1, axis=0)
        out.append(jnp.where(fresh, jnp.zeros_like(rows), rows))
    return out


def write_slot_state(state, layer: int, slot, rows):
    """That slot's new rows of one layer (``rows``: name -> ``[1, *shape]``), in
    place in the donated pool."""
    out = {}
    for name, new in rows.items():
        a = state[name]
        start = (jnp.int32(layer), slot) + (jnp.int32(0),) * (a.ndim - 2)
        out[name] = jax.lax.dynamic_update_slice(a, new[None].astype(a.dtype), start)
    return out
