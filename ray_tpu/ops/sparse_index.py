"""A learned sparse selection over cached positions (DeepSeek Sparse
Attention's "lightning indexer"): the scores of a query row against the index
keys of its slot, and the EXACT top ``k`` of them.

    I[r, s] = sum_j w[r, j] relu(q[r, j] . keys[s])        float32, j over the indexer's heads
    S_r     = the k positions s <= limit[r] of largest I[r, s]; all of them where
              limit[r] < k; of equal scores the lower position first

Three functions, all plain ``jax.numpy`` (XLA carries them on every backend):

* :func:`index_scores`: the per-head products ``[R, heads, keys]`` never exist
  at once (1024 x 32 x 32768 float32 are 4.3 GB): a tile of keys at a time
  under ``lax.map``, each tile's heads summed before the next is multiplied.
* :func:`select_mask`: no sort. A float32 score is read as an unsigned integer
  of the same order, and the ``k``-th largest of a row is found a bit at a
  time from the top (32 counts of ``key >= candidate`` over the row: a radix
  select); what lies above it is chosen, and of what EQUALS it the lowest
  positions fill the rest (a cumulative count, taken only where a row has more
  equals than places: never with real scores, always in the tests).
  ``jax.lax.top_k`` over ``[1024, 32768]`` is a sort of every row on a TPU and
  ``approx_max_k`` is another model.
* :func:`mask_positions`: the chosen positions of a row in ascending order,
  for the path that gathers rows by token (a decode or verify window wherever
  the paged kernels do not serve: ``models/latent.py::sparse_paged_serves``).

``ops/index_paged.py`` is :func:`index_scores` of a short window with the
cached keys read by a Pallas kernel from each slot's live blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: float32 numbers of per-head scores (rows x heads x keys of a tile) that one
#: turn of :func:`index_scores` may hold: 128 MB
_TILE_SCORES = 32 * 1024 * 1024


def index_scores(q, w, keys):
    """``q [R, Hi, di]`` (the rows' indexer queries, rotated), ``w [R, Hi]``
    float32 (the heads' weights, scaled), ``keys [S, di]`` (the slot's index
    keys, the rows' own laid in) -> ``I [R, S]`` float32. The products in the
    operands' dtype with float32 accumulation (the configuration's precision:
    nothing below it), relu and the sum over the heads float32."""
    R, Hi, _ = q.shape
    S = keys.shape[0]
    tile = S
    while R * Hi * tile > _TILE_SCORES and tile % 2 == 0 and tile > 128:
        tile //= 2

    def scores(k):
        s = jnp.einsum("rhd,sd->rhs", q, k, preferred_element_type=F32)
        return jnp.sum(jax.nn.relu(s) * w.astype(F32)[:, :, None], axis=1)

    if tile == S:
        return scores(keys)
    out = jax.lax.map(scores, keys.reshape(S // tile, tile, keys.shape[1]))  # [tiles, R, tile]
    return jnp.moveaxis(out, 0, 1).reshape(R, S)


def _ordered(scores):
    """float32 -> uint32 of the same order (-0.0 as 0.0); every real score is
    above 0, which stands for "no position"."""
    x = jnp.where(scores == 0, 0.0, scores).astype(F32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def kth_largest(key, k: int):
    """``key [R, S]`` uint32 -> per row the largest ``T`` with ``count(key >=
    T) >= k`` (0 where even ``T = 1`` has fewer): the ``k``-th largest key of
    the row where it has ``k`` non-zero ones. 32 counts over the row."""

    def bit(i, T):
        cand = T | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[:, None], axis=1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, T)

    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[0], jnp.uint32))


def select_mask(scores, limit, k: int):
    """``scores [R, S]`` float32, ``limit [R]`` int32 (row ``r`` may choose
    among positions ``s <= limit[r]``) -> ``[R, S]`` bool: exactly ``min(k,
    limit[r] + 1)`` positions a row, those of the largest scores, of equal
    scores the lower positions."""
    S = scores.shape[1]
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] <= limit[:, None]
    key = jnp.where(valid, _ordered(scores), jnp.uint32(0))
    T = kth_largest(key, k)[:, None]
    above = key > T
    # a row with fewer than k valid positions ends at T = 0: all of them are above
    equal = (key == T) & valid & (T > 0)
    places = k - jnp.sum(above, axis=1, dtype=jnp.int32)  # what the equals may fill

    def by_position():
        rank = jnp.cumsum(equal, axis=1, dtype=jnp.int32) - 1
        return above | (equal & (rank < places[:, None]))

    crowded = jnp.any(jnp.sum(equal, axis=1, dtype=jnp.int32) > places)
    return jax.lax.cond(crowded, by_position, lambda: above | equal)


def mask_positions(mask, k: int):
    """``mask [R, S]`` bool with at most ``k`` set a row -> ``(positions [R,
    k] int32, real [R, k] bool)``: the set positions ascending, then padding
    (position 0, not real)."""
    S = mask.shape[1]
    # distinct per chosen position: no tie for any top-k to break
    order = jnp.where(mask, S - jnp.arange(S, dtype=jnp.int32), 0)
    best, _ = jax.lax.top_k(order, k)
    return jnp.where(best > 0, S - best, 0), best > 0
