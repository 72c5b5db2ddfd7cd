"""The depthwise causal SHORT convolution of a recurrent mixer, served: ``K``
taps a channel over a sequence whose last ``K - 1`` inputs are what a
sequence leaves behind (its TAIL), zeros where the context starts::

    c_t = sum_{j < K} taps[j] * z_{t - (K - 1) + j}

Two mixers use it: ``models/kimi_linear.py``'s KDA layers (4 taps over q, k and
v, inside the mixer: :func:`taps_over` and :func:`next_tail`) and ``models/
lfm2.py``'s gated short-convolution layers, whose whole state it is
(:func:`chunk` over a prefill chunk, :func:`step` for a decode batch over a
layer's slab of the state pool).

A window is handed over with its tail in front, ``[B, K - 1 + C, D]``: output
``t`` is then ``K`` shifted slices of it, and the next tail is cut from it at
the window's last REAL inputs, not at its end (a chunk is padded to its
bucket; a chunk of one real row keeps ``K - 2`` inputs of the old tail).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def taps_over(window, taps, C: int):
    """The convolution's ``C`` outputs over ``window [B, K - 1 + C, D]`` (the
    tail in front) with ``taps [K, D]``: float32 ``[B, C, D]``."""
    taps = taps.astype(F32)
    return sum(window[:, j : j + C].astype(F32) * taps[j] for j in range(taps.shape[0]))


def next_tail(window, true_len, keep: int):
    """The tail after a window's first ``true_len [B]`` inputs: rows
    ``true_len .. true_len + keep`` of ``window [B, keep + C, D]``."""
    return jax.vmap(lambda w, at: jax.lax.dynamic_slice_in_dim(w, at, keep, axis=0))(window, true_len)


def chunk(z, tail, taps, true_len):
    """A window of several positions: ``z [B, C, D]`` after ``tail [B, K - 1,
    D]`` -> ``(c [B, C, D] float32, new_tail [B, K - 1, D])``, the tail cut
    behind the first ``true_len [B]`` rows of ``z`` (the real ones)."""
    window = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
    return taps_over(window, taps, z.shape[1]), next_tail(window, true_len, tail.shape[1])


def step(pool, layer: int, slots, z, taps, fresh):
    """One position a slot, in place in a layer's slab of the donated state
    pool: ``pool [layers, slots, (K - 1) x D]`` (a sequence's tail stored as
    ONE row), ``slots [B]`` the rows' slots (padding on the null slot 0,
    where colliding writes are trash on trash), ``z [B, D]``, ``fresh [B]``
    (the sequence starts here: the slot reads as zeros whatever it held) ->
    ``(c [B, D] float32, pool)``. Reads and writes the ``B`` named rows of
    the slab and no other."""
    B, D = z.shape
    tail = jnp.where(fresh[:, None], 0, pool[layer, slots]).reshape(B, -1, D)
    window = jnp.concatenate([tail, z[:, None].astype(pool.dtype)], axis=1)
    c = taps_over(window, taps, 1)[:, 0]
    return c, pool.at[layer, slots].set(window[:, 1:].reshape(B, -1))
