"""Sequence-parallel attention: ring attention + Ulysses all-to-all.

The long-context north star (SURVEY §5.7 — absent in the reference, which
has only the raw p2p collectives a user could hand-build this from,
``util/collective/collective.py:531,594``). Two first-class variants:

* **Ring attention** (``ring_attention`` / ``ring_attention_sharded``):
  Q stays put; K/V chunks rotate around the ``seq`` mesh axis via
  ``jax.lax.ppermute`` while each step's partial attention is merged with
  the running online-softmax state (m, l, acc). The S×S score matrix
  never exists — per device the working set is O(S_local²) per step and
  the K/V ring traffic rides ICI neighbor links. The rotation for step
  t+1 is issued before step t's compute so XLA's async collectives can
  overlap communication with the chunk matmuls.

* **Ulysses** (``ulysses_attention`` / ``ulysses_attention_sharded``):
  one ``all_to_all`` swaps the sharded axis from sequence to heads, each
  device then runs *dense local* attention (the pallas flash kernel) on
  full sequences for its head subset, and a second ``all_to_all`` swaps
  back. Cheaper collectives than the ring for moderate S (2 all-to-alls
  vs n-1 permutes) but caps the seq-parallel degree at n_kv_heads.

Both are differentiable: the ring scan body is ``jax.checkpoint``-ed so
the backward pass recomputes chunk scores instead of storing the
O(S_local·S) slices (blockwise-remat, the ring-attention paper recipe),
and ``ppermute``/``all_to_all`` transpose to their inverses.

The ``*_sharded`` wrappers apply ``jax.shard_map`` over the canonical
mesh axes (batch over data/fsdp, heads over tensor, sequence over seq)
so callers hand in global arrays under ``jit`` as usual.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import DATA, FSDP, SEQUENCE, TENSOR

_NEG_INF = -1e30


def _chunk_attention(q, k, v, q_offset, k_offset, *, causal: bool, sm_scale: float):
    """Partial attention of a local Q block against one K/V chunk.

    q: [b, h, sq, d]; k/v: [b, h, sk, d]. Returns the *unnormalized*
    accumulator pv = P·V (f32), the row max m and row sum l of the
    masked, max-shifted scores — the online-softmax sufficient stats.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        mask = q_pos >= k_pos
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [b,h,sq,1]
    p = jnp.exp(s - m)
    if causal:
        # Rows with every position masked have m == _NEG_INF and would
        # otherwise get p == exp(0) == 1 on the masked entries.
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return pv, m, l


def _merge(acc, m, l, pv_i, m_i, l_i):
    """Merge one chunk's stats into the running online-softmax state."""
    m_new = jnp.maximum(m, m_i)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(m_i - m_new)
    return acc * alpha + pv_i * beta, m_new, l * alpha + l_i * beta


def ring_attention(
    q,
    k,
    v,
    *,
    axis_name: str = SEQUENCE,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    kv_repeat: int = 1,
):
    """Ring attention over a sequence-sharded mesh axis.

    Must be called inside ``shard_map`` (or any SPMD context where
    ``axis_name`` is bound). q: ``[b, h, s_local, d]``; k/v:
    ``[b, h // kv_repeat, s_local, d]``. Sequence is sharded
    contiguously, so shard i holds global positions
    ``[i*s_local, (i+1)*s_local)``.

    GQA: pass the *unrepeated* K/V plus ``kv_repeat`` — the ring rotates
    the small KV heads and repeats locally per chunk, so ICI traffic
    keeps GQA's 1/group_size savings.

    n devices → n chunk computes but only n-1 ppermutes: the local chunk
    is folded in during step 0 and the last received chunk is consumed
    outside the scan without a further rotation.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    s_loc = q.shape[2]
    q32 = q.astype(jnp.float32)
    q_offset = my_idx * s_loc

    def rep(x):
        return jnp.repeat(x, kv_repeat, axis=1) if kv_repeat > 1 else x

    # chunk at device j moves to device j-1 each step, so after t steps
    # device i holds the chunk originally owned by (i + t) % n.
    perm = [(j, (j - 1) % n) for j in range(n)]

    @jax.checkpoint
    def merge_chunk(acc, m, l, kc, vc, t):
        """Fold one K/V chunk into the online-softmax state; checkpointed
        so backward recomputes the O(s_loc²) scores per chunk instead of
        storing them (blockwise remat)."""
        k_offset = ((my_idx + t) % n) * s_loc
        pv_i, m_i, l_i = _chunk_attention(
            q32, rep(kc), rep(vc), q_offset, k_offset, causal=causal, sm_scale=sm_scale
        )
        return _merge(acc, m, l, pv_i, m_i, l_i)

    def step(carry, t):
        acc, m, l, kc, vc = carry
        # Issue the rotation for the NEXT step before this step's compute:
        # no data dependence between them, so XLA can overlap the ppermute
        # with the chunk matmuls.
        kn = jax.lax.ppermute(kc, axis_name, perm)
        vn = jax.lax.ppermute(vc, axis_name, perm)
        acc, m, l = merge_chunk(acc, m, l, kc, vc, t)
        return (acc, m, l, kn, vn), None

    b, h, _, d = q.shape
    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    (acc, m, l, kc, vc), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n - 1)
    )
    # Final chunk: consumed in place, no further rotation (n-1 permutes).
    acc, m, l = merge_chunk(acc, m, l, kc, vc, n - 1)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention_sharded(
    q,
    k,
    v,
    mesh,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    kv_repeat: int = 1,
    seq_axis: str = SEQUENCE,
    batch_axes: Tuple[str, ...] = (DATA, FSDP),
    head_axis: str = TENSOR,
):
    """Global-array entry point: shard_map the ring over ``mesh``.

    q: ``[batch, heads, seq, head_dim]``; k/v may carry fewer (KV) heads
    with ``kv_repeat`` set (GQA) — the small KV heads are what rotates.
    Batch rides the data/fsdp axes, heads the tensor axis, sequence the
    seq axis.
    """
    spec = P(batch_axes, head_axis, seq_axis, None)
    fn = jax.shard_map(
        functools.partial(
            ring_attention,
            axis_name=seq_axis,
            causal=causal,
            sm_scale=sm_scale,
            kv_repeat=kv_repeat,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ulysses_attention(
    q,
    k,
    v,
    *,
    axis_name: str = SEQUENCE,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    impl: str = "auto",
):
    """Ulysses (DeepSpeed-style) sequence parallelism.

    Inside shard_map with q/k/v ``[b, h, s_local, d]``: all-to-all
    redistributes from seq-sharded to head-sharded, dense local (flash)
    attention runs on the full sequence for h/n heads, and the inverse
    all-to-all restores sequence sharding. Requires ``h % n == 0``.
    """
    from ray_tpu.ops.attention import flash_attention

    n = jax.lax.psum(1, axis_name)
    h = q.shape[1]
    h_kv = k.shape[1]
    if h % n != 0:
        raise ValueError(f"ulysses needs heads ({h}) divisible by seq-parallel degree ({n})")
    kv_repeat = h // h_kv

    # [b, h, s_loc, d] -> [b, h/n, s_loc*n, d]
    def seq_to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh = seq_to_heads(q)
    if h_kv % n == 0:
        # GQA: all-to-all the small KV heads, repeat locally afterwards —
        # keeps the collective at 1/group_size the repeated volume.
        kh, vh = seq_to_heads(k), seq_to_heads(v)
        if kv_repeat > 1:
            kh = jnp.repeat(kh, kv_repeat, axis=1)
            vh = jnp.repeat(vh, kv_repeat, axis=1)
    else:
        # Too few KV heads to split n ways: repeat first (full volume).
        kh = seq_to_heads(jnp.repeat(k, kv_repeat, axis=1))
        vh = seq_to_heads(jnp.repeat(v, kv_repeat, axis=1))
    o = flash_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale, impl=impl)
    return heads_to_seq(o)


def ulysses_attention_sharded(
    q,
    k,
    v,
    mesh,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    seq_axis: str = SEQUENCE,
    batch_axes: Tuple[str, ...] = (DATA, FSDP),
    head_axis: str = TENSOR,
    impl: str = "auto",
):
    spec = P(batch_axes, head_axis, seq_axis, None)
    fn = jax.shard_map(
        functools.partial(
            ulysses_attention,
            axis_name=seq_axis,
            causal=causal,
            sm_scale=sm_scale,
            impl=impl,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
