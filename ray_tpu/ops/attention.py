"""Flash attention for TPU (Pallas).

The hot op of the model stack: blockwise attention with online softmax so
the S×S score matrix never materializes in HBM — O(S) memory, MXU-friendly
block matmuls, fp32 accumulators with bf16-friendly inputs.

Pipelining design (the part that makes it beat plain XLA): the K/V stream
is a *grid dimension*, not an in-kernel loop — each (1, block_k, d) K/V
tile is its own BlockSpec block, so Pallas double-buffers the HBM→VMEM
tile DMAs against the MXU work of the previous tile. The online-softmax
state (m, l, acc) lives in VMEM scratch that persists across the K grid
steps (grid dims are ("parallel", "parallel", "arbitrary")); the output
tile is written once on the last K step. For causal masking the K tile
index is *clamped* at the diagonal — Pallas skips the DMA when a block
index repeats, so the masked-out upper-triangle tiles cost neither
bandwidth nor (via ``pl.when``) compute.

Forward and backward are Pallas kernels wired through ``jax.custom_vjp``
(FlashAttention-2 backward: saved logsumexp, D = rowsum(dO·O), split dq
and dk/dv passes, both K/Q-streamed the same way). On non-TPU backends
the kernels run in interpreter mode so CI exercises the same code path
(fake-ICI testing strategy, SURVEY §4.3).

The reference stack has no equivalent op — attention lives inside torch
models; this kernel is the TPU-native foundation the model zoo builds on.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def default_blocks(seq_q: int) -> tuple:
    """FORWARD blocks, tuned on v5e (round-5 sweep): (512, 1024) wins at
    s=2048 (67 vs 57 TFLOP/s) AND s=8192 (61 vs 56). The backward has its
    own per-bucket table (``default_bwd_blocks``) — the custom_vjp
    threads them independently, so the fwd no longer has to run
    bwd-shaped blocks or vice versa."""
    return (512, 1024)


#: Backward blocks per sequence bucket: seq_q upper bound → (block_q,
#: block_k). The backward keeps ~3x the forward's VMEM live per tile
#: (dq/dk+dv fp32 accumulators plus q, k, v, do tiles and the lse/delta
#: rows), and the dkv pass streams Q tiles innermost — so the backward
#: wants SMALLER q tiles than the forward to keep double-buffering room,
#: while big K tiles keep the MXU fed. Running forward-shaped blocks in
#: the backward is where the r05 51% (fwd) → 28-34% (fwd+bwd) MFU cliff
#: lived. Table seeded from the v5e VMEM model; the kernels' share of a
#: training step on the chip is ``flash_kernel_time_share`` (PERF.md).
BWD_BLOCK_BUCKETS = (
    (1024, (256, 512)),
    (2048, (256, 1024)),
    (4096, (256, 1024)),
)
#: fallback for sequences above the largest bucket
_BWD_BLOCKS_LONG = (128, 1024)


def default_bwd_blocks(seq_q: int) -> tuple:
    """Backward (block_q, block_k) for this sequence bucket."""
    for bound, blocks in BWD_BLOCK_BUCKETS:
        if seq_q <= bound:
            return blocks
    return _BWD_BLOCKS_LONG


def _pick_block(seq: int, want: int) -> Optional[int]:
    """Largest block ≤ ``want`` that divides ``seq`` (scanning every
    candidate ≥ 128, so e.g. seq=4160 picks 320). Sequences shorter than
    128 become a single block; longer ones with no ≥128 divisor return
    None — the caller raises rather than letting a seq-sized tile blow
    VMEM."""
    if seq < 128:
        return seq
    for b in range(min(want, seq), 127, -1):
        if seq % b == 0:
            return b
    if seq <= 1024:
        return seq  # single tile still fits VMEM comfortably
    return None


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def reference_attention(q, k, v, *, causal: bool = True, sm_scale: Optional[float] = None):
    """Pure-XLA attention (O(S^2) memory) — correctness oracle + fallback."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool), k=klen - qlen)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _last_kb(qb, block_q: int, block_k: int, num_kb: int):
    """Last K tile index a causal Q tile attends to."""
    return jnp.minimum(num_kb - 1, ((qb + 1) * block_q - 1) // block_k)


def _causal_mask(s, qb, kb, block_q: int, block_k: int):
    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _apply_causal_mask(s, qb, kb, block_q: int, block_k: int, num_inner: int):
    """Shared masking policy for all three kernels. Tiles strictly below
    the diagonal need no mask; branching per tile (lax.cond) only pays
    off when diagonal tiles are a small fraction of the work (>=8 inner
    tiles — measured on v5e); below that the branch overhead exceeds the
    saved iota/compare/select."""
    if num_inner >= 8:
        on_diag = (kb + 1) * block_k > qb * block_q
        return jax.lax.cond(
            on_diag,
            lambda s: _causal_mask(s, qb, kb, block_q, block_k),
            lambda s: s,
            s,
        )
    return _causal_mask(s, qb, kb, block_q, block_k)


# ---------------------------------------------------------------------------
# forward kernel — grid (bh, num_q, num_k), K innermost ("arbitrary")
# ---------------------------------------------------------------------------

def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, num_kb: int, causal: bool, sm_scale: float,
):
    from jax.experimental import pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (kb <= _last_kb(qb, block_q, block_k, num_kb)) if causal else True

    @pl.when(run)
    def _compute():
        # Matmul inputs stay in their storage dtype (bf16 on TPU runs the
        # MXU at full rate; an fp32 upcast would quarter it) — fp32 comes
        # from the accumulator via preferred_element_type.
        q = q_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [bq, bk] fp32
        if causal:
            s = _apply_causal_mask(s, qb, kb, block_q, block_k, num_kb)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))


def _kv_head_map(h: int, hk: int):
    """Flattened (batch*q_head) grid index → flattened (batch*kv_head)
    K/V block index. GQA never materializes repeated K/V — the index map
    re-reads the shared head (Pallas skips the DMA when the block index
    repeats across consecutive q-heads)."""
    if h == hk:
        return lambda bh: bh
    group = h // hk
    return lambda bh: (bh // h) * hk + (bh % h) // group


def _flash_fwd(q, k, v, causal: bool, sm_scale: float, block_q: int, block_k: int, h: int, hk: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    grid = (bh, num_qb, num_kb)
    kvh = _kv_head_map(h, hk)

    if causal:
        # Clamp the K tile index at this Q tile's diagonal: repeated block
        # indices skip the DMA, so masked-out tiles cost no bandwidth.
        kv_idx = lambda b, i, j: (kvh(b), jnp.minimum(j, _last_kb(i, block_q, block_k, num_kb)), 0)
    else:
        kv_idx = lambda b, i, j: (kvh(b), j, 0)

    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
    ]
    kernel = functools.partial(
        _fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        num_kb=num_kb,
        causal=causal,
        sm_scale=sm_scale,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2) — both streamed like the forward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, block_q: int, block_k: int, num_kb: int, causal: bool, sm_scale: float,
):
    from jax.experimental import pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    run = (kb <= _last_kb(qb, block_q, block_k, num_kb)) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal:
            s = _apply_causal_mask(s, qb, kb, block_q, block_k, num_kb)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * sm_scale).astype(kblk.dtype)
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, kblk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, block_q: int, block_k: int, num_qb: int, causal: bool, sm_scale: float,
):
    # Inner grid dim is (group * num_qb): for GQA each kv head's dk/dv
    # accumulates over every q head in its group before the final write.
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    inner = pl.program_id(2)
    qb = inner % num_qb

    @pl.when(inner == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    run = (qb >= (kb * block_k) // block_q) if causal else True

    @pl.when(run)
    def _compute():
        kblk = k_ref[0]
        vblk = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal:
            s = _apply_causal_mask(s, qb, kb, block_q, block_k, num_qb)
        p = jnp.exp(s - lse)  # [bq, bk]
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(inner == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd(causal, sm_scale, block_q, block_k, h, hk, res, g):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, o, lse = res
    do = g
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    group = h // hk
    kvh = _kv_head_map(h, hk)
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [bh, seq_q, 1]

    if causal:
        kv_idx = lambda b, i, j: (kvh(b), jnp.minimum(j, _last_kb(i, block_q, block_k, num_kb)), 0)
    else:
        kv_idx = lambda b, i, j: (kvh(b), j, 0)
    q_idx = lambda b, i, j: (b, i, 0)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel,
            block_q=block_q, block_k=block_k, num_kb=num_kb,
            causal=causal, sm_scale=sm_scale,
        ),
        grid=(bh, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec((1, block_q, 1), q_idx),
            pl.BlockSpec((1, block_q, 1), q_idx),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_idx),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)

    # dkv pass runs over KV heads; the inner dim walks every q head in
    # the GQA group × every q tile. bkv → base q-head block for the group.
    def q_head_base(bkv):
        return (bkv // hk) * h + (bkv % hk) * group if h != hk else bkv

    if causal:
        # Clamp the Q tile index from below at the diagonal: tiles above
        # it contribute nothing to this K tile's dk/dv.
        qd_idx = lambda b, j, i: (
            q_head_base(b) + i // num_qb,
            jnp.maximum(i % num_qb, (j * block_k) // block_q), 0,
        )
    else:
        qd_idx = lambda b, j, i: (q_head_base(b) + i // num_qb, i % num_qb, 0)
    kv2_idx = lambda b, j, i: (b, j, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel,
            block_q=block_q, block_k=block_k, num_qb=num_qb,
            causal=causal, sm_scale=sm_scale,
        ),
        grid=(k.shape[0], num_kb, group * num_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), qd_idx),
            pl.BlockSpec((1, block_k, d), kv2_idx),
            pl.BlockSpec((1, block_k, d), kv2_idx),
            pl.BlockSpec((1, block_q, d), qd_idx),
            pl.BlockSpec((1, block_q, 1), qd_idx),
            pl.BlockSpec((1, block_q, 1), qd_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), kv2_idx),
            pl.BlockSpec((1, block_k, d), kv2_idx),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash3(q, k, v, causal, sm_scale, block_q, block_k, bwd_block_q, bwd_block_k, h, hk):
    o, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, h, hk)
    return o


def _flash3_fwd(q, k, v, causal, sm_scale, block_q, block_k, bwd_block_q, bwd_block_k, h, hk):
    o, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, h, hk)
    return o, (q, k, v, o, lse)


def _flash3_bwd(causal, sm_scale, block_q, block_k, bwd_block_q, bwd_block_k, h, hk, res, g):
    # the backward runs ITS tuned blocks — the fwd blocks only shaped the
    # saved residuals (q/k/v/o/lse are whole arrays, not tiles)
    return _flash_bwd(causal, sm_scale, bwd_block_q, bwd_block_k, h, hk, res, g)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_q_bwd: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
    impl: str = "auto",
):
    """Multi-head attention. q: ``[batch, heads, seq, head_dim]``;
    k/v: ``[batch, kv_heads, seq, head_dim]`` where ``heads`` is a
    multiple of ``kv_heads`` — GQA is handled *inside* the kernel by
    mapping each q head's K/V block index onto its shared kv head, so
    repeated K/V never hits HBM (reference pattern: KV-repeat before
    torch SDPA; here the index map replaces the repeat).

    ``block_q``/``block_k`` tile the FORWARD; ``block_q_bwd``/
    ``block_k_bwd`` tile the backward independently (default: the
    per-sequence-bucket table ``default_bwd_blocks`` — the backward's
    VMEM/streaming profile wants different tiles than the forward).

    ``impl``: "pallas" (flash kernel), "xla" (reference), or "auto"
    (pallas on TPU, xla elsewhere — CI still covers the kernel through
    interpret-mode tests).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, seq_q, d = q.shape
    hk = k.shape[1]
    if h % hk:
        raise ValueError(f"q heads ({h}) must be a multiple of kv heads ({hk})")
    if impl == "xla":
        if hk != h:
            k = jnp.repeat(k, h // hk, axis=1)
            v = jnp.repeat(v, h // hk, axis=1)
        return reference_attention(q, k, v, causal=causal, sm_scale=sm_scale)

    seq_k = k.shape[2]
    dbq, dbk = default_blocks(seq_q)
    bbq, bbk = default_bwd_blocks(seq_q)
    block_q = _pick_block(seq_q, block_q or dbq)
    block_k = _pick_block(seq_k, block_k or dbk)
    block_q_bwd = _pick_block(seq_q, block_q_bwd or bbq)
    block_k_bwd = _pick_block(seq_k, block_k_bwd or bbk)
    if None in (block_q, block_k, block_q_bwd, block_k_bwd):
        raise ValueError(
            f"sequence lengths ({seq_q}, {seq_k}) have no block divisor "
            f"≥128 — pad the sequence to a multiple of 128"
        )
    qf = q.reshape(b * h, seq_q, d)
    kf = k.reshape(b * hk, seq_k, d)
    vf = v.reshape(b * hk, seq_k, d)
    o = _flash3(
        qf, kf, vf, causal, sm_scale, block_q, block_k,
        block_q_bwd, block_k_bwd, h, hk,
    )
    return o.reshape(b, h, seq_q, d)


def flash_attention_sharded(
    q, k, v, mesh, *, q_spec, kv_spec, causal: bool = True, impl: str = "auto"
):
    """:func:`flash_attention` on arrays sharded over ``mesh``: every
    device runs the kernel on its own (batch, heads) block.

    GSPMD cannot partition a Mosaic kernel — jax refuses to lower one
    under ``jit`` on more than one device ("wrap the call in a
    shard_map") — so the sharded train step calls the kernel through
    here. ``q_spec``/``kv_spec`` are the PartitionSpecs of the
    ``[batch, heads, seq, head_dim]`` operands; sequence and head_dim
    must be unsharded in them (dense attention is local to a sequence),
    and a head axis must divide both the q and the kv heads."""
    fn = jax.shard_map(
        functools.partial(flash_attention, causal=causal, impl=impl),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v)
