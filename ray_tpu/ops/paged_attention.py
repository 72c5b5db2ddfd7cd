"""Paged attention for a SHORT query window (decode, speculative verify):
a Pallas TPU kernel that reads, for each slot, only the cache blocks that
hold positions its queries may see, straight from the paged cache through
the block table. Nothing of the cache is gathered, sliced or copied outside
the kernel.

The cache keeps its layout ``[n_layers, num_blocks, bs, n_kv, hd]`` (K and V
apart): one block of one layer is contiguous, and read as rows it is
``[bs * n_kv, hd]``, token-major, KV head minor. The kernel never parts the
heads. A wave of ``P`` blocks is one ``[P * bs * n_kv, hd]`` matrix; EVERY
query head is multiplied against EVERY row of it, and the columns of another
KV head are masked with the positions past the query's own. That spends
``n_kv`` times the FLOPs the mathematics needs and saves every relayout:
decode attention is bound by the bytes of K and V, each row of a wave goes
through the MXU once whichever heads ride along, and only the softmax's
vector work grows.

One invocation a layer, no grid: a loop over the slots and, inside, over the
slot's OWN waves (it ends at the slot's last live block, whatever the
table's width). The block table, the positions and the layer's index are
scalar-prefetched; each live block of a wave is one DMA for K and one for V
into one of two VMEM buffers, and the next wave (of this slot, or the first
of the next slot that has any) is in flight while this one is multiplied.
A wave's copies signal ONE semaphore a buffer (K's, V's), and a DMA semaphore
counts BYTES (probed on a v5e, PERF.md PR 57): a FULL wave, every wave of a
slot but its last, is waited for ONCE a buffer, by a descriptor as large as
the whole buffer, and its issue loop has a static count (unrolled by two);
a slot's last, partial wave issues its live blocks in a loop and waits by the
bits of their count (13 blocks: 8 + 4 + 1). A wave is multiplied WHOLE
whatever it fetched, so its fixed price (the scalar work, the matmuls' and
the softmax's ``R`` columns) is paid a wave, not a byte: :func:`blocks_a_wave`.
Online softmax with float32 scores, state and accumulator; ``P`` is cast to
the cache's dtype for the second matmul, as the gather path does.

A PADDING slot (its table starts on the null block: block 0 is never
allocated, so no request's does) reads nothing and comes back as zeros. What
may be stale or never written (the tail of a slot's last block, the blocks
of a wave that were not fetched) is masked out of the scores and zeroed in V
before the second matmul: a NaN there cannot reach the output.

A cache of FEW KV heads (``n_kv`` 4: fewer than a tile's eight sublanes) is
stored with a block's heads joined to its tokens, ``[n_layers, num_blocks, bs
* n_kv, hd]`` (``CacheLayout.flat_blocks``): the same rows in the same order,
so the kernel is the same; as ``[bs, 4, hd]`` the device would pad every
token's heads to a whole tile. The caller then says ``n_kv``.

A layer that keeps a WINDOW (``keeps`` = ``W``: query at ``i`` sees ``j`` iff
``i - W < j <= i``) is told so, and each slot's waves then start at the
slot's FIRST live block, the one that holds ``min_c pos[b, c] - W + 1``, not
at block 0: the table's entries behind it were given back and read the null
block, and are never touched. Rows of the first wave that lie behind a
query's window are masked like those past it. Its span of ``W / bs + 1``
blocks is cut into waves it fills (:func:`blocks_a_wave`: 33 blocks are 2 x
17, not 16 + 16 + 1 with the third wave multiplied for one block).

NARROW heads (``hd`` 64: half a lane tile) are served with a token's heads
side by side in ONE row, ``[n_layers, num_blocks, bs, n_kv * hd]`` (``n_kv * hd``
whole lanes: a block of 16 tokens of 8 heads is ``[16, 512]``, four whole lane
tiles a token, nothing padded; ``CacheLayout.flat_blocks``). The kernel is
then told ONE key head as wide as the row, and the caller's queries are laid
out to match: head ``h`` of KV head ``g`` holds its 64 numbers in lanes ``g *
hd ..`` of a row of zeros, so ``q . k`` over the whole row is the head's own
product and every product runs over whole lanes. The scores are ``[rows,
tokens]`` (no column of another head to mask), the accumulator a row wide, and
a head's output its own lanes of it. That multiplies ``n_kv`` times what the
mathematics needs, the same as a wave of parted heads above, with an ``n_kv``-th
of its softmax work. The caller says ``n_kv`` and the queries say ``hd``.

The layer is an operand, not a constant of the kernel, and the call is
jitted by itself: the model's 16 calls are one traced and lowered kernel,
which is what keeps a decode program's start-up at the gather's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: what a masked score is set to (the gather path's value)
_MASKED = -1e30

#: query rows (window x heads) up to which the kernel serves; a prefill chunk
#: (256 x 32 rows) wants a flash kernel over context + chunk, not this one:
#: ``ops/latent_flash.py`` is that kernel (Xing4's chunk calls it over keys
#: expanded from its latent rows; ``models/llama.py``'s chunk over K and V
#: gathered through the table, where the configuration has layer kinds)
_MAX_QUERY_ROWS = 256

#: rows ``[bs * n_kv a block, hd]`` a DMA wave brings in, as whole blocks: on the
#: chip 2048 beat 1024 on 8 KV heads (a table full of context at 86% against
#: 72% of the HBM roofline) and tied 4096 on 16 (PERF.md, PR 30)
_WAVE_ROWS = 2048

#: copies of a FULL wave's issue loop laid out in a row (its count is static)
_ISSUE_UNROLL = 2


def blocks_a_wave(block: tuple, block_size: int, table_blocks: int, keeps: int = 0) -> int:
    """Blocks a DMA wave of a call over a cache whose blocks are ``block``
    (``k_cache.shape[2:]``) of ``block_size`` tokens under a table of
    ``table_blocks``: what :func:`paged_attention` takes by default, and what
    the model runner counts a launch's multiplied columns with.

    A layer that keeps everything: ``_WAVE_ROWS`` rows of 128 lanes, as many
    bytes whichever way the heads lie. A layer that keeps a window of ``keeps``
    holds ``keeps // block_size + 1`` live blocks a slot in all steps but the
    one in ``block_size`` that starts a block; that span is cut into the FEWEST
    waves that are at most a block over the default, all the same size, in
    whole lane tiles of score columns (512 in blocks of 16 x 8 heads: 33 blocks
    are 2 x 17, not 16 + 16 + 1; 1024 in blocks of 16 x 4: 65 are 2 x 34)."""
    rows, width = math.prod(block[:-1]), block[-1]
    P = max(1, _WAVE_ROWS * 128 // (rows * width))
    if keeps:
        span = keeps // block_size + 1
        waves = -(-span // (P + 1))
        tile = 128 // math.gcd(rows, 128)  # blocks that make whole lane tiles
        P = -(-(-(-span // waves)) // tile) * tile
    return min(table_blocks, P)


def kernel_serves(
    window: int, n_heads: int, k_cache, backend: str | None = None, n_kv: int | None = None,
    head_dim: int | None = None,
) -> bool:
    """Whether :func:`paged_attention` runs the kernel for a query window of
    ``window`` positions a slot over ``k_cache`` (anything with the shape and
    the dtype of the paged cache): on a TPU, for a short window, where a block
    read as ``[bs * n_kv, hd]`` rows is whole ``(16, 128)`` tiles, in a dtype
    the MXU multiplies, and the block is stored so that nothing is padded:
    ``[n_layers, num_blocks, bs, n_kv, hd]`` where the heads of a token are
    whole ``(8, 128)`` tiles (``n_kv`` 8, 16: probed for a described v5e,
    PERF.md PR 30), or FLAT, ``[n_layers, num_blocks, bs * n_kv, hd]`` with
    ``n_kv`` said beside it (``n_kv`` 4, eight query heads a KV head: run
    against the gather on the chip, PERF.md PR 44), or, for NARROW heads said
    beside it (``head_dim`` 64 with ``n_kv`` 8), a token's heads in one row of
    whole lanes, ``[n_layers, num_blocks, bs, n_kv * head_dim]`` (PERF.md PR 49).
    ONE KV head (multi-query attention: 20 query heads a slot, no whole tile of
    8 or 16 query rows) goes the flat way, ``[n_layers, num_blocks, bs * 1,
    hd]`` with ``n_kv`` 1 said beside it: a block is one whole bf16 tile, the
    20 query rows a slot ride as they are (Mosaic pads them inside its own
    layout; nothing is padded by the caller), and the block table of 256 slots x
    512 blocks is scalar-prefetched whole (512 KB of SMEM). Compiled AND run
    against the gather on a v5e (PERF.md PR 52), one layer's call, 256 slots,
    a table of 8192 positions: contexts log-normal about 1.5 k (415 k live
    tokens) 1.59 ms against the gather's 7.76, 4096 a slot (1.04 M) 3.53
    against 7.33, max|diff| / max|ref| 0.0055-0.0063 in bf16, a padding slot
    zeros; a block is 4 KB a DMA, so the kernel reaches 16% of the HBM roofline
    where 8 KV heads a block reached 86%.
    SIX query heads a KV head beside eight in ONE program (48 and 64 query
    rows a slot over the 5-D cache of 8 KV heads, the 64-row calls with ``keeps``
    512: layers of two kinds) compiled AND run against the gather on a v5e
    (PERF.md PR 56), one layer's call, 32 slots, a table of 8192 positions,
    contexts log-normal about 1.7 k (62 k live tokens): 48 rows 0.406 ms against
    the gather's 4.21 (77% of the HBM roofline), 64 rows under ``keeps`` 512
    (16 k live) 0.267 against 4.21 (32%: 33 blocks a slot are two short waves),
    max|diff| / max|ref| 0.0066 / 0.0083 in bf16.
    Those times were a call a dispatch; under ≈ 0.25 ms that reads the HOST. By
    32 calls in one device loop (PERF.md PR 57, a layer's call alone on a v5e,
    the parent's kernel -> one wait a wave and a window's waves cut to its
    span): Mistral 0.284 -> 0.280 ms (84 -> 85% of the HBM roofline); Laguna 48
    rows 0.438 -> 0.428 (85 -> 87%), 64 rows under ``keeps`` 512 0.146 -> 0.112
    (56 -> 72%: two waves of 17 for 16 + 16 + 1); Mellum2 (flat, 4 KV heads, 16
    KB a block) a full layer 0.645 -> 0.507 (56 -> 72%), a window layer 0.311
    -> 0.239 (48 -> 62%: two waves of 34); LFM2 (heads in lanes, 16 KB) 1.048 ->
    0.831 (55 -> 70%); Jamba2 (ONE KV head, 4 KB) 1.514 -> 1.156 (17 -> 23%:
    256 starts a wave at ≈ 11 ns each are what is left).
    THIRTY KV heads under ONE query row each (Olmo-Hybrid-7B's attending
    layers: plain multi-head attention at 128, 30 no multiple of 8, so the 5-d
    cache is refused) go the flat way too, ``[n_layers, num_blocks, bs * 30,
    hd]`` with ``n_kv`` 30 said beside it: a block of 16 is ``[480, 128]``, 30
    whole bf16 tiles and 120 KB a DMA (the largest block of any configuration),
    a wave 4 blocks = 1920 rows, the 30 query rows multiplied against every row
    of it and 29 of 30 columns masked (the products of 32 rows over 8 KV heads,
    a byte for a byte). Compiled AND run against the gather on a v5e (PERF.md
    PR 64), one layer's call, 64 slots, a table of 4096 positions, contexts
    log-normal about 810 (52,103 live tokens), 32 calls in one device loop:
    1.122 ms against the gather's 26.6 (87% of the HBM roofline: 1.60 GB of K
    and V), max|diff| / max|ref| 0.0057 in bf16, a padding slot zeros.
    Everything else (the CPU, a prefill chunk, odd widths) takes the gather. Decided at trace time; the
    model runner asks the same question to know what a launch reads."""
    backend = backend or jax.default_backend()
    if head_dim and n_kv and len(k_cache.shape) == 4 and k_cache.shape[3] == n_kv * head_dim != head_dim:
        # heads in lanes: to the kernel ONE key head as wide as the row
        _, _, bs, hd = k_cache.shape
        whole_heads, n_kv = n_heads % n_kv == 0, 1
    elif len(k_cache.shape) == 5:
        _, _, bs, n_kv, hd = k_cache.shape
        whole_heads = n_kv % 8 == 0
    else:
        _, _, rows, hd = k_cache.shape
        whole_heads = bool(n_kv) and rows % n_kv == 0
        bs = rows // n_kv if whole_heads else 0
    return (
        backend == "tpu"
        and window * n_heads <= _MAX_QUERY_ROWS
        and k_cache.dtype in (jnp.bfloat16, jnp.float32)
        and hd % 128 == 0
        and whole_heads
        and (bs * n_kv) % 16 == 0
        and n_heads % n_kv == 0
    )


def _kernel(
    tables_ref,  # SMEM [B * M] int32
    pos_ref,  # SMEM [B * C] int32
    nblk_ref,  # SMEM [B] int32: the slot's blocks up to its last live one, 0 for a padding slot
    next_ref,  # SMEM [B + 1] int32: the first slot >= i that has live blocks (B: none)
    layer_ref,  # SMEM [1] int32
    *refs,  # keeps: first_ref SMEM [B] int32, the slot's FIRST live block; then
    # q_ref VMEM [B, C * H, hd]; k_hbm, v_hbm ANY [L, N, *block]; o_ref VMEM [B, C * H,
    # hd]; kbuf, vbuf VMEM [2, P, *block]; sems DMA [2 (k, v), 2 (buffer)]
    window: int,
    table_width: int,
    n_kv: int,
    keeps: int,
    scale: float,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    first_ref, refs = (refs[0], refs[1:]) if keeps else (None, refs)
    q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems = refs
    B, rows, hd = q_ref.shape
    P = kbuf.shape[1]
    bs = math.prod(kbuf.shape[2:-1]) // n_kv
    C, M = window, table_width
    H = rows // C
    rep = H // n_kv
    R = P * bs * n_kv
    layer = layer_ref[0]

    def wave_at(b, w):
        """Where wave ``w`` of slot ``b`` starts in the flat table, and the live
        blocks from there on (``P`` or more: a FULL wave)."""
        first = first_ref[b] if keeps else 0
        return b * M + first + w * P, nblk_ref[b] - first - w * P

    def start_wave(b, w, buf):
        at, left = wave_at(b, w)

        def issue(i, carry=0):
            blk = tables_ref[at + i]
            pltpu.make_async_copy(k_hbm.at[layer, blk], kbuf.at[buf, i], sems.at[0, buf]).start()
            pltpu.make_async_copy(v_hbm.at[layer, blk], vbuf.at[buf, i], sems.at[1, buf]).start()
            return carry

        @pl.when(left >= P)
        def _():  # a known count: unrolled by hand (Mosaic unrolls a loop whole or not at all)
            U = min(P, _ISSUE_UNROLL)

            def some(j, carry):
                for u in range(U):
                    issue(j * U + u)
                return carry

            jax.lax.fori_loop(0, P // U, some, 0)
            for i in range(P - P % U, P):
                issue(i)

        @pl.when(left < P)
        def _():
            jax.lax.fori_loop(0, left, issue, 0)

    def wait_blocks(buf, n):
        """Wait until ``n`` (static) blocks of K and of V have landed in ``buf``:
        a DMA semaphore counts BYTES, so one wait as large as ``n`` blocks is
        satisfied by the ``n`` copies of a block each that signalled it (the
        descriptor is never started: its source only says the size)."""
        for which, into in enumerate((kbuf, vbuf)):
            some = into.at[buf, pl.ds(0, n)]
            pltpu.make_async_copy(some, some, sems.at[which, buf]).wait()

    def wait_wave(b, w, buf):
        _, left = wave_at(b, w)

        @pl.when(left >= P)
        def _():  # ONE wait a buffer
            wait_blocks(buf, P)

        # a slot's last, partial wave: by the bits of its count (at 17 live
        # blocks of 32: 16 + 1), at most log2(P) waits a buffer
        part = jnp.where(left < P, left, 0)
        for bit in (1 << i for i in reversed(range((P - 1).bit_length()))):
            @pl.when(part & bit > 0)
            def _(bit=bit):
                wait_blocks(buf, bit)

    # static over the call: which KV head a score's row and column belong to,
    # and the token of a column / of a V row inside its wave
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, R), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, R), 1)
    same_head = (row % H) // rep == col % n_kv
    col_tok = col // n_kv
    vrow_tok = jax.lax.broadcasted_iota(jnp.int32, (R, hd), 0) // n_kv
    row_c = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // H

    @pl.when(next_ref[0] < B)
    def _():
        start_wave(next_ref[0], 0, 0)

    def slot(b, buf):
        n_waves = pl.cdiv(wave_at(b, 0)[1], P)
        q = q_ref[b]
        # the last position each query row may see, and the slot's own last
        limit = jnp.full((rows, 1), pos_ref[b * C], jnp.int32)
        last = pos_ref[b * C]
        for c in range(1, C):
            limit = jnp.where(row_c == c, pos_ref[b * C + c], limit)
            last = jnp.maximum(last, pos_ref[b * C + c])

        def wave(w, carry):
            m, l, acc, buf = carry
            ends_slot = w + 1 == n_waves
            nb = jnp.where(ends_slot, next_ref[b + 1], b)

            @pl.when(nb < B)
            def _():
                start_wave(nb, jnp.where(ends_slot, 0, w + 1), 1 - buf)

            wait_wave(b, w, buf)
            base = (first_ref[b] + w * P) * bs if keeps else w * (P * bs)

            @pl.when(base + P * bs > last + 1)
            def _():  # rows past the slot's context: stale, or never fetched
                v = vbuf[buf].reshape(R, hd)
                vbuf[buf] = jnp.where(vrow_tok <= last - base, v, 0).reshape(vbuf.shape[1:])

            k = kbuf[buf].reshape(R, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            seen = same_head & (col_tok <= limit - base)
            if keeps:  # behind the query's window: the head of the first wave
                seen &= col_tok > limit - base - keeps
            s = jnp.where(seen, s * scale, _MASKED)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=1, keepdims=True)
            v = vbuf[buf].reshape(R, hd)
            acc = alpha * acc + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - buf

        # wave 0 holds position 0 (a window's first position), which every
        # row sees (the first row's, which the others' windows reach or pass:
        # what a row sums before its own first key, the next real score wipes
        # with alpha = 0): m is real after it
        m, l, acc, buf = jax.lax.fori_loop(
            0, n_waves, wave,
            (
                jnp.full((rows, 1), _MASKED, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, hd), jnp.float32),
                buf,
            ),
        )
        # a padding slot ran no wave: zeros, not 0 / 0
        o_ref[b] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
        return buf

    jax.lax.fori_loop(0, B, slot, 0)


@functools.partial(jax.jit, static_argnames=("wave_blocks", "interpret", "n_kv", "keeps", "scale"))
def _call(
    q, k_cache, v_cache, layer, block_tables, pos, *, wave_blocks: int, interpret: bool,
    n_kv: int, keeps: int, scale: float,
):
    # imported here, as ops/attention.py does: a second of import that only a
    # process which runs the kernel pays (the model module is imported by all)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, hd = q.shape
    block = k_cache.shape[2:]  # [bs, n_kv, hd], or flat [bs * n_kv, hd]
    bs = math.prod(block[:-1]) // n_kv
    M, P = block_tables.shape[1], wave_blocks
    # block 0 is the null block: a table whose first live entry is it is a
    # padding slot's (a window's entries behind its first live block are too)
    first = ()
    head = block_tables[:, 0]
    if keeps:
        first_block = jnp.minimum(jnp.maximum(pos.min(axis=1) - keeps + 1, 0) // bs, M - 1)
        head = jnp.take_along_axis(block_tables, first_block[:, None], axis=1)[:, 0]
        first = (first_block,)
    nblk = jnp.where(head == 0, 0, jnp.minimum(pos.max(axis=1) // bs + 1, M))
    slots = jnp.arange(B, dtype=jnp.int32)
    first_live_from = jax.lax.cummin(jnp.where(nblk > 0, slots, B), reverse=True)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, window=C, table_width=M, n_kv=n_kv, keeps=keeps, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + len(first),
            grid=(),
            in_specs=[vmem, any_space, any_space],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, P, *block), k_cache.dtype),
                pltpu.VMEM((2, P, *block), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, C * H, hd), q.dtype),
        # a window layer's call by its kind's name: the trace tells the two kinds apart
        name="paged_attn_window" if keeps else "paged_attn",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        block_tables.reshape(-1),
        pos.reshape(-1),
        nblk,
        jnp.append(first_live_from, B),
        layer.reshape(1),
        *first,
        q.reshape(B, C * H, hd),
        k_cache,
        v_cache,
    )
    return out.reshape(B, C, H, hd)


def paged_attention(
    q, k_cache, v_cache, layer, block_tables, pos, *, wave_blocks=None, interpret=None,
    n_kv=None, keeps=0,
):
    """Causal attention of ``q [B, C, H, hd]`` over each slot's cached
    context: query ``(b, c)`` sees key position ``j`` of slot ``b`` iff
    ``j <= pos[b, c]``. ``k_cache`` / ``v_cache`` are the WHOLE paged caches
    ``[n_layers, num_blocks, bs, n_kv, hd]`` (``layer`` is indexed inside the
    kernel), ``block_tables [B, M]`` int32, ``pos [B, C]`` int32. Returns
    ``[B, C, H, hd]`` in ``q``'s dtype. A slot reads
    ``min(max_c pos[b, c] // bs + 1, M)`` blocks and no other; a padding slot
    (``block_tables[b, 0] == 0``) reads none and returns zeros.

    ``n_kv``: the KV heads of a cache stored flat, ``[n_layers, num_blocks, bs
    * n_kv, hd]`` (a 5-D cache says it by its shape), or, where the cache's rows
    are ``n_kv`` times as wide as the queries' heads, with a token's heads in
    one row, ``[n_layers, num_blocks, bs, n_kv * hd]`` (narrow heads: the
    module's docstring). ``keeps``: ``W`` for a
    layer that keeps a window (query at ``i`` sees ``j`` iff ``i - W < j <=
    i``); a slot then reads from the block that holds ``min_c pos[b, c] - W +
    1`` on, and is a padding slot if THAT entry of its table is the null block.

    ``wave_blocks``: blocks a DMA wave (default: :func:`blocks_a_wave`).
    ``interpret``: run the kernel in Pallas' TPU interpreter (what the CPU
    tests do); by default wherever the backend is not a TPU."""
    if k_cache.ndim == 5:
        n_kv = k_cache.shape[3]
    hd = q.shape[-1]
    in_lanes = k_cache.ndim == 4 and k_cache.shape[-1] == n_kv * hd != hd
    if in_lanes:
        # head h's numbers in the lanes of its KV head, zeros in the others';
        # to the kernel ONE key head as wide as the row
        H = q.shape[2]
        own = jnp.arange(H)[:, None] // (H // n_kv) == jnp.arange(n_kv)[None]  # [H, n_kv]
        q = jnp.where(own[:, :, None], q[..., None, :], 0).reshape(*q.shape[:-1], n_kv * hd)
        heads, n_kv = n_kv, 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bs = math.prod(k_cache.shape[2:-1]) // n_kv
    out = _call(
        q, k_cache, v_cache, jnp.asarray(layer, jnp.int32), block_tables, pos,
        wave_blocks=wave_blocks or blocks_a_wave(k_cache.shape[2:], bs, block_tables.shape[1], keeps),
        interpret=bool(interpret), n_kv=int(n_kv), keeps=int(keeps), scale=1.0 / math.sqrt(hd),
    )
    if in_lanes:  # a head's output: its own lanes of the row
        out = jnp.where(own[:, :, None], out.reshape(*out.shape[:-1], heads, hd), 0).sum(axis=-2)
    return out
