"""Paged attention over LATENT rows for a short query window (decode,
speculative verify): a Pallas TPU kernel that reads, for each slot, only the
cache blocks that hold its cached context, straight from the paged latent
cache through the block table. ``ops/paged_attention.py`` is the same
mechanism for K / V rows; what differs is the row: ONE shared key a token,
``W = kr + dr`` wide, whose first ``kr`` numbers are the value too.

The cache is read AS STORED, ``[n_layers, num_blocks, R, T x W]``
(``CacheLayout.flat_blocks``): a block's rows laid flat in ``R`` rows of ``T``
tokens each, ``T`` the fewest tokens that fill whole lanes of 128 (two rows of
576 are 1152 = 9 x 128), so that one block of one layer is contiguous whole
tiles and ONE DMA. The kernel never parts the tokens of a row. The absorbed
queries come laid out ``T`` times, ``[T x C x H, T x W]``, row ``(t, c, h)``
holding query ``(c, h)`` in lanes ``[t W, (t + 1) W)`` and zeros elsewhere: the
product against a wave of rows scores token ``t`` of every row in row group
``t``, and ``P @ rows`` accumulates the values of token ``t`` in lanes ``[t W,
t W + kr)`` of the same group. That spends ``T`` times the FLOPs the
mathematics needs and saves every relayout (the MXU is bound by loading the
wave's tiles either way: nine a 256 tokens); the groups are summed once a
slot.

A grid over the slots (the laid-out queries of 64 slots would not fit VMEM
at once); inside a step, a loop over the slot's OWN waves of live blocks. The
block table, the context lengths and the layer's index are scalar-prefetched;
each live block of a wave is one DMA into one of two VMEM buffers (all of a
wave's on ONE semaphore, waited for by the binary digits of their count), and
the next wave (of this slot, or the first of the next slot that has any) is in
flight while this one is multiplied. Online softmax with float32 scores,
state and accumulator; ``P`` is cast to the cache's dtype for the second
product, as the gather path does.

The window's OWN rows are not in the cache yet (a step writes after its last
layer), so the kernel returns the softmax's state beside the unnormalised
sum, ``(acc, m, l)``, and the caller folds the window's rows in under the
same softmax (``models/latent.py``).

A PADDING slot (its table starts on the null block) reads nothing and comes
back as ``(0, -1e30, 0)``. What may be stale or never fetched is masked out
of the scores; the dead rows of a slot's last block are zeroed in the buffer
before the second product and the buffers start as zeros, so nothing but
finite numbers ever meets a probability of 0: a NaN there cannot reach the
output.

A model that SELECTS (``models/latent.py::_sparse_attention``: the top
``index_topk`` positions a query) hands its choice over as ONE more operand,
``chosen``, laid out as a wave's scores are (a row a token-of-a-stored-row and
query, ``T x C`` of them, every head's alike) and applied where the scores of
what is stale are masked: the same blocks are read, the same two products
run, and a probability is 0 by the mask itself (a wave may hold nothing a
query chose, so the running maximum may still be the masked value after it).
Without the operand the kernel is the program it was: it is absent at trace
time, not switched off (``latent_rows`` in a trace; ``latent_rows_selected``
with it).

The layer is an operand, not a constant of the kernel, and the call is
jitted by itself: a model's calls (7, or 40 under a ``lax.scan``) are one
traced and lowered kernel. The wave's copies and waits
(:func:`wave_copies`) and the slots' schedule (:func:`slot_waves`) are also
``ops/index_paged.py``'s, over the other array of such a model's cache.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: what a masked score is set to (the gather path's value)
_MASKED = -1e30

#: query rows (window x heads) up to which the kernel serves: decode and a
#: verify window of up to 8 at 32 heads (``ops/paged_attention.py``'s limit)
_MAX_QUERY_ROWS = 256

#: scores (laid-out query rows x tokens) a wave may hold in float32: 64 rows
#: (decode at 32 heads) against 128 blocks = 2048 tokens; a wider window
#: takes fewer blocks a wave
_WAVE_SCORES = 64 * 1024

#: DMA starts issued in one straight-line group: the scalar core issues a
#: wave's 128 descriptors between the products, not under them, and a loop
#: turn a block cost as much as the descriptor (on the chip, 64 slots of the
#: ``kda-reason-offline`` mix: 0.506 -> 0.437 ms a layer, and the waits by
#: binary digits 0.437 -> 0.365; PERF.md, PR 36)
_START_UNROLL = 8


def kernel_serves(
    window: int, n_heads: int, latent_width: int, kv_lora_rank: int, cache,
    backend: str | None = None,
) -> bool:
    """Whether :func:`attend_paged` runs the kernel for a query window of
    ``window`` positions a slot over ``cache`` (anything with the shape and
    dtype of the latent cache): on a TPU, for a short window, where the cache
    is stored in whole ``(8, 128)`` tiles (``CacheLayout.block_shape``:
    ``[layers, blocks, R, T x W]``, ``T`` the fewest tokens that fill whole
    lanes), the value part is whole lanes and the query rows whole sublanes,
    in a dtype the MXU multiplies. Everything else (the CPU, a prefill chunk,
    odd widths) keeps the gather. Decided at trace time; the model's
    ``attention_path`` asks the same question to say what a launch reads."""
    backend = backend or jax.default_backend()
    if backend != "tpu" or len(cache.shape) != 4:
        return False
    R, TW = cache.shape[2:]
    return (
        window * n_heads <= _MAX_QUERY_ROWS
        and (window * n_heads) % 8 == 0
        and cache.dtype in (jnp.bfloat16, jnp.float32)
        and TW == math.lcm(latent_width, 128)
        and R % 8 == 0
        and kv_lora_rank % 128 == 0
        and kv_lora_rank <= latent_width
    )


def wave_copies(tables_ref, nblk_ref, next_ref, layer, cache_hbm, buf, sems, table_width: int):
    """``(start_wave, turn)`` of a kernel that reads each slot's live blocks
    in waves (this file's, and ``ops/index_paged.py``'s over the index keys):
    ``tables_ref`` SMEM ``[B * table_width]``, ``nblk_ref`` SMEM ``[B]`` (live
    blocks a slot), ``next_ref`` SMEM ``[B + 1]`` (the first slot ``>= i`` that
    has any; ``B``: none), ``cache_hbm [L, N, *block]``, ``buf [2, P,
    *block]``, ``sems`` DMA ``[2]``. ``start_wave(slot, wave, buffer)`` starts
    a wave's copies; ``turn(slot, wave, buffer, ends_slot)`` starts the NEXT
    wave's (of this slot or, where this wave ``ends_slot``, the first of the
    next slot that has any) into the other buffer and waits for this one's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, M = buf.shape[1], table_width

    def live_in_wave(slot, w):
        return jnp.minimum(P, nblk_ref[slot] - w * P)

    def start_wave(slot, w, i_buf):
        """One DMA a live block of the wave, ``_START_UNROLL`` at a time in
        straight-line code (the scalar core issues them: a branch a block
        costs as much as the descriptor)."""
        k = live_in_wave(slot, w)

        def start(i):
            blk = tables_ref[slot * M + w * P + i]
            pltpu.make_async_copy(cache_hbm.at[layer, blk], buf.at[i_buf, i], sems.at[i_buf]).start()

        def group(g, carry):
            for u in range(_START_UNROLL):
                start(g * _START_UNROLL + u)
            return carry

        def one(i, carry):
            start(i)
            return carry

        whole = k // _START_UNROLL
        jax.lax.fori_loop(0, whole, group, 0)
        jax.lax.fori_loop(whole * _START_UNROLL, k, one, 0)

    def wait_wave(slot, w, i_buf):
        """The wave's DMAs signal ONE semaphore by their bytes: waited for by
        the binary digits of the live count, at most ``log2(P) + 1`` waits
        where a wait a block would be ``P``."""
        k = live_in_wave(slot, w)
        size = 1 << (P.bit_length() - 1)
        while size:
            at = k // (2 * size) * (2 * size)  # what the digits above this one stand for

            @pl.when((k & size) != 0)
            def _(size=size, at=at):
                pltpu.make_async_copy(
                    cache_hbm.at[layer, pl.ds(0, size)], buf.at[i_buf, pl.ds(at, size)], sems.at[i_buf]
                ).wait()

            size //= 2

    def turn(slot, w, i_buf, ends_slot):
        nb = jnp.where(ends_slot, next_ref[slot + 1], slot)

        @pl.when(nb < nblk_ref.shape[0])
        def _():
            start_wave(nb, jnp.where(ends_slot, 0, w + 1), 1 - i_buf)

        wait_wave(slot, w, i_buf)

    return start_wave, turn


def _kernel(
    tables_ref,  # SMEM [B * M] int32
    ctx_ref,  # SMEM [B] int32: cached positions the slot's queries see
    nblk_ref,  # SMEM [B] int32: live blocks of the slot, 0 for a padding slot
    next_ref,  # SMEM [B + 1] int32: the first slot >= i that has live blocks (B: none)
    buf_ref,  # SMEM [B] int32: the buffer the slot's first wave lands in
    layer_ref,  # SMEM [1] int32
    q_ref,  # VMEM [1, T * C * H, T * W]: this slot's queries, laid out T times
    cache_hbm,  # ANY [L, N, R, T * W]
    acc_ref,  # VMEM [1, C * H, kr] float32
    m_ref,  # VMEM [1, C * H, 1] float32
    l_ref,  # VMEM [1, C * H, 1] float32
    buf,  # VMEM [2, P, R, T * W]
    sems,  # DMA [2 (buffer)]
    *,
    table_width: int,
    row_width: int,
    scale: float,
    chosen_ref=None,  # :func:`_kernel_selected`'s one more operand
):
    from jax.experimental import pallas as pl

    B = nblk_ref.shape[0]
    _, P, R, TW = buf.shape
    _, rows, _ = q_ref.shape
    _, CH, kr = acc_ref.shape
    W, M = row_width, table_width
    T = TW // W
    bs = R * T
    b = pl.program_id(0)
    layer = layer_ref[0]

    start_wave, turn = wave_copies(tables_ref, nblk_ref, next_ref, layer, cache_hbm, buf, sems, M)

    @pl.when(b == 0)
    def _():
        # never-fetched rows of a buffer must be finite: they meet P = 0
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

        @pl.when(next_ref[0] < B)
        def _():
            start_wave(next_ref[0], 0, buf_ref[next_ref[0]])

    n = nblk_ref[b]
    n_waves = pl.cdiv(n, P)
    ctx = ctx_ref[b]
    q = q_ref[0]
    # the token a score belongs to, inside its wave: stored row ``col`` holds
    # tokens ``col T .. col T + T - 1`` and query row group ``t`` scores the t-th
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, P * R), 0)
    tok = jax.lax.broadcasted_iota(jnp.int32, (rows, P * R), 1) * T
    for t in range(1, T):
        tok = tok + (row >= t * CH).astype(jnp.int32)

    def groups(x, combine):
        """``x [T x CH, .]`` -> ``[CH, .]``: the T row groups combined."""
        out = x[:CH]
        for t in range(1, T):
            out = combine(out, x[t * CH : (t + 1) * CH])
        return out

    def wave(w, carry):
        m, l, acc, i_buf = carry
        ends_slot = w + 1 == n_waves
        turn(b, w, i_buf, ends_slot)
        base = w * (P * bs)

        @pl.when(ends_slot & (n * bs > ctx))
        def _():  # the dead rows of the slot's last block: whatever was left there
            i = n - 1 - w * P
            last = buf[i_buf, i]
            r = jax.lax.broadcasted_iota(jnp.int32, (R, TW), 0) * T
            lane = jax.lax.broadcasted_iota(jnp.int32, (R, TW), 1)
            for t in range(1, T):
                r = r + (lane >= t * W).astype(jnp.int32)
            buf[i_buf, i] = jnp.where((n - 1) * bs + r < ctx, last, jnp.zeros_like(last))

        k = buf[i_buf].reshape(P * R, TW)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        seen = tok < ctx - base
        if chosen_ref is not None:
            # a row a (token of a stored row, query): every head's alike
            mine = chosen_ref[0, w]
            heads = CH // (mine.shape[0] // T)
            mine = [jnp.broadcast_to(mine[i : i + 1], (heads, P * R)) for i in range(mine.shape[0])]
            seen = seen & (jnp.concatenate(mine, axis=0) != 0)
        s = jnp.where(seen, s * scale, _MASKED)
        m_new = jnp.maximum(m, groups(s.max(axis=1, keepdims=True), jnp.maximum))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - jnp.concatenate([m_new] * T, axis=0))
        if chosen_ref is not None:
            # a wave may hold nothing a query chose: while m is still the
            # masked value, exp(s - m) of a masked score is 1, not 0
            p = jnp.where(seen, p, 0.0)
        l = alpha * l + groups(p.sum(axis=1, keepdims=True), jnp.add)
        acc = jnp.concatenate([alpha] * T, axis=0) * acc + jnp.dot(
            p.astype(k.dtype), k, preferred_element_type=jnp.float32
        )
        return m_new, l, acc, 1 - i_buf

    # wave 0 holds position 0, which every query sees: m is real after it
    # (under a selection: after the first wave that holds a chosen position)
    m, l, acc, _ = jax.lax.fori_loop(
        0, n_waves, wave,
        (
            jnp.full((CH, 1), _MASKED, jnp.float32),
            jnp.zeros((CH, 1), jnp.float32),
            jnp.zeros((rows, TW), jnp.float32),
            buf_ref[b],
        ),
    )
    # group t's values of ITS token are lanes [t W, t W + kr)
    out = acc[:CH, :kr]
    for t in range(1, T):
        out = out + acc[t * CH : (t + 1) * CH, t * W : t * W + kr]
    acc_ref[0] = out
    m_ref[0] = m
    l_ref[0] = l


def _kernel_selected(tables_ref, ctx_ref, nblk_ref, next_ref, buf_ref, layer_ref, q_ref, chosen_ref, *rest, **static):
    """:func:`_kernel` under a selection: ONE more operand behind the
    queries, laid out as the scores of a wave, VMEM ``[1, waves, T * C, P *
    R]`` float32 (0 / 1): what query ``c`` chose of the wave's tokens."""
    _kernel(tables_ref, ctx_ref, nblk_ref, next_ref, buf_ref, layer_ref, q_ref, *rest, chosen_ref=chosen_ref, **static)


def slot_waves(block_tables, ctx_len, block_size: int, wave_blocks: int):
    """What a kernel over the slots' live blocks (:func:`wave_copies`) takes
    by scalar prefetch beside the table: ``(live blocks a slot [B], the first
    slot >= i that has any [B] (B: none), the buffer a slot's first wave lands
    in [B])``. Block 0 is the null block: a table that starts on it is a
    padding slot's, and reads nothing."""
    B, M = block_tables.shape
    nblk = jnp.where(block_tables[:, 0] == 0, 0, jnp.minimum(-(-ctx_len // block_size), M))
    slots = jnp.arange(B, dtype=jnp.int32)
    first_live_from = jax.lax.cummin(jnp.where(nblk > 0, slots, B), reverse=True)
    waves = -(-nblk // wave_blocks)
    first_buf = (jnp.cumsum(waves) - waves) % 2
    return nblk, first_live_from, first_buf


@functools.partial(
    jax.jit, static_argnames=("kv_lora_rank", "scale", "wave_blocks", "interpret")
)
def _call(q_row, cache, layer, block_tables, ctx_len, chosen=None, *, kv_lora_rank, scale, wave_blocks, interpret):
    # imported here, as ops/paged_attention.py does: a second of import that
    # only a process which runs the kernel pays
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, W = q_row.shape
    _, _, R, TW = cache.shape
    T, kr = TW // W, kv_lora_rank
    bs, CH = R * T, C * H
    M, P = block_tables.shape[1], wave_blocks
    nblk, first_live_from, first_buf = slot_waves(block_tables, ctx_len, bs, P)
    # queries laid out T times: row (t, c, h) holds q[c, h] in lanes [t W, (t + 1) W)
    q = q_row.reshape(B, 1, CH, 1, W).astype(cache.dtype)
    eye = jnp.eye(T, dtype=cache.dtype).reshape(1, T, 1, T, 1)
    q = (q * eye).reshape(B, T * CH, TW)
    vmem = pltpu.VMEM
    selection = ()
    if chosen is not None:
        # ``[B, C, M bs]`` laid out as a wave's scores, ``[B, waves, (t, c), P x R]``: token ``col T + t``
        # of a wave goes to column ``col`` of row group ``t``. A stride-``T`` de-interleave of lanes, which as
        # XLA's reshape + transpose was 0.57 ms a layer at a table of 32,768 (PERF.md, PR 62); as a product
        # with the permutation it is the MXU's and a few microseconds (0 and 1 are exact in bfloat16)
        waves, PR = -(-M // P), P * R
        to = jnp.arange(T * PR, dtype=jnp.int32)
        permutation = (jnp.arange(T * PR, dtype=jnp.int32)[:, None] == (to % PR * T + to // PR)[None, :])
        laid = jnp.pad(chosen, ((0, 0), (0, 0), (0, (waves * P - M) * bs))).reshape(B * C * waves, T * PR)
        laid = jnp.dot(laid.astype(jnp.bfloat16), permutation.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        laid = laid.reshape(B, C, waves, T, PR).transpose(0, 2, 3, 1, 4).reshape(B, waves, T * C, PR)
        selection = ((laid, pl.BlockSpec((1, waves, T * C, PR), lambda b, *_: (b, 0, 0, 0), memory_space=vmem)),)
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel if chosen is None else _kernel_selected, table_width=M, row_width=W, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, T * CH, TW), lambda b, *_: (b, 0, 0), memory_space=vmem),
                *(spec for _, spec in selection),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, CH, kr), lambda b, *_: (b, 0, 0), memory_space=vmem),
                pl.BlockSpec((1, CH, 1), lambda b, *_: (b, 0, 0), memory_space=vmem),
                pl.BlockSpec((1, CH, 1), lambda b, *_: (b, 0, 0), memory_space=vmem),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, P, R, TW), cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, CH, kr), jnp.float32),
            jax.ShapeDtypeStruct((B, CH, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, CH, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="latent_rows" if chosen is None else "latent_rows_selected",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        block_tables.reshape(-1), ctx_len, nblk, jnp.append(first_live_from, B), first_buf,
        layer.reshape(1), q, *(laid for laid, _ in selection), cache,
    )
    return acc.reshape(B, C, H, kr), m.reshape(B, C, H), l.reshape(B, C, H)


def attend_paged(
    q_row, cache, layer, block_tables, ctx_len, *, kv_lora_rank, scale, chosen=None, wave_blocks=None,
    interpret=None,
):
    """Absorbed queries ``q_row [B, C, H, W]`` over each slot's CACHED
    context: every query of slot ``b`` sees the cached positions ``j <
    ctx_len[b]`` (the window's own rows are the caller's). ``cache`` is the
    WHOLE latent cache ``[n_layers, num_blocks, R, T x W]`` (``layer`` is
    indexed inside the kernel), ``block_tables [B, M]`` int32, ``ctx_len
    [B]`` int32. Returns the online softmax as it stands after those
    positions, float32: ``acc [B, C, H, kv_lora_rank]`` (``Σ_j exp(s_j - m)
    c_j``, the probabilities cast to the cache's dtype before the product),
    ``m [B, C, H]`` (the largest scaled score, -1e30 where there was none)
    and ``l [B, C, H]`` (``Σ_j exp(s_j - m)``). A slot reads ``min(ceil(
    ctx_len / block_size), M)`` blocks and no other; a padding slot
    (``block_tables[b, 0] == 0``) reads none.

    ``chosen [B, C, M x block_size]`` bool, of a model that selects: query
    ``(b, c)``, every head of it, sees of those positions the ones it chose
    and no other. The blocks read are the same (the selection is a mask on the
    scores, laid out as the kernel lays them); a query that chose nothing
    cached comes back as ``(0, -1e30, 0)``. Absent, the kernel has no such
    operand and is the program it was.

    ``wave_blocks``: blocks a DMA wave (default: what keeps a wave's float32
    scores at ``_WAVE_SCORES``). ``interpret``: run the kernel in Pallas' TPU
    interpreter (what the CPU tests do); by default wherever the backend is
    not a TPU."""
    B, C, H, W = q_row.shape
    _, _, R, TW = cache.shape
    M = block_tables.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if wave_blocks is None:
        wave_blocks = max(1, _WAVE_SCORES // ((TW // W) * C * H * R))
    return _call(
        q_row, cache, jnp.asarray(layer, jnp.int32), block_tables, ctx_len, chosen,
        kv_lora_rank=kv_lora_rank, scale=float(scale), wave_blocks=min(M, wave_blocks),
        interpret=bool(interpret),
    )
