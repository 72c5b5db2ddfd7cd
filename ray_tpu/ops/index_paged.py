"""The indexer's scores of a short query window (decode, speculative verify)
against each slot's CACHED index keys, read from the paged cache through the
block table: ``ops/sparse_index.py::index_scores`` with the keys fetched by a
Pallas TPU kernel, a slot's LIVE blocks alone, where XLA's gather reads the
table as wide as it is handed for every slot of the bucket (8 x 32,768 x 128
bf16 = 67 MB a layer where the contexts hold 33 MB).

    I[b, c, s] = sum_j w[b, c, j] relu(q[b, c, j] . keys_b[s])        s < ctx_len[b], float32

The mathematics is ``index_scores``' to the letter: the products in the
cache's dtype with float32 accumulation, relu, the heads' float32 weights, the
float32 sum over the heads. The mechanism is ``ops/latent_paged.py``'s (its
:func:`~ray_tpu.ops.latent_paged.wave_copies`): a grid over the slots, the
table, the contexts and the layer scalar-prefetched, one DMA a live block of
the index array as stored (``[n_layers, num_blocks, block_size, di]``: a block
of 16 keys of 128 bf16 is 4 KB and whole tiles), a wave in flight while the
one before is multiplied, ``[C x Hi, di] x [wave, di]`` a wave. What lies past
a slot's context is never fetched and reads 0, and so does a padding slot
(its table starts on the null block); a NaN there cannot reach a score. The
window's OWN keys are not in the cache yet: their ``C x C`` scores come in as
an operand and are laid in where the keys will be written.

The exact choice over these scores stays ``ops/sparse_index.py::select_mask``'s
(XLA's 32 passes over ``[16, 32768]`` are 0.03 ms a layer: PERF.md, PR 62).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import latent_paged

#: float32 scores a head (query rows x tokens) a wave may hold: 64 rows (a
#: window of two at 32 heads) against 512 blocks = 8192 tokens, 2 MB of them
#: beside 2 x 2 MB of keys. A wave's fixed price, not its bytes, is what a
#: layer pays (on the chip, 8 slots at 9 k to 24 k: 0.67 / 0.45 / 0.35 / 0.30
#: ms at 64 / 128 / 256 / 512 blocks a wave: PERF.md, PR 62)
_WAVE_SCORES = 512 * 1024


def kernel_serves(window: int, n_heads: int, head_dim: int, cache, backend: str | None = None) -> bool:
    """Whether :func:`index_scores` runs for a window of ``window`` queries a
    slot over ``cache`` (anything with the shape and dtype of the index
    array): on a TPU, the keys stored a row a token in whole ``(8, 128)``
    tiles, the query rows whole sublanes, in a dtype the MXU multiplies.
    Decided at trace time."""
    backend = backend or jax.default_backend()
    if backend != "tpu" or len(cache.shape) != 4:
        return False
    R, width = cache.shape[2:]
    return (
        (window * n_heads) % 8 == 0
        and cache.dtype in (jnp.bfloat16, jnp.float32)
        and width == head_dim
        and head_dim % 128 == 0
        and R % 8 == 0
    )


def _kernel(
    tables_ref,  # SMEM [B * M] int32
    ctx_ref,  # SMEM [B] int32: cached positions of the slot
    nblk_ref,  # SMEM [B] int32: live blocks of the slot, 0 for a padding slot
    next_ref,  # SMEM [B + 1] int32: the first slot >= i that has live blocks (B: none)
    buf_ref,  # SMEM [B] int32: the buffer the slot's first wave lands in
    layer_ref,  # SMEM [1] int32
    q_ref,  # VMEM [1, C * Hi, di]
    w_ref,  # VMEM [1, C * Hi, 1] float32
    own_ref,  # VMEM [1, C, C] float32: query c against the window's own key c'
    cache_hbm,  # ANY [L, N, R, di]
    out_ref,  # VMEM [1, waves, C, P * R] float32
    buf,  # VMEM [2, P, R, di]
    sems,  # DMA [2 (buffer)]
    *,
    table_width: int,
):
    from jax.experimental import pallas as pl

    B = nblk_ref.shape[0]
    _, P, R, di = buf.shape
    _, _, C, PR = out_ref.shape
    Hi = q_ref.shape[1] // C
    b = pl.program_id(0)
    start_wave, turn = latent_paged.wave_copies(
        tables_ref, nblk_ref, next_ref, layer_ref[0], cache_hbm, buf, sems, table_width
    )

    @pl.when((b == 0) & (next_ref[0] < B))
    def _():
        start_wave(next_ref[0], 0, buf_ref[next_ref[0]])

    # what no wave of the slot covers
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    n_waves = pl.cdiv(nblk_ref[b], P)
    ctx = ctx_ref[b]
    q, weights = q_ref[0], w_ref[0]
    tok = jax.lax.broadcasted_iota(jnp.int32, (C, PR), 1)

    def wave(w, i_buf):
        turn(b, w, i_buf, w + 1 == n_waves)
        k = buf[i_buf].reshape(PR, di)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * weights
        total = jnp.concatenate(
            [s[c * Hi : (c + 1) * Hi].sum(axis=0, keepdims=True) for c in range(C)], axis=0
        )
        # the dead rows of the last block and the wave's unfetched blocks:
        # whatever the buffer held
        out_ref[0, w] = jnp.where(tok < ctx - w * PR, total, 0.0)
        return 1 - i_buf

    jax.lax.fori_loop(0, n_waves, wave, buf_ref[b])

    # the window's own keys are not in the cache yet: their scores, made
    # beside the kernel, are laid in where the keys will be written
    own = own_ref[0]
    waves = out_ref.shape[1]
    for c in range(C):
        at = ctx + c

        @pl.when(at < waves * PR)
        def _(c=c, at=at):
            w = at // PR
            out_ref[0, w] = jnp.where(tok == at - w * PR, own[:, c : c + 1], out_ref[0, w])


@functools.partial(jax.jit, static_argnames=("wave_blocks", "interpret"))
def _call(q, w, own, cache, layer, block_tables, ctx_len, *, wave_blocks, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, C, Hi, di = q.shape
    _, _, R, _ = cache.shape
    M, P = block_tables.shape[1], wave_blocks
    waves = -(-M // P)
    nblk, first_live_from, first_buf = latent_paged.slot_waves(block_tables, ctx_len, R, P)
    vmem = pltpu.VMEM
    out = pl.pallas_call(
        functools.partial(_kernel, table_width=M),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, C * Hi, di), lambda b, *_: (b, 0, 0), memory_space=vmem),
                pl.BlockSpec((1, C * Hi, 1), lambda b, *_: (b, 0, 0), memory_space=vmem),
                pl.BlockSpec((1, C, C), lambda b, *_: (b, 0, 0), memory_space=vmem),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, waves, C, P * R), lambda b, *_: (b, 0, 0, 0), memory_space=vmem),
            scratch_shapes=[
                pltpu.VMEM((2, P, R, di), cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, waves, C, P * R), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="index_rows",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        block_tables.reshape(-1), ctx_len, nblk, jnp.append(first_live_from, B), first_buf,
        layer.reshape(1), q.reshape(B, C * Hi, di).astype(cache.dtype),
        w.astype(jnp.float32).reshape(B, C * Hi, 1), own.astype(jnp.float32), cache,
    )
    return out.swapaxes(1, 2).reshape(B, C, waves * P * R)[..., : M * R]


def index_scores(q, w, own, cache, layer, block_tables, ctx_len, *, wave_blocks=None, interpret=None):
    """``q [B, C, Hi, di]`` (the window's indexer queries, rotated), ``w [B,
    C, Hi]`` (the heads' weights, scaled) against each slot's cached index
    keys, and ``own [B, C, C]`` float32 (query ``c`` against the window's own
    key ``c'``, not in the cache yet) laid in at ``ctx_len[b] + c'`` -> ``I
    [B, C, M x block_size]`` float32, 0 at every other position ``>=
    ctx_len[b]``. ``cache`` is the WHOLE index array ``[n_layers, num_blocks,
    block_size, di]`` (``layer`` is indexed inside the kernel), ``block_tables
    [B, M]`` int32, ``ctx_len [B]`` int32. A slot reads ``min(ceil(ctx_len /
    block_size), M)`` blocks and no other; a padding slot none.

    ``wave_blocks``: blocks a DMA wave (default: what keeps a wave's float32
    scores a head at ``_WAVE_SCORES``). ``interpret``: run the kernel in
    Pallas' TPU interpreter (what the CPU tests do); by default wherever the
    backend is not a TPU."""
    B, C, Hi, _ = q.shape
    R, M = cache.shape[2], block_tables.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if wave_blocks is None:
        wave_blocks = max(1, _WAVE_SCORES // (C * Hi * R))
    return _call(
        q, w, own, cache, jnp.asarray(layer, jnp.int32), block_tables, ctx_len,
        wave_blocks=min(M, wave_blocks), interpret=bool(interpret),
    )
