"""Flash attention for a LONG query window over keys it is handed whole (a
prefill chunk over its context with its own rows laid over it): a Pallas TPU
kernel whose causal diagonal is OFFSET by a traced scalar. Query ``c`` of the
window stands at position ``ctx_len + c`` and sees key ``j`` iff ``j <=
ctx_len + c``; of the window's ``C`` queries the first ``true_len`` are real.
``ops/attention.py``'s mask (``q_pos >= k_pos`` from 0) cannot say that, and
its tiles do not stop at a live length.

What it keeps out of HBM is the score matrix: float32 scores ``[H, C, S]``
written, read for the softmax, written again as probabilities and read for
the second product were 5.6 GB a layer at Xing4's widths (PERF.md, PR 31),
six to seven times what the two products cost the MXU. Here a ``[block_q,
block_k]`` tile of them lives in VMEM and dies there.

Design, as ``ops/attention.py::_fwd_kernel``: the grid is ``(heads, query
tiles, key tiles)``, the key stream innermost, so Pallas double-buffers the
next K / V tile against this one's products; online softmax with float32
scores, state and accumulator in VMEM scratch across the key steps; the
output tile is written on the last. ``ctx_len`` and ``true_len`` are
scalar-prefetched, so the index maps read them: a query tile's key-tile
index is CLAMPED at the last tile any of its real queries sees (a repeated
block index costs no DMA) and ``pl.when`` skips the products, so **a key
tile wholly past ``ctx_len + true_len - 1`` is never fetched and never
multiplied**, and a query tile past ``true_len`` runs nothing and comes back
as zeros. A key tile wholly under the query tile's diagonal takes no mask.
The cost follows the live context, not ``S``.

A head's key may have a part that is ONE row a position for all heads (MLA's
rotary part): ``k_shared [S, ds]`` beside ``k [H, S, dk]``, and the score is
the sum of two products, ``q . k + q_shared . k_shared``, each over whole
lanes: nothing is concatenated to 192 columns and padded to 256. ``dk`` and
``dv`` need not agree.

GROUPED heads (``group`` query heads a key head: ``k`` and ``v`` are ``[H /
group, S, .]``) read their key head's tiles through the index map, ``h //
group``: nothing is repeated in HBM. A layer that keeps a WINDOW (``window``
= ``W``, static: query at ``i`` sees ``j`` iff ``i - W < j <= i``) masks the
keys behind it, and a key tile WHOLLY behind the window of a query tile's
first query is clamped out of the index map and skipped like one past the
diagonal: never fetched, never multiplied. (``models/llama.py``'s chunk, for
a configuration with layer kinds: K and V gathered through the table, a
window layer's from the block that holds its first visible key on, so that
``ctx_len`` is the chunk's offset in what it is handed.) Without either
argument the call lowers to the program it always was.

NARROW heads (``dk`` = ``dv`` = 64: half a lane tile) go through in PAIRS
(:func:`flash_attention` lays them out, the kernel is the same): two key heads
side by side are one key head of 128 lanes, ``[Hkv / 2, S, 128]`` (a cache that
stores a token's heads in one row gathers to that form for free), and a query
head holds its 64 numbers in its own key head's half of a row of zeros, so
``q . k`` over the 128 lanes is the head's own product; its output is its half
of the pair's ``[.., 128]``. Twice the multiplies, every one over whole lanes,
and ``group`` doubles.

The mathematics is the materialised softmax's to the letter
(``models/xing4.py::_attend_expanded``): scores float32, times ``scale``,
masked scores ``-1e30``, probabilities cast to V's dtype for the second
product, float32 accumulation. A padded query (``c >= true_len``) sees what
the last real one sees; its output is finite and nobody reads it. What lies
past the live context may be stale or never written: it is masked out of the
scores and zeroed in V before the second product, so a NaN there cannot
reach the output.

The call is jitted by itself: a model whose layers run under two scans
lowers the kernel once, not once a scan.

A chunk of a model that SELECTS (``models/latent.py::_sparse_attention``: a
learned choice of positions a query, handed over as a mask) has a kernel of
its own, :func:`attend_selected`, which shares no line with the one above:
it is handed the LATENT rows and expands a key tile's K and V from them in
VMEM (see there). The calls above lower to the text they always did.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: what a masked score is set to (``_attend_expanded``'s value)
_MASKED = -1e30

#: queries a tile (the whole window where it is shorter) and keys a tile: on
#: the chip, at Xing4's widths (32 heads, 128 + 64 shared, 128, bf16, 1024
#: queries; ms at a context of 0 / 2048 / 5120 / 7168), (1024, 1024) 0.28 /
#: 0.61 / 1.10 / 1.40 beat (512, 1024) 0.32 / 0.67 / 1.17 / 1.45, (512, 512)
#: 0.40 / 0.92 / 1.66 / 2.15 and (1024, 256) 0.70 / 1.74 / 3.28 / 4.32: a key
#: step costs its fixed part whatever it multiplies; (512, 2048) 0.48 / 0.87 /
#: 1.21 / 1.46 multiplies more dead columns and gains nothing back. 256
#: queries: (256, 1024) 0.16 / 0.24 / 0.38 / 0.45 (PERF.md, PR 32)
_QUERY_TILE = 1024
_KEY_TILE = 1024

#: VMEM :func:`attend_selected`'s kernel may use: at GLM-5's widths and (1024,
#: 1024) tiles its buffers and the tile's float32 scores and probabilities are
#: past the 16 MB a kernel gets unasked (a v5e core has 128 MB)
_SELECTED_VMEM = 64 * 1024 * 1024


def tiles(window: int, keys: int) -> tuple:
    """``(block_q, block_k)`` the kernel runs a window of ``window`` queries
    over ``keys`` key positions with."""
    return min(window, _QUERY_TILE), min(keys, _KEY_TILE)


def kernel_serves(
    window: int, keys: int, dk: int, dv: int, ds: int, dtype, backend: str | None = None,
    kv_heads: int = 0,
) -> bool:
    """Whether :func:`flash_attention` runs the kernel for ``window`` queries
    over ``keys`` key positions at head widths ``dk`` (+ ``ds`` shared, 0 for
    none) and ``dv``: on a TPU, in a dtype the MXU multiplies, where the
    window and the keys are whole tiles of whole ``(16, 128)`` registers and
    every product runs over whole lanes (``ds``: half a register's lanes is
    what Mosaic still takes as a block as wide as its array; compiled for a
    described v5e, ``tests/test_olmoe.py``). A value head of one and a half
    lane tiles (``dv`` 192: DeepSeek-V3-style heads whose value is as wide as
    the whole key) goes through as it is, a value tile ``[block_k, 192]`` as
    wide as its array: Mosaic takes it (compiled for the same v5e, at 64
    heads), the array lies in HBM on 256 lanes either way, and nothing is
    padded by hand or written back; ``dv`` is whole lanes or whole lanes and
    a half. NARROW heads (``dk`` = ``dv`` = 64, no shared part) are served in
    pairs where the caller says an even number of key heads (``kv_heads``).
    Everything else (the CPU, odd
    widths) keeps the materialised softmax. Decided at trace time; the model
    runner asks the same question to know what a prefill launch reads."""
    backend = backend or jax.default_backend()
    block_q, block_k = tiles(window, keys)
    if dk == dv == 64 and ds == 0 and kv_heads and kv_heads % 2 == 0:
        dk = dv = 128  # a pair of them: what the kernel sees
    return (
        backend == "tpu"
        and dtype in (jnp.bfloat16, jnp.float32)
        and block_q % 128 == 0
        and block_k % 128 == 0
        and window % block_q == 0
        and keys % block_k == 0
        and dk % 128 == 0
        and dv % 128 in (0, 64)
        and dv >= 128
        and ds % 64 == 0
    )


def _last_key(i, ctx_len, true_len, block_q: int):
    """The last key position a REAL query of query tile ``i`` sees: -1 for a
    tile with none."""
    seen = ctx_len + jnp.minimum((i + 1) * block_q, true_len) - 1
    return jnp.where(i * block_q < true_len, seen, -1)


def _kernel(
    ctx_ref,  # SMEM [1] int32: the first query's position
    len_ref,  # SMEM [1] int32: real queries of the window
    q_ref,  # VMEM [1, block_q, dk]
    k_ref,  # VMEM [1, block_k, dk]
    v_ref,  # VMEM [1, block_k, dv]
    *rest,  # shared: qs_ref [1, block_q, ds], ks_ref [block_k, ds]; then
    # o_ref [1, block_q, dv] and the scratch acc [block_q, dv], m, l [block_q, 1]
    scale: float,
    shared: bool,
    window: int,
):
    from jax.experimental import pallas as pl

    (qs_ref, ks_ref), rest = (rest[:2], rest[2:]) if shared else ((None, None), rest)
    o_ref, acc_ref, m_ref, l_ref = rest
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    i, j = pl.program_id(1), pl.program_id(2)
    ctx_len, true_len = ctx_ref[0], len_ref[0]
    n_live = ctx_len + true_len
    first_q = ctx_len + i * block_q
    lo = j * block_k

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(masked: bool):
        v = v_ref[0]
        contract_last = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_ref[0], k_ref[0], contract_last, preferred_element_type=jnp.float32)
        if shared:
            s += jax.lax.dot_general(
                qs_ref[0], ks_ref[...], contract_last, preferred_element_type=jnp.float32
            )
        s = s * scale
        if masked:
            q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = k_pos <= jnp.minimum(q_pos, n_live - 1)
            if window:
                seen &= k_pos > q_pos - window
            s = jnp.where(seen, s, _MASKED)
            # rows past the live context: stale, or never written
            v = jnp.where(lo + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) < n_live, v, 0)
        # key 0 is in tile 0 and every query of a tile that runs sees it: m
        # is real from the first step on (under a window: the tile's first
        # query sees its first live key tile; what a later row sums before
        # its own first key, its first real score wipes with alpha = 0)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    live = lo <= _last_key(i, ctx_len, true_len, block_q)
    under_diagonal = lo + block_k - 1 <= first_q
    if window:
        # wholly behind the first query's window: skipped; no mask only where
        # the LAST query's window still reaches the tile's first key
        live &= lo + block_k - 1 > first_q - window
        under_diagonal &= lo > first_q + block_q - 1 - window

    @pl.when(live & under_diagonal)
    def _():
        step(masked=False)

    @pl.when(live & jnp.logical_not(under_diagonal))
    def _():
        step(masked=True)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        # a query tile past true_len ran no step: zeros, not 0 / 0
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret", "group", "window")
)
def _call(
    q, k, v, q_shared, k_shared, ctx_len, true_len, *, scale, block_q, block_k, interpret,
    group=1, window=0,
):
    # imported here, as ops/paged_attention.py does: a second of import that
    # only a process which runs the kernel pays
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, C, dk = q.shape
    S, dv = v.shape[1], v.shape[2]
    shared = q_shared is not None

    def key_tile(i, j, ctx, n):
        # clamped at the last tile the query tile's real queries see: the
        # tiles past it repeat its index and are not fetched
        last = jnp.minimum(j, jnp.maximum(_last_key(i, ctx[0], n[0], block_q), 0) // block_k)
        if not window:
            return last
        # and from below at the tile that holds the first query's first key
        return jnp.maximum(last, jnp.maximum(ctx[0] + i * block_q - window + 1, 0) // block_k)

    q_tile = lambda h, i, j, ctx, n: (h, i, 0)  # noqa: E731
    kv_tile = lambda h, i, j, ctx, n: (h, key_tile(i, j, ctx, n), 0)  # noqa: E731
    if group > 1:
        kv_tile = lambda h, i, j, ctx, n: (h // group, key_tile(i, j, ctx, n), 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, block_q, dk), q_tile),
        pl.BlockSpec((1, block_k, dk), kv_tile),
        pl.BlockSpec((1, block_k, dv), kv_tile),
    ]
    operands = [q, k, v]
    if shared:
        ds = q_shared.shape[2]
        in_specs += [
            pl.BlockSpec((1, block_q, ds), q_tile),
            pl.BlockSpec((block_k, ds), lambda h, i, j, ctx, n: (key_tile(i, j, ctx, n), 0)),
        ]
        operands += [q_shared, k_shared]
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, shared=shared, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H, C // block_q, S // block_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, dv), q_tile),
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H, C, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        # a window layer's call by its kind's name: the trace tells the two kinds apart
        name="latent_flash_window" if window else "latent_flash",
        interpret=interpret,
    )(ctx_len.reshape(1), true_len.reshape(1), *operands)


def flash_attention(
    q, k, v, ctx_len, true_len, *, scale: float, q_shared=None, k_shared=None,
    block_q=None, block_k=None, interpret=None, group=1, window=None,
):
    """Attention of ``q [H, C, dk]`` (+ ``q_shared [H, C, ds]``) over ``k [H,
    S, dk]`` (+ ``k_shared [S, ds]``, one row a position for every head) and
    ``v [H, S, dv]``: query ``c`` sees key ``j`` iff ``j <= min(ctx_len + c,
    ctx_len + true_len - 1)`` (``ctx_len``, ``true_len`` int32 scalars, traced).
    ``group``: query heads a key head, ``k`` and ``v`` then ``[H / group, S,
    .]``. ``window``: ``W``, static, for keys no further back than ``j >
    ctx_len + c - W``; a key tile wholly behind a query tile's window is not
    read (absent: all).
    Returns ``[H, C, dv]`` in ``q``'s dtype; rows of a query tile past
    ``true_len`` are zeros. Reads ``ctx_len + true_len`` keys rounded up to
    ``block_k`` and no other, a query tile at most up to its own diagonal.

    ``block_q`` / ``block_k``: the tiles (default :func:`tiles`; ``C`` and
    ``S`` must be whole tiles). ``interpret``: run the kernel in Pallas'
    interpreter (what the CPU tests do); by default wherever the backend is
    not a TPU."""
    default_q, default_k = tiles(q.shape[1], k.shape[1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    H, C, d = q.shape
    paired = d == 64 and v.shape[2] == 64 and q_shared is None and k.shape[0] % 2 == 0
    if paired:
        # two key heads side by side are one of 128 lanes; a query head's
        # numbers in its own key head's half of a row of zeros
        pairs, S = k.shape[0] // 2, k.shape[1]
        k, v = (a.reshape(pairs, 2, S, d).transpose(0, 2, 1, 3).reshape(pairs, S, 2 * d) for a in (k, v))
        own = (jnp.arange(H)[:, None] // group) % 2 == jnp.arange(2)[None]  # [H, 2]
        q = jnp.where(own[:, None, :, None], q[:, :, None, :], 0).reshape(H, C, 2 * d)
        group *= 2
    out = _call(
        q, k, v, q_shared, k_shared,
        jnp.asarray(ctx_len, jnp.int32), jnp.asarray(true_len, jnp.int32),
        scale=float(scale), block_q=block_q or default_q, block_k=block_k or default_k,
        interpret=bool(interpret), group=int(group), window=int(window or 0),
    )
    if paired:  # a head's output: its own half of the pair's
        out = jnp.where(own[:, None, :, None], out.reshape(H, C, 2, d), 0).sum(axis=2)
    return out


# ---------------------------------------------------------------------------
# a chunk under a SELECTION, over latent rows: K and V expanded in the kernel


def selected_serves(
    window: int, keys: int, kr: int, dn: int, dr: int, dv: int, dtype, backend: str | None = None
) -> bool:
    """Whether :func:`attend_selected` runs the kernel for a chunk of
    ``window`` queries over ``keys`` latent rows ``[c (kr) | k_shared (dr)]``
    at head widths ``dn`` + ``dr`` and ``dv``: on a TPU, in bf16 (what it was
    compiled and timed in), the chunk ONE query tile (so a key tile of a head
    is expanded once) and the keys whole key tiles, the mask's tile in whole
    int8 registers ``(32, 128)``, and every product over whole lanes: a key
    ``[k_nope | k_shared]`` and a value of whole lane tiles (GLM-5's 192 + 64
    and 256), ``kr`` too (the value's product reads the row's first ``kr``
    lanes). Everything else keeps the materialised softmax. Decided at trace
    time, from shapes and the backend."""
    backend = backend or jax.default_backend()
    block_k = tiles(window, keys)[1]
    return (
        backend == "tpu"
        and dtype == jnp.bfloat16
        and window <= _QUERY_TILE
        and window % 128 == 0
        and block_k % 128 == 0
        and keys % block_k == 0
        and kr % 128 == 0
        and (dn + dr) % 128 == 0
        and dv % 128 == 0
    )


def _selected_kernel(
    ctx_ref,  # SMEM [1] int32: the first query's position
    len_ref,  # SMEM [1] int32: real queries of the chunk
    q_ref,  # VMEM [1, C, dn + dr]: a head's queries, [q_nope | q_shared]
    rows_ref,  # VMEM [block_k, kr + dr]: a key tile of latent rows
    wk_ref,  # VMEM [1, kr + dr, dn + dr]: the head's [[W_k, 0], [0, I]]
    wv_ref,  # VMEM [1, kr, dv]: the head's W_v
    mask_ref,  # VMEM [C, block_k] int8: which keys each query attends to
    o_ref,  # VMEM [1, C, dv]
    acc_ref,  # scratch [C, dv] float32
    m_ref,  # scratch [C, 1] float32
    l_ref,  # scratch [C, 1] float32
    *,
    scale: float,
    kr: int,
):
    from jax.experimental import pallas as pl

    block_k = rows_ref.shape[0]
    j = pl.program_id(1)
    n_live = ctx_ref[0] + len_ref[0]
    lo = j * block_k

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(lo < n_live)
    def _():
        rows = rows_ref[...]
        # rows past the live context: stale, or never written. Zeros: K and V
        # of them are zeros, and no real query's mask names them
        rows = jnp.where(lo + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0) < n_live, rows, 0)
        # the tile's K and V for this head, as XLA's expansion rounds them
        k = jnp.dot(rows, wk_ref[0], preferred_element_type=jnp.float32).astype(rows.dtype)
        v = jnp.dot(rows[:, :kr], wv_ref[0], preferred_element_type=jnp.float32).astype(rows.dtype)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = jnp.where(mask_ref[...].astype(jnp.int32) != 0, s * scale, _MASKED)
        # a query's first chosen key may lie in a LATER tile: until then its
        # m is the masked value, p is 1 for every key and l and acc sum
        # nobody's rows; its first real score wipes them with alpha = 0
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        # a chunk with no real query ran no step: zeros, not 0 / 0
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def _selected_call(q, rows, w_key, w_v, mask, ctx_len, true_len, *, scale, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, C, dk = q.shape
    S, W = rows.shape
    kr, dv = w_v.shape[1], w_v.shape[2]

    def key_tile(j, ctx, n):
        # clamped at the tile of the last real query's own position: the
        # tiles past it repeat its index and are not fetched
        return jnp.minimum(j, jnp.maximum(ctx[0] + n[0] - 1, 0) // block_k)

    head = lambda h, j, ctx, n: (h, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_selected_kernel, scale=scale, kr=kr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H, S // block_k),
            in_specs=[
                pl.BlockSpec((1, C, dk), head),
                pl.BlockSpec((block_k, W), lambda h, j, ctx, n: (key_tile(j, ctx, n), 0)),
                pl.BlockSpec((1, W, dk), head),
                pl.BlockSpec((1, kr, dv), head),
                pl.BlockSpec((C, block_k), lambda h, j, ctx, n: (0, key_tile(j, ctx, n))),
            ],
            out_specs=pl.BlockSpec((1, C, dv), head),
            scratch_shapes=[
                pltpu.VMEM((C, dv), jnp.float32),
                pltpu.VMEM((C, 1), jnp.float32),
                pltpu.VMEM((C, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H, C, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_SELECTED_VMEM,
        ),
        name="latent_flash_selected",
        interpret=interpret,
    )(ctx_len.reshape(1), true_len.reshape(1), q, rows, w_key, w_v, mask)


def attend_selected(
    q, rows, w_k, w_v, mask, ctx_len, true_len, *, scale: float, block_k=None, interpret=None
):
    """Attention of ONE slot's chunk under a selection, in the EXPANDED form,
    over the slot's latent rows: ``q [H, C, dn + dr]`` (a head's ``[q_nope |
    q_shared]``), ``rows [S, kr + dr]`` (``[c | k_shared]``, the chunk's own
    laid in), ``w_k [H, kr, dn]`` and ``w_v [H, kr, dv]`` (``W_kvb`` a head),
    ``mask [C, S]`` (bool or int8): query ``c`` attends to key ``j`` iff
    ``mask[c, j]``. The mask is the WHOLE predicate and must be causal already
    (a real query names no key past its own position ``ctx_len + c``, which
    lies under ``ctx_len + true_len``: ``ops/sparse_index.py::select_mask``'s
    is), and every real query names at least one key. Returns ``[H, C, dv]``
    in ``q``'s dtype; what a query past ``true_len`` gets is finite and
    nobody's.

    The grid is ``(heads, key tiles)`` and the chunk is ONE query tile, so
    each (head, key tile) is expanded ONCE, in VMEM: ``K = rows [[W_k, 0], [0,
    I]]`` (one product over the whole row gives ``[k_nope | k_shared]``: the
    identity copies the shared part exactly, and nothing is concatenated at
    192 lanes) and ``V = c W_v``, rounded to the rows' dtype as XLA's
    expansion rounds them (``models/latent.py::attend_flash``); K and V are
    never in HBM, nor are the scores. Then the online softmax of
    :func:`flash_attention`, to the letter: scores float32, times ``scale``,
    unchosen ones ``-1e30``, probabilities cast to V's dtype, float32
    accumulation. A key tile past the live length ``ctx_len + true_len``
    (prefetched scalars) is neither fetched (rows, mask) nor expanded nor
    multiplied, so ONE instance at the table's width serves every context;
    rows past the live length within the last tile are read as zeros, so what
    is stale or NaN there cannot reach the output.

    ``block_k``: the key tile (default :func:`tiles`'; ``S`` must be whole
    tiles). ``interpret``: as :func:`flash_attention`."""
    H, C, _ = q.shape
    kr, dn = w_k.shape[1], w_k.shape[2]
    dr = rows.shape[1] - kr
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # [[W_k, 0], [0, I]] a head: [kr + dr, dn + dr]
    w_key = jnp.concatenate(
        [
            jnp.pad(w_k, ((0, 0), (0, 0), (0, dr))),
            jnp.broadcast_to(jnp.pad(jnp.eye(dr, dtype=w_k.dtype), ((0, 0), (dn, 0))), (H, dr, dn + dr)),
        ],
        axis=1,
    )
    return _selected_call(
        q, rows, w_key, w_v, mask.astype(jnp.int8),
        jnp.asarray(ctx_len, jnp.int32), jnp.asarray(true_len, jnp.int32),
        scale=float(scale), block_k=block_k or tiles(C, rows.shape[0])[1], interpret=bool(interpret),
    )
