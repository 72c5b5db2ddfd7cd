"""Latent attention (MLA) over a paged cache of latent rows: what the model
modules that cache ONE compressed row a token a layer share
(``models/xing4.py``, ``models/kimi_linear.py``).

Everything here knows only dimensions, read off the model's config object
``cfg``: ``kv_lora_rank`` (kr), ``qk_nope_head_dim`` (dn), ``qk_rope_head_dim``
(dr: the part of a key all heads share; rotated or not is the model's
business, done before the row comes here), ``v_head_dim`` (dv),
``latent_width`` (kr + dr), ``max_seq_len``, ``dtype`` and ``attn_scale`` (the
factor on the float32 scores); and of a layer's weights ``p`` only ``w_kvb
[kr, H, dn + dv]``. The cache row is ``[c | k_shared]``; the two paths
(:func:`absorbs`), the two kernels' predicates (:func:`flash_serves` for a
prefill chunk, :func:`paged_serves` for decode and verify), the ONE attention
door of a paged body (:func:`latent_attention`) and the block write
(:func:`write_blocks`) are the same mathematics for every such model.

What a prefill chunk pays for in front of its attention follows its own end,
not the table: it gathers the latent rows, lays its own over them and expands
K and V over the whole key tiles up to its last real query and no further
(:func:`key_rungs`: a rung a tile, 8 at a table of 8192; one ``lax.switch``
inside the ONE program a bucket, the rung a traced scalar). At the table's
width that was 69 GFLOP and 0.13 GB a layer whatever the context (PERF.md,
PR 53).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.models.interface import CacheLayout
from ray_tpu.ops import index_paged, latent_flash, latent_paged, sparse_index

F32 = jnp.float32


def absorbs(cfg, window: int) -> bool:
    """Whether a query window of ``window`` positions a slot attends over
    the latent rows directly (``W_kvb`` absorbed into the query and the
    output) or expands K and V of its context first. From the counts: a
    (query, cached position) pair costs ``2 (kr + dr) + 2 kr`` a head
    absorbed against ``2 (dn + dr) + 2 dv`` expanded, and the expansion
    ``2 kr (dn + dv)`` a head a cached position a launch, which ``window``
    queries share: absorbed while ``window (2 kr - dn - dv) < kr (dn +
    dv)`` (under 171 queries at the published widths: decode and verify
    absorb, a prefill chunk of 256 or 1024 expands)."""
    kr, dn, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    return window * (2 * kr - dn - dv) < kr * (dn + dv)


#: queries that attend at a time where :func:`attend_expanded` materialises
#: the softmax (training's ``forward``; a prefill chunk wherever the flash
#: kernel does not serve: the CPU, odd widths): the float32 scores of 1024
#: queries x 32 heads over a table of 8192 are 1.07 GB at once (the
#: compile-only rehearsal's largest temporary), 0.27 GB a block. The kernel
#: (:func:`attend_flash`) keeps a tile of them in VMEM and has no use for it
_QUERY_BLOCK = 256


def probs(cfg, s, mask, dtype):
    """Causal softmax of float32 scores ``s [B, C, H, S]`` under ``mask [B,
    C, S]``, scaled by ``cfg.attn_scale``, as ``dtype``."""
    s = jnp.where(mask[:, :, None, :], s * cfg.attn_scale, -1e30)
    return jax.nn.softmax(s, axis=-1).astype(dtype)


def absorb_query(cfg, p, q_nope, q_rope):
    """``W_kvb``'s key part absorbed into the query: ``[q_nope W_k | q_rope]
    [B, C, H, kr + dr]``, to be multiplied against a whole latent row ``[c |
    k_rope]``: one product over ``kr + dr``, and no slice of gathered rows."""
    with jax.named_scope("mla.absorb"):
        w_k = p["w_kvb"][..., : cfg.qk_nope_head_dim]
        return jnp.concatenate([jnp.einsum("bchk,rhk->bchr", q_nope, w_k), q_rope], axis=-1)


def attend_rows(cfg, q_row, rows, mask, own):
    """Absorbed queries ``q_row [B, C, H, kr + dr]`` over latent rows
    directly: ``Σ_j p_ij c_j`` ``[B, C, H, kr]``, before ``W_kvb``'s value
    part. Two sets of keys under one softmax: ``rows [B, S, kr + dr]``, the
    context BEFORE the window (``mask [B, C, S]`` says which of it), and ``own
    [B, C, kr + dr]``, the window's own rows, query ``c`` seeing ``c' <= c``:
    the gathered context is never copied to lay the window over it."""
    kr = cfg.kv_lora_rank
    with jax.named_scope("mla.attend"):
        s = jnp.einsum("bchw,bsw->bchs", q_row, rows, preferred_element_type=F32)
        S, C = rows.shape[1], own.shape[1]
        s_own = jnp.einsum("bchw,bdw->bchd", q_row, own, preferred_element_type=F32)
        within = jnp.broadcast_to(jnp.tril(jnp.ones((C, C), bool)), (mask.shape[0], C, C))
        pr = probs(
            cfg, jnp.concatenate([s, s_own], axis=-1), jnp.concatenate([mask, within], axis=-1),
            rows.dtype,
        )
        o_lat = jnp.einsum("bchs,bsw->bchw", pr[..., :S], rows)
        return (o_lat + jnp.einsum("bchd,bdw->bchw", pr[..., S:], own))[..., :kr]


def paged_serves(cfg, window: int, cache, backend=None) -> bool:
    """Whether a decode or verify window of ``window`` queries a slot attends
    through the kernel over latent rows (:func:`attend_paged`):
    ``ops/latent_paged.py::kernel_serves`` on what the code can observe
    (backend, dtype, window x heads, the widths, the cache's stored form).
    Off a TPU the cache is not looked at."""
    if (backend or jax.default_backend()) != "tpu" or not absorbs(cfg, window):
        return False
    return latent_paged.kernel_serves(
        window, cfg.n_heads, cfg.latent_width, cfg.kv_lora_rank, cache["latent"], "tpu"
    )


def attend_paged(cfg, q_row, cache, layer, block_tables, first, own, chosen=None, interpret=None):
    """:func:`attend_rows` with the context read by the kernel
    (``ops/latent_paged.py``) from each slot's own live blocks: the cached
    positions ``j < first[b]`` come back as an online softmax's state ``(acc,
    m, l)``, and the window's own rows ``own [B, C, kr + dr]`` (query ``c``
    seeing ``c' <= c``) are folded in here under the SAME softmax, ``B x C x
    H`` numbers. A padding slot (its table starts on the null block) returns
    zeros, as :func:`latent_attention`'s gather does. ``chosen [B, C, keys]``
    bool, of a model that selects: query ``c`` sees a position, cached
    (the kernel takes the selection as a mask) or the window's own (``first +
    c'``, here), only if it chose it."""
    kr = cfg.kv_lora_rank
    with jax.named_scope("mla.attend"):
        acc, m, l = latent_paged.attend_paged(
            q_row, cache["latent"], layer, block_tables, first,
            kv_lora_rank=kr, scale=cfg.attn_scale, chosen=chosen, interpret=interpret,
        )
        C = own.shape[1]
        s_own = jnp.einsum("bchw,bdw->bchd", q_row, own, preferred_element_type=F32)
        within = jnp.tril(jnp.ones((C, C), bool))[None, :, None, :]
        if chosen is not None:
            within = within & window_columns(chosen, first, C)[:, :, None, :]
        s_own = jnp.where(within, s_own * cfg.attn_scale, -1e30)
        m_new = jnp.maximum(m, s_own.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p_own = jnp.exp(s_own - m_new[..., None])
        acc = alpha[..., None] * acc + jnp.einsum(
            "bchd,bdw->bchw", p_own.astype(own.dtype), own[..., :kr], preferred_element_type=F32
        )
        o_lat = acc / (alpha * l + p_own.sum(axis=-1))[..., None]
        return jnp.where((block_tables[:, 0] != 0)[:, None, None, None], o_lat, 0).astype(q_row.dtype)


def window_columns(chosen, first, window: int):
    """Of a selection ``chosen [B, C, keys]`` the columns of the window's own
    positions, ``chosen[b, c, first[b] + c']`` -> ``[B, C, window]`` (False
    past the table): a select a position, as everywhere a window's own are
    picked out of the table's width."""
    key_pos = jnp.arange(chosen.shape[-1], dtype=jnp.int32)
    own = [jnp.any(chosen & (key_pos == (first + c)[:, None, None]), axis=-1) for c in range(window)]
    return jnp.stack(own, axis=-1)


def absorb_output(cfg, p, o_lat):
    """``W_kvb``'s value part after the attention: ``[B, C, H, kr]`` ->
    ``[B, C, H, dv]``."""
    with jax.named_scope("mla.absorb"):
        return jnp.einsum("bchr,rhk->bchk", o_lat, p["w_kvb"][..., cfg.qk_nope_head_dim :])


def attend_expanded(cfg, p, q_nope, q_rope, rows, mask):
    """Attention of a window's queries over latent rows ``rows [B, S, kr +
    dr]`` (``mask [B, C, S]``: which a query sees), K and V expanded from the
    rows first: the same mathematics as ``W_kvb`` absorbed into the query and
    the output (:func:`absorb_query`, :func:`attend_rows`,
    :func:`absorb_output`; :func:`absorbs` says which costs less for a
    window). Scores and softmax float32. Returns ``[B, C, H, dv]``."""
    dn, kr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    w_k, w_v = p["w_kvb"][..., :dn], p["w_kvb"][..., dn:]
    c, k_rope = rows[..., :kr], rows[..., kr:]
    with jax.named_scope("mla.expand"):
        # a head's key is [k_nope | the ONE k_rope]: one product over dn + dr
        # a (query, key) pair instead of two passes over the float32 scores
        k_nope = jnp.einsum("bsr,rhk->bshk", c, w_k)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (*k_nope.shape[:3], k_rope.shape[-1]))],
            axis=-1,
        )
        v = jnp.einsum("bsr,rhk->bshk", c, w_v)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    def attend(q, mask):
        s = jnp.einsum("bchk,bshk->bchs", q, k, preferred_element_type=F32)
        return jnp.einsum("bchs,bshk->bchk", probs(cfg, s, mask, rows.dtype), v)

    B, C = mask.shape[:2]
    with jax.named_scope("mla.attend"):
        if C <= _QUERY_BLOCK or C % _QUERY_BLOCK:
            return attend(q, mask)
        # a block of queries at a time, [blocks, B, block, ...] under lax.map

        def split(a):
            return jnp.moveaxis(a.reshape(B, C // _QUERY_BLOCK, _QUERY_BLOCK, *a.shape[2:]), 1, 0)

        out = jax.lax.map(lambda t: attend(*t), (split(q), split(mask)))
        return jnp.moveaxis(out, 0, 1).reshape(B, C, *out.shape[3:])


def flash_serves(cfg, window: int, cache, keys=None, backend=None) -> bool:
    """Whether a window of ``window`` queries attends through the flash
    kernel on the expanded path (:func:`attend_flash`) over ``keys`` key
    positions (a table's width in tokens; the runner's full width where
    none is given): ``ops/latent_flash.py::kernel_serves`` on what the code
    can observe (backend, dtype, whole tiles, the head widths: a value head
    of 192 beside keys of 128 + 64, DeepSeek-V3's, goes through as a value
    tile as wide as its array; nothing is padded here). Off a TPU the cache
    is not looked at."""
    if (backend or jax.default_backend()) != "tpu":
        return False
    return latent_flash.kernel_serves(
        window, keys or table_keys(cfg, cache), cfg.qk_nope_head_dim, cfg.v_head_dim,
        cfg.qk_rope_head_dim, cache["latent"].dtype, "tpu",
    )


def gather_rungs(cfg, window: int, cache) -> tuple:
    """``Model.gather_rungs`` of a model whose chunks go through
    :func:`latent_attention`: the widths K and V of a chunk's context are
    EXPANDED at, which the runner counts (``prefill_width.expanded_tokens``).
    :func:`key_rungs` at the runner's full table; a model that selects expands
    where it gathers, at :func:`index_rungs`, wherever its chunk attends in
    the absorbed form (:func:`attend_masked`: nothing is expanded, the rung is
    what it multiplies), and a key tile at a time up to its own end where the
    kernel expands them (:func:`selected_serves`; what it GATHERS in front is
    still its rung)."""
    keys, bs = table_keys(cfg, cache), block_size_of(cfg, cache)
    if indexed(cfg) and not selected_serves(cfg, window, cache, keys):
        return index_rungs(cfg, keys, bs)
    return key_rungs(window, keys, bs)


def table_keys(cfg, cache) -> int:
    """Positions of the block table a prefill chunk is handed: ``max_seq_len``
    in whole blocks (``model_runner.py``'s ``max_blocks_per_seq``)."""
    bs = block_size_of(cfg, cache)
    return -(-cfg.max_seq_len // bs) * bs


def attend_flash(cfg, p, q_nope, q_rope, rows, ctx_len, true_len):
    """:func:`attend_expanded` for ONE slot through the flash kernel
    (``ops/latent_flash.py``): queries ``[C, H, .]`` over latent rows ``rows
    [S, kr + dr]`` with the window's own rows laid over them, query ``c``
    seeing row ``j`` iff ``j <= ctx_len + c``, the first ``true_len`` queries
    real. K and V are expanded by XLA as there, heads leading (``k_rope``
    stays ONE row a position: the kernel adds its product to ``k_nope``'s),
    from every one of the ``S`` rows it is handed: the caller hands the key
    tiles up to ``ctx_len + true_len`` and no more (:func:`latent_attention`'s
    rung), which are the tiles the kernel reads; the float32 scores never
    leave VMEM. Returns ``[C, H, dv]``; what a query past ``true_len`` gets
    is finite and nobody's."""
    dn, kr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    c, k_rope = rows[:, :kr], rows[:, kr:]
    with jax.named_scope("mla.expand"):
        k_nope = jnp.einsum("sr,rhk->hsk", c, p["w_kvb"][..., :dn])
        v = jnp.einsum("sr,rhk->hsk", c, p["w_kvb"][..., dn:])
    with jax.named_scope("mla.attend"):
        out = latent_flash.flash_attention(
            q_nope.swapaxes(0, 1), k_nope, v, ctx_len, true_len, scale=cfg.attn_scale,
            q_shared=q_rope.swapaxes(0, 1), k_shared=k_rope,
        )
    return out.swapaxes(0, 1)


def cache_layout(cfg, block_size: int, dtype=None, n_layers=None) -> CacheLayout:
    """The flat-block latent cache: ``n_layers`` layers WRITE a row a token
    (every layer of the model unless told: a hybrid's attention layers alone)."""
    arrays = (("latent", (cfg.latent_width,)),)
    if indexed(cfg):
        # the indexer's key beside the latent row, under the same block table
        arrays += (("index", (cfg.index_head_dim,)),)
    return CacheLayout(
        kind="latent", n_layers=cfg.n_layers if n_layers is None else n_layers, block_size=block_size,
        arrays=arrays, dtype=dtype or cfg.dtype, flat_blocks=True,
    )


def blocks_of_window(cfg, cache, window: int) -> int:
    """Blocks a window of ``window`` CONTIGUOUS positions can touch."""
    bs = block_size_of(cfg, cache)
    return (window + bs - 2) // bs + 1


# ---------------------------------------------------------------------------
# a learned sparse selection inside the attention (``cfg.index_topk`` > 0:
# ``models/glm_dsa.py``): an indexer scores every earlier position of the slot
# from a second cached row a token (``index``: its key), and the softmax runs
# over the ``index_topk`` best positions of each query alone


def indexed(cfg) -> bool:
    """Whether the model's attention selects (``index_topk`` positions a query)."""
    return bool(getattr(cfg, "index_topk", 0))


def index_rungs(cfg, keys: int, block_size: int) -> tuple:
    """:func:`key_rungs` of a model that selects: its chunk scores, selects
    and attends over the key positions up to a rung, every whole number of
    ``2 x index_topk`` positions up to the table (eight rungs of 4096 at a
    table of 32,768: a chunk's three parts cost by the rung, and a rung a
    tile, 32 of them, would be 32 instances of each in every layer body). The
    table whole where that is no whole number of whole blocks."""
    step = 2 * cfg.index_topk
    if keys % step or step % block_size:
        return (keys,)
    return tuple(range(step, keys + 1, step))


def _chosen(cfg, q_i, w_i, ikeys, limit):
    """The selection of ONE slot's queries: ``q_i [R, Hi, di]``, ``w_i [R,
    Hi]`` against index keys ``ikeys [S, di]``, query ``r`` choosing among the
    positions ``<= limit[r]`` -> ``[R, S]`` bool (``ops/sparse_index.py``)."""
    with jax.named_scope("dsa.index"):
        scores = sparse_index.index_scores(q_i, w_i, ikeys)
    with jax.named_scope("dsa.topk"):
        return sparse_index.select_mask(scores, limit, cfg.index_topk)


def selection(cfg, index, pos):
    """What each query of whole sequences may attend to: ``index`` as
    :func:`_sparse_attention` takes it over ``[B, S]`` positions ``pos`` (all
    of a sequence's at once: ``forward``) -> ``[B, S, S]`` bool, causal and
    chosen."""
    q_i, k_i, w_i = index
    return jax.vmap(lambda q, k, w, at: _chosen(cfg, q, w, k, at))(q_i, k_i, w_i, pos)


def _query_block(cfg, queries: int, keys: int) -> int:
    """Queries of a chunk that :func:`attend_masked` attends at a time: at
    most 32, and fewer where their float32 scores over ``keys`` positions (all
    heads) would pass 256 MB (a table past 32,768); a power of two that
    divides ``queries``. Why 32: timed a layer on the chip at every rung
    (PERF.md, PR 58; the chip's chunk went that way until PR 60's kernel),
    32 queries at a time are as fast as 64 from a rung of
    16,384 up and faster below it (4.8 against 6.1 and 9.4 ms at 4096 for 32,
    64 and 256), and 128 or more over a rung of 8192 ran 10 x slower than
    their operations (196 ms: XLA lays the scores out heads-major there)."""
    block = queries
    while (block > 32 or block * cfg.n_heads * keys * 4 > (256 << 20)) and block % 2 == 0 and block > 8:
        block //= 2
    return block


def attend_masked(cfg, q_row, rows, mask):
    """Absorbed queries ``q_row [C, H, kr + dr]`` of ONE slot over its latent
    rows ``rows [S, kr + dr]`` (the chunk's own laid in), each query under its
    own ``mask [C, S]`` (the selection: causal already): ``[C, H, kr]``, the
    materialised softmax between :func:`absorb_query` and
    :func:`absorb_output`. What a selecting chunk runs wherever the kernel
    does not serve (:func:`selected_serves`: the CPU, odd widths), and what
    the kernel's tests hold it to. A block of queries at a time
    (:func:`_query_block`); the unchosen positions are multiplied and masked,
    not gathered around: 1024 queries x 2048 chosen rows gathered by token
    would be 2.4 GB a layer."""
    kr, C = cfg.kv_lora_rank, q_row.shape[0]

    def attend(args):
        q, m = args
        s = jnp.einsum("chw,sw->chs", q, rows, preferred_element_type=F32)
        pr = probs(cfg, s[None], m[None], rows.dtype)[0]
        return jnp.einsum("chs,sw->chw", pr, rows[:, :kr])

    block = _query_block(cfg, C, rows.shape[0])
    if block == C:
        return attend((q_row, mask))
    out = jax.lax.map(attend, (q_row.reshape(C // block, block, *q_row.shape[1:]),
                               mask.reshape(C // block, block, -1)))
    return out.reshape(C, *out.shape[2:])


def selected_serves(cfg, window: int, cache, keys=None, backend=None) -> bool:
    """Whether a selecting chunk of ``window`` queries attends in the
    EXPANDED form through the kernel that takes the selection as a mask
    (:func:`attend_selected`) over a table of ``keys`` positions (the
    runner's full width where none is given):
    ``ops/latent_flash.py::selected_serves`` on what the code can observe
    (backend, dtype, whole tiles, the widths), for a window long enough to
    expand (:func:`absorbs`). Off a TPU the cache is not looked at."""
    if (backend or jax.default_backend()) != "tpu" or not indexed(cfg) or absorbs(cfg, window):
        return False
    return latent_flash.selected_serves(
        window, keys or table_keys(cfg, cache), cfg.kv_lora_rank, cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim, cfg.v_head_dim, cache["latent"].dtype, "tpu",
    )


def sparse_paged_serves(cfg, window: int, cache, backend=None) -> bool:
    """Whether a selecting decode or verify window of ``window`` queries a
    slot reads its slots' LIVE blocks through the two paged kernels (the
    index keys: ``ops/index_paged.py``; the latent rows under the selection
    as a mask: ``ops/latent_paged.py``): :func:`paged_serves` and the index
    kernel's ``kernel_serves`` on what the code can observe (backend, dtype,
    window x heads, the widths, the stored form of both arrays). Off a TPU
    the cache is not looked at."""
    return (
        indexed(cfg)
        and paged_serves(cfg, window, cache, backend)
        and index_paged.kernel_serves(window, cfg.index_n_heads, cfg.index_head_dim, cache["index"], "tpu")
    )


def attend_selected(cfg, p, q_nope, q_rope, rows, mask, ctx_len, true_len, interpret=None):
    """ONE slot's chunk under its selection, in the expanded form, through
    the kernel (``ops/latent_flash.py::attend_selected``): queries ``[C, H,
    .]`` over latent rows ``rows [S, kr + dr]`` (the chunk's own laid in),
    query ``c`` attending to row ``j`` iff ``mask[c, j]`` (``[C, S]``, causal
    and chosen; ``S`` the TABLE's width: the kernel reads the key tiles up to
    ``ctx_len + true_len`` and no other, so what lies past a chunk's rung is
    padding nobody fetches). K and V of a key tile are expanded from the rows
    INSIDE the kernel, a head at a time: they are never whole in HBM (at
    24,576 positions x 64 heads x 256 they would be 1.6 GB), nor are the
    scores. The mathematics is :func:`attend_masked`'s between
    :func:`absorb_query` and :func:`absorb_output` with ``W_kvb`` multiplied
    into the rows instead (K and V rounded to the rows' dtype, as
    :func:`attend_flash`'s are). Returns ``[C, H, dv]``."""
    dn = cfg.qk_nope_head_dim
    with jax.named_scope("mla.expand"):
        w = p["w_kvb"].swapaxes(0, 1)  # [H, kr, dn + dv]
        q = jnp.concatenate([q_nope, q_rope], axis=-1).swapaxes(0, 1)
    with jax.named_scope("mla.attend"):
        out = latent_flash.attend_selected(
            q, rows, w[..., :dn], w[..., dn:], mask, ctx_len, true_len,
            scale=cfg.attn_scale, interpret=interpret,
        )
    return out.swapaxes(0, 1)


def _token_rows(cfg, cache, layer, tables, positions):
    """The latent rows of each slot at ``positions [B, C, k]`` (each under the
    slot's context; ``tables [B, M]``), gathered BY TOKEN out of the cache as
    it is stored: a stored row of ``T`` tokens a position
    (``CacheLayout.flat_blocks``), the token's part cut out of it after (a
    select a part: ``T`` is 2 at the published width). 2048 scattered
    positions of 16 k touch nearly nine blocks in ten, so whole blocks would
    be the whole context. Returns ``[B, C, k, kr + dr]``."""
    L, N, *block = cache["latent"].shape
    W, bs = cfg.latent_width, block_size_of(cfg, cache)
    flat = cache["latent"].reshape(L * N, *block)
    col = jnp.minimum(positions // bs, tables.shape[1] - 1)
    blk = layer * N + jnp.take_along_axis(tables, col.reshape(col.shape[0], -1), axis=1).reshape(col.shape)
    at = positions % bs
    if len(block) == 2:  # [bs / T, T x W]
        T = block[1] // W
        stored, part = flat[blk, at // T].reshape(*positions.shape, T, W), at % T
    else:  # one row of bs x W a block (the tests' toy widths)
        T = bs
        stored, part = flat[blk].reshape(*positions.shape, T, W), at
    rows = stored[..., 0, :]
    for t in range(1, T):
        rows = jnp.where((part == t)[..., None], stored[..., t, :], rows)
    return rows


def _sparse_attention(cfg, p, q_nope, q_rope, row, index, cache, layer, block_tables, pos, true_lens):
    """:func:`latent_attention` of a model that selects. ``index``: ``(q_i
    [B, C, Hi, di], k_i [B, C, di], w [B, C, Hi])`` of the window (the
    indexer's queries and key, rotated, and the heads' weights, scaled). Every
    window goes through the three parts, whatever its context (under
    ``index_topk`` positions all are chosen, and the result is the dense
    one): ``dsa.index`` (the scores of each query against the slot's index
    keys, the window's own laid in), ``dsa.topk`` (the exact choice,
    ``ops/sparse_index.py``), ``dsa.attend``:

    * a prefill chunk, one slot at a time: the index keys and the latent rows
      gathered up to its rung (:func:`index_rungs`, a ``lax.switch``), the
      scores and the choice there, and the softmax over ALL rows of the rung
      under the selection as a mask (gathering 2048 chosen rows a query by
      token was slower at every context up to 32 k: PERF.md, PR 58). Where
      the kernel serves (:func:`selected_serves`: a TPU, bf16, whole tiles)
      in the EXPANDED form, half the operations a (query, key) pair
      (:func:`absorbs`): the switch returns what is rung-sized, the mask and
      the rows padded to the table's width, and ONE instance of the kernel
      behind it (:func:`attend_selected`) expands K and V a key tile at a
      time in VMEM up to the chunk's end and keeps the scores there.
      Elsewhere (the CPU, odd widths) in the absorbed form, XLA's
      materialised softmax inside the switch (:func:`attend_masked` between
      :func:`absorb_query` and :func:`absorb_output`);
    * a decode or verify window (:func:`absorbs`; a few positions), ``W_kvb``
      absorbed. Where the paged kernels serve (:func:`sparse_paged_serves`: a
      TPU, bf16, both arrays in whole tiles) each slot's LIVE blocks are read
      from the cache as it lies and nothing at the table's width: the scores
      of the cached keys by ``ops/index_paged.py``, which lays the window's
      own ``C x C`` (made beside it) in at ``first + c``; the exact choice
      over those scores (``sparse_index.select_mask``: what lies past a slot's
      context reads 0 and is over every query's limit); the attention over
      the live latent blocks through ``ops/latent_paged.py`` with the selection as a MASK on its scores, the
      window's own rows folded in under the same softmax, an own position only
      if chosen (:func:`attend_paged`): the same sum as over the chosen rows
      gathered, in another order. Elsewhere (the CPU, odd widths: the
      fallback, and what the kernels' tests hold them to) the index keys at
      the table's width, the chosen positions in order
      (``sparse_index.mask_positions``: a sort), the chosen latent rows
      gathered BY TOKEN (:func:`_token_rows`; a chosen position of the window
      itself is the window's own row, laid in by a select each), the softmax
      over ``index_topk`` rows a query.

    Returns ``(out, blocks)`` as there, ``blocks [B, nblk * block_size, kr +
    dr + di]``: a token's two rows side by side."""
    B, C = pos.shape
    q_i, k_i, w_i = index
    W, di, K = cfg.latent_width, cfg.index_head_dim, cfg.index_topk
    bs, nblk = block_size_of(cfg, cache), blocks_of_window(cfg, cache, C)
    tables = jnp.pad(block_tables, ((0, 0), (0, nblk)))
    first = pos[:, 0]
    own = jnp.concatenate([row, k_i.astype(row.dtype)], axis=-1)  # [B, C, W + di]

    def context(name, table, width):
        a = cache[name]
        L, N, *block = a.shape
        return a.reshape(L * N, *block)[layer * N + table].reshape(-1, width)

    def window_blocks():
        ids = jax.vmap(lambda t, at: jax.lax.dynamic_slice(t, (at // bs,), (nblk,)))(tables, first)
        old = jnp.concatenate(
            [jax.vmap(lambda t: context("latent", t, W))(ids), jax.vmap(lambda t: context("index", t, di))(ids)],
            axis=-1,
        )
        return jax.vmap(lambda r, n, a: jax.lax.dynamic_update_slice(r, n, (a % bs, 0)))(old, own, first)

    if absorbs(cfg, C):
        q_row = absorb_query(cfg, p, q_nope, q_rope)
        if sparse_paged_serves(cfg, C, cache):
            # the kernels read each slot's own live blocks; the window's own
            # keys are scored beside them and laid in where they will be written
            keys = block_tables.shape[1] * bs
            with jax.named_scope("dsa.index"):
                own_scores = jax.vmap(sparse_index.index_scores)(q_i, w_i, k_i.astype(cache["index"].dtype))
                scores = index_paged.index_scores(q_i, w_i, own_scores, cache["index"], layer, block_tables, first)
            with jax.named_scope("dsa.topk"):
                chosen = sparse_index.select_mask(scores.reshape(B * C, keys), pos.reshape(-1), K)
            with jax.named_scope("dsa.attend"):
                o_lat = attend_paged(
                    cfg, q_row, cache, layer, block_tables, first, row, chosen=chosen.reshape(B, C, keys)
                )
            live = (block_tables[:, 0] != 0)[:, None, None]
            return absorb_output(cfg, p, o_lat), jnp.where(live, window_blocks(), 0)
        # the slots at once, on explicit batch axes: a padding slot (the null
        # block's table) reads the null block's rows, finite and nobody's, and
        # comes back as zeros. The window's own keys and rows are laid in by
        # SELECTS over its ``C`` positions: a gather of them a chosen position
        # (``own[sel - first]`` under ``vmap``) ran as 32,768 sequential slices
        # on the chip, 53 of a layer's 57 ms (PERF.md, PR 58)
        keys = tables.shape[1] * bs
        k = min(K, keys)
        key_pos = jnp.arange(keys, dtype=jnp.int32)
        with jax.named_scope("dsa.index"):
            ikeys = jax.vmap(lambda t: context("index", t, di))(tables)  # [B, keys, di]
            for c in range(C):
                at = (key_pos[None, :] == (first + c)[:, None])[..., None]
                ikeys = jnp.where(at, k_i[:, c, None, :].astype(ikeys.dtype), ikeys)
            scores = jax.vmap(sparse_index.index_scores)(q_i, w_i, ikeys)  # [B, C, keys]
        with jax.named_scope("dsa.topk"):
            chosen = sparse_index.select_mask(scores.reshape(B * C, keys), pos.reshape(-1), K)
            sel, real = sparse_index.mask_positions(chosen, k)
            sel, real = sel.reshape(B, C, k), real.reshape(B, C, k)
        with jax.named_scope("dsa.attend"):
            rows = _token_rows(cfg, cache, layer, tables, jnp.minimum(sel, jnp.maximum(first - 1, 0)[:, None, None]))
            for c in range(C):
                rows = jnp.where((sel == (first + c)[:, None, None])[..., None], row[:, c, None, None, :], rows)
            s = jnp.einsum("bchw,bckw->bchk", q_row, rows, preferred_element_type=F32)
            pr = probs(cfg, s.reshape(1, B * C, -1, k), real.reshape(1, B * C, k), rows.dtype).reshape(s.shape)
            o_lat = jnp.einsum("bchk,bckw->bchw", pr, rows[..., : cfg.kv_lora_rank])
        live = (block_tables[:, 0] != 0)[:, None, None]
        o_lat = jnp.where(live[..., None], o_lat, 0)
        return absorb_output(cfg, p, o_lat), jnp.where(live, window_blocks(), 0)

    keys = block_tables.shape[1] * bs
    widths = index_rungs(cfg, keys, bs)
    flash = selected_serves(cfg, C, cache, keys)
    q_row = None if flash else absorb_query(cfg, p, q_nope, q_rope)

    def attend(b, width: int):
        table = tables[b, : width // bs + nblk]
        with jax.named_scope("dsa.index"):
            ikeys = jax.lax.dynamic_update_slice(context("index", table, di), own[b][:, W:], (first[b], 0))[:width]
        chosen = _chosen(cfg, q_i[b], w_i[b], ikeys, pos[b])
        with jax.named_scope("dsa.attend"):
            rows = jax.lax.dynamic_update_slice(context("latent", table, W), row[b], (first[b], 0))[:width]
            if flash:
                # what is rung-sized, padded to the table: the ONE kernel
                # behind the switch reads neither past the live length
                return (jnp.pad(rows, ((0, keys - width), (0, 0))),
                        jnp.pad(chosen.astype(jnp.int8), ((0, 0), (0, keys - width))))
            return attend_masked(cfg, q_row[b], rows, chosen)

    def slot(b):
        n = jnp.sum(first[b] + true_lens[b] > jnp.asarray(widths[:-1], jnp.int32))
        out = jax.lax.switch(n, [functools.partial(attend, b, width) for width in widths])
        if not flash:
            return out
        rows, mask = out
        with jax.named_scope("dsa.attend"):
            return attend_selected(cfg, p, q_nope[b], q_rope[b], rows, mask, first[b], true_lens[b])

    out = slot(0)[None] if B == 1 else jax.lax.map(slot, jnp.arange(B))
    return (out if flash else absorb_output(cfg, p, out)), window_blocks()


def latent_attention(
    cfg, p, q_nope, q_rope, row, cache, layer, block_tables, pos, true_lens, flash=None, index=None,
):
    """Causal attention of a window's queries (``[B, C, H, .]``, rope
    applied) over the cached context of their slots through ``block_tables
    [B, M]`` AND the window's own rows ``row [B, C, kr + dr]``: the ONE
    place a serving step reads the cache for attention. A slot's window is
    CONTIGUOUS: ``pos[b, c] = pos[b, 0] + c`` (all three entry points).
    Query ``(b, c)`` sees key position ``j`` of its slot iff ``j <= pos[b,
    c]``; the first ``true_lens[b]`` queries of a slot are real. A window
    attends to itself as after the write (:func:`_paged_layers` says why
    the write itself comes last), by the path chosen at trace time from the
    window (:func:`absorbs`): a prefill chunk gathers the blocks of the key
    positions up to its RUNG (the first of :func:`key_rungs`' widths that
    holds its last real query, ``first + true_len - 1``: a ``lax.switch`` on
    a traced scalar inside the one program; the last rung is the table), lays
    its rows over the
    positions they will be written to (one ``dynamic_update_slice``) and
    expands K and V of THAT context from the latent rows, then attends
    through the flash kernel where it serves (:func:`flash_serves`: a TPU,
    whole tiles; the scores stay in VMEM; ``flash``: the caller's answer to
    that question, asked there so that a module's own predicate is the one
    asked) and through :func:`attend_expanded`'s materialised softmax
    elsewhere, over the same rung (its mask is a prefix: the rows past the
    rung were masked out of every real query's scores); a batch of such
    windows (a verify window too long to absorb: the tests' alone) runs a
    slot at a time, each on its own rung; a decode or verify window absorbs
    ``W_kvb`` and attends over the rows before it directly and over its own
    rows beside them under one softmax: through the kernel over latent rows
    where it serves (:func:`paged_serves`: a TPU, a short window, the cache
    stored in whole tiles; each slot's own live blocks are read from the
    cache as it lies, :func:`attend_paged`, and XLA gathers only the window's
    ``nblk`` blocks, for the write) and elsewhere a slot at a time, a real
    slot's context gathered at the table's width (attending 8 slots of like
    context at a time instead was SLOWER on the chip, PR 35). The window's
    blocks for the write are gathered by themselves on every path but that
    one (``nblk`` blocks, whatever the rung).

    Returns ``(out [B, C, H, dv], blocks [B, nblk * block_size, kr + dr])``:
    ``blocks`` are the ``nblk`` (:func:`blocks_of_window`) blocks from the
    window's first on, old rows and new, as the cache must hold them after
    the step. A model that SELECTS (:func:`indexed`) hands the window's
    ``index`` over and goes :func:`_sparse_attention`'s way."""
    if index is not None:
        return _sparse_attention(cfg, p, q_nope, q_rope, row, index, cache, layer, block_tables, pos, true_lens)
    B, C = pos.shape
    L, N, *block = cache["latent"].shape
    W, bs, nblk = cfg.latent_width, block_size_of(cfg, cache), blocks_of_window(cfg, cache, C)
    # ``nblk`` null columns behind the table: a window that ends at the
    # table's end (a padded last chunk) spills into the null block, and no
    # slice below is clamped
    tables = jnp.pad(block_tables, ((0, 0), (0, nblk)))
    key_pos = jnp.arange(tables.shape[1] * bs, dtype=jnp.int32)
    first = pos[:, 0]

    def context(table):
        # ONE gather of whole blocks out of the cache seen as [layers x
        # blocks, *block] (a free reshape: the layers and the blocks are
        # not tiled)
        return cache["latent"].reshape(L * N, *block)[layer * N + table].reshape(-1, W)

    def window_blocks():
        # of the cache only the ``nblk`` blocks the window will be written
        # into, the window's rows laid in
        ids = jax.vmap(lambda t, at: jax.lax.dynamic_slice(t, (at // bs,), (nblk,)))(tables, first)
        return jax.vmap(lambda r, n, a: jax.lax.dynamic_update_slice(r, n, (a % bs, 0)))(
            jax.vmap(context)(ids), row, first
        )

    if paged_serves(cfg, C, cache):
        # the kernel reads each slot's own live blocks
        q_row = absorb_query(cfg, p, q_nope, q_rope)
        o_lat = attend_paged(cfg, q_row, cache, layer, block_tables, first, row)
        blocks = window_blocks()
        blocks = jnp.where((block_tables[:, 0] != 0)[:, None, None], blocks, 0)
        return absorb_output(cfg, p, o_lat), blocks
    if absorbs(cfg, C):
        # a short window over many slots, ONE SLOT AT A TIME: a padding slot
        # (its table is the null block's, :func:`_paged_layers`) reads
        # nothing, as under ``ops/paged_attention.py``; a real slot's
        # gathered context (9.4 MB at a table of 8192) stays as it is, the
        # window's rows are a second set of keys, and only the window's
        # blocks are rebuilt. ``W_kvb`` is absorbed for all slots at once
        q_row = absorb_query(cfg, p, q_nope, q_rope)

        def slot(args):
            table, q, own, at = args

            def read():
                rows = context(table)
                blocks = jax.lax.dynamic_slice(rows, (at // bs * bs, 0), (nblk * bs, W))
                blocks = jax.lax.dynamic_update_slice(blocks, own, (at % bs, 0))
                mask = jnp.broadcast_to(key_pos < at, (1, C, rows.shape[0]))
                return attend_rows(cfg, q[None], rows[None], mask, own[None])[0], blocks

            def nothing():
                return (jnp.zeros((*q.shape[:2], cfg.kv_lora_rank), q.dtype), jnp.zeros((nblk * bs, W), own.dtype))

            return jax.lax.cond(table[0] != 0, read, nothing)

        o_lat, blocks = jax.lax.map(slot, (tables, q_row, row, first))
        return absorb_output(cfg, p, o_lat), blocks
    keys = block_tables.shape[1] * bs
    if flash is None:
        flash = flash_serves(cfg, C, cache, keys)

    widths = key_rungs(C, keys, bs)

    def attend(b, width: int):
        # slot ``b`` over the first ``width`` key positions of its table: the
        # blocks that hold them and ``nblk`` columns more (the next blocks',
        # or the null ones behind the table), so that the window's rows are
        # laid where they will be written without a clamp, whatever of them
        # spills past ``width``: padding rows, and no key for anybody
        rows = context(tables[b, : width // bs + nblk])
        rows = jax.lax.dynamic_update_slice(rows, row[b], (first[b], 0))[:width]
        if flash:
            return attend_flash(cfg, p, q_nope[b], q_rope[b], rows, first[b], true_lens[b])
        mask = key_pos[:width] <= pos[b, :, None]
        return attend_expanded(cfg, p, q_nope[b][None], q_rope[b][None], rows[None], mask[None])[0]

    def slot(b):
        # the first rung that holds the slot's last real query's position
        n = jnp.sum(first[b] + true_lens[b] > jnp.asarray(widths[:-1], jnp.int32))
        return jax.lax.switch(n, [functools.partial(attend, b, width) for width in widths])

    if B == 1:  # a prefill chunk
        return slot(0)[None], window_blocks()
    return jax.lax.map(slot, jnp.arange(B)), window_blocks()


def key_rungs(window: int, keys: int, block_size: int) -> tuple:
    """The widths, in key positions and ascending, a prefill chunk of
    ``window`` queries gathers and expands its context at, one of them a
    launch, chosen on the device (:func:`latent_attention`): every whole
    number of key tiles (``ops/latent_flash.py::tiles``: the kernel's, 1024
    positions; the materialised softmax takes the same) up to a table of
    ``keys`` positions, ``[tile, 2 tile, .., keys]``: eight rungs at 8192.
    The table whole where it is no whole number of tiles of whole blocks (and
    where it is one tile: the tests' toy tables). Read off shapes: no argument
    of anybody's.

    A rung is one more instance of the kernel in each of a program's layer
    bodies, and a WARM start-up pays ≈ 0.7 s a rung for them (Xing4's
    ``warmup_s`` 10.5 -> 15.6 s, GigaChat3.1's 20.1 -> 27.0). Doublings (1, 2,
    4, 8 tiles; 12.5 and 22.0 s) were measured beside these: the same rate on
    GigaChat3.1's prompts (1 to 4 tiles), but +1.6 to +4.3% where these read
    +5.5 to +6.3% on ``mla-longdoc-batch``, whose chunks at 5 to 7 tiles of
    context a doubling hands the whole table (PERF.md, PR 53)."""
    tile = latent_flash.tiles(window, keys)[1]
    if keys % tile or tile % block_size:
        return (keys,)
    return tuple(range(tile, keys + 1, tile))


def block_size_of(cfg, cache) -> int:
    return math.prod(cache["latent"].shape[2:]) // cfg.latent_width


def write_blocks(cfg, cache, block_tables, first, blocks, layer0: int = 0):
    """Every layer's updated blocks of a step, ``blocks [n_layers, B, nblk
    * block_size, kr + dr]`` (:func:`latent_attention`; ``kr + dr + di`` for
    a model that selects: each array takes its part), into the cache:
    ONE scatter of whole rows of the cache seen as ``[layers x blocks,
    block]``, in place in the donated argument (a scatter of one ``kr +
    dr``-wide window a token was 40,960 sequential updates a prefill chunk:
    160 ms on the chip; one whose window spans the layers copied the cache
    whole). A block the window touches is rewritten with its old rows and
    the new; what lies past a slot's blocks, and a padding slot, is the null
    block: colliding trash writes are fine, nothing masked-in reads them.
    ``blocks`` may be those of the ``blocks.shape[0]`` layers from ``layer0``
    on alone (a module that writes its own rows of a cache it shares)."""
    (_, N, *block), L = cache["latent"].shape, blocks.shape[0]
    B = first.shape[0]
    bs = block_size_of(cfg, cache)
    nblk = blocks.shape[2] // bs
    tables = jnp.pad(block_tables, ((0, 0), (0, nblk)))
    ids = jax.vmap(lambda t, at: jax.lax.dynamic_slice(t, (at,), (nblk,)))(tables, first // bs)
    rows = (jnp.arange(layer0, layer0 + L, dtype=jnp.int32)[:, None, None] * N + ids[None]).reshape(-1)
    parts = {"latent": blocks}
    if "index" in cache:  # a model that selects: the indexer's key beside the row
        W = cfg.latent_width
        parts = {"latent": blocks[..., :W], "index": blocks[..., W:]}
    out = {}
    for name, part in parts.items():
        block = cache[name].shape[2:]
        flat = cache[name].reshape(-1, *block).at[rows].set(part.reshape(L * B * nblk, *block))
        out[name] = flat.reshape(cache[name].shape)
    return out
