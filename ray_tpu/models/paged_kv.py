"""A K and a V row a token in a paged cache: where a block lies, the ONE place a
serving step writes its rows, and the ONE place it attends over that cache. The
sibling of ``models/latent.py`` (the same for a latent row a token).

Every function takes SHAPES, and the few numbers a cache stored flat does not
say by its shape (``n_kv``, ``head_dim``, the layer's window ``keeps``), never
a configuration object: ``models/llama.py``, ``models/lfm2.py`` (64-wide heads
in lanes) and ``models/jamba.py`` (one KV head) hand over what their layer is
and take what the shapes decide.

The cache, ``CacheLayout`` of kind ``"kv"`` (``models/interface.py``): a K and
a V array ``[n_layers, num_blocks, block_size, n_kv, head_dim]`` a layer group,
or, where that would pad (``CacheLayout.flat_blocks``), a block's rows laid
flat: few KV heads of whole lanes joined to the tokens, ``[.., block_size *
n_kv, head_dim]`` (``n_kv`` 4, 1), narrow heads joined in one row of whole
lanes, ``[.., block_size, n_kv * head_dim]`` (``head_dim`` 64). Block id 0 is
the NULL block: never allocated, a masked read of it never reaches the
softmax, and it is the sink of every write that has no live block.

A step's rows are written two WAYS (:func:`write_way`, from ``B``, ``C`` and
the block's size; :func:`write_kv` runs it): a row a token (decode, a verify
window of several slots, a chunk that is no whole number of blocks: a padding
row goes to the null block), or, ONE sequence's chunk of whole blocks, the
blocks its span touches read, overlaid and written back whole (a padding row
keeps what its position held; what lies past the sequence's blocks is the null
block).

A window of queries attends three WAYS (:func:`way`, from the shapes and the
backend at trace time; :func:`attention` runs it, :func:`program_path` says
it to the host, with what a launch of it reads):

* ``"kernel"``: a short window (decode, verify) on a TPU, the Pallas kernel
  ``ops/paged_attention.py``: each slot's own live blocks out of the whole
  cache, a window layer's from its first live one on; nothing is gathered.
* ``"flash"``: ONE sequence's prefill chunk on a TPU, in whole tiles and at
  head widths the kernel takes: K and V gathered through the table (a full
  layer: as wide as the table; a window layer: from the block that holds the
  chunk's first visible key, ``keeps + chunk`` positions) and
  ``ops/latent_flash.py`` over them (grouped heads, key tiles past the
  diagonal or wholly behind the window never fetched or multiplied): no score
  is computed past the live context or written.
* ``"gather"``: every other shape (a verify window too wide for the decode
  kernel, a chunk that is no whole tile, odd head widths) and everything off
  the chip: ``cache[layer, block_tables]`` for every slot as wide as the
  table, the softmax materialised (:func:`attend_gathered`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.ops import latent_flash
from ray_tpu.ops import paged_attention as paged_attn

#: what a launch reads of the cache (``AttentionPath.reads``) on each way, the kernel's first
_READS = {"kernel": "blocks", "flash": "live", "gather": "table"}


def block_size(k_cache, n_kv: int, head_dim: int) -> int:
    """Positions a block of ``k_cache`` holds, in whichever of its three forms
    it is stored (the module's docstring): a block's numbers over a token's."""
    return math.prod(k_cache.shape[2:]) // (n_kv * head_dim)


def table_keys(max_seq_len: int, bs: int) -> int:
    """Key positions under the block table of a sequence of ``max_seq_len``."""
    return -(-max_seq_len // bs) * bs


def block_at(block_tables, pos, bs: int):
    """Id of the block that holds position ``pos[b, c]`` of slot ``b``:
    ``block_tables [B, M]``, ``pos [B, C]`` -> ``[B, C]`` (a position past
    the table reads its last column)."""
    M = block_tables.shape[1]
    return jnp.take_along_axis(block_tables, jnp.minimum(pos // bs, M - 1), axis=1)


def write_way(batch: int, window: int, bs: int) -> str:
    """``"blocks"`` | ``"rows"``: how :func:`write_kv` lays ``batch`` windows
    of ``window`` rows into blocks of ``bs`` positions. Whole blocks for ONE
    sequence's window of a whole number of blocks (a prefill chunk), a row a
    token for every other (decode, a verify window of several slots, a toy
    chunk that ends inside a block). SHAPES decide, on every backend."""
    return "blocks" if batch == 1 and window % bs == 0 else "rows"


def write_updates(batch: int, window: int, block, bs: int) -> int:
    """Scatter updates ONE array's write of a step's rows issues, blocks
    stored as ``block`` (a cache array's ``shape[2:]``) of ``bs`` positions:
    by blocks the ``window / bs + 1`` blocks the window can touch, by rows a
    token's rows of its block (``n_kv`` in a cache of few heads stored flat,
    else one) a row. What ``prefill_width.written_updates`` sums a launch,
    over K and V of every attending layer."""
    if write_way(batch, window, bs) == "blocks":
        return window // bs + 1
    return batch * window * (block[0] // bs)


def rows_at(block_tables, pos, valid, bs: int):
    """Where the rows way writes a window's rows: ``(blk, off)``, each ``[B,
    C]``, the block id (the null block where not ``valid``) and the position
    in it; ``None`` where the window is written by blocks. A caller that
    writes several layers through one table makes it once."""
    if write_way(*pos.shape, bs) == "blocks":
        return None
    return jnp.where(valid, block_at(block_tables, pos, bs), 0), pos % bs


def write_kv(cache, layer: int, block_tables, pos, valid, k, v, names=("k", "v"), at=None):
    """Write a window's K and V, ``k`` / ``v [B, C, n_kv, hd]`` at positions
    ``pos [B, C]`` (contiguous a slot) of the slots of ``block_tables [B,
    M]``, into layer ``layer`` of the arrays ``names`` (the layer's group's) of
    ``cache``, the rows that are not ``valid [B, C]`` left out. Heads in lanes:
    ``k`` and ``v`` come as ONE head as wide as the row. ``at``: what
    :func:`rows_at` gave for this table, where the caller made it already.

    The ONE place a serving step writes a K/V cache, the way
    :func:`write_way` chooses from ``B``, ``C`` and the block's size:
    :func:`scatter_kv` by rows, :func:`write_blocks` by blocks. After either
    the cache is the same bit for bit everywhere but the null block
    (``tests/test_kv_block_write.py``)."""
    block = cache[names[0]].shape[2:]
    bs = math.prod(block) // math.prod(k.shape[2:])
    if write_way(*pos.shape, bs) == "blocks":
        return write_blocks(cache, layer, block_tables[0], pos[0, 0], valid[0], k[0], v[0], names)
    blk, off = at if at is not None else rows_at(block_tables, pos, valid, bs)
    return scatter_kv(cache, layer, blk, off, k, v, names)


def scatter_kv(cache, layer: int, blk, off, k, v, names=("k", "v")):
    """The rows way of :func:`write_kv`: per-token K/V into their cache slots,
    an update a token. blk/off: [...] int32 (:func:`rows_at`),
    k/v: [..., n_kv, hd]. Padding rows target the null block — colliding
    trash writes are fine, nothing masked-in ever reads them. ``names``: the
    layer's group's arrays. A cache stored flat takes a token's heads at the
    rows ``off * n_kv ..`` of its block, an update a token a head (heads in
    lanes: ``k`` and ``v`` come as ONE head as wide as the row)."""
    k_name, v_name = names
    if cache[k_name].ndim == 4:
        n_kv = k.shape[-2]
        blk = blk[..., None]
        off = off[..., None] * n_kv + jnp.arange(n_kv, dtype=off.dtype)
    return {
        **cache,
        k_name: cache[k_name].at[layer, blk, off].set(k),
        v_name: cache[v_name].at[layer, blk, off].set(v),
    }


def write_blocks(cache, layer: int, table, start, valid, k, v, names=("k", "v")):
    """The blocks way of :func:`write_kv` (``models/latent.py::write_blocks``'
    form): ONE sequence's window ``k`` / ``v [C, n_kv, hd]``, ``C`` a whole
    number of blocks, at the positions ``start ..`` of the sequence of ``table
    [M]``. The ``C / bs + 1`` blocks the table names from ``start // bs`` on
    (the null block behind its end) are gathered, the window's rows laid over
    them from ``start % bs`` on, the span's OLD rows kept wherever the
    window's row is not ``valid [C]``, and the span written back as ONE
    scatter of whole blocks into the array seen as ``[layers x blocks,
    *block]``, in place in the donated argument. A block is contiguous in all
    three stored forms, so one body serves them: a token is ``block[0] / bs``
    leading rows of its block.

    Read-modify-write and not an aligned overwrite: a chunk starts wherever
    the cached context ends, and a full prefix hit prefills ONE token at
    ``len - 1`` into a copied block whose earlier rows are the prefix
    (``inference/kv_cache.py::acquire_prefix``). A block of the span past the
    window's last valid row is rewritten with what it held; the null block
    takes colliding writes of its own old rows and of whatever lies past the
    sequence's blocks: nothing masked-in ever reads them.

    Why: a row a token a head is a sequential update, on a v5e 74 ns each
    whatever its width. One call, K and V of a layer, a chunk of 1024, rows ->
    blocks, ms on a v5e (PERF.md, PR 66): a flat cache of 30 heads ``[.., 480,
    128]`` 4.54 -> 0.16, of 4 heads ``[.., 64, 128]`` 0.61 -> 0.04, of one
    ``[.., 16, 128]`` 0.16 -> 0.03; ``[.., 16, 8, 128]`` 0.14 -> 0.05; heads in
    lanes ``[.., 16, 512]`` 0.21 -> 0.04; a chunk of 256 gains in each too
    (0.044-1.13 -> 0.015-0.054), so no shape is kept back."""
    (_, N, *block), C = cache[names[0]].shape, k.shape[0]
    bs = math.prod(block) // math.prod(k.shape[1:])
    nblk, per = C // bs + 1, block[0] // bs  # the span's blocks; a token's rows of its block
    ids = layer * N + jax.lax.dynamic_slice(jnp.pad(table, (0, nblk)), (start // bs,), (nblk,))
    first = (start % bs * per, *(0,) * (len(block) - 1))
    keep = jnp.repeat(valid, per).reshape(-1, *(1,) * (len(block) - 1))
    out = {}
    for name, new in zip(names, (k, v)):
        flat = cache[name].reshape(-1, *block)
        span = flat[ids].reshape(nblk * block[0], *block[1:])
        new = new.reshape(C * per, *block[1:]).astype(span.dtype)
        new = jnp.where(keep, new, jax.lax.dynamic_slice(span, first, new.shape))
        span = jax.lax.dynamic_update_slice(span, new, first)
        out[name] = flat.at[ids].set(span.reshape(nblk, *block)).reshape(cache[name].shape)
    return {**cache, **out}


def chunk_keys(keeps: int, chunk: int, table_keys: int, bs: int) -> int:
    """Key positions a prefill chunk of ``chunk`` queries is handed in a
    layer that keeps a window of ``keeps`` (0: all): the table's width, or
    for a window layer the window, the chunk and a block's slack (the keys
    start on a block), in whole key tiles."""
    if not keeps:
        return table_keys
    tile = latent_flash.tiles(chunk, table_keys)[1]
    return min(table_keys, -(-(keeps + chunk + bs) // tile) * tile)


def way(
    window: int, batch: int, q_heads: int, k_cache, table_keys: int, *, n_kv: int, head_dim: int,
    keeps: int = 0, backend=None,
) -> str:
    """``"kernel"`` | ``"flash"`` | ``"gather"``: how ``batch`` windows of
    ``window`` queries of ``q_heads`` heads attend over ``k_cache`` (anything
    with the cache's shape and dtype) under a table of ``table_keys``
    positions, in a layer that keeps ``keeps`` (the module's docstring). The
    ONLY place that asks ``ops/paged_attention.py::kernel_serves`` and
    ``ops/latent_flash.py::kernel_serves`` for a K/V cache. SHAPES decide, and
    the backend: nothing of a configuration's name, ``model_type`` or layer
    kinds. A plain GQA configuration (Mistral, OLMoE) takes the lines a full
    layer of Mellum2 takes, and ``n_kv`` / ``head_dim`` are said to both
    predicates whatever the cache's form: each reads them only where the
    shape leaves the question open (a flat cache's heads; 64-wide heads, which
    the flash kernel serves in pairs of an even ``n_kv``).

    The flash way is ONE sequence's (``batch == 1``: a prefill chunk) and what
    ``latent_flash.kernel_serves`` asks: a TPU, bf16 / float32, the chunk and
    the keys in whole tiles, heads of whole lanes. No shape that passes is
    kept back. A layer's call alone on a v5e, 4096 table keys, ms at a context
    of 0 / 1024 / 2048 / 3072, materialised -> gather + kernel (PERF.md,
    PR 51): 32 heads over 8 of 128, 1024 queries 1.23 -> 0.22 / 0.34 / 0.45 /
    0.56, 256 queries 0.33 -> 0.10 / 0.14 / 0.17 / 0.21; 16 over 16, 1024
    queries 0.66 -> 0.18 / 0.24 / 0.29 / 0.35, 256 queries 0.160 -> 0.123 /
    0.141 / 0.159 / 0.177: the one point that loses (by a tenth, past half the
    table; 0.08 ms of it the gather of 16 KV heads at the table's width, which
    the materialised way fuses) is a CONTEXT, a traced scalar, not a shape,
    and over the table the shape gains.

    ONE KV head under 20 query heads (Jamba2; ``group`` 20: every query head
    reads key head 0's tiles) was compiled and RUN against the materialised
    way on a v5e, 8192 table keys (PERF.md, PR 52): 1024 queries 1.69 -> 0.26
    ms at a context of 0 and 1.68 -> 0.32 at 2048; 256 queries 0.39 -> 0.22
    and 0.40 -> 0.22; max|diff| / max|ref| 0.005-0.010 in bf16.

    SIX and EIGHT query heads a KV head in one program (48 heads over every
    key, 64 under a window of 512 narrower than the chunk: 2048 keys handed, in
    whole tiles, of a table of 8192) were compiled and RUN against the
    materialised way on a v5e (PERF.md, PR 56), ms at a context of 0 / 1024 /
    3072: 48 heads, 1024 queries 3.70 -> 0.46 / 0.63 / 0.98, 256 queries 1.00
    -> 0.29 / 0.31 / 0.43; 64 heads under the window, 1024 queries 5.11 -> 0.50
    / 0.74 / 0.74, 256 queries 1.28 -> 0.29 / 0.30 / 0.29; max|diff| / max|ref|
    0.005-0.020 in bf16.

    THIRTY KV heads under one query head each (Olmo-Hybrid-7B: ``group`` 1, the
    cache stored flat ``[.., bs * 30, 128]``; the decode kernel's numbers are
    in ``ops/paged_attention.py::kernel_serves``) were compiled and RUN against
    the materialised way on a v5e (PERF.md, PR 64), 4096 table keys, ms at a
    context of 0 / 1024 / 2048: 1024 queries 1.35 -> 0.44 / 0.57 / 0.69, 256
    queries 0.52 -> 0.34 / 0.39 / 0.42; max|diff| / max|ref| 0.004-0.011 in
    bf16."""
    if paged_attn.kernel_serves(window, q_heads, k_cache, backend, n_kv=n_kv, head_dim=head_dim):
        return "kernel"
    keys = chunk_keys(keeps, window, table_keys, block_size(k_cache, n_kv, head_dim))
    if batch == 1 and latent_flash.kernel_serves(
        window, keys, head_dim, head_dim, 0, k_cache.dtype, backend, kv_heads=n_kv
    ):
        return "flash"
    return "gather"


def program_path(
    window: int, k_cache, max_seq_len: int, q_heads, *, n_kv: int, head_dim: int, backend=None
) -> tuple:
    """What a model says to the host of its PROGRAMS of a query window (a
    prefill chunk's bucket, 1 for decode, a verify bucket), as
    :func:`attention` chooses in them: ``(way, reads, key_tile)``. ``way``:
    that of one sequence's window in a layer that keeps all under the table of
    ``max_seq_len`` positions, with ``q_heads`` the query heads of every kind
    of layer the model has (where the kinds differ, the way furthest from the
    kernel); ``reads``: what a launch then reads of the cache
    (``AttentionPath.reads``); ``key_tile``: ``Model.key_tile``, the key tile
    of the chunk's flash kernel, 1 where the chunk is not its to serve. A
    model's ``attention_path`` is its own prefix and suffix around ``way``."""
    keys = table_keys(max_seq_len, block_size(k_cache, n_kv, head_dim))
    ways = {
        way(window, 1, heads, k_cache, keys, n_kv=n_kv, head_dim=head_dim, backend=backend)
        for heads in q_heads
    }
    how = max(ways, key=tuple(_READS).index)
    return how, _READS[how], latent_flash.tiles(window, keys)[1] if how == "flash" else 1


def attention(
    q, k_cache, v_cache, layer: int, block_tables, pos, valid=None, *, n_kv: int, head_dim: int,
    keeps: int = 0,
):
    """Causal attention of ``q [B, C, H, hd]`` (rope applied) over the
    cached context of its slot through ``block_tables [B, M]``, so K/V of
    the step's own tokens must be in the cache already. Query ``(b, c)`` at
    global position ``pos[b, c]`` sees key position ``j`` of its slot iff
    ``j <= pos[b, c]`` and, in a layer that keeps a window, ``j > pos[b, c] -
    keeps``. Returns ``[B, C, H, hd]``. GQA stays grouped ``[n_kv, rep]``;
    scores, mask and softmax are float32. ``k_cache`` / ``v_cache``: the
    arrays of the layer's group, ``layer`` its index among them; ``valid [B,
    C]`` the real rows (the flash way counts them).

    The ONE place a serving step reads a K/V cache for attention, the
    :func:`way` the shapes decide."""
    B, C = pos.shape
    M = block_tables.shape[1]
    bs = block_size(k_cache, n_kv, head_dim)
    hd = head_dim
    how = way(C, B, q.shape[2], k_cache, M * bs, n_kv=n_kv, head_dim=hd, keeps=keeps)
    if how == "kernel":
        return paged_attn.paged_attention(q, k_cache, v_cache, layer, block_tables, pos, n_kv=n_kv, keeps=keeps)
    if how == "flash":
        keys = chunk_keys(keeps, C, M * bs, bs)
        ctx_len = pos[0, 0]
        table, first = block_tables[0], 0
        if keeps:
            # from the block that holds the first key the chunk's first query
            # sees: the chunk's offset in what it is handed is its context
            first = jnp.maximum(ctx_len - keeps + 1, 0) // bs
            table = jax.lax.dynamic_slice(jnp.pad(table, (0, keys // bs)), (first,), (keys // bs,))
        with jax.named_scope("attn.gather"):
            ks = k_cache[layer, table].reshape(keys, n_kv, hd).transpose(1, 0, 2)
            vs = v_cache[layer, table].reshape(keys, n_kv, hd).transpose(1, 0, 2)
        o = latent_flash.flash_attention(
            q[0].transpose(1, 0, 2), ks, vs, ctx_len - first * bs, valid[0].sum(dtype=jnp.int32),
            scale=1.0 / math.sqrt(hd), group=q.shape[2] // n_kv, window=keeps or None,
        )
        return o.transpose(1, 0, 2)[None]
    return attend_gathered(q, k_cache, v_cache, layer, block_tables, pos, n_kv, M * bs, keeps)


def attention_counted(
    q, k_cache, v_cache, layer: int, block_tables, pos, true_lens, *, n_kv: int, head_dim: int
):
    """:func:`attention` in a layer that keeps all, told each window's real
    rows counted (``true_lens [B]``): ``models/lfm2.py``'s and ``jamba.py``'s.

    A KEPT COPY of the flash way's operands (CHANGES.md, PR 61; ROADMAP D12):
    :func:`attention`'s, traced in ANOTHER ORDER (``pos[0, 0]`` after the
    gather and the query's transpose, the table's row twice, no ``ctx_len -
    0``, ``true_lens[0]`` for ``valid[0].sum()``), the order those two modules'
    copies of the chooser had and their prefill programs' lowered text is held
    to. Nothing a compiler keeps apart: it goes, the callers handing ``valid``
    to :func:`attention`, in a PR with pairs in ``conv-reason-offline`` and
    ``ssm-reason-offline``. Every other way IS :func:`attention`."""
    B, C = pos.shape
    hd = head_dim
    keys = block_tables.shape[1] * block_size(k_cache, n_kv, hd)
    if way(C, B, q.shape[2], k_cache, keys, n_kv=n_kv, head_dim=hd) != "flash":
        return attention(q, k_cache, v_cache, layer, block_tables, pos, n_kv=n_kv, head_dim=hd)
    with jax.named_scope("attn.gather"):
        ks = k_cache[layer, block_tables[0]].reshape(keys, n_kv, hd).transpose(1, 0, 2)
        vs = v_cache[layer, block_tables[0]].reshape(keys, n_kv, hd).transpose(1, 0, 2)
    o = latent_flash.flash_attention(
        q[0].transpose(1, 0, 2), ks, vs, pos[0, 0], true_lens[0],
        scale=1.0 / math.sqrt(hd), group=q.shape[2] // n_kv,
    )
    return o.transpose(1, 0, 2)[None]


def attend_gathered(q, k_cache, v_cache, layer: int, block_tables, pos, n_kv: int, keys: int, window: int = 0):
    """The ``"gather"`` way: ``cache[layer, block_tables]`` gathered for every
    slot as wide as the table (``keys`` positions of ``n_kv`` heads, whatever
    form a block is stored in) and the softmax materialised, GQA grouped,
    float32."""
    B, C, H, hd = q.shape
    ks = k_cache[layer, block_tables].reshape(B, keys, n_kv, -1)
    vs = v_cache[layer, block_tables].reshape(B, keys, n_kv, -1)
    qg = q.reshape(B, C, n_kv, H // n_kv, -1)
    s = jnp.einsum("bcgrh,bsgh->bcgrs", qg, ks).astype(jnp.float32)
    s = s * (1.0 / math.sqrt(hd))
    key_pos = jnp.arange(keys, dtype=jnp.int32)
    mask = key_pos <= pos[:, :, None]  # [B, C, keys]
    if window:
        mask &= key_pos > pos[:, :, None] - window
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bcgrs,bsgh->bcgrh", pattn.astype(vs.dtype), vs)
    return o.reshape(B, C, H, -1)
