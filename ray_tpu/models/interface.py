"""What the runtime knows of a model: the smallest interface between the
serving engine (and the train programs) and a model module, and the
description of the paged cache a model owns.

Four small records and one dict, no plugin system (the fourth,
:class:`Drafter`, is what a model with a drafter of its own says of it):

* :class:`CacheLayout` says what ONE token leaves in the paged cache of
  each layer: named arrays ``[n_layers, num_blocks, block_size, *row]``
  (``k`` and ``v`` rows ``[n_kv, hd]`` for a KV cache; one ``latent`` row
  of ``kv_lora_rank + rope`` numbers for a latent cache, a block's rows
  laid flat in whole tiles: ``flat_blocks``). A block id names
  ``block_size`` positions of a sequence in every layer of ONE layer group
  (:class:`LayerGroup`) and every array of it. A model whose layers all keep
  a sequence whole has one group, and a block id then covers every layer, as
  it always did; a model with layer KINDS (window layers beside full ones)
  names a group a kind, each with its own arrays, its own ``num_blocks`` and
  its own block table a request. The device tensors, the COW copy, the
  export / import / tier payload (:func:`gather_paged_blocks` /
  :func:`scatter_paged_blocks`: the arrays stacked,
  ``[n_arrays, n_layers, P, *block]``) and the pool's byte
  arithmetic all read this one description.
* :class:`StateLayout` says what one SEQUENCE leaves, whatever its length,
  in the layers that recur instead of attending (a gated delta-rule layer's
  matrix state a head and the last inputs of its short convolutions):
  named arrays ``[n_layers, num_slots, *shape]``, a slot a running request,
  slot 0 the null slot as block 0 is the null block. A model whose layers
  all attend has none (``Model.state_layout`` is None), and for a model
  that has one ``CacheLayout.n_layers`` counts the layers that WRITE rows,
  not the model's depth.
* :class:`Model` is what a model module registers: config -> params, the
  cache description, the three paged entry points, ``forward`` and the
  logical axes, plus the one thing the runner asks about a step's attention
  (:class:`AttentionPath`: which path a window takes and what it reads).
* :func:`model_of` finds a config object's model by the config's type.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple


@dataclass(frozen=True)
class LayerGroup:
    """The layers of a model that keep the SAME part of a sequence in the
    paged cache, and so share a pool of blocks and a block table."""

    #: the group's name: the key of its pool in ``engine_stats()["kv_pools"]``
    #: and the suffix of its arrays' names (:meth:`CacheLayout.array_name`)
    name: str
    #: the model's layers that write into the group's arrays, in order: layer
    #: ``layers[i]`` is index ``i`` of them
    layers: Tuple[int, ...]
    #: how much of a sequence the group keeps: 0 = every position, ``W`` = the
    #: last ``W`` (query ``i`` sees key ``j`` iff ``i - W < j <= i``): blocks
    #: wholly behind that are given back while the sequence runs
    keeps: int = 0


@dataclass(frozen=True)
class CacheLayout:
    #: ``"kv"`` (per-head keys and values) or ``"latent"`` (one compressed
    #: row a token, K and V at once)
    kind: str
    n_layers: int
    block_size: int
    #: name -> the shape of one token's row in one layer, in payload order
    arrays: Tuple[Tuple[str, Tuple[int, ...]], ...]
    dtype: Any
    #: whether a block's rows are stored FLAT, ``T`` tokens a stored row
    #: (``[n_layers, num_blocks, block_size / T, T * row]``), instead of
    #: ``[n_layers, num_blocks, block_size, *row]``: for a row that is not
    #: whole lanes of 128 (a latent row of 576), the tiled device layout of
    #: the latter pads every row and XLA re-lays the whole cache out to gather
    #: from it. ``T`` is the fewest tokens that fill whole lanes (two rows of
    #: 576 are 1152 = 9 x 128): a block of 16 is then ``[8, 1152]``, nine
    #: whole ``(8, 128)`` tiles, contiguous on the device and ONE DMA of a
    #: kernel (``ops/latent_paged.py``; as one row ``[9216]`` of ``[blocks,
    #: 9216]``, the form until PR 36, a block was 72 pieces of 256 B and no
    #: slice Mosaic would copy). Where ``block_size / T`` is no multiple of 8
    #: (the tests' toy widths) the block stays ONE row of ``block_size x row``
    flat_blocks: bool = False
    #: the layer groups, the one that keeps a sequence whole FIRST (its pool is
    #: the block manager's own, with prefix reuse and the payloads; a group that
    #: keeps the last ``W`` positions is a pool beside it). A layout that names
    #: none has ONE, ``all``: every one of its ``n_layers`` layers, everything
    #: kept, the arrays under their plain names. ``n_layers`` is the groups' sum
    groups: Tuple[LayerGroup, ...] = ()

    def __post_init__(self):
        if not self.groups:
            object.__setattr__(self, "groups", (LayerGroup("all", tuple(range(self.n_layers))),))

    def array_name(self, name: str, group: int) -> str:
        """The key of array ``name`` of group ``group`` in the device cache:
        the plain name for the first group, ``name.group`` for the others."""
        return name if group == 0 else f"{name}.{self.groups[group].name}"

    @property
    def one_payload(self) -> bool:
        """Whether the arrays share ONE row shape, so that a block's export /
        import / tier payload is one stacked array."""
        return len({shape for _, shape in self.arrays}) == 1

    @property
    def row_shape(self) -> Tuple[int, ...]:
        """The one row shape all arrays share (what lets the payload be one
        stacked array)."""
        shapes = {shape for _, shape in self.arrays}
        if len(shapes) != 1:
            raise ValueError(f"arrays of different rows cannot share a payload: {self.arrays}")
        return next(iter(shapes))

    @property
    def row_width(self) -> int:
        """Numbers a token leaves in one layer, all arrays together."""
        return sum(math.prod(shape) for _, shape in self.arrays)

    @property
    def dtype_name(self) -> str:
        """The dtype as a transfer descriptor spells it (``"bfloat16"``)."""
        import numpy as np

        return str(np.dtype(self.dtype))

    @property
    def bytes_per_token(self) -> int:
        import numpy as np

        return self.n_layers * self.row_width * np.dtype(self.dtype).itemsize

    @property
    def block_bytes(self) -> int:
        return self.block_size * self.bytes_per_token

    def block_shape(self, row: Tuple[int, ...]) -> Tuple[int, ...]:
        """The shape of one block of one layer in a device array of that row."""
        if self.flat_blocks and len(row) == 2 and row[1] % 128 == 0:
            # K/V heads of whole lanes, too few to fill a tile's sublanes by
            # themselves: the heads join the tokens, ``[bs * n_kv, hd]``,
            # token-major, whole ``(16, 128)`` tiles and nothing padded (as
            # ``[bs, 4, hd]`` the device pads every token's 4 heads to a tile)
            return (self.block_size * row[0], row[1])
        if self.flat_blocks:
            width = math.prod(row)
            t = math.lcm(width, 128) // width
            if self.block_size % (8 * t) == 0:
                return (self.block_size // t, t * width)
            return (self.block_size * width,)
        return (self.block_size, *row)

    def init(self, num_blocks) -> Dict[str, Any]:
        """The device-side cache: zeros, block 0 of every group reserved as
        its null block. ``num_blocks``: the blocks of each group, in the groups'
        order (an int: of the one group)."""
        import jax.numpy as jnp

        sizes = num_blocks if isinstance(num_blocks, (tuple, list)) else (num_blocks,)
        return {
            self.array_name(name, g): jnp.zeros(
                (len(group.layers), n, *self.block_shape(row)), self.dtype
            )
            for g, (group, n) in enumerate(zip(self.groups, sizes, strict=True))
            for name, row in self.arrays
        }

    def payload_shape(self, n_blocks: Optional[int]) -> Tuple[Optional[int], ...]:
        """Shape of the export / import / tier payload of ``n_blocks`` blocks."""
        return (len(self.arrays), self.n_layers, n_blocks, *self.block_shape(self.row_shape))

    def describe(self) -> Dict[str, Any]:
        """What ``engine_stats()["kv_layout"]`` says."""
        import numpy as np

        said = {
            "kind": self.kind,
            "row_width": self.row_width,
            "bytes_per_token": self.bytes_per_token,
        }
        if not self.one_payload:
            # rows of different widths side by side (a latent row and an
            # indexer's key): each array's share of a token's bytes
            item = np.dtype(self.dtype).itemsize
            said["arrays"] = {
                name: {"row_width": math.prod(shape), "bytes_per_token": self.n_layers * math.prod(shape) * item}
                for name, shape in self.arrays
            }
        if len(self.groups) > 1:  # one group: what bytes_per_token says
            row_bytes = self.row_width * np.dtype(self.dtype).itemsize
            said["groups"] = {
                g.name: {"layers": len(g.layers), "keeps": g.keeps or "all",
                         "bytes_per_token": len(g.layers) * row_bytes}
                for g in self.groups
            }
        return said


@dataclass(frozen=True)
class StateLayout:
    """What one sequence holds in the recurrent layers of a model, beside
    its rows in the paged cache: fixed-size arrays a sequence a layer."""

    #: the recurrence's name (``"kda"``: the gated delta rule a channel; ``"gdn"``: a head)
    kind: str
    #: the layers that recur (each holds every array below)
    n_layers: int
    #: name -> (the shape of one sequence's array in one layer, its dtype)
    arrays: Tuple[Tuple[str, Tuple[int, ...], Any], ...]

    @property
    def bytes_per_seq(self) -> int:
        import numpy as np

        return self.n_layers * sum(
            math.prod(shape) * np.dtype(dtype).itemsize for _, shape, dtype in self.arrays
        )

    @property
    def stored_bytes_per_seq(self) -> int:
        """What the pool's layout really HOLDS a sequence on a device that
        stores an array's last two dimensions in tiles of 8 sublanes (of 32 bits:
        16 rows of bfloat16) x 128 lanes: :attr:`bytes_per_seq` where nothing is
        padded. An array of one dimension a sequence lies with the slots on the
        sublanes, so only its lanes pad (a state of ``[H, 96, 192]`` is stored as
        ``[H, 96, 256]``, a third more; the same heads joined along the lanes,
        ``[96, H x 192]``, as they are)."""
        import numpy as np

        total = 0
        for _, shape, dtype in self.arrays:
            item = np.dtype(dtype).itemsize
            lanes = -(-shape[-1] // 128) * 128
            if len(shape) == 1:
                rows = 1
            else:
                sublanes = 8 * 4 // item
                rows = math.prod(shape[:-2]) * (-(-shape[-2] // sublanes) * sublanes)
            total += rows * lanes * item
        return self.n_layers * total

    def init(self, num_slots: int) -> Dict[str, Any]:
        """The device-side pool: zeros, slot 0 reserved as the null slot."""
        import jax.numpy as jnp

        return {
            name: jnp.zeros((self.n_layers, num_slots, *shape), dtype)
            for name, shape, dtype in self.arrays
        }

    def describe(self) -> Dict[str, Any]:
        """What ``engine_stats()["state_layout"]`` says."""
        return {"kind": self.kind, "layers": self.n_layers, "bytes_per_seq": self.bytes_per_seq}


def _rows_of(a, blocks):
    """An array of the cache seen as ``[layers x blocks, *block]`` (a free
    reshape) and the rows of ``blocks`` ([P] int32) in every layer, ``[L *
    P]``: gathers and scatters of whole ROWS of that view are in place and
    copy nothing, whatever a block's shape (indexed ``a[:, blocks]``, a
    flat-block latent cache was copied whole, 3 GB of temporaries: the
    compile-only probe of PR 31; a K/V cache was not, either way)."""
    import jax.numpy as jnp

    L, N = a.shape[:2]
    rows = (jnp.arange(L, dtype=jnp.int32)[:, None] * N + blocks[None]).reshape(-1)
    return a.reshape(L * N, *a.shape[2:]), rows


def copy_paged_blocks(cache, src, dst):
    """Duplicate whole cache blocks device-side (prefix-cache COW):
    ``src``/``dst`` are [P] int32 block ids; every layer's rows at ``dst``
    become copies of ``src``, in every array. Padding pairs point both ids
    at the null block (0): writing the null block's own trash back onto
    itself keeps the shape static and the content inert."""
    out = {}
    for name, a in cache.items():
        flat, from_rows = _rows_of(a, src)
        _, to_rows = _rows_of(a, dst)
        out[name] = flat.at[to_rows].set(flat[from_rows]).reshape(a.shape)
    return out


def gather_paged_blocks(cache, blocks):
    """Pull whole cache blocks off the device (KV-cache migration export):
    ``blocks`` is [P] int32 block ids (padded with 0 = null); returns the
    arrays stacked in the layout's order, ``[n_arrays, n_layers, P,
    *block]`` (``CacheLayout.payload_shape``): the contiguous host window
    the transfer path ships replica to replica. Padding rows carry
    null-block trash the caller slices off host-side."""
    import jax.numpy as jnp

    out = []
    for a in cache.values():
        flat, rows = _rows_of(a, blocks)
        out.append(flat[rows].reshape(a.shape[0], blocks.shape[0], *a.shape[2:]))
    return jnp.stack(out)


def scatter_paged_blocks(cache, blocks, payload):
    """Write migrated blocks into the device cache (the import side):
    ``payload`` is the :func:`gather_paged_blocks` layout. Padding entries
    point at the null block: duplicate index-0 writes land trash on trash,
    keeping the compiled shape static and the content inert."""
    out = {}
    for i, (name, a) in enumerate(cache.items()):
        flat, rows = _rows_of(a, blocks)
        out[name] = flat.at[rows].set(payload[i].reshape(-1, *a.shape[2:])).reshape(a.shape)
    return out


def lm_head(params, x, eps: float, tied: bool):
    """``x [..., D]`` -> float32 logits ``[..., vocab]`` through the final norm
    and the head: ``params["lm_head"] [D, vocab]``, or, ``tied``, the embedding
    read again (``params["embed"] [vocab, D]``)."""
    import jax.numpy as jnp

    from ray_tpu.ops.layers import rms_norm

    x = rms_norm(x, params["final_norm"], eps)
    if tied:
        return jnp.einsum("...d,vd->...v", x, params["embed"]).astype(jnp.float32)
    return jnp.einsum("...d,dv->...v", x, params["lm_head"]).astype(jnp.float32)


def stack_aux(aux):
    """The expert layers' counters (a dict a layer), stacked over the layers."""
    import jax.numpy as jnp

    return {k: jnp.stack([a[k] for a in aux]) for k in aux[0]} if aux else {}


def step_counters(aux):
    """Of the counters of a step's expert layers (:func:`stack_aux`) those the
    runner reads (the group limit's where there is one)."""
    keys = ("load", "bias_changed", "group_changed", "routed_rows")
    return {k: aux[k] for k in keys if k in aux}


def step_outputs(cache, logits, counters=None, state=None):
    """What a paged step returns (:class:`Model`): ``(cache, logits)``, with
    the state pool after the cache where the model has one, and last, where
    experts were routed (``counters`` neither None nor empty), what the runner
    reads with the logits: the expert loads ``[n_layers, E]`` int32 of the
    step's valid rows, or a dict of ``load`` and further counters a layer
    (:func:`step_counters`)."""
    out = (cache, logits) if state is None else (cache, state, logits)
    return out if counters is None or len(counters) == 0 else (*out, counters)


@dataclass(frozen=True)
class Drafter:
    """A model's OWN drafter for speculative decoding (a multi-token-prediction
    module kept after training), run by the target's programs over the
    target's cache: ``EngineConfig.speculative_draft`` ``"mtp"``."""

    #: the drafter's name, for ``engine_stats()`` and the launch span's path
    kind: str
    #: positions of a step's verify window: the committed last token and the drafts
    window: int
    #: rows a token it writes to the paged cache BESIDE the model's own
    #: (they are the last ``cache_layers`` of ``CacheLayout.n_layers``)
    cache_layers: int
    #: the ONE program of an all-greedy step, ``(cfg, params, cache, tokens [B,
    #: window], block_tables, ctx_lens, true_lens, known) -> (cache, (new [B,
    #: window], accepted [B], draft [B]), counters)``: verify, the picks, the
    #: comparison, the drafter over what was committed, the next drafts
    step: Callable
    #: the same step as two programs around the host's sampler: ``verify(cfg,
    #: params, cache, tokens, block_tables, ctx_lens, true_lens) -> (cache,
    #: logits [B, window, V], hidden, counters)``, then ``draft(cfg, params,
    #: cache, hidden, next_tokens, block_tables, ctx_lens, true_lens) -> (cache,
    #: logits [B, V], counters)``
    verify: Callable
    draft: Callable


class AttentionPath(NamedTuple):
    """How a program of one query window attends over the paged cache."""

    #: the model's name for the path, for the runner's launch span
    name: str
    #: what a launch reads of the cache: ``"table"`` (the table as wide as
    #: it is handed over, for every slot of the batch bucket), ``"slots"``
    #: (as wide, for the real slots alone: a padding slot reads nothing),
    #: ``"blocks"`` (each real slot's own live blocks, whatever the table's
    #: width: a kernel) or ``"live"`` (a prefill chunk: the positions up to
    #: the end of the chunk in whole key tiles, ``Model.key_tile``, whatever
    #: the table's width: a kernel)
    reads: str


@dataclass(frozen=True)
class Model:
    """What a model module registers (``MODEL`` at its end)."""

    #: the family's name, for logs and ``engine_stats()``
    name: str
    init_params: Callable        # (cfg, rng) -> params
    forward: Callable            # (cfg, params, tokens [B, S], **kw) -> logits [B, S, V] float32
    logical_axes: Callable       # (cfg) -> pytree of logical axis names, as params
    param_count: Callable        # (cfg) -> int
    cache_layout: Callable       # (cfg, block_size, dtype=None) -> CacheLayout
    #: the three paged entry points, ``(cfg, params, cache, ...)`` as
    #: ``models/llama.py`` documents them; each returns ``(cache, logits)``
    #: and, where experts are routed, a third output: the expert loads
    #: ``[n_expert_layers, E]`` int32, or a dict with ``load`` and further
    #: per-layer counters
    paged_prefill_step: Callable
    paged_verify_step: Callable
    paged_decode_step: Callable
    #: (cfg, window, cache) -> the :class:`AttentionPath` a program of that
    #: query window (a prefill chunk's bucket, 1 for decode, a verify bucket)
    #: takes: fixed a program, so the runner asks once a window
    attention_path: Callable
    #: (cfg) -> ``None`` for a model without routed experts, else
    #: ``(lo, hi)``: the range of experts this process holds
    held_experts: Callable
    #: (cfg, layers) -> of the ``layers`` expert layers a paged step ran, how
    #: many read their three expert matrices IN PLACE in the stack of a
    #: scanned group (``ops/moe.py::grouped_matmul`` told a ``layer``): what
    #: the program was traced with, said on the host, no device output. 0
    #: where every expert layer's matrices are operands of their own (layers
    #: unrolled, or a Python loop over them)
    experts_in_place: Callable = lambda cfg, layers: 0
    #: (cfg, window, cache) -> positions a key tile of a program whose
    #: ``attention_path`` reads ``"live"``
    key_tile: Callable = lambda cfg, window, cache: 1
    #: (cfg, window, cache) -> the widths, in key positions and ascending, a
    #: prefill chunk of ``window`` queries GATHERS its context at (a latent
    #: model: and expands K and V at): a chunk takes the first that holds its
    #: last real query, whatever its ``attention_path`` reads. Empty: the
    #: table whole, whatever the context
    gather_rungs: Callable = lambda cfg, window, cache: ()
    #: (cfg) -> 0 for a model whose attention sees every live position, else
    #: the positions a query's attention SELECTS among them at most (a learned
    #: sparse selection: ``index_topk``): the runner counts, from its own
    #: lengths, how much of the live context the launches' queries chose
    selection: Callable = lambda cfg: 0
    #: ``None`` for a model whose layers all attend, else (cfg) -> the
    #: :class:`StateLayout` of its recurrent layers. Such a model's paged
    #: entry points take the state arrays after the cache (both donated) and
    #: the slot index (prefill: a scalar; decode: ``[B]``, padding on the
    #: null slot) as their last argument, and return ``(cache, state, logits
    #: [, counters])``; a sequence's state reads as zeros where its context
    #: starts (``ctx_len == 0``), whatever the slot held
    state_layout: Optional[Callable] = None
    #: ``None`` for a model without a drafter of its own, else (cfg) -> its
    #: :class:`Drafter` (or None where the configuration keeps none). Such a
    #: model's ``paged_prefill_step`` takes one more argument last, the token
    #: that follows the chunk (-1: none yet), and then runs the drafter over
    #: the chunk too; ``cache_layout`` counts the drafter's rows
    drafter: Optional[Callable] = None


def model_of(cfg) -> Model:
    """The model a config object belongs to, by the config's type."""
    from ray_tpu.models import deepseek_v3, glm_dsa, jamba, kimi_linear, lfm2, llama, olmo_hybrid, xing4

    models = {
        llama.LlamaConfig: llama.MODEL, xing4.Xing4Config: xing4.MODEL,
        kimi_linear.KimiLinearConfig: kimi_linear.MODEL,
        deepseek_v3.DeepseekV3Config: deepseek_v3.MODEL, lfm2.Lfm2Config: lfm2.MODEL,
        jamba.JambaConfig: jamba.MODEL, glm_dsa.GlmDsaConfig: glm_dsa.MODEL,
        olmo_hybrid.OlmoHybridConfig: olmo_hybrid.MODEL,
    }
    try:
        return models[type(cfg)]
    except KeyError:
        raise TypeError(
            f"no model is registered for a {type(cfg).__name__} "
            f"(known: {sorted(t.__name__ for t in models)})"
        ) from None
