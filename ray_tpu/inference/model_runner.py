"""Bucketed jitted prefill/decode steps over the paged KV cache.

Fixed shapes are the whole game on TPU: XLA compiles one program per
input shape, so the runner rounds every prefill chunk up to a length
bucket and every decode batch up to a size bucket. After warmup the
engine must see ZERO recompiles — the jit cache holds exactly one entry
per bucket, asserted via ``recompiles_after_warmup()`` (backed by
``PjitFunction._cache_size``).

The model is the runner's only through ``models/interface.py``: ``model_of(
cfg)`` gives the three paged entry points it jits, the cache description
(``CacheLayout``: what it allocates, copies, exports and imports) and the
attention path each query window takes (``Model.attention_path``, asked
once a window: ``attention_paths``).

A decode or verify launch is handed the table at ONE width,
``max_blocks_per_seq``, on every backend: ONE program a batch bucket (and a
verify window), so a batch whose contexts grow or shrink finds its program
compiled. How much of the cache the program then reads is the attention's
business alone (``AttentionPath.reads``): a Pallas kernel reads each slot's own
live blocks and no other (``blocks``: a TPU, whole tiles; ``ops/
paged_attention.py::kernel_serves`` and ``models/latent.py::paged_serves`` say
when, from shapes and the backend), the fallback gathers the table as wide as
it is (``table``; ``slots`` where a padding slot reads nothing).
``decode_width`` counts what was handed over and what the launched program
reads of the cache, either way, and for a K/V cache the key positions it
MULTIPLIES: the kernel's waves are whole (``paged_attention.blocks_a_wave``
blocks each), so a slot's last wave multiplies columns it did not fetch.

A prefill chunk is always handed the full-width row. Wherever the flash
kernel (``ops/latent_flash.py``) does not serve (the CPU, a chunk that is no
whole tile, odd head widths) a chunk of ``models/llama.py`` or ``models/
xing4.py`` attends over all of it (``table``); where it does (a TPU, whole
tiles: every model's own predicate, from shapes) the chunk attends over the key
tiles up to its own end alone (``live``). What it GATHERS in front of that is
asked apart (``Model.gather_rungs``): a latent model's chunk gathers, overlays
and expands K and V over the whole key tiles up to its last real query and no
further, on either path (``models/latent.py::key_rungs``: one program, the
rung chosen on the device); ``models/llama.py``'s, ``lfm2.py``'s and
``jamba.py``'s still gather the table whole. ``prefill_width`` counts the
positions up to each chunk's end, the key positions its attention reads and
those its program gathers and expands, for every model.

A MoE config's steps return a third output, the expert loads
``[n_layers, E]`` of the launch's real rows (or a dict with them under
``load`` beside further counters a layer). It is copied to the host with
the logits, inside ``readback`` (the device has finished by then: no second
sync), and summed into ``moe`` (:meth:`_account_moe`). A dense config's
steps have two outputs and no such account.

A model with recurrent layers (``Model.state_layout``: a per-sequence state
beside the rows a token) has a second pool on the device, ``state``: arrays
``[layers, slots, ...]``, slot 0 the null slot. Its entry points take it
after the cache, both donated, and the slot index of each sequence last
(``prefill_chunk(slot=...)``, ``decode(slots=...)``: the engine hands over
what the block manager assigned); the other models' calls are as they were.

Every step is two halves: a LAUNCH (``launch_prefill``, ``launch_decode``:
inputs, the ``jit`` call, a :class:`Launched` back at once) and a READ
(:meth:`PagedModelRunner.read`: the wait, then the copy); ``prefill_chunk``
and ``decode`` are both at once. A decode launch may name, for any row, a row
of the picks of the decode launch before it in place of a token the host has
not read yet (:func:`decode_program`), and a drafter's ONE-program step a row
of the step before it in place of a window (:func:`mtp_programs`): the engine's
loop launches step n + 1 from step n's result on the device while n runs.

The device cache lives here as functional state: every step donates the
cache buffer (``donate_argnums``) and returns the new value, and the
runner swaps its reference. Donation is unconditional — the CPU backend
honours it too, so the tests run the same use-after-donate rules as the
chip: a reference to ``runner.cache`` taken before a step is dead after
it.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.models import paged_kv
from ray_tpu.models.interface import (
    AttentionPath,
    copy_paged_blocks,
    gather_paged_blocks,
    model_of,
    scatter_paged_blocks,
)
from ray_tpu.observability import timeline
from ray_tpu.ops import paged_attention

logger = logging.getLogger(__name__)

#: block-copy pairs per compiled COW program (pairs pad with null->null)
_COW_WIDTH = 4

#: a ``block_until_ready()`` that takes less found its array FINISHED: the
#: device stood waiting for the host (:meth:`PagedModelRunner.read`). On a
#: v5e a finished array's call takes 4-10 us (p95 14, the longest of 1,350
#: 38.5; beside a running program the same) and an unfinished one's no less
#: than the completion's way back to the thread, 270 us and more (PERF.md
#: section 5, PR 54); under a busy GIL the threshold errs towards "not ready"
_READY_S = 50e-6
#: blocks per compiled KV gather/scatter program (KV-cache migration);
#: short chunks pad with the null block so the shape never varies
_KV_IO_WIDTH = 8


def _round_up_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds largest bucket {buckets[-1]}")


def decode_program(step, cfg, pools: int, widest: int):
    """The ONE decode program a batch bucket: the model's ``step`` with the
    argmax of its logits beside them (``picks``, int32, the first largest as
    ``np.argmax``): a batch whose requests are all greedy reads back the picks
    and leaves the logits on the device (42 MB a step at 64 slots of a 163,840
    vocabulary), any other reads the logits. Named after the step: the trace
    tells programs apart by their launch's name.

    A row's token may be one the host has not seen: ``-1 - j`` names row ``j``
    of the picks of the decode launch before this one (the LAST argument, a
    device array already: a launch makes no further put), merged here and not
    in the three models. The picks go out ``widest`` wide, the largest batch
    bucket, so that any bucket's program takes any other's and there is still
    one program a bucket. ``pools``: how many arguments after the parameters
    are the donated pools, which are also the first outputs."""
    import jax.numpy as jnp

    def paged_decode_step(*args):
        *args, earlier = args
        tokens = args[pools + 1]  # after the parameters and the pools
        args[pools + 1] = jnp.where(
            tokens < 0, earlier[jnp.clip(-1 - tokens, 0, widest - 1)], tokens
        )
        out = step(cfg, *args)
        picks = jnp.argmax(out[pools], axis=-1).astype(jnp.int32)
        picks = jnp.pad(picks, (0, widest - picks.shape[0]))
        return (*out[:pools], (out[pools], picks), *out[pools + 1 :])

    return paged_decode_step


def mtp_programs(drafter, cfg, widest: int):
    """The three programs of a model's own drafter (``models/interface.py::
    Drafter``), each named for the trace: the ONE program of an all-greedy
    step, its three small results packed into one int32 array ``[widest,
    window + 2]`` (the new tokens, how many drafts were accepted, the next
    draft: one copy to the host), and the two of a step with the host's
    sampler between (the second hands back the next drafts, its argmax, beside
    its logits, which stay on the device unless asked for).

    The ONE program takes its window from the step before it, as
    :func:`decode_program` takes a token from the picks: a row whose FIRST
    token is ``-1 - j`` names row ``j`` of the packed result of the drafter
    step before this one (the LAST argument, a device array already) and
    stands for ``[new_j[accepted_j], draft_j]``, the last token that step
    committed and the draft it handed back, ``accepted_j`` positions after the
    context length the host gave (the host gives the least: a step commits at
    least one token). Merged here and not in the model: the ``Drafter``
    interface knows nothing of it. The result goes out ``widest`` rows, the
    largest batch bucket, so that any bucket's program takes any other's."""
    import jax.numpy as jnp

    C = drafter.window

    def paged_mtp_step(params, cache, tokens, tables, ctx_lens, true_lens, known, earlier):
        named = tokens[:, 0] < 0
        before = earlier[jnp.clip(-1 - tokens[:, 0], 0, widest - 1)]  # [B, window + 2]
        accepted = before[:, C]
        last = jnp.take_along_axis(before[:, :C], accepted[:, None], axis=1)[:, 0]
        rides = named & (true_lens > 1)  # a window the plan left no room to draft in is [x] alone
        tokens = tokens.at[:, 0].set(jnp.where(named, last, tokens[:, 0]))
        tokens = tokens.at[:, 1].set(jnp.where(rides, before[:, C + 1], tokens[:, 1]))
        ctx_lens = ctx_lens + jnp.where(named, accepted, 0)
        cache, (new, accepted, draft), counters = drafter.step(
            cfg, params, cache, tokens, tables, ctx_lens, true_lens, known
        )
        out = jnp.concatenate([new, accepted[:, None], draft[:, None]], axis=1)
        return cache, jnp.pad(out, ((0, widest - out.shape[0]), (0, 0))), counters

    def paged_mtp_verify(*args):
        cache, logits, hidden, counters = drafter.verify(cfg, *args)
        return cache, (logits, hidden), counters

    def paged_mtp_draft(*args):
        cache, logits, counters = drafter.draft(cfg, *args)
        return cache, (jnp.argmax(logits, axis=-1).astype(jnp.int32), logits), counters

    return paged_mtp_step, paged_mtp_verify, paged_mtp_draft


@dataclass
class Launched:
    """A paged step on its way to the device: what :meth:`PagedModelRunner.
    read` waits for and copies. It holds the step's outputs and nothing else
    of it: the pools went back into the runner at the launch."""

    #: which half of the runner's ``moe`` account its loads go to
    kind: str
    #: the device array ``read`` returns on the host: a step's logits, or of
    #: a greedy decode batch the picks (its logits are then held by nobody)
    out: Any
    #: a MoE step's expert loads, still on the device (None: a dense model)
    loads: Any = None
    #: real rows of a decode batch: ``read`` strips the padding after them
    n: Optional[int] = None
    #: a decode launch's picks (``[largest decode bucket]`` int32, on the
    #: device), or the packed result of a drafter's ONE-program step (``[largest
    #: decode bucket, window + 2]``): what the NEXT launch of its kind may take
    #: a row's token, or window, from
    picks: Any = None
    #: the first half of a drafter's two-program step: the window's last
    #: residuals, on the device, for the second half
    hidden: Any = None


class PagedModelRunner:
    def __init__(
        self,
        cfg,
        params,
        *,
        num_blocks: int,
        block_size: int,
        prefill_buckets: Sequence[int],
        decode_buckets: Sequence[int],
        verify_buckets: Sequence[int] = (),
        cache_dtype=None,
        state_slots: int = 0,
        drafter: bool = False,
    ):
        import jax
        import jax.numpy as jnp

        #: where ``launch``, ``device_wait`` and ``readback`` of a call go
        #: unless the caller hands in its own account (the engine does, for
        #: the calls of its step loop: a PhaseClock is one thread's)
        self.clock = timeline.PhaseClock("runner")
        self.cfg = cfg
        #: the model module's side of the interface (``models/interface.py``):
        #: the three paged entry points, the cache description, what a
        #: window's attention reads
        self.model = model_of(cfg)
        #: the model's OWN drafter where the engine drafts with it
        #: (``speculative_draft`` ``"mtp"``), else None: decode is then its
        #: step programs (:meth:`launch_mtp_step`) and a prefill chunk runs it
        #: over the prompt; plain decode and verify are not warmed
        self.drafter = None
        if drafter:
            self.drafter = self.model.drafter(cfg) if self.model.drafter else None
            if self.drafter is None:
                raise ValueError(f"a {self.model.name} model of this configuration has no drafter of its own")
        self.params = params
        self.block_size = block_size
        #: blocks of the pool; of each layer group's pool, in the layout's order,
        #: where the model's cache has several (``CacheLayout.groups``)
        self.num_blocks = num_blocks
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        self.decode_buckets = tuple(sorted(decode_buckets))
        #: speculative-verify window buckets (k+1 positions per step);
        #: empty unless the engine enables speculation, so plain
        #: deployments keep their exact compile_count
        self.verify_buckets = tuple(sorted(verify_buckets))
        #: fixed block-table width every request/table row pads to
        self.max_blocks_per_seq = -(-cfg.max_seq_len // block_size)
        #: what a token leaves in the cache, owned by the model: the device
        #: tensors, the block copy, the export / import / tier payload and
        #: the pool's bytes all follow it
        self.cache_layout = self.model.cache_layout(cfg, block_size, cache_dtype)
        #: the layer groups as the accounts below read them: ``(layers,
        #: positions kept (0: all))`` each, the group that keeps all first
        self._groups = tuple((len(g.layers), g.keeps) for g in self.cache_layout.groups)
        whole = num_blocks[0] if isinstance(num_blocks, (tuple, list)) else num_blocks
        if whole - 1 < self.max_blocks_per_seq:
            raise ValueError(
                f"num_blocks={whole} can't hold one max-length sequence "
                f"({self.max_blocks_per_seq} blocks + null block)"
            )
        t0 = time.perf_counter()
        self.cache = jax.block_until_ready(self.cache_layout.init(num_blocks))
        #: what a SEQUENCE leaves in the model's recurrent layers, if it has
        #: any (``None``: every layer attends), and the pool of it on the
        #: device: ``state_slots`` usable slots behind the null slot 0
        self.state_layout = self.model.state_layout(cfg) if self.model.state_layout else None
        self.state_slots = state_slots if self.state_layout is not None else 0
        self.state = None
        if self.state_layout is not None:
            if state_slots < 1:
                raise ValueError(
                    f"a {self.model.name} model keeps {self.state_layout.bytes_per_seq} B of state "
                    "a sequence: the runner needs state_slots >= 1"
                )
            self.state = jax.block_until_ready(self.state_layout.init(state_slots + 1))
        #: start-up account: seconds to allocate the cache, and per warmed
        #: program its compile (or load from the compile cache) and first run
        self.cache_alloc_s = time.perf_counter() - t0
        #: per query window (1 = decode, the verify buckets; a prefill bucket
        #: at its first launch: :meth:`_path`): the attention path its program
        #: takes, as the model names it (the launch span's ``path``), and what
        #: a decode or verify launch reads of the cache on it
        #: (:meth:`_count_width`). Fixed a program, so asked once a window
        self.attention_paths: Dict[int, AttentionPath] = {
            c: self.model.attention_path(cfg, c, self.cache) for c in (1, *self.verify_buckets)
        }
        #: running sums over decode and verify launches: the width handed
        #: over (tokens: the table's, every launch), the longest context of
        #: the batch, the contexts of the real slots, and the positions the
        #: program reads (the gather: batch bucket x table width, or real
        #: slots x table width where a padding slot reads nothing; the
        #: kernel: each real slot's live blocks)
        self.decode_width: Dict[str, int] = dict.fromkeys(
            ("launches", "width_tokens", "needed_tokens", "live_tokens", "gathered_tokens"), 0
        )
        if any(keeps for _, keeps in self._groups):
            #: of ``gathered_tokens``, what is read of the groups that keep a window (by layers)
            self.decode_width["window_read_tokens"] = 0
        #: a K/V cache: the blocks a DMA wave of ``ops/paged_attention.py`` a layer
        #: group (a window group's waves are cut to its span), and beside what a
        #: launch reads the key positions its program MULTIPLIES (the kernel: each
        #: real slot's waves, whole, the last one's unfetched columns too; the
        #: gather: what it reads), the kernel's waves and those of them that were
        #: full (ONE wait a buffer), all by the groups' layers
        self._wave_blocks: Tuple[int, ...] = ()
        if self.cache_layout.kind == "kv":
            keys = self.cache_layout.arrays[0][0]
            self._wave_blocks = tuple(
                paged_attention.blocks_a_wave(
                    self.cache[self.cache_layout.array_name(keys, g)].shape[2:], block_size,
                    self.max_blocks_per_seq, keeps,
                )
                for g, (_, keeps) in enumerate(self._groups)
            )
            self.decode_width.update(multiplied_tokens=0, waves=0, single_wait_waves=0)
        #: running sums over prefill launches: the width of the table handed
        #: over (tokens), the positions up to the chunk's end, and the key
        #: positions the program's attention reads: the table's width
        #: (``reads`` = ``table``) or, through a flash kernel (``live``), the
        #: positions up to the chunk's end in whole key tiles; and the key
        #: positions the program gathers (a latent model: and expands K and V
        #: at) in front of its attention: the table's width or, where the
        #: model's chunk takes rungs (``Model.gather_rungs``), the positions
        #: up to the chunk's end in whole rungs
        self.prefill_width: Dict[str, int] = dict.fromkeys(
            ("launches", "width_tokens", "live_tokens", "read_tokens", "expanded_tokens"), 0
        )
        if self.cache_layout.kind == "kv":
            #: a K/V cache: the scatter updates the launches' K/V writes issue
            #: (:meth:`_chunk_write_updates` a launch)
            self.prefill_width["written_updates"] = 0
        #: a model whose attention SELECTS (``Model.selection`` positions a
        #: query at most): running sums over every launch's real query
        #: positions, from the host's own lengths: the queries, those whose
        #: live context (the positions up to their own) is more than a
        #: selection holds, the positions chosen and the positions live
        self.selection: int = self.model.selection(cfg)
        self.sparse_attention: Optional[Dict[str, int]] = None
        if self.selection:
            self.sparse_attention = dict.fromkeys(("queries", "queries_past_topk", "chosen", "live"), 0)
        #: MoE configs only: what the experts saw, as running sums over
        #: decode and verify launches and, apart, prefill launches
        #: (:meth:`_account_moe`); ``None`` for a dense model
        self.moe: Optional[Dict[str, Dict[str, float]]] = None
        #: the range of experts this process holds (all of them unless the
        #: model is one chip's share of an expert-parallel deployment)
        self.held_experts: Optional[Tuple[int, int]] = self.model.held_experts(cfg)
        if self.held_experts is not None:
            keys = ("launches", "assignments", "held_assignments", "bias_changed",
                    "expert_slots", "experts_touched", "max_load", "mean_load",
                    "routed_rows", "group_changed", "expert_layers", "stacked_layers")
            self.moe = {kind: dict.fromkeys(keys, 0) for kind in ("decode", "prefill")}
        self.warmup_programs: Dict[str, float] = {}

        # argument 1 of the partials (cfg is bound) is the cache: donated,
        # updated in place — at a real width a copied cache does not fit;
        # argument 2 the state pool, where the model has one
        donated = (1,) if self.state is None else (1, 2)
        self._prefill_jit = jax.jit(
            partial(self.model.paged_prefill_step, cfg), donate_argnums=donated
        )
        #: what a decode launch hands over where no row names an earlier pick
        self._no_picks = jnp.zeros(self.decode_buckets[-1], jnp.int32)
        self._decode_jit = jax.jit(
            decode_program(self.model.paged_decode_step, cfg, len(donated), self.decode_buckets[-1]),
            donate_argnums=donated,
        )
        # speculative verification: prefill-shaped, all-position logits.
        # Always constructed (an uncalled jit holds zero cache entries so
        # compile accounting is unchanged), only warmed when the engine
        # passes verify buckets.
        self._verify_jit = jax.jit(
            partial(self.model.paged_verify_step, cfg), donate_argnums=donated
        )
        if self.drafter is not None:
            widest = self.decode_buckets[-1]
            #: what a drafter's step hands over where no row names an earlier window
            self._no_windows = jnp.zeros((widest, self.drafter.window + 2), jnp.int32)
            self._mtp_step_jit, self._mtp_verify_jit, self._mtp_draft_jit = (
                jax.jit(f, donate_argnums=(1,)) for f in mtp_programs(self.drafter, cfg, widest)
            )
        # COW block duplication (prefix cache): cache is arg 0 here.
        # partial() gives THIS runner its own jit identity — a bare
        # module-level function would share one compiled-program cache
        # across every runner in the process, and another runner's cache
        # shape would show up in this one's recompile accounting
        self._copy_jit = jax.jit(
            partial(copy_paged_blocks), donate_argnums=(0,)
        )
        # KV-cache migration programs (disaggregated serving): the gather
        # reads blocks out (export — never donated, the cache stays
        # live), the scatter writes imported blocks in (donation like the
        # COW copy). Compiled at warmup only when the engine opts in
        # (kv_transfer_enabled), so plain deployments keep their exact
        # compile_count; a lazy first use still works, it just shows up
        # in recompiles_after_warmup.
        self._gather_jit = jax.jit(partial(gather_paged_blocks))
        self._scatter_jit = jax.jit(
            partial(scatter_paged_blocks), donate_argnums=(0,)
        )
        self._warmup_compiles: Optional[int] = None
        #: jit cache entries per program, as :meth:`_run` (which every call
        #: of a program goes through) last saw them: the one record of what
        #: compiled, summed for the counts and compared to tell which call did
        self._entries: Dict[str, int] = {}

    # -- compile accounting ----------------------------------------------
    def _jit_cache_entries(self) -> int:
        return sum(self._entries.values())

    def mark_warm(self) -> None:
        """Call after warmup: compiles past this point are regressions."""
        self._warmup_compiles = self._jit_cache_entries()

    def recompiles_after_warmup(self) -> int:
        if self._warmup_compiles is None:
            return 0
        return max(0, self._jit_cache_entries() - self._warmup_compiles)

    def _run(self, program: str, fn, *args):
        """Call one of the compiled programs. A call that grows its jit
        cache after :meth:`mark_warm` compiled inside serving: say which
        program and which shapes, once, where the timeline shows it
        beside the step that paid for it."""
        start_us = timeline._now_us()
        out = fn(*args)
        entries = fn._cache_size()
        if entries != self._entries.get(program, 0):
            self._entries[program] = entries
            if self._warmup_compiles is not None:
                shapes = [list(a.shape) for a in args if hasattr(a, "shape")]
                timeline.record_event(
                    "recompile", "inference", start_us, timeline._now_us(),
                    args={"program": program, "arg_shapes": shapes},
                )
                logger.warning(
                    "%s compiled after warm-up for argument shapes %s", program, shapes
                )
        return out

    def _warm(self, program: str, fn, *args, bucket=None):
        """One warm-up call, timed to the end of its first run."""
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(self._run(program, fn, *args))
        label = program if bucket is None else f"{program}[{bucket}]"
        self.warmup_programs[label] = time.perf_counter() - t0
        return out

    def _step(self, run, program: str, fn, *args, slots=None, last=()):
        """One paged step through ``run`` (:meth:`_run` or :meth:`_warm`):
        keeps the new cache (and state pool) and returns ``(logits, loads)``,
        both still on the device (the decode program's ``logits`` is the pair
        ``(logits, picks)``); ``loads`` is None for a dense model.
        ``slots``: the state slot(s) of the step's sequence(s), handed to a
        model that keeps per-sequence state and to no other. ``last``: what
        the runner's own wrapper of the step takes after the model's arguments."""
        if self.state is None:
            self.cache, logits, *loads = run(program, fn, self.params, self.cache, *args, *last)
        else:
            self.cache, self.state, logits, *loads = run(
                program, fn, self.params, self.cache, self.state, *args, slots, *last
            )
        return logits, (loads[0] if loads else None)

    def _path_name(self, window: int) -> str:
        """The launch span's ``path`` of that window's programs: the attention
        path's name and, where the program runs the model's drafter, its kind."""
        name = self._path(window).name
        return name if self.drafter is None else f"{name}+{self.drafter.kind}"

    def _path(self, window: int) -> AttentionPath:
        """The attention path of the programs of that query window."""
        path = self.attention_paths.get(window)
        if path is None:
            path = self.attention_paths[window] = self.model.attention_path(
                self.cfg, window, self.cache
            )
        return path

    def compile_count(self) -> int:
        return self._jit_cache_entries()

    def warmup(
        self, buckets_prefill=None, buckets_decode=None, *, kv_io: bool = False
    ) -> None:
        """Compile every (or the given) bucket up front with trash inputs
        aimed at the null block, then :meth:`mark_warm`. Decode compiles
        once per batch bucket and verify once per (batch bucket x window
        bucket), each at the table's full width: whatever its contexts, a
        live batch finds its program here.
        ``kv_io`` also compiles the KV-migration gather/scatter programs
        (disaggregated serving opts in; plain engines keep their compile
        count)."""
        M = self.max_blocks_per_seq
        bs = self.block_size
        for c in buckets_prefill if buckets_prefill is not None else self.prefill_buckets:
            tokens = np.zeros(c, np.int32)
            row = self._tables((), 0)
            self._step(
                partial(self._warm, bucket=c), "paged_prefill_step", self._prefill_jit,
                tokens, row, np.int32(0), np.int32(0), slots=np.int32(0),
                last=() if self.drafter is None else (np.int32(-1),),
            )
        batches = buckets_decode if buckets_decode is not None else self.decode_buckets
        if self.drafter is not None:
            # decode IS the drafter's step: the one program of an all-greedy
            # batch and the two of a sampled one, and no plain decode or verify
            C = self.drafter.window
            for b in batches:
                warm = partial(self._warm, bucket=f"{b}x{C}x{M * bs}")
                window = (np.zeros((b, C), np.int32), self._tables((), b),
                          np.zeros(b, np.int32), np.zeros(b, np.int32))
                self._step(warm, "paged_mtp_step", self._mtp_step_jit, *window, np.ones(b, np.int32),
                           last=(self._no_windows,))
                (_, hidden), _ = self._step(warm, "paged_mtp_verify", self._mtp_verify_jit, *window)
                self._step(warm, "paged_mtp_draft", self._mtp_draft_jit, hidden, *window)
            batches = ()
        for b in batches:
            self._step(
                partial(self._warm, bucket=f"{b}x{M * bs}"),
                "paged_decode_step", self._decode_jit,
                np.zeros(b, np.int32),
                np.zeros(b, np.int32),
                self._tables((), b),
                np.ones(b, np.int32),
                slots=np.zeros(b, np.int32), last=(self._no_picks,),
            )
        # speculative-verify windows (only when the engine opted in via
        # verify_buckets — plain engines keep their exact compile count).
        # The batch axis rides the decode buckets: every (B-bucket,
        # window-bucket) pair a live engine can issue gets compiled here.
        for c in self.verify_buckets if self.drafter is None else ():
            for b in batches:
                self._step(
                    partial(self._warm, bucket=f"{b}x{c}x{M * bs}"),
                    "paged_verify_step", self._verify_jit,
                    np.zeros((b, c), np.int32),
                    self._tables((), b),
                    np.zeros(b, np.int32),
                    np.zeros(b, np.int32),
                )
        # the COW copy program (all-null pairs write the null block's
        # trash back onto itself)
        pad = np.zeros(_COW_WIDTH, np.int32)
        self.cache = self._warm(
            "copy_paged_blocks", self._copy_jit, self.cache, pad, pad
        )
        if kv_io:
            ids = np.zeros(_KV_IO_WIDTH, np.int32)
            kv = np.asarray(
                self._warm("gather_paged_blocks", self._gather_jit, self.cache, ids)
            )
            self.cache = self._warm(
                "scatter_paged_blocks", self._scatter_jit, self.cache, ids, kv
            )
        self.mark_warm()

    # -- steps ------------------------------------------------------------
    def copy_blocks(self, pairs: Sequence[Tuple[int, int]]) -> None:
        """Device-side block duplication (prefix-cache COW): each
        ``(src, dst)`` pair copies one whole block across every layer.
        Pairs beyond ``_COW_WIDTH`` run in chunks; short chunks pad with
        null->null no-op pairs so the compiled shape never varies."""
        for i in range(0, len(pairs), _COW_WIDTH):
            chunk = pairs[i : i + _COW_WIDTH]
            src = np.zeros(_COW_WIDTH, np.int32)
            dst = np.zeros(_COW_WIDTH, np.int32)
            for j, (s, d) in enumerate(chunk):
                src[j], dst[j] = s, d
            self.cache = self._run(
                "copy_paged_blocks", self._copy_jit, self.cache, src, dst
            )

    def gather_blocks(self, block_ids: Sequence[int]) -> np.ndarray:
        """Read whole cache blocks to host (KV-migration export):
        returns the cache layout's payload (``CacheLayout.payload_shape``:
        ``[2, n_layers, len(block_ids), block_size, n_kv, head_dim]`` for a
        KV cache, ``[1, n_layers, len(block_ids), block_size, row]`` for a
        latent one) as numpy in the cache dtype. Runs in _KV_IO_WIDTH
        chunks padded with the null block so the compiled shape never
        varies; padding rows are sliced off before concatenation."""
        outs = []
        for i in range(0, len(block_ids), _KV_IO_WIDTH):
            chunk = block_ids[i : i + _KV_IO_WIDTH]
            ids = np.zeros(_KV_IO_WIDTH, np.int32)
            ids[: len(chunk)] = chunk
            out = self._run("gather_paged_blocks", self._gather_jit, self.cache, ids)
            outs.append(np.asarray(out)[:, :, : len(chunk)])
        if not outs:
            layout = self.cache_layout
            return np.zeros(layout.payload_shape(0), np.dtype(layout.dtype))
        return np.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]

    def scatter_blocks(self, block_ids: Sequence[int], kv: np.ndarray) -> None:
        """Write migrated KV blocks into this cache (KV-migration
        import): ``kv`` is the :meth:`gather_blocks` layout, one row per
        id in ``block_ids``. Short chunks pad with the null block (its
        rows get zero-filled trash — inert by construction)."""
        for i in range(0, len(block_ids), _KV_IO_WIDTH):
            chunk = block_ids[i : i + _KV_IO_WIDTH]
            ids = np.zeros(_KV_IO_WIDTH, np.int32)
            ids[: len(chunk)] = chunk
            buf = np.zeros(
                kv.shape[:2] + (_KV_IO_WIDTH,) + kv.shape[3:], kv.dtype
            )
            buf[:, :, : len(chunk)] = kv[:, :, i : i + len(chunk)]
            self.cache = self._run(
                "scatter_paged_blocks", self._scatter_jit, self.cache, ids, buf
            )

    def launch_prefill(
        self,
        tokens: Sequence[int],
        block_row: Sequence[int],
        ctx_len: int,
        clock: Optional[timeline.PhaseClock] = None,
        slot: int = 0,
        next_token: int = -1,
    ) -> Launched:
        """Put one prefill chunk on its way to the device and return without
        waiting for it: :meth:`read` gives the logits [vocab] (fp32 numpy) of
        the chunk's last valid token. ``slot``: the request's state slot (a
        model with per-sequence state; a chunk at ``ctx_len`` 0 starts the
        slot's state from zeros inside the program). ``next_token``: the
        prompt's token after the chunk (-1: the chunk is the prompt's last),
        for a runner whose model drafts for itself: the chunk then runs the
        drafter over its positions too. ``clock``: the caller's
        account for the call's phases, if it keeps one (the runner's own
        otherwise)."""
        clock = clock or self.clock
        if self.state is not None and not 1 <= slot <= self.state_slots:
            raise ValueError(f"a sequence of this model needs a state slot in [1, {self.state_slots}], got {slot}")
        true_len = len(tokens)
        bucket = _round_up_bucket(true_len, self.prefill_buckets)
        path = self._path(bucket)
        bs = self.block_size
        width = np.shape(block_row)[-1] * bs
        end = ctx_len + true_len
        tile = self.model.key_tile(self.cfg, bucket, self.cache) if path.reads == "live" else 0
        # by the groups' layers: the positions a query of the chunk sees, and
        # the key positions read: in whole key tiles where a kernel serves, from
        # the block-aligned position the keys are handed from; else the table
        firsts = [max(0, ctx_len - keeps + 1) if keeps else 0 for _, keeps in self._groups]
        live = self._by_layers(end - first for first in firsts)
        read = self._by_layers(
            ((end - 1 - first // bs * bs) // tile - first % bs // tile + 1) * tile if tile else width
            for first in firsts
        )
        rungs = self.model.gather_rungs(self.cfg, bucket, self.cache)
        pw = self.prefill_width
        pw["launches"] += 1
        pw["width_tokens"] += width
        pw["live_tokens"] += live
        pw["read_tokens"] += read
        pw["expanded_tokens"] += next((rung for rung in rungs if rung >= end), width)
        if "written_updates" in pw:
            pw["written_updates"] += self._chunk_write_updates(bucket)
        self._count_selection(ctx_len, end)
        with clock.phase(
            "launch", program="paged_prefill_step", bucket=bucket, path=self._path_name(bucket),
        ):
            with clock.part("inputs"):
                padded = np.zeros(bucket, np.int32)
                padded[:true_len] = tokens
                row = np.asarray(block_row, np.int32)
            with clock.part("call"):
                logits, loads = self._step(
                    self._run, "paged_prefill_step", self._prefill_jit,
                    padded, row, np.int32(ctx_len), np.int32(true_len), slots=np.int32(slot),
                    last=() if self.drafter is None else (np.int32(next_token),),
                )
        return Launched("prefill", logits, loads)

    def prefill_chunk(
        self,
        tokens: Sequence[int],
        block_row: Sequence[int],
        ctx_len: int,
        clock: Optional[timeline.PhaseClock] = None,
        launched: Optional[Callable[[], None]] = None,
        slot: int = 0,
        next_token: int = -1,
    ) -> np.ndarray:
        """:meth:`launch_prefill` and :meth:`read` at once: the logits
        [vocab] of the chunk's last valid token. ``launched``: called once
        when the program is on its way to the device, before this thread
        waits for it (here and in :meth:`decode` and :meth:`verify_batch`):
        the caller's chance to do host work that the device's run hides."""
        step = self.launch_prefill(tokens, block_row, ctx_len, clock, slot, next_token)
        return self.read(step, clock, launched)

    def read(
        self,
        step: Launched,
        clock: Optional[timeline.PhaseClock] = None,
        before_wait: Optional[Callable[[], None]] = None,
    ) -> np.ndarray:
        """Wait for a launched step, then copy what it gives to the host (a
        decode batch's real rows, its padding stripped): two phases, so that
        the device's time is told from the copy's. A MoE step's expert loads
        come over in the same ``readback`` and go into the step's half of
        :attr:`moe`. ``before_wait`` runs first: the launch has returned, the
        wait has not begun. Counts the wait on the clock (``clock.reads``), and
        as ``ready`` if it returned at once: the step was done before the
        host asked."""
        clock = clock or self.clock
        if before_wait is not None:
            before_wait()
        with clock.phase("device_wait") as wait:
            step.out.block_until_ready()
        reads = clock.reads
        reads["reads"] += 1
        reads["ready"] += wait.seconds < _READY_S
        with clock.phase("readback"):
            with clock.part("logits"):
                host = np.asarray(step.out)
            if step.loads is not None:
                with clock.part("loads"):
                    loads = step.loads
                    counters = loads if isinstance(loads, dict) else {"load": loads}
                    self._account_moe(step.kind, {k: np.asarray(v) for k, v in counters.items()})
        return host if step.n is None else host[: step.n]

    def _account_moe(self, kind: str, counters: Dict[str, np.ndarray]) -> None:
        """Add one launch's expert loads ``[n_layers, E]`` (assignments of
        its real rows per layer and expert, over ALL the experts the router
        chooses among) to the running sums: every field but ``launches`` is
        a sum over launch AND layer. ``assignments`` = real rows x top_k x
        layers; ``held_assignments`` = those to the experts this process
        holds (all of them unless it is one chip's share of an
        expert-parallel deployment); ``expert_slots`` = layers x E;
        ``experts_touched`` = slots with a load above 0 (what sets the
        expert bytes a launch reads); ``max_load`` / ``mean_load`` = the
        largest and the mean load of a layer (their ratio is the imbalance a
        grouped matmul pays for). ``counters`` holds that array under
        ``load`` and, where the router's choice adds a per-expert bias to the
        scores, ``bias_changed`` ``[n_layers]``: real rows whose kept set
        differs from the top-k of the scores alone; where the choice is
        limited to groups of experts, ``routed_rows`` and ``group_changed``
        ``[n_layers]``: the real rows, and those whose kept set differs from
        the plain top-k of score + bias. ``expert_layers`` = the layers;
        ``stacked_layers`` = those of them whose grouped matmuls read the
        layer's matrices in place in a scanned group's stack, as the model
        says of the program (``Model.experts_in_place``: no device output)."""
        loads = counters["load"]
        lo, hi = self.held_experts
        acc = self.moe[kind]
        acc["launches"] += 1
        acc["assignments"] += int(loads.sum())
        acc["held_assignments"] += int(loads[:, lo:hi].sum())
        for key in ("bias_changed", "routed_rows", "group_changed"):
            acc[key] += int(np.sum(counters.get(key, 0)))
        acc["expert_layers"] += loads.shape[0]
        acc["stacked_layers"] += self.model.experts_in_place(self.cfg, loads.shape[0])
        acc["expert_slots"] += loads.size
        acc["experts_touched"] += int(np.count_nonzero(loads))
        acc["max_load"] += int(loads.max(axis=1).sum())
        acc["mean_load"] += float(loads.mean(axis=1).sum())

    def _count_selection(self, first: int, end: int) -> None:
        """Count the query positions ``[first, end)`` of one sequence in
        :attr:`sparse_attention`: position ``t`` has ``t + 1`` live positions
        and chooses ``min(t + 1, selection)`` of them."""
        sa, K = self.sparse_attention, self.selection
        if sa is None or end <= first:
            return
        full = max(first, min(end, K))  # positions under it choose everything
        live = (end * (end + 1) - first * (first + 1)) // 2
        sa["queries"] += end - first
        sa["queries_past_topk"] += end - full
        sa["live"] += live
        sa["chosen"] += (full * (full + 1) - first * (first + 1)) // 2 + (end - full) * K

    def _chunk_write_updates(self, bucket: int) -> int:
        """The scatter updates the K/V write of ONE prefill launch of ``bucket``
        rows issues: ``paged_kv.write_updates`` an array, over K and V of every
        layer of every group."""
        return sum(
            layers * paged_kv.write_updates(
                1, bucket, self.cache[self.cache_layout.array_name(name, g)].shape[2:], self.block_size
            )
            for g, (layers, _) in enumerate(self._groups) for name, _ in self.cache_layout.arrays
        )

    def _by_layers(self, per_group) -> float:
        """The mean over the cache's layers of a number a layer group (an int
        where it comes out whole, as it does for a model of one group)."""
        total = sum(layers * value for (layers, _), value in zip(self._groups, per_group, strict=True))
        whole, rest = divmod(total, self.cache_layout.n_layers)
        return whole if not rest else total / self.cache_layout.n_layers

    def _tables(self, block_rows, bucket: int) -> np.ndarray:
        """The block tables a step is handed: the slots' rows (``M`` =
        ``max_blocks_per_seq`` blocks each) padded to ``bucket`` slots with the
        null block's table: a table a layer group, ``[groups, bucket, M]``
        (each row then one row a group), and for a model of ONE group its
        table as it always was, ``[bucket, M]``. ``bucket`` 0: one row, no
        slot axis."""
        M = self.max_blocks_per_seq
        lead = (len(self._groups),) if len(self._groups) > 1 else ()
        bt = np.zeros((*lead, bucket, M) if bucket else (*lead, M), np.int32)
        if len(block_rows):  # [n, M] or [n, groups, M] -> the slots' axis before the last
            bt[..., : len(block_rows), :] = np.moveaxis(np.asarray(block_rows, np.int32), 0, -2)
        return bt

    def _count_width(self, ctx_lens: Sequence[int], bucket: int, window: int = 1) -> None:
        """Count one decode (``window`` 1) or verify launch in
        :attr:`decode_width`: the table it is handed (``max_blocks_per_seq``
        wide, every launch) and what its program reads of the cache.
        ``ctx_lens``: the real slots' contexts INCLUDING what this step
        writes."""
        bs = self.block_size
        width = self.max_blocks_per_seq * bs
        reads = self._path(window).reads
        # by the groups' layers: a group that keeps a window holds, and a
        # kernel reads, each slot's blocks from the window's first on
        live = self._by_layers(
            sum(min(int(c), keeps) if keeps else int(c) for c in ctx_lens) for _, keeps in self._groups
        )
        if reads == "blocks":  # a padding slot reads none; a real slot its live blocks
            blocks = [
                [-(-int(c) // bs) - (max(0, int(c) - keeps) // bs if keeps else 0) for c in ctx_lens]
                for _, keeps in self._groups
            ]
            per_group = [bs * sum(of_slots) for of_slots in blocks]
        elif reads == "slots":  # each real slot the table whole
            per_group = [len(ctx_lens) * width] * len(self._groups)
        else:
            per_group = [bucket * width] * len(self._groups)
        read = self._by_layers(per_group)
        for c in ctx_lens:  # a slot's window: the last ``window`` positions at most
            self._count_selection(max(0, int(c) - window), int(c))
        dw = self.decode_width
        if "window_read_tokens" in dw:  # of ``read``, the part of the groups that keep a window
            dw["window_read_tokens"] += self._by_layers(
                value if keeps else 0 for (_, keeps), value in zip(self._groups, per_group)
            )
        dw["launches"] += 1
        dw["width_tokens"] += width
        dw["needed_tokens"] += int(max(ctx_lens))
        dw["live_tokens"] += live
        dw["gathered_tokens"] += read
        if self._wave_blocks and reads == "blocks":  # the kernel: whole waves of ``P`` blocks
            groups = list(zip(blocks, self._wave_blocks))
            waves = [sum(-(-n // P) for n in of_slots) for of_slots, P in groups]
            dw["waves"] += self._by_layers(waves)
            dw["multiplied_tokens"] += self._by_layers(w * P * bs for w, (_, P) in zip(waves, groups))
            dw["single_wait_waves"] += self._by_layers(sum(n // P for n in of_slots) for of_slots, P in groups)
        elif self._wave_blocks:
            dw["multiplied_tokens"] += read

    def verify_batch(
        self,
        windows: Sequence[Sequence[int]],
        block_rows: Sequence[Sequence[int]],
        ctx_lens: Sequence[int],
        clock: Optional[timeline.PhaseClock] = None,
        launched: Optional[Callable[[], None]] = None,
    ) -> List[np.ndarray]:
        """Run speculative-verify windows (``[last_committed, d_1..d_k]``
        each) for a batch of slots in ONE jitted step. Returns one
        logits array [len(window), vocab] (fp32 numpy) per slot, a row
        per valid window position. The batch axis pads to a decode
        bucket; padding slots carry ``true_len=0`` so every position is
        invalid and the writes land on the null block. ``block_rows`` are
        ``max_blocks_per_seq`` wide, and so handed to the step."""
        clock = clock or self.clock
        n = len(windows)
        cbucket = _round_up_bucket(max(len(w) for w in windows), self.verify_buckets)
        bbucket = _round_up_bucket(n, self.decode_buckets)
        self._count_width([c + len(w) for c, w in zip(ctx_lens, windows)], bbucket, cbucket)
        with clock.phase(
            "launch", program="paged_verify_step",
            bucket=f"{bbucket}x{cbucket}x{self.max_blocks_per_seq * self.block_size}",
            path=self._path(cbucket).name,
        ):
            with clock.part("inputs"):
                tokens = np.zeros((bbucket, cbucket), np.int32)
                tables = self._tables(block_rows, bbucket)
                ctx = np.zeros(bbucket, np.int32)
                tl = np.zeros(bbucket, np.int32)
                for i, w in enumerate(windows):
                    tokens[i, : len(w)] = w
                    ctx[i] = ctx_lens[i]
                    tl[i] = len(w)
            with clock.part("call"):
                logits, loads = self._step(
                    self._run, "paged_verify_step", self._verify_jit, tokens, tables, ctx, tl
                )
        out = self.read(Launched("decode", logits, loads), clock, launched)
        return [out[i, : len(w)] for i, w in enumerate(windows)]

    def _mtp_inputs(self, windows, block_rows, ctx_lens, bucket: int):
        """A drafter step's padded inputs: ``(tokens [bucket, window], tables
        [bucket, M], ctx [bucket], true_lens [bucket])``; a padding slot has
        no row (``true_len`` 0) and the null block's table."""
        C = self.drafter.window
        tokens = np.zeros((bucket, C), np.int32)
        tables = self._tables(block_rows, bucket)
        ctx = np.zeros(bucket, np.int32)
        tl = np.zeros(bucket, np.int32)
        for i, w in enumerate(windows):
            tokens[i, : len(w)] = w
            ctx[i] = ctx_lens[i]
            tl[i] = len(w)
        return tokens, tables, ctx, tl

    def launch_mtp_step(
        self,
        windows: Sequence[Sequence[int]],
        known: Sequence[int],
        block_rows: Sequence[Sequence[int]],
        ctx_lens: Sequence[int],
        clock: Optional[timeline.PhaseClock] = None,
        greedy: bool = True,
        after: Optional[Launched] = None,
    ) -> Launched:
        """Put a decode step of a model that drafts for itself on its way:
        each slot's window (``[x_n, d]``: the committed last token and the
        draft; ``[x_n]`` where no draft rides; ``known[i]`` 2: both tokens
        committed, a slot whose drafter has no row at the first yet) at
        positions ``ctx_lens[i] ..``. ``greedy``: the ONE program (verify, the
        picks, the comparison, the drafter over what was committed, the next
        drafts): :meth:`read` gives ``[n, window + 2]`` int32, a slot's new
        tokens, how many drafts were accepted (its new tokens are the first 1
        + accepted), its next draft. ``after``: the ONE-program step before
        this one, read or not; a window whose first token is ``-1 - j`` then
        stands for what row ``j`` of ITS result leaves, which never leaves the
        device: the last token it committed and its draft, ``ctx_lens[i]`` the
        position after ONE committed token (the program adds what was
        accepted; the launch span's ``ahead`` says whether one was given).
        Without ``greedy`` the first of two: :meth:`read` gives the logits
        ``[n, window, V]`` for the host's sampler, and
        :meth:`launch_mtp_draft` goes on from what it sampled."""
        clock = clock or self.clock
        n, C = len(windows), self.drafter.window
        bucket = _round_up_bucket(n, self.decode_buckets)
        # a named window may stand as far on as that step accepted drafts
        ctx_most = [c + (C - 1) * (w[0] < 0) for c, w in zip(ctx_lens, windows)]
        self._count_width([c + len(w) for c, w in zip(ctx_most, windows)], bucket, C)
        program = "paged_mtp_step" if greedy else "paged_mtp_verify"
        with clock.phase(
            "launch", program=program, bucket=f"{bucket}x{C}x{self.max_blocks_per_seq * self.block_size}",
            path=self._path_name(C), ahead=int(after is not None),
        ):
            with clock.part("inputs"):
                inputs = self._mtp_inputs(windows, block_rows, ctx_lens, bucket)
                kn = np.ones(bucket, np.int32)
                kn[:n] = known
                if inputs[0].min() < 0 and (after is None or not greedy):
                    raise ValueError(
                        "a window names a row of an earlier ONE-program drafter step, and none was given"
                    )
            with clock.part("call"):
                if greedy:
                    out, loads = self._step(
                        self._run, program, self._mtp_step_jit, *inputs, kn,
                        last=(self._no_windows if after is None else after.picks,),
                    )
                    # what the read copies to the host is a few small integers:
                    # the copies start when the step ends, not when the host asks
                    # (a step read late is asked for beside the NEXT running
                    # program, where each copy takes 2-3 ms and they came in turn)
                    for leaf in (out, *(loads.values() if isinstance(loads, dict) else (loads,))):
                        if leaf is not None:
                            leaf.copy_to_host_async()
                    return Launched("decode", out, loads, n, out)
                (logits, hidden), loads = self._step(self._run, program, self._mtp_verify_jit, *inputs)
        return Launched("decode", logits, loads, n, hidden=hidden)

    def launch_mtp_draft(
        self,
        verified: Launched,
        follows: Sequence[Sequence[int]],
        block_rows: Sequence[Sequence[int]],
        ctx_lens: Sequence[int],
        clock: Optional[timeline.PhaseClock] = None,
        logits: bool = False,
    ) -> Launched:
        """The second program of a step with the host's sampler between:
        the drafter over the positions of each window whose FOLLOWING token
        the host has committed (``follows[i]``: those tokens, in order), from
        the residuals ``verified`` left on the device. :meth:`read` gives the
        next drafts ``[n]`` int32 (with ``logits`` the drafter's logits ``[n,
        V]`` they are the argmax of: the same program)."""
        clock = clock or self.clock
        n, C = len(follows), self.drafter.window
        bucket = _round_up_bucket(n, self.decode_buckets)
        with clock.phase(
            "launch", program="paged_mtp_draft",
            bucket=f"{bucket}x{C}x{self.max_blocks_per_seq * self.block_size}",
            path=self._path_name(C),
        ):
            with clock.part("inputs"):
                inputs = self._mtp_inputs(follows, block_rows, ctx_lens, bucket)
            with clock.part("call"):
                (picks, lg), loads = self._step(
                    self._run, "paged_mtp_draft", self._mtp_draft_jit, verified.hidden, *inputs
                )
        return Launched("decode", lg if logits else picks, loads, n)

    def launch_decode(
        self,
        tokens: Sequence[int],
        positions: Sequence[int],
        block_rows: Sequence[Sequence[int]],
        ctx_lens: Sequence[int],
        clock: Optional[timeline.PhaseClock] = None,
        slots: Optional[Sequence[int]] = None,
        greedy: bool = False,
        after: Optional[Launched] = None,
    ) -> Launched:
        """Put a decode batch on its way to advance one token and return
        without waiting for it: :meth:`read` gives the logits [n, vocab] of
        the n REAL slots or, with ``greedy``, their argmax ``[n]`` int32,
        which the same program takes on the device (the logits then stay
        there, held by nobody). ``after``: the decode launch before this one,
        read or not; a token ``-1 - j`` then stands for row ``j`` of ITS
        picks, which never leave the device (the launch span's ``ahead`` says
        whether one was given). ``slots``: each sequence's state
        slot (a model with per-sequence state; padding rows take the null
        slot). ``block_rows`` are ``max_blocks_per_seq`` wide, and so handed
        to the step."""
        clock = clock or self.clock
        n = len(tokens)
        if self.state is not None and (slots is None or len(slots) != n):
            raise ValueError("a decode batch of this model needs the state slot of each sequence")
        bucket = _round_up_bucket(n, self.decode_buckets)
        self._count_width(ctx_lens, bucket)
        with clock.phase(
            "launch", program="paged_decode_step",
            bucket=f"{bucket}x{self.max_blocks_per_seq * self.block_size}",
            path=self._path(1).name, ahead=int(after is not None),
        ):
            with clock.part("inputs"):
                t = np.zeros(bucket, np.int32)
                p = np.zeros(bucket, np.int32)
                bt = self._tables(block_rows, bucket)
                cl = np.ones(bucket, np.int32)  # padding slots: ctx=1 over the null block
                t[:n] = tokens
                p[:n] = positions
                cl[:n] = ctx_lens
                sl = np.zeros(bucket, np.int32)
                if slots is not None:
                    sl[:n] = slots
                if after is None and t.min() < 0:
                    raise ValueError("a token names a row of an earlier decode launch, and none was given")
            with clock.part("call"):
                (logits, picks), loads = self._step(
                    self._run, "paged_decode_step", self._decode_jit, t, p, bt, cl, slots=sl,
                    last=(self._no_picks if after is None else after.picks,),
                )
        if greedy:
            # what the read copies to the host is a few small integers (the picks,
            # the expert loads): the copies start when the step ends, not when the
            # host asks, as the drafter's ONE-program step does (asked for beside
            # the NEXT running program each took 4-5 ms at 128 slots and they came
            # in turn: PERF.md, PR 49). A batch that reads its logits copies them
            # when it asks: 33 MB a step nobody may want
            for leaf in (picks, *(loads.values() if isinstance(loads, dict) else (loads,))):
                if leaf is not None:
                    leaf.copy_to_host_async()
        return Launched("decode", picks if greedy else logits, loads, n, picks)

    def decode(
        self,
        tokens: Sequence[int],
        positions: Sequence[int],
        block_rows: Sequence[Sequence[int]],
        ctx_lens: Sequence[int],
        clock: Optional[timeline.PhaseClock] = None,
        launched: Optional[Callable[[], None]] = None,
        slots: Optional[Sequence[int]] = None,
        greedy: bool = False,
    ) -> np.ndarray:
        """:meth:`launch_decode` and :meth:`read` at once: logits [n, vocab]
        for the n REAL slots (padding stripped), or with ``greedy`` their
        argmax ``[n]`` int32."""
        step = self.launch_decode(tokens, positions, block_rows, ctx_lens, clock, slots, greedy)
        return self.read(step, clock, launched)
