"""The deployment class of this family's serving cells: the program's
``LLMServer`` (Mellum2 runs through ``ray_tpu.models.llama`` by its config)
with the benchmark's general additions (``harness/server.py``) and this
family's own drive of the correctness check. Defined at module level so that
it is pickled by reference.

Why its own drive. The model's cache has two layer GROUPS, each with a pool
of blocks and a table a sequence of its own, and a window group's table has
to SLIDE: before every chunk and every decode step the blocks wholly behind
the window are given back and read the null block. The harness's general
drive hands the runner one row of consecutive block ids; this one hands the
check's sequences to a SCHEDULER of the engine's own class and settings
(``ContinuousBatchingScheduler``: its admission, its slide before each chunk,
its growth before each decode step) over a ``PagedBlockManager`` of the
engine's idle pools at their real sizes, and runs every plan it makes through
the runner's warmed programs with the rows that manager gives. So the long
prompt really decodes over a table whose head the scheduler has given back
chunk by chunk, and a slide that is a block late or early is the program's
fault to be found, not a copy of it here. What stays outside ``correct``: the
engine's own assembly of a launch's rows at 64 live slots and its launch ahead
of the read (``PERF.md`` section 7).

Four readings decide ``correct``:

* the logits after the whole model, at the end of both prompts and at every
  decode step, against the float32 reference's full pass (``logit_rel_tol``);
* the expert FFN alone on the held range (``expert_ffn_rel_tol``): the
  program's ``llama._ffn`` and the reference's on the SAME normed activations
  at the shapes of the largest chunk and of the decode batch, where no expert
  can flip (``families/olmoe/server.py`` says why the logits cannot tell a
  wrong gate from their own routing noise);
* a WINDOW layer alone and a FULL layer alone (``window_attn_rel_tol``,
  ``full_attn_rel_tol``): the program's attention half of one layer
  (``llama._paged_attention_block``: q / k / v, the kind's rope, the write
  through the group's table, the chunk's flash kernel and the decode kernel
  over a table that has slid) on seeded normed activations, three chunks and
  then decode steps at the decode batch's shape, against the reference's
  attention of that kind on the same activations. A window mask that is off by
  a block, YaRN left out or applied to the wrong kind change a few keys' worth
  of one layer's scores: the logits after 28 layers of routed experts do not
  tell them from noise, one layer alone does."""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer

#: a row of the expert FFN's reading is compared only where the reference's
#: last chosen expert beat the first one left out by more than this share of
#: its probability: the two float32 routers agree to about 1e-6
TIE_MARGIN = 1e-4
#: decode steps of a layer's reading alone
ALONE_DECODE_STEPS = 3


def check_scheduler(runner, max_decode_batch: int, max_prefills_per_step: int = 1):
    """A scheduler as the engine's own is made (``InferenceEngine.__init__``:
    the largest prefill bucket a chunk, the engine's decode batch and chunks a
    step), over a block manager of the runner's pools: the first group's
    blocks its own, a window pool a group beside it. The engine is idle while
    the check runs, so every block of every pool is free; the engine's own
    scheduler is left alone, because its step thread would run what it was
    handed."""
    from ray_tpu.inference.kv_cache import PagedBlockManager
    from ray_tpu.inference.scheduler import ContinuousBatchingScheduler

    groups = runner.cache_layout.groups
    sizes = np.atleast_1d(runner.num_blocks)
    manager = PagedBlockManager(
        int(sizes[0]), runner.block_size, group=groups[0].name,
        windows=[(g.name, int(n), g.keeps) for g, n in zip(groups[1:], sizes[1:], strict=True)],
    )
    return ContinuousBatchingScheduler(
        manager, max_decode_batch=max_decode_batch, max_prefill_chunk=runner.prefill_buckets[-1],
        max_prefills_per_step=max_prefills_per_step,
    )


def drive(runner, scheduler, tokens, prompt_lens: List[int], decode_steps: int) -> List[Any]:
    """Chunked prefill then teacher-forced decode of ``tokens [B, T]``: the
    sequences are handed to ``scheduler`` and every plan it makes is run
    through the runner's warmed programs, a launch's rows asked of the
    scheduler's block manager as the engine asks them; what the engine would
    sample is forced to the next token of ``tokens``. ``[(row, position,
    logits [V])]``, in the order of the launches."""
    from ray_tpu.inference.scheduler import Request

    width, blocks = runner.max_blocks_per_seq, scheduler.blocks
    reqs = [
        Request(f"check-{i}", [int(t) for t in tokens[i, :n]], max_new_tokens=decode_steps + 1)
        for i, n in enumerate(prompt_lens)
    ]
    row_of = {r.request_id: i for i, r in enumerate(reqs)}
    for r in reqs:
        scheduler.add(r)
    got: List[Any] = []

    def commit(req, position: int, logits) -> None:
        i = row_of[req.request_id]
        got.append((i, position, logits))
        last = position + 1 == tokens.shape[1]  # the row's last reading: nothing follows it
        req.generated.append(0 if last else int(tokens[i, position + 1]))

    # a sequence through stays where it stood, holding its blocks
    while any(len(r.generated) < r.max_new_tokens for r in reqs):
        plan = scheduler.schedule()
        if plan.reaped or not (plan.prefills or plan.decodes):
            raise RuntimeError(f"the check's sequences found a pool dry: {blocks.pool_stats()}")
        for req, start, chunk in plan.prefills:
            logits = runner.prefill_chunk(
                req.prompt[start : start + chunk], blocks.table_row(req.request_id, width), start
            )
            req.prefill_pos = start + chunk
            if req.prefill_done:
                commit(req, len(req.prompt) - 1, logits)
        if plan.decodes:
            poss = [r.context_len - 1 for r in plan.decodes]
            logits = runner.decode(
                [r.generated[-1] for r in plan.decodes], poss,
                [blocks.table_row(r.request_id, width) for r in plan.decodes], [p + 1 for p in poss],
            )
            for j, (req, p) in enumerate(zip(plan.decodes, poss)):
                commit(req, p, logits[j])
    return got


def attention_alone(runner, model: Dict[str, Any], seed: int, kind: str,
                    reference_attention: Callable) -> Dict[str, Any]:
    """One layer of ``kind`` alone: per phase (``chunks``, ``decode``) the
    largest, over the rows, of ``max|attention - reference| / max|reference|``
    over a row's outputs. The program's side: ``llama._paged_attention_block``
    on seeded normed activations over a fresh cache of that ONE layer and a
    manager's sliding table: two whole chunks of the largest bucket, a third
    with a padded tail, then decode steps at the decode batch's shape (one real
    slot, the rest padding on the null table). On a TPU the chunks run the
    flash kernel and the steps the decode kernel, as the timed path does."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.inference.kv_cache import PagedBlockManager
    from ray_tpu.models import llama

    cfg, bs, M = runner.cfg, runner.block_size, runner.max_blocks_per_seq
    C, B = runner.prefill_buckets[-1], runner.decode_buckets[-1]
    window = model["sliding_window"] if kind == "sliding_attention" else 0
    tail = max(1, (C * 5) // 8)
    T = 2 * C + tail + ALONE_DECODE_STEPS
    layers = [p for p, w in zip(runner.params["layers"], cfg.layer_windows) if bool(w) == bool(window)]
    p = layers[len(layers) // 2]
    rng = np.random.default_rng([int(seed), 96 if window else 95])
    h = jnp.asarray(rng.standard_normal((T, cfg.dim)).astype(np.float32), cfg.dtype)  # unit RMS
    # both sides norm the SAME (rounded) rows: the program inside its block
    # under a norm vector of ones, the reference's attention is handed them normed
    h32 = h.astype(jnp.float32)
    normed = h32 * jax.lax.rsqrt(jnp.mean(h32 * h32, axis=-1, keepdims=True) + cfg.norm_eps)
    want = np.asarray(reference_attention(model, p, normed, kind))
    # one layer's arrays under the first group's names, and the table of a
    # sequence in a pool of this kind alone
    one = llama.cache_layout(cfg, bs, runner.cache["k"].dtype)
    blocks = M + 2
    cache = {n: jnp.zeros((1, blocks, *one.block_shape(row)), one.dtype) for n, row in one.arrays}
    if window:
        manager = PagedBlockManager(blocks, bs, windows=[("window", blocks, window)])
    else:
        manager = PagedBlockManager(blocks, bs)
    row_of = lambda: np.asarray(manager.table_row("alone", M), np.int32).reshape(-1, M)[-1]  # noqa: E731

    def block(cache, x, pos, valid, table):
        blk = jnp.where(valid, llama._block_at(table, pos, bs), 0)
        cache, y = llama._paged_attention_block(
            cfg, {**p, "attn_norm": jnp.ones_like(p["attn_norm"])}, cache, x, pos, valid, table, 0,
            ("k", "v"), window, blk, pos % bs, llama._rope_at(cfg, pos, window),
        )
        return cache, y - x  # the attention's own term

    step = jax.jit(block, donate_argnums=0)
    have = []
    for start, n in ((0, C), (C, C), (2 * C, tail)):
        if not manager.grow_to("alone", T, (start, start + n)):
            raise RuntimeError("the layer's own pool ran dry")
        x = jnp.full((C, cfg.dim), 100.0, cfg.dtype).at[:n].set(h[start : start + n])  # the padding holds anything
        pos = (start + jnp.arange(C, dtype=jnp.int32))[None]
        cache, y = step(cache, x[None], pos, (jnp.arange(C) < n)[None], jnp.asarray(row_of())[None])
        have.append(np.asarray(y[0, :n], np.float32))
    for t in range(2 * C + tail, T):
        if not manager.grow_to("alone", t + 1):
            raise RuntimeError("the layer's own pool ran dry")
        tables = np.zeros((B, M), np.int32)
        tables[0] = row_of()
        x = jnp.zeros((B, 1, cfg.dim), cfg.dtype).at[0, 0].set(h[t])
        pos = jnp.zeros((B, 1), jnp.int32).at[0, 0].set(t)
        cache, y = step(cache, x, pos, (jnp.arange(B) == 0)[:, None], jnp.asarray(tables))
        have.append(np.asarray(y[0], np.float32))
    have = np.concatenate(have)
    err = np.max(np.abs(have - want), axis=-1) / np.max(np.abs(want), axis=-1)
    edge = 2 * C + tail
    return {
        "worst": {"chunks": float(np.max(err[:edge])), "decode": float(np.max(err[edge:]))},
        "released_behind": manager.pool_stats().get("window", {}).get("released_behind", 0),
        "finite": bool(np.all(np.isfinite(have))),
    }


def expert_ffn_alone(runner, model: Dict[str, Any], seed: int, reference_ffn: Callable) -> Dict[str, Any]:
    """Per shape (rows of the launch) and checked layer: the largest, over the
    compared real rows, of ``max|ffn - reference| / max|reference|`` over a
    row's outputs, the held range's part on both sides. ``worst``: per shape."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = runner.cfg
    ffn = jax.jit(lambda p, h, valid: llama._ffn(cfg, p, h, valid)[0])
    rng = np.random.default_rng([int(seed), 98])
    out: Dict[str, Any] = {"by_layer": {}, "worst": {}, "not_compared": 0, "finite": True}
    layers = runner.params["layers"]
    for rows in (runner.prefill_buckets[-1], runner.decode_buckets[-1]):
        real = rows - rows // 8
        valid = jnp.arange(rows) < real
        readings = []
        for p in layers[:: max(1, len(layers) // 3)][:3]:
            h = rng.standard_normal((rows, cfg.dim)).astype(np.float32)
            h[real:] *= 100.0  # the padding rows hold anything
            h = jnp.asarray(h, cfg.dtype)
            have = np.asarray(ffn(p, h, valid), np.float32)
            want, margin = reference_ffn(model, p, h[:real].astype(jnp.float32))
            want, margin = np.asarray(want), np.asarray(margin)
            sure = margin > TIE_MARGIN
            # a row may have none of its experts held: its part is 0 on both sides
            scale = np.maximum(np.max(np.abs(want), axis=-1), 1e-3 * np.max(np.abs(want)))
            err = np.max(np.abs(have[:real] - want), axis=-1) / scale
            readings.append(float(np.max(err[sure])))
            out["not_compared"] += int(real - sure.sum())
            out["finite"] &= bool(np.all(np.isfinite(have)) and not np.any(have[real:]))
        out["by_layer"][str(rows)] = readings
        out["worst"][str(rows)] = max(readings)
    return out


class BenchMellumServer(BenchServer, LLMServer):
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        """The four readings (the module's docstring). The harness holds the
        worst entry of ``rel_err`` to ``logit_rel_tol``: a reading with a limit
        of its own is entered as a share of THAT limit times ``logit_rel_tol``,
        so that an entry passes exactly when its reading is within its own
        limit. The engine must be idle: the check writes into blocks 1.. of
        both free pools, which later requests overwrite."""
        family = families.of(model)
        runner = self.engine.runner
        rng = np.random.default_rng([int(seed), 99])
        totals = [n + decode_steps for n in prompt_lens]
        tokens = rng.integers(1, model["vocab_size"], size=(len(totals), max(totals))).astype(np.int32)
        own = self.engine.scheduler
        scheduler = check_scheduler(runner, own.max_decode_batch, own.max_prefills_per_step)
        driven = drive(runner, scheduler, tokens, prompt_lens, decode_steps)
        want = family.reference_logits(model, runner.params, tokens, [(i, p) for i, p, _ in driven])
        got: Dict[str, Any] = {
            "positions": [[i, p] for i, p, _ in driven],
            "rel_err": [float(np.max(np.abs(have - ref)) / np.max(np.abs(ref)))
                        for (_, _, have), ref in zip(driven, want)],
            "finite": bool(all(np.all(np.isfinite(h)) for _, _, h in driven)),
            "pools": scheduler.blocks.pool_stats(),
        }
        limits = model["correctness"]
        for name, limit, alone in (
            ("expert_ffn", "expert_ffn_rel_tol",
             expert_ffn_alone(runner, model, seed, family.reference_expert_ffn)),
            ("window_attn", "window_attn_rel_tol",
             attention_alone(runner, model, seed, "sliding_attention", family.reference_attention)),
            ("full_attn", "full_attn_rel_tol",
             attention_alone(runner, model, seed, "full_attention", family.reference_attention)),
        ):
            for what, reading in alone["worst"].items():
                got["positions"].append([name, what])
                got["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * reading)
            got["finite"] = bool(got["finite"] and alone["finite"])
            got[name] = alone
        return got
