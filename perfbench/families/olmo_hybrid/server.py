"""The deployment class of this family's serving cells: the program's
``LLMServer`` with the benchmark's general additions (``harness/server.py``)
and this family's own ``bench_check``.

Why its own. The program's runner takes a STATE SLOT a sequence beside the
block-table row (``prefill_chunk(slot=...)``, ``decode(slots=...)``), which the
harness's check does not hand over, so the check's drive is here
(:func:`drive`: the harness's, its sequences on slots scattered over the pool,
as ``families/jamba/server.py``'s).

Four readings, each entered as a share of ITS limit times ``logit_rel_tol``
(the harness holds the worst entry to that one number; every entry carries
its reading, and one that is not a logit's its own limit too, so that the
harness's line shows each beside the other):

* the logits after the whole model, under ``logit_rel_tol``, from the
  programs the window launches: prefill in chunks of the largest bucket, then
  decode THROUGH the cache and the state pool; at each sequence's last prompt
  position and after EVERY decode step;
* THE STATE POOL as those programs left it (:func:`pool_states`), read TWICE:
  after the chunked prefill (the prefill program's slot across its chunk
  edges, a padded tail and a chunk of ONE row, shorter than the taps) and
  after the last decode step (the decode kernel's pass over each layer's slab,
  step after step), against the reference's ``S`` after the sequence's last
  position and its convolution's last three inputs, as ``|have - want| /
  |want|`` over a layer's array (Frobenius), the largest over the driven
  slots. The FIRST recurrent layer, whose input is the embedding alone, under
  ``state_rel_tol`` (``S``) and ``tail_rel_tol`` (the tail); every layer's
  under ``state_deep_rel_tol`` and ``tail_deep_rel_tol`` (the deeper layers'
  inputs carry the bfloat16 rounding of the residual stream before them);
* a GATED DELTANET mixer ALONE under ``gdn_rel_tol`` (:func:`gdn_alone`): the
  program's mixer from a zero state over a chunk, a second chunk with a padded
  tail that holds anything, a third of ONE real row, then decode steps over a
  one-layer pool of the decode batch's slots (through ``ops/kda.py``'s kernel
  where it serves; the other slots hold 7s and must stand still), against the
  reference's token-by-token recurrence on the same activations;
* an ATTENTION mixer ALONE under ``attn_rel_tol`` (:func:`attn_alone`): the
  program's ``_attention_mix`` (30 query heads over 30 KV heads, QK-norm, no
  position term; the write, the flash kernel over a chunk from an empty
  context, then the paged kernel over decode steps at the decode batch's
  shape) on a one-layer cache, against the reference's causal attention on the
  same activations."""

from __future__ import annotations

import sys
import time
from typing import Any, Callable, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer
from ..jamba.server import _slots_of
from ..kimi_linear.server import check_slots
from ..lfm2.server import _row_err, _spread

#: real rows of the second chunk of :func:`gdn_alone` as a share of the bucket, and the decode steps alone
TAIL_SHARE, DECODE_STEPS = 0.7, 4


def drive(runner, model: Dict[str, Any], seed: int, prompt_lens: List[int],
          decode_steps: int, reference: Callable) -> Dict[str, Any]:
    """``harness/server.py::BenchServer.bench_check``'s drive with a state
    slot a sequence: prefill in chunks then teacher-forced decode through
    both pools with the runner's warmed programs (the ones the window
    launches), against ``reference`` over the same tokens from the same
    weights; the state pool as those programs left it after the prefill and
    after the last step (:func:`pool_states`), under ``"state"``."""
    bs, width = runner.block_size, runner.max_blocks_per_seq
    rng = np.random.default_rng([int(seed), 99])
    totals = [n + decode_steps for n in prompt_lens]
    tokens = rng.integers(1, model["vocab_size"], size=(len(totals), max(totals))).astype(np.int32)
    rows, nxt = [], 1
    for n in totals:
        need = -(-n // bs)
        row = np.zeros(width, np.int32)
        row[:need] = np.arange(nxt, nxt + need)
        nxt += need
        rows.append(row)
    slots = check_slots(len(totals), runner.state_slots)
    got: List[Any] = []  # (row, position, logits [V])
    largest = runner.prefill_buckets[-1]
    for i, n in enumerate(prompt_lens):
        start = 0
        while start < n:
            c = min(largest, n - start)
            logits = runner.prefill_chunk(tokens[i, start : start + c], rows[i], start, slot=slots[i])
            start += c
        got.append((i, n - 1, logits))
    pools = [_slots_of(runner, slots)]
    for d in range(decode_steps):
        poss = [n + d for n in prompt_lens]
        logits = runner.decode(
            [int(tokens[i, p]) for i, p in enumerate(poss)], poss, rows, [p + 1 for p in poss],
            slots=slots,
        )
        got += [(i, p, logits[i]) for i, p in enumerate(poss)]
    pools.append(_slots_of(runner, slots))
    want, kept = reference(
        model, runner.params, tokens, [(i, p) for i, p, _ in got], [(n, t) for n, t in zip(prompt_lens, totals)]
    )
    return {
        "positions": [[i, p] for i, p, _ in got],
        "rel_err": [float(np.max(np.abs(have - ref)) / np.max(np.abs(ref)))
                    for (_, _, have), ref in zip(got, want)],
        "finite": bool(all(np.all(np.isfinite(h)) for _, _, h in got)),
        "state": pool_states(pools, kept),
    }


def as_the_pool_lies(S):
    """The reference's state of one layer ``[H, dk, dv]`` as the pool keeps a
    slot's: the heads joined along the lanes, ``[dk, H x dv]``."""
    S = np.asarray(S, np.float32)
    return S.transpose(1, 0, 2).reshape(S.shape[1], -1)


def pool_states(pools: List[Dict[str, Any]], kept: List[List[Any]]) -> Dict[str, Any]:
    """What the serving programs left in the state pool (``pools``: the driven
    slots' rows after the prefill and after the last decode step) against the
    reference's (``kept``: per driven sequence and recurrent layer, per
    instant, ``(S [H, dk, dv], tail [K - 1, 2 H dk + H dv])``): per instant,
    array and layer the largest, over the driven slots, of ``|have - want| /
    |want|`` (Frobenius). ``worst``: per instant and array (``state``,
    ``tail``), ``first`` (the first recurrent layer: its input is the embedding
    alone, the same on both sides) and ``deep`` (every layer)."""

    def rel(have, want):
        return float(np.linalg.norm(have - want.reshape(-1)) / np.linalg.norm(want))

    lay = (as_the_pool_lies, lambda tail: np.asarray(tail, np.float32))
    by_layer, worst = {}, {}
    for when, (instant, pool) in enumerate(zip(("prefill", "decode"), pools)):
        for which, (name, short) in enumerate((("gdn_state", "state"), ("gdn_conv", "tail"))):
            readings = [
                max(rel(pool[name][layer, i], lay[which](seq[layer][when][which])) for i, seq in enumerate(kept))
                for layer in range(pool[name].shape[0])
            ]
            by_layer[f"{instant}.{short}"] = readings
            worst[f"{instant}.{short}.first"], worst[f"{instant}.{short}.deep"] = readings[0], max(readings)
    return {"by_layer": by_layer, "worst": worst,
            "finite": bool(all(np.all(np.isfinite(a)) for pool in pools for a in pool.values()))}


def gdn_alone(runner, model: Dict[str, Any], seed: int, reference_gdn: Callable) -> Dict[str, Any]:
    """Per checked Gated DeltaNet layer: the largest, over the real rows, of
    ``max|mix - reference| / max|reference|`` over a row's outputs, the
    program's mixer run as the serving steps run it: a chunk from a zero state,
    a chunk with a padded tail, a chunk of ONE real row (shorter than the taps)
    in the smallest bucket, then one-position steps over a one-layer pool of
    the decode batch's slots in slot order (the kernel where it serves; one
    real slot, the others hold 7s and nobody holds them). ``worst``: per phase
    (``chunks``, ``decode``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import olmo_hybrid as oh
    from ray_tpu.ops import kda

    cfg = runner.cfg
    C, small, B = runner.prefill_buckets[-1], runner.prefill_buckets[0], runner.decode_buckets[-1]
    H, keep = cfg.gdn_heads, cfg.conv_kernel - 1
    n2 = max(1, int(C * TAIL_SHARE))
    windows = ((C, C), (C, n2), (small, 1))  # (bucket, real rows)
    T = sum(n for _, n in windows) + DECODE_STEPS
    n_slots, slot = B + 1, 1 + B // 3
    rng = np.random.default_rng([int(seed), 97])
    chunk = jax.jit(lambda p, x, S, tail, n: oh._gdn_mix(
        cfg, p, x[None], S, tail, (jnp.arange(x.shape[0]) < n)[None]))
    (_, shape, _), _ = oh.state_layout(cfg).arrays
    in_kernel = kda.kernel_serves(jax.ShapeDtypeStruct((1, n_slots, *shape), jnp.float32), heads=H)
    held = jnp.arange(n_slots) == slot

    def step(p, x_t, pool, tails):
        x = jnp.zeros((n_slots, 1, cfg.dim), cfg.dtype).at[slot, 0].set(x_t)
        if in_kernel:
            recur = lambda *a: oh._gdn_in_pool(0, jnp.zeros((n_slots,), bool), *a)  # noqa: E731
            y, pool, tails = oh._gdn_mix(cfg, p, x, pool, tails, held[:, None], recur)
        else:
            y, S, tails = oh._gdn_mix(cfg, p, x, kda.heads_apart(pool[0], H), tails, held[:, None])
            pool = pool.at[0].set(kda.heads_joined(S))
        return y[slot, 0], pool, tails

    step = jax.jit(step, donate_argnums=(2, 3))
    layers = [p for p in runner.params["layers"] if "gdn_wqkv" in p]
    out: Dict[str, Any] = {"by_layer": {"chunks": [], "decode": []}, "worst": {}, "finite": True}
    for i in _spread(len(layers)):
        p = layers[i]
        x = jnp.asarray(rng.standard_normal((T, cfg.dim)).astype(np.float32), cfg.dtype)  # unit RMS, as the stream is
        want = np.asarray(reference_gdn(model, p, x.astype(jnp.float32)))
        S = jnp.zeros((1, H, cfg.gdn_key_dim, cfg.gdn_value_dim), jnp.float32)
        tail = jnp.zeros((1, keep, cfg.conv_width), cfg.dtype)
        have, start = [], 0
        for bucket, n in windows:
            padded = jnp.full((bucket, cfg.dim), 100.0, cfg.dtype).at[:n].set(x[start : start + n])
            y, S, tail = chunk(p, padded, S, tail, jnp.int32(n))
            have.append(np.asarray(y[0, :n], np.float32))
            start += n
        pool = jnp.full((1, n_slots, *shape), 7.0, jnp.float32).at[0, slot].set(kda.heads_joined(S[0]))
        tails = jnp.full((n_slots, keep, cfg.conv_width), 7.0, cfg.dtype).at[slot].set(tail[0])
        for t in range(start, T):
            y, pool, tails = step(p, x[t], pool, tails)
            have.append(np.asarray(y[None], np.float32))
        have = np.concatenate(have)
        err = _row_err(have, want)
        out["by_layer"]["chunks"].append(float(np.max(err[:start])))
        out["by_layer"]["decode"].append(float(np.max(err[start:])))
        others = np.asarray(pool[0])[np.arange(n_slots) != slot]
        out["finite"] &= bool(np.all(np.isfinite(have)) and np.all(others == 7.0))
    out["worst"] = {k: max(v) for k, v in out["by_layer"].items()}
    return out


def attn_alone(runner, model: Dict[str, Any], seed: int, reference_attention: Callable) -> Dict[str, Any]:
    """Per checked attending layer: the largest, over the rows, of ``max|mix
    - reference| / max|reference|`` over a row's outputs, the program's
    attention mixer as the serving steps run it on a fresh one-layer cache: a
    prefill chunk from an empty context (the flash kernel on a TPU), then
    one-position steps at the decode batch's shape (the paged kernel on a
    TPU; one real slot, the rest padding), against the reference's causal
    attention on the same activations. ``worst``: per phase."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import olmo_hybrid as oh

    cfg, bs = runner.cfg, runner.block_size
    C, M, B = runner.prefill_buckets[-1], runner.max_blocks_per_seq, runner.decode_buckets[-1]
    T = C + DECODE_STEPS
    rng = np.random.default_rng([int(seed), 96])
    fresh = {k: v[:1] for k, v in oh.cache_layout(cfg, bs, runner.cache["k"].dtype).init(M + 8).items()}
    table = jnp.arange(1, M + 1, dtype=jnp.int32)
    tables = jnp.zeros((B, M), jnp.int32).at[0].set(table)

    @jax.jit
    def chunk(p, cache, x):
        pos = jnp.arange(C, dtype=jnp.int32)[None]
        cache, y = oh._attention_mix(cfg, p, cache, 0, x[None], pos, jnp.ones((1, C), bool), table[None])
        return cache, y[0]

    @jax.jit
    def step(p, cache, x, t):
        pos = jnp.zeros((B, 1), jnp.int32).at[0, 0].set(t)
        h = jnp.zeros((B, 1, cfg.dim), cfg.dtype).at[0, 0].set(x)
        cache, y = oh._attention_mix(cfg, p, cache, 0, h, pos, (jnp.arange(B) == 0)[:, None], tables)
        return cache, y[0]

    layers = [p for p in runner.params["layers"] if "wq" in p]
    out: Dict[str, Any] = {"by_layer": {"chunk": [], "decode": []}, "worst": {}, "finite": True}
    for i in _spread(len(layers)):
        p = layers[i]
        x = jnp.asarray(rng.standard_normal((T, cfg.dim)).astype(np.float32), cfg.dtype)
        want = np.asarray(reference_attention(model, p, x.astype(jnp.float32)))
        cache, y = chunk(p, dict(fresh), x[:C])
        have = [np.asarray(y, np.float32)]
        for t in range(C, T):
            cache, y = step(p, cache, x[t], jnp.int32(t))
            have.append(np.asarray(y, np.float32))
        have = np.concatenate(have)
        err = _row_err(have, want)
        out["by_layer"]["chunk"].append(float(np.max(err[:C])))
        out["by_layer"]["decode"].append(float(np.max(err[C:])))
        out["finite"] &= bool(np.all(np.isfinite(have)))
    out["worst"] = {k: max(v) for k, v in out["by_layer"].items()}
    return out


class BenchOlmoHybridServer(BenchServer, LLMServer):
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        """The four readings (the module's docstring). The engine must be
        idle: the check writes into blocks 1.. and state slots 1.. of the
        free pools; a later request's first chunk starts its slot from zeros."""
        family = families.of(model)
        runner = self.engine.runner
        t0 = time.monotonic()
        got = drive(runner, model, seed, prompt_lens, decode_steps, family.reference_logits_and_state)
        took = {"drive+reference": time.monotonic() - t0}
        limits = model["correctness"]

        def enter(name: str, what: str, reading: float, limit: str) -> None:
            got["positions"].append([name, what, round(reading, 6), limits[limit]])
            got["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * reading)

        # the logits' entries: a sequence's worst over its positions (its reading under ``logit_rel_tol``)
        rows = sorted({i for i, _ in got["positions"]})
        worst = [max(e for (r, _), e in zip(got["positions"], got["rel_err"]) if r == i) for i in rows]
        got["positions"] = [[i, "worst of its positions", round(e, 5)] for i, e in zip(rows, worst)]
        got["rel_err"] = worst
        state = got.pop("state")
        for what, reading in state["worst"].items():
            _, array, depth = what.split(".")
            enter("state", what, reading, array + {"first": "_rel_tol", "deep": "_deep_rel_tol"}[depth])
        got["finite"] = bool(got["finite"] and state["finite"])
        got["state"] = state
        for name, limit, reads, reference in (
            ("gdn", "gdn_rel_tol", gdn_alone, family.reference_gdn),
            ("attn", "attn_rel_tol", attn_alone, family.reference_attention),
        ):
            t0 = time.monotonic()
            alone = reads(runner, model, seed, reference)
            took[name] = time.monotonic() - t0
            for what, reading in alone["worst"].items():
                enter(name, what, reading, limit)
            got["finite"] = bool(got["finite"] and alone["finite"])
            got[name] = alone
        # where the check's seconds went (set-up time: the reference's passes are most of it)
        print("[olmo_hybrid] check: " + ", ".join(f"{k} {v:.1f}s" for k, v in took.items()), file=sys.stderr)
        return got
