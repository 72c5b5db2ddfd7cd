"""The deployment class of this family's serving cells: the program's
``LLMServer`` with the benchmark's general additions (``harness/server.py``)
and this family's own ``bench_check``.

Why its own. The program's runner takes a STATE SLOT a sequence beside the
block-table row (``prefill_chunk(slot=...)``, ``decode(slots=...)``), which the
harness's check does not hand over, so the check's drive is here
(:func:`drive`: the harness's, its sequences on slots scattered over the pool,
as ``families/lfm2/server.py``'s).

Four readings, each entered as a share of ITS limit times ``logit_rel_tol``
(the harness holds the worst entry to that one number; every entry carries
its reading, and one that is not a logit's its own limit too, so that the
harness's line shows each beside the other):

* the logits after the whole model, under ``logit_rel_tol``, from the
  programs the window launches: at each sequence's last prompt position and
  after EVERY decode step;
* THE STATE POOL as those programs left it (:func:`pool_states`), read TWICE:
  after the chunked prefill (the prefill program's slot across its chunk
  edges, a padded tail and a chunk of ONE row, shorter than the taps) and
  after the last decode step (the decode program's slots of each layer's
  slab, step after step), against the reference's ``h`` after the sequence's
  last position and its convolution's last three inputs, as ``|have - want| /
  |want|`` over a layer's array (Frobenius), the largest over the driven
  slots. The FIRST Mamba layer, whose input is the embedding alone, under
  ``state_rel_tol`` (``h``) and ``tail_rel_tol`` (the tail); every layer's
  under ``state_deep_rel_tol`` and ``tail_deep_rel_tol`` (the deeper layers'
  inputs carry the bfloat16 rounding of the residual stream before them);
* a MAMBA mixer ALONE under ``mamba_rel_tol`` (:func:`mamba_alone`): the
  program's mixer from a fresh slot over a chunk, a second chunk with a padded
  tail that holds anything, a third of ONE real row, then decode steps at the
  decode batch's shape on pools of its own, against the reference's mixer over
  the whole sequence on the same activations;
* an ATTENTION mixer ALONE under ``attn_rel_tol`` (:func:`attn_alone`): the
  program's ``_attention_mix`` (20 query heads over ONE KV head, no position
  term; the write, the flash kernel over a chunk from an empty context, then
  the paged kernel over decode steps at the decode batch's shape) on a
  one-layer cache, against the reference's causal attention on the same
  activations."""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer
from ..kimi_linear.server import check_slots
from ..lfm2.server import _row_err, _spread

#: real rows of the second chunk of :func:`mamba_alone` as a share of the bucket, and the decode steps alone
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


def _slots_of(runner, slots: List[int]) -> Dict[str, Any]:
    """The driven slots' rows of both state arrays, ``[layers, len(slots), numbers]`` float32."""
    at = np.asarray(slots)
    return {name: np.asarray(a[:, at], np.float32).reshape(a.shape[0], len(slots), -1)
            for name, a in runner.state.items()}


def pool_states(pools: List[Dict[str, Any]], kept: List[List[Any]]) -> Dict[str, Any]:
    """What the serving programs left in the state pool (``pools``: the driven
    slots' rows after the prefill and after the last decode step) against the
    reference's (``kept``: per driven sequence and Mamba layer, per instant,
    ``(h [N, Di], tail [K - 1, Di])``): per instant, array and layer the
    largest, over the driven slots, of ``|have - want| / |want|`` (Frobenius).
    ``worst``: per instant and array (``ssm``, ``tail``), ``first`` (the first
    Mamba layer: its input is the embedding alone, the same on both sides) and
    ``deep`` (every layer: the deeper layers' inputs carry the rounding of the
    layers before them)."""

    def rel(have, want):
        return float(np.linalg.norm(have - want.reshape(-1)) / np.linalg.norm(want))

    by_layer, worst = {}, {}
    for when, (instant, pool) in enumerate(zip(("prefill", "decode"), pools)):
        for which, (name, short) in enumerate((("ssm", "ssm"), ("conv_tail", "tail"))):
            readings = [
                max(rel(pool[name][layer, i], np.asarray(seq[layer][when][which], np.float32))
                    for i, seq in enumerate(kept))
                for layer in range(pool[name].shape[0])
            ]
            by_layer[f"{instant}.{short}"] = readings
            worst[f"{instant}.{short}.first"], worst[f"{instant}.{short}.deep"] = readings[0], max(readings)
    return {"by_layer": by_layer, "worst": worst,
            "finite": bool(all(np.all(np.isfinite(a)) for pool in pools for a in pool.values()))}


def mamba_alone(runner, model: Dict[str, Any], seed: int, reference_mamba: Callable) -> Dict[str, Any]:
    """Per checked Mamba layer: the largest, over the real rows, of ``max|mix
    - reference| / max|reference|`` over a row's outputs, the program's mixer
    run as the serving steps run it: a chunk from a FRESH slot that holds
    anything, a chunk with a padded tail, a chunk of ONE real row (shorter than
    the taps) in the smallest bucket, then one-position steps at the decode
    batch's shape on pools of its own (one real slot, the rest padding on the
    null slot). ``worst``: per phase (``chunks``, ``decode``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import jamba

    cfg = runner.cfg
    C, small, B = runner.prefill_buckets[-1], runner.prefill_buckets[0], runner.decode_buckets[-1]
    n2 = max(1, int(C * TAIL_SHARE))
    windows = ((C, C), (C, n2), (small, 1))  # (bucket, real rows)
    T = sum(n for _, n in windows) + DECODE_STEPS
    slot = 1 + B // 3
    rng = np.random.default_rng([int(seed), 97])
    chunk = jax.jit(
        lambda p, h, state, n, fresh: jamba._mamba_chunk(cfg, p, h, state, 0, jnp.int32(slot), fresh, n),
        donate_argnums=2,
    )
    step = jax.jit(lambda p, h, state, slots: jamba._mamba_step(
        cfg, p, h, state, 0, slots, jnp.zeros(slots.shape, bool)), donate_argnums=2)
    layers = [p for p in runner.params["layers"] if "in_proj" in p]
    out: Dict[str, Any] = {"by_layer": {"chunks": [], "decode": []}, "worst": {}, "finite": True}
    for i in _spread(len(layers)):
        p = layers[i]
        u = jnp.asarray(rng.standard_normal((T, cfg.dim)).astype(np.float32), cfg.dtype)  # unit RMS, as a norm leaves them
        want = np.asarray(reference_mamba(model, p, u.astype(jnp.float32)))
        # pools of ONE layer that hold anything: the first chunk is the slot's first
        state = {name: jnp.full((1, B + 1, *shape), 7.0, dtype)
                 for name, shape, dtype in jamba.state_layout(cfg).arrays}
        have, start = [], 0
        for bucket, n in windows:
            padded = jnp.full((bucket, cfg.dim), 100.0, cfg.dtype).at[:n].set(u[start : start + n])
            y, state = chunk(p, padded, state, jnp.int32(n), jnp.asarray(start == 0))
            have.append(np.asarray(y[:n], np.float32))
            start += n
        # the decode batch: one slot goes on, the others are padding on the null slot
        slots = jnp.zeros((B,), jnp.int32).at[0].set(slot)
        for t in range(start, T):
            y, state = step(p, jnp.zeros((B, cfg.dim), cfg.dtype).at[0].set(u[t]), state, slots)
            have.append(np.asarray(y[:1], np.float32))
        have = np.concatenate(have)
        err = _row_err(have, want)
        out["by_layer"]["chunks"].append(float(np.max(err[:start])))
        out["by_layer"]["decode"].append(float(np.max(err[start:])))
        out["finite"] &= bool(np.all(np.isfinite(have)))
    out["worst"] = {k: max(v) for k, v in out["by_layer"].items()}
    return out


def attn_alone(runner, model: Dict[str, Any], seed: int, reference_attention: Callable) -> Dict[str, Any]:
    """Per attending layer: the largest, over the rows, of ``max|mix -
    reference| / max|reference|`` over a row's outputs, the program's
    attention mixer as the serving steps run it on a fresh one-layer cache: a
    prefill chunk from an empty context (the flash kernel on a TPU), then
    one-position steps at the decode batch's shape (the paged kernel on a
    TPU; one real slot, the rest padding), against the reference's causal
    attention on the same activations. ``worst``: per phase."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import jamba

    cfg, bs = runner.cfg, runner.block_size
    C, M, B = runner.prefill_buckets[-1], runner.max_blocks_per_seq, runner.decode_buckets[-1]
    T = C + DECODE_STEPS
    rng = np.random.default_rng([int(seed), 96])
    fresh = {k: v[:1] for k, v in jamba.cache_layout(cfg, bs, runner.cache["k"].dtype).init(M + 8).items()}
    table = jnp.arange(1, M + 1, dtype=jnp.int32)
    tables = jnp.zeros((B, M), jnp.int32).at[0].set(table)

    @jax.jit
    def chunk(p, cache, u):
        pos = jnp.arange(C, dtype=jnp.int32)[None]
        cache, y = jamba._attention_mix(cfg, p, cache, 0, u[None], pos, jnp.ones((1, C), bool), table[None])
        return cache, y[0]

    @jax.jit
    def step(p, cache, u, t):
        pos = jnp.zeros((B, 1), jnp.int32).at[0, 0].set(t)
        h = jnp.zeros((B, 1, cfg.dim), cfg.dtype).at[0, 0].set(u)
        cache, y = jamba._attention_mix(cfg, p, cache, 0, h, pos, (jnp.arange(B) == 0)[:, None], tables)
        return cache, y[0]

    layers = [p for p in runner.params["layers"] if "wq" in p]
    out: Dict[str, Any] = {"by_layer": {"chunk": [], "decode": []}, "worst": {}, "finite": True}
    for i in _spread(len(layers)):
        p = layers[i]
        u = jnp.asarray(rng.standard_normal((T, cfg.dim)).astype(np.float32), cfg.dtype)
        want = np.asarray(reference_attention(model, p, u.astype(jnp.float32)))
        cache, y = chunk(p, dict(fresh), u[:C])
        have = [np.asarray(y, np.float32)]
        for t in range(C, T):
            cache, y = step(p, cache, u[t], jnp.int32(t))
            have.append(np.asarray(y, np.float32))
        have = np.concatenate(have)
        err = _row_err(have, want)
        out["by_layer"]["chunk"].append(float(np.max(err[:C])))
        out["by_layer"]["decode"].append(float(np.max(err[C:])))
        out["finite"] &= bool(np.all(np.isfinite(have)))
    out["worst"] = {k: max(v) for k, v in out["by_layer"].items()}
    return out


class BenchJambaServer(BenchServer, LLMServer):
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        """The four readings (the module's docstring). The engine must be
        idle: the check writes into blocks 1.. and state slots 1.. of the
        free pools; a later request's first chunk starts its slot from zeros."""
        family = families.of(model)
        runner = self.engine.runner
        got = drive(runner, model, seed, prompt_lens, decode_steps, family.reference_logits_and_state)
        limits = model["correctness"]

        def enter(name: str, what: str, reading: float, limit: str) -> None:
            got["positions"].append([name, what, round(reading, 6), limits[limit]])
            got["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * reading)

        # the logits' entries: a sequence's worst over its positions (its reading under ``logit_rel_tol``)
        rows = sorted({i for i, _ in got["positions"]})
        got["positions"] = [
            [i, "worst of its positions",
             round(max(e for (r, _), e in zip(got["positions"], got["rel_err"]) if r == i), 5)]
            for i in rows
        ]
        state = got.pop("state")
        for what, reading in state["worst"].items():
            _, array, depth = what.split(".")
            enter("state", what, reading, {"ssm": "state", "tail": "tail"}[array]
                  + {"first": "_rel_tol", "deep": "_deep_rel_tol"}[depth])
        got["finite"] = bool(got["finite"] and state["finite"])
        got["state"] = state
        for name, limit, alone in (
            ("mamba", "mamba_rel_tol", mamba_alone(runner, model, seed, family.reference_mamba)),
            ("attn", "attn_rel_tol", attn_alone(runner, model, seed, family.reference_attention)),
        ):
            for what, reading in alone["worst"].items():
                enter(name, what, reading, limit)
            got["finite"] = bool(got["finite"] and alone["finite"])
            got[name] = alone
        return got
