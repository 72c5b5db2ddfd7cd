"""The deployment class of this family's serving cells: the program's
``LLMServer`` with the benchmark's general additions (``harness/server.py``)
and this family's own ``bench_check``.

Why its own. The program's runner takes a STATE SLOT a sequence beside the
block-table row (``prefill_chunk(slot=...)``, ``decode(slots=...)``), which the
harness's check does not hand over, so the check's drive is here
(:func:`drive`: the harness's, its sequences on slots scattered over the pool,
as ``families/kimi_linear/server.py``'s).

Five readings, each entered as a share of ITS limit times ``logit_rel_tol``
(the harness holds the worst entry to that one number; every entry carries
its reading, and one that is not a logit's its own limit too, so that the
harness's line shows each beside the other):

* the logits after the whole model, under ``logit_rel_tol``, from the
  programs the window launches: at each sequence's last prompt position and
  at the first, second and last decode step;
* THE STATE POOL as those programs left it (:func:`pool_tails`), read TWICE:
  after the chunked prefill (the prefill program's slice of one slot across
  its chunk edges, a padded tail and a chunk SHORTER than the taps) and after
  the last decode step (the decode program's rows of each layer's slab, step
  after step), against the reference's ``z`` at the sequence's last two
  positions, as ``|have - want| / |want|`` over a layer's tail (Frobenius). The
  first convolution layer, whose input is the embedding alone, under
  ``state_rel_tol``; every layer's under ``state_deep_rel_tol`` (the deeper
  layers' inputs carry the routing noise of the layers before them);
* a CONVOLUTION mixer ALONE under ``conv_rel_tol`` (:func:`conv_alone`): the
  program's mixer from a zero tail over a chunk, a second chunk with a padded
  tail that holds anything, a third of ONE real row, then decode steps at the
  decode batch's shape on a pool of its own, against the reference's
  convolution over the whole sequence on the same activations;
* an ATTENTION mixer ALONE under ``attn_rel_tol`` (:func:`attn_alone`): the
  program's ``_attention_mix`` (head norms, rotation, the write, the flash
  kernel over a chunk from an empty context, then the paged kernel over
  decode steps at the decode batch's shape) on a one-layer cache, against the
  reference's causal attention on the same activations;
* the expert FFN ALONE under ``expert_ffn_rel_tol``, as the ``olmoe``,
  ``xing4`` and ``kimi_linear`` families read it."""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer
from ..kimi_linear.server import check_slots, compared_steps

#: in score + bias; the two float32 routers agree to about 1e-6
TIE_MARGIN = 1e-4
#: real rows of the second chunk of :func:`conv_alone` as a share of the bucket, and the decode steps alone
TAIL_SHARE, DECODE_STEPS = 0.7, 4


def drive(runner, model: Dict[str, Any], seed: int, prompt_lens: List[int],
          decode_steps: int, reference: Callable) -> Dict[str, Any]:
    """``harness/server.py::BenchServer.bench_check``'s drive with a state
    slot a sequence: prefill in chunks then teacher-forced decode through
    both pools with the runner's warmed programs (the ones the window
    launches), against ``reference`` over the same tokens from the same
    weights; the state pool as those programs left it after the prefill and
    after the last step (:func:`pool_tails`), under ``"state"``."""
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
    compared = compared_steps(decode_steps)
    for d in range(decode_steps):
        poss = [n + d for n in prompt_lens]
        logits = runner.decode(
            [int(tokens[i, p]) for i, p in enumerate(poss)], poss, rows, [p + 1 for p in poss],
            slots=slots,
        )
        if d in compared:
            got += [(i, p, logits[i]) for i, p in enumerate(poss)]
    pools.append(_slots_of(runner, slots))
    want, tails = reference(
        model, runner.params, tokens, [(i, p) for i, p, _ in got], [(n, t) for n, t in zip(prompt_lens, totals)]
    )
    return {
        "positions": [[i, p] for i, p, _ in got],
        "rel_err": [float(np.max(np.abs(have - ref)) / np.max(np.abs(ref)))
                    for (_, _, have), ref in zip(got, want)],
        "finite": bool(all(np.all(np.isfinite(h)) for _, _, h in got)),
        "state": pool_tails(pools, tails),
    }


def _slots_of(runner, slots: List[int]):
    """The driven slots' rows of the state pool, ``[layers, len(slots), (K - 1) x D]`` float32."""
    return np.asarray(runner.state["conv_tail"][:, np.asarray(slots)], np.float32)


def pool_tails(pools: List[Any], tails: List[List[Any]]) -> Dict[str, Any]:
    """What the serving programs left in the state pool (``pools``: the driven
    slots' rows after the prefill and after the last decode step) against the
    reference's (``tails``: per driven sequence and convolution layer ``[2
    (after the prefill, after the last step), K - 1, D]``): per instant and
    layer the largest, over the driven slots, of ``|have - want| / |want|``
    (Frobenius). ``worst``: per instant, ``first`` (the first convolution
    layer: its input is the embedding alone, the same on both sides) and
    ``deep`` (every layer)."""

    def rel(have, want):
        return float(np.linalg.norm(have - want) / np.linalg.norm(want))

    by_layer, worst = {}, {}
    for when, (name, pool) in enumerate(zip(("prefill", "decode"), pools)):
        by_layer[name] = [
            max(rel(pool[layer, i], np.asarray(seq[layer][when], np.float32).reshape(-1))
                for i, seq in enumerate(tails))
            for layer in range(pool.shape[0])
        ]
        worst[f"{name}.first"], worst[f"{name}.deep"] = by_layer[name][0], max(by_layer[name])
    return {"by_layer": by_layer, "worst": worst,
            "finite": bool(all(np.all(np.isfinite(pool)) for pool in pools))}


def _spread(n: int) -> List[int]:
    """Three indices spread over ``range(n)``."""
    return sorted({0, n // 2, n - 1})


def _row_err(have, want):
    """Per row ``max|have - want| / max|want|`` over the row's outputs."""
    return np.max(np.abs(have - want), axis=-1) / np.max(np.abs(want), axis=-1)


def conv_alone(runner, model: Dict[str, Any], seed: int, reference_conv: Callable) -> Dict[str, Any]:
    """Per checked convolution layer: the largest, over the real rows, of
    ``max|mix - reference| / max|reference|`` over a row's outputs, the
    program's mixer run as the serving steps run it: a chunk, a chunk with a
    padded tail, a chunk of ONE real row (shorter than the taps), then
    one-position steps at the decode batch's shape on a pool of its own (one
    real slot, the rest padding on the null slot). ``worst``: per phase
    (``chunks``, ``decode``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import lfm2

    cfg = runner.cfg
    C, B = runner.prefill_buckets[-1], runner.decode_buckets[-1]
    n2 = max(1, int(C * TAIL_SHARE))
    lens = (C, n2, 1)
    T = sum(lens) + DECODE_STEPS
    keep, slot = cfg.conv_kernel - 1, 1 + B // 3
    rng = np.random.default_rng([int(seed), 97])
    chunk = jax.jit(lambda p, h, tail, n: lfm2._conv_chunk(p, h, tail, n))
    step = jax.jit(lambda p, h, pool, slots, fresh: lfm2._conv_step(p, h, pool, 0, slots, fresh))
    layers = [p for p in runner.params["layers"] if "conv_in" in p]
    out: Dict[str, Any] = {"by_layer": {"chunks": [], "decode": []}, "worst": {}, "finite": True}
    for i in _spread(len(layers)):
        p = layers[i]
        u = jnp.asarray(rng.standard_normal((T, cfg.dim)).astype(np.float32), cfg.dtype)  # unit RMS, as a norm leaves them
        want = np.asarray(reference_conv(model, p, u.astype(jnp.float32)))
        tail = jnp.zeros((1, keep, cfg.dim), cfg.dtype)
        have, start = [], 0
        for n in lens:
            padded = jnp.full((C, cfg.dim), 100.0, cfg.dtype).at[:n].set(u[start : start + n])
            y, tail = chunk(p, padded[None], tail, jnp.full((1,), n, jnp.int32))
            have.append(np.asarray(y[0, :n], np.float32))
            start += n
        # the decode batch: one slot goes on, the others are padding on the null slot
        pool = jnp.full((1, B + 1, keep * cfg.dim), 7.0, cfg.dtype).at[0, slot].set(tail.reshape(-1))
        slots = jnp.zeros((B,), jnp.int32).at[0].set(slot)
        for t in range(start, T):
            y, pool = step(p, jnp.zeros((B, cfg.dim), cfg.dtype).at[0].set(u[t]), pool, slots,
                           jnp.zeros((B,), bool))
            have.append(np.asarray(y[:1], np.float32))
        have = np.concatenate(have)
        err = _row_err(have, want)
        out["by_layer"]["chunks"].append(float(np.max(err[:start])))
        out["by_layer"]["decode"].append(float(np.max(err[start:])))
        out["finite"] &= bool(np.all(np.isfinite(have)))
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

    from ray_tpu.models import lfm2

    cfg, bs = runner.cfg, runner.block_size
    C, M, B = runner.prefill_buckets[-1], runner.max_blocks_per_seq, runner.decode_buckets[-1]
    T = C + DECODE_STEPS
    rng = np.random.default_rng([int(seed), 96])
    fresh = {k: v[:1] for k, v in lfm2.cache_layout(cfg, bs, runner.cache["k"].dtype).init(M + 8).items()}
    table = jnp.arange(1, M + 1, dtype=jnp.int32)
    tables = jnp.zeros((B, M), jnp.int32).at[0].set(table)

    @jax.jit
    def chunk(p, cache, u):
        pos = jnp.arange(C, dtype=jnp.int32)[None]
        cache, y = lfm2._attention_mix(cfg, p, cache, 0, u[None], pos, jnp.ones((1, C), bool), table[None])
        return cache, y[0]

    @jax.jit
    def step(p, cache, u, t):
        pos = jnp.zeros((B, 1), jnp.int32).at[0, 0].set(t)
        h = jnp.zeros((B, 1, cfg.dim), cfg.dtype).at[0, 0].set(u)
        cache, y = lfm2._attention_mix(cfg, p, cache, 0, h, pos, (jnp.arange(B) == 0)[:, None], tables)
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


def expert_ffn_alone(runner, model: Dict[str, Any], seed: int, reference_ffn: Callable) -> Dict[str, Any]:
    """Per shape (rows of the launch) and checked layer: the largest, over
    the compared real rows, of ``max|ffn - reference| / max|reference|`` over
    a row's outputs (``families/xing4/server.py`` says why), the row's own
    ``max|reference|`` or the median row's, whichever is larger. ``worst``: per shape."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import lfm2

    cfg = runner.cfg
    ffn = jax.jit(lambda p, h, valid: lfm2._ffn(cfg, p, h[None], valid[None], True)[0][0])
    rng = np.random.default_rng([int(seed), 98])
    out: Dict[str, Any] = {"by_layer": {}, "worst": {}, "not_compared": 0, "finite": True}
    layers = [p for p in runner.params["layers"] if "router" in p]
    for rows in (runner.prefill_buckets[-1], runner.decode_buckets[-1]):
        real = rows - rows // 8
        valid = jnp.arange(rows) < real
        readings = []
        for i in _spread(len(layers)):
            p = layers[i]
            h = rng.standard_normal((rows, cfg.dim)).astype(np.float32)
            h[real:] *= 100.0  # the padding rows hold anything
            h = jnp.asarray(h, cfg.dtype)
            have = np.asarray(ffn(p, h, valid), np.float32)
            want, margin = reference_ffn(model, p, h[:real].astype(jnp.float32))
            want, margin = np.asarray(want), np.asarray(margin)
            sure = margin > TIE_MARGIN
            # a row none of whose 4 experts is held here has a reference of zeros (no shared expert
            # stands under it), and one whose only held expert has a small gate a small one: a row
            # is held to its own largest output or the median row's, whichever is larger
            scale = np.max(np.abs(want), axis=-1)
            err = np.max(np.abs(have[:real] - want), axis=-1) / np.maximum(scale, np.median(scale))
            readings.append(float(np.max(err[sure])))
            out["not_compared"] += int(real - sure.sum())
            out["finite"] &= bool(np.all(np.isfinite(have)))
        out["by_layer"][str(rows)] = readings
        out["worst"][str(rows)] = max(readings)
    return out


class BenchLfm2Server(BenchServer, LLMServer):
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        """The five readings (the module's docstring). The engine must be
        idle: the check writes into blocks 1.. and state slots 1.. of the
        free pools; a later request's first chunk starts its slot from zeros."""
        family = families.of(model)
        runner = self.engine.runner
        got = drive(runner, model, seed, prompt_lens, decode_steps, family.reference_logits_and_tails)
        limits = model["correctness"]

        def enter(name: str, what: str, reading: float, limit: str) -> None:
            got["positions"].append([name, what, round(reading, 6), limits[limit]])
            got["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * reading)

        # a logit's entry: the sequence, the position, its reading (under ``logit_rel_tol``)
        got["positions"] = [[i, p, round(err, 4)] for (i, p), err in zip(got["positions"], got["rel_err"])]
        state = got.pop("state")
        for what, reading in state["worst"].items():
            enter("state", what, reading, "state_rel_tol" if what.endswith("first") else "state_deep_rel_tol")
        got["finite"] = bool(got["finite"] and state["finite"])
        got["state"] = state
        for name, limit, alone in (
            ("expert_ffn", "expert_ffn_rel_tol",
             expert_ffn_alone(runner, model, seed, family.reference_expert_ffn)),
            ("conv", "conv_rel_tol", conv_alone(runner, model, seed, family.reference_conv)),
            ("attn", "attn_rel_tol", attn_alone(runner, model, seed, family.reference_attention)),
        ):
            for what, reading in alone["worst"].items():
                enter(name, what, reading, limit)
            got["finite"] = bool(got["finite"] and alone["finite"])
            got[name] = alone
        return got
