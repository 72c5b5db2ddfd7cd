"""The deployment class of this family's serving cells: the program's
``LLMServer`` with the benchmark's general additions (``harness/server.py``)
and this family's own ``bench_check``.

Why its own. The program's runner takes a STATE SLOT a sequence beside the
block-table row (``prefill_chunk(slot=...)``, ``decode(slots=...)``), which the
harness's check does not hand over, so the check's drive is here
(:func:`drive`: the harness's, its sequences on slots scattered over the pool).

Five readings, each entered as a share of ITS limit times ``logit_rel_tol``
(the harness holds the worst entry to that one number):

* the logits after the whole model, under ``logit_rel_tol``, from the
  programs the window launches (the ONE decode program returns the logits
  beside the picks an all-greedy batch reads back);
* THE STATE POOL after that drive (:func:`pool_state`): what the timed
  programs left in ``runner.state`` for each driven slot (the prefill
  program's slice of one slot across its chunk edges and a padded tail, the
  decode program's whole slab in slot order, step after step) against what
  the reference's recurrence leaves after the same tokens, as ``|have - want|
  / |want|`` over a layer's state (Frobenius). The first KDA layer, whose
  input is the embedding alone, under ``state_rel_tol``: it tells a pool kept
  in bfloat16, a slot mix-up and a carry lost at a chunk's edge; every KDA
  layer's state and convolution tail under ``state_deep_rel_tol`` (the deeper
  layers' inputs carry the routing noise of the layers before them);
* the expert FFN ALONE under ``expert_ffn_rel_tol``, as the ``olmoe`` and
  ``xing4`` families read it: a hard top-8 choice flips on bfloat16's
  rounding and the logits' limit has to leave room for that;
* a KDA layer ALONE under ``kda_rel_tol`` (:func:`kda_alone`): the
  program's mixer (``ray_tpu.models.kimi_linear._kda_mix``: convolution,
  gates, the chunked form, the one-token update, the head norm and output
  gate) from a zero state over TWO prefill chunks, the second with a padded
  tail, then decode steps at the decode batch's shape, against the
  reference's token-by-token recurrence (``reference.kda``) on the SAME
  normed activations, with the weights of three KDA layers spread over the
  depth: the mixer's OUTPUTS, which the pool's reading does not see;
* a latent attention layer ALONE under ``mla_rel_tol`` (:func:`mla_alone`):
  the logits swing with the hard top-8 choice of 26 expert layers (one run
  in eleven read 0.197 where the others read 0.03-0.06), more than a rotated
  shared key moves them (0.05-0.15); this reading is what tells that one."""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer

#: in score + bias; the two float32 routers agree to about 1e-6
TIE_MARGIN = 1e-4
#: real rows of the second chunk of :func:`kda_alone` as a share of the bucket, and its decode steps
KDA_TAIL_SHARE, KDA_DECODE_STEPS = 0.7, 4


def check_slots(n: int, pool: int) -> List[int]:
    """State slots for the check's ``n`` sequences, scattered over a pool of
    ``pool`` usable slots (1..pool), no two alike: neighbours and the ends
    are not what a mix-up would leave alone."""
    if n > pool:
        raise ValueError(f"the check drives {n} sequences at once and the pool has {pool} slots")
    step = next(s for s in (29, 13, 7, 5, 3, 1) if np.gcd(s, pool) == 1)
    return [1 + (17 + step * i) % pool for i in range(n)]


def compared_steps(decode_steps: int) -> List[int]:
    """The decode steps whose logits are compared: the first two and the
    last (every step feeds the state's reading; the logits' reading is
    heavy-tailed, and its limit was read over a handful of positions a run)."""
    return sorted({0, 1, decode_steps - 1} & set(range(decode_steps)))


def drive(runner, model: Dict[str, Any], seed: int, prompt_lens: List[int],
          decode_steps: int, reference_logits: Callable) -> Dict[str, Any]:
    """``harness/server.py::BenchServer.bench_check``'s drive with a state
    slot a sequence: prefill in chunks then teacher-forced decode through
    both pools with the runner's warmed programs (the ones the window
    launches), against ``reference_logits`` over the same tokens from the
    same weights; then the state pool as those programs left it
    (:func:`pool_state`), under ``"state"``."""
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
    compared = compared_steps(decode_steps)
    for d in range(decode_steps):
        poss = [n + d for n in prompt_lens]
        logits = runner.decode(
            [int(tokens[i, p]) for i, p in enumerate(poss)], poss, rows, [p + 1 for p in poss],
            slots=slots,
        )
        if d in compared:
            got += [(i, p, logits[i]) for i, p in enumerate(poss)]
    want, states = reference_logits(model, runner.params, tokens, [(i, p) for i, p, _ in got], totals)
    return {
        "positions": [[i, p] for i, p, _ in got],
        "rel_err": [float(np.max(np.abs(have - ref)) / np.max(np.abs(ref)))
                    for (_, _, have), ref in zip(got, want)],
        "finite": bool(all(np.all(np.isfinite(h)) for _, _, h in got)),
        "state": pool_state(runner, slots, states),
    }


def pool_state(runner, slots: List[int], states: List[List[Any]]) -> Dict[str, Any]:
    """What the serving programs left in the state pool against what the
    reference's recurrence leaves (``states``: per driven sequence and KDA
    layer ``(S [H, dk, dv], tail [K - 1, 3 W])``): per layer the largest, over
    the driven slots, of ``|have - want| / |want|`` (Frobenius) of the matrix
    state and of the convolution's tail. ``worst``: ``first`` (the first KDA
    layer's state: its input is the embedding alone, the same on both sides)
    and ``deep`` (every layer's state and tail)."""
    pool = {k: np.asarray(v[:, np.asarray(slots)], np.float32) for k, v in runner.state.items()}

    def rel(have, want):
        return float(np.linalg.norm(have - want) / np.linalg.norm(want))

    n_layers = pool["kda_state"].shape[0]
    by_layer = {"kda_state": [], "kda_conv": []}
    for layer in range(n_layers):
        for name, which in (("kda_state", 0), ("kda_conv", 1)):
            by_layer[name].append(max(
                rel(pool[name][layer, i].reshape(-1), np.asarray(seq[layer][which], np.float32).reshape(-1))
                for i, seq in enumerate(states)
            ))
    return {
        "by_layer": by_layer,
        "worst": {"first": by_layer["kda_state"][0],
                  "deep": max(by_layer["kda_state"] + by_layer["kda_conv"])},
        "finite": bool(all(np.all(np.isfinite(a)) for a in pool.values())),
    }


def _spread(n: int) -> List[int]:
    """Three indices spread over ``range(n)``."""
    return sorted({0, n // 2, n - 1})


def kda_alone(runner, model: Dict[str, Any], seed: int, reference_kda: Callable) -> Dict[str, Any]:
    """Per checked KDA layer: the largest, over the real rows, of ``max|mix -
    reference| / max|reference|`` over a row's outputs, the program's mixer
    run as the serving steps run it: chunk, chunk with a padded tail, then
    one-token updates at the decode batch's shape (one real slot, the rest
    padding). ``worst``: per phase (``chunks``, ``decode``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import kimi_linear as kl

    cfg = runner.cfg
    C, B = runner.prefill_buckets[-1], runner.decode_buckets[-1]
    n2 = max(1, int(C * KDA_TAIL_SHARE))
    T = C + n2 + KDA_DECODE_STEPS
    rng = np.random.default_rng([int(seed), 97])
    mix = jax.jit(lambda p, h, S, tail, valid: kl._kda_mix(cfg, p, h, S, tail, valid))
    layers = [p for p in runner.params["layers"] if "kda_wqkv" in p]
    out: Dict[str, Any] = {"by_layer": {"chunks": [], "decode": []}, "worst": {}, "finite": True}
    for i in _spread(len(layers)):
        p = layers[i]
        h = jnp.asarray(rng.standard_normal((T, cfg.dim)).astype(np.float32), cfg.dtype)  # unit RMS, as a norm leaves them
        want = np.asarray(reference_kda(model, p, h.astype(jnp.float32)))
        S = jnp.zeros((1, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32)
        tail = jnp.zeros((1, cfg.conv_kernel - 1, 3 * cfg.kda_width), cfg.dtype)
        have = []
        y, S, tail = mix(p, h[None, :C], S, tail, jnp.ones((1, C), bool))
        have.append(np.asarray(y[0], np.float32))
        padded = jnp.zeros((C, cfg.dim), cfg.dtype).at[:n2].set(h[C : C + n2]).at[n2:].set(100.0)
        y, S, tail = mix(p, padded[None], S, tail, (jnp.arange(C) < n2)[None])
        have.append(np.asarray(y[0, :n2], np.float32))
        # the decode batch: slot 0 goes on, the others are padding on a zero state
        S = jnp.zeros((B, *S.shape[1:]), jnp.float32).at[0].set(S[0])
        tail = jnp.zeros((B, *tail.shape[1:]), cfg.dtype).at[0].set(tail[0])
        valid = (jnp.arange(B) == 0)[:, None]
        for t in range(C + n2, T):
            y, S, tail = mix(p, jnp.zeros((B, 1, cfg.dim), cfg.dtype).at[0, 0].set(h[t]), S, tail, valid)
            have.append(np.asarray(y[0], np.float32))
        have = np.concatenate(have)
        err = np.max(np.abs(have - want), axis=-1) / np.max(np.abs(want), axis=-1)
        out["by_layer"]["chunks"].append(float(np.max(err[: C + n2])))
        out["by_layer"]["decode"].append(float(np.max(err[C + n2 :])))
        out["finite"] &= bool(np.all(np.isfinite(have)))
    out["worst"] = {k: max(v) for k, v in out["by_layer"].items()}
    return out


def mla_alone(runner, model: Dict[str, Any], seed: int, reference_attention: Callable) -> Dict[str, Any]:
    """Per checked attending layer: the largest, over the rows, of ``max|mix
    - reference| / max|reference|`` over a row's outputs, the program's
    latent attention as a prefill chunk runs it (``_mla_qkv``, ``latent.
    latent_attention`` over a fresh one-layer cache: the flash kernel on a
    TPU, then ``wo``) on one chunk from an empty context, against the
    reference's causal attention on the same activations."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import kimi_linear as kl
    from ray_tpu.models import latent

    cfg, bs = runner.cfg, runner.block_size
    C, M = runner.prefill_buckets[-1], runner.max_blocks_per_seq
    rng = np.random.default_rng([int(seed), 96])
    cache = kl.cache_layout(cfg, bs, runner.cache["latent"].dtype).init(M + 8)
    cache = {"latent": cache["latent"][:1]}
    table = jnp.arange(1, M + 1, dtype=jnp.int32)[None]
    pos = jnp.arange(C, dtype=jnp.int32)[None]

    @jax.jit
    def mix(p, h):
        q_nope, q_shared, row = kl._mla_qkv(cfg, p, h[None])
        o, _ = latent.latent_attention(cfg, p, q_nope, q_shared, row, cache, 0, table, pos, jnp.full((1,), C, jnp.int32))
        return jnp.einsum("bchk,hkd->bcd", o.astype(h.dtype), p["wo"])[0]

    layers = [p for p in runner.params["layers"] if "w_kva" in p]
    readings, finite = [], True
    for i in _spread(len(layers)):
        h = jnp.asarray(rng.standard_normal((C, cfg.dim)).astype(np.float32), cfg.dtype)
        have = np.asarray(mix(layers[i], h), np.float32)
        want = np.asarray(reference_attention(model, layers[i], h.astype(jnp.float32)))
        readings.append(float(np.max(np.max(np.abs(have - want), axis=-1) / np.max(np.abs(want), axis=-1))))
        finite &= bool(np.all(np.isfinite(have)))
    return {"by_layer": {"chunk": readings}, "worst": {"chunk": max(readings)}, "finite": finite}


def expert_ffn_alone(runner, model: Dict[str, Any], seed: int, reference_ffn: Callable) -> Dict[str, Any]:
    """Per shape (rows of the launch) and checked layer: the largest, over
    the compared real rows, of ``max|ffn - reference| / max|reference|`` over
    a row's outputs (``families/xing4/server.py`` says why). ``worst``: per shape."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import kimi_linear as kl

    cfg = runner.cfg
    ffn = jax.jit(lambda p, h, valid: kl._ffn(cfg, p, h[None], valid[None], True)[0][0])
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
            err = np.max(np.abs(have[:real] - want), axis=-1) / np.max(np.abs(want), axis=-1)
            readings.append(float(np.max(err[sure])))
            out["not_compared"] += int(real - sure.sum())
            out["finite"] &= bool(np.all(np.isfinite(have)))
        out["by_layer"][str(rows)] = readings
        out["worst"][str(rows)] = max(readings)
    return out


class BenchKimiLinearServer(BenchServer, LLMServer):
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        """The five readings (the module's docstring). The engine must be
        idle: the check writes into blocks 1.. and state slots 1.. of the
        free pools; a later request's first chunk starts its slot from zeros."""
        family = families.of(model)
        runner = self.engine.runner
        got = drive(runner, model, seed, prompt_lens, decode_steps, family.reference_logits_and_states)
        limits = model["correctness"]
        state = got.pop("state")
        for what, limit in (("first", "state_rel_tol"), ("deep", "state_deep_rel_tol")):
            got["positions"].append(["state", what])
            got["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * state["worst"][what])
        got["finite"] = bool(got["finite"] and state["finite"])
        got["state"] = state
        for name, limit, alone in (
            ("expert_ffn", "expert_ffn_rel_tol",
             expert_ffn_alone(runner, model, seed, family.reference_expert_ffn)),
            ("kda", "kda_rel_tol", kda_alone(runner, model, seed, family.reference_kda)),
            ("mla", "mla_rel_tol", mla_alone(runner, model, seed, family.reference_attention)),
        ):
            for what, reading in alone["worst"].items():
                got["positions"].append([name, what])
                got["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * reading)
            got["finite"] = bool(got["finite"] and alone["finite"])
            got[name] = alone
        return got
