"""The deployment class of this family's serving cells: the program's
``LLMServer`` with the benchmark's general additions (``harness/server.py``)
and this family's own ``bench_check``.

Why its own. Decode of this model is the drafter's step (a verify window of
two and the MTP module), which the harness's check does not drive: it calls
``runner.decode``, a program this deployment never warms. The drive is here,
through the programs the window launches: ``prefill_chunk`` told the token that
follows each chunk, then the step's two-program form (``launch_mtp_step(greedy
=False)`` hands back both rows' logits, ``launch_mtp_draft(logits=True)`` the
module's), teacher-forced (the "generated" tokens are drawn from the seed, so
rounding cannot change what is compared), and last the ONE-program form
against the two-program form's own tokens.

Four readings, each entered as a share of ITS limit times ``logit_rel_tol``
(the harness holds the worst entry to that one number):

* ``logits``: the main model's logits under ``logit_rel_tol``: after each
  prompt's prefill (one prompt of two full chunks and a part, a short one),
  and BOTH rows of every verify window after it: the window one position
  earlier of a slot without a draft, then windows whose second token is the
  ORACLE draft (the sequence's own next token), and one whose second token is
  WRONG (row 0 alone is anybody's then);
* ``expert_ffn``: the expert FFN ALONE under ``expert_ffn_rel_tol``, as the
  ``olmoe`` and ``xing4`` families read it (a hard top-8 choice flips on
  bfloat16's rounding and the logits' limit has to leave room for that), here
  with the GROUP limit: the program's ``_ffn`` of an expert layer against the
  reference's on the same normed activations; a row whose margin (of the
  group choice or of the experts') is under ``TIE_MARGIN`` is not compared. A
  wrong group choice fails THIS reading (the plain top-8 in place of the
  limited one changes the kept set of about two rows in five);
* ``mtp``: the MTP module's logits under ``mtp_logit_rel_tol``, through ITS
  cache row: at the prompt's last position (the row that waited for the first
  output token) and after each step's committed positions;
* ``tokens``: the ONE-program step (``paged_mtp_step``) against the plain
  path (windows of one through the two-program form): with the oracle draft
  it commits the plain path's next TWO tokens and says 1 accepted, with a
  wrong one its next ONE and 0. A position whose plain logits' first and
  second differ by less than ``PICK_MARGIN`` of their largest is not compared
  (two programs round differently; an argmax may then differ). Entered as 0
  or twice the limit."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer

#: in score + bias and in group score; the two float32 routers agree to about 1e-6
TIE_MARGIN = 1e-4
#: of the largest |logit| of the position: below it two programs' argmax may differ
PICK_MARGIN = 0.01


def _rel(have, want) -> float:
    return float(np.max(np.abs(have - want)) / np.max(np.abs(want)))


def drafting(engine_cfg):
    """The engine block as a deployment of this family runs it: the MTP
    module as the drafter (``speculative_k`` = the module's depth, fixed;
    prefix reuse off, which the engine refuses beside it). An engine block
    that already says how it speculates is left alone; one that says nothing
    (the CPU rehearsal's toy engine, which replaces the file's) gets this,
    because the family's check and its counters are the drafter's."""
    import dataclasses

    if engine_cfg is None or engine_cfg.speculative_k > 0:
        return engine_cfg
    return dataclasses.replace(
        engine_cfg, speculative_k=1, speculative_draft="mtp", speculative_adaptive=False,
        prefix_cache_enabled=False,
    )


class BenchDeepseekV3Server(BenchServer, LLMServer):
    def __init__(self, model_cfg=None, engine_cfg=None, **kwargs):
        super().__init__(model_cfg, drafting(engine_cfg), **kwargs)

    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        limits = model["correctness"]
        got = self.window_check(model, seed, prompt_lens, decode_steps)
        out: Dict[str, Any] = {"positions": [], "rel_err": [], "finite": got["finite"]}

        def enter(name: str, limit: str, readings: Dict[str, float]) -> None:
            for where, reading in readings.items():
                out["positions"].append([name, where])
                out["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * reading)

        enter("logits", "logit_rel_tol", got["logits"])
        enter("mtp", "mtp_logit_rel_tol", got["mtp"])
        enter("tokens", "logit_rel_tol", {k: 0.0 if same else 2 * limits["logit_rel_tol"]
                                          for k, same in got["tokens"].items()})
        alone = self.expert_ffn_check(model, seed)
        enter("expert_ffn", "expert_ffn_rel_tol", alone["worst"])
        out["finite"] = bool(out["finite"] and alone["finite"])
        out.update(window=got, expert_ffn=alone)
        return out

    def window_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                     decode_steps: int) -> Dict[str, Any]:
        """The drive through the cache (module docstring): ``logits`` and
        ``mtp``: ``{where: max|have - reference| / max|reference|}`` over the
        vocabulary; ``tokens``: ``{where: the one-program step agreed}``;
        ``not_compared``: token comparisons left out for a near tie."""
        runner = self.engine.runner
        bs, width, V = runner.block_size, runner.max_blocks_per_seq, model["vocab_size"]
        rng = np.random.default_rng([int(seed), 99])
        n = len(prompt_lens)
        # prompt, the first output token, 2 tokens a teacher-forced step, the wrong-draft step's 1, room for the plain path's
        totals = [p + 1 + 2 * decode_steps + 4 for p in prompt_lens]
        tokens = rng.integers(1, V, size=(n, max(totals))).astype(np.int32)
        rows, nxt = [], 1
        for t in totals:
            need = -(-t // bs)
            row = np.zeros(width, np.int32)
            row[:need] = np.arange(nxt, nxt + need)
            nxt += need
            rows.append(row)
        main: List[Any] = []   # (where, row, position, logits [V])
        draft: List[Any] = []  # (where, row, position of the hidden state, logits [V])
        finite = True

        def step(windows, known, ctxs, follows, where: str, rows_compared=(0, 1)):
            """One step in its two-program form: the windows' logits (rows
            ``rows_compared`` are entered) and the module's after ``follows``."""
            verified = runner.launch_mtp_step(windows, known, rows, ctxs, greedy=False)
            lg = runner.read(verified)
            for i in range(n):
                main.extend((f"{where}.{c}", i, ctxs[i] + c, lg[i, c]) for c in rows_compared)
            after = runner.read(runner.launch_mtp_draft(verified, follows, rows, ctxs, logits=True))
            draft.extend((where, i, ctxs[i] + len(follows[i]) - 1, after[i]) for i in range(n))
            return lg

        largest = runner.prefill_buckets[-1]
        for i, p in enumerate(prompt_lens):
            start = 0
            while start < p:
                c = min(largest, p - start)
                logits = runner.prefill_chunk(
                    tokens[i, start : start + c], rows[i], start,
                    next_token=int(tokens[i, start + c]) if start + c < p else -1,
                )
                start += c
            main.append(("prefill", i, p - 1, logits))
        at = list(prompt_lens)  # tokens[i, : at[i] + 1] are committed; the cache holds rows below at[i]
        # a slot without a draft: the window one position earlier, both tokens
        # committed; the module first over the row that waited alone, then both
        pair = [[int(tokens[i, a - 1]), int(tokens[i, a])] for i, a in enumerate(at)]
        verified = runner.launch_mtp_step(pair, [2] * n, rows, [a - 1 for a in at], greedy=False)
        runner.read(verified)
        waited = runner.read(runner.launch_mtp_draft(
            verified, [[int(tokens[i, a])] for i, a in enumerate(at)], rows, [a - 1 for a in at], logits=True))
        draft.extend(("waited", i, a - 1, waited[i]) for i, a in enumerate(at))
        step(pair, [2] * n, [a - 1 for a in at],
             [[int(tokens[i, a]), int(tokens[i, a + 1])] for i, a in enumerate(at)], "known")
        at = [a + 1 for a in at]
        for d in range(decode_steps):  # the oracle draft: the sequence's own next token
            step([[int(tokens[i, a]), int(tokens[i, a + 1])] for i, a in enumerate(at)], [1] * n, at,
                 [[int(tokens[i, a + 1]), int(tokens[i, a + 2])] for i, a in enumerate(at)], f"oracle{d}")
            at = [a + 2 for a in at]
        # a wrong draft: row 0 is the model's, row 1 nobody's; one position is committed
        step([[int(tokens[i, a]), int(tokens[i, a + 1] % (V - 1)) + 1] for i, a in enumerate(at)], [1] * n, at,
             [[int(tokens[i, a + 1])] for i, a in enumerate(at)], "wrong", rows_compared=(0,))
        at = [a + 1 for a in at]

        # the plain path from here, a window of one at a time, then the
        # one-program step from the same context with the oracle and a wrong draft
        plain, sure = [], []
        ctx = list(at)
        last = [int(tokens[i, a]) for i, a in enumerate(at)]
        for _ in range(2):
            verified = runner.launch_mtp_step([[t] for t in last], [1] * n, rows, ctx, greedy=False)
            lg = runner.read(verified)[:, 0]
            picks = [int(np.argmax(r)) for r in lg]
            top = np.sort(lg, axis=-1)
            sure.append([bool(top[i, -1] - top[i, -2] > PICK_MARGIN * np.max(np.abs(lg[i]))) for i in range(n)])
            runner.read(runner.launch_mtp_draft(verified, [[t] for t in picks], rows, ctx))
            plain.append(picks)
            finite &= bool(np.all(np.isfinite(lg)))
            last, ctx = picks, [c + 1 for c in ctx]
        first = [int(tokens[i, a]) for i, a in enumerate(at)]
        agreed: Dict[str, bool] = {}
        not_compared = 0
        for name, second in (("oracle", plain[0]), ("wrong", [t % (V - 1) + 1 for t in plain[0]])):
            out = runner.read(runner.launch_mtp_step(
                [[a, b] for a, b in zip(first, second)], [1] * n, rows, at, greedy=True))
            for i in range(n):
                new, accepted = [int(t) for t in out[i, :2]], int(out[i, 2])
                want_accepted = int(name == "oracle")
                if not sure[0][i] or (want_accepted and not sure[1][i]):
                    not_compared += 1
                    continue
                agreed[f"{name}.{i}"] = (
                    accepted == want_accepted and new[: 1 + accepted] == [p[i] for p in plain][: 1 + accepted]
                )
        main_ref, draft_ref = families.of(model).reference_both_logits(
            model, runner.params, tokens, [(i, p) for _, i, p, _ in main], [(i, p) for _, i, p, _ in draft]
        )
        finite &= all(bool(np.all(np.isfinite(h))) for *_, h in main + draft)
        return {
            "logits": {f"{w}@{i},{p}": _rel(h, r) for (w, i, p, h), r in zip(main, main_ref)},
            "mtp": {f"{w}@{i},{p}": _rel(h, r) for (w, i, p, h), r in zip(draft, draft_ref)},
            "tokens": agreed, "not_compared": not_compared, "finite": bool(finite),
        }

    def expert_ffn_check(self, model: Dict[str, Any], seed: int) -> Dict[str, Any]:
        """Per shape (rows of the launch) and checked layer: the largest,
        over the compared real rows, of ``max|ffn - reference| /
        max|reference|`` over a row's outputs. ``worst``: per shape. The
        shapes: the largest prefill chunk, and the step's batch x window."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import xing4

        runner = self.engine.runner
        cfg = runner.cfg
        reference_ffn = families.of(model).reference_expert_ffn
        # a layer is cut out of its stack inside the program, as the serving steps' scan does
        ffn = jax.jit(lambda stacked, layer, h, valid: xing4._ffn(
            cfg, {k: v[layer] for k, v in stacked.items()}, h[None], valid[None], True)[0][0])
        rng = np.random.default_rng([int(seed), 98])
        out: Dict[str, Any] = {"by_layer": {}, "worst": {}, "not_compared": 0, "finite": True}
        # two of the main model's expert layers and the MTP module's
        main = runner.params["moe"]
        n_layers = main["router"].shape[0]
        layers = [(main, 0), (main, n_layers - 1)]
        if "mtp" in runner.params:
            layers.append((runner.params["mtp"]["moe"], 0))
        for rows in (runner.prefill_buckets[-1], 2 * runner.decode_buckets[-1]):
            real = rows - rows // 8
            valid = jnp.arange(rows) < real
            readings = []
            for stacked, layer in layers:
                # unit RMS, as a block's norm leaves them; the padding rows hold anything
                h = rng.standard_normal((rows, cfg.dim)).astype(np.float32)
                h[real:] *= 100.0
                h = jnp.asarray(h, cfg.dtype)
                have = np.asarray(ffn(stacked, layer, h, valid), np.float32)
                want, margin = reference_ffn(model, stacked, layer, h[:real].astype(jnp.float32))
                want, margin = np.asarray(want), np.asarray(margin)
                sure = margin > TIE_MARGIN
                err = np.max(np.abs(have[:real] - want), axis=-1) / np.max(np.abs(want), axis=-1)
                readings.append(float(np.max(err[sure])))
                out["not_compared"] += int(real - sure.sum())
                out["finite"] &= bool(np.all(np.isfinite(have)))
            out["by_layer"][str(rows)] = readings
            out["worst"][str(rows)] = max(readings)
        return out
