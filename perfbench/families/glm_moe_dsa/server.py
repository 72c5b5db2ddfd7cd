"""The deployment class of this family's serving cells: the ``deepseek_v3``
family's (the program's ``LLMServer`` with the benchmark's additions, the MTP
module as the drafter, and the drive through the programs the window launches:
``window_check`` and ``expert_ffn_check`` are that class's, run here against
THIS family's reference) with a ``bench_check`` of SIX readings, each entered as
a share of ITS limit times ``logit_rel_tol`` (the harness holds the worst entry
to that one number):

* ``logits``, ``mtp``, ``tokens``, ``expert_ffn``: as
  ``families/deepseek_v3/server.py`` reads them (prefill told the token that
  follows each chunk, both rows of the verify windows, the module through its
  own cache rows, the ONE-program step against the plain path, the expert FFN
  alone), over a prompt well past ``index_topk`` and one under it. Every
  program of the drive selects: these are the selection, both cached rows and
  both attention paths END TO END;
* ``selection``: the SELECTION alone. The program's three parts
  (``xing4._latent_qkv`` with its indexer, ``sparse_index.index_scores``,
  ``sparse_index.select_mask``: what a prefill chunk runs, at the chunk's
  widths) on normed activations of a sequence of ``SELECT_CONTEXT`` positions,
  its last chunk the queries, against the reference's ``S_t`` on the same
  inputs for ``SELECT_QUERIES`` sampled queries. A position whose reference
  score lies within ``select_margin`` (of the row's largest |score|) of the
  ``index_topk``-th is not compared: bfloat16's rounding of ``q_I`` and ``k_I``
  moves a score by a few thousandths, and either side of such a tie is the
  model. Every other position must be chosen by both or by neither: the
  reading is the worst query's share of ``index_topk`` that differs, held
  under ``select_miss_tol``;
* ``attention``: the attention ALONE with the REFERENCE's ``S_t`` handed to
  the program (``latent.attend_masked`` between ``absorb_query`` and
  ``absorb_output``, then ``wo``: the chunk's path), so that no flipped
  position stands between the two sides: ``max|out - reference| /
  max|reference|`` over the sampled queries, under ``attention_rel_tol``."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ... import families
from ..deepseek_v3.server import BenchDeepseekV3Server

#: positions of the sequence the two readings of one layer alone run over: the
#: last ``prefill_buckets[-1]`` of them are the queries (three times
#: ``index_topk``: two of three positions are left out)
SELECT_CONTEXT = 6144
#: sampled queries of the chunk, evenly spread, the first and the last among them
SELECT_QUERIES = 16


class BenchGlmDsaServer(BenchDeepseekV3Server):
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        limits = model["correctness"]
        out = super().bench_check(model, seed, prompt_lens, decode_steps)
        alone = self.selection_check(model, seed)
        for name, limit in (("selection", "select_miss_tol"), ("attention", "attention_rel_tol")):
            for where, reading in alone[name].items():
                out["positions"].append([name, where])
                out["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * reading)
        out["finite"] = bool(out["finite"] and alone["finite"])
        out["selection"] = alone
        return out

    def selection_check(self, model: Dict[str, Any], seed: int) -> Dict[str, Any]:
        """Per checked layer (the first dense layer, the last expert layer, the
        MTP module's): ``selection``: the worst sampled query's share of
        ``index_topk`` positions on which program and reference differ outside
        the margin; ``attention``: the attention sublayer's output under the
        reference's selection against the reference's; ``score_err``: the
        largest |I - reference| over the sampled rows as a share of the row's
        largest |score| (what the margin has to cover), ``not_compared``: the
        positions inside the margin."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import latent, xing4
        from ray_tpu.ops import sparse_index

        runner = self.engine.runner
        cfg = runner.cfg
        K, margin = cfg.index_topk, float(model["correctness"]["select_margin"])
        C = runner.prefill_buckets[-1]
        T = max(min(SELECT_CONTEXT, cfg.max_seq_len), C)
        first = T - C
        queries = sorted({first + int(round(i * (C - 1) / (SELECT_QUERIES - 1))) for i in range(SELECT_QUERIES)})
        reference_attention = families.of(model).reference_attention

        @jax.jit
        def program(stacked, layer, h, chosen_ref):
            p = {k: v[layer] for k, v in stacked.items() if not k.startswith(("w_gate", "w_up", "w_down", "shared_", "router"))}
            pos = jnp.arange(T, dtype=jnp.int32)[None]
            q_nope, q_rope, row, (q_i, k_i, w_i) = xing4._latent_qkv(cfg, p, h[None], pos)
            scores = sparse_index.index_scores(q_i[0, first:], w_i[0, first:], k_i[0])
            chosen = sparse_index.select_mask(scores, pos[0, first:], K)
            q_row = latent.absorb_query(cfg, p, q_nope[:, first:], q_rope[:, first:])
            o_lat = latent.attend_masked(cfg, q_row[0], row[0], chosen_ref)
            o = latent.absorb_output(cfg, p, o_lat[None])
            return scores, chosen, jnp.einsum("bchk,hkd->bcd", o.astype(h.dtype), p["wo"])[0]

        layers = [(group, runner.params[group], i) for group, i in (("dense", 0), ("moe", -1)) if group in runner.params]
        if "mtp" in runner.params:
            layers.append(("mtp", runner.params["mtp"]["moe"], 0))
        rng = np.random.default_rng([int(seed), 97])
        out: Dict[str, Any] = {"selection": {}, "attention": {}, "score_err": {}, "not_compared": 0, "finite": True}
        for name, stacked, layer in layers:
            layer %= next(iter(stacked.values())).shape[0]
            # unit RMS, as a block's norm leaves them
            h = jnp.asarray(rng.standard_normal((T, cfg.dim)).astype(np.float32), cfg.dtype)
            want, rows = reference_attention(model, stacked, layer, h.astype(jnp.float32), queries)
            chosen_ref = np.zeros((C, T), bool)
            chosen_ref[:] = np.tril(np.ones((C, T), bool), first)  # a query not sampled: everything it sees
            for t, (chosen, _) in rows.items():
                chosen_ref[t - first] = chosen
            scores, chosen, have = program(stacked, layer, h, jnp.asarray(chosen_ref))
            scores, chosen, have = np.asarray(scores), np.asarray(chosen), np.asarray(have, np.float32)
            want = np.asarray(want)
            miss, err = 0.0, 0.0
            for t, (ref_chosen, ref_scores) in rows.items():
                seen = np.arange(T) <= t  # the query's candidates, whatever the reference made of them
                scored = seen & np.isfinite(ref_scores)
                scale = np.max(np.abs(ref_scores[scored]))
                kept = np.sort(ref_scores[scored])[::-1][: K]
                near = scored & (np.abs(ref_scores - kept[-1]) <= margin * scale) if scored.sum() > K else np.zeros_like(seen)
                differs = (chosen[t - first] != ref_chosen) & seen & ~near
                miss = max(miss, float(differs.sum()) / K)
                err = max(err, float(np.max(np.abs(scores[t - first][scored] - ref_scores[scored])) / scale))
                out["not_compared"] += int((near & seen).sum())
            at = [t - first for t in rows]
            out["selection"][name] = miss
            out["score_err"][name] = err
            out["attention"][name] = float(np.max(np.abs(have[at] - want[list(rows)])) / np.max(np.abs(want[list(rows)])))
            out["finite"] &= bool(np.all(np.isfinite(have)) and np.all(np.isfinite(scores)))
        return out
