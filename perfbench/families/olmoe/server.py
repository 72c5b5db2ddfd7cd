"""The deployment class of this family's serving cells: the program's
``LLMServer`` (the same one the dense family deploys: OLMoE runs through
``ray_tpu.models.llama`` by its config) with the benchmark's general
additions (``harness/server.py``) and this family's second reading of the
correctness check. Defined at module level so that it is pickled by
reference.

Why a second reading. The general check compares logits after the whole
model. The system's residual stream is bfloat16 and the reference's is
float32, so a token whose last chosen and first left-out router
probabilities lie closer than that noise takes another expert than the
reference, and the limit on the logits has to leave room for it: by the
readings in the configuration file, experts computed in float8, or a
token's eighth expert left out, pass under that limit on most seeds. The
expert FFN is the layer this family's cells exist for, so it is read a
second time ALONE, where nothing can flip: the program's own FFN of a
block (``ray_tpu.models.llama._ffn``, the function ``forward`` and the
three paged steps call: router, sort, grouped matmuls, combine, the
``valid`` mask) and the reference's (``reference.expert_ffn``) are given
the SAME normed activations, from the seed, at the shapes of the largest
prefill chunk and of the decode batch, an eighth of the rows padding, with
the weights of three layers spread over the depth (the layers are one code
path and differ in their weights alone; all twelve read alike on the chip
and cost 9 s a run where three cost 2). Both route in float32 from
identical inputs, so they choose the same experts, except where the
reference's own margin between the last chosen and the first left out is
under ``TIE_MARGIN``: such a row (about one in a thousand) is not compared,
either choice being right there."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer

#: a row is compared only where the reference's last chosen expert beat the
#: first one left out by more than this share of its probability: the two
#: float32 routers agree to about 1e-6, so such a row cannot flip
TIE_MARGIN = 1e-4


class BenchOlmoeServer(BenchServer, LLMServer):
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        """The general check, and the expert FFN's reading beside it. The
        harness holds the worst entry of ``rel_err`` to ``logit_rel_tol``:
        the FFN's readings are entered as a share of THEIR limit
        (``correctness.expert_ffn_rel_tol``) times ``logit_rel_tol``, so
        that an entry passes exactly when its reading is within its own
        limit. The readings as they were read are in ``expert_ffn``."""
        got = super().bench_check(model, seed, prompt_lens, decode_steps)
        ffn = self.expert_ffn_check(model, seed)
        limits = model["correctness"]
        share = limits["logit_rel_tol"] / limits["expert_ffn_rel_tol"]
        for rows, reading in ffn["worst"].items():
            got["positions"].append(["expert_ffn", rows])
            got["rel_err"].append(share * reading)
        got["finite"] = bool(got["finite"] and ffn["finite"])
        got["expert_ffn"] = ffn
        return got

    def expert_ffn_check(self, model: Dict[str, Any], seed: int) -> Dict[str, Any]:
        """Per shape (rows of the launch) and checked layer: the largest,
        over the compared real rows, of ``max|ffn - reference| /
        max|reference|`` over a row's outputs. ``worst``: the largest over
        those layers, per shape."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import llama

        runner = self.engine.runner
        cfg = runner.cfg
        reference_ffn = families.of(model).reference_expert_ffn
        ffn = jax.jit(lambda p, h, valid: llama._ffn(cfg, p, h, valid)[0])
        rng = np.random.default_rng([int(seed), 98])
        out: Dict[str, Any] = {"by_layer": {}, "worst": {}, "not_compared": 0, "finite": True}
        for rows in (runner.prefill_buckets[-1], runner.decode_buckets[-1]):
            real = rows - rows // 8
            valid = jnp.arange(rows) < real
            readings = []
            layers = runner.params["layers"]
            for p in layers[:: max(1, len(layers) // 3)]:
                # unit RMS, as a block's norm leaves them; the padding rows hold anything
                h = rng.standard_normal((rows, cfg.dim)).astype(np.float32)
                h[real:] *= 100.0
                h = jnp.asarray(h, cfg.dtype)
                have = np.asarray(ffn(p, h, valid), np.float32)
                want, margin = reference_ffn(model, p, h[None, :real].astype(jnp.float32))
                want, margin = np.asarray(want)[0], np.asarray(margin)[0]
                sure = margin > TIE_MARGIN
                err = np.max(np.abs(have[:real] - want), axis=-1) / np.max(np.abs(want), axis=-1)
                readings.append(float(np.max(err[sure])))
                out["not_compared"] += int(real - sure.sum())
                out["finite"] &= bool(np.all(np.isfinite(have)) and not np.any(have[real:]))
            out["by_layer"][str(rows)] = readings
            out["worst"][str(rows)] = max(readings)
        return out
