"""The deployment class of this family's serving cells: the program's
``LLMServer`` with the benchmark's general additions (``harness/server.py``)
and this family's second reading of the correctness check, as the ``olmoe``
family has its own. Defined at module level so that it is pickled by
reference.

Why a second reading. The system's residual state is bfloat16 and the
reference's float32, so a token whose 4th and 5th expert scores (with the
bias) lie closer than that noise keeps another expert than the reference,
and the limit on the logits has to leave room for it. The routed FFN is
therefore read a second time ALONE, where nothing can flip: the program's
own FFN of an expert layer (``ray_tpu.models.xing4._ffn``: the shared
expert, the router with its bias, the sort, the grouped matmuls over the
HELD experts, the combine, the ``valid`` mask) and the reference's
(``reference.expert_ffn``, given the same held range) on the SAME normed
activations, at the shapes of the largest prefill chunk and of the decode
batch, an eighth of the rows padding, with the weights of three layers
spread over the depth. A row whose margin between the last kept and the
first left out is under ``TIE_MARGIN`` is not compared.

Why a third. Six logits cannot tell a wrong normalisation of the residual
maps from the model's own noise (one Sinkhorn round instead of 20 reads
inside it: the seeded maps are near doubly stochastic after one). The
hyper-connected residual is therefore read ALONE as well, where bfloat16's
rounding of the state is all the noise there is: the program's ``_hyper`` of
a sublayer (the three maps, Sinkhorn, both mixes) around ``F`` = the identity
and the reference's, on the SAME state, at the same two shapes, both
sublayers of three layers spread over the depth, under its own limit
``correctness.residual_rel_tol``."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer

#: in score + bias; the two float32 routers agree to about 1e-6
TIE_MARGIN = 1e-4


class BenchXing4Server(BenchServer, LLMServer):
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        """The general check, and beside it the expert FFN's reading and the
        residual's, each entered as a share of ITS limit
        (``correctness.expert_ffn_rel_tol``, ``residual_rel_tol``) times
        ``logit_rel_tol``: an entry passes exactly when its reading is within
        its own limit. The readings as they were read are in ``expert_ffn``
        and ``residual``."""
        got = super().bench_check(model, seed, prompt_lens, decode_steps)
        limits = model["correctness"]
        for name, limit, read in (("expert_ffn", "expert_ffn_rel_tol", self.expert_ffn_check),
                                  ("residual", "residual_rel_tol", self.residual_check)):
            alone = read(model, seed)
            for rows, reading in alone["worst"].items():
                got["positions"].append([name, rows])
                got["rel_err"].append(limits["logit_rel_tol"] / limits[limit] * reading)
            got["finite"] = bool(got["finite"] and alone["finite"])
            got[name] = alone
        return got

    def residual_check(self, model: Dict[str, Any], seed: int) -> Dict[str, Any]:
        """Per shape (rows of the launch): the largest, over the checked
        sublayers and the rows, of ``max|_hyper(X) - reference| /
        max|reference|`` over a row's ``n x D`` outputs, ``F`` the identity.
        The state: a part all streams share (they start as one embedding
        repeated) and a part of each stream's own, as ``cfg.dtype``."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import xing4

        runner = self.engine.runner
        cfg = runner.cfg
        reference_residual = families.of(model).reference_residual
        rng = np.random.default_rng([int(seed), 99])
        out: Dict[str, Any] = {"by_sublayer": {}, "worst": {}, "finite": True}
        stacked = runner.params["moe"]
        n_layers = stacked["router"].shape[0]
        for rows in (runner.prefill_buckets[-1], runner.decode_buckets[-1]):
            readings = []
            for layer in range(0, n_layers, max(1, n_layers // 3)):
                for sub, norm in (("hc_attn", "attn_norm"), ("hc_mlp", "mlp_norm")):
                    p = {k: stacked[k][layer] for k in (f"{sub}_phi", f"{sub}_b", f"{sub}_alpha", norm)}
                    X = rng.standard_normal((rows, 1, cfg.dim)) + 0.5 * rng.standard_normal((rows, cfg.hc_mult, cfg.dim))
                    X = jnp.asarray(X.astype(np.float32), cfg.dtype)
                    hyper = jax.jit(lambda p, X, sub=sub, norm=norm: xing4._hyper(cfg, p, sub, norm, X, lambda h: (h, None))[0])
                    have = np.asarray(hyper(p, X), np.float32)
                    want = np.asarray(reference_residual(model, p, sub, norm, X.astype(jnp.float32)))
                    err = np.max(np.abs(have - want), axis=(-1, -2)) / np.max(np.abs(want), axis=(-1, -2))
                    readings.append(float(np.max(err)))
                    out["finite"] &= bool(np.all(np.isfinite(have)))
            out["by_sublayer"][str(rows)] = readings
            out["worst"][str(rows)] = max(readings)
        return out

    def expert_ffn_check(self, model: Dict[str, Any], seed: int) -> Dict[str, Any]:
        """Per shape (rows of the launch) and checked layer: the largest,
        over the compared real rows, of ``max|ffn - reference| /
        max|reference|`` over a row's outputs. ``worst``: per shape."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import xing4

        runner = self.engine.runner
        cfg = runner.cfg
        reference_ffn = families.of(model).reference_expert_ffn
        ffn = jax.jit(lambda p, h, valid: xing4._ffn(cfg, p, h[None], valid[None], True)[0][0])
        rng = np.random.default_rng([int(seed), 98])
        out: Dict[str, Any] = {"by_layer": {}, "worst": {}, "not_compared": 0, "finite": True}
        stacked = runner.params["moe"]
        n_layers = stacked["router"].shape[0]
        for rows in (runner.prefill_buckets[-1], runner.decode_buckets[-1]):
            real = rows - rows // 8
            valid = jnp.arange(rows) < real
            readings = []
            for layer in range(0, n_layers, max(1, n_layers // 3)):
                p = {k: v[layer] for k, v in stacked.items()}
                # unit RMS, as a block's norm leaves them; the padding rows hold anything
                h = rng.standard_normal((rows, cfg.dim)).astype(np.float32)
                h[real:] *= 100.0
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
