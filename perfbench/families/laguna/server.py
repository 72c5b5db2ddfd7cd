"""The deployment class of this family's serving cells: the program's
``LLMServer`` (Laguna runs through ``ray_tpu.models.llama`` by its config)
with the benchmark's general additions (``harness/server.py``) and the drive of
the correctness check that a cache of two layer GROUPS needs. The drive is
Mellum2's, imported and not copied (``families/mellum/server.py`` says why a
scheduler of the engine's class has to slide the window group's table, and how
each reading is taken); what differs here is what the readings are OF:

* the logits after the whole model (``logit_rel_tol``): prompts long enough
  that the window table has slid INSIDE the prefill (a window of 512 under
  chunks of 1024), then decode steps through both kernels;
* the expert FFN alone (``expert_ffn_rel_tol``): ``llama._ffn`` of three SPARSE
  layers (layer 0 is dense) against the reference's on the same normed
  activations: the held range under a router 256 wide, sigmoid scores
  normalised and scaled, the shared expert in;
* a WINDOW layer alone (``window_attn_rel_tol``): 64 query heads, the plain
  table of its own base over the whole head, the gate in;
* a FULL layer alone (``full_attn_rel_tol``): 48 query heads, half of each head
  under YaRN's table, the gate in.

``llama._paged_attention_block`` is what both layer readings run: the layer's
heads are its ``wq``'s, the rope its kind's, and the gate multiplies the
kernel's output before ``wo``."""

from __future__ import annotations

import types
from typing import Any, Dict, List

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer

from ... import families
from ...harness.server import BenchServer
from ..mellum.server import attention_alone, check_scheduler, drive, expert_ffn_alone


class BenchLagunaServer(BenchServer, LLMServer):
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
        # the FFN's reading over the layers that route (a dense layer has no router to compare)
        sparse = types.SimpleNamespace(
            cfg=runner.cfg, params={"layers": [p for p in runner.params["layers"] if "router" in p]},
            prefill_buckets=runner.prefill_buckets, decode_buckets=runner.decode_buckets,
        )
        limits = model["correctness"]
        for name, limit, alone in (
            ("expert_ffn", "expert_ffn_rel_tol",
             expert_ffn_alone(sparse, model, seed, family.reference_expert_ffn)),
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
