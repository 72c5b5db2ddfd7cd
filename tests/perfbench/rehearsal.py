"""Tiny versions of the benchmark's real files for the CPU rehearsal: the
same structure and generator kinds, toy sizes. Never used on the chip."""

import copy
import functools
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "perfbench")

TINY_LENGTHS = {
    "pairing_seed": 1,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8, "clip": [8, 60]},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.6, "clip": [4, 12]},
}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def tiny_config(name: str):
    return tiny(_load("configs", f"{name}.json"))


def tiny(config):
    """``config`` at the toy sizes of ITS family, with a toy engine and the
    tolerances of float32 against float32."""
    from perfbench import families

    cfg = copy.deepcopy(config)
    cfg.update(families.of(cfg).TOY_SIZES)
    if "serving" in cfg:
        cfg["serving"]["engine"] = {
            "num_blocks": 64, "block_size": 8, "prefill_buckets": [16, 32],
            "decode_buckets": [4], "max_decode_batch": 4, "warmup": False,
        }
        cfg["correctness"].update(prompt_lens=[40, 12], decode_steps=2, logit_rel_tol=1e-3)
    else:
        cfg["correctness"].update(loss_abs_tol=1e-3, loss_window=2)
    return cfg


def tiny_traffic(name: str):
    t = copy.deepcopy(_load("traffic", f"{name}.json"))
    if t["kind"] == "paced_open":
        t.update(rate_per_s=4.0, lead_in_requests=4, drain_grace_s=30.0, lengths=TINY_LENGTHS,
                 trace_seconds=1.0)
    elif t["kind"] == "closed":
        t.update(clients=min(4, t["clients"]), lead_in_seconds=1.0, multiset_size=16, rounds=2,
                 lengths=TINY_LENGTHS, trace_seconds=1.0)
    else:
        # a learning rate at which a dozen steps on 128 random tokens lower
        # the loss by more than the batches' own noise
        t.update(seq_len=16, global_batch=8, warm_steps=1, trace_steps=2, lr=0.003)
    return t


@functools.lru_cache(maxsize=None)
def toy_engine_stats(config_name: str):
    """``engine_stats()`` of a fresh replica of the configuration's family at
    its toy sizes, built in this process (no cluster, no request): the tree
    of counters the ``stats_delta`` readers dig into, as THAT family's
    program has it. A few seconds a family on the CPU, once a process. The
    configuration is found as the harness finds it (``cells.config_of``)."""
    from perfbench import families
    from perfbench.harness import cells
    from perfbench.harness.program import engine_config

    model = tiny(cells.config_of(cells.benchmark(), config_name))
    fam = families.of(model)
    cfg = fam.model_config(model, max_seq_len=int(model["max_position_embeddings"]),
                           **model["serving"].get("model_overrides", {}))
    server = fam.server_class()(cfg, engine_config(model["serving"]["engine"]), seed=7, export_metrics=False)
    try:
        return server.engine_stats()
    finally:
        server.engine.stop()
