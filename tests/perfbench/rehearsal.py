"""Tiny versions of the benchmark's real files for the CPU rehearsal: the
same structure and generator kinds, toy sizes. Never used on the chip."""

import copy
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "perfbench")

TINY_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
    "intermediate_size": 128, "vocab_size": 256, "num_hidden_layers": 2,
    "max_position_embeddings": 128, "torch_dtype": "float32",
}
TINY_LENGTHS = {
    "pairing_seed": 1,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8, "clip": [8, 60]},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.6, "clip": [4, 12]},
}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def tiny_config(name: str):
    cfg = copy.deepcopy(_load("configs", f"{name}.json"))
    cfg.update(TINY_MODEL)
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
        t.update(seq_len=16, global_batch=8, warm_steps=1, trace_steps=2)
    return t
