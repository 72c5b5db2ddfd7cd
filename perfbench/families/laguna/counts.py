"""Parameters, operations a token needs, cache bytes a token and the two
attention kernels' operations and bytes of a Laguna-style decoder
(grouped-query attention in layers of two kinds with their own number of query
heads, a gate a head, a window layer that keeps ``sliding_window`` positions
and a full layer that keeps them all, dense layers of ``intermediate_size``
beside sparse ones: a router over ``deployment.num_experts_total`` experts of
which ``num_experts`` are held here, ``num_experts_per_tok`` a token, and a
shared expert; untied head), from the configuration file's keys alone."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

KIND_OF_GROUP = {"full": "full_attention", "window": "sliding_attention"}


def _w(model: Dict[str, Any]) -> Dict[str, Any]:
    dep = model.get("deployment") or {}
    n = model["num_hidden_layers"]
    return dict(
        D=model["hidden_size"], KV=model["num_key_value_heads"], hd=model["head_dim"],
        I=model["intermediate_size"], F=model["moe_intermediate_size"],
        S=model["shared_expert_intermediate_size"], held=model["num_experts"],
        E=int(dep.get("num_experts_total", model["num_experts"])), k=model["num_experts_per_tok"],
        V=model["vocab_size"], L=n, W=model["sliding_window"], gate=1 if model["gating"] else 0,
        kinds=model["layer_types"][:n], heads=model["num_attention_heads_per_layer"][:n],
        ffn=model["mlp_layer_types"][:n],
    )


def group_layers(model: Dict[str, Any]) -> Dict[str, Tuple[int, int]]:
    """group -> ``(layers, positions kept (0: all))`` of the paged cache."""
    w = _w(model)
    return {group: (w["kinds"].count(kind), w["W"] if group == "window" else 0)
            for group, kind in KIND_OF_GROUP.items()}


def group_heads(model: Dict[str, Any], group: str) -> int:
    """The query heads of the layers of ``group`` (one number a kind)."""
    w = _w(model)
    heads = {h for h, kind in zip(w["heads"], w["kinds"]) if kind == KIND_OF_GROUP[group]}
    if len(heads) != 1:
        raise ValueError(f"the {group} layers have {sorted(heads)} query heads: one number a kind")
    return heads.pop()


def attention_params(model: Dict[str, Any], layer: int) -> int:
    """q, k, v, o projections and the gate a head of one layer."""
    w = _w(model)
    H = w["heads"][layer]
    return 2 * w["D"] * H * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"] + w["gate"] * w["D"] * H


def expert_params(model: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down projections."""
    w = _w(model)
    return 3 * w["D"] * w["F"]


def ffn_params(model: Dict[str, Any], layer: int) -> int:
    """The FFN HELD by one layer: the dense MLP, or the held experts, the
    shared one and the router over all experts."""
    w = _w(model)
    if w["ffn"][layer] == "dense":
        return 3 * w["D"] * w["I"]
    return w["held"] * expert_params(model) + 3 * w["D"] * w["S"] + w["D"] * w["E"]


def layer_params(model: Dict[str, Any], layer: int) -> int:
    return attention_params(model, layer) + ffn_params(model, layer) + 2 * _w(model)["D"]


def param_count(model: Dict[str, Any]) -> int:
    """All parameters as run: layers, untied embedding and head, final norm."""
    w = _w(model)
    return sum(layer_params(model, l) for l in range(w["L"])) + 2 * w["V"] * w["D"] + w["D"]


def matmul_params_per_token(model: Dict[str, Any]) -> float:
    """Weights ONE token is multiplied against on THIS chip: the projections
    and the gate, a dense layer's MLP, a sparse layer's router, shared expert
    and its expected share of the held experts (``top_k x held / E``: routing
    is over all ``E``), and the head; not the embedding (a lookup)."""
    w = _w(model)
    total = float(w["V"] * w["D"])
    for l in range(w["L"]):
        total += attention_params(model, l)
        if w["ffn"][l] == "dense":
            total += 3 * w["D"] * w["I"]
        else:
            total += w["D"] * w["E"] + 3 * w["D"] * w["S"] + expert_params(model) * w["k"] * w["held"] / w["E"]
    return total


def _pair_flops(model: Dict[str, Any], full_keys: float, window_keys: float) -> float:
    """Scores and values over the keys a query sees, summed over the layers:
    ``2 x 2 x H_l x hd`` a (query, key) pair in a layer of ``H_l`` heads."""
    w = _w(model)
    return sum(
        2 * 2 * H * w["hd"] * (full_keys if kind == "full_attention" else window_keys)
        for H, kind in zip(w["heads"], w["kinds"])
    )


def forward_flops_per_token(model: Dict[str, Any], context_len: float) -> float:
    """Operations one token's forward pass REQUIRES here when its context is
    ``context_len``: 2 a weight it is multiplied against, and scores and values
    over the keys each kind of layer lets it see."""
    w = _w(model)
    return 2.0 * matmul_params_per_token(model) + _pair_flops(model, context_len, min(context_len, w["W"]))


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward per token of a causal sequence of ``seq_len``
    (a full layer's mean context ``seq_len / 2``; a window layer's the mean of
    ``min(i, W)``), recompute not counted."""
    w = _w(model)
    W = min(w["W"], seq_len)
    mean_window = (W * (W + 1) / 2 + (seq_len - W) * W) / seq_len
    return 6.0 * matmul_params_per_token(model) + 3.0 * _pair_flops(model, seq_len / 2.0, mean_window)


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Cache bytes a token writes: K and V in every layer of both groups (what
    a window group HOLDS of a sequence is bounded: :func:`kv_bytes_held`)."""
    w = _w(model)
    return w["L"] * 2 * w["KV"] * w["hd"] * dtype_bytes


def _live_blocks(context_len: int, keeps: int, block_size: int) -> int:
    """Blocks a sequence at ``context_len`` holds in a group: whole blocks, a
    window group's from the block that holds ``context_len - keeps`` on."""
    return -(-context_len // block_size) - (max(0, context_len - keeps) // block_size if keeps else 0)


def kv_bytes_held(model: Dict[str, Any], context_len: int, block_size: int = 16,
                  dtype_bytes: int = 2) -> Dict[str, int]:
    """group -> bytes a sequence of ``context_len`` holds in the group's pool
    while it decodes."""
    w = _w(model)
    row = 2 * w["KV"] * w["hd"] * dtype_bytes
    return {group: layers * _live_blocks(context_len, keeps, block_size) * block_size * row
            for group, (layers, keeps) in group_layers(model).items()}


# -- the two attention kernels' costs (ONE layer of a group, one launch) -------

def paged_attn_cost(model: Dict[str, Any], group: str, contexts: List[int], block_size: int = 16,
                    dtype_bytes: int = 2) -> Dict[str, float]:
    """The decode kernel (``ops/paged_attention.py``) in ONE layer of
    ``group`` for slots at ``contexts``: the K and V bytes of each slot's live
    blocks (a window layer's from its first live block on), the queries and
    outputs of the group's heads, and the operations of scores and values over
    the keys each slot SEES. It is bound by the bytes."""
    w = _w(model)
    keeps, H = group_layers(model)[group][1], group_heads(model, group)
    row = 2 * w["KV"] * w["hd"] * dtype_bytes
    blocks = sum(_live_blocks(c, keeps, block_size) for c in contexts)
    seen = sum(min(c, keeps) if keeps else c for c in contexts)
    return {
        "bytes": float(blocks * block_size * row + 2 * len(contexts) * H * w["hd"] * dtype_bytes),
        "flops": float(2 * 2 * H * w["hd"] * seen),
    }


def chunk_attn_cost(model: Dict[str, Any], group: str, ctx_len: int, chunk: int,
                    dtype_bytes: int = 2) -> Dict[str, float]:
    """The chunk kernel (``ops/latent_flash.py``, grouped heads) in ONE layer
    of ``group`` for ``chunk`` queries after ``ctx_len`` cached positions: the
    operations of scores and values over the (query, key) pairs the mask lets
    through, and the bytes of the queries, the outputs and the keys and values
    read once a KV head. It is bound by the operations."""
    w = _w(model)
    keeps, H = group_layers(model)[group][1], group_heads(model, group)
    pairs = sum(min(i + 1, keeps) if keeps else i + 1 for i in range(ctx_len, ctx_len + chunk))
    first = max(0, ctx_len - keeps + 1) if keeps else 0
    keys = ctx_len + chunk - first
    return {
        "flops": float(2 * 2 * H * w["hd"] * pairs),
        "bytes": float(dtype_bytes * (2 * chunk * H * w["hd"] + 2 * keys * w["KV"] * w["hd"])),
    }
