"""Parameters, operations a token needs, cache bytes a token and the two
attention kernels' operations and bytes of a Mellum2-style decoder
(grouped-query attention at a head width of its own, layers of two kinds, a
window layer that keeps ``sliding_window`` positions and a full layer that
keeps them all, a router over ``deployment.num_experts_total`` experts of
which ``num_experts`` are held here, ``num_experts_per_tok`` a token, no
shared expert, untied head), from the configuration file's keys alone."""

from __future__ import annotations

from typing import Any, Dict, Tuple


def _w(model: Dict[str, Any]) -> Dict[str, int]:
    dep = model.get("deployment") or {}
    kinds = model["layer_types"][: model["num_hidden_layers"]]
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"], KV=model["num_key_value_heads"],
        hd=model["head_dim"], F=model["moe_intermediate_size"], held=model["num_experts"],
        E=int(dep.get("num_experts_total", model["num_experts"])), k=model["num_experts_per_tok"],
        V=model["vocab_size"], L=model["num_hidden_layers"], W=model["sliding_window"],
        window=sum(kind == "sliding_attention" for kind in kinds),
        full=sum(kind == "full_attention" for kind in kinds),
    )


def group_layers(model: Dict[str, Any]) -> Dict[str, Tuple[int, int]]:
    """group -> ``(layers, positions kept (0: all))`` of the paged cache."""
    w = _w(model)
    return {"full": (w["full"], 0), "window": (w["window"], w["W"])}


def projection_params(model: Dict[str, Any]) -> int:
    """q, k, v, o projections of one layer."""
    w = _w(model)
    return 2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]


def expert_params(model: Dict[str, Any]) -> int:
    """One expert: gate, up and down projections."""
    w = _w(model)
    return 3 * w["D"] * w["F"]


def layer_params(model: Dict[str, Any]) -> int:
    """Parameters HELD by one decoder layer: attention, the held experts, the
    router over all experts and the two block norms."""
    w = _w(model)
    return projection_params(model) + w["held"] * expert_params(model) + w["D"] * w["E"] + 2 * w["D"]


def param_count(model: Dict[str, Any]) -> int:
    """All parameters as run: layers, untied embedding and head, final norm."""
    w = _w(model)
    return w["L"] * layer_params(model) + 2 * w["V"] * w["D"] + w["D"]


def matmul_params_per_token(model: Dict[str, Any]) -> float:
    """Weights ONE token is multiplied against on THIS chip: the projections,
    the router, its expected share of the held experts (``top_k x held / E``:
    routing is over all ``E``) and the head; not the embedding (a lookup)."""
    w = _w(model)
    per_layer = projection_params(model) + w["D"] * w["E"] + expert_params(model) * w["k"] * w["held"] / w["E"]
    return w["L"] * per_layer + w["V"] * w["D"]


def keys_seen(model: Dict[str, Any], context_len: float) -> float:
    """Key positions a query at a context of ``context_len`` sees, summed over
    the layers: all of them in a full layer, at most the window in a window layer."""
    w = _w(model)
    return w["full"] * context_len + w["window"] * min(context_len, w["W"])


def forward_flops_per_token(model: Dict[str, Any], context_len: float) -> float:
    """Operations one token's forward pass REQUIRES here when its context is
    ``context_len``: 2 a weight it is multiplied against, and scores and values
    over the keys each kind of layer lets it see."""
    w = _w(model)
    return 2.0 * matmul_params_per_token(model) + 2 * 2 * w["H"] * w["hd"] * keys_seen(model, context_len)


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward per token of a causal sequence of ``seq_len``
    (a full layer's mean context ``seq_len / 2``; a window layer's the mean of
    ``min(i, W)``), recompute not counted."""
    w = _w(model)
    W = min(w["W"], seq_len)
    mean_window = (W * (W + 1) / 2 + (seq_len - W) * W) / seq_len
    keys = w["full"] * seq_len / 2.0 + w["window"] * mean_window
    return 6.0 * matmul_params_per_token(model) + 3.0 * 2 * 2 * w["H"] * w["hd"] * keys


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Cache bytes a token writes: K and V in every layer of both groups (what
    a window group HOLDS of a sequence is bounded: :func:`kv_bytes_held`)."""
    w = _w(model)
    return w["L"] * 2 * w["KV"] * w["hd"] * dtype_bytes


def kv_bytes_held(model: Dict[str, Any], context_len: int, block_size: int = 16,
                  dtype_bytes: int = 2) -> Dict[str, int]:
    """group -> bytes a sequence of ``context_len`` holds in the group's pool
    while it decodes: whole blocks, a window group's from the block that holds
    ``context_len - W`` on."""
    w = _w(model)
    row = 2 * w["KV"] * w["hd"] * dtype_bytes
    out = {}
    for group, (layers, keeps) in group_layers(model).items():
        blocks = -(-context_len // block_size) - (max(0, context_len - keeps) // block_size if keeps else 0)
        out[group] = layers * blocks * block_size * row
    return out


# -- the two attention kernels' costs (ONE layer of a group, one launch) -------

def paged_attn_cost(model: Dict[str, Any], group: str, contexts, block_size: int = 16,
                    dtype_bytes: int = 2) -> Dict[str, float]:
    """The decode kernel (``ops/paged_attention.py``) in ONE layer of
    ``group`` for slots at ``contexts``: the K and V bytes of each slot's live
    blocks (a window layer's from its first live block on), the queries and
    outputs, and the operations of scores and values over the keys each slot
    SEES. It is bound by the bytes."""
    w = _w(model)
    keeps = group_layers(model)[group][1]
    row = 2 * w["KV"] * w["hd"] * dtype_bytes
    blocks = sum(
        -(-c // block_size) - (max(0, c - keeps) // block_size if keeps else 0) for c in contexts
    )
    seen = sum(min(c, keeps) if keeps else c for c in contexts)
    return {
        "bytes": float(blocks * block_size * row + 2 * len(contexts) * w["H"] * w["hd"] * dtype_bytes),
        "flops": float(2 * 2 * w["H"] * w["hd"] * seen),
    }


def chunk_attn_cost(model: Dict[str, Any], group: str, ctx_len: int, chunk: int,
                    dtype_bytes: int = 2) -> Dict[str, float]:
    """The chunk kernel (``ops/latent_flash.py``, grouped heads) in ONE layer
    of ``group`` for ``chunk`` queries after ``ctx_len`` cached positions: the
    operations of scores and values over the (query, key) pairs the mask lets
    through, and the bytes of the queries, the outputs and the keys and values
    read once a KV head. It is bound by the operations."""
    w = _w(model)
    keeps = group_layers(model)[group][1]
    pairs = 0
    for i in range(ctx_len, ctx_len + chunk):
        pairs += min(i + 1, keeps) if keeps else i + 1
    first = max(0, ctx_len - keeps + 1) if keeps else 0
    keys = ctx_len + chunk - first
    return {
        "flops": float(2 * 2 * w["H"] * w["hd"] * pairs),
        "bytes": float(dtype_bytes * (2 * chunk * w["H"] * w["hd"] + 2 * keys * w["KV"] * w["hd"])),
    }
