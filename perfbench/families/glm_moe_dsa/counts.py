"""What the ``glm_moe_dsa`` family counts, from a configuration file alone (no
import of the program): parameters as run on this chip (the indexer of every
layer and the MTP module included), operations a token requires (the indexer
over ALL of its context, the attention over the ``index_topk`` positions it
keeps), cache bytes a token (BOTH rows of every layer, the module's among them),
and what the three parts of the selection multiply and move, for their shares
of the roofline (``PERF.md``; the harness has no roofline reader).

``n_routed_experts`` in the file is the number of experts HELD here (one chip's
share of the deployment); the router's width is
``deployment.n_routed_experts_total``. ``num_hidden_layers`` counts the main
model's layers; the MTP module (``num_nextn_predict_layers``) is one more block
of the expert kind beside ``eh_proj`` and three norm vectors."""

from __future__ import annotations

from typing import Any, Dict


def _w(model: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"], qr=model["q_lora_rank"],
        kr=model["kv_lora_rank"], dn=model["qk_nope_head_dim"], dr=model["qk_rope_head_dim"],
        dv=model["v_head_dim"], F=model["intermediate_size"], Fm=model["moe_intermediate_size"],
        held=model["n_routed_experts"], E=model["deployment"]["n_routed_experts_total"],
        shared=model["n_shared_experts"], k=model["num_experts_per_tok"],
        L=model["num_hidden_layers"], dense=model["first_k_dense_replace"], V=model["vocab_size"],
        mtp=model["num_nextn_predict_layers"],
        Hi=model["index_n_heads"], di=model["index_head_dim"], topk=model["index_topk"],
    )


def indexer_params(model: Dict[str, Any]) -> int:
    """The indexer of one layer: its queries' projection from the query's
    latent, its key's from the layer's input with the layer norm's weight and
    bias, and the heads' weights'."""
    w = _w(model)
    return w["qr"] * w["Hi"] * w["di"] + w["D"] * w["di"] + 2 * w["di"] + w["D"] * w["Hi"]


def attention_params(model: Dict[str, Any]) -> int:
    """The two projections down and two up, their two norms, ``W_o``, and the indexer."""
    w = _w(model)
    return (w["D"] * w["qr"] + w["qr"] + w["qr"] * w["H"] * (w["dn"] + w["dr"])
            + w["D"] * (w["kr"] + w["dr"]) + w["kr"] + w["kr"] * w["H"] * (w["dn"] + w["dv"])
            + w["H"] * w["dv"] * w["D"] + indexer_params(model))


def layer_params(model: Dict[str, Any], moe: bool) -> int:
    """One layer as held here: attention with its indexer, both block norms,
    and the dense MLP or the router (+ bias), the shared expert and the HELD experts."""
    w = _w(model)
    ffn = 3 * w["D"] * w["F"]
    if moe:
        ffn = w["D"] * w["E"] + w["E"] + 3 * w["D"] * w["Fm"] * (w["shared"] + w["held"])
    return attention_params(model) + 2 * w["D"] + ffn


def mtp_params(model: Dict[str, Any]) -> int:
    """The MTP module: ``eh_proj [2 D, D]``, its three norm vectors, one
    expert layer (the embedding and the head are the main model's)."""
    w = _w(model)
    return w["mtp"] * (2 * w["D"] * w["D"] + 3 * w["D"] + layer_params(model, True))


def param_count(model: Dict[str, Any]) -> int:
    w = _w(model)
    return (w["dense"] * layer_params(model, False) + (w["L"] - w["dense"]) * layer_params(model, True)
            + 2 * w["V"] * w["D"] + w["D"] + mtp_params(model))


def index_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """The indexer's key of every layer, the MTP module's among them."""
    w = _w(model)
    return (w["L"] + w["mtp"]) * w["di"] * dtype_bytes


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """BOTH rows a layer (the latent row and the indexer's key), the MTP module's among them."""
    w = _w(model)
    return (w["L"] + w["mtp"]) * (w["kr"] + w["dr"]) * dtype_bytes + index_bytes_per_token(model, dtype_bytes)


def attention_flops_per_pair(model: Dict[str, Any], absorbed: bool) -> int:
    """Operations of one (query, attended position) pair in one layer, all
    heads: scores and values over the latent row (absorbed: ``2 (kr + dr) +
    2 kr`` a head, what the program does on both of its paths) or over expanded
    heads (``2 (dn + dr) + 2 dv``: what the mathematics requires)."""
    w = _w(model)
    a_head = 2 * (w["kr"] + w["dr"]) + 2 * w["kr"] if absorbed else 2 * (w["dn"] + w["dr"]) + 2 * w["dv"]
    return w["H"] * a_head


def index_flops_per_pair(model: Dict[str, Any]) -> int:
    """Operations of one (query, earlier position) pair of the indexer in one
    layer: a product over ``di`` a head, the relu, the weight and the sum."""
    w = _w(model)
    return w["Hi"] * (2 * w["di"] + 3)


def index_scores_cost(model: Dict[str, Any], window: int, keys: int, dtype_bytes: int = 2) -> Dict[str, float]:
    """``dsa.index`` of ONE layer: ``window`` queries scored against ``keys``
    positions. ``bytes``: the queries, their weights and the keys in, the
    float32 scores out (the per-head products are the fusion's own)."""
    w = _w(model)
    moved = dtype_bytes * (window * w["Hi"] * w["di"] + keys * w["di"]) + 4 * window * (w["Hi"] + keys)
    return {"flops": float(window * keys * index_flops_per_pair(model)), "bytes": float(moved)}


def topk_cost(model: Dict[str, Any], window: int, keys: int, passes: int = 32) -> Dict[str, float]:
    """``dsa.topk`` of ONE layer: the radix select compares and counts the
    ``window x keys`` ordered scores once a bit (``passes``: 2 operations an
    element a pass, on the vector unit). ``bytes``: what it MUST move, the
    float32 scores in and the mask out; the passes between re-read the ordered
    scores from wherever the compiler keeps them (67 MB at 1024 x 16384: on a
    v5e they stay in VMEM, and the part is bound by the vector unit, not HBM)."""
    return {"flops": float(2 * passes * window * keys), "bytes": float(window * keys * (4 + 1))}


def masked_attend_cost(model: Dict[str, Any], window: int, keys: int, dtype_bytes: int = 2) -> Dict[str, float]:
    """``dsa.attend`` of ONE layer's prefill chunk: ``window`` absorbed queries
    over ALL ``keys`` latent rows of the rung under the selection as a mask.
    ``bytes``: what the mathematics must move (queries in, rows once, the mask,
    the output); the float32 scores that XLA spills between its two products
    are the implementation's, not the roofline's."""
    w = _w(model)
    width = w["kr"] + w["dr"]
    moved = dtype_bytes * (window * w["H"] * (width + w["kr"]) + keys * width) + window * keys
    return {"flops": float(window * keys * attention_flops_per_pair(model, True)), "bytes": float(moved)}


def gathered_attend_cost(model: Dict[str, Any], window: int, dtype_bytes: int = 2) -> Dict[str, float]:
    """``dsa.attend`` of ONE slot of a decode or verify window in one layer:
    ``window`` queries, each over its own ``index_topk`` rows gathered by token."""
    w = _w(model)
    width = w["kr"] + w["dr"]
    moved = dtype_bytes * window * (w["topk"] * width + w["H"] * (width + w["kr"]))
    return {"flops": float(window * w["topk"] * attention_flops_per_pair(model, True)), "bytes": float(moved)}


def matmul_params_per_token(model: Dict[str, Any]) -> float:
    """Weights one token is multiplied against on THIS chip by the MAIN model:
    attention and the indexer's projections, the router, the shared expert, its
    expected share of the held experts (``top_k x held / E``: routing is over
    all ``E``), the dense MLPs and the head (the embedding is a lookup)."""
    w = _w(model)
    attn = attention_params(model) - w["qr"] - w["kr"] - 2 * w["di"]
    dense = attn + 3 * w["D"] * w["F"]
    moe = attn + w["D"] * w["E"] + 3 * w["D"] * w["Fm"] * (w["shared"] + w["k"] * w["held"] / w["E"])
    return w["dense"] * dense + (w["L"] - w["dense"]) * moe + w["V"] * w["D"]


def forward_flops_per_token(model: Dict[str, Any], context_len: int) -> float:
    """Operations one token's forward pass REQUIRES of the main model here at a
    context length: 2 a weight it is multiplied against, the indexer over ALL
    of the context, scores and values (expanded form) over the ``min(context,
    index_topk)`` positions it keeps. The MTP module is a drafter's cost, not a
    token's requirement, and is not counted."""
    w = _w(model)
    attended = min(context_len, w["topk"])
    return (2 * matmul_params_per_token(model)
            + w["L"] * (index_flops_per_pair(model) * context_len + attention_flops_per_pair(model, False) * attended))


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward over a causal sequence (mean context ``seq_len / 2``)."""
    return 3 * forward_flops_per_token(model, seq_len / 2)
