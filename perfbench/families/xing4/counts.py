"""What the ``xing4`` family counts, from a configuration file alone (no
import of the program): parameters as run on this chip, operations a token
requires, cache bytes a token, and the two latent-attention forms' costs.

``n_routed_experts`` in the file is the number of experts HELD here (one
chip's share of the deployment); the router's width is
``deployment.n_routed_experts_total``."""

from __future__ import annotations

from typing import Any, Dict


def _w(model: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"], qr=model["q_lora_rank"],
        kr=model["kv_lora_rank"], dn=model["qk_nope_head_dim"], dr=model["qk_rope_head_dim"],
        dv=model["v_head_dim"], F=model["intermediate_size"], Fm=model["moe_intermediate_size"],
        held=model["n_routed_experts"], E=model["deployment"]["n_routed_experts_total"],
        shared=model["n_shared_experts"], k=model["num_experts_per_tok"], n=model["hc_mult"],
        L=model["num_hidden_layers"], dense=model["first_k_dense_replace"], V=model["vocab_size"],
    )


def attention_params(model: Dict[str, Any]) -> int:
    """The two projections down and two up, their two norms, and ``W_o``."""
    w = _w(model)
    return (w["D"] * w["qr"] + w["qr"] + w["qr"] * w["H"] * (w["dn"] + w["dr"])
            + w["D"] * (w["kr"] + w["dr"]) + w["kr"] + w["kr"] * w["H"] * (w["dn"] + w["dv"])
            + w["H"] * w["dv"] * w["D"])


def mhc_params(model: Dict[str, Any]) -> int:
    """Both sublayers' maps of one layer: phi, b and alpha each."""
    w = _w(model)
    maps = 2 * w["n"] + w["n"] ** 2
    return 2 * (w["n"] * w["D"] * maps + maps + 3)


def layer_params(model: Dict[str, Any], moe: bool) -> int:
    """One layer as held here: attention, mHC, both block norms, and the
    dense MLP or the router (+ bias), the shared expert and the HELD experts."""
    w = _w(model)
    ffn = 3 * w["D"] * w["F"]
    if moe:
        ffn = w["D"] * w["E"] + w["E"] + 3 * w["D"] * w["Fm"] * (w["shared"] + w["held"])
    return attention_params(model) + mhc_params(model) + 2 * w["D"] + ffn


def param_count(model: Dict[str, Any]) -> int:
    w = _w(model)
    return (w["dense"] * layer_params(model, False) + (w["L"] - w["dense"]) * layer_params(model, True)
            + 2 * w["V"] * w["D"] + w["D"])


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """ONE latent row a layer: the normed latent and the rotated rope part."""
    w = _w(model)
    return w["L"] * (w["kr"] + w["dr"]) * dtype_bytes


def attention_flops_per_pair(model: Dict[str, Any], absorbed: bool) -> int:
    """Operations of one (query, cached position) pair in one layer, all
    heads: scores and values over the latent row (absorbed: ``2 (kr + dr) +
    2 kr`` a head) or over expanded heads (``2 (dn + dr) + 2 dv``)."""
    w = _w(model)
    a_head = 2 * (w["kr"] + w["dr"]) + 2 * w["kr"] if absorbed else 2 * (w["dn"] + w["dr"]) + 2 * w["dv"]
    return w["H"] * a_head


def expansion_flops_per_position(model: Dict[str, Any]) -> int:
    """Operations to expand K and V of ONE cached position from its latent in
    one layer (the expanded form pays this a launch, whatever the queries)."""
    w = _w(model)
    return 2 * w["kr"] * w["H"] * (w["dn"] + w["dv"])


def absorb_break_even_window(model: Dict[str, Any]) -> float:
    """Queries a slot above which expanding costs less than absorbing."""
    return expansion_flops_per_position(model) / (
        attention_flops_per_pair(model, True) - attention_flops_per_pair(model, False)
    )


def matmul_params_per_token(model: Dict[str, Any]) -> float:
    """Weights one token is multiplied against on THIS chip: attention, the
    mHC maps, the router, the shared expert, its expected share of the held
    experts (``top_k x held / E``: routing is over all ``E``), the dense
    MLPs and the head (the embedding is a lookup)."""
    w = _w(model)
    maps = 2 * w["n"] * w["D"] * (2 * w["n"] + w["n"] ** 2)
    attn = attention_params(model) - w["qr"] - w["kr"]
    dense = attn + maps + 3 * w["D"] * w["F"]
    moe = (attn + maps + w["D"] * w["E"]
           + 3 * w["D"] * w["Fm"] * (w["shared"] + w["k"] * w["held"] / w["E"]))
    return w["dense"] * dense + (w["L"] - w["dense"]) * moe + w["V"] * w["D"]


def forward_flops_per_token(model: Dict[str, Any], context_len: int) -> float:
    """Operations one token's forward pass REQUIRES here at a context length:
    2 a weight it is multiplied against, and scores and values over the
    context in the expanded form (the cheaper a pair; the expansion itself is
    a launch's, :func:`expansion_flops_per_position`)."""
    w = _w(model)
    return 2 * matmul_params_per_token(model) + w["L"] * attention_flops_per_pair(model, False) * context_len


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward over a causal sequence (mean context ``seq_len / 2``)."""
    return 3 * forward_flops_per_token(model, seq_len / 2)
