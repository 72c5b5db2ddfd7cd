"""What the ``kimi_linear`` family counts, from a configuration file alone
(no import of the program): parameters as run on this chip, operations a
token requires, cache bytes a token and state bytes a SEQUENCE.

``num_experts`` in the file is the number of experts HELD here (one chip's
share of the deployment); the router's width is
``deployment.num_experts_total``."""

from __future__ import annotations

from typing import Any, Dict


def _w(model: Dict[str, Any]) -> Dict[str, int]:
    lin = model["linear_attn_config"]
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"], kr=model["kv_lora_rank"],
        dn=model["qk_nope_head_dim"], dr=model["qk_rope_head_dim"], dv=model["v_head_dim"],
        Hk=lin["num_heads"], dk=lin["head_dim"], taps=lin["short_conv_kernel_size"],
        n_kda=len(lin["kda_layers"]), n_mla=len(lin["full_attn_layers"]),
        F=model["intermediate_size"], Fm=model["moe_intermediate_size"],
        held=model["num_experts"], E=model["deployment"]["num_experts_total"],
        shared=model["num_shared_experts"], k=model["num_experts_per_token"],
        L=model["num_hidden_layers"], dense=model["first_k_dense_replace"], V=model["vocab_size"],
    )


def kda_params(model: Dict[str, Any]) -> int:
    """A KDA mixer: the three projections and their convolutions, the two
    low-rank gates (rank = the head size) with ``dt_bias`` and ``A_log``,
    ``W_beta``, the head norm and ``W_o``."""
    w = _w(model)
    W, r = w["Hk"] * w["dk"], w["dk"]
    return (3 * w["D"] * W + w["taps"] * 3 * W + 2 * (w["D"] * r + r * W) + W + w["Hk"]
            + w["D"] * w["Hk"] + w["dk"] + W * w["D"])


def mla_params(model: Dict[str, Any]) -> int:
    """A latent attention: ``W_q`` straight to the heads (no ``q_lora_rank``),
    the projection down with its norm, the projection up, ``W_o``."""
    w = _w(model)
    return (w["D"] * w["H"] * (w["dn"] + w["dr"]) + w["D"] * (w["kr"] + w["dr"]) + w["kr"]
            + w["kr"] * w["H"] * (w["dn"] + w["dv"]) + w["H"] * w["dv"] * w["D"])


def ffn_params(model: Dict[str, Any], moe: bool) -> int:
    """The dense MLP, or the router (+ bias), the shared expert and the HELD experts."""
    w = _w(model)
    if not moe:
        return 3 * w["D"] * w["F"]
    return w["D"] * w["E"] + w["E"] + 3 * w["D"] * w["Fm"] * (w["shared"] + w["held"])


def _kinds(model: Dict[str, Any]):
    """``(is it an attending layer, is it an expert layer)`` a layer, in order."""
    full = set(model["linear_attn_config"]["full_attn_layers"])
    return [(l + 1 in full, l >= model["first_k_dense_replace"]) for l in range(model["num_hidden_layers"])]


def param_count(model: Dict[str, Any]) -> int:
    w = _w(model)
    layers = sum(
        (mla_params(model) if mla else kda_params(model)) + 2 * w["D"] + ffn_params(model, moe)
        for mla, moe in _kinds(model)
    )
    return layers + 2 * w["V"] * w["D"] + w["D"]


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """ONE latent row in each ATTENDING layer (7 of 27): 8,064 B at the published sizes."""
    w = _w(model)
    return w["n_mla"] * (w["kr"] + w["dr"]) * dtype_bytes


def state_bytes_per_seq(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What a SEQUENCE holds in the KDA layers whatever its length: the
    float32 state a head and the last ``taps - 1`` inputs of the three
    convolutions (43.4 MB over 20 layers at the published sizes)."""
    w = _w(model)
    return w["n_kda"] * (w["Hk"] * w["dk"] * w["dk"] * 4 + (w["taps"] - 1) * 3 * w["Hk"] * w["dk"] * dtype_bytes)


def kda_state_flops_per_token(model: Dict[str, Any]) -> int:
    """Operations of ONE layer's recurrence for one token, all heads: the
    decay (1 a number of the state), ``S^T k``, the rank-one update and ``S^T
    q`` (2 each)."""
    w = _w(model)
    return w["Hk"] * 7 * w["dk"] * w["dk"]


def kda_update_bytes(model: Dict[str, Any], slots: int) -> int:
    """Bytes ONE layer's decode update must move for ``slots`` sequences: the
    float32 state read and written once."""
    w = _w(model)
    return slots * 2 * w["Hk"] * w["dk"] * w["dk"] * 4


def matmul_params_per_token(model: Dict[str, Any]) -> float:
    """Weights one token is multiplied against on THIS chip (the embedding
    is a lookup; of the held experts its expected share ``top_k x held / E``)."""
    w = _w(model)
    total = w["V"] * w["D"]
    for mla, moe in _kinds(model):
        total += mla_params(model) - w["kr"] if mla else kda_params(model) - w["dk"] - w["Hk"]
        total += (w["D"] * w["E"] + 3 * w["D"] * w["Fm"] * (w["shared"] + w["k"] * w["held"] / w["E"])
                  if moe else 3 * w["D"] * w["F"])
    return total


def forward_flops_per_token(model: Dict[str, Any], context_len: int) -> float:
    """Operations one token's forward pass REQUIRES here at a context length:
    2 a weight it is multiplied against, the recurrence in the KDA layers
    (whatever the context) and scores and values over the context in the
    attending layers, expanded (the cheaper a pair)."""
    w = _w(model)
    pair = w["H"] * (2 * (w["dn"] + w["dr"]) + 2 * w["dv"])
    return (2 * matmul_params_per_token(model) + w["n_kda"] * kda_state_flops_per_token(model)
            + w["n_mla"] * pair * context_len)


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward over a causal sequence (mean context ``seq_len / 2``)."""
    return 3 * forward_flops_per_token(model, seq_len / 2)
