"""What the ``olmo_hybrid`` family counts, from a configuration file alone (no
import of the program): parameters as run, operations a token requires, cache
bytes a token, state bytes a SEQUENCE, and the bytes each of the decode step's
two kernels must move (for its share of the HBM roofline from the trace's
``kda_update.N`` and ``paged_attn.N`` durations)."""

from __future__ import annotations

from typing import Any, Dict


def _w(model: Dict[str, Any]) -> Dict[str, int]:
    kinds = list(model["layer_types"])
    if len(kinds) != model["num_hidden_layers"]:
        raise ValueError("layer_types names one kind a layer: its length is num_hidden_layers")
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"], KV=model["num_key_value_heads"],
        hd=model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"],
        Hk=model["linear_num_key_heads"], Hv=model["linear_num_value_heads"],
        dk=model["linear_key_head_dim"], dv=model["linear_value_head_dim"], taps=model["linear_conv_kernel_dim"],
        F=model["intermediate_size"], V=model["vocab_size"], L=model["num_hidden_layers"],
        n_gdn=kinds.count("linear_attention"), n_attn=kinds.count("full_attention"),
    )


def gdn_params(model: Dict[str, Any]) -> int:
    """A Gated DeltaNet mixer: ``W_q``, ``W_k``, ``W_v`` and their filters,
    ``W_a`` and ``W_b`` with ``A_log`` and ``dt_bias`` a head, the output gate
    ``W_g``, the head norm and ``W_o`` (88,750,332 at the published widths)."""
    w = _w(model)
    conv = 2 * w["Hk"] * w["dk"] + w["Hv"] * w["dv"]
    return (w["D"] * conv + w["taps"] * conv + 2 * w["D"] * w["Hv"] + 2 * w["Hv"]
            + w["D"] * w["Hv"] * w["dv"] + w["dv"] + w["Hv"] * w["dv"] * w["D"])


def attn_params(model: Dict[str, Any]) -> int:
    """An attention mixer: ``W_q``, ``W_k``, ``W_v``, ``W_o`` and the two QK-norm
    vectors (58,990,080 at the published widths)."""
    w = _w(model)
    return (w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"] + w["H"] * w["hd"] + w["KV"] * w["hd"]
            + w["H"] * w["hd"] * w["D"])


def param_count(model: Dict[str, Any]) -> int:
    """Every layer: its mixer, the gated MLP and the two norms on the sublayers'
    outputs; the embedding, the untied head and the final norm."""
    w = _w(model)
    every = 3 * w["D"] * w["F"] + 2 * w["D"]
    return (w["n_gdn"] * (gdn_params(model) + every) + w["n_attn"] * (attn_params(model) + every)
            + 2 * w["V"] * w["D"] + w["D"])


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """A K and a V row of every KV head in each ATTENDING layer: 61,440 B over 4 layers of 30 x 128."""
    w = _w(model)
    return w["n_attn"] * 2 * w["KV"] * w["hd"] * dtype_bytes


def state_bytes_per_seq(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What a SEQUENCE holds in the Gated DeltaNet layers whatever its length:
    the float32 state a head and the last ``taps - 1`` inputs of the
    convolution over q, k and v (27,371,520 B over 12 layers of 30 x 96 x 192)."""
    w = _w(model)
    conv = 2 * w["Hk"] * w["dk"] + w["Hv"] * w["dv"]
    return w["n_gdn"] * (w["Hv"] * w["dk"] * w["dv"] * 4 + (w["taps"] - 1) * conv * dtype_bytes)


def gdn_state_flops_per_token(model: Dict[str, Any]) -> int:
    """Operations of ONE layer's recurrence for one token, all heads: the decay
    (1 a number of the state), ``S^T k``, the rank-one update and ``S^T q`` (2 each)."""
    w = _w(model)
    return w["Hv"] * 7 * w["dk"] * w["dv"]


def gdn_update_bytes(model: Dict[str, Any], slots: int) -> int:
    """Bytes ONE layer's decode update must move for ``slots`` sequences: the
    float32 state read and written once (4,423,680 B a slot at 30 x 96 x 192)."""
    w = _w(model)
    return slots * 2 * w["Hv"] * w["dk"] * w["dv"] * 4


def paged_attn_bytes(model: Dict[str, Any], live_tokens: int, dtype_bytes: int = 2) -> int:
    """Bytes ONE attending layer's decode attention must move over
    ``live_tokens`` cached positions (all slots together): their K and V rows
    read once (15,360 B a token at 30 x 128 in bf16)."""
    w = _w(model)
    return live_tokens * 2 * w["KV"] * w["hd"] * dtype_bytes


def matmul_params_per_token(model: Dict[str, Any]) -> int:
    """Weights one token is multiplied against (the embedding is a lookup; the
    filters, the norms and the gates' biases multiply nothing)."""
    w = _w(model)
    conv = 2 * w["Hk"] * w["dk"] + w["Hv"] * w["dv"]
    gdn = w["D"] * conv + 2 * w["D"] * w["Hv"] + 2 * w["D"] * w["Hv"] * w["dv"]
    attn = w["D"] * w["hd"] * (2 * w["H"] + 2 * w["KV"])
    return w["n_gdn"] * gdn + w["n_attn"] * attn + w["L"] * 3 * w["D"] * w["F"] + w["V"] * w["D"]


def forward_flops_per_token(model: Dict[str, Any], context_len: int) -> float:
    """Operations one token's forward pass REQUIRES at a context length: 2 a
    weight it is multiplied against, the recurrence in the Gated DeltaNet layers
    (whatever the context) and scores and values over the context in the
    attending layers."""
    w = _w(model)
    return (2 * matmul_params_per_token(model) + w["n_gdn"] * gdn_state_flops_per_token(model)
            + w["n_attn"] * 4 * w["H"] * w["hd"] * context_len)


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward over a causal sequence (mean context ``seq_len / 2``)."""
    return 3 * forward_flops_per_token(model, seq_len / 2)
