"""Parameters, operations a token needs, cache bytes a token and the
expert FFN's operations and bytes of an OLMoE-style decoder (multi-head
attention with QK-norm, a router and ``num_experts`` gated SiLU experts of
width ``intermediate_size`` per layer, ``num_experts_per_tok`` of them a
token, no shared expert, untied head), from the configuration file's keys
alone."""

from __future__ import annotations

from typing import Any, Dict


def projection_params(model: Dict[str, Any]) -> int:
    """q, k, v, o projections of one layer."""
    d, hd = model["hidden_size"], model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def attention_params(model: Dict[str, Any]) -> int:
    """The projections and the two QK-norm vectors of one layer."""
    heads = model["num_attention_heads"] + model["num_key_value_heads"]
    return projection_params(model) + heads * model["head_dim"]


def expert_params(model: Dict[str, Any]) -> int:
    """One expert: gate, up and down projections."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def layer_params(model: Dict[str, Any]) -> int:
    """Parameters HELD by one decoder layer: attention, every expert, the
    router and the two block norms."""
    d = model["hidden_size"]
    return (
        attention_params(model) + model["num_experts"] * expert_params(model)
        + d * model["num_experts"] + 2 * d
    )


def param_count(model: Dict[str, Any]) -> int:
    """All parameters as run: layers, untied embedding and head, final norm."""
    d, v = model["hidden_size"], model["vocab_size"]
    return model["num_hidden_layers"] * layer_params(model) + 2 * v * d + d


def active_matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that ONE token multiplies against: the projections, the
    router, the ``num_experts_per_tok`` experts it is routed to (not all)
    and the head; not the embedding table (a lookup) nor the norm vectors."""
    d = model["hidden_size"]
    per_layer = (
        projection_params(model) + d * model["num_experts"]
        + model["num_experts_per_tok"] * expert_params(model)
    )
    return model["num_hidden_layers"] * per_layer + model["vocab_size"] * d


def _attention_flops(model: Dict[str, Any], keys: float) -> float:
    """Scores and values of one token over ``keys`` visible keys, forward."""
    return (
        model["num_hidden_layers"] * 2 * 2 * keys
        * model["num_attention_heads"] * model["head_dim"]
    )


def forward_flops_per_token(model: Dict[str, Any], context_len: float) -> float:
    """Operations one token's forward pass requires when it attends to
    ``context_len`` keys, over the experts it is routed to."""
    return 2.0 * active_matmul_params(model) + _attention_flops(model, context_len)


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward per token of a causal sequence of ``seq_len``
    (mean visible keys = seq/2), recompute not counted."""
    return 6.0 * active_matmul_params(model) + 3.0 * _attention_flops(model, seq_len / 2.0)


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return (
        model["num_hidden_layers"] * 2 * model["num_key_value_heads"]
        * model["head_dim"] * dtype_bytes
    )


# -- the grouped expert matmul's roofline (one layer, one launch) -------------

def moe_ffn_flops(model: Dict[str, Any], assignments: float) -> float:
    """Operations the expert FFN of ONE layer requires for ``assignments``
    (token, expert) pairs: three matmuls of 2 x hidden x width each. Tile
    padding of a grouped matmul is not required work and is not counted."""
    return 2.0 * assignments * expert_params(model)


def moe_ffn_bytes(model: Dict[str, Any], assignments: float, experts_touched: float,
                  dtype_bytes: int = 2) -> float:
    """Bytes the expert FFN of ONE layer has to move: the weights of the
    experts that received a row, once each; per assignment the input row
    read twice (gate, up) and the expert's hidden row written and read,
    and the output row written."""
    d, f = model["hidden_size"], model["intermediate_size"]
    rows = assignments * (3 * d + 2 * f)
    return dtype_bytes * (experts_touched * expert_params(model) + rows)
