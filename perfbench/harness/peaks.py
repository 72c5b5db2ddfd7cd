"""The table of peaks, and the operations a token needs, by configuration.

Peaks are keyed by ``device_kind`` as JAX reports it. A device that is not
in the table is an error, never a default."""

from __future__ import annotations

from typing import Any, Dict

#: Google Cloud documentation, "TPU v5e" system architecture page: 197
#: TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e: 197 TFLOP/s bf16, 16 GB HBM, 819 GB/s)",
    },
}


def peaks_for(device_kind: str) -> Dict[str, Any]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's table of "
            f"peaks ({sorted(PEAKS)}): add it with its source, do not guess"
        ) from None


def layer_params(model: Dict[str, Any]) -> int:
    """Parameters of one decoder layer of a Mistral-style dense block:
    q, k, v, o projections, the gated MLP and two norm vectors."""
    d = model["hidden_size"]
    hd = model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * model["intermediate_size"]
    return attn + mlp + 2 * d


def param_count(model: Dict[str, Any]) -> int:
    """All parameters as run: layers, untied embedding and head, final norm."""
    d, v = model["hidden_size"], model["vocab_size"]
    return model["num_hidden_layers"] * layer_params(model) + 2 * v * d + d


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that a token multiplies against: everything but the
    embedding table (a lookup) and the norm vectors."""
    d, v = model["hidden_size"], model["vocab_size"]
    return model["num_hidden_layers"] * (layer_params(model) - 2 * d) + v * d


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token of a
    causal sequence of ``seq_len``: 6 per matmul parameter, plus attention
    scores and values, 2 matmuls x 2 ops x (mean visible keys = seq/2) x
    heads x head_dim forward, three times that with the backward pass.
    Recomputed operations (remat) are not counted."""
    attn_fwd = (
        model["num_hidden_layers"] * 2 * 2 * (seq_len / 2.0)
        * model["num_attention_heads"] * model["head_dim"]
    )
    return 6.0 * matmul_params(model) + 3.0 * attn_fwd


def forward_flops_per_token(model: Dict[str, Any], context_len: float) -> float:
    """Operations one token's forward pass requires when it attends to
    ``context_len`` keys (serving: prefill token or decode token)."""
    attn = (
        model["num_hidden_layers"] * 2 * 2 * context_len
        * model["num_attention_heads"] * model["head_dim"]
    )
    return 2.0 * matmul_params(model) + attn


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    return (
        model["num_hidden_layers"] * 2 * model["num_key_value_heads"]
        * model["head_dim"] * dtype_bytes
    )
