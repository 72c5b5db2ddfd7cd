"""What the ``jamba`` family counts, from a configuration file alone (no
import of the program): parameters as run, operations a token requires, cache
bytes a token, state bytes a SEQUENCE, and what one call of each of the
recurrence's kernels has to do (:func:`ssm_scan_cost`, :func:`ssm_update_bytes`):
a kernel's share of its roofline is ``least time / (time share x busy /
calls)`` against them."""

from __future__ import annotations

from typing import Any, Dict, List


def _w(model: Dict[str, Any]) -> Dict[str, int]:
    D, H = model["hidden_size"], model["num_attention_heads"]
    kinds = layer_kinds(model)
    return dict(
        D=D, H=H, KV=model["num_key_value_heads"], hd=model.get("head_dim") or D // H,
        Di=model["mamba_expand"] * D, N=model["mamba_d_state"], K=model["mamba_d_conv"],
        R=model["mamba_dt_rank"], F=model["intermediate_size"], L=model["num_hidden_layers"],
        V=model["vocab_size"], n_attn=kinds.count("attention"), n_mamba=kinds.count("mamba"),
    )


def layer_kinds(model: Dict[str, Any]) -> List[str]:
    """``"attention"`` | ``"mamba"`` a layer: layer ``l`` attends iff ``l % period == offset``."""
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return ["attention" if l % period == offset else "mamba" for l in range(model["num_hidden_layers"])]


def mamba_params(model: Dict[str, Any]) -> int:
    """A Mamba mixer: ``in_proj`` D x 2 Di, the filter and its bias, ``x_proj``
    Di x (R + 2 N), the three inner norms, ``dt_proj`` R x Di and its bias,
    ``A_log`` N x Di, the skip ``D``, ``out_proj`` Di x D."""
    w = _w(model)
    D, Di, N, K, R = w["D"], w["Di"], w["N"], w["K"], w["R"]
    return (D * 2 * Di + K * Di + Di + Di * (R + 2 * N) + R + 2 * N + R * Di + Di + N * Di + Di + Di * D)


def attention_params(model: Dict[str, Any]) -> int:
    """An attention mixer: q, k, v, o; no bias, no norm, no position table."""
    w = _w(model)
    return 2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]


def mlp_params(model: Dict[str, Any]) -> int:
    w = _w(model)
    return 3 * w["D"] * w["F"]


def param_count(model: Dict[str, Any]) -> int:
    """Parameters as run: the whole model; the embedding counted once (it is also the head)."""
    w = _w(model)
    per_layer = mlp_params(model) + 2 * w["D"]
    return (w["n_mamba"] * (mamba_params(model) + per_layer) + w["n_attn"] * (attention_params(model) + per_layer)
            + w["V"] * w["D"] + w["D"])


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """A K and a V row in each ATTENDING layer (2 of 28): 1,024 B at the published sizes."""
    w = _w(model)
    return w["n_attn"] * 2 * w["KV"] * w["hd"] * dtype_bytes


def state_bytes_per_seq(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What a SEQUENCE holds in the Mamba layers whatever its length: ``h [N,
    Di]`` float32 and the convolution's last ``K - 1`` inputs (9,318,400 B over
    26 layers at the published sizes)."""
    w = _w(model)
    return w["n_mamba"] * (w["N"] * w["Di"] * 4 + (w["K"] - 1) * w["Di"] * dtype_bytes)


def matmul_params_per_token(model: Dict[str, Any]) -> int:
    """Weights one token is multiplied against (the embedding is a lookup, the tied head a product)."""
    w = _w(model)
    mamba = w["D"] * 2 * w["Di"] + w["Di"] * (w["R"] + 2 * w["N"]) + w["R"] * w["Di"] + w["Di"] * w["D"]
    return (w["V"] * w["D"] + w["n_mamba"] * mamba + w["n_attn"] * attention_params(model)
            + w["L"] * mlp_params(model))


def recurrence_flops_per_token(model: Dict[str, Any]) -> int:
    """Vector operations one position of ONE Mamba layer's recurrence needs a
    (state, channel) pair, exponential apart: ``dt A`` (1), the decay times the
    state (1), the input ``dtx B`` and its add (2), the read-out ``h C`` and its
    add (2): 6, and the convolution's ``2 K`` a channel."""
    w = _w(model)
    return 6 * w["N"] * w["Di"] + 2 * w["K"] * w["Di"]


def forward_flops_per_token(model: Dict[str, Any], context_len: int) -> float:
    """Operations one token's forward pass REQUIRES at a context length: 2 a
    weight it is multiplied against, the recurrence and the taps of the Mamba
    layers (whatever the context) and scores and values over the context in
    the attending layers."""
    w = _w(model)
    return (2 * matmul_params_per_token(model) + w["n_mamba"] * recurrence_flops_per_token(model)
            + w["n_attn"] * 4 * w["H"] * w["hd"] * context_len)


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward over a causal sequence (mean context ``seq_len / 2``)."""
    return 3 * forward_flops_per_token(model, seq_len / 2)


def ssm_scan_cost(model: Dict[str, Any], chunk: int) -> Dict[str, float]:
    """What ONE call of the chunk's kernel (``ssm_scan.N``: one Mamba layer,
    ``chunk`` positions of one sequence) has to do: ``vector_ops`` (6 a (state,
    channel) pair a position and ``dt x`` once a channel), ``exps`` (one a pair
    a position), ``bytes``: ``dt`` and ``x`` read and ``y`` written once as
    float32, ``B`` and ``C``, ``A``, and the slot's state read once and written
    once."""
    w = _w(model)
    pairs = w["N"] * w["Di"]
    return {
        "vector_ops": chunk * (6 * pairs + w["Di"]),
        "exps": chunk * pairs,
        "bytes": chunk * (3 * w["Di"] + 2 * w["N"]) * 4 + pairs * 4 + 2 * pairs * 4,
    }


def ssm_update_bytes(model: Dict[str, Any], slots: int) -> int:
    """What ONE call of the decode kernel (``ssm_update.N``: one Mamba layer,
    one position of ``slots`` named slots) has to move: each named slot's
    state read once and written once (float32), ``dt`` and ``x`` read and ``y``
    written a slot, ``B`` and ``C``, and ``A`` once."""
    w = _w(model)
    pairs = w["N"] * w["Di"]
    return slots * (2 * pairs + 3 * w["Di"] + 2 * w["N"]) * 4 + pairs * 4
