"""What the ``lfm2`` family counts, from a configuration file alone (no
import of the program): parameters as run on this chip and of the whole
model, operations a token requires, cache bytes a token, state bytes a
SEQUENCE, and what one call of each attention kernel has to do at heads of
64 (:func:`paged_attn_cost`, :func:`flash_cost`): a kernel's share of its
roofline is ``least time / (time share x busy / calls)`` against them.

``num_experts`` in the file is the number of experts HELD here (one chip's
share of the deployment); the router's width is
``deployment.num_experts_total``."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def _w(model: Dict[str, Any]) -> Dict[str, int]:
    kinds = model["layer_types"]
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"], KV=model["num_key_value_heads"],
        hd=model["hidden_size"] // model["num_attention_heads"], taps=model["conv_L_cache"],
        n_conv=kinds.count("conv"), n_attn=kinds.count("full_attention"),
        F=model["intermediate_size"], Fm=model["moe_intermediate_size"],
        held=model["num_experts"], E=model["deployment"]["num_experts_total"],
        k=model["num_experts_per_tok"], L=model["num_hidden_layers"],
        dense=model["num_dense_layers"], V=model["vocab_size"],
    )


def conv_params(model: Dict[str, Any]) -> int:
    """A convolution mixer: ``in_proj`` D x 3 D, the filter D x taps, ``out_proj`` D x D."""
    w = _w(model)
    return 3 * w["D"] * w["D"] + w["taps"] * w["D"] + w["D"] * w["D"]


def attention_params(model: Dict[str, Any]) -> int:
    """An attention mixer: q, k, v, o and the two head norms."""
    w = _w(model)
    return 2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"] + 2 * w["hd"]


def ffn_params(model: Dict[str, Any], moe: bool, experts: int = None) -> int:
    """The dense MLP, or the router (+ bias) and ``experts`` experts (default: the HELD)."""
    w = _w(model)
    if not moe:
        return 3 * w["D"] * w["F"]
    return w["D"] * w["E"] + w["E"] + 3 * w["D"] * w["Fm"] * (w["held"] if experts is None else experts)


def _kinds(model: Dict[str, Any]) -> List[Tuple[bool, bool]]:
    """``(is it an attending layer, is it an expert layer)`` a layer, in order."""
    return [(kind == "full_attention", l >= model["num_dense_layers"])
            for l, kind in enumerate(model["layer_types"])]


def param_count(model: Dict[str, Any], experts: int = None) -> int:
    """Parameters as run (``experts``: with that many experts a layer, e.g.
    ``deployment.num_experts_total`` for the whole model); the embedding
    counted once: it is also the head."""
    w = _w(model)
    layers = sum(
        (attention_params(model) if attn else conv_params(model)) + 2 * w["D"] + ffn_params(model, moe, experts)
        for attn, moe in _kinds(model)
    )
    return layers + w["V"] * w["D"] + w["D"]


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """A K and a V row in each ATTENDING layer (6 of 24): 12,288 B at the published sizes."""
    w = _w(model)
    return w["n_attn"] * 2 * w["KV"] * w["hd"] * dtype_bytes


def state_bytes_per_seq(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What a SEQUENCE holds in the convolution layers whatever its length:
    the last ``taps - 1`` inputs (147,456 B over 18 layers at the published sizes)."""
    w = _w(model)
    return w["n_conv"] * (w["taps"] - 1) * w["D"] * dtype_bytes


def matmul_params_per_token(model: Dict[str, Any]) -> float:
    """Weights one token is multiplied against on THIS chip (the embedding
    is a lookup, the tied head a product; of the held experts its expected
    share ``top_k x held / E``)."""
    w = _w(model)
    total = w["V"] * w["D"]
    for attn, moe in _kinds(model):
        total += attention_params(model) - 2 * w["hd"] if attn else conv_params(model) - w["taps"] * w["D"]
        total += (w["D"] * w["E"] + 3 * w["D"] * w["Fm"] * w["k"] * w["held"] / w["E"]
                  if moe else 3 * w["D"] * w["F"])
    return total


def forward_flops_per_token(model: Dict[str, Any], context_len: int) -> float:
    """Operations one token's forward pass REQUIRES here at a context length:
    2 a weight it is multiplied against, the taps of the convolution layers
    (whatever the context) and scores and values over the context in the
    attending layers."""
    w = _w(model)
    return (2 * matmul_params_per_token(model) + w["n_conv"] * 2 * w["taps"] * w["D"]
            + w["n_attn"] * 4 * w["H"] * w["hd"] * context_len)


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward over a causal sequence (mean context ``seq_len / 2``)."""
    return 3 * forward_flops_per_token(model, seq_len / 2)


def paged_attn_cost(model: Dict[str, Any], live_tokens: int, slots: int, dtype_bytes: int = 2) -> Dict[str, float]:
    """What ONE call of the decode kernel (``paged_attn.N``: one layer, one
    position a slot) has to do over ``live_tokens`` cached positions in all,
    of ``slots`` sequences: ``flops`` the mathematics needs (scores and
    values, every query head over its own KV head), ``flops_run`` what the
    kernel multiplies at heads of 64 (a token's ``KV`` heads ride in one row:
    ``KV`` times as much), ``bytes`` K and V read once, q read and the output
    written."""
    w = _w(model)
    pair = 4 * w["H"] * w["hd"]
    return {
        "flops": pair * live_tokens,
        "flops_run": pair * w["KV"] * live_tokens,
        "bytes": (2 * w["KV"] * w["hd"] * live_tokens + 2 * slots * w["H"] * w["hd"]) * dtype_bytes,
    }


def flash_cost(model: Dict[str, Any], ctx_len: int, true_len: int, dtype_bytes: int = 2) -> Dict[str, float]:
    """What ONE call of the chunk's kernel (``latent_flash.N``: one layer,
    ``true_len`` queries behind ``ctx_len`` cached positions) has to do:
    ``flops`` over the pairs under the diagonal, ``flops_run`` at heads of 64
    in pairs (twice), ``bytes`` K and V up to the chunk's end read once a KV
    head, q read and the output written."""
    w = _w(model)
    pairs = true_len * ctx_len + true_len * (true_len + 1) // 2
    flops = 4 * w["H"] * w["hd"] * pairs
    return {
        "flops": flops,
        "flops_run": 2 * flops,
        "bytes": (2 * w["KV"] * w["hd"] * (ctx_len + true_len) + 2 * true_len * w["H"] * w["hd"]) * dtype_bytes,
    }
