"""What the ``deepseek_v3`` family counts, from a configuration file alone (no
import of the program): parameters as run on this chip (the MTP module
included), operations a token requires, cache bytes a token (the module's row
included), and what the two kernels of the latent paths multiply and move at
this family's widths (a value head of its own width, ``dv``).

``n_routed_experts`` in the file is the number of experts HELD here (one
chip's share of the deployment); the router's width is
``deployment.n_routed_experts_total``. ``num_hidden_layers`` counts the main
model's layers; the MTP module (``num_nextn_predict_layers``) is one more
block of the expert kind beside ``eh_proj`` and three norm vectors."""

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
    )


def attention_params(model: Dict[str, Any]) -> int:
    """The two projections down and two up, their two norms, and ``W_o``."""
    w = _w(model)
    return (w["D"] * w["qr"] + w["qr"] + w["qr"] * w["H"] * (w["dn"] + w["dr"])
            + w["D"] * (w["kr"] + w["dr"]) + w["kr"] + w["kr"] * w["H"] * (w["dn"] + w["dv"])
            + w["H"] * w["dv"] * w["D"])


def layer_params(model: Dict[str, Any], moe: bool) -> int:
    """One layer as held here: attention, both block norms, and the dense MLP
    or the router (+ bias), the shared expert and the HELD experts."""
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


def kv_bytes_per_token(model: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """ONE latent row a layer, the MTP module's among them."""
    w = _w(model)
    return (w["L"] + w["mtp"]) * (w["kr"] + w["dr"]) * dtype_bytes


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


def latent_flash_cost(model: Dict[str, Any], window: int, live_keys: int, key_tile: int = 1024,
                      dtype_bytes: int = 2) -> Dict[str, float]:
    """What ONE call of the flash kernel (``ops/latent_flash.py``, one layer's
    prefill chunk) multiplies and moves at these widths, for the roofline: a
    window of ``window`` queries over ``live_keys`` live positions read in
    whole key tiles. ``flops``: scores over ``dn + dr`` and values over ``dv``
    a head for every (query, read key) pair under the diagonal's tiles (upper
    bound: whole tiles). ``bytes``: Q (``dn`` + ``dr``) and the output (``dv``)
    once, K (``dn`` a head), the ONE shared ``k_rope`` and V (``dv`` a head,
    which lies on 256 lanes in HBM at 192: counted as moved, ``dv`` rounded up
    to whole lanes) over the read keys."""
    w = _w(model)
    read = -(-live_keys // key_tile) * key_tile
    lanes = -(-w["dv"] // 128) * 128
    flops = 2.0 * w["H"] * window * read * (w["dn"] + w["dr"] + w["dv"])
    moved = dtype_bytes * (
        w["H"] * window * (w["dn"] + w["dr"] + lanes) + read * (w["H"] * (w["dn"] + lanes) + w["dr"])
    )
    return {"flops": flops, "bytes": float(moved)}


def latent_rows_cost(model: Dict[str, Any], window: int, live_keys: int, block_size: int = 16,
                     dtype_bytes: int = 2) -> Dict[str, float]:
    """What ONE slot of the kernel over latent rows (``ops/latent_paged.py``,
    one layer of a decode or verify window) multiplies and moves: ``window x
    H`` absorbed query rows against ``live_keys`` latent rows read in whole
    blocks, scores over ``kr + dr`` and values over ``kr``; the rows are read
    ONCE for all heads and all ``window`` queries, which is why a window of
    two fills the MXU twice as well as one at the same bytes."""
    w = _w(model)
    read = -(-live_keys // block_size) * block_size
    width = w["kr"] + w["dr"]
    flops = 2.0 * window * w["H"] * read * (width + w["kr"])
    moved = dtype_bytes * (read * width + window * w["H"] * (width + w["kr"]))
    return {"flops": flops, "bytes": float(moved)}


def matmul_params_per_token(model: Dict[str, Any]) -> float:
    """Weights one token is multiplied against on THIS chip by the MAIN model:
    attention, the router, the shared expert, its expected share of the held
    experts (``top_k x held / E``: routing is over all ``E``), the dense MLPs
    and the head (the embedding is a lookup)."""
    w = _w(model)
    attn = attention_params(model) - w["qr"] - w["kr"]
    dense = attn + 3 * w["D"] * w["F"]
    moe = attn + w["D"] * w["E"] + 3 * w["D"] * w["Fm"] * (w["shared"] + w["k"] * w["held"] / w["E"])
    return w["dense"] * dense + (w["L"] - w["dense"]) * moe + w["V"] * w["D"]


def forward_flops_per_token(model: Dict[str, Any], context_len: int) -> float:
    """Operations one token's forward pass REQUIRES of the main model here at a
    context length: 2 a weight it is multiplied against, and scores and values
    over the context in the expanded form. The MTP module is a drafter's cost,
    not a token's requirement, and is not counted."""
    w = _w(model)
    return 2 * matmul_params_per_token(model) + w["L"] * attention_flops_per_pair(model, False) * context_len


def train_flops_per_token(model: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward over a causal sequence (mean context ``seq_len / 2``)."""
    return 3 * forward_flops_per_token(model, seq_len / 2)
