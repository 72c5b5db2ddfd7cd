"""Xing4-family decoder LM: latent attention (MLA), a hyper-connected
residual of ``hc_mult`` streams (mHC), leading dense layers and then
sigmoid-routed experts beside a shared expert, of which this process may
hold a range (one chip's share of an expert-parallel deployment).

Not ``models/llama.py`` with options: the attention has two projections
down and two up and a rotary part shared by all heads, the cache row is ONE
latent vector a token a layer (``kv_lora_rank`` normed numbers + the
rotated rope part: K and V at once), the residual state is ``[hc_mult, D]``
a token, and the layers are of two kinds. What it shares with that module
is the runtime's interface (``models/interface.py``: ``MODEL`` at the end),
``ops/moe.py`` and the norms.

A sublayer ``F`` (attention or FFN), with ``h`` its input after its RMS
norm, acts on the residual state ``X [n, D]`` of a token (n = ``hc_mult``):

    xbar   = rms(vec X)                                   # no weight
    H_pre  = sigmoid(a_pre (xbar phi_pre) + b_pre)        # [n]
    H_post = 2 sigmoid(a_post (xbar phi_post) + b_post)   # [n]
    H_res  = sinkhorn(exp(clip(a_res mat(xbar phi_res) + b_res)))  # [n, n]
    X      = H_res X + H_post^T (x) F(norm(H_pre X))

maps and Sinkhorn-Knopp (rows, then columns, each divided by its sum +
``hc_eps``, ``hc_sinkhorn_iters`` rounds) in float32, with the TOKENS on the
minor axis: a map's entry is one vector over the tokens, the rounds are
``ops/mhc.py`` (one Pallas kernel on a TPU, ``lax.fori_loop`` elsewhere).
What is float32 is the maps, their ``n^2`` vectors and the accumulators of the
product and the mixes; never a whole state: a bfloat16 state meets ``phi`` as
it lies (``phi`` in three bfloat16 pieces, every partial product exact), and
``rms``'s scalar a token scales the product. The state is the embedding
repeated n times at the start and the n streams summed before the final norm.

Attention: ``c_q = rms(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` a head;
``[c | k_rope] = h W_kva``, ``c = rms(c)``; ``[k_nope | v] = c W_kvb`` a
head; ``q_rope`` and the one ``k_rope`` rotated at the token's position with
YaRN's frequencies; scores ``(q_nope k_nope + q_rope k_rope) (dn + dr)^-1/2
m^2``. The cache holds ``(c, rotated k_rope)``. Behind the one attention
door of the paged body (``latent.latent_attention``) there are two paths that
are the same mathematics at different costs, chosen at trace time from the
query window (:func:`absorbs`): a prefill chunk EXPANDS K and V of its
context from the latent rows; a decode or verify window ABSORBS ``W_kvb``
into the query and the output and attends over the latent rows directly.
The expanded path attends two ways, chosen at trace time from what the
code can observe (``latent.flash_serves``: backend, dtype, whole tiles): on a
TPU through the flash kernel ``ops/latent_flash.py`` (the float32 scores stay
in VMEM; key tiles past the live context are not read), elsewhere, and in
:func:`forward` (training needs a gradient), through the materialised
softmax of ``latent.attend_expanded``.

Layers of one kind are stacked on a leading axis and run under
``jax.lax.scan`` (two scans: the dense layers, the expert layers), so that
a 40-layer program traces and compiles two layer bodies.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import latent, paged_kv
from ray_tpu.models.interface import AttentionPath, Model, lm_head, step_counters, step_outputs
# the latent paths, the cache's layout and the block write know only
# dimensions: ``models/latent.py`` has them, for this module and ``models/
# kimi_linear.py``
from ray_tpu.models.latent import absorbs, cache_layout
from ray_tpu.ops import latent_flash, mhc
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.moe import DENSE_AXES, MOE_AXES, gated_mlp, routed_ffn
from ray_tpu.parallel.sharding import constrain

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Xing4Config:
    vocab_size: int = 131072
    dim: int = 3584
    n_layers: int = 40
    #: the first ``n_dense_layers`` have a dense MLP, the rest routed experts
    n_dense_layers: int = 2
    n_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_hidden: int = 9216
    #: width of one routed expert; the shared expert is ``n_shared_experts`` wide of it
    moe_hidden: int = 1024
    #: how many experts the ROUTER chooses among (its width)
    n_routed_experts: int = 64
    #: the range ``(lo, hi)`` of them this process holds and computes; all:
    #: ``(0, n_routed_experts)``
    held_experts: Tuple[int, int] = (0, 64)
    n_shared_experts: int = 1
    moe_top_k: int = 4
    routed_scaling_factor: float = 2.0
    #: the choice limited to the ``topk_group`` best of ``n_group`` groups of
    #: neighbouring experts (``ops/moe.py::route``); 1: no group stage
    n_group: int = 1
    topk_group: int = 1
    #: streams of the hyper-connected residual; 0: the PLAIN residual ``x +
    #: F(norm(x))`` (no maps, no stream axis: ``models/deepseek_v3.py``)
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    #: ``mhc_h_res_clamp_max`` (the minimum is its negative)
    hc_res_clamp: float = 30.0
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    #: YaRN; ``rope_factor`` 1 is the plain table
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    moe_aux_loss_coeff: float = 0.0

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def depth(self) -> int:
        """The blocks a token passes through: what the seeded weights' last
        projections are scaled down by (:func:`init_params`)."""
        return self.n_layers

    @property
    def n_held(self) -> int:
        return self.held_experts[1] - self.held_experts[0]

    @property
    def latent_width(self) -> int:
        """One token's cache row in one layer: the normed latent and the rotated rope part."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        """The factor on the float32 scores (``models/latent.py`` reads it)."""
        return softmax_scale(self)

    @staticmethod
    def tiny(**overrides) -> "Xing4Config":
        """CI-sized config: 2 dense + 2 expert layers, 8 experts of which
        this process holds all unless told, 2 streams."""
        base = dict(
            vocab_size=256, dim=64, n_layers=4, n_dense_layers=2, n_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, mlp_hidden=96, moe_hidden=32, n_routed_experts=8,
            held_experts=(0, 8), moe_top_k=2, hc_mult=2, max_seq_len=64,
            rope_factor=4.0, rope_original_max=32,
        )
        base.update(overrides)
        return Xing4Config(**base)


# ---------------------------------------------------------------------------
# params (layers of one kind stacked on a leading axis) + logical axes


def _group_shapes(cfg: Xing4Config, moe: bool) -> Dict[str, Tuple[int, ...]]:
    """Shapes of ONE layer's weights of a kind (``moe``: an expert layer)."""
    n, D, H = cfg.hc_mult, cfg.dim, cfg.n_heads
    maps = 2 * n + n * n
    shapes = {
        "attn_norm": (D,),
        "w_qa": (D, cfg.q_lora_rank),
        "q_norm": (cfg.q_lora_rank,),
        "w_qb": (cfg.q_lora_rank, H, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
        "w_kva": (D, cfg.latent_width),
        "kv_norm": (cfg.kv_lora_rank,),
        "w_kvb": (cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim),
        "wo": (H, cfg.v_head_dim, D),
        "mlp_norm": (D,),
    }
    if latent.indexed(cfg):
        # the indexer of a learned sparse selection (``models/glm_dsa.py``): its
        # queries from the query's latent, its ONE key and its heads' weights
        # from the layer's input; the key's layer norm has a weight and a bias
        shapes.update({
            "idx_wq": (cfg.q_lora_rank, cfg.index_n_heads, cfg.index_head_dim),
            "idx_wk": (D, cfg.index_head_dim),
            "idx_k_norm": (cfg.index_head_dim,),
            "idx_k_bias": (cfg.index_head_dim,),
            "idx_ww": (D, cfg.index_n_heads),
        })
    for sub in ("hc_attn", "hc_mlp") if n else ():
        shapes.update({f"{sub}_phi": (n * D, maps), f"{sub}_b": (maps,), f"{sub}_alpha": (3,)})
    if moe:
        Fm, Fs = cfg.moe_hidden, cfg.n_shared_experts * cfg.moe_hidden
        shapes.update({
            "router": (D, cfg.n_routed_experts),
            "router_bias": (cfg.n_routed_experts,),
            "w_gate": (cfg.n_held, D, Fm),
            "w_up": (cfg.n_held, D, Fm),
            "w_down": (cfg.n_held, Fm, D),
            "shared_gate": (D, Fs),
            "shared_up": (D, Fs),
            "shared_down": (Fs, D),
        })
    else:
        shapes.update({
            "w_gate": (D, cfg.mlp_hidden),
            "w_up": (D, cfg.mlp_hidden),
            "w_down": (cfg.mlp_hidden, D),
        })
    return shapes


_AXES = {
    "w_qa": ("embed", None), "w_qb": (None, "heads", "head_dim"),
    "w_kva": ("embed", None), "w_kvb": (None, "heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
    "shared_gate": ("embed", "mlp"), "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed"),
}


def _groups(cfg: Xing4Config):
    """``(name, stacked layers, is it an expert group)`` of the layer kinds present."""
    return [
        (name, count, moe)
        for name, count, moe in (("dense", cfg.n_dense_layers, False), ("moe", cfg.n_moe_layers, True))
        if count > 0
    ]


def logical_axes(cfg: Xing4Config) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis-name tuples; the
    leading axis of a layer group is the layer."""
    out: Dict[str, Any] = {
        "embed": ("vocab", "embed"), "final_norm": (None,), "lm_head": ("embed", "vocab"),
    }
    for name, _, moe in _groups(cfg):
        own = {**_AXES, **(MOE_AXES if moe else DENSE_AXES)}
        out[name] = {
            k: (None, *own.get(k, (None,) * len(shape)))
            for k, shape in _group_shapes(cfg, moe).items()
        }
    return out


def init_params(cfg: Xing4Config, rng: jax.Array) -> Dict[str, Any]:
    """Seeded weights under which what is new MATTERS: projections and
    experts normal / sqrt(fan-in) in ``cfg.dtype``, each sublayer's LAST
    projection (``wo``, ``w_down``, ``shared_down``) a further 1 / sqrt(2 x
    layers) smaller and a ROUTED expert's a quarter of that, norm vectors
    1. Why the quarter: with independent random experts a hard top-4
    choice whose gates sum to 2 is discontinuous at full size, and the
    rounding of bfloat16 flips a token's 4th / 5th expert about once in
    twenty (token, layer) pairs; through 38 expert layers the flips feed on
    each other and the logits read 0.09-0.34 against the float32 reference
    on the chip at the published widths (PERF.md, PR 31; 40 toy layers:
    0.2 at the median, against 0.015 with dense layers alone or with every
    expert kept), where no limit parts the model from a wrong one. A trained
    model's neighbouring experts are not independent draws. The
    router and its bias, and the mHC maps, float32. mHC: ``phi`` normal /
    sqrt(n D) (so ``xbar phi`` is of order 1; the ``res`` columns a quarter
    of that), ``alpha`` uniform in [0.5, 1.5], ``b_pre`` / ``b_post``
    normal, ``b_res`` 1 on the diagonal + normal x 0.2: ``H_res`` lands
    tens of per cent away from the identity and from 1/n at a contrast
    under which the published 20 Sinkhorn rounds converge to 1e-5 (a
    diagonal of 1.5 with a spread of 1 did not: rows summed to 1 +- 0.03),
    ``H_pre`` / ``H_post`` away from constants. The router's
    bias is normal x 0.03, beside sigmoid scores whose 4th and 5th of 64
    lie about 0.025 apart: it changes the kept set for a counted share of
    tokens (``bias_changed``; normal x 0.1 changed it for 96%)."""
    with jax.threefry_partitionable(True):
        return _init_params(cfg, rng)


def _draw(key, shape, fan_in, dtype):
    """Normal / sqrt(fan_in), drawn a slice of the leading axis at a time:
    the float32 draw of a whole stacked weight (or of the embedding) is
    gigabytes beside 12 GB of weights being made."""
    def draw(k):
        return (jax.random.normal(k, shape[1:], F32) / math.sqrt(fan_in)).astype(dtype)

    return jax.lax.map(draw, jax.random.split(key, shape[0]))


def _init_group(cfg: Xing4Config, key, count: int, moe: bool) -> Dict[str, Any]:
    """``count`` stacked layers of a kind (:func:`init_params` says how drawn)."""
    n = cfg.hc_mult
    shapes = _group_shapes(cfg, moe)
    out = {}
    for (name, shape), k in zip(shapes.items(), jax.random.split(key, len(shapes))):
        full = (count, *shape)
        if name.endswith("norm"):
            out[name] = jnp.ones(full, cfg.dtype)
        elif name.endswith("_phi"):
            phi = _draw(k, full, shape[0], F32)
            out[name] = phi.at[..., 2 * n:].multiply(0.25)
        elif name.endswith("_alpha"):
            out[name] = jax.random.uniform(k, full, F32, 0.5, 1.5)
        elif name.endswith("_b"):
            b = jax.random.normal(k, full, F32)
            res = 0.2 * b[:, 2 * n:] + jnp.eye(n, dtype=F32).reshape(-1)
            out[name] = jnp.concatenate([b[:, : 2 * n], res], axis=1)
        elif name == "idx_k_bias":
            out[name] = jnp.zeros(full, cfg.dtype)
        elif name == "router":
            # routing logits are precision-sensitive: keep f32
            out[name] = _draw(k, full, shape[0], F32)
        elif name == "router_bias":
            out[name] = 0.03 * jax.random.normal(k, full, F32)
        else:
            # contraction dims: all but the last of a 2-D weight; the
            # rank of the up-projections; heads x v of ``wo``; an
            # expert's own input width
            fan_in = {"w_qb": shape[0], "w_kvb": shape[0], "idx_wq": shape[0],
                      "wo": shape[0] * shape[1]}.get(name, shape[-2])
            if name in ("wo", "w_down", "shared_down"):
                # a sublayer's last projection, scaled down by the depth
                # (the 1 / sqrt(2 L) of GPT-2's initialisation)
                fan_in *= 2 * cfg.depth
            if moe and name == "w_down":
                fan_in *= 16  # a ROUTED expert's output a quarter of that
            out[name] = _draw(k, full, fan_in, cfg.dtype)
    return out


def _init_params(cfg: Xing4Config, rng: jax.Array) -> Dict[str, Any]:
    k_embed, k_head, k_dense, k_moe = jax.random.split(rng, 4)
    # the two vocabulary-sized matrices in (up to) 16 slices of their leading axis
    v, d = math.gcd(16, cfg.vocab_size), math.gcd(16, cfg.dim)
    params = {
        "embed": _draw(k_embed, (v, cfg.vocab_size // v, cfg.dim), cfg.dim, cfg.dtype).reshape(
            cfg.vocab_size, cfg.dim
        ),
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": _draw(k_head, (d, cfg.dim // d, cfg.vocab_size), cfg.dim, cfg.dtype).reshape(
            cfg.dim, cfg.vocab_size
        ),
    }
    for (name, count, moe), key in zip(_groups(cfg), (k_dense, k_moe)):
        params[name] = _init_group(cfg, key, count, moe)
    return params


def param_count(cfg: Xing4Config) -> int:
    layers = sum(
        count * sum(math.prod(s) for s in _group_shapes(cfg, moe).values())
        for _, count, moe in _groups(cfg)
    )
    return 2 * cfg.vocab_size * cfg.dim + layers + cfg.dim


# ---------------------------------------------------------------------------
# the pieces of a layer


def yarn_inv_freq(cfg: Xing4Config):
    """YaRN's rotary frequencies ``[dr / 2]``: the table's own ``theta^(-2i
    / dr)`` where a pair turns more than ``beta_fast`` times over the
    original context, those divided by ``rope_factor`` where it turns fewer
    than ``beta_slow`` times, a linear ramp between."""
    dr = cfg.qk_rope_head_dim
    extra = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr))
    if cfg.rope_factor == 1.0:
        return extra

    def correction_dim(rotations: float) -> float:
        return (dr * math.log(cfg.rope_original_max / (rotations * 2 * math.pi))) / (
            2 * math.log(cfg.rope_theta)
        )

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dr - 1)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: Xing4Config) -> float:
    """``(dn + dr)^-1/2 m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) if cfg.rope_mscale_all_dim else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rope(cfg: Xing4Config, x, pos):
    """``x [..., dr]`` rotated at int positions ``pos``, broadcastable against
    ``x``'s leading axes: (even, odd) neighbours are a pair, as in ``models/
    llama.py``. The table's own attention factor (``mscale`` over
    ``mscale_all_dim``) multiplies cos and sin."""
    ang = pos.astype(F32)[..., None] * yarn_inv_freq(cfg)
    att = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / _yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim
    )
    cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att
    x1, x2 = x[..., ::2].astype(F32), x[..., 1::2].astype(F32)
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _latent_qkv(cfg: Xing4Config, p, h, pos):
    """The projections of one attention on normed activations ``h [B, C,
    D]`` at positions ``pos [B, C]``: ``(q_nope [B, C, H, dn], q_rope [B, C,
    H, dr]`` rotated, ``row [B, C, kr + dr])``, the row being what the cache
    holds of the token: the normed latent and the rotated rope part. Fourth,
    for a model that selects (``latent.indexed``), what its indexer makes of
    the token (:func:`_indexer`), else None."""
    dn, kr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla.q"):
        c_q = rms_norm(h @ p["w_qa"], p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bcr,rhk->bchk", c_q, p["w_qb"])
        q_nope, q_rope = q[..., :dn], _rope(cfg, q[..., dn:], pos[:, :, None])
    with jax.named_scope("mla.latent"):
        ckv = h @ p["w_kva"]
        c = rms_norm(ckv[..., :kr], p["kv_norm"], cfg.norm_eps)
        row = jnp.concatenate([c, _rope(cfg, ckv[..., kr:], pos)], axis=-1)
    return q_nope, q_rope, row, _indexer(cfg, p, h, c_q, pos) if latent.indexed(cfg) else None


def _indexer(cfg: Xing4Config, p, h, c_q, pos):
    """The indexer's part of a token: ``(q_i [B, C, Hi, di]`` from the
    query's normed latent ``c_q``, ``k_i [B, C, di]`` the layer norm of ``h
    W_k``, both with their first ``qk_rope_head_dim`` numbers rotated at the
    token's position, ``w [B, C, Hi])`` float32, ``h W_w`` scaled by ``Hi^-1/2
    di^-1/2``: ``k_i`` is what the cache's ``index`` row holds of the token,
    and ``ops/sparse_index.py`` scores ``sum_j w_j relu(q_i_j . k_i)``."""
    dr = cfg.qk_rope_head_dim
    with jax.named_scope("dsa.project"):
        q = jnp.einsum("bcr,rhk->bchk", c_q, p["idx_wq"])
        q = jnp.concatenate([_rope(cfg, q[..., :dr], pos[:, :, None]), q[..., dr:]], axis=-1)
        k = (h @ p["idx_wk"]).astype(F32)
        k = (k - k.mean(-1, keepdims=True)) * jax.lax.rsqrt(k.var(-1, keepdims=True) + cfg.norm_eps)
        k = k.astype(h.dtype) * p["idx_k_norm"] + p["idx_k_bias"]
        k = jnp.concatenate([_rope(cfg, k[..., :dr], pos), k[..., dr:]], axis=-1)
        w = (h @ p["idx_ww"]).astype(F32) * (cfg.index_n_heads * cfg.index_head_dim) ** -0.5
    return q, k, w


def _ffn(cfg: Xing4Config, p, h, valid, moe: bool, at=None):
    """The FFN of one layer on normed activations ``h [B, C, D]``: ``(ffn(h),
    aux)``. A dense layer: the gated SiLU MLP, ``aux`` empty. An expert layer:
    ``ops/moe.py::routed_ffn`` with the shared expert and the group limit;
    ``at``: the layer's index in its group's STACKS of expert matrices
    (:func:`_scan_layers`), absent where ``p``'s are the layer's own."""
    if not moe:
        return gated_mlp(h, p["w_gate"], p["w_up"], p["w_down"]), {}
    return routed_ffn(
        p, h, valid, top_k=cfg.moe_top_k, scale=cfg.routed_scaling_factor, held=cfg.held_experts,
        shared=True, n_group=cfg.n_group, topk_group=cfg.topk_group, layer=at,
    )


def _bf16_pieces(w):
    """A float32 array as the sum of three bfloat16 ones (8 + 8 + 8 bits of
    mantissa), side by side on the last axis: ``[..., 3 m]``. Rounded by
    ``reduce_precision``, which the compiler keeps: a ``convert`` to bfloat16
    and back inside one fusion is computed in the registers' float32 on a TPU
    (excess precision), the remainder then reads 0 and the second and third
    pieces are lost (on the chip, PR 50: the maps off by 2e-3)."""
    def rounded(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    hi = rounded(w)
    mid = rounded(w - hi)
    lo = rounded(w - hi - mid)
    return jnp.concatenate([hi, mid, lo], axis=-1).astype(jnp.bfloat16)


def _state_dot(x, phi):
    """``sum_{j, d} x[t, j, d] phi[j, d, m]`` as ``[maps, T]`` float32, to the
    accuracy of float32 operands at the highest precision. On a TPU a
    bfloat16 state is never converted: it is exact in bfloat16, so the float32
    ``phi`` goes in as its three bfloat16 pieces and ONE product with float32
    accumulation reads the state as it lies (every partial product is exact:
    what ``HIGHEST`` computes from a bfloat16 operand). Elsewhere (the CPU
    has no such product) and for any other dtype: float32 operands. The
    product is taken ``[T, .]`` and turned: XLA lays it tokens-minor itself,
    where asked for as ``[., T]`` it copied the whole state first."""
    if x.dtype != jnp.bfloat16 or jax.default_backend() != "tpu":
        return jnp.einsum("tjd,jdm->tm", x.astype(F32), phi, precision=_HIGHEST).T
    z = jnp.einsum("tjd,jdm->tm", x, _bf16_pieces(phi), preferred_element_type=F32).T
    maps = phi.shape[-1]
    return z[:maps] + (z[maps : 2 * maps] + z[2 * maps :])


def mhc_maps(cfg: Xing4Config, phi, b, alpha, X):
    """The three maps of one sublayer from the residual state ``X [..., n,
    D]``: ``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` float32,
    ``H_res`` doubly stochastic by Sinkhorn-Knopp. Computed with the TOKENS
    on the minor axis (``[maps, T]``: a layout the vector unit fills), the
    rounds in ``ops/mhc.py``; ``rsqrt(mean x^2)``, a scalar a token, scales
    the product and not the state, so no normalised copy of the state exists."""
    n, lead = cfg.hc_mult, X.shape[:-2]
    with jax.named_scope("mhc.maps"):
        x = X.reshape(-1, *X.shape[-2:])
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(F32)), axis=(-2, -1)) + cfg.norm_eps)
        z = _state_dot(x, phi.astype(F32).reshape(*x.shape[1:], -1)) * inv
        pre = jax.nn.sigmoid(alpha[0] * z[:n] + b[:n, None])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n : 2 * n] + b[n : 2 * n, None])
        res = alpha[2] * z[2 * n :] + b[2 * n :, None]
    with jax.named_scope("mhc.sinkhorn"):
        res = mhc.sinkhorn(res, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, clamp=cfg.hc_res_clamp)
    return pre.T.reshape(*lead, n), post.T.reshape(*lead, n), res.T.reshape(*lead, n, n)


def _hyper(cfg: Xing4Config, p, sub: str, norm: str, X, F: Callable):
    """One sublayer through the hyper-connected residual: ``X <- H_res X +
    H_post^T (x) F(norm(H_pre X))``. ``F`` returns ``(y [..., D], extra)``;
    returns ``(X, extra)``. What is float32: the maps, and inside the two
    mixes' fusions the coefficients and the accumulators (the matmul's highest
    precision); the state is kept in ``cfg.dtype``, each mix reads it as it
    lies, and no float32 image of it is written. A configuration without
    streams (``hc_mult`` 0) has the plain residual, ``X [..., D]``: ``X +
    F(norm(X))``."""
    if not cfg.hc_mult:
        y, extra = F(rms_norm(X, p[norm], cfg.norm_eps))
        return X + y.astype(X.dtype), extra
    pre, post, res = mhc_maps(cfg, p[f"{sub}_phi"], p[f"{sub}_b"], p[f"{sub}_alpha"], X)
    with jax.named_scope("mhc.mix"):
        x_in = jnp.einsum("...n,...nd->...d", pre, X.astype(F32), precision=_HIGHEST)
    y, extra = F(rms_norm(x_in.astype(X.dtype), p[norm], cfg.norm_eps))
    with jax.named_scope("mhc.mix"):
        mixed = jnp.einsum("...ij,...jd->...id", res, X.astype(F32), precision=_HIGHEST)
        out = mixed + post[..., None] * y.astype(F32)[..., None, :]
    return out.astype(X.dtype), extra


def _layer(cfg: Xing4Config, p, X, attention: Callable, valid, moe: bool, at=None):
    """One layer on the residual state ``X [B, C, n, D]``: the attention
    sublayer (``attention(p, h) -> (out [B, C, D], rows)``, ``rows`` what
    the layer leaves for the cache, or None), then the FFN (``at``: as
    :func:`_ffn`). Returns ``(X, rows, aux)``."""
    X, rows = _hyper(cfg, p, "hc_attn", "attn_norm", X, partial(attention, p))
    X, aux = _hyper(cfg, p, "hc_mlp", "mlp_norm", X, lambda h: _ffn(cfg, p, h, valid, moe, at))
    return X, rows, aux


#: an expert layer's three matrices: what a serving scan does NOT slice
_EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def _scan_layers(cfg: Xing4Config, params, X, attention: Callable, valid, wrap=None, layer0: int = 0,
                 experts_in_place: bool = False):
    """Every layer over ``X``, the layers of a kind under one scan.
    ``attention(p, h, layer) -> (out, rows)`` (``layer`` the layer's index
    in the model, traced). Returns ``(X, rows, aux)``: ``rows`` what the
    layers' attentions returned, stacked over ALL layers in order (None
    where they return None), ``aux`` the expert layers' stacked ``load
    [n_moe, E]``, ``bias_changed [n_moe]``, ``aux_loss [n_moe]`` (empty
    without expert layers). ``layer0``: the index the first layer goes by.

    ``experts_in_place`` (the serving body): the expert group's three
    matrices are not among the scan's ``xs`` but closed over whole, and a
    layer reads its own in the stack by its index in the group
    (``ops/moe.py::grouped_matmul``). As a slice of ``xs`` each was copied
    out of the stack every layer, because a Pallas call takes a whole
    operand. Everything else of a layer is sliced as before. ``forward``
    keeps the slices: the gradient of a layer through a whole stack would be
    a whole stack's."""
    rows, aux = [], {}
    for name, count, moe in _groups(cfg):
        if name not in params:
            continue
        sliced, whole = params[name], {}
        if moe and experts_in_place:
            whole = {k: sliced[k] for k in _EXPERT_STACKS}
            sliced = {k: v for k, v in sliced.items() if k not in whole}

        def body(carry, p, moe=moe, whole=whole, first=layer0):
            X, layer = carry
            X, layer_rows, layer_aux = _layer(
                cfg, {**p, **whole}, X, lambda p, h: attention(p, h, layer), valid, moe,
                layer - first if whole else None,
            )
            return (X, layer + 1), (layer_rows, layer_aux)

        if wrap is not None:
            body = wrap(body)
        (X, _), (group_rows, group_aux) = jax.lax.scan(
            body, (X, jnp.int32(layer0)), sliced
        )
        layer0 += count
        rows.append(group_rows)
        if moe:
            aux = group_aux
    rows = None if rows[0] is None else jnp.concatenate(rows)
    return X, rows, aux


def _lm_head(cfg: Xing4Config, params, X):
    """The residual state ``X [..., n, D]`` -> float32 logits ``[..., vocab]``:
    the streams summed (where there are streams), the final norm, the head."""
    x = X.astype(F32).sum(axis=-2).astype(X.dtype) if cfg.hc_mult else X
    return lm_head(params, x, cfg.norm_eps, tied=False)


def _embed(cfg: Xing4Config, params, tokens):
    """The embedding of each token, repeated over the ``hc_mult`` streams."""
    x = params["embed"][tokens]
    if not cfg.hc_mult:
        return x
    return jnp.broadcast_to(x[..., None, :], (*x.shape[:-1], cfg.hc_mult, cfg.dim))


# ---------------------------------------------------------------------------
# forward (the full sequence: training, and the tests' other side)


def forward(cfg: Xing4Config, params, tokens, *, remat=False, mesh=None, rules=None,
            return_aux: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): causal attention
    over the sequence itself, K and V expanded (no cache). ``remat``: a
    truthy value checkpoints each layer. With ``return_aux`` also returns
    the summed load-balance loss of the expert layers."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (B, S, S))
    emb = constrain(params["embed"], mesh, rules, (None, None))
    X = _embed(cfg, {"embed": emb}, tokens)

    def attention(p, h, layer):
        q_nope, q_rope, rows, index = _latent_qkv(cfg, p, h, pos)
        seen = causal if index is None else latent.selection(cfg, index, pos)
        o = latent.attend_expanded(cfg, p, q_nope, q_rope, rows, seen)
        return jnp.einsum("bchk,hkd->bcd", o.astype(h.dtype), p["wo"]), None

    wrap = jax.checkpoint if remat not in (False, None) else None
    X, _, aux = _scan_layers(cfg, params, X, attention, None, wrap)
    logits = _lm_head(cfg, params, X)
    logits = constrain(logits, mesh, rules, ("act_batch", "act_seq", "act_vocab"))
    if return_aux:
        return logits, (aux["aux_loss"].sum() if aux else jnp.zeros((), F32))
    return logits


def next_token_loss(cfg: Xing4Config, params, tokens, targets, *, remat=False,
                    mesh=None, rules=None):
    logits, aux = forward(
        cfg, params, tokens, remat=remat, mesh=mesh, rules=rules, return_aux=True
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32), axis=-1)
    return nll.mean() + cfg.moe_aux_loss_coeff * aux


# ---------------------------------------------------------------------------
# the latent paged cache and the three serving steps over ONE body
#
# One array ``latent [n_layers, num_blocks, block_size x (kr + dr)]`` shared
# by every request: a token's row in a layer is its normed latent and its
# rotated rope part, K and V at once (46,080 B a token over 40 layers at the
# published widths, against 819,200 B if the expanded heads were cached), and
# a block's 16 rows are stored as ONE row of 9216 numbers (``CacheLayout.
# flat_blocks``: 576 is 4.5 lanes of 128; as ``[16, 576]`` the device layout
# padded each row to 640 and XLA re-laid the whole 2 GB cache out, twice, to
# gather from it, and copied it again to scatter into it; the compile-only
# rehearsal of a prefill chunk then did not fit the chip).
# A block id covers every layer (every layer is of the one kind: one layer
# group, ``models/interface.py``), block 0 is the null block, and every read masks on
# ``key_pos <= pos``, so stale rows past a slot's context are never read.


def _paged_layers(cfg: Xing4Config, params, cache, tokens, pos, valid, block_tables, embed=None):
    """Every layer of the model over the latent paged cache: the body of
    the three serving steps. ``tokens [B, C]``, ``pos [B, C]`` their global
    positions (contiguous a slot), ``valid [B, C]`` bool (padding rows reach
    no routed expert; what they leave in the cache lies past their slot's
    context, or in the null block), ``block_tables [B, M]``. Per layer: the
    projections, attention over the cache with the window's own rows laid
    over it, ``wo``, the FFN, each through the hyper-connected residual.
    Returns ``(cache, X [B, C, n, D], aux)``. ``embed``: what stands where
    the embedding of ``tokens`` would (a module that is fed something else:
    ``models/deepseek_v3.py``'s drafter), with the index of the cache layer
    its ``params`` write first, ``(X0, layer0)``.

    The layers READ the cache they were handed and the step writes every
    layer's blocks in ONE scatter after the last layer: a cache carried
    through the two scans and updated in each layer was copied whole by
    XLA on its way into the loop (2.3 GB at the benchmark's sizes: the
    compile-only rehearsal did not fit the chip), where one scatter of
    whole blocks into the donated argument is in place. What a layer's
    attention sees is the same either way."""
    # a padding slot (no valid row) is pointed at the null block whatever
    # its table holds
    block_tables = jnp.where(valid.any(axis=1, keepdims=True), block_tables, 0)
    true_lens = valid.sum(axis=1, dtype=jnp.int32)  # the valid rows lead (all three entry points)
    # the predicate asked HERE (a test patches it), where it is not needed too
    window, bs = pos.shape[1], latent.block_size_of(cfg, cache)
    flash = not absorbs(cfg, window) and latent.flash_serves(cfg, window, cache, block_tables.shape[1] * bs)

    def attention(p, h, layer):
        q_nope, q_rope, row, index = _latent_qkv(cfg, p, h, pos)
        o, blocks = latent.latent_attention(
            cfg, p, q_nope, q_rope, row, cache, layer, block_tables, pos, true_lens,
            flash=flash, index=index,
        )
        return jnp.einsum("bchk,hkd->bcd", o.astype(h.dtype), p["wo"]), blocks

    X0, layer0 = (_embed(cfg, params, tokens), 0) if embed is None else embed
    X, blocks, aux = _scan_layers(
        cfg, params, X0, attention, valid, layer0=layer0, experts_in_place=True
    )
    return latent.write_blocks(cfg, cache, block_tables, pos[:, 0], blocks, layer0), X, aux


def paged_prefill_step(cfg: Xing4Config, params, cache, tokens, block_table, ctx_len, true_len):
    """One prefill chunk for ONE request: arguments and outputs as
    ``models/llama.py::paged_prefill_step``. Head: the chunk's last valid row."""
    idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cache, X, aux = _paged_layers(
        cfg, params, cache, tokens[None], (ctx_len + idx)[None], (idx < true_len)[None],
        block_table[None],
    )
    logits = _lm_head(cfg, params, X[0, jnp.maximum(true_len - 1, 0)])
    return step_outputs(cache, logits, step_counters(aux))


def paged_verify_step(cfg: Xing4Config, params, cache, tokens, block_tables, ctx_lens, true_lens):
    """Speculative verification for a batch of slots, windows of C positions
    a slot: as ``models/llama.py::paged_verify_step``. Head: every row."""
    idx = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    cache, X, aux = _paged_layers(
        cfg, params, cache, tokens, ctx_lens[:, None] + idx, idx < true_lens[:, None],
        block_tables,
    )
    return step_outputs(cache, _lm_head(cfg, params, X), step_counters(aux))


def paged_decode_step(cfg: Xing4Config, params, cache, tokens, positions, block_tables, ctx_lens):
    """One decode step for a batch of slots: as ``models/llama.py::
    paged_decode_step`` (a slot whose token would be written to the null
    block is padding). Head: the one row a slot."""
    del ctx_lens
    pos = positions[:, None]
    valid = paged_kv.block_at(block_tables, pos, latent.block_size_of(cfg, cache)) != 0
    cache, X, aux = _paged_layers(cfg, params, cache, tokens[:, None], pos, valid, block_tables)
    return step_outputs(cache, _lm_head(cfg, params, X[:, 0]), step_counters(aux))


# ---------------------------------------------------------------------------
# sharded training step (rehearsed at toy sizes: the benchmark serves this model)


def partition_rules(cfg: Xing4Config, rules) -> list:
    """Ordered ``(regex, PartitionSpec)`` pairs for every param by its path
    (and so for grads and optimizer state, as ``models/llama.py::
    partition_rules``), derived from :func:`logical_axes`."""
    from ray_tpu.parallel.sharding import tree_path_names

    axes = logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    names = tree_path_names(jax.tree_util.tree_map(lambda t: 0, axes, is_leaf=is_axes))
    leaves = jax.tree_util.tree_leaves(axes, is_leaf=is_axes)
    out = [(r"(^|/)v_(row|col)(/|$)", rules.spec((None,)))]
    return out + [(f"(^|/){name}$", rules.spec(ax)) for name, ax in zip(names, leaves)]


def param_shardings(cfg: Xing4Config, mesh, rules):
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda axes: NamedSharding(mesh, rules.spec(axes)),
        logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple),
    )


def _opt_state_shardings(cfg: Xing4Config, mesh, rules, optimizer, params):
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.parallel.sharding import match_partition_rules

    abstract = jax.eval_shape(optimizer.init, params)
    specs = match_partition_rules(partition_rules(cfg, rules), abstract)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )


def init_sharded(cfg: Xing4Config, mesh, rules, rng, optimizer=None):
    """Params (and optimizer state) made directly onto the mesh, as
    ``models/llama.py::init_sharded``."""
    shardings = param_shardings(cfg, mesh, rules)
    with jax.threefry_partitionable(True):
        params = jax.jit(partial(init_params, cfg), out_shardings=shardings)(rng)
    if optimizer is None:
        return params
    oshard = _opt_state_shardings(cfg, mesh, rules, optimizer, params)
    return params, jax.jit(partial(optimizer.init), out_shardings=oshard)(params)


def make_train_step(cfg: Xing4Config, optimizer, *, remat=False, donate: bool = True,
                    mesh=None, rules=None):
    """Jitted ``step((params, opt_state), batch) -> (state, loss)`` with
    params, grads and optimizer state pinned to the one spec table, as
    ``models/llama.py::make_train_step``."""
    import optax

    from ray_tpu.parallel.sharding import constrain_tree

    prules = partition_rules(cfg, rules) if rules is not None else None
    act = rules if mesh is not None else None

    def step(state, batch):
        params, opt_state = state
        params = constrain_tree(params, mesh, prules)
        tokens = constrain(batch["tokens"], mesh, act, ("act_batch", "act_seq"))
        targets = constrain(batch["targets"], mesh, act, ("act_batch", "act_seq"))
        loss, grads = jax.value_and_grad(
            lambda p: next_token_loss(cfg, p, tokens, targets, remat=remat, mesh=mesh, rules=act)
        )(params)
        grads = constrain_tree(grads, mesh, prules)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        opt_state = constrain_tree(opt_state, mesh, prules)
        params = constrain_tree(optax.apply_updates(params, updates), mesh, prules)
        return (params, opt_state), loss

    out_shardings = None
    if prules is not None and mesh is not None:
        abstract = jax.eval_shape(partial(init_params, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32))
        out_shardings = (
            (param_shardings(cfg, mesh, rules),
             _opt_state_shardings(cfg, mesh, rules, optimizer, abstract)),
            None,
        )
    return jax.jit(step, donate_argnums=(0,) if donate else (), out_shardings=out_shardings)


def batch_sharding(mesh, rules):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, rules.spec(("batch", "seq")))


# ---------------------------------------------------------------------------
# what the runtime knows of this module (models/interface.py)


def _attention_path(cfg: Xing4Config, window: int, cache, backend=None) -> AttentionPath:
    """The absorbed path reads each real slot's own live blocks through the
    kernel over latent rows where it serves (``latent.paged_serves``: a TPU,
    the cache in whole tiles) and gathers a slot at a time elsewhere, a real
    slot as wide as the table, nothing for a padding slot; the expanded path
    gathers and expands the key tiles up to the chunk's end either way
    (``latent.key_rungs``, ``Model.gather_rungs``) and says what its ATTENTION
    is accounted at: the table (the materialised softmax: the CPU's, where a
    toy table is one tile) or, through the flash kernel, the key tiles up to
    the live context alone."""
    if latent.paged_serves(cfg, window, cache, backend=backend):
        return AttentionPath("latent.paged", "blocks")
    if absorbs(cfg, window):
        return AttentionPath("latent.absorbed", "slots")
    if latent.flash_serves(cfg, window, cache, backend=backend):
        return AttentionPath("latent.flash", "live")
    return AttentionPath("latent.expanded", "table")


MODEL = Model(
    name="xing4",
    init_params=init_params,
    forward=forward,
    logical_axes=logical_axes,
    param_count=param_count,
    cache_layout=cache_layout,
    paged_prefill_step=paged_prefill_step,
    paged_verify_step=paged_verify_step,
    paged_decode_step=paged_decode_step,
    attention_path=_attention_path,
    held_experts=lambda cfg: cfg.held_experts if cfg.n_moe_layers > 0 else None,
    # every expert layer of the paged body: ``_paged_layers`` scans them all in place
    experts_in_place=lambda cfg, layers: layers,
    key_tile=lambda cfg, window, cache: latent_flash.tiles(window, latent.table_keys(cfg, cache))[1],
    gather_rungs=latent.gather_rungs,
)
