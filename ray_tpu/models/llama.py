"""Llama-family decoder LM, TPU-native (pure JAX + pallas flash attention).

The flagship model path (SURVEY §7 step 7 north star). Design:
  * pure-function model — params are a plain dict pytree; no flax Module
    state to fight GSPMD;
  * every parameter has *logical* axis names (``logical_axes``); a
    ``ShardingRules`` table (``ray_tpu.parallel.sharding``) maps them to
    mesh axes, so DP/FSDP/TP/SP re-parallelization is a table swap;
  * attention is ``ray_tpu.ops.flash_attention`` (pallas on TPU, XLA
    fallback elsewhere), GQA mapped in-kernel (K/V stay at n_kv_heads);
  * bf16-friendly: matmuls in the param dtype, softmax/logits/loss in
    fp32 (MXU wants bf16 inputs + f32 accumulation).

The reference has no JAX model zoo (torch-only, e.g. RLlib models and
Train examples); this is build-new per SURVEY §2.4.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import paged_kv
from ray_tpu.models.interface import AttentionPath, CacheLayout, LayerGroup, Model, lm_head, step_outputs
from ray_tpu.ops.attention import flash_attention, flash_attention_sharded
from ray_tpu.ops.layers import rms_norm
from ray_tpu.parallel.sharding import constrain


@dataclass(frozen=True)
class RopeScaling:
    """YaRN over ``rope_theta`` (as ``models/xing4.py::yarn_inv_freq``): the
    table's own frequencies where a pair turns more than ``beta_fast`` times
    over ``original_max`` positions, those divided by ``factor`` where it turns
    fewer than ``beta_slow`` times, a linear ramp between; cos and sin both
    times ``attention_factor`` (so a layer's scores by its square)."""

    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class LayerKind:
    """What a KIND of attention layer is: layers of one kind differ in their
    weights alone. ``LlamaConfig.layer_kinds`` names one a layer; a configuration
    that names none has the kinds its ``layer_windows`` and ``rope_scaling`` spell
    (``LlamaConfig.kind_of``), every one with ``n_heads`` query heads."""

    #: query at ``i`` sees key ``j`` iff ``i - window < j <= i``; 0: every ``j <= i``
    window: int = 0
    #: query heads (over the configuration's ``n_kv_heads``: grouped-query)
    n_heads: int = 32
    #: the base of the kind's rotary table
    rope_theta: float = 10000.0
    #: how many numbers of a head are rotated, the FIRST ones (the others pass
    #: as they are); 0: the whole head. The table is computed for this width
    rotary_dim: int = 0
    #: YaRN over ``rope_theta``; None = the plain table
    rope_scaling: Optional[RopeScaling] = None


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_hidden: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    #: attention impl: "auto" | "pallas" | "xla" (dense local) or
    #: "ring" | "ulysses" (sequence-parallel over the mesh's seq axis —
    #: pass the mesh to ``forward``/``make_train_step``)
    attention_impl: str = "auto"
    #: RMS-normalise the q and the k projection, each over its WHOLE width
    #: (all heads together), before the split into heads and the rotary
    #: embedding (OLMoE, OLMo-2); adds ``q_norm`` / ``k_norm`` to a layer
    qk_norm: bool = False
    #: >0 turns every MLP block into a MoE FFN with this many experts of
    #: width ``mlp_hidden`` (see ops/moe.py: dropless and grouped by expert
    #: wherever the experts live on one device; GShard dense dispatch over
    #: an ``expert`` mesh axis larger than 1)
    moe_experts: int = 0
    moe_top_k: int = 2
    #: whether the ``moe_top_k`` kept gates are divided by their sum
    #: (Mixtral, GShard) or stay the softmax's own values (OLMoE)
    moe_renormalize: bool = True
    #: read by the expert-parallel path (``ops.moe.moe_ffn``) ALONE: the
    #: dropless path has no capacity and drops nothing
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coeff: float = 0.01
    #: ``(lo, hi)``: this process holds the experts ``lo <= e < hi`` of the
    #: ``moe_experts`` the router chooses among (one chip's share of an
    #: expert-parallel deployment): the expert matrices stack those alone, the
    #: router keeps its width, and a layer's FFN is THIS share's part of it
    #: (``ops/moe.py::dropless_moe_ffn(held=)``). None = all held
    moe_held: Optional[Tuple[int, int]] = None
    #: the width of one head where it is not ``dim // n_heads`` (0: it is)
    attn_head_dim: int = 0
    #: layer KINDS: for each layer the window it attends over (``W``: query at
    #: ``i`` sees key ``j`` iff ``i - W < j <= i``; 0: every ``j <= i``). Empty
    #: = every layer full. A configuration that names kinds has a paged cache
    #: of two layer groups (:func:`cache_layout`)
    layer_windows: Tuple[int, ...] = ()
    #: YaRN for the layers WITHOUT a window (a window layer reaches no
    #: further back than its window and keeps the plain table of
    #: ``rope_theta``). None = the plain table everywhere
    rope_scaling: Optional[RopeScaling] = None
    #: layer kinds that differ in MORE than their window (query heads, the
    #: rope's base, its rotated width, YaRN or not): a :class:`LayerKind` a
    #: layer. It then says everything of a layer's attention: ``layer_windows``
    #: is read from it and ``n_heads`` / ``rope_theta`` / ``rope_scaling`` by no
    #: layer. Empty = the kinds the four fields above spell
    layer_kinds: Tuple[LayerKind, ...] = ()
    #: a learned sigmoid gate a HEAD on the attention output, from the block's
    #: normed input (``wg [dim, heads]``, no bias), before ``wo``
    attn_gate: bool = False
    #: the layers whose FFN is a dense gated MLP of width ``dense_mlp_hidden``
    #: where the others route (``moe_experts`` > 0); with no experts every
    #: layer is dense of ``mlp_hidden`` and this is read by none
    dense_layers: Tuple[int, ...] = ()
    dense_mlp_hidden: int = 0
    #: a routed layer's scores: ``"softmax"`` over the experts or ``"sigmoid"``
    #: each (``ops/moe.py::route``); ``moe_scale`` multiplies the kept gates last
    moe_scoring: str = "softmax"
    moe_scale: float = 1.0
    #: >0: a SHARED expert of this width beside the routed ones, every row
    #: through it, ungated (``shared_gate`` / ``shared_up`` / ``shared_down``)
    moe_shared_hidden: int = 0
    #: SEEDED weights alone (:func:`init_params`): each sublayer's LAST
    #: projection (``wo`` over its own fan-in of heads x head_dim, ``w_down``,
    #: ``shared_down``) a further 1 / sqrt(2 x layers) smaller, and a ROUTED
    #: expert's an eighth of that: with independent random experts a hard
    #: top-8 of 256 flips on bfloat16's rounding and through 39 routed layers
    #: the flips feed on each other (``models/xing4.py::init_params`` says
    #: why, ``kimi_linear.py`` why an eighth; here the logits read 0.10-0.41
    #: against the float32 reference without it: PERF.md, PR 56). Off, as
    #: every configuration before it was drawn
    init_depth_scaled: bool = False

    def __post_init__(self):
        if self.layer_kinds:
            windows = tuple(kind.window for kind in self.layer_kinds)
            if len(windows) != self.n_layers or self.layer_windows not in ((), windows):
                raise ValueError(
                    f"layer_kinds names {len(windows)} layers of {self.n_layers}; layer_windows, "
                    "where given beside it, must say the same windows"
                )
            object.__setattr__(self, "layer_windows", windows)

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.dim // self.n_heads

    def window_of(self, layer: int) -> int:
        return self.layer_windows[layer] if self.layer_windows else 0

    def kind_of(self, layer: int) -> LayerKind:
        """The kind of layer ``layer``: as ``layer_kinds`` names it, or the one
        the plain fields spell (YaRN for a layer without a window alone)."""
        if self.layer_kinds:
            return self.layer_kinds[layer]
        return self.kind_of_window(self.window_of(layer))

    def kind_of_window(self, window: int) -> LayerKind:
        """The kind of the layers that keep ``window``, for a caller that knows
        a layer by its window alone; kinds that share a window are not told
        apart that way."""
        if not self.layer_kinds:
            return LayerKind(window, self.n_heads, self.rope_theta, 0, None if window else self.rope_scaling)
        kinds = {kind for kind in self.layer_kinds if kind.window == window}
        if len(kinds) != 1:
            raise ValueError(f"{len(kinds)} kinds of layer keep a window of {window}: name the layer's kind")
        return kinds.pop()

    @property
    def kinds(self) -> Tuple[LayerKind, ...]:
        """The distinct kinds, in the order of their first layers."""
        return tuple(dict.fromkeys(self.kind_of(layer) for layer in range(self.n_layers)))

    def routes(self, layer: int) -> bool:
        """Whether layer ``layer``'s FFN is routed experts."""
        return self.moe_experts > 0 and layer not in self.dense_layers

    @staticmethod
    def llama2_7b(**overrides) -> "LlamaConfig":
        base = dict(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=32, mlp_hidden=11008, max_seq_len=4096,
            dtype=jnp.bfloat16,
        )
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """CI-sized config (dryrun / unit tests)."""
        base = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            mlp_hidden=128, max_seq_len=64,
        )
        base.update(overrides)
        return LlamaConfig(**base)


# ---------------------------------------------------------------------------
# params + logical sharding axes


def _layer_shapes(cfg: LlamaConfig, layer: int = 0) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of layer ``layer``'s weights: its kind's query heads, its
    FFN's kind. A configuration whose layers are all alike: any layer's."""
    hd = cfg.head_dim
    heads = cfg.kind_of(layer).n_heads
    shapes = {
        "attn_norm": (cfg.dim,),
        "wq": (cfg.dim, heads, hd),
        "wk": (cfg.dim, cfg.n_kv_heads, hd),
        "wv": (cfg.dim, cfg.n_kv_heads, hd),
        "wo": (heads, hd, cfg.dim),
        "mlp_norm": (cfg.dim,),
    }
    if cfg.routes(layer):
        held = cfg.moe_experts if cfg.moe_held is None else cfg.moe_held[1] - cfg.moe_held[0]
        shapes.update(
            {
                "router": (cfg.dim, cfg.moe_experts),
                "w_gate": (held, cfg.dim, cfg.mlp_hidden),
                "w_up": (held, cfg.dim, cfg.mlp_hidden),
                "w_down": (held, cfg.mlp_hidden, cfg.dim),
            }
        )
    else:
        hidden = cfg.dense_mlp_hidden if cfg.moe_experts > 0 else cfg.mlp_hidden
        shapes.update(
            {
                "w_gate": (cfg.dim, hidden),
                "w_up": (cfg.dim, hidden),
                "w_down": (hidden, cfg.dim),
            }
        )
    if cfg.qk_norm:
        # last, so that the other weights of a layer draw the same keys
        shapes.update({"q_norm": (heads * hd,), "k_norm": (cfg.n_kv_heads * hd,)})
    if cfg.attn_gate:
        shapes["wg"] = (cfg.dim, heads)
    if cfg.moe_shared_hidden and cfg.routes(layer):
        shared = cfg.moe_shared_hidden
        shapes.update(
            {"shared_gate": (cfg.dim, shared), "shared_up": (cfg.dim, shared), "shared_down": (shared, cfg.dim)}
        )
    return shapes


def logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis-name tuples."""
    from ray_tpu.ops.moe import moe_logical_axes

    dense = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
    axes = {
        "attn_norm": (None,),
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "mlp_norm": (None,),
        "q_norm": (None,), "k_norm": (None,),
        "wg": ("embed", "heads"),
        **{f"shared_{name[2:]}": spec for name, spec in dense.items()},
    }

    def layer(index: int):
        ffn = moe_logical_axes() if cfg.routes(index) else dense
        return {name: ffn.get(name) or axes[name] for name in _layer_shapes(cfg, index)}

    return {
        "embed": ("vocab", "embed"),
        "layers": [layer(index) for index in range(cfg.n_layers)],
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def init_params(cfg: LlamaConfig, rng: jax.Array) -> Dict[str, Any]:
    # partitionable threefry, same as init_sharded: the legacy lowering
    # produces different values once XLA spatially partitions the RNG,
    # so this is the only mode where the single-chip reference and the
    # sharded init agree for the same seed (see init_sharded's docstring)
    with jax.threefry_partitionable(True):
        return _init_params(cfg, rng)


def _init_params(cfg: LlamaConfig, rng: jax.Array) -> Dict[str, Any]:
    keys = jax.random.split(rng, cfg.n_layers + 2)

    def dense(key, shape, fan_in):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    _MOE_PARAMS = ("router", "w_gate", "w_up", "w_down")

    def layer(key, index):
        shapes = _layer_shapes(cfg, index)
        ks = jax.random.split(key, len(shapes))
        out = {}
        moe = cfg.routes(index)
        for (name, shape), k in zip(shapes.items(), ks):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, cfg.dtype)
            elif moe and name == "router":
                # routing logits are precision-sensitive: keep f32
                out[name] = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[0])
            elif cfg.init_depth_scaled and name in ("wo", "w_down", "shared_down"):
                fan_in = (shape[0] * shape[1] if name == "wo" else shape[-2]) * 2 * cfg.n_layers
                out[name] = dense(k, shape, fan_in * (64 if moe and name == "w_down" else 1))
            elif moe and name in _MOE_PARAMS:
                # (E, fan_in, fan_out): contraction dim is shape[-2]
                out[name] = dense(k, shape, shape[-2])
            else:
                out[name] = dense(k, shape, shape[0] if len(shape) == 2 else cfg.dim)
        return out

    return {
        "embed": dense(keys[0], (cfg.vocab_size, cfg.dim), cfg.dim),
        "layers": [layer(keys[i + 1], i) for i in range(cfg.n_layers)],
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": dense(keys[-1], (cfg.dim, cfg.vocab_size), cfg.dim),
    }


def partition_rules(cfg: LlamaConfig, rules) -> list:
    """Ordered ``(regex, PartitionSpec)`` pairs covering every llama
    param — the regex-rule source of truth ``match_partition_rules``
    applies to params, grads, AND optimizer state (optax mu/nu mirror the
    param tree, so the same path suffixes match; scalar leaves like
    adam's ``count`` are skipped by the matcher). Specs derive from the
    ``ShardingRules`` table, so swapping ddp/fsdp/tp re-derives the whole
    set. Overrides go in FRONT (first ``re.search`` hit wins)."""
    if cfg.moe_experts > 0 and cfg.dense_layers:
        raise ValueError(
            "a configuration with dense layers beside routed ones has no partition rules: w_gate, w_up "
            "and w_down are told apart by name alone here, and a name has one rank"
        )
    sp = rules.spec
    out = [
        # factored second-moment stats (adafactor v_row/v_col) are
        # rank-REDUCED mirrors named after their param — the param's spec
        # cannot apply (and after trailing-None stripping it may even
        # have the right length for the wrong dims), so pin them
        # replicated by NAME, in front of the param rules
        (r"(^|/)v_(row|col)(/|$)", sp((None,))),
        (r"(^|/)embed$", sp(("vocab", "embed"))),
        (r"(attn_norm|mlp_norm|final_norm|q_norm|k_norm)$", sp((None,))),
        (r"wq$", sp(("embed", "heads", "head_dim"))),
        (r"wg$", sp(("embed", "heads"))),
        (r"shared_(gate|up)$", sp(("embed", "mlp"))),
        (r"shared_down$", sp(("mlp", "embed"))),
        (r"(wk|wv)$", sp(("embed", "kv_heads", "head_dim"))),
        (r"wo$", sp(("heads", "head_dim", "embed"))),
        (r"lm_head$", sp(("embed", "vocab"))),
    ]
    if cfg.moe_experts > 0:
        out += [
            (r"router$", sp((None, None))),
            (r"(w_gate|w_up)$", sp(("expert", "embed", "mlp"))),
            (r"w_down$", sp(("expert", "mlp", "embed"))),
        ]
    else:
        out += [
            (r"(w_gate|w_up)$", sp(("embed", "mlp"))),
            (r"w_down$", sp(("mlp", "embed"))),
        ]
    return out


def param_count(cfg: LlamaConfig) -> int:
    layers = sum(
        math.prod(shape) for layer in range(cfg.n_layers) for shape in _layer_shapes(cfg, layer).values()
    )
    return (
        cfg.vocab_size * cfg.dim * 2  # embed + lm_head
        + layers
        + cfg.dim
    )


# ---------------------------------------------------------------------------
# forward


def _inv_freq(cfg: LlamaConfig, kind: Union[int, LayerKind] = 0):
    """The rotary frequencies ``[rotated / 2]`` of a kind of layer (or, an int,
    of the kind that keeps that window) and what multiplies its cos and sin:
    the plain table of the kind's ``rope_theta`` over the width it rotates and
    1, or under the kind's ``rope_scaling`` YaRN's blend and its attention
    factor."""
    if not isinstance(kind, LayerKind):
        kind = cfg.kind_of_window(kind)
    hd = kind.rotary_dim or cfg.head_dim
    plain = 1.0 / (kind.rope_theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    y = kind.rope_scaling
    if y is None:
        return plain, 1.0

    def correction_dim(rotations: float) -> float:
        return (hd * math.log(y.original_max / (rotations * 2 * math.pi))) / (
            2 * math.log(kind.rope_theta)
        )

    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), hd - 1)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / y.factor * ramp + plain * (1.0 - ramp), y.attention_factor


def _cos_sin(ang, factor: float):
    """cos and sin of the angles, times YaRN's attention factor where there is one."""
    if factor == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rope_tables(cfg: LlamaConfig, seq_len: int, offset: int = 0, window: Union[int, LayerKind] = 0):
    inv_freq, factor = _inv_freq(cfg, window)
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    return _cos_sin(jnp.outer(pos, inv_freq), factor)  # [S, hd/2] each


def apply_rope(x, cos, sin):
    """x: [B, S, H, hd] — rotate pairs (even, odd); tables ``[S, r / 2]``
    narrower than the head rotate its first ``r`` numbers, the others pass."""
    r = 2 * cos.shape[-1]
    if r < x.shape[-1]:
        return jnp.concatenate([apply_rope(x[..., :r], cos, sin), x[..., r:]], axis=-1)
    x1, x2 = x[..., ::2], x[..., 1::2]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


def _axis_size(mesh, axes) -> int:
    """Devices along a mesh axis name (or a tuple of them)."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    names = axes if isinstance(axes, tuple) else (axes,)
    return math.prod(shape[a] for a in names)


def _qkv(cfg: LlamaConfig, p, h):
    """The q, k, v projections of one block on normed activations
    ``h [..., D]``: ``(q [..., H, hd], k [..., KV, hd], v [..., KV, hd])``,
    before the rotary embedding. With ``cfg.qk_norm`` q and k are
    RMS-normalised over their whole projection (all heads together), as
    ``modeling_olmoe`` does, not per head."""
    q = jnp.einsum("...d,dhk->...hk", h, p["wq"])
    k = jnp.einsum("...d,dhk->...hk", h, p["wk"])
    v = jnp.einsum("...d,dhk->...hk", h, p["wv"])
    if cfg.qk_norm:
        lead = h.shape[:-1]
        q = rms_norm(q.reshape(*lead, -1), p["q_norm"], cfg.norm_eps).reshape(q.shape)
        k = rms_norm(k.reshape(*lead, -1), p["k_norm"], cfg.norm_eps).reshape(k.shape)
    return q, k, v


def _expert_parallel(mesh) -> bool:
    from ray_tpu.parallel.mesh import EXPERT

    return mesh is not None and EXPERT in mesh.axis_names and _axis_size(mesh, EXPERT) > 1


def _ffn(cfg: LlamaConfig, p, h, valid=None, mesh=None, rules=None):
    """The FFN of one block on normed activations ``h [..., D]``: returns
    ``(ffn(h) [..., D], aux)``, the residual not added. Dense: the gated
    SiLU MLP, ``aux`` None. MoE (a layer that has a ``router``; beside the routed
    experts a shared one where it has ``shared_gate``): ``aux["aux_loss"]`` and, on the dropless
    path, ``aux["load"]`` ``[E]`` int32. ``valid [...]`` bool marks the
    real rows of a padded serving step: padding rows reach no expert and
    are not counted (the dense MLP is row-wise and needs no mask).

    ``perfbench/families/olmoe/server.py`` calls this by name: the expert
    FFN's own correctness reading runs what the steps run."""
    if "router" in p:  # the layer's own weights say its FFN's kind
        from ray_tpu.ops.moe import dropless_moe_ffn, gated_mlp, moe_ffn

        experts = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
        if _expert_parallel(mesh):
            if (cfg.moe_scoring, cfg.moe_scale, cfg.moe_shared_hidden) != ("softmax", 1.0, 0):
                raise ValueError("the expert-parallel path routes by softmax alone, with no shared expert")
            return moe_ffn(
                experts, h, top_k=cfg.moe_top_k, renormalize=cfg.moe_renormalize,
                capacity_factor=cfg.moe_capacity_factor,
            )
        rows = h.reshape(-1, h.shape[-1])
        out, aux = dropless_moe_ffn(
            experts, rows, top_k=cfg.moe_top_k,
            renormalize=cfg.moe_renormalize,
            valid=None if valid is None else valid.reshape(-1),
            held=cfg.moe_held, scoring=cfg.moe_scoring, scale=cfg.moe_scale,
        )
        if "shared_gate" in p:
            with jax.named_scope("moe.shared"):
                shared = gated_mlp(rows, p["shared_gate"], p["shared_up"], p["shared_down"])
                if valid is not None:  # a padding row comes back as zeros, as from the routed experts
                    shared = jnp.where(valid.reshape(-1)[:, None], shared, 0)
                out = out + shared
        return out.reshape(h.shape), aux
    gate = jnp.einsum("...d,dm->...m", h, p["w_gate"])
    up = jnp.einsum("...d,dm->...m", h, p["w_up"])
    # Megatron split: the hidden activation shards over tensor, the
    # down-projection's output all-reduces back to the replicated stream
    gate = constrain(gate, mesh, rules, ("act_batch", "act_seq", "act_mlp"))
    up = constrain(up, mesh, rules, ("act_batch", "act_seq", "act_mlp"))
    return jnp.einsum("...m,md->...d", jax.nn.silu(gate) * up, p["w_down"]), None


def _attend_by_kind(cfg: LlamaConfig, q, k, v, window: int):
    """``forward``'s attention for a configuration with layer kinds, on XLA:
    ``q [B, S, H, hd]``, ``k`` / ``v`` ``[B, S, KV, hd]`` (rope applied) ->
    ``[B, S, H, hd]``; float32 scores, the causal mask and, for a window
    layer, ``j > i - window``. (Training such a model wants the mask in
    ``ops/attention.py``'s kernel: ROADMAP R4.)"""
    B, S, H, hd = q.shape
    qg = q.reshape(B, S, cfg.n_kv_heads, H // cfg.n_kv_heads, hd)
    s = jnp.einsum("bcgrh,bsgh->bgrcs", qg, k).astype(jnp.float32) / math.sqrt(hd)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)
    pattn = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    o = jnp.einsum("bgrcs,bsgh->bcgrh", pattn.astype(v.dtype), v)
    return o.reshape(B, S, H, hd)


def _head_gate(p, h, o):
    """``o [..., H, hd]`` times a sigmoid a head of the block's normed input
    ``h [..., D]`` (``LlamaConfig.attn_gate``): float32, back in ``o``'s dtype."""
    with jax.named_scope("attn.gate"):
        g = jax.nn.sigmoid(jnp.einsum("...d,dh->...h", h, p["wg"], preferred_element_type=jnp.float32))
        return (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)


def _attention_block(cfg: LlamaConfig, p, x, cos, sin, mesh=None, rules=None, window: int = 0):
    B, S, _ = x.shape
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, h)
    # attention ENTRY pin: q/k/v leave the projection in the head-sharded
    # layout the attention impl expects (ring attention's shard_map specs
    # are exactly these) — without it GSPMD picks per-op and the bwd
    # disagrees with the fwd across the remat boundary
    q = constrain(q, mesh, rules, ("act_batch", "act_seq", "act_heads", None))
    k = constrain(k, mesh, rules, ("act_batch", "act_seq", "act_kv_heads", None))
    v = constrain(v, mesh, rules, ("act_batch", "act_seq", "act_kv_heads", None))
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    rep = q.shape[2] // cfg.n_kv_heads
    if cfg.layer_windows:
        if mesh is not None or cfg.attention_impl not in ("auto", "xla"):
            raise ValueError(
                "a configuration with layer kinds (layer_windows) runs forward() on one device "
                "through XLA: the flash kernel of ops/attention.py has no window mask yet"
            )
        o = _attend_by_kind(cfg, q, k, v, window).transpose(0, 2, 1, 3)
    elif cfg.attention_impl in ("ring", "ulysses"):
        if mesh is None:
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r} is sequence-parallel: "
                "pass the mesh to forward()/make_train_step()"
            )
        from ray_tpu.ops.ring_attention import (
            ring_attention_sharded,
            ulysses_attention_sharded,
        )
        from ray_tpu.parallel.mesh import TENSOR

        # [B, S, H, hd] → [B, H, S, hd]; K/V stay at n_kv_heads — the
        # seq-parallel impls rotate/exchange the small GQA heads and
        # repeat locally, keeping collective volume at 1/rep.
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if rep > 1 and cfg.n_kv_heads % _axis_size(mesh, TENSOR) != 0:
            # Too few KV heads for the tensor axis: pre-repeat (rare).
            kt = jnp.repeat(kt, rep, axis=1)
            vt = jnp.repeat(vt, rep, axis=1)
            rep = 1
        if cfg.attention_impl == "ring":
            o = ring_attention_sharded(qt, kt, vt, mesh, causal=True, kv_repeat=rep)
        else:
            o = ulysses_attention_sharded(qt, kt, vt, mesh, causal=True)
    else:
        # GQA K/V stay at n_kv_heads — the flash kernel maps q-head →
        # kv-head in its index map, so the repeat never touches HBM.
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if mesh is not None and rules is not None:
            # sharded step: each device attends over its own (batch,
            # heads) block — the kernel goes through shard_map because
            # GSPMD cannot partition it. The sequence stays whole here
            # whatever ``act_seq`` says (that axis is ring/ulysses').
            kv_axis = rules["act_kv_heads"]
            if rep > 1 and kv_axis is not None and cfg.n_kv_heads % _axis_size(mesh, kv_axis):
                # too few KV heads for the head axis: pre-repeat (rare)
                kt = jnp.repeat(kt, rep, axis=1)
                vt = jnp.repeat(vt, rep, axis=1)
                kv_axis = rules["act_heads"]
            o = flash_attention_sharded(
                qt, kt, vt, mesh,
                q_spec=rules.spec(("act_batch", "act_heads")),
                kv_spec=jax.sharding.PartitionSpec(rules["act_batch"], kv_axis),
                causal=True, impl=cfg.attention_impl,
            )
        else:
            o = flash_attention(qt, kt, vt, causal=True, impl=cfg.attention_impl)
    o = o.transpose(0, 2, 1, 3)  # [B, S, H, hd]
    if cfg.attn_gate:
        o = _head_gate(p, h, o)
    # attention EXIT pin + name: the flash output is the expensive tensor
    # the selective-remat policy saves (recompute elementwise, never the
    # attention itself)
    o = constrain(o, mesh, rules, ("act_batch", "act_seq", "act_heads", None))
    o = checkpoint_name(o, "flash_attn_out")
    out = x + jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    return constrain(out, mesh, rules, ("act_batch", "act_seq", "act_embed"))


def _mlp_block(cfg: LlamaConfig, p, x, mesh=None, rules=None):
    """Dense or MoE FFN with its norm and residual. Returns (x, aux_loss)."""
    h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    if "router" in p:
        # entry/exit pins bracket the expert compute (interior shardings
        # over the ``expert`` axis are moe_ffn's own business) so the
        # MoE FFN keeps the same replicated-residual contract as the
        # dense branch and fwd/bwd agree across the remat boundary
        h = constrain(h, mesh, rules, ("act_batch", "act_seq", "act_embed"))
    out, aux = _ffn(cfg, p, h, mesh=mesh, rules=rules)
    out = constrain(x + out, mesh, rules, ("act_batch", "act_seq", "act_embed"))
    return out, 0.0 if aux is None else aux["aux_loss"]


def _remat_policy(remat):
    """``remat``: False (no checkpointing), True/"full" (recompute
    everything — the pre-unified default), or "selective" (save matmul
    outputs and the flash-attention output, recompute only the cheap
    elementwise tail: norms, rope, silu, residual adds). Selective remat
    trades a little memory for skipping the expensive recompute — on the
    stable shardings it is what closes the fwd-vs-fwd+bwd MFU cliff."""
    if remat in (False, None):
        return None, False
    if remat is True or remat == "full":
        return None, True
    if remat == "selective":
        pol = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("flash_attn_out"),
        )
        return pol, True
    raise ValueError(f"remat must be False, True, 'full', or 'selective'; got {remat!r}")


def forward(cfg: LlamaConfig, params, tokens, *, remat=False, mesh=None,
            rules=None, return_aux: bool = False):
    """tokens [B, S] int32 → logits [B, S, vocab] (f32).

    ``mesh`` is required for the sequence-parallel attention impls
    ("ring"/"ulysses"), which shard_map over its ``seq`` axis. With
    ``rules`` (a ``ShardingRules``) AND a mesh, every intermediate is
    pinned via ``with_sharding_constraint`` so fwd and bwd agree on one
    sharding per tensor (the multichip involuntary-remat fix); without
    them the function is bit-identical to the unconstrained reference.
    ``remat``: False | True/"full" | "selective" (see ``_remat_policy``).
    With ``return_aux`` also returns the summed MoE load-balance loss."""
    B, S = tokens.shape
    # Embedding lookup: gathering from a vocab/embed-sharded table leaves
    # the output embed-dim-sharded, and SPMD cannot reshard D-over-fsdp →
    # batch-over-fsdp without a full rematerialization (the exact
    # involuntary-remat warning MULTICHIP_r05 logged). Pin the table
    # REPLICATED for the lookup instead — the all-gather becomes
    # voluntary (ZeRO-3 semantics: params materialize for compute) and
    # the batch/seq constraint on the output is a cheap slice.
    emb = constrain(params["embed"], mesh, rules, (None, None))
    x = emb[tokens]
    x = constrain(x, mesh, rules, ("act_batch", "act_seq", "act_embed"))
    policy, do_remat = _remat_policy(remat)

    def block_of(kind: LayerKind):
        """One layer's block, with the mask and the rope table of its kind."""
        cos, sin = rope_tables(cfg, S, window=kind)
        window = kind.window

        def block(carry, p):
            x, aux = carry
            # remat-boundary pin: the carry is the tensor saved at every
            # checkpoint boundary — its fwd sharding must be explicit so the
            # recompute and the bwd accumulation land on the same layout
            x = constrain(x, mesh, rules, ("act_batch", "act_seq", "act_embed"))
            x = _attention_block(cfg, p, x, cos, sin, mesh=mesh, rules=rules, window=window)
            x, layer_aux = _mlp_block(cfg, p, x, mesh=mesh, rules=rules)
            return x, aux + layer_aux

        return jax.checkpoint(block, policy=policy) if do_remat else block

    # a kind's block is made once, the full layers' first (as ever)
    blocks = {kind: block_of(kind) for kind in sorted(cfg.kinds, key=lambda kind: kind.window)}
    carry = (x, jnp.zeros((), jnp.float32))
    for layer, p in enumerate(params["layers"]):
        carry = blocks[cfg.kind_of(layer)](carry, p)
    x, aux = carry
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)
    logits = constrain(logits, mesh, rules, ("act_batch", "act_seq", "act_vocab"))
    if return_aux:
        return logits, aux
    return logits


def next_token_loss(cfg: LlamaConfig, params, tokens, targets, *, remat=False,
                    mesh=None, rules=None):
    logits, aux = forward(
        cfg, params, tokens, remat=remat, mesh=mesh, rules=rules, return_aux=True
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32), axis=-1)
    return nll.mean() + cfg.moe_aux_loss_coeff * aux


# ---------------------------------------------------------------------------
# sharded training step


def param_shardings(cfg: LlamaConfig, mesh, rules):
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda axes: NamedSharding(mesh, rules.spec(axes)),
        logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def batch_sharding(mesh, rules):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, rules.spec(("batch", "seq")))


def init_sharded(cfg: LlamaConfig, mesh, rules, rng, optimizer=None):
    """Init params (and optimizer state) directly onto the mesh: the init
    computation is jitted with explicit out_shardings so no host has to
    hold a full replica (how 7B+ params fit a v4-32 host).

    Runs under partitionable threefry: the legacy (non-partitionable)
    RNG lowering produces DIFFERENT values when XLA spatially partitions
    it, so the same seed gave different params per rules table — sharded
    init silently diverged from the single-chip reference (measured
    max-abs 0.6 on the tiny config). Partitionable threefry is
    sharding-invariant, so init values match the unsharded path exactly
    whatever the mesh."""
    shardings = param_shardings(cfg, mesh, rules)
    with jax.threefry_partitionable(True):
        params = jax.jit(partial(init_params, cfg), out_shardings=shardings)(rng)
    if optimizer is None:
        return params
    # Optimizer state inits pinned to the SAME matched rule table the
    # train step constrains it to (mu/nu mirror the params; adam's count
    # stays replicated). Without explicit out_shardings the jitted init
    # hands back single-device state, and the step's first call would
    # emit rule-sharded state — a guaranteed one-step recompile (and on
    # real HBM, a full unsharded optimizer replica). partial() gives
    # THIS call its own jit identity: callers reuse one optax optimizer
    # across meshes (the multichip dryrun inits on two), and a bare
    # ``optimizer.init`` would share one C++ jit cache across them — the
    # PR 6 ``copy_paged_blocks`` cache-pollution class.
    oshard = _opt_state_shardings(cfg, mesh, rules, optimizer, params)
    opt_state = jax.jit(partial(optimizer.init), out_shardings=oshard)(params)
    return params, opt_state


def _opt_state_shardings(cfg: LlamaConfig, mesh, rules, optimizer, params):
    """NamedShardings for ``optimizer``'s state over ``params`` (arrays
    or abstract), from the same matched rule table as the params."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.parallel.sharding import match_partition_rules

    abstract = jax.eval_shape(optimizer.init, params)
    ospecs = match_partition_rules(partition_rules(cfg, rules), abstract)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        ospecs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


# ---------------------------------------------------------------------------
# paged-KV autoregressive decode (inference engine path)
#
# Layout (vLLM-style, GQA-aware): one K and one V tensor of shape
#   [n_layers, num_blocks, block_size, n_kv_heads, head_dim]
# shared by every request. A request owns a list of block ids (its block
# table row); token position p lives at (blocks[p // block_size],
# p % block_size) in every layer of ONE layer group, so the host-side
# allocator hands out one id per block_size tokens a group, not one per
# layer. A configuration whose layers are all of a kind has one group of
# every layer; one with layer kinds (``layer_windows``) has a K and a V
# tensor a group, each with its own ``num_blocks`` and its own table a
# request (:func:`cache_layout`, :func:`_paged_layers`), and few KV heads
# (4) are stored joined to the tokens, [.., block_size * n_kv_heads,
# head_dim]. K/V stay at n_kv_heads (GQA kept compressed in HBM, exactly as
# the flash kernel does): queries are grouped [n_kv, rep] at score time,
# so cache traffic is 1/rep of the repeated layout.
#
# Block id 0 is the NULL block: never allocated, padding positions write
# into it and masked reads from it never reach the softmax. Keeping the
# trash in-band is what lets every step run with fully static shapes.


def cache_layout(cfg: LlamaConfig, block_size: int, dtype=None) -> CacheLayout:
    """The cache description of this block (``models/interface.py``): a K
    and a V row ``[n_kv_heads, head_dim]`` a token a layer. One group of every
    layer (``all``); for a configuration with layer kinds (``layer_windows``) a
    group a kind, the full layers' first (``full``: keeps a sequence whole) and
    one a window width (``window``, or ``window<W>`` where there are several). Heads
    of whole lanes that are too few to fill a tile (``n_kv`` 4) are stored
    joined to the tokens (``CacheLayout.flat_blocks``), nothing padded."""
    row = (cfg.n_kv_heads, cfg.head_dim)
    windows = cfg.layer_windows or (0,) * cfg.n_layers
    widths = sorted(set(windows))
    if widths[0] != 0 or len(windows) != cfg.n_layers:
        raise ValueError(
            f"layer_windows names {len(windows)} layers of {cfg.n_layers}, full ones "
            f"among them: {widths[0] == 0} (the paged cache needs a group that keeps a sequence whole)"
        )
    names = {0: "all" if len(widths) == 1 else "full"}
    groups = tuple(
        LayerGroup(
            names.get(w, "window" if len(widths) == 2 else f"window{w}"),
            tuple(l for l, lw in enumerate(windows) if lw == w), w,
        )
        for w in widths
    )
    return CacheLayout(
        kind="kv", n_layers=cfg.n_layers, block_size=block_size,
        arrays=(("k", row), ("v", row)), dtype=dtype or cfg.dtype, groups=groups,
        flat_blocks=(
            cfg.head_dim % 128 == 0 and cfg.n_kv_heads % 8 != 0
            and (block_size * cfg.n_kv_heads) % 16 == 0
        ),
    )


def init_paged_kv_cache(
    cfg: LlamaConfig, num_blocks: int, block_size: int, dtype=None
) -> Dict[str, jax.Array]:
    """Device-side paged KV cache (zeros; block 0 reserved as null)."""
    return cache_layout(cfg, block_size, dtype).init(num_blocks)


def _rope_at(cfg: LlamaConfig, positions, kind: Union[int, LayerKind] = 0):
    """cos/sin tables at arbitrary int positions: [...] -> ([..., rotated/2]
    x2), of a layer of that kind, or with that window (:func:`_inv_freq`)."""
    inv_freq, factor = _inv_freq(cfg, kind)
    return _cos_sin(positions.astype(jnp.float32)[..., None] * inv_freq, factor)


def _apply_rope_flat(x, cos, sin):
    """x: [..., H, hd] with per-row position tables [..., r/2]: the first
    ``r`` numbers of a head are rotated (:func:`apply_rope`)."""
    r = 2 * cos.shape[-1]
    if r < x.shape[-1]:
        return jnp.concatenate([_apply_rope_flat(x[..., :r], cos, sin), x[..., r:]], axis=-1)
    x1, x2 = x[..., ::2], x[..., 1::2]
    c = cos[..., None, :]
    s = sin[..., None, :]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


#: ``perfbench/families/mellum/server.py`` reads this name (ROADMAP D20: an
#: alias kept for the benchmark; the function is ``models/paged_kv.py``'s)
_block_at = paged_kv.block_at


def _block_size(cfg: LlamaConfig, cache) -> int:
    return paged_kv.block_size(cache["k"], cfg.n_kv_heads, cfg.head_dim)


def _stacked(loads: list):
    """A MoE configuration's expert loads ``[n_layers, E]`` int32 of a step's
    valid rows, the third output of its paged steps; None where no layer routes."""
    return jnp.stack(loads) if loads else None


def _ffn_residual(cfg: LlamaConfig, p, x, valid, loads: list):
    """``x + ffn(norm(x))`` of one block in a paged step; a MoE block's
    expert load of the ``valid`` rows is appended to ``loads``."""
    out, aux = _ffn(cfg, p, rms_norm(x, p["mlp_norm"], cfg.norm_eps), valid)
    if aux is not None:
        loads.append(aux["load"])
    return x + out


def _group_table(block_tables, group: int):
    """The block table of layer group ``group``: ``block_tables [G, B, M]``,
    a table a group in the layout's order; a model of ONE group is handed its
    table as it always was, ``[B, M]``."""
    return block_tables[group] if block_tables.ndim == 3 else block_tables


def _paged_layers(cfg: LlamaConfig, params, cache, x, pos, valid, block_tables):
    """Every block of the model over a paged cache: the body of the three
    serving steps. ``x [B, C, D]`` embedded tokens, ``pos [B, C]`` their
    global positions, ``valid [B, C]`` bool (a padding row's K/V goes to the
    null block, or, in a chunk written by whole blocks, nowhere: its position
    keeps what it held (``paged_kv.write_kv``); it reaches no expert),
    ``block_tables`` one table a layer group
    of the cache (:func:`cache_layout`, :func:`_group_table`). Per layer:
    norm, q/k/v, rope at ``pos``, K/V written to the cache, attention over
    the cache (``models/paged_kv.py::attention``, after the write so a window
    attends to itself), ``wo`` (:func:`_paged_attention_block`), the FFN.
    Returns ``(cache, x, loads)``, ``loads`` a list of a MoE block's expert
    loads a layer.

    Layer ``l`` writes and reads its GROUP's arrays through its group's
    table, with its kind's rope table and mask. What is per group is made
    once a group (the block a position is written to, where the window is
    written by rows), what is per kind once
    a kind (the rope table). A configuration without layer kinds has one
    group, every layer in it.

    Positions past a slot's committed context may hold stale K/V (the
    rejected tail of a verify window, a preempted chunk); that is safe by
    construction: every read masks on ``key_pos <= pos``, so nothing past
    the querying token is ever read, and the next write to a position
    overwrites it in place."""
    layout = cache_layout(cfg, _block_size(cfg, cache))
    bs = layout.block_size
    tables = [_group_table(block_tables, g) for g in range(len(layout.groups))]
    # the rows way's addresses, once a group (a chunk of whole blocks is written by blocks: paged_kv.write_kv)
    by_rows = paged_kv.write_way(*pos.shape, bs) == "rows"
    blks = [jnp.where(valid, paged_kv.block_at(table, pos, bs), 0) if by_rows else None for table in tables]
    off = pos % bs if by_rows else None
    # a kind's rope table, in the groups' order (kinds may share a group: its window is what a group is)
    ropes = {
        kind: _rope_at(cfg, pos, kind)
        for kind in dict.fromkeys(cfg.kind_of(l) for group in layout.groups for l in group.layers)
    }
    where = {l: (g, i) for g, group in enumerate(layout.groups) for i, l in enumerate(group.layers)}
    loads = []
    for layer, p in enumerate(params["layers"]):
        g, index = where[layer]
        names = (layout.array_name("k", g), layout.array_name("v", g))
        cache, x = _paged_attention_block(
            cfg, p, cache, x, pos, valid, tables[g], index, names, layout.groups[g].keeps,
            blks[g], off, ropes[cfg.kind_of(layer)],
        )
        x = _ffn_residual(cfg, p, x, valid, loads)
    return cache, x, loads


def _paged_attention_block(
    cfg: LlamaConfig, p, cache, x, pos, valid, block_table, index: int, names, window: int,
    blk, off, rope,
):
    """The attention half of one layer over its group's arrays (``names``,
    the layer their index ``index``)
    through its group's table ``block_table [B, M]``: the norm, q / k / v, the
    kind's rope table ``rope`` (cos, sin at ``pos``, as wide as the kind
    rotates), the write of the step's K and V (``paged_kv.write_kv``: at
    ``(blk, off)`` by rows, through the table by blocks), the attention
    over the cache, the head gate where the configuration has one, and ``wo``;
    the layer's query heads are its ``wq``'s.
    Returns ``(cache, x + attention)``."""
    cos, sin = rope
    with jax.named_scope("attn.window" if window else "attn.full"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, h)
        q = _apply_rope_flat(q, cos, sin)
        k = _apply_rope_flat(k, cos, sin)
        cache = paged_kv.write_kv(cache, index, block_table, pos, valid, k, v, names, at=(blk, off))
        o = paged_kv.attention(
            q, cache[names[0]], cache[names[1]], index, block_table, pos, valid,
            n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim, keeps=window,
        )
        if cfg.attn_gate:  # on the kernel's output: no reason to leave the kernel
            o = _head_gate(p, h, o)
        return cache, x + jnp.einsum("bchk,hkd->bcd", o.astype(x.dtype), p["wo"])


def paged_prefill_step(
    cfg: LlamaConfig, params, cache, tokens, block_table, ctx_len, true_len
):
    """One prefill chunk for ONE request (``B = 1`` of :func:`_paged_layers`).

    tokens: [C] int32 (right-padded chunk), block_table: [M] int32 (padded
    with 0 = null; ``[G, M]``, a row a layer group, for a configuration with
    layer kinds, and ``[G, B, M]`` in the two steps below), ctx_len: scalar int32 tokens ALREADY cached (chunked
    prefill: >0 from the second chunk on), true_len: scalar int32 valid
    tokens in this chunk (``valid = idx < true_len``). Head: the chunk's
    last valid row only. Returns ``(cache, logits [vocab])``, and a MoE
    config's expert loads (``interface.step_outputs``).
    """
    idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cache, x, loads = _paged_layers(
        cfg, params, cache, params["embed"][tokens][None],
        (ctx_len + idx)[None], (idx < true_len)[None],
        jnp.expand_dims(block_table, -2),  # [M] -> [1, M]; a table a group: [G, M] -> [G, 1, M]
    )
    logits = lm_head(params, x[0, jnp.maximum(true_len - 1, 0)], cfg.norm_eps, tied=False)
    return step_outputs(cache, logits, _stacked(loads))


def paged_verify_step(
    cfg: LlamaConfig, params, cache, tokens, block_tables, ctx_lens, true_lens
):
    """Speculative verification for a BATCH of slots: windows of C
    positions a slot, :func:`_paged_layers` as it is.

    tokens: [B, C] int32 (right-padded verify windows ``[last_committed,
    d_1..d_k]`` per slot), block_tables: [B, M] int32, ctx_lens: [B] int32
    tokens already cached per slot, true_lens: [B] int32 valid window
    lengths (``valid = idx < true_len``; 0 for a padding slot). Head:
    EVERY row, so the host accepts or rejects each drafted token by
    itself. Returns ``(cache, logits [B, C, vocab])``, and a MoE config's
    expert loads (``interface.step_outputs``).
    """
    idx = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    cache, x, loads = _paged_layers(
        cfg, params, cache, params["embed"][tokens],
        ctx_lens[:, None] + idx, idx < true_lens[:, None], block_tables,
    )
    return step_outputs(cache, lm_head(params, x, cfg.norm_eps, tied=False), _stacked(loads))


def paged_decode_step(
    cfg: LlamaConfig, params, cache, tokens, positions, block_tables, ctx_lens
):
    """One decode step for a BATCH of slots (``C = 1`` of :func:`_paged_layers`).

    tokens: [B] int32 (this step's input token per slot), positions: [B]
    int32 (its global position), block_tables: [B, M] int32, ctx_lens: [B]
    int32, ``positions + 1`` on a real slot (the mask is taken from
    ``positions``; the argument stays for the callers). A slot whose token
    would be written to the null block is padding: ``valid`` is taken
    from the block table. Head: the one row a slot. Returns
    ``(cache, logits [B, vocab])``, and a MoE config's expert loads
    (``interface.step_outputs``).
    """
    del ctx_lens
    pos = positions[:, None]
    whole = _group_table(block_tables, 0)  # the group that keeps all
    valid = paged_kv.block_at(whole, pos, _block_size(cfg, cache)) != 0
    cache, x, loads = _paged_layers(
        cfg, params, cache, params["embed"][tokens][:, None], pos, valid, block_tables
    )
    return step_outputs(cache, lm_head(params, x[:, 0], cfg.norm_eps, tied=False), _stacked(loads))


def make_train_step(cfg: LlamaConfig, optimizer, *, remat=False, donate: bool = True,
                    mesh=None, rules=None):
    """Returns jitted ``step((params, opt_state), batch) → (state, loss)``.

    Gradient reduction over data/fsdp axes is inserted by GSPMD from the
    input shardings — there is no hand-written psum (scaling-book recipe:
    annotate, compile, let XLA place collectives on ICI).

    With ``rules`` (a ``ShardingRules``) and ``mesh``, the UNIFIED
    named-sharding path engages: params, grads, optimizer updates, and
    optimizer state are all pinned to the ONE spec table
    (``partition_rules`` + ``match_partition_rules``), and the forward
    pins its intermediates — fwd, bwd, and the optimizer update agree on
    every tensor, so the multichip compile has zero involuntary
    rematerializations. Without ``rules`` the step is the legacy
    unconstrained one (``mesh`` alone is still needed for the
    sequence-parallel attention impls). ``remat``: False | True/"full" |
    "selective" (save dots + flash outputs, recompute the elementwise
    tail)."""
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
            lambda p: next_token_loss(
                cfg, p, tokens, targets, remat=remat, mesh=mesh, rules=act
            )
        )(params)
        # grad → optimizer handoff: grads carry the params' specs (one
        # table), so adamw's elementwise update never repartitions
        grads = constrain_tree(grads, mesh, prules)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        updates = constrain_tree(updates, mesh, prules)
        opt_state = constrain_tree(opt_state, mesh, prules)
        params = optax.apply_updates(params, updates)
        params = constrain_tree(params, mesh, prules)
        return (params, opt_state), loss

    out_shardings = None
    if prules is not None and mesh is not None:
        # The state leaves the step under exactly the specs it came in
        # with (``init_sharded``'s). Left to XLA, a mesh axis of size 1
        # (one chip) comes back normalized to an equivalent but
        # different-looking spec, and the second call compiles again.
        abstract = jax.eval_shape(
            partial(init_params, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
        )
        out_shardings = (
            (
                param_shardings(cfg, mesh, rules),
                _opt_state_shardings(cfg, mesh, rules, optimizer, abstract),
            ),
            None,
        )
    return jax.jit(
        step, donate_argnums=(0,) if donate else (), out_shardings=out_shardings
    )


# ---------------------------------------------------------------------------
# what the runtime knows of this module (models/interface.py)


def _program_path(cfg: LlamaConfig, window: int, cache) -> tuple:
    return paged_kv.program_path(
        window, cache["k"], cfg.max_seq_len, {kind.n_heads for kind in cfg.kinds},
        n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
    )


def _attention_path(cfg: LlamaConfig, window: int, cache) -> AttentionPath:
    """The path of the programs of that query window, from the shapes and
    the backend as ``models/paged_kv.py::attention`` chooses it: ``kernel``
    (decode, verify; reads ``blocks``), ``flash`` (a prefill chunk in whole
    tiles; reads the ``live`` context in whole key tiles), ``gather``
    (everything else; reads the ``table``). A configuration with layer kinds
    runs the same path in both kinds of layer and says so (``kernel+window``:
    each group's kernel reads the slot's live blocks of that group, a window's
    from its first live one on), and the query heads of each kind where they
    differ (``kernel+window[full:48h,window:64h]``)."""
    kinds = "+window" if cfg.layer_windows else ""
    if len({kind.n_heads for kind in cfg.kinds}) > 1:  # the heads a kind, as window or full
        kinds += "[" + ",".join(f"{'window' if k.window else 'full'}:{k.n_heads}h" for k in cfg.kinds) + "]"
    way, reads, _ = _program_path(cfg, window, cache)
    return AttentionPath(f"{way}{kinds}", reads)


MODEL = Model(
    name="llama",
    init_params=init_params,
    forward=forward,
    logical_axes=logical_axes,
    param_count=param_count,
    cache_layout=cache_layout,
    paged_prefill_step=paged_prefill_step,
    paged_verify_step=paged_verify_step,
    paged_decode_step=paged_decode_step,
    attention_path=_attention_path,
    held_experts=lambda cfg: (cfg.moe_held or (0, cfg.moe_experts)) if cfg.moe_experts > 0 else None,
    key_tile=lambda cfg, window, cache: _program_path(cfg, window, cache)[2],
)
