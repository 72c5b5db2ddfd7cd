"""Mixture-of-Experts FFNs: one routing function, two ways to run the experts.

:func:`route` is the one routing of every MoE path: float32 router logits,
a score an expert (``scoring``: a softmax over the experts, or a sigmoid
each), ``top_k`` of the scores (for the choice alone, plus a per-expert
``bias``, and where the architecture says so among the experts of its best
GROUPS alone: ``n_group`` / ``topk_group``), and the kept gates renormalised only where the architecture says
so (``renormalize``; OLMoE does not) and scaled by ``scale``.

:func:`dropless_moe_ffn` is what runs wherever the experts this process
computes live on one device: all of them, or, TOLD a ``held`` range, one
chip's share of an expert-parallel deployment (it routes over all the
experts and computes its own experts' part; what the absent experts would
have added is left out, nothing stands in for the exchange) (serving's three paged steps, ``forward`` without an ``expert`` mesh
axis): the ``T x k`` assignments are flattened, sorted by expert, pushed
through the three expert matmuls GROUPED by expert (:func:`grouped_matmul`:
on a TPU the Pallas grouped matmul of ``megablox``, elsewhere
``jax.lax.ragged_dot``), unsorted, weighted by the gates and summed over
``k``. No capacity, no dropped token, no ``[T, E, C]`` tensor; a ``valid``
row mask keeps padding rows out of every group, so what shares a batch
with a request cannot change its answer. It returns the per-expert load of
the valid rows.

:func:`moe_ffn` is the expert-PARALLEL path (training over an ``expert``
mesh axis larger than 1). Reference: absent (SURVEY §2.4 — EP is a
build-new item). Design is the GSPMD dense-dispatch recipe (Switch/GShard):
the routing produces a capacity-limited one-hot dispatch tensor;
dispatch/combine are einsums, expert FFNs run batched over the expert dim,
and sharding the expert dim over the ``expert`` mesh axis makes XLA insert
the all-to-alls over ICI — no hand-written collectives (scaling-book
recipe). Capacity semantics: each expert processes at most
``capacity = ceil(top_k * tokens / experts * capacity_factor)``
assignments; overflow assignments are dropped (their combine weight is
zero and the token falls back to the residual stream) — the standard
Switch Transformer drop policy, which only this path has."""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def init_moe_params(
    rng: jax.Array,
    dim: int,
    hidden: int,
    num_experts: int,
    dtype=jnp.float32,
) -> Dict[str, Any]:
    """Router + per-expert SwiGLU FFN params (stacked over experts)."""
    kr, kg, ku, kd = jax.random.split(rng, 4)
    scale_in = 1.0 / math.sqrt(dim)
    scale_hid = 1.0 / math.sqrt(hidden)
    return {
        "router": (jax.random.normal(kr, (dim, num_experts), jnp.float32) * scale_in),
        "w_gate": (jax.random.normal(kg, (num_experts, dim, hidden), jnp.float32) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(ku, (num_experts, dim, hidden), jnp.float32) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(kd, (num_experts, hidden, dim), jnp.float32) * scale_hid).astype(dtype),
    }


def moe_logical_axes() -> Dict[str, Tuple[Optional[str], ...]]:
    """Logical axes for the params above (rules map "expert"→EXPERT mesh
    axis so expert FFNs shard with all-to-all dispatch inserted by XLA)."""
    return {
        "router": (None, None),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


def route(
    router: jnp.ndarray,
    x: jnp.ndarray,
    *,
    top_k: int,
    renormalize: bool,
    router_noise: float = 0.0,
    rng: Optional[jax.Array] = None,
    scoring: str = "softmax",
    bias: Optional[jnp.ndarray] = None,
    scale: float = 1.0,
    n_group: int = 1,
    topk_group: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The routing of every MoE path. x: [T, d], router: [d, E] →
    ``(gates [T, k] float32, experts [T, k] int32, probs [T, E] float32)``.

    Router logits, scores and ``top_k`` are float32 at the matmul's
    highest precision: the 8th and 9th expert of a token can be close, and
    a TPU's default precision would round the products to bfloat16.
    ``scoring``: ``"softmax"`` over the experts, or ``"sigmoid"`` of each
    logit by itself. ``bias`` [E] (a weight: the correction of auxiliary-
    loss-free balancing) is added to the scores for the CHOICE of the
    ``top_k`` alone; a kept expert's gate is its score without it.
    ``renormalize`` divides the kept gates by their sum (Mixtral, GShard);
    without it the gates are the scores' own values (OLMoE:
    ``norm_topk_prob`` false). ``scale`` multiplies the gates last.

    ``n_group`` > 1 LIMITS the choice to groups of experts (DeepSeek-V3's
    ``noaux_tc``: the experts of a group live on one node, and a token may
    reach ``topk_group`` nodes): the ``E`` experts are ``n_group`` runs of
    ``E / n_group`` neighbours; a group's score is the sum of its TWO largest
    ``score + bias``; the ``topk_group`` groups that score highest stay, and
    the ``top_k`` are chosen among THEIR experts alone (every other expert's
    ``score + bias`` reads ``-inf`` for the choice). The gates are still the
    scores without the bias. ``n_group`` 1 is no group stage at all: the code
    before it, bit for bit."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )  # [T, E]
    if router_noise > 0.0 and rng is not None:
        logits = logits + router_noise * jax.random.normal(rng, logits.shape)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got {scoring!r}")
    if n_group > 1:
        with jax.named_scope("moe.groups"):
            choice = probs if bias is None else probs + bias.astype(jnp.float32)
            T, E = choice.shape
            best_two, _ = jax.lax.top_k(choice.reshape(T, n_group, E // n_group), 2)
            _, groups = jax.lax.top_k(best_two.sum(axis=-1), topk_group)  # [T, topk_group]
            stays = (groups[:, :, None] == jnp.arange(n_group, dtype=groups.dtype)).any(axis=1)
            choice = jnp.where(jnp.repeat(stays, E // n_group, axis=1), choice, -jnp.inf)
        _, experts = jax.lax.top_k(choice, top_k)
        gates = jnp.take_along_axis(probs, experts, axis=-1)
    elif bias is None:
        gates, experts = jax.lax.top_k(probs, top_k)  # [T, k]
    else:
        _, experts = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalize:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        gates = gates * scale
    return gates, experts.astype(jnp.int32), probs


def load_balance_loss(probs: jnp.ndarray, experts: jnp.ndarray) -> jnp.ndarray:
    """Switch load-balance aux loss ``E * sum_e f_e * p_e``: mean router
    probability per expert times the fraction of tokens whose FIRST choice
    it is."""
    E = probs.shape[-1]
    first = jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32).mean(axis=0)
    return E * jnp.sum(probs.mean(axis=0) * first)


def grouped_matmul(
    xs: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray, layer: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """``xs [m, k]`` (rows sorted by group) times ``w [g, k, n]``, each row
    against its group's matrix → ``[m, n]``; ``group_sizes [g]`` int32 may
    sum to less than ``m`` (what the rows behind the last group hold is
    unspecified).

    ``w`` may also be a STACK of layers ``[L, g, k, n]`` with ``layer`` (an
    int32 scalar, traced under a ``lax.scan`` over the layers): the rows go
    against ``w[layer]``, read where it lies in the stack. A Pallas call
    wants a whole operand, so a scan that hands it a slice of its ``xs``
    first COPIES the slice out (``dynamic-slice_bitcast_fusion``: 0.47 GB a
    matrix a layer at GigaChat3.1's widths, 2.3 x the time of the matmuls
    it fed; PERF.md, PR 43). Here the operand is the stack itself, seen as
    ``[L g, k, n]`` (its leading dimensions merged: a bitcast), under group
    sizes of length ``L g`` that are zero outside the layer's own ``g``:
    ``gmm`` visits no tile of an empty group, so the kernel reads what it
    read of the slice, at the same tiling.

    On a TPU: the Pallas grouped matmul ``megablox.gmm`` (device operations
    ``gmm.N``) at the tiling read on the chip at OLMoE's widths (PERF.md,
    PR 27): against ``jax.lax.ragged_dot``'s own Mosaic kernel 1.09 against
    1.63 ms a layer's three matmuls at 4 rows an expert and 1.86 against
    3.15 ms at 128, where an ``m`` tile of 256 beat 128 (2.08). ``gmm``
    wants ``m`` in whole tiles: the rows are padded up to one (behind the
    last group, in none), so every bucket runs the one kernel (64 rows,
    one matmul: 0.36 against 0.42 ms). Elsewhere,
    and for widths that are not whole lanes: ``jax.lax.ragged_dot`` (on
    ``w[layer]`` of a stack)."""
    m, k = xs.shape
    n = w.shape[-1]
    if jax.default_backend() == "tpu" and k % 128 == 0 and n % 128 == 0:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        if w.ndim == 4:
            L, g = w.shape[:2]
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((L * g,), group_sizes.dtype), group_sizes, (layer * g,)
            )
            w = w.reshape(L * g, k, n)
        tm = 256 if m >= 4096 else 128
        out = gmm(
            jnp.pad(xs, ((0, -m % tm), (0, 0))), w, group_sizes,
            preferred_element_type=xs.dtype, tiling=(tm, min(k, 1024), min(n, 1024)),
        )
        return out[:m]
    if w.ndim == 4:
        w = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
    return jax.lax.ragged_dot(xs, w, group_sizes)


def dropless_moe_ffn(
    params: Dict[str, Any],
    x: jnp.ndarray,
    *,
    top_k: int,
    renormalize: bool,
    valid: Optional[jnp.ndarray] = None,
    scoring: str = "softmax",
    scale: float = 1.0,
    held: Optional[Tuple[int, int]] = None,
    n_group: int = 1,
    topk_group: int = 1,
    layer: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: [T, d] → (out [T, d], aux) with every valid row through all
    ``top_k`` of its experts: none dropped, no capacity.

    ``layer``: where ``w_gate`` / ``w_up`` / ``w_down`` are the STACKS of a
    scanned model's layers (``[L, g, k, n]``), the layer whose experts these
    are (:func:`grouped_matmul` reads them in place). The router and its
    bias are the layer's own, as ever.

    ``valid``: [T] bool, absent = all. A row that is not valid is in no
    expert's group (its assignments sort behind the last group), costs no
    expert FLOPs beyond the grouped matmul's own tile padding, comes back
    as zeros and is not counted. ``scoring``, ``scale``, ``n_group`` /
    ``topk_group`` and a ``params["router_bias"]`` [E], where there is one:
    as in :func:`route`.

    ``held = (lo, hi)``: this process holds the experts ``lo <= e < hi`` of
    the ``E`` the router chooses among (one chip's share of an
    expert-parallel deployment), and ``w_gate`` / ``w_up`` / ``w_down``
    stack those ``hi - lo`` alone. Routing is over all ``E``; an assignment
    to an absent expert is in no group, like a padding row's, and adds
    nothing: the output is THIS share's part of the layer, and the shares
    of all ranges sum to the whole layer. Absent = all ``E`` held.

    ``aux``: ``load`` [E] int32 (assignments of the valid rows per expert,
    over ALL ``E``; sums to ``valid.sum() * top_k``), ``aux_loss`` (Switch
    load-balance loss over ALL rows' routing: a training regulariser, and
    training has no padding rows) and, with a router bias, ``bias_changed``
    (int32: valid rows whose kept set differs from the ``top_k`` of the
    scores alone) and, under a group limit (``n_group`` > 1),
    ``group_changed`` (int32: valid rows whose kept set differs from the
    plain ``top_k`` of ``score + bias``: the limit ENGAGED for them) beside
    ``routed_rows`` (int32: the valid rows, what that is a share of)."""
    T, d = x.shape
    E = params["router"].shape[1]
    bias = params.get("router_bias")
    with jax.named_scope("moe.route"):
        gates, experts, probs = route(
            params["router"], x, top_k=top_k, renormalize=renormalize,
            scoring=scoring, bias=bias, scale=scale, n_group=n_group, topk_group=topk_group,
        )
    with jax.named_scope("moe.dispatch"):
        flat = experts.reshape(T * top_k)
        if valid is not None:
            # expert id E: behind every group, in none
            flat = jnp.where(jnp.repeat(valid, top_k), flat, E)
        lo, hi = held if held is not None else (0, E)
        # the held experts' groups are numbered from 0; an assignment to an
        # absent expert sorts behind them all, as a padding row's does
        group = flat if held is None else jnp.where((flat >= lo) & (flat < hi), flat - lo, hi - lo)
        order = jnp.argsort(group, stable=True)  # assignment ids, by expert
        load = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
        sizes = load if held is None else load[lo:hi]
        xs = x[order // top_k]  # [T*k, d]: each token's row, once per expert
    with jax.named_scope("moe.experts"):
        gate = grouped_matmul(xs, params["w_gate"], sizes, layer)
        up = grouped_matmul(xs, params["w_up"], sizes, layer)
        ys = grouped_matmul(jax.nn.silu(gate) * up, params["w_down"], sizes, layer)
        if held is not None:
            # what the grouped matmul leaves behind the last group is
            # unspecified: an absent expert's assignment adds exactly nothing
            ys = jnp.where((jnp.arange(T * top_k) < sizes.sum())[:, None], ys, 0)
    with jax.named_scope("moe.combine"):
        y = jnp.zeros_like(ys).at[order].set(ys).reshape(T, top_k, d)
        out = jnp.einsum(
            "tkd,tk->td", y, gates.astype(x.dtype),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        if valid is not None:
            # all k assignments of a padding row lie behind the last group:
            # whatever the grouped matmul left there is replaced, not scaled
            out = jnp.where(valid[:, None], out, 0)
    aux = {"load": load, "aux_loss": load_balance_loss(probs, experts)}

    def differs_from_top_k_of(scores):
        # the kept are the top_k of ``scores`` unless an expert that was not
        # kept scores above the lowest kept: no second top_k
        kept = (experts[:, :, None] == jnp.arange(E, dtype=experts.dtype)).any(axis=1)
        lowest_kept = jnp.take_along_axis(scores, experts, axis=-1).min(axis=-1)
        changed = jnp.where(kept, -jnp.inf, scores).max(axis=-1) > lowest_kept
        if valid is not None:
            changed = changed & valid
        return changed.sum().astype(jnp.int32)

    if bias is not None:
        aux["bias_changed"] = differs_from_top_k_of(probs)
    if n_group > 1:
        aux["group_changed"] = differs_from_top_k_of(
            probs if bias is None else probs + bias.astype(jnp.float32)
        )
        aux["routed_rows"] = (
            jnp.int32(T) if valid is None else valid.sum().astype(jnp.int32)
        )
    return out, aux


def gated_mlp(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray):
    """The gated SiLU MLP every row goes through: a dense FFN, or the
    SHARED expert beside routed ones (computed alike on every chip of an
    expert-parallel deployment, so counted once when the shares are summed)."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


#: logical axes of a dense gated MLP's three matrices, and of a stack of HELD
#: experts' (they stay whole on each device: an ``expert`` mesh axis is the
#: deployment's, of which this process is one rank; :func:`moe_logical_axes`
#: is the expert-PARALLEL path's)
DENSE_AXES = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
MOE_AXES = {"w_gate": (None, "embed", "mlp"), "w_up": (None, "embed", "mlp"),
            "w_down": (None, "mlp", "embed")}


def routed_ffn(
    p: Dict[str, Any], h: jnp.ndarray, valid: Optional[jnp.ndarray], *, top_k: int, scale: float,
    held: Tuple[int, int], shared: bool, n_group: int = 1, topk_group: int = 1,
    layer: Optional[jnp.ndarray] = None,
):
    """The SIGMOID-routed expert FFN of one layer on normed activations ``h
    [B, C, D]``: ``(ffn(h), aux)``. This process's part of the routed experts
    (:func:`dropless_moe_ffn` told the range ``held`` of them it holds, unless
    that is all: sigmoid scores, the choice with ``p["router_bias"]``, gates
    normalised over the kept and scaled by ``scale``) and, ``shared``, the
    shared expert on every row beside them (``p["shared_gate" | "shared_up" |
    "shared_down"]``). ``valid [B, C]`` marks the real rows of a padded
    serving step; ``aux``: ``load [E]``, ``bias_changed``, ``aux_loss`` (and
    the group limit's). ``layer``: ``p``'s three expert matrices are the
    STACKS of a scanned group and this is the layer's index in them."""
    if tuple(held) == (0, p["router"].shape[1]):
        held = None
    if shared:
        with jax.named_scope("moe.shared"):
            out = gated_mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    experts = {k: p[k] for k in ("router", "router_bias", "w_gate", "w_up", "w_down")}
    routed, aux = dropless_moe_ffn(
        experts, h.reshape(-1, h.shape[-1]), top_k=top_k, renormalize=True,
        valid=None if valid is None else valid.reshape(-1),
        scoring="sigmoid", scale=scale, held=held, n_group=n_group, topk_group=topk_group, layer=layer,
    )
    routed = routed.reshape(h.shape)
    return (out + routed if shared else routed), aux


def moe_ffn(
    params: Dict[str, Any],
    x: jnp.ndarray,
    *,
    top_k: int = 2,
    renormalize: bool = True,
    capacity_factor: float = 1.25,
    router_noise: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The expert-parallel path. x: [B, S, d] → (out [B, S, d], aux dict
    with load-balance loss).

    Dense dispatch: one-hot [T, E, C] tensors route tokens to expert
    slots; dropped (over-capacity) tokens contribute zero and fall back
    to the residual stream. ``renormalize``: as in :func:`route`."""
    B, S, d = x.shape
    E = params["router"].shape[1]
    T = B * S
    # GShard capacity: expected per-expert load is top_k*T/E assignments
    # under balanced routing — omitting top_k would silently drop
    # ~(1 - cf/top_k) of dispatches from step 0
    capacity = max(1, int(math.ceil(top_k * T / E * capacity_factor)))

    xt = x.reshape(T, d)
    gate_vals, expert_idx, probs = route(
        params["router"], xt, top_k=top_k, renormalize=renormalize,
        router_noise=router_noise, rng=rng,
    )

    # per-(token, choice) slot position within the chosen expert, by
    # arrival order: cumsum of one-hot over the flattened (T*k) axis
    choice_onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # [T, k, E]
    flat_choice = choice_onehot.reshape(T * top_k, E)
    positions = jnp.cumsum(flat_choice, axis=0) - flat_choice  # slots before me
    slot = (positions * flat_choice).sum(-1).reshape(T, top_k)  # [T, k]
    kept = slot < capacity

    gate_vals = gate_vals * kept.astype(gate_vals.dtype)

    # dispatch [T, E, C] (bool) and combine [T, E, C] (weighted)
    slot_onehot = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)  # [T, k, C]
    disp = jnp.einsum("tke,tkc->tec", choice_onehot.astype(jnp.float32), slot_onehot)
    combine = jnp.einsum("tk,tke,tkc->tec", gate_vals, choice_onehot.astype(jnp.float32), slot_onehot)

    # route tokens to expert slots: [E, C, d]
    expert_in = jnp.einsum("tec,td->ecd", disp, xt.astype(jnp.float32)).astype(x.dtype)

    # expert FFN batched over E (sharded over the expert mesh axis)
    h_gate = jnp.einsum("ecd,edh->ech", expert_in, params["w_gate"])
    h_up = jnp.einsum("ecd,edh->ech", expert_in, params["w_up"])
    expert_out = jnp.einsum("ech,ehd->ecd", jax.nn.silu(h_gate) * h_up, params["w_down"])

    out = jnp.einsum("tec,ecd->td", combine, expert_out.astype(jnp.float32))
    out = out.reshape(B, S, d).astype(x.dtype)
    return out, {
        "aux_loss": load_balance_loss(probs, expert_idx),
        "dropped_fraction": 1.0 - kept.astype(jnp.float32).mean(),
    }
