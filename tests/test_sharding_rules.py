"""Unified partition rules end-to-end (ISSUE 14).

``match_partition_rules`` units, numerics parity of the constrained
fwd/bwd/optimizer step against the unconstrained single-chip reference,
zero post-warmup recompiles for the constrained step, backward-block
parity against the XLA attention grad, and the involuntary-remat
tripwire's stderr capture. All pure-jax on the virtual CPU mesh — no
cluster, no warmup (tier-1 CAUTION: the suite saturates its cap)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models.llama import (
    LlamaConfig,
    batch_sharding,
    init_params,
    make_train_step,
    next_token_loss,
    param_shardings,
    partition_rules,
)
from ray_tpu.parallel.mesh import MeshSpec, cpu_mesh_devices, make_mesh
from ray_tpu.parallel.sharding import (
    match_partition_rules,
    tp_rules,
    tree_path_names,
)


# -- match_partition_rules units ------------------------------------------


def test_match_rules_scalar_skip_and_match():
    tree = {
        "layers": [{"wq": np.zeros((4, 8)), "count": np.zeros(())}],
        "one": np.zeros((1,)),
    }
    specs = match_partition_rules([(r"wq$", P("fsdp", "tensor"))], tree)
    assert specs["layers"][0]["wq"] == P("fsdp", "tensor")
    # scalar and single-element leaves never consult the rules
    assert specs["layers"][0]["count"] == P()
    assert specs["one"] == P()


def test_match_rules_no_rule_found_raises():
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules([(r"wq$", P())], {"wz": np.zeros((4, 4))})


def test_match_rules_override_precedence_first_wins():
    tree = {"a": {"wq": np.zeros((4, 8))}, "b": {"wq": np.zeros((4, 8))}}
    # override in FRONT: the targeted path diverges, the generic rule
    # still covers the rest
    specs = match_partition_rules(
        [(r"a/wq$", P("tensor", None)), (r"wq$", P("fsdp", None))], tree
    )
    assert specs["a"]["wq"] == P("tensor", None)
    assert specs["b"]["wq"] == P("fsdp", None)
    # generic rule first: it shadows the targeted one entirely
    specs = match_partition_rules(
        [(r"wq$", P("fsdp", None)), (r"a/wq$", P("tensor", None))], tree
    )
    assert specs["a"]["wq"] == P("fsdp", None)


def test_match_rules_rank_reduced_leaf_replicates():
    """A matched spec LONGER than the leaf's rank (adafactor v_row/v_col,
    SM3 diagonals — rank-reduced mirrors named after their 2-D param)
    replicates instead of raising or mis-applying the param's spec."""
    tree = {"v_row": {"wq": np.zeros((8,))}, "full": {"wq": np.zeros((8, 4))}}
    specs = match_partition_rules([(r"wq$", P("fsdp", "tensor"))], tree)
    assert specs["v_row"]["wq"] == P()
    assert specs["full"]["wq"] == P("fsdp", "tensor")


def test_init_sharded_factored_optimizer_state():
    """init_sharded survives a rank-reducing optimizer: factored adafactor
    stats don't mirror param shapes, so the suffix-matched param spec is
    inapplicable to them — they init replicated and the constrained step
    still runs (the reproduction from the ISSUE-14 review pass)."""
    from ray_tpu.models.llama import init_sharded

    cfg = LlamaConfig.tiny()
    mesh = make_mesh(MeshSpec(data=2, fsdp=2, tensor=2), cpu_mesh_devices(8))
    opt = optax.adafactor(1e-3, min_dim_size_to_factor=2)

    # the v_(row|col) NAME rule replicates every factored stat — the
    # rank-length backstop alone can't: wq's stripped rank-2 spec would
    # otherwise "fit" its rank-2 v_row and shard the wrong dims
    specs = match_partition_rules(
        partition_rules(cfg, tp_rules()), opt.init(init_params(cfg, jax.random.PRNGKey(0)))
    )
    names = tree_path_names(specs)
    factored = {
        n: s
        for n, s in zip(names, jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)))
        if "/v_row/" in n or "/v_col/" in n
    }
    assert factored and all(s == P() for s in factored.values()), factored

    params, opt_state = init_sharded(
        cfg, mesh, tp_rules(), jax.random.PRNGKey(0), opt
    )
    # same-seed parity of sharded init vs the eager single-chip
    # reference: both run partitionable threefry, so values are
    # bit-identical whatever the mesh
    ref = init_params(cfg, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(
        np.asarray(params["embed"]), np.asarray(ref["embed"])
    )
    step = make_train_step(
        cfg, opt, donate=False, mesh=mesh, rules=tp_rules(), remat="selective"
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size, jnp.int32
    )
    batch = jax.device_put(
        {"tokens": tokens, "targets": tokens}, batch_sharding(mesh, tp_rules())
    )
    (_, _), loss = step((params, opt_state), batch)
    assert np.isfinite(float(loss))


def test_llama_rules_cover_params_grads_and_opt_state():
    """One regex table covers the param tree AND the optax state (mu/nu
    mirror params, so the same suffixes match; scalar count is skipped)."""
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = optax.adamw(1e-3).init(params)
    prules = partition_rules(cfg, tp_rules())
    specs_p = match_partition_rules(prules, params)  # raises on any gap
    specs_o = match_partition_rules(prules, opt_state)
    # the mirrored wq leaf landed on the identical spec
    names = tree_path_names(specs_o)
    leaves = jax.tree_util.tree_leaves(
        specs_o, is_leaf=lambda x: isinstance(x, P)
    )
    wq_specs = {n: s for n, s in zip(names, leaves) if n.endswith("wq")}
    assert wq_specs, names[:8]
    for spec in wq_specs.values():
        assert spec == specs_p["layers"][0]["wq"]


# -- constrained step: numerics parity + zero recompiles ------------------


def test_constrained_step_matches_unconstrained_reference():
    """The unified (rules-constrained, selective-remat) step on the 8-dev
    CPU mesh produces the same losses as the unconstrained single-device
    step on identical params/batch — the constraints move shardings, not
    values. Also asserts zero post-warmup recompiles for the constrained
    step (the jit cache stays at one entry across repeat steps)."""
    cfg = LlamaConfig.tiny()
    opt = optax.adamw(1e-3)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size, jnp.int32
    )
    batch = {"tokens": tokens, "targets": tokens}

    ref_step = make_train_step(cfg, opt, donate=False)
    ref_state = (params, opt.init(params))
    ref_losses = []
    for _ in range(3):
        ref_state, loss = ref_step(ref_state, batch)
        ref_losses.append(float(loss))

    mesh = make_mesh(MeshSpec(data=2, fsdp=2, tensor=2), cpu_mesh_devices(8))
    rules = tp_rules()
    sharded = jax.device_put(params, param_shardings(cfg, mesh, rules))
    bd = jax.device_put(batch, batch_sharding(mesh, rules))
    con_step = make_train_step(
        cfg, opt, donate=False, mesh=mesh, rules=rules, remat="selective"
    )
    # optimizer state pinned to the same matched table the step emits —
    # the zero-recompile assertion below depends on it
    from jax.sharding import NamedSharding

    ospecs = match_partition_rules(partition_rules(cfg, rules), opt.init(params))
    con_opt = jax.device_put(
        opt.init(params),
        jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), ospecs,
            is_leaf=lambda x: isinstance(x, P),
        ),
    )
    con_state = (sharded, con_opt)
    con_losses = []
    for _ in range(3):
        con_state, loss = con_step(con_state, bd)
        con_losses.append(float(loss))

    np.testing.assert_allclose(ref_losses, con_losses, rtol=2e-4)
    assert con_step._cache_size() == 1, (
        f"constrained step recompiled after warmup: "
        f"{con_step._cache_size()} cache entries"
    )


def test_selective_remat_matches_no_remat():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab_size
    )
    l0 = next_token_loss(cfg, params, tokens, tokens, remat=False)
    l1 = next_token_loss(cfg, params, tokens, tokens, remat="selective")
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)


def test_remat_rejects_unknown_mode():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="remat"):
        next_token_loss(cfg, params, tokens, tokens, remat="bogus")


# -- backward block tuning ------------------------------------------------


def test_backward_blocks_parity_vs_xla_grad():
    """The Pallas backward running DIFFERENT (tuned) blocks than the
    forward still matches the XLA attention gradient, GQA included."""
    from ray_tpu.ops.attention import flash_attention, reference_attention

    b, h, hk, s, d = 1, 4, 2, 256, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hk, s, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hk, s, d))

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, impl="pallas",
            block_q=128, block_k=128, block_q_bwd=256, block_k_bwd=128,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        rep = h // hk
        out = reference_attention(
            q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
            causal=True,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b2 in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2), atol=5e-5)


def test_default_bwd_blocks_bucket_table():
    from ray_tpu.ops.attention import default_bwd_blocks

    assert default_bwd_blocks(512) == (256, 512)
    assert default_bwd_blocks(2048) == (256, 1024)
    assert default_bwd_blocks(16384) == (128, 1024)
    # every bucket choice divides its bucket bound (usable as-is)
    for bound, (bq, bk) in [(1024, default_bwd_blocks(1024)),
                            (2048, default_bwd_blocks(2048)),
                            (8192, default_bwd_blocks(8192))]:
        assert bound % bq == 0 and bound % bk == 0


# -- involuntary-remat tripwire -------------------------------------------


def test_tripwire_capture_counts_and_replays():
    """The dryrun's fd-level stderr capture counts involuntary-remat
    lines written by C++ (bypassing sys.stderr) and replays the bytes."""
    spec = importlib.util.spec_from_file_location(
        "_graft_entry_for_test",
        os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    import sys

    # write to whatever fd sys.stderr maps to (pytest's fd capture
    # remaps it; in the real dryrun it IS fd 2 — where XLA's C++ writes)
    fd = sys.stderr.fileno()
    counts: list = []
    with mod._capture_xla_stderr(counts):
        os.write(
            fd,
            b"W0000 [SPMD] Involuntary full rematerialization. blah\n"
            b"other line\n"
            b"E0000 [spmd] Involuntary full rematerialization. again\n",
        )
    assert counts == [2]
    counts2: list = []
    with mod._capture_xla_stderr(counts2):
        os.write(fd, b"nothing to see\n")
    assert counts2 == [0]
