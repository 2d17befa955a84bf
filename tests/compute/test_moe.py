"""Sparse MoE model: routing invariants, expert-parallel training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import moe, train
from dstack_tpu.models.moe import MoEConfig


def test_route_respects_topk_and_capacity():
    t, e, k, cap = 16, 4, 2, 5
    logits = jax.random.normal(jax.random.PRNGKey(0), (t, e))
    dispatch, combine, aux = moe._route(logits, k, cap)
    assert dispatch.shape == (t, e, cap)
    # each token dispatched to at most k slots, each slot holds <= 1 token
    per_token = np.asarray(dispatch).sum(axis=(1, 2))
    assert (per_token <= k).all()
    per_slot = np.asarray(dispatch).sum(axis=0)
    assert (per_slot <= 1.0 + 1e-6).all()
    # combine weights live exactly where dispatch does and sum <= 1 per token
    c = np.asarray(combine)
    assert (c[np.asarray(dispatch) == 0] == 0).all()
    assert (c.sum(axis=(1, 2)) <= 1.0 + 1e-5).all()
    assert float(aux) > 0


def test_route_drops_tokens_over_capacity():
    # all tokens prefer expert 0 with capacity 2 -> only 2 fit
    t, e = 8, 4
    logits = jnp.tile(jnp.array([[10.0, 1.0, 0.0, -1.0]]), (t, 1))
    dispatch, _combine, _aux = moe._route(logits, 1, 2)
    assert float(dispatch[:, 0, :].sum()) == 2.0


def test_moe_forward_and_param_count():
    cfg = MoEConfig.tiny_moe()
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == cfg.num_params()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    logits = moe.forward(params, tokens, cfg)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert jnp.isfinite(logits).all()


@pytest.mark.slow
def test_moe_train_step_decreases_loss():
    cfg = MoEConfig.tiny_moe()
    opt = train.default_optimizer()
    state = moe.create_state(jax.random.PRNGKey(0), cfg, opt)
    step = moe.make_train_step(cfg, opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size)
    losses = []
    for _ in range(6):
        state, m = step(state, {"tokens": tokens})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert float(m["aux_loss"]) > 0


@pytest.mark.slow
def test_moe_expert_parallel_matches_unsharded(cpu_devices):
    """dcn=1 data=2, expert=2, tensor=2 mesh: expert-sharded training step
    produces the same loss as the single-device step."""
    from dstack_tpu.models.llama import ShardingPolicy
    from dstack_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = MoEConfig.tiny_moe()
    opt = train.default_optimizer()
    mesh = build_mesh(MeshSpec(data=2, expert=2, tensor=2), cpu_devices)
    policy = ShardingPolicy()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size)

    state = moe.create_state(jax.random.PRNGKey(0), cfg, opt, mesh, policy)
    step = moe.make_train_step(cfg, opt, mesh, policy)
    state, m = step(state, {"tokens": tokens})

    ref_state = moe.create_state(jax.random.PRNGKey(0), cfg, opt)
    ref_step = moe.make_train_step(cfg, opt)
    _, ref_m = ref_step(ref_state, {"tokens": tokens})
    assert abs(float(m["loss"]) - float(ref_m["loss"])) < 2e-2
    # expert weights really are sharded over the expert axis
    sharding = state.params["layers"]["w_gate"].sharding
    assert "expert" in (sharding.spec[1] or ())

def test_route_token_mask_excludes_pads():
    """Masked (padding) tokens claim no expert-capacity slots: real tokens
    route exactly as they would with no pads present (the serving engine's
    prefill relies on this — serving/dense.py _mlp_block)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dstack_tpu.models.moe import _route

    e, k, cap = 4, 2, 3
    real = jax.random.normal(jax.random.PRNGKey(0), (5, e))
    # identical pad rows, like bucket-padding's repeated token-0 embedding
    pads = jnp.tile(jax.random.normal(jax.random.PRNGKey(1), (1, e)), (27, 1))
    full = jnp.concatenate([real, pads], axis=0)
    mask = jnp.concatenate([jnp.ones(5), jnp.zeros(27)])

    d_ref, c_ref, _ = _route(real, k, cap)
    d_full, c_full, _ = _route(full, k, cap, token_mask=mask)
    np.testing.assert_array_equal(np.asarray(d_full[:5]), np.asarray(d_ref))
    np.testing.assert_allclose(np.asarray(c_full[:5]), np.asarray(c_ref))
    assert float(jnp.abs(d_full[5:]).sum()) == 0.0
    assert float(jnp.abs(c_full[5:]).sum()) == 0.0
