"""MoE execution paths: dense (exact) vs gshard / tp (capacity-based) vs
gather-decode, plus router invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import MoEConfig, ParallelConfig
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_lib
from repro.models.spec import init_params
from repro.sharding import make_rules


def _setup(E=4, top_k=2, d=32, eff=64, capacity_factor=8.0):
    cfg = MoEConfig(num_experts=E, top_k=top_k, expert_ff=eff,
                    capacity_factor=capacity_factor)
    specs = moe_lib.moe_specs(d, cfg, "silu_glu")
    params = init_params(specs, jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, d))
    return cfg, params, x


def test_gshard_matches_dense_with_ample_capacity():
    """With capacity >> tokens, the capacity-dispatch path is exact."""
    cfg, params, x = _setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(mesh, ParallelConfig())
    y_dense, aux_d = moe_lib.moe_dense(params, cfg, x, act="silu_glu",
                                       dtype=jnp.float32)
    with mesh:
        y_g, aux_g = moe_lib.moe_gshard(params, cfg, x, rules=rules,
                                        act="silu_glu", dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_g),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux_d), float(aux_g), rtol=1e-5)


def test_tp_matches_dense_with_ample_capacity():
    cfg, params, x = _setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(mesh, ParallelConfig())
    y_dense, _ = moe_lib.moe_dense(params, cfg, x, act="silu_glu",
                                   dtype=jnp.float32)
    with mesh:
        y_tp, _ = moe_lib.moe_tp(params, cfg, x, rules=rules,
                                 act="silu_glu", dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_tp),
                               rtol=2e-5, atol=2e-5)


def test_gather_decode_matches_dense():
    cfg, params, _ = _setup(E=8, top_k=2)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (1, 1, 32))
    y_dense, _ = moe_lib.moe_dense(params, cfg, x, act="silu_glu",
                                   dtype=jnp.float32)
    y_gather, _ = moe_lib.moe_gather_decode(params, cfg, x, act="silu_glu",
                                            dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_gather),
                               rtol=2e-5, atol=2e-5)


def test_capacity_drops_fall_through_to_residual():
    """Tokens beyond capacity produce zero output (residual passthrough),
    never garbage."""
    cfg, params, x = _setup(capacity_factor=0.05)   # almost everything drops
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = make_rules(mesh, ParallelConfig())
    with mesh:
        y, _ = moe_lib.moe_gshard(params, cfg, x, rules=rules,
                                  act="silu_glu", dtype=jnp.float32)
    assert bool(jnp.isfinite(y).all())
    # most rows zero
    norms = jnp.linalg.norm(y.reshape(-1, y.shape[-1]), axis=-1)
    assert float((norms == 0).mean()) > 0.5


def test_router_gates_normalized():
    cfg, params, x = _setup()
    gates, idx, probs = moe_lib._route(params["router"],
                                       x.reshape(-1, x.shape[-1]), cfg)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert int(idx.max()) < cfg.num_experts
    # aux loss is minimal (==1 scaled) for a perfectly uniform router
    E = cfg.num_experts
    uniform = jnp.full((64, E), 1.0 / E)
    idx_u = jnp.tile(jnp.arange(cfg.top_k), (64, 1))
    aux = moe_lib._aux_loss(uniform, idx_u, E)
    assert float(aux) >= 0.99
