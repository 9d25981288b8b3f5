"""The §Perf optimization variants must be math-preserving: same loss and
same updated params as the baseline on a tiny model (single-device mesh —
shardings degenerate but every code path still executes)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro.config import ModelConfig, ParallelConfig, TrainConfig
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.sharding import make_rules
from repro.train.optimizer import adamw_init
from repro.train.train_step import compile_train_step


def _step_result(cfg: ModelConfig, parallel: ParallelConfig):
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg, parallel, make_rules(mesh, parallel))
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 16)
    fn, p_sh, o_sh, b_sh = compile_train_step(
        model, TrainConfig(global_batch=2, seq_len=16), mesh, parallel,
        donate=False)
    with mesh:
        p2, o2, metrics = fn(params, adamw_init(params), batch)
    return p2, float(metrics["loss"])


@pytest.mark.parametrize("variant", [
    dict(shard_model_axes=False, sequence_parallel=False),   # fsdp2d
    dict(grad_dtype="bfloat16"),                             # bf16 grads
    dict(zero="zero1"),
    dict(remat="full"),
])
def test_variant_preserves_math(tiny_cfg, variant):
    cfg = dataclasses.replace(tiny_cfg, dtype="float32")
    base = ParallelConfig(remat="none", moe_impl="dense")
    p_base, l_base = _step_result(cfg, base)
    p_var, l_var = _step_result(cfg, dataclasses.replace(base, **variant))
    # bf16 grads evaluate the forward on the bf16 view of the params, so a
    # float32-dtype model sees bf16-rounding-level shifts
    tol = 2e-2 if variant.get("grad_dtype") == "bfloat16" else 1e-5
    assert abs(l_var - l_base) < tol
    for a, b in zip(jax.tree_util.tree_leaves(p_base),
                    jax.tree_util.tree_leaves(p_var)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)
