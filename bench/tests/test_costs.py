"""The jaxpr counter agrees with the analytic counts at the cells' shapes
(shapes only: nothing is allocated or run)."""
import jax
import jax.numpy as jnp
import pytest

from bench import common, costs
from bench.reference import lm as ref


def _program(cell):
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(cell.config["arch"]).replace(**cell.config.get("overrides", {}))
    model = build_model(cfg)
    return cfg, model, jax.eval_shape(model.init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("cell", ["granite-4l.silo-train"])
def test_train_flops(cell):
    from repro.optim import adamw
    from repro.training.trainer import make_train_step

    c = common.load_cell(cell)
    _, model, params = _program(c)
    opt = adamw(1e-4)
    step = make_train_step(lambda p, b, r: model.loss(p, b), opt)
    B, S = c.traffic["batch"], c.traffic["seq_len"]
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "labels")}
    counted = costs.jaxpr_costs(step, params, jax.eval_shape(opt.init, params), batch,
                                jax.ShapeDtypeStruct((2,), jnp.uint32)).flops
    assert counted == pytest.approx(costs.train_flops(ref.Arch(c.config), B, S), rel=0.01)
