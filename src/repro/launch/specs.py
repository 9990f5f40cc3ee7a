"""Abstract train state and its shardings (ShapeDtypeStruct only —
weak-type-correct, shardable, zero device allocation)."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import RunConfig
from repro.optim.api import Optimizer
from repro.sharding.auto import shardings_for
from repro.sharding.specs import AxisRules
from repro.train.state import TrainState


def abstract_state(model, optimizer: Optimizer, run_cfg: RunConfig) -> Any:
    from repro.train.state import init_state
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(
        lambda r: init_state(model, optimizer, r, run_cfg), rng)


def param_shardings(model, params_sds, rules: AxisRules, mesh: Mesh) -> Any:
    return shardings_for(params_sds, model.param_axes(), rules, mesh)


def _spec_of(sh) -> P:
    return sh.spec if hasattr(sh, "spec") else sh


def state_shardings(model, optimizer: Optimizer, run_cfg: RunConfig,
                    state_sds: TrainState, p_shardings, mesh: Mesh
                    ) -> TrainState:
    """Optimizer/compression states inherit param sharding by shape
    matching: equal shape -> same spec; shape[:-1] (adafactor row) ->
    spec[:-1]; shape[:-2]+[-1] (adafactor col) -> spec minus that dim;
    anything else -> replicated."""
    p_sds = state_sds.params

    def derive(p_shape, spec, s_shape):
        spec_t = tuple(_spec_of(spec)) + (None,) * (
            len(p_shape) - len(tuple(_spec_of(spec))))
        if s_shape == p_shape:
            return P(*spec_t)
        if s_shape == p_shape[:-1]:
            return P(*spec_t[:-1])
        if len(p_shape) >= 2 and s_shape == p_shape[:-2] + p_shape[-1:]:
            return P(*(spec_t[:-2] + spec_t[-1:]))
        return P()

    def map_state_field(field):
        return jax.tree.map(
            lambda p, sp, s: NamedSharding(
                mesh, derive(p.shape, sp, s.shape)),
            p_sds, p_shardings, field)

    opt = state_sds.opt_state
    new_opt = type(opt)(*[
        (map_state_field(f) if isinstance(f, dict)
         else NamedSharding(mesh, P()))
        for f in opt])
    comp = state_sds.comp_state
    new_comp = (type(comp)(map_state_field(comp.residual))
                if comp != () else ())
    return TrainState(
        params=p_shardings,
        opt_state=new_opt,
        comp_state=new_comp,
        step=NamedSharding(mesh, P()),
    )
