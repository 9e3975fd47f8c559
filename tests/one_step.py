"""The amp-O2 train step the telemetry and checkpoint zero-cost tests
trace: bf16 fwd/bwd, dynamic loss scaling, fused Adam, skip-step
selects, and the trace-time telemetry branch."""

import jax
import jax.numpy as jnp

from apex_tpu import telemetry
from apex_tpu.optimizers import grad_norm_stats


def make_one_step(model, scaler, tx):
    """``one_step(params, opt_state, scaler_state, ids, pos, labels) ->
    (params, opt_state, scaler_state, loss, aux)`` where ``aux`` is None
    (an empty pytree — adds nothing to the compiled program) with
    telemetry disabled, else the in-step scalar dict (loss / loss_scale
    / overflow / unskipped / grad_norm / grad_max) that rides a training
    scan's stacked outputs."""

    def one_step(params, opt_state, scaler_state, ids, pos, labels):
        def loss_fn(p):
            per_tok = model.apply({"params": p}, ids, pos, None, labels)
            return jnp.mean(per_tok) * scaler_state.loss_scale

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, found_inf = scaler.unscale(grads, scaler_state)
        new_scaler_state = scaler.update(scaler_state, found_inf)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        new_params = jax.tree_util.tree_map(
            lambda p, u: jnp.where(found_inf, p, p + u.astype(p.dtype)),
            params, updates)
        new_opt_state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(found_inf, old, new),
            new_opt_state, opt_state)
        unscaled_loss = loss / scaler_state.loss_scale
        aux = None
        if telemetry.enabled():  # trace-time branch: disabled is free
            aux = telemetry.collect(
                None, loss=unscaled_loss,
                **scaler.metrics(new_scaler_state),
                **grad_norm_stats(grads))
        return (new_params, new_opt_state, new_scaler_state,
                unscaled_loss, aux)

    return one_step
