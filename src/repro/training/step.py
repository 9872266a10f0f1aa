"""Train-step factory: loss -> grads -> (optional compressed psum) -> opt.

The returned step is a pure function ``(state, batch) -> (state, metrics)``
suitable for jit/pjit with shardings from ``distributed.sharding``.
Microbatching (gradient accumulation) runs as a lax.scan over microbatch
slices; remat policy is forwarded into the layer scan.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import model as MODEL
from repro.training import optimizer as OPT
from repro.training.grad_compression import compressed_psum, init_error_feedback


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: OPT.OptConfig = OPT.OptConfig()
    microbatches: int = 1           # gradient-accumulation steps
    remat: bool = True              # checkpoint layer bodies
    grad_compression: bool = False  # int8 DP all-reduce w/ error feedback
    dp_axes: tuple[str, ...] = ("data",)


def init_train_state(cfg: ArchConfig, tcfg: TrainConfig, key) -> dict[str, Any]:
    params = MODEL.init_params(cfg, key)
    state = {
        "params": params,
        "opt": OPT.opt_init(params, tcfg.opt, cfg.opt_state_dtype),
    }
    if tcfg.grad_compression:
        state["err"] = init_error_feedback(params)
    return state


def abstract_train_state(cfg: ArchConfig, tcfg: TrainConfig):
    """ShapeDtypeStruct train state (dry-run: no allocation)."""
    return jax.eval_shape(
        lambda k: init_train_state(cfg, tcfg, k), jax.random.PRNGKey(0)
    )


def _split_micro(batch, n: int):
    """(B, ...) -> (n, B/n, ...) for every leaf."""
    def f(x):
        B = x.shape[0]
        return x.reshape(n, B // n, *x.shape[1:])
    return jax.tree.map(f, batch)


def make_train_step(cfg: ArchConfig, tcfg: TrainConfig) -> Callable:
    cast = jnp.dtype(cfg.dtype)

    def loss_of(params, mb):
        compute_params = jax.tree.map(
            lambda p: p.astype(cast) if p.dtype in (jnp.float32, jnp.bfloat16) else p,
            params,
        )
        return MODEL.loss_fn(compute_params, cfg, mb, remat=tcfg.remat)

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def train_step(state, batch):
        params = state["params"]

        if tcfg.microbatches > 1:
            micro = _split_micro(batch, tcfg.microbatches)

            def acc(carry, mb):
                gsum, lsum = carry
                (loss, metrics), grads = grad_fn(params, mb)
                gsum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), gsum, grads)
                return (gsum, lsum + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), metrics = jax.lax.scan(acc, (g0, jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / tcfg.microbatches, gsum)
            loss = lsum / tcfg.microbatches
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        else:
            (loss, metrics), grads = grad_fn(params, batch)

        if tcfg.grad_compression:
            grads, new_err = compressed_psum(grads, state["err"], tcfg.dp_axes)

        new_params, new_opt, opt_metrics = OPT.opt_update(
            params, grads, state["opt"], tcfg.opt
        )
        new_state = {"params": new_params, "opt": new_opt}
        if tcfg.grad_compression:
            new_state["err"] = new_err
        return new_state, {"loss": loss, **metrics, **opt_metrics}

    return train_step


def make_dp_train_step(cfg: ArchConfig, tcfg: TrainConfig, mesh, batch_template):
    """Explicit data-parallel step under shard_map — required for the int8
    compressed all-reduce (named axes).  Params/opt are replicated; the
    batch is sharded over the DP axes; the per-rank error-feedback buffers
    carry a leading DP axis and stay device-local.

    Signature of the returned fn: (state, err, batch) -> (state, err, metrics)
    where err leaves are (n_dp, *param_shape) sharded on the DP axis.
    """
    from jax.sharding import PartitionSpec as P

    assert tcfg.grad_compression, "use make_train_step for the uncompressed path"
    cast = jnp.dtype(cfg.dtype)

    def loss_of(params, mb):
        compute_params = jax.tree.map(lambda p: p.astype(cast), params)
        return MODEL.loss_fn(compute_params, cfg, mb, remat=tcfg.remat)

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def local_step(state, err, batch):
        params = state["params"]
        err_local = jax.tree.map(lambda e: e[0], err)
        (loss, metrics), grads = grad_fn(params, batch)
        grads, new_err = compressed_psum(grads, err_local, tcfg.dp_axes)
        new_params, new_opt, opt_metrics = OPT.opt_update(
            params, grads, state["opt"], tcfg.opt
        )
        metrics = {"loss": loss, **metrics, **opt_metrics}
        metrics = jax.tree.map(
            lambda m: jax.lax.pmean(m, tcfg.dp_axes)
            if jnp.issubdtype(jnp.asarray(m).dtype, jnp.floating)
            else jax.lax.psum(m, tcfg.dp_axes),
            metrics,
        )
        return (
            {"params": new_params, "opt": new_opt},
            jax.tree.map(lambda e: e[None], new_err),
            metrics,
        )

    dp = P(tcfg.dp_axes)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), dp, jax.tree.map(lambda _: dp, batch_template)),
        out_specs=(P(), dp, P()),
        check_vma=False,
    )
    return jax.jit(fn)


def init_dp_error_feedback(cfg: ArchConfig, params, n_dp: int):
    """(n_dp, *shape) error-feedback buffers for make_dp_train_step."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_dp,) + p.shape, jnp.bfloat16), params
    )
