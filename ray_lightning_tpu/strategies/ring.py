"""RingTPUStrategy: explicit per-rank collective scheduling (Horovod flavor).

Parity target: ``HorovodRayStrategy`` (/root/reference/ray_lightning/
ray_horovod.py:32-183), whose value over plain DDP is a *different
collective protocol* (Horovod's C++ ring-allreduce wrapping the optimizer).
On TPU the distinction is the programming model, not the wire protocol: this
strategy builds the step with ``shard_map`` — each device runs a per-rank
program on its local batch shard and gradients are averaged with an explicit
``lax.pmean`` over the "data" axis — instead of letting GSPMD infer the
collective from sharding propagation. The emitted ICI all-reduce is
identical in the common case; the explicit schedule is the escape hatch when
manual control over collective placement beats the partitioner.
"""
from __future__ import annotations

from typing import Any, Callable

from ray_lightning_tpu.strategies.ddp import RayTPUStrategy
from ray_lightning_tpu.utils.rank_zero import rank_zero_warn


class RingTPUStrategy(RayTPUStrategy):
    strategy_name = "horovod_ray"

    def bind_module(self, module: Any) -> None:
        # The per-rank step already runs inside shard_map, where a kernel
        # sees its local shard: the module gets no mesh to wrap it in.
        self._module = module

    def compile_train_step(
        self,
        module: Any,
        tx: Any,
        log_grad_norm: bool = False,
        fold_steps: int = 1,
        fold_stacked: bool = False,
    ) -> Callable:
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = self.mesh
        prep = self._prep_compute(module)

        def per_rank_step(params, opt_state, batch, rng):
            # Runs per device on its batch shard; params/opt replicated in.
            def loss_fn(p):
                p, b = prep(p, batch)
                loss, logs = module.training_step(p, b, rng)
                return loss, dict(logs)

            (loss, logs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            # Explicit ring/tree all-reduce over the data axis — the
            # hvd.DistributedOptimizer analog (ray_horovod_launcher.py:202).
            grads = jax.lax.pmean(grads, "data")
            if log_grad_norm:
                # Post-allreduce: the same global norm every rank logs.
                logs["grad_norm"] = optax.global_norm(grads)
            logs.setdefault("loss", loss)
            logs = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, "data"), logs
            )
            updates, opt_state2 = tx.update(grads, opt_state, params)
            params2 = optax.apply_updates(params, updates)
            return params2, opt_state2, logs

        sharded = shard_map(
            per_rank_step,
            mesh=mesh,
            in_specs=(P(), P(), P("data"), P()),
            out_specs=(P(), P(), P()),
        )

        def step(params, opt_state, batch, rng, step_idx):
            rng = jax.random.fold_in(rng, step_idx)
            return sharded(params, opt_state, batch, rng)

        if fold_steps > 1:
            return self._fold_train_step(step, fold_steps, stacked=fold_stacked)
        return jax.jit(step, donate_argnums=(0, 1))

    def compile_eval_step(self, module: Any, stage: str) -> Callable:
        """Per-rank masked eval: each device reduces its real samples
        locally, then one explicit ``psum`` merges (sums, count) — same
        (sums, count) contract as the base strategy's GSPMD version."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        if stage == "predict":
            return super().compile_eval_step(module, stage)

        fn = module.validation_step if stage in ("val", "validate") else module.test_step
        prep = self._prep_compute(module)

        if not getattr(module, "supports_per_sample_eval", True):

            def per_rank_batched(params, batch, mask):
                params, batch = prep(params, batch)
                logs = dict(fn(params, batch))
                count = jax.lax.psum(mask.astype(jnp.float32).sum(), "data")
                # Whole-batch metric: weight each rank's mean by its count.
                local = mask.astype(jnp.float32).sum()
                sums = {
                    k: jax.lax.psum(jnp.asarray(v, jnp.float32) * local, "data")
                    for k, v in logs.items()
                }
                return sums, count

            sharded = shard_map(
                per_rank_batched,
                mesh=self.mesh,
                in_specs=(P(), P("data"), P("data")),
                out_specs=(P(), P()),
            )
            return jax.jit(sharded)

        def per_rank_eval(params, batch, mask):
            params, batch = prep(params, batch)

            def per_sample(b):
                one = jax.tree_util.tree_map(lambda x: x[None], b)
                return {k: jnp.asarray(v) for k, v in dict(fn(params, one)).items()}

            vals = jax.vmap(per_sample)(batch)
            m = mask.astype(jnp.float32)
            count = jax.lax.psum(m.sum(), "data")
            sums = {
                k: jax.lax.psum((v.astype(jnp.float32).reshape(-1) * m).sum(), "data")
                for k, v in vals.items()
            }
            return sums, count

        sharded = shard_map(
            per_rank_eval,
            mesh=self.mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P()),
        )
        return jax.jit(sharded)


class HorovodRayStrategy(RingTPUStrategy):
    """Compat-named ring strategy with the reference's ctor surface
    (num_workers/num_cpus_per_worker/use_gpu, ray_horovod.py:73-91)."""

    def __init__(
        self,
        num_workers: int = 1,
        num_cpus_per_worker: float = 1,
        use_gpu: bool = False,
        **kwargs: Any,
    ) -> None:
        if use_gpu:
            rank_zero_warn(
                "use_gpu=True is a CUDA concept; falling back to accelerator "
                "auto-detection."
            )
        kwargs.setdefault("use_tpu", "auto" if use_gpu else False)
        super().__init__(
            num_workers=num_workers,
            num_cpus_per_worker=num_cpus_per_worker,
            **kwargs,
        )
