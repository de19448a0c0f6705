"""GSPMDStrategy: multi-axis mesh parallelism (dp x fsdp x tp x sp).

Beyond-parity strategy (the reference's surface is pure DP variants,
SURVEY.md §2c): one strategy that expresses data parallelism, ZeRO/FSDP
parameter sharding, megatron-style tensor parallelism, and ring-attention
sequence parallelism as *mesh axes* — the GSPMD recipe from the scaling
playbook. Models opt in by providing ``param_logical_axes()`` (see
``parallel.logical``); models without it degrade to FSDP-by-largest-axis
(the ZeRO rule from ``parallel.zero``).

The compiled step is identical to the DP one — XLA's partitioner inserts
all-reduce / reduce-scatter / all-gather traffic from the input shardings,
riding ICI within a slice and DCN across slices.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from ray_lightning_tpu.strategies.ddp import RayTPUStrategy

_AXES = ("data", "fsdp", "model", "seq", "ep", "pp")


class GSPMDStrategy(RayTPUStrategy):
    """Args (beyond RayTPUStrategy's):

    mesh_shape: dict axis-name -> size over {"data","fsdp","model","seq",
        "ep","pp"} (data parallel, ZeRO/FSDP, tensor, sequence, expert,
        pipeline). Sizes must multiply to ``num_workers``. Missing axes get
        size 1; if *no* axis is given, everything lands on "data" (pure DP).
    logical_axis_rules: override for ``parallel.logical.DEFAULT_RULES``.
    sequence_parallel: shard the sequence dim of inputs over the "seq"
        axis and switch mesh-aware models to ring attention (mutually
        exclusive with a pp axis > 1).
    """

    strategy_name = "gspmd_ray"

    def __init__(
        self,
        *args: Any,
        mesh_shape: Optional[Dict[str, int]] = None,
        logical_axis_rules: Optional[Sequence[Tuple[str, Optional[str]]]] = None,
        sequence_parallel: bool = False,
        **kwargs: Any,
    ) -> None:
        super().__init__(*args, **kwargs)
        shape = dict(mesh_shape or {})
        for ax in shape:
            if ax not in _AXES:
                raise ValueError(f"unknown mesh axis {ax!r}; valid: {_AXES}")
        total = 1
        for ax in _AXES:
            total *= shape.get(ax, 1)
        if mesh_shape and total != self.num_workers:
            raise ValueError(
                f"mesh_shape {shape} covers {total} devices but "
                f"num_workers={self.num_workers}"
            )
        if not mesh_shape:
            shape = {"data": self.num_workers}
        if sequence_parallel and shape.get("seq", 1) < 2:
            raise ValueError(
                "sequence_parallel=True needs mesh_shape['seq'] >= 2"
            )
        if sequence_parallel and shape.get("pp", 1) > 1:
            raise ValueError(
                "sequence_parallel cannot be combined with pipeline "
                "parallelism (ring attention inside the pp shard_map)"
            )
        self.mesh_shape = shape
        self.logical_axis_rules = logical_axis_rules
        self.sequence_parallel = sequence_parallel

    # -- mesh -----------------------------------------------------------
    def build_mesh(self):
        from ray_lightning_tpu.parallel.mesh import build_mesh

        sizes = tuple(self.mesh_shape.get(ax, 1) for ax in _AXES)
        return build_mesh(axis_shape=sizes, axis_names=_AXES)

    # -- module hook ----------------------------------------------------
    def bind_module(self, module: Any) -> None:
        super().bind_module(module)
        if self.sequence_parallel and hasattr(module, "bind_mesh"):
            module.bind_mesh(self.mesh, "seq")

    # -- shardings ------------------------------------------------------
    def param_sharding(self, params: Any) -> Any:
        module = getattr(self, "_module", None)
        if module is not None and hasattr(module, "param_logical_axes"):
            from ray_lightning_tpu.parallel.logical import (
                tree_logical_shardings,
            )

            return tree_logical_shardings(
                params,
                module.param_logical_axes(),
                self.mesh,
                rules=self.logical_axis_rules,
            )
        # Fallback: FSDP-by-largest-divisible-axis over "fsdp" (ZeRO-3 rule),
        # replicated if the fsdp axis is trivial.
        from ray_lightning_tpu.parallel.zero import replicated, tree_shardings

        if self.mesh.shape["fsdp"] > 1:
            return tree_shardings(params, self.mesh, axis_name="fsdp")
        return replicated(self.mesh)

    def opt_sharding(self, opt_state: Any, params: Any) -> Any:
        """Moment trees (optax state subtrees with the params' treedef, e.g.
        adam mu/nu) inherit the param shardings leaf-for-leaf; everything
        else (counts, schedule state) replicates. Matching by structure
        rather than shape avoids collisions between same-shape params with
        different layouts (e.g. wi/wo2 when d_ff == d_model)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        param_shardings = self.param_sharding(params)
        params_def = jax.tree_util.tree_structure(params)
        rep = NamedSharding(self.mesh, P())

        def is_param_tree(node: Any) -> bool:
            try:
                return jax.tree_util.tree_structure(node) == params_def
            except Exception:  # noqa: BLE001
                return False

        def node_sharding(node: Any) -> Any:
            return param_shardings if is_param_tree(node) else rep

        return jax.tree_util.tree_map(
            node_sharding, opt_state, is_leaf=is_param_tree
        )

    def batch_sharding(self) -> Any:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def spec_for(x: Any) -> NamedSharding:
            import numpy as np

            shape = np.shape(x)
            batch_axes: Tuple[str, ...] = tuple(
                ax for ax in ("data", "fsdp") if self.mesh.shape[ax] > 1
            )
            spec: list = [batch_axes or None]
            if (
                self.sequence_parallel
                and len(shape) >= 2
                and shape[1] % self.mesh.shape["seq"] == 0
            ):
                spec.append("seq")
            spec += [None] * (len(shape) - len(spec))
            return NamedSharding(self.mesh, P(*spec))

        return spec_for

    def stacked_batch_sharding(self) -> Any:
        """Per-leaf callable (this strategy's batch_sharding contract):
        the per-step spec is computed on the inner shape — where the
        seq-axis rule looks at dim 1 — then shifted by the shared
        fold-axis rule (Strategy._shift_spec)."""
        spec_for = self.batch_sharding()
        return lambda x: self._shift_spec(spec_for(x[0]))

    def make_global_batch(self, host_batch: Any, stacked: bool = False) -> Any:
        import jax

        spec_for = (
            self.stacked_batch_sharding() if stacked else self.batch_sharding()
        )
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(spec_for(x), x),
            host_batch,
        )

    # -- state movement -------------------------------------------------
    # The jitted all-gather must run on every process (see base attr).
    gather_is_collective = True

    def gather_state(self, tree: Any) -> Any:
        from ray_lightning_tpu.parallel.zero import gather_to_host

        return gather_to_host(tree, self.mesh)

    # -- dp sizing ------------------------------------------------------
    def sampler_kwargs(self) -> Dict[str, int]:
        """Dataset sharding must follow the *data-parallel extent*, not the
        host count: when tp/sp span hosts (dp < num_hosts), host groups
        sharing one dp shard must load IDENTICAL rows — otherwise
        make_array_from_process_local_data would silently assemble
        divergent "replicated" batches and gradients would drift per host.
        """
        env = self.dist_env
        if env is None:
            return {"num_replicas": 1, "rank": 0}
        dp = self.mesh_shape.get("data", 1) * self.mesh_shape.get("fsdp", 1)
        if dp % env.num_hosts == 0:
            return {"num_replicas": env.num_hosts, "rank": env.host_rank}
        if env.num_hosts % dp == 0:
            # dp axes lead the mesh (row-major device order), so host h's
            # devices all live in dp shard h*dp//num_hosts.
            return {
                "num_replicas": dp,
                "rank": env.host_rank * dp // env.num_hosts,
            }
        raise ValueError(
            f"data-parallel extent {dp} and num_hosts {env.num_hosts} must "
            f"divide one another for consistent per-host data sharding"
        )

    @property
    def batch_multiplier(self) -> int:
        """Global batch = per-replica batch x (data x fsdp) ranks; model/seq
        axes do not multiply the batch."""
        env = self.dist_env
        if env is None:
            return 1
        dp = self.mesh_shape.get("data", 1) * self.mesh_shape.get("fsdp", 1)
        # The loop multiplies the host-local loader batch; scale by this
        # host's share of the dp extent.
        return max(1, dp // env.num_hosts)
