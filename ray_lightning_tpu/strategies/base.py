"""Strategy base: resource planning (driver side) + compiled execution (worker side).

The reference's strategies subclass PTL Strategy classes and configure
launchers + process groups (ray_ddp.py:23-126). Here a Strategy owns both
sides explicitly:

- driver: plan worker actors (count, resources, env) and pick the launcher —
  the analog of ``_configure_launcher`` + resource bookkeeping
  (ray_ddp.py:84-126);
- worker: rendezvous (``jax.distributed.initialize`` — replacing
  ``init_process_group``, ray_ddp.py:192-196), build the device Mesh, place
  params/optimizer/batch with NamedShardings, and compile the train/eval
  steps. Gradient averaging is *not* a per-parameter hook like DDP: the loss
  is the mean over the globally-sharded batch, so XLA's SPMD partitioner
  inserts the all-reduce into the compiled step itself.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_lightning_tpu.parallel.env import DistEnv


@dataclass
class WorkerPlan:
    """Placement request for one worker actor."""

    host_rank: int
    resources: Dict[str, float]
    env: Dict[str, str]
    num_cpus: float = 1.0


class Strategy:
    """Base distributed strategy."""

    strategy_name = "base"

    def __init__(
        self,
        num_workers: int = 1,
        num_cpus_per_worker: float = 1,
        use_tpu: Any = "auto",
        num_hosts: Optional[int] = None,
        init_hook: Optional[Callable[[], None]] = None,
        resources_per_worker: Optional[Dict[str, float]] = None,
        **kwargs: Any,
    ) -> None:
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        self.num_workers = int(num_workers)  # chip-level DP ranks
        self.num_cpus_per_worker = num_cpus_per_worker
        self.use_tpu = use_tpu
        self._num_hosts = num_hosts
        self.init_hook = init_hook
        self.resources_per_worker = dict(resources_per_worker or {})
        self.extra_kwargs = kwargs
        # Worker-side state (populated in setup_worker)
        self.mesh = None
        self.dist_env: Optional[DistEnv] = None
        self._is_remote = False
        self._module: Optional[Any] = None

    # ------------------------------------------------------------------
    # Driver side
    # ------------------------------------------------------------------
    def _resolve_use_tpu(self) -> bool:
        if self.use_tpu == "auto":
            from ray_lightning_tpu import fabric

            # A chip probe that crashed or timed out raises FabricError
            # here; answering False would plan a CPU run on a TPU host.
            return fabric.cluster_resources().get("TPU", 0) >= 1
        return bool(self.use_tpu)

    def _resolve_num_hosts(self, use_tpu: bool) -> int:
        if self._num_hosts is not None:
            if self.num_workers % self._num_hosts:
                raise ValueError(
                    f"num_workers={self.num_workers} not divisible by "
                    f"num_hosts={self._num_hosts}"
                )
            return self._num_hosts
        if use_tpu:
            from ray_lightning_tpu import fabric
            from ray_lightning_tpu.utils.rank_zero import rank_zero_warn

            # One actor per TPU host. chips_per_host must hold on EVERY
            # host we place on, so a heterogeneous pod (unequal per-node
            # chip counts) plans against the minimum rather than trusting
            # whichever node happens to be listed first.
            per_node = [
                int(n["Resources"].get("TPU", 0))
                for n in fabric.nodes()
                if n["Resources"].get("TPU", 0) > 0
            ]
            if not per_node:
                return self.num_workers  # no TPU nodes visible yet: 1 chip/actor
            if len(set(per_node)) > 1:
                rank_zero_warn(
                    f"TPU nodes report unequal chip counts {sorted(set(per_node))}; "
                    f"planning with chips_per_host={min(per_node)} so every "
                    "worker actor fits on any TPU node"
                )
            chips_per_host = min(per_node)
            if self.num_workers % chips_per_host == 0:
                num_hosts = self.num_workers // chips_per_host
                # One whole-host actor per node in this branch.
                if num_hosts > len(per_node):
                    rank_zero_warn(
                        f"planning {num_hosts} TPU worker actors of "
                        f"{chips_per_host} chips each but only "
                        f"{len(per_node)} TPU nodes are visible; placement "
                        "will fail unless more nodes join"
                    )
            elif self.num_workers < chips_per_host:
                # Part of one host: ONE actor holding num_workers chips,
                # which the fabric pins to exactly those chips (or refuses,
                # naming the counts a host can be cut into).
                num_hosts = 1
            else:
                # Several processes would have to cooperate across parts
                # of one host's chips; each process of a TPU job owns whole
                # hosts (or, alone, one pinned part of a host).
                raise ValueError(
                    f"num_workers={self.num_workers} does not fill whole TPU "
                    f"hosts of {chips_per_host} chips: use a multiple of "
                    f"{chips_per_host}, or fewer than {chips_per_host} "
                    "workers (one process on part of one host)"
                )
            return max(1, num_hosts)
        return 1  # CPU: one process with N virtual devices

    def plan_workers(self) -> Tuple[List[WorkerPlan], bool]:
        """Compute actor placements. Returns (plans, use_tpu)."""
        from ray_lightning_tpu.utils.rank_zero import rank_zero_warn

        req_tpu = self.resources_per_worker.get("TPU")
        if req_tpu is not None and float(req_tpu) != int(req_tpu):
            # Reference behavior for fractional accelerators
            # (ray_ddp.py:84-100): a fraction means chip SHARING, which PJRT
            # cannot isolate — warn loudly rather than fail mysteriously.
            rank_zero_warn(
                f"requesting a fractional TPU per worker (TPU={req_tpu}): "
                "TPU chips cannot be shared between XLA runtimes; expect "
                "workers to contend for the same chip. Use whole chips."
            )
        use_tpu = self._resolve_use_tpu()
        num_hosts = self._resolve_num_hosts(use_tpu)
        chips_per_host = self.num_workers // num_hosts
        plans: List[WorkerPlan] = []
        for host_rank in range(num_hosts):
            resources = dict(self.resources_per_worker)
            env: Dict[str, str] = {}
            if use_tpu:
                resources["TPU"] = float(chips_per_host)
            else:
                # CPU mode: the actor simulates its chips with virtual XLA
                # host devices (the test strategy from SURVEY.md §4).
                env["JAX_PLATFORMS"] = "cpu"
                flags = os.environ.get("XLA_FLAGS", "")
                import re

                flags = re.sub(
                    r"--xla_force_host_platform_device_count=\d+", "", flags
                ).strip()
                env["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count={chips_per_host}"
                ).strip()
            plans.append(
                WorkerPlan(
                    host_rank=host_rank,
                    resources=resources,
                    env=env,
                    num_cpus=self.num_cpus_per_worker,
                )
            )
        return plans, use_tpu

    def _configure_launcher(self, trainer: Any):
        from ray_lightning_tpu.launchers.tpu_launcher import TPULauncher

        return TPULauncher(self, trainer)

    # Rank properties, valid on the driver before launch (the reference's
    # driver-side fallbacks, ray_horovod.py:110-141) and inside workers after
    # setup_worker.
    @property
    def world_size(self) -> int:
        return self.num_workers

    @property
    def global_rank(self) -> int:
        return self.dist_env.host_rank if self.dist_env else 0

    @property
    def local_rank(self) -> int:
        return self.dist_env.local_rank if self.dist_env else 0

    @property
    def node_rank(self) -> int:
        return self.dist_env.node_rank if self.dist_env else 0

    def set_remote(self, remote: bool) -> None:
        self._is_remote = remote

    # ------------------------------------------------------------------
    # Worker side
    # ------------------------------------------------------------------
    def setup_worker(self, dist_env: DistEnv) -> None:
        """Rendezvous + build the mesh. Called once inside each worker."""
        import jax

        from ray_lightning_tpu.parallel import mesh as mesh_lib

        self.dist_env = dist_env
        self._is_remote = True
        mesh_lib.setup_distributed(dist_env)
        n_devices = len(jax.devices())
        if n_devices != dist_env.world_size:
            raise RuntimeError(
                f"strategy expected {dist_env.world_size} global devices "
                f"(num_workers), found {n_devices}"
            )
        self.mesh = self.build_mesh()

    def bind_module(self, module: Any) -> None:
        """Give the strategy the user module before state placement, so
        sharding rules can consult module hooks (``param_logical_axes``,
        ``bind_mesh``). Called by the loop once the mesh exists.

        Mesh-aware modules get the mesh: under GSPMD a Pallas kernel is a
        custom call the partitioner cannot split, so the module wraps it
        in a ``shard_map`` over the mesh axes that shard its operands."""
        self._module = module
        if hasattr(module, "bind_mesh"):
            module.bind_mesh(self.mesh, None)

    def build_mesh(self):
        from ray_lightning_tpu.parallel.mesh import build_mesh

        return build_mesh(axis_names=("data",))

    # -- shardings ------------------------------------------------------
    def param_sharding(self, params: Any) -> Any:
        """Sharding (pytree or single) for model params: replicated for DP."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def opt_sharding(self, opt_state: Any, params: Any) -> Any:
        """Sharding for optimizer state: replicated for plain DP."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def batch_sharding(self) -> Any:
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P("data"))

    @staticmethod
    def _place_tree(tree: Any, sharding: Any) -> Any:
        """device_put a pytree without aliasing caller-held buffers.

        Placed arrays are donated by the compiled step; device_put can reuse
        the source buffer even with may_alias=False (observed on the CPU
        backend), which would delete the caller's arrays on donation. A host
        round-trip guarantees fresh device buffers; placement happens once
        per run so the copy cost is setup-only.
        """
        import jax
        import numpy as np

        def place(x, s):
            host = x if isinstance(x, np.ndarray) else np.asarray(jax.device_get(x))
            return jax.device_put(host, s)

        if isinstance(sharding, jax.sharding.Sharding):
            return jax.tree_util.tree_map(lambda x: place(x, sharding), tree)
        return jax.tree_util.tree_map(place, tree, sharding)

    def place_params(self, params: Any) -> Any:
        return self._place_tree(params, self.param_sharding(params))

    def place_opt_state(self, opt_state: Any, params: Any) -> Any:
        return self._place_tree(opt_state, self.opt_sharding(opt_state, params))

    @staticmethod
    def _shift_spec(sharding: Any) -> Any:
        """THE fold-axis rule, in one place: a (K, batch, ...) stacked
        chunk replicates the leading fold axis and shifts the per-step
        spec right by one."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(sharding.mesh, P(None, *tuple(sharding.spec)))

    def stacked_batch_sharding(self) -> Any:
        """Sharding for a (K, batch, ...) step-folded chunk (see
        :meth:`_shift_spec`). Strategies whose ``batch_sharding`` returns
        a per-leaf callable (GSPMDStrategy) override this accordingly."""
        return self._shift_spec(self.batch_sharding())

    def make_global_batch(self, host_batch: Any, stacked: bool = False) -> Any:
        """Host-local numpy batch -> globally sharded jax.Array pytree.

        ``stacked=True``: the leaves carry a leading fold axis (K, B, ...)
        — one transfer covering K steps (see ``stage_batches(stack=K)``).
        """
        import jax

        sharding = (
            self.stacked_batch_sharding() if stacked else self.batch_sharding()
        )
        if self.dist_env is None or not self.dist_env.is_distributed:
            # Single-process: plain device_put carries the same semantics
            # with less per-call bookkeeping than the multi-host assembler.
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding), host_batch
            )
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(sharding, x),
            host_batch,
        )

    def stage_batches(
        self, host_batches: Any, depth: int = 3, stack: int = 0
    ) -> Any:
        """Iterate device-resident global batches, overlapping host->device
        transfer with compute.

        A small thread pool keeps ``depth`` transfers in flight
        (order-preserving) so the step stream never stalls on a blocking
        ``device_put``.
        This is the TPU analog of the reference relying on torch DataLoader
        ``pin_memory`` + async ``.cuda()`` copies in its hot loop.

        ``stack=K > 1`` (the trainer's steps_per_execution path) stacks K
        host batches into ONE (K, batch, ...) transfer, so a folded chunk
        costs a single H2D round trip instead of K; yields ``(n, batch)``
        pairs where full chunks have ``n == K`` and the epoch tail arrives
        as ``n == 1`` singles.
        """
        import collections
        from concurrent.futures import ThreadPoolExecutor

        import numpy as np

        def chunks():
            if stack <= 1:
                for hb in host_batches:
                    yield 1, False, hb
                return
            buf = []
            for hb in host_batches:
                buf.append(hb)
                if len(buf) == stack:
                    yield stack, True, buf  # stacked IN the executor task
                    buf = []
            for hb in buf:  # tail shorter than the fold: plain singles
                yield 1, False, hb

        def assemble(payload, stacked):
            # The K-batch host stack runs here, on a staging thread — the
            # consuming (step-dispatching) thread never pays the memcpy.
            if stacked:
                import jax

                payload = jax.tree_util.tree_map(
                    lambda *xs: np.stack(xs), *payload
                )
            return self.make_global_batch(payload, stacked)

        ex = ThreadPoolExecutor(max_workers=depth, thread_name_prefix="rlt-stage")
        pending: "collections.deque" = collections.deque()
        try:
            for n, stacked, hb in chunks():
                pending.append((n, ex.submit(assemble, hb, stacked)))
                while len(pending) >= depth:
                    n0, fut = pending.popleft()
                    yield (n0, fut.result()) if stack > 1 else fut.result()
            while pending:
                n0, fut = pending.popleft()
                yield (n0, fut.result()) if stack > 1 else fut.result()
        finally:
            ex.shutdown(wait=False, cancel_futures=True)

    # -- precision ------------------------------------------------------
    @staticmethod
    def _compute_dtype(module: Any):
        """Trainer-level mixed precision: params stay fp32 masters; the
        compute graph (params AND batch as seen by the module's step) is
        cast to bfloat16 — grads come back fp32 through the cast transpose.
        bf16 is TPU-native, so fp16 requests map to bf16 too (no loss
        scaling needed)."""
        import jax.numpy as jnp

        p = str(getattr(module, "precision", "fp32") or "fp32").lower()
        if p in ("fp32", "32", "32-true", "float32"):
            return None
        if p in ("bf16", "bf16-mixed", "bfloat16", "16", "16-mixed",
                 "fp16", "float16"):
            return jnp.bfloat16
        if p in ("bf16-true", "16-true"):
            # True-half (params/opt state STORED in bf16) is a memory-layout
            # choice the module owns (e.g. GPTConfig.compute_dtype); quietly
            # running it as mixed would break its memory promise.
            raise ValueError(
                f"precision {p!r} (true half) is not a trainer-level option; "
                "use 'bf16-mixed', or store low-precision params in the "
                "module itself"
            )
        raise ValueError(f"unsupported precision {p!r}")

    def _prep_compute(self, module: Any) -> Callable:
        """One shared cast policy for every compiled program: returns
        ``prep(params, batch) -> (params, batch)`` applying the trainer's
        mixed-precision dtype (no-op for fp32)."""
        cdt = self._compute_dtype(module)
        if cdt is None:
            return lambda params, batch: (params, batch)
        cast = self._cast_floating
        return lambda params, batch: (cast(params, cdt), cast(batch, cdt))

    @staticmethod
    def _cast_floating(tree: Any, dtype: Any) -> Any:
        import jax
        import jax.numpy as jnp

        def cast(x):
            x = jnp.asarray(x)
            return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x

        return jax.tree_util.tree_map(cast, tree)

    # -- compiled steps -------------------------------------------------
    def compile_train_step(
        self,
        module: Any,
        tx: Any,
        log_grad_norm: bool = False,
        fold_steps: int = 1,
        fold_stacked: bool = False,
    ) -> Callable:
        """Build the jitted train step.

        The whole optimization step — fwd, bwd, (XLA-inserted) grad
        all-reduce, optimizer update — is one compiled program, the TPU
        equivalent of the reference's ★ HOT LOOP (SURVEY.md §3.1) where
        DDP hooks overlap allreduce with backward.

        ``log_grad_norm`` adds the pre-clip global gradient norm to the
        step's logs — computed in-graph (one reduction XLA fuses into the
        backward), not a host-side hook.

        ``fold_steps=K > 1`` returns a FOLDED step (the trainer's
        ``steps_per_execution``): one executable that ``lax.scan``s K
        optimizer steps, taking a tuple of K staged batches (stacked
        in-graph) and returning per-step logs stacked on a leading K
        axis. One device dispatch then covers K steps — on a
        high-latency link to the chip (remote PJRT), dispatch/transfer
        round trips stop bounding steps/sec. Per-step math is identical
        to the unfolded step (same per-step rng fold; asserted in
        tests/test_trainer.py).
        """
        import jax
        import optax

        prep = self._prep_compute(module)

        def step(params, opt_state, batch, rng, step_idx):
            # Per-step rng derivation happens *inside* the compiled program
            # (the loop passes the base key + step counter), avoiding a
            # separate fold_in dispatch on the host every step.
            rng = jax.random.fold_in(rng, step_idx)

            def loss_fn(p):
                p, b = prep(p, batch)
                loss, logs = module.training_step(p, b, rng)
                return loss, dict(logs)

            # Scope names go into the program's metadata only: a profile
            # then tells the forward-and-backward pass (the module names
            # its forward and its loss; autodiff marks their transposes)
            # from the optimizer update.
            with jax.named_scope("forward_backward"):
                (loss, logs), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
            if log_grad_norm:
                logs["grad_norm"] = optax.global_norm(grads)
            with jax.named_scope("optimizer"):
                updates, opt_state2 = tx.update(grads, opt_state, params)
                params2 = optax.apply_updates(params, updates)
            # Pin outputs to the strategy's shardings: without the
            # constraint GSPMD may pick a different layout for the updated
            # state, causing a reshard every step (observed on multi-axis
            # meshes). Sharding rules only need shapes, so they work on
            # tracers.
            params2 = jax.lax.with_sharding_constraint(
                params2, self.param_sharding(params2)
            )
            opt_state2 = jax.lax.with_sharding_constraint(
                opt_state2, self.opt_sharding(opt_state2, params2)
            )
            logs.setdefault("loss", loss)
            return params2, opt_state2, logs

        if fold_steps <= 1:
            return jax.jit(step, donate_argnums=(0, 1))
        return self._fold_train_step(step, fold_steps, stacked=fold_stacked)

    @staticmethod
    def _fold_train_step(
        step: Callable, fold_steps: int, stacked: bool = False
    ) -> Callable:
        """Jit a ``(params, opt, batch, rng, step_idx)`` step body into the
        K-folded executable (``compile_train_step``'s ``fold_steps``
        contract): scans the step over K batches, returns per-step logs
        stacked on a leading K axis.

        ``stacked=False``: takes a K-tuple of separately staged batches and
        stacks them in-graph. ``stacked=True``: takes ONE (K, batch, ...)
        pytree straight off the stacked staging path
        (``stage_batches(stack=K)``) — the flag exists because a K-tuple
        of batch tuples and a single stacked batch tuple are structurally
        ambiguous at the pytree level.
        """
        import jax
        import jax.numpy as jnp

        K = int(fold_steps)

        def kstep(params, opt_state, batches, rng, step_idx):
            if stacked:
                xs = batches  # already (K, batch, ...) leaves
            else:
                # Stack the K staged batches INSIDE the compiled program:
                # one executable dispatch, no separate concat kernel.
                xs = jax.tree_util.tree_map(
                    lambda *bs: jnp.stack(bs), *batches
                )

            def body(carry, x):
                p, o = carry
                i, b = x
                p, o, logs = step(p, o, b, rng, step_idx + i)
                return (p, o), logs

            (params2, opt_state2), logs = jax.lax.scan(
                body, (params, opt_state), (jnp.arange(K), xs)
            )
            return params2, opt_state2, logs

        return jax.jit(kstep, donate_argnums=(0, 1))

    @staticmethod
    def compile_folded_eval_step(eval_step: Callable) -> Callable:
        """Fold a compiled ``(params, batch, mask) -> (sums, count)`` eval
        step over a stacked (K, ...) chunk: one dispatch scans K eval
        batches and returns their summed (sums, count). ``jax.jit``
        retraces per distinct leading-dim K, so this costs one compile
        per fold size actually seen — in practice exactly one, because
        ``stage_batches`` emits a single stack size and routes tail
        batches to the unfolded ``eval_step``. Masked sums/counts accumulate
        associatively, so chunking preserves the epoch means up to fp32
        summation order (the on-device partial sums reassociate the
        reduction; equal to the unfolded path within float tolerance,
        asserted in tests). Unlike the train fold there are no host
        cadences to quantize. Works for any strategy's val/test step (the
        inner jitted step inlines when traced)."""
        import jax

        def feval(params, batches, masks):
            sums, counts = jax.lax.map(
                lambda x: eval_step(params, x[0], x[1]), (batches, masks)
            )
            return (
                jax.tree_util.tree_map(lambda v: v.sum(0), sums),
                counts.sum(),
            )

        return jax.jit(feval)

    def compile_eval_step(self, module: Any, stage: str) -> Callable:
        """Compile the eval program.

        predict: ``(params, batch, mask) -> (preds, mask)`` replicated, so
        every host can fetch and trim padding rows.

        val/test: ``(params, batch, mask) -> (sums, count)`` where ``sums``
        holds per-key metric totals over REAL samples only and ``count`` the
        real-sample total. The user step still computes per-batch means (the
        reference contract); exactness comes from vmapping it over singleton
        batches — XLA fuses the vmap back into the same batched program — and
        mask-weighting, so wrap-around padding (trainer/data.py tail) never
        contaminates metrics. Modules whose metrics are not per-sample means
        can set ``supports_per_sample_eval = False`` to keep whole-batch
        evaluation (batch-count weighted)."""
        import jax
        import jax.numpy as jnp

        prep = self._prep_compute(module)

        if stage == "predict":
            from jax.sharding import NamedSharding, PartitionSpec as P

            def pstep(params, batch, mask):
                params, batch = prep(params, batch)
                return module.predict_step(params, batch), mask

            # Replicate predictions so every host can fetch the full result.
            return jax.jit(
                pstep, out_shardings=NamedSharding(self.mesh, P())
            )

        fn = module.validation_step if stage in ("val", "validate") else module.test_step

        if not getattr(module, "supports_per_sample_eval", True):

            def estep_batched(params, batch, mask):
                params, batch = prep(params, batch)
                logs = dict(fn(params, batch))
                count = mask.astype(jnp.float32).sum()
                return (
                    {k: jnp.asarray(v, jnp.float32) * count for k, v in logs.items()},
                    count,
                )

            return jax.jit(estep_batched)

        def estep(params, batch, mask):
            params, batch = prep(params, batch)

            def per_sample(b):
                one = jax.tree_util.tree_map(lambda x: x[None], b)
                return {k: jnp.asarray(v) for k, v in dict(fn(params, one)).items()}

            vals = jax.vmap(per_sample)(batch)
            m = mask.astype(jnp.float32)
            count = m.sum()
            sums = {
                k: (v.astype(jnp.float32).reshape(-1) * m).sum()
                for k, v in vals.items()
            }
            return sums, count

        return jax.jit(estep)

    # -- state movement -------------------------------------------------
    #: Whether gather_state is a COLLECTIVE every process must enter
    #: (sharded/GSPMD override with True). Callers use this to decide
    #: whether non-zero ranks must participate in checkpoint gathers
    #: (collective: skipping deadlocks) or can skip them (plain
    #: device_get: participating is wasted D2H traffic).
    gather_is_collective = False

    def gather_state(self, tree: Any) -> Any:
        """Device pytree -> host numpy pytree (full, unsharded).

        DP state is replicated so this is a plain device_get; sharded
        strategies override with an all-gather (SURVEY.md §7 "checkpoint of
        sharded state").
        """
        import jax
        import numpy as np

        return jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)), tree)

    def barrier(self, name: str = "barrier") -> None:
        """Block until every process reaches this point.

        Cross-process ordering (e.g. "all ranks finished their checkpoint
        writes before rank 0 deletes a directory") must not rest on
        library-internal synchronization; this is the explicit primitive.
        TPU-native: a named tiny collective over all global devices
        (``sync_global_devices``); single-process runs need no sync.
        """
        import jax

        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(name)

    def sampler_kwargs(self) -> Dict[str, int]:
        """Dataset sharding is per *host process*; in-host distribution across
        chips happens via the batch sharding (contrast with the reference's
        per-worker-process sampler, ray_ddp.py:315-324)."""
        env = self.dist_env
        if env is None:
            return {"num_replicas": 1, "rank": 0}
        return {"num_replicas": env.num_hosts, "rank": env.host_rank}

    @property
    def batch_multiplier(self) -> int:
        """Local chips per host: host batch = batch_size * this."""
        env = self.dist_env
        return env.local_chips if env else 1

    def teardown_worker(self) -> None:
        import jax

        if self.dist_env is not None and self.dist_env.is_distributed:
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001
                pass


class SingleDeviceStrategy(Strategy):
    """In-process strategy used when Trainer has no distributed strategy.

    Runs on the default local device set (1-chip TPU or N virtual CPU
    devices) without any launcher — the non-distributed baseline.
    """

    strategy_name = "single_device"

    def __init__(self, **kwargs: Any) -> None:
        super().__init__(num_workers=1, **kwargs)

    def setup_worker(self, dist_env: DistEnv) -> None:
        import jax

        self.dist_env = dist_env
        n = len(jax.local_devices())
        dist_env.world_size = n
        dist_env.local_chips = n
        self.mesh = self.build_mesh()
