"""The worker-side training engine.

Runs inside each worker actor (or in-process for the single-device path):
builds params/optimizer on the mesh, compiles the train/eval steps through
the strategy, iterates epochs with host-side callbacks only at boundaries,
and packages rank-0 results as a WorkerOutput.

This replaces the role PTL's Trainer loop plays for the reference (the
``results = function(...)`` hot loop at ray_launcher.py:297 runs PTL's whole
fit); here the loop is framework-owned and XLA-first: one compiled step per
batch, async dispatch, metrics fetched at epoch/log boundaries to avoid
device->host syncs (SURVEY.md §7 "No mid-step Python").
"""
from __future__ import annotations

import os
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_lightning_tpu.launchers.utils import WorkerOutput
from ray_lightning_tpu.utils.seed import reset_seed
from ray_lightning_tpu.utils.state_stream import (
    load_state_stream,
    to_state_stream,
)


@dataclass
class TrainerSpec:
    """Picklable trainer configuration shipped driver -> workers.

    The reference pickles a live PTL Trainer through ``function.__self__``
    (ray_launcher.py:269-288) and reconciles side effects afterward; we
    design the shipped state explicitly instead (SURVEY.md §7 hard parts).
    """

    max_epochs: int = 1
    max_steps: Optional[int] = None
    # Debug: train on a fixed unshuffled slice and validate on the SAME
    # slice (PTL's overfit_batches); int = batches, float = epoch fraction.
    overfit_batches: Optional[Any] = None
    # Debug: enable jax_debug_nans in the worker — any NaN/inf produced by
    # a compiled step re-runs de-optimized and raises at the culprit op
    # (PTL's detect_anomaly analog; costs a per-step sync, debug only).
    detect_anomaly: bool = False
    # Wall-clock budget in seconds (Trainer parses str/timedelta forms).
    # Single-process: checked at every step boundary. Multi-process: checked
    # at collective boundaries (mid-epoch val, epoch end) with a cross-rank
    # consensus so every rank takes the same stop decision — a local
    # per-step clock check could diverge across ranks and deadlock the
    # next collective.
    max_time: Optional[float] = None
    limit_train_batches: Optional[Any] = None  # int or float fraction
    limit_val_batches: Optional[Any] = None
    limit_test_batches: Optional[Any] = None
    limit_predict_batches: Optional[Any] = None
    num_sanity_val_steps: int = 2
    check_val_every_n_epoch: int = 1
    # Mid-epoch validation (PTL semantics): int = every N train batches,
    # float in (0, 1) = that fraction of an epoch. None = epoch end only.
    val_check_interval: Optional[Any] = None
    accumulate_grad_batches: int = 1
    gradient_clip_val: Optional[float] = None
    # Fold K optimizer steps into ONE compiled dispatch (lax.scan inside
    # the executable; Keras-on-TPU's steps_per_execution). Per-step math
    # is unchanged; host-visible cadences (logging, val_check_interval,
    # callbacks, stop checks) quantize to K-step chunk boundaries, and
    # epoch/max_steps tails shorter than K run through the single-step
    # executable so budgets are exact. The win is dispatch amortization:
    # on a high-latency link to the chip, launch round trips stop
    # bounding steps/sec.
    steps_per_execution: int = 1
    log_every_n_steps: int = 50
    enable_checkpointing: bool = True
    default_root_dir: str = "."
    seed: Optional[int] = None
    precision: str = "fp32"
    # EMA of model weights (trainer/ema.py): decay enables the in-step
    # averaged copy riding opt_state; eval_ema evaluates with it.
    ema_decay: Optional[float] = None
    eval_ema: bool = False
    # Sharded (orbax) saves overlap tensorstore writes with the next epoch;
    # the finalization marker still gates restartability (checkpoint_io.py).
    async_checkpointing: bool = False
    # Log the pre-clip global grad norm each step (in-graph reduction).
    log_grad_norm: bool = False
    # Ship gathered optimizer state in the fit output so the driver's
    # save_checkpoint() writes fully-resumable files. Off = skip the
    # ~2x-params gather/transfer for Adam when worker-side ModelCheckpoint
    # is the only checkpoint path.
    ship_optimizer_state: bool = True
    # Print a parameter summary table at fit start (rank 0), PTL's
    # enable_model_summary.
    enable_model_summary: bool = True
    # predict(): accumulate + ship outputs through the rank-0 channel.
    # False = streaming inference (PredictionWriter writes per-rank shards;
    # per-rank memory stays O(1 batch)).
    return_predictions: bool = True
    callbacks: List[Any] = field(default_factory=list)


class TrainingPreempted(RuntimeError):
    """The fit answered a preemption notice (serve.preempt) with
    checkpoint-on-notice: a validated checkpoint was written at the
    step boundary the notice caught, and the loop exited cleanly.
    ``Trainer.fit``'s ``max_restarts`` loop catches this and resumes
    from ``ckpt_path`` bit-exactly, losing at most the one step that
    was in flight — instead of everything since the last periodic
    checkpoint. Picklable across the fabric (a worker-side preemption
    reaches the driver's retry loop as this same type)."""

    def __init__(self, ckpt_path: str, global_step: int = 0) -> None:
        super().__init__(
            f"fit preempted: checkpoint-on-notice written to {ckpt_path} "
            f"at step {global_step}"
        )
        self.ckpt_path = ckpt_path
        self.global_step = int(global_step)

    def __reduce__(self):  # keep attrs across cloudpickle round trips
        return (type(self), (self.ckpt_path, self.global_step))


def _host_device() -> Any:
    """This process's CPU device, or None (jax's default placement) when
    ``JAX_PLATFORMS`` left it no CPU backend."""
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return None


def _limit(n_batches: Optional[int], limit: Any) -> Optional[int]:
    """None n_batches = a streaming loader (unknown length): int limits
    bound it, fractional limits have nothing to take a fraction OF."""
    if limit is None:
        return n_batches
    if isinstance(limit, float):
        if n_batches is None:
            raise ValueError(
                "fractional batch limits need a sized dataset; streaming "
                "(IterableDataset) loaders have no length — use an int "
                "limit or max_steps"
            )
        return max(1, int(n_batches * limit))
    return int(limit) if n_batches is None else min(n_batches, int(limit))


class TrainingLoop:
    """Executes fit/validate/test/predict for one worker process."""

    def __init__(
        self,
        spec: TrainerSpec,
        module: Any,
        strategy: Any,
        dist_env: Any,
        tune_session: Any = None,
        datamodule: Any = None,
    ) -> None:
        self.spec = spec
        self.module = module
        self.strategy = strategy
        self.dist_env = dist_env
        self.tune_session = tune_session
        self.datamodule = datamodule
        # Trainer-facade state visible to callbacks
        self.current_epoch = 0
        self.global_step = 0
        self.should_stop = False
        self.callback_metrics: Dict[str, Any] = {}
        self.logged_metrics: Dict[str, Any] = {}
        self.state: Dict[str, Any] = {"status": "initializing", "stage": None}
        self.callbacks = list(spec.callbacks)
        # Device state
        self.params = None
        self.opt_state = None
        self._tx = None
        self._rng = None
        self.sanity_checking = False
        # Host mirror of optax.MultiSteps progress (accumulation only):
        # _update_count = inner updates applied (windows + flushes),
        # _mini_host = micro-batches since the last update. Kept in sync
        # deterministically so current_lr never costs a device fetch.
        self._update_count: Optional[int] = None
        self._mini_host = 0

    # -- facade properties used by callbacks ---------------------------
    @property
    def global_rank(self) -> int:
        return self.dist_env.host_rank

    @property
    def world_size(self) -> int:
        return self.dist_env.world_size

    @property
    def default_root_dir(self) -> str:
        return self.spec.default_root_dir

    @property
    def has_validation(self) -> bool:
        return self._val_loader is not None

    @property
    def lightning_module(self) -> Any:  # parity-friendly alias
        return self.module

    # ------------------------------------------------------------------
    def _call_callbacks(self, hook: str, *args: Any) -> None:
        for cb in self.callbacks:
            getattr(cb, hook)(self, self.module, *args)

    def _setup_common(self) -> None:
        import jax

        reset_seed()
        self.module.trainer = self
        self.module.precision = self.spec.precision
        self.strategy.bind_module(self.module)
        seed = self.spec.seed if self.spec.seed is not None else 0
        self._rng = jax.random.PRNGKey(seed)

        source = self.module
        if self.datamodule is not None:
            # Per-node data prep hook, like the reference's worker-side
            # ``prepare_data`` call (ray_launcher.py:290).
            self.datamodule.prepare_data()
            self.datamodule.setup()
            source = self.datamodule
        skw = self.strategy.sampler_kwargs()
        try:
            loader = source.train_dataloader()
        except NotImplementedError:
            loader = None
        if loader is not None and hasattr(loader, "with_sampler"):
            loader = loader.with_sampler(
                num_replicas=skw["num_replicas"], rank=skw["rank"], seed=seed
            )
        self._train_loader = loader
        val = source.val_dataloader()
        if val is not None and hasattr(val, "with_sampler"):
            # Val/test are evaluated un-shuffled (test_ddp.py:179-211
            # semantics) and sharded the same per-host way.
            val = val.with_sampler(
                num_replicas=skw["num_replicas"], rank=skw["rank"], seed=seed
            )
        self._val_loader = val
        if self.spec.overfit_batches:
            # Overfit debugging: same fixed slice for train AND val, no
            # shuffling (order defines the slice). Batch limits were set
            # by the Trainer; only the loader wiring happens here. Val is
            # only redirected when the module HAS a val loop to run.
            if self._train_loader is not None and getattr(
                self._train_loader, "shuffle", False
            ):
                self._train_loader.shuffle = False
                sampler = getattr(self._train_loader, "sampler", None)
                if sampler is not None and hasattr(sampler, "shuffle"):
                    sampler.shuffle = False
            if val is not None:
                self._val_loader = self._train_loader

    def _init_state(self, ckpt_stream: Optional[Any]) -> None:
        import jax

        # Shape probe only — prefetch=0 so no background thread spins up
        # assembling batches that get discarded.
        sample_batch = next(iter(self._train_loader.iter_batches(1, prefetch=0)))
        init_rng, self._rng = jax.random.split(self._rng)
        # Initial state is built on the host: built on the default device,
        # the first chip would hold the whole unsharded model AND optimizer
        # state before place_* shards them — a peak the other chips never
        # see, and the first thing to overflow on a model ZeRO makes fit.
        with jax.default_device(_host_device()):
            params = self.module.init_params(init_rng, sample_batch)
            self._tx = self._wrap_optimizer(self._unpack_optimizers())
            opt_state = self._tx.init(params)
        sharded_path = (
            ckpt_stream.get("orbax_path")
            if isinstance(ckpt_stream, dict)
            else None
        )
        if ckpt_stream is not None and sharded_path is None:
            state = load_state_stream(ckpt_stream)
            params = state["params"]
            if "opt_state" in state:
                restored = state["opt_state"]
                expected = jax.tree_util.tree_structure(
                    jax.eval_shape(self._tx.init, params)
                )
                if jax.tree_util.tree_structure(restored) != expected:
                    raise RuntimeError(
                        "checkpointed optimizer state does not match the "
                        "current optimizer: accumulate_grad_batches/"
                        "gradient_clip_val/ema_decay/configure_optimizers "
                        "changed since the checkpoint was written. Resume "
                        "with the same optimizer options, or load params "
                        "only via validate/test/predict(ckpt_path=...)"
                    )
                opt_state = restored
            elif int(state.get("global_step", 0) or 0) > 0:
                warnings.warn(
                    "resuming fit from a checkpoint that carries training "
                    "progress (global_step="
                    f"{state['global_step']}) but no optimizer state — "
                    "Adam moments and any embedded LR schedule restart "
                    "from scratch. Prefer a worker-written checkpoint "
                    "(ModelCheckpoint) or a driver save_checkpoint() taken "
                    "after a fit (which now includes optimizer state).",
                    RuntimeWarning,
                    stacklevel=2,
                )
            self._restore_progress(state)
        self.params = self.strategy.place_params(params)
        self.opt_state = self.strategy.place_opt_state(opt_state, params)
        if sharded_path is not None:
            # Sharded restore: read straight into this topology's
            # shardings (works across different worker counts/mesh shapes).
            from ray_lightning_tpu.trainer.checkpoint_io import (
                OrbaxCheckpointIO,
            )

            restored, meta = OrbaxCheckpointIO().restore(
                sharded_path,
                {"params": self.params, "opt_state": self.opt_state},
            )
            self.params = restored["params"]
            self.opt_state = restored["opt_state"]
            self._restore_progress(meta)
        if self.spec.accumulate_grad_batches > 1:
            # Seed the host mirror from the (possibly restored) MultiSteps
            # counters — one fetch at init, none per step.
            # .ravel()[0]: counters may arrive as 0-d or replicated 1-d
            # arrays; plain int(ndim>0 array) is a NumPy deprecation.
            self._mini_host = int(
                np.asarray(jax.device_get(self.opt_state.mini_step)).ravel()[0]
            )
            self._update_count = int(
                np.asarray(jax.device_get(self.opt_state.gradient_step)).ravel()[0]
            )
            if getattr(self, "_resumed_mid_epoch", False) and self._mini_host:
                # Mid-epoch resume re-runs the epoch from batch 0: keeping
                # the restored partial window would accumulate those
                # batches' gradients a second time into the same update.
                import jax.numpy as jnp
                import optax

                ms = self.opt_state
                self.opt_state = self.strategy.place_opt_state(
                    optax.MultiStepsState(
                        mini_step=jnp.zeros_like(ms.mini_step),
                        gradient_step=ms.gradient_step,
                        inner_opt_state=ms.inner_opt_state,
                        acc_grads=jax.tree_util.tree_map(
                            jnp.zeros_like, ms.acc_grads
                        ),
                        skip_state=ms.skip_state,
                    ),
                    params,
                )
                self._mini_host = 0
        if self.spec.ema_decay:
            # A restored EMA sum only continues correctly under the decay
            # it was accumulated with (stored in the state).
            from ray_lightning_tpu.trainer.ema import find_ema_state

            st = find_ema_state(self.opt_state)
            if st is not None:
                stored = float(np.asarray(jax.device_get(st.decay)).ravel()[0])
                # The state stores float32; compare at that precision.
                if abs(stored - float(np.float32(self.spec.ema_decay))) > 1e-7:
                    raise RuntimeError(
                        f"checkpoint EMA was accumulated with decay "
                        f"{stored}, but this Trainer has ema_decay="
                        f"{self.spec.ema_decay}; resume with the same value"
                    )

    def _unpack_optimizers(self) -> Any:
        """Unpack ``configure_optimizers()`` return forms.

        Accepted (Lightning's dict convention, adapted to optax — the
        schedule lives INSIDE the transform, so the extra entry is for
        monitoring only):

        - ``optax.GradientTransformation``
        - ``{"optimizer": tx, "lr_schedule": step -> lr}``
        - ``(tx, lr_schedule)``
        """
        from ray_lightning_tpu.trainer.module import unpack_optimizers

        opt, self._lr_schedule = unpack_optimizers(
            self.module.configure_optimizers()
        )
        return opt

    @property
    def current_lr(self) -> Optional[float]:
        """Learning rate the NEXT optimizer update will use, from the
        module's declared ``lr_schedule`` (None when not declared).

        optax applies ``sched(update_count)`` with a 0-based count, so the
        next update after ``global_step`` micro-batches uses index
        ``global_step // K`` (one update per K micro-batches under
        ``accumulate_grad_batches=K`` / ``optax.MultiSteps``) — the same
        next-update convention PTL's LearningRateMonitor reports after
        ``scheduler.step()``.
        """
        from ray_lightning_tpu.trainer.module import schedule_lr

        # With accumulation the host mirror counts ACTUAL inner updates
        # (full windows + epoch-end partial-window flushes, both of which
        # advance the embedded schedule).
        return schedule_lr(
            getattr(self, "_lr_schedule", None),
            global_step=self.global_step,
            update_count=getattr(self, "_update_count", None),
        )

    def _wrap_optimizer(self, tx: Any) -> Any:
        """Apply Trainer-level optimizer options around the module's optax
        transform — both stay inside the one compiled step:

        - ``gradient_clip_val``: global-norm clip (PTL's default
          ``gradient_clip_algorithm="norm"``) chained before the update.
        - ``accumulate_grad_batches=K``: ``optax.MultiSteps`` accumulates K
          micro-batch grads on device and applies one update every K-th
          step; grads are averaged, so K micro-batches == one K-times-larger
          batch. ``global_step`` keeps counting micro-batches. A partial
          window left at epoch end is flushed (PTL applies an optimizer step
          on the last batch regardless of accumulation phase) — see
          ``_flush_accumulation``.
        """
        import optax

        if self.spec.gradient_clip_val:
            tx = optax.chain(
                optax.clip_by_global_norm(float(self.spec.gradient_clip_val)),
                tx,
            )
        if self.spec.ema_decay:
            from ray_lightning_tpu.trainer.ema import params_ema

            # After the optimizer so the EMA absorbs post-update weights;
            # inside _inner_tx so accumulation flushes update it too.
            tx = optax.chain(tx, params_ema(float(self.spec.ema_decay)))
        self._inner_tx = tx  # pre-MultiSteps transform, used by the flush
        if self.spec.accumulate_grad_batches > 1:
            tx = optax.MultiSteps(
                tx, every_k_schedule=int(self.spec.accumulate_grad_batches)
            )
        return tx

    def _flush_accumulation(self) -> None:
        """Apply any partially-accumulated gradient window at epoch end.

        ``MultiStepsState.acc_grads`` holds the running MEAN over the
        micro-batches seen so far, so applying the inner transform to it is
        exactly the update those micro-batches deserve — no zero-padding
        dilution, matching PTL's last-batch-forces-a-step semantics.
        """
        if self.spec.accumulate_grad_batches <= 1:
            return
        import jax

        # The host mirror tracks mini_step exactly (incremented per step,
        # reset at window/flush) — no device sync needed here.
        if self._mini_host == 0:
            return
        self._mini_host = 0
        self._update_count += 1
        if getattr(self, "_flush_step", None) is None:
            import jax.numpy as jnp
            import optax

            inner_tx = self._inner_tx
            strategy = self.strategy

            def flush(params, ms):
                updates, inner2 = inner_tx.update(
                    ms.acc_grads, ms.inner_opt_state, params
                )
                params2 = optax.apply_updates(params, updates)
                params2 = jax.lax.with_sharding_constraint(
                    params2, strategy.param_sharding(params2)
                )
                new_ms = optax.MultiStepsState(
                    mini_step=jnp.zeros_like(ms.mini_step),
                    gradient_step=ms.gradient_step + 1,
                    inner_opt_state=inner2,
                    acc_grads=jax.tree_util.tree_map(
                        jnp.zeros_like, ms.acc_grads
                    ),
                    skip_state=ms.skip_state,
                )
                new_ms = jax.lax.with_sharding_constraint(
                    new_ms, strategy.opt_sharding(new_ms, params2)
                )
                return params2, new_ms

            self._flush_step = jax.jit(flush, donate_argnums=(0, 1))
        self.params, self.opt_state = self._flush_step(
            self.params, self.opt_state
        )

    def _restore_progress(self, state: Dict[str, Any]) -> None:
        # A checkpoint saved mid-epoch (val_check_interval save, or a
        # max_steps/should_stop break) resumes by re-running that epoch —
        # re-trained batches beat silently skipping the epoch's remainder.
        bump = 0 if state.get("mid_epoch") else 1
        self._resumed_mid_epoch = bool(state.get("mid_epoch"))
        rb = int(state.get("resume_batch") or 0)
        self._resume_batch = 0
        if rb and state.get("mid_epoch"):
            # Checkpoint-on-notice (preemption): continue the SAME epoch
            # at the exact next batch — the loader stream is
            # deterministic given set_epoch + the sampler seed, so
            # skipping the trained prefix reproduces the uninterrupted
            # run bit-for-bit. The partial grad-accumulation window is
            # KEPT (no MultiSteps reset: no batch is re-accumulated).
            self._resume_batch = rb
            self._resumed_mid_epoch = False
        self.current_epoch = int(state.get("epoch", -1)) + bump
        self.global_step = int(state.get("global_step", 0))
        for cb in self.callbacks:
            cb_state = state.get("callbacks", {}).get(type(cb).__name__)
            if cb_state:
                cb.load_state_dict(cb_state)

    # ------------------------------------------------------------------
    def save_checkpoint(
        self, path: str, sharded: bool = False, weights_only: bool = False
    ) -> None:
        """Write a checkpoint.

        Default: rank 0 gathers full state into a state-stream file (the
        reference's wire format, SURVEY.md §3.4). ``sharded=True``: every
        process writes its own shards via orbax — no gather, scales with
        GSPMD/ZeRO state (call from ALL ranks). ``weights_only=True``
        (state-stream files only) leaves the optimizer state out: a third
        of the bytes under Adam, loadable for serving and evaluation, and
        a resumed fit warns that its moments restart.
        """
        events = getattr(self, "_events", None)  # None outside a fit
        if events is not None:
            events.record(
                "trainer", "checkpoint", path=str(path), sharded=sharded,
                epoch=self.current_epoch, step=self.global_step,
            )
        if sharded:
            from ray_lightning_tpu.trainer.checkpoint_io import (
                OrbaxCheckpointIO,
            )

            meta = {
                "epoch": self.current_epoch,
                "mid_epoch": not getattr(self, "_epoch_complete", True),
                "global_step": self.global_step,
                "callbacks": {
                    type(cb).__name__: cb.state_dict() for cb in self.callbacks
                },
            }
            rb = getattr(self, "_preempt_resume_batch", None)
            if rb:
                meta["resume_batch"] = int(rb)
            if getattr(self, "_sharded_io", None) is None:
                from ray_lightning_tpu.trainer.checkpoint_io import (
                    AsyncOrbaxCheckpointIO,
                )

                self._sharded_io = (
                    AsyncOrbaxCheckpointIO()
                    if self.spec.async_checkpointing
                    else OrbaxCheckpointIO()
                )
            self._sharded_io.save(
                path,
                {"params": self.params, "opt_state": self.opt_state},
                meta,
                is_rank_zero=self.global_rank == 0,
            )
            return
        # checkpoint_state's gathers are collective under multi-process
        # sharding — every rank must run them; only rank 0 writes. (For
        # plain-device_get strategies non-zero ranks skip the gather.)
        if self.global_rank != 0 and not self.strategy.gather_is_collective:
            return
        state = self.checkpoint_state(weights_only=weights_only)
        if self.global_rank != 0:
            return
        stream = to_state_stream(state)
        from ray_lightning_tpu.utils.state_stream import state_stream_to_file

        state_stream_to_file(stream, path)

    @property
    def gather_is_collective(self) -> bool:
        """Do checkpoint-state gathers require every rank (see Strategy)?"""
        return bool(getattr(self.strategy, "gather_is_collective", False))

    def finalize_checkpoints(self) -> None:
        """Drain any in-flight async sharded save (no-op otherwise).

        Callbacks call this before deleting checkpoint directories that
        could still be mid-write. The explicit barrier makes the cross-rank
        ordering guaranteed by THIS call — not inherited from orbax's
        wait_until_finished internals — so rank 0 can only reach a
        directory deletion after every rank's writes are durable.
        """
        if getattr(self, "_sharded_io", None) is not None:
            self._sharded_io.finalize()
            self.strategy.barrier("finalize_checkpoints")

    def checkpoint_state(self, weights_only: bool = False) -> Dict[str, Any]:
        state = {
            "params": self.strategy.gather_state(self.params),
            "epoch": self.current_epoch,
            "mid_epoch": not getattr(self, "_epoch_complete", True),
            "global_step": self.global_step,
            "callbacks": {
                type(cb).__name__: cb.state_dict() for cb in self.callbacks
            },
        }
        if not weights_only:
            state["opt_state"] = self.strategy.gather_state(self.opt_state)
        rb = getattr(self, "_preempt_resume_batch", None)
        if rb:
            # Checkpoint-on-notice only: the exact epoch position for a
            # continue-the-epoch resume (see _restore_progress).
            state["resume_batch"] = int(rb)
        return state

    # ------------------------------------------------------------------
    def _preempt_pending(self, synced: bool) -> bool:
        """Has a preemption notice landed on this process
        (serve.preempt)? ``synced=True`` reaches a cross-rank consensus
        (any preempted rank stops everyone — the gang checkpoints and
        exits as a unit) and is a collective, like
        :meth:`_out_of_time`."""
        from ray_lightning_tpu.serve.preempt import peek_state

        st = peek_state()
        local = bool(st and st.get("pending"))
        if not synced:
            return local
        import jax

        if jax.process_count() == 1:
            return local
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(np.asarray(local))
        return bool(np.any(flags))

    def _preempt_exit(self, resume_batch: Optional[int]) -> None:
        """Checkpoint-on-notice: write a VALIDATED resume checkpoint at
        this step boundary, then exit the fit cleanly via
        :class:`TrainingPreempted` (which ``Trainer.fit``'s
        ``max_restarts`` loop catches and resumes from bit-exactly).

        ``resume_batch`` — batches of the current epoch already trained
        — rides the checkpoint so the resume continues the epoch at the
        exact next batch (the loader stream is deterministic given
        ``set_epoch`` + the sampler seed) instead of the re-run-the-epoch
        semantics periodic mid-epoch checkpoints use; any partial
        grad-accumulation window is likewise kept, not reset. None =
        the epoch just completed (resume starts the next one). The
        checkpoint name sorts into the ``last*`` resume group, so the
        restart scan picks it over older rolling checkpoints.
        """
        cb = next(
            (c for c in self.callbacks if hasattr(c, "best_model_path")),
            None,
        )
        d = getattr(cb, "dirpath", None) if cb is not None else None
        if not d:
            d = os.path.join(self.spec.default_root_dir, "checkpoints")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(
            d, f"last-preempt-step{self.global_step:08d}.ckpt"
        )
        self._events.record(
            "trainer", "fit_preempt_checkpoint", level="warn",
            path=path, step=self.global_step, epoch=self.current_epoch,
            resume_batch=int(resume_batch or 0),
        )
        self._preempt_resume_batch = (
            int(resume_batch) if resume_batch else None
        )
        try:
            self.save_checkpoint(path)
        finally:
            self._preempt_resume_batch = None
        if self.global_rank == 0:
            # VALIDATED: an unreadable file must raise here (crash
            # semantics, resume from an older checkpoint) — never hand
            # the retry loop a checkpoint that cannot load.
            with open(path, "rb") as f:
                load_state_stream(f.read())
        tel = getattr(self, "telemetry", None)
        if tel is not None:
            tel.fit_done = True  # the fit-stall watchdog stands down
        self.state = {"status": "preempted", "stage": "fit"}
        raise TrainingPreempted(path, self.global_step)

    # ------------------------------------------------------------------
    def _out_of_time(self, synced: bool) -> bool:
        """Has the fit's wall-clock budget expired?

        ``synced=True`` reaches a cross-rank consensus (any rank out of
        time stops everyone) and may only be called at points every rank
        reaches together — it is a collective. ``synced=False`` is a pure
        local clock read, safe anywhere but only used to stop when this
        process is the whole world.
        """
        if getattr(self, "_fit_deadline", None) is None:
            return False
        import time as _time

        local = _time.monotonic() >= self._fit_deadline
        if not synced:
            return local
        import jax

        if jax.process_count() == 1:
            return local
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(np.asarray(local))
        return bool(np.any(flags))

    # ------------------------------------------------------------------
    def _anomaly_guard(self):
        """Own jax_debug_nans for the duration of one run (detect_anomaly).

        Worker-side: the compiled steps run here. With detect_anomaly,
        NaN/inf in any jitted output re-runs the computation de-optimized
        and raises at the producing op. try/finally restoration covers the
        raise itself — the feature's primary outcome is an exception, and
        leaking the de-optimizing flag into the caller's process (or
        clobbering a user-set one) would outlive the run.
        """
        import contextlib

        @contextlib.contextmanager
        def guard():
            import jax

            prev = bool(jax.config.jax_debug_nans)
            jax.config.update(
                "jax_debug_nans", bool(self.spec.detect_anomaly)
            )
            try:
                yield
            finally:
                jax.config.update("jax_debug_nans", prev)

        return guard()

    def run_fit(self, ckpt_stream: Optional[bytes] = None) -> Optional[WorkerOutput]:
        with self._anomaly_guard():
            try:
                return self._run_fit_impl(ckpt_stream)
            except (SystemExit, KeyboardInterrupt):
                raise
            except TrainingPreempted:
                # Not a crash: the checkpoint-on-notice already ran and
                # its own typed event fired — no fit_exception, no
                # flight-recorder bundle.
                raise
            except BaseException as exc:
                # Forensics BEFORE the raise unwinds: a structured event
                # plus a rate-limited flight-recorder bundle (metrics,
                # event tail, all-thread stacks) so a crashed fit leaves
                # a black box, not just a traceback. crash_dump never
                # raises — it must not mask the real error.
                from ray_lightning_tpu.obs.blackbox import crash_dump
                from ray_lightning_tpu.obs.events import get_event_log

                get_event_log().record(
                    "trainer", "fit_exception", level="error",
                    error=f"{type(exc).__name__}: {exc}"[:300],
                    epoch=self.current_epoch, step=self.global_step,
                )
                crash_dump(f"fit_exception:{type(exc).__name__}")
                raise
            finally:
                wd = getattr(self, "_watchdog", None)
                if wd is not None:
                    wd.stop()
                    self._watchdog = None
                if getattr(self, "_gc_hooked", False):
                    from ray_lightning_tpu.obs.jaxmon import remove_gc_hook

                    self._gc_hooked = False
                    remove_gc_hook()

    def _run_fit_impl(
        self, ckpt_stream: Optional[bytes] = None
    ) -> Optional[WorkerOutput]:
        import jax
        import time as _time

        self.state = {"status": "running", "stage": "fit"}
        # Observability: per-step breakdown (data wait / compiled step /
        # drain) + compile events into the process registry; throughput
        # (tokens/s, MFU) lands at fit end. A few monotonic() reads per
        # dispatched chunk — noise next to a compiled step.
        from ray_lightning_tpu.obs.events import get_event_log
        from ray_lightning_tpu.obs.jaxmon import (
            install_compile_listener,
            install_gc_hook,
        )
        from ray_lightning_tpu.obs.telemetry import TrainTelemetry
        from ray_lightning_tpu.obs.trace import span, step_annotation

        install_compile_listener()
        # The collector's pauses for the length of the fit (run_fit's
        # finally takes the hook out): telemetry's snapshot ships them.
        install_gc_hook()
        self._gc_hooked = True
        self.telemetry = TrainTelemetry()
        spans = self.telemetry.spans
        self._events = get_event_log()
        self._events.record(
            "trainer", "fit_start",
            max_epochs=self.spec.max_epochs, resume_step=self.global_step,
        )
        # Opt-in fit-stall watchdog (obs.health): RLT_TRAIN_WATCHDOG_S=N
        # flags (event + rate-limited black-box bundle) a fit that
        # records no optimizer step for N seconds. Off by default — the
        # driver cannot distinguish a giant compile from a hang without
        # an operator-chosen budget.
        self._watchdog = None
        try:
            wd_s = float(os.environ.get("RLT_TRAIN_WATCHDOG_S", "0") or 0)
        except ValueError:
            wd_s = 0.0
        if wd_s > 0:
            from ray_lightning_tpu.obs import blackbox as obs_blackbox
            from ray_lightning_tpu.obs import health as obs_health

            wd = obs_health.Watchdog(
                interval_s=max(0.25, min(wd_s / 4.0, 5.0)),
                events=self._events,
                on_unhealthy=lambda comp, rep: obs_blackbox.crash_dump(
                    f"unhealthy:{comp}"
                ),
            )
            wd.add_check(
                obs_health.fit_stall_check(self.telemetry, wd_s)
            )
            self._watchdog = wd.start()
        self._fit_deadline = (
            _time.monotonic() + self.spec.max_time
            if self.spec.max_time is not None
            else None
        )
        # Per-step clock reads may STOP the loop only when this process is
        # the whole world; multi-process stops ride consensus boundaries.
        self._time_check_per_step = (
            self._fit_deadline is not None and jax.process_count() == 1
        )
        # Preemption checkpoint-on-notice (serve.preempt): single-process
        # fits answer the notice at the very next chunk boundary;
        # multi-process fits at the same consensus boundaries max_time
        # uses (mid-epoch val, epoch end), so every rank writes the same
        # checkpoint and takes the same exit.
        self._preempt_per_step = jax.process_count() == 1
        self._setup_common()
        if self._train_loader is None:
            raise RuntimeError("fit requires train_dataloader()")
        self._init_state(ckpt_stream)
        fold = max(1, int(self.spec.steps_per_execution))
        train_step = self.strategy.compile_train_step(
            self.module,
            self._tx,
            log_grad_norm=self.spec.log_grad_norm,
            fold_steps=fold,
            # Chunks arrive as ONE stacked (K, batch, ...) transfer from
            # the staging pipeline (stage_batches(stack=K)) — a folded
            # chunk costs a single H2D round trip, not K.
            fold_stacked=True,
        )
        # Tail chunks (epoch remainder, max_steps cap) shorter than the
        # fold run through the plain executable; jit compiles lazily, so
        # an epoch divisible by the fold never pays this compile.
        single_step = (
            train_step
            if fold == 1
            else self.strategy.compile_train_step(
                self.module, self._tx, log_grad_norm=self.spec.log_grad_norm
            )
        )
        val_step = (
            self.strategy.compile_eval_step(self.module, "val")
            if self._val_loader is not None
            else None
        )

        if self.spec.enable_model_summary and self.global_rank == 0:
            import sys

            from ray_lightning_tpu.utils.summary import summarize_params

            # stderr: stdout is a data channel for CLI generate and JSON
            # pipelines; diagnostics must not interleave into it.
            print(summarize_params(self.params), file=sys.stderr, flush=True)
        self.module.on_fit_start()
        self._call_callbacks("on_fit_start")
        mult = self.strategy.batch_multiplier

        # Pre-train sanity validation (PTL's num_sanity_val_steps): run a few
        # val batches so a broken eval path fails BEFORE a long train epoch.
        # Metrics are discarded and ``sanity_checking`` gates Tune reports
        # (tune/callbacks.py guard; reference tune.py:113-114). Skipped on
        # resume — the restored run already validated.
        if (
            val_step is not None
            and self.spec.num_sanity_val_steps
            and self.current_epoch == 0
            and self.global_step == 0
        ):
            self.sanity_checking = True
            saved_cb = dict(self.callback_metrics)
            saved_logged = dict(self.logged_metrics)
            try:
                self._run_eval_epoch(
                    val_step,
                    self._val_loader,
                    "sanity",
                    # PTL convention: -1 means run the FULL val set as sanity.
                    max_batches=(
                        None
                        if self.spec.num_sanity_val_steps < 0
                        else self.spec.num_sanity_val_steps
                    ),
                )
                self._call_callbacks("on_validation_end")
            finally:
                self.callback_metrics = saved_cb
                self.logged_metrics = saved_logged
                self.sanity_checking = False

        stop = False
        start_epoch = self.current_epoch
        for epoch in range(start_epoch, self.spec.max_epochs):
            if stop or self.should_stop:
                break
            self.current_epoch = epoch
            self._epoch_complete = False  # checkpoints saved mid-epoch
            # (val_check_interval) must resume by RE-RUNNING this epoch,
            # not skipping its remaining batches.
            self._train_loader.set_epoch(epoch)
            self._events.record(
                "trainer", "epoch_start", epoch=epoch, step=self.global_step
            )
            self.module.on_train_epoch_start(epoch)
            self._call_callbacks("on_train_epoch_start")

            n_batches = _limit(
                self._train_loader.num_batches(mult), self.spec.limit_train_batches
            )
            # Per-step device scalars buffer only until the next
            # log_every_n_steps boundary, where they drain into host float
            # lists — live device buffers stay O(log interval), not
            # O(steps), so 100k-step epochs don't pin 100k live scalars
            # for one giant end-of-epoch fetch.
            pending_logs: List[Tuple[Dict[str, Any], int]] = []
            epoch_host_vals: Dict[str, List[float]] = {}

            def _drain_logs() -> Dict[str, float]:
                """Fetch buffered device scalars (one device_get), append
                to the epoch's host accumulators, return the LATEST step's
                host values (what on_train_batch_end logs). Entries are
                ``(logs, n)``: a folded dispatch contributes one entry of
                n stacked per-step scalars."""
                if not pending_logs:
                    return {}
                with span(spans, "fit.drain_wait", entries=len(pending_logs)):
                    fetched = jax.device_get(pending_logs)
                pending_logs.clear()
                last: Dict[str, float] = {}
                for d, n in fetched:
                    for k, v in d.items():
                        vals = np.asarray(v).reshape(n)
                        epoch_host_vals.setdefault(k, []).extend(
                            float(x) for x in vals
                        )
                    last = {
                        k: float(np.asarray(v).reshape(n)[-1])
                        for k, v in d.items()
                    }
                return last
            # Device staging pipeline: host batch assembly (loader prefetch
            # thread) -> H2D transfer (stager pool) -> step dispatch, all
            # overlapped with device compute.
            import itertools

            # Mid-epoch validation cadence (PTL's val_check_interval):
            # int = every N batches; float fraction = that share of the
            # epoch's batches.
            vci = self.spec.val_check_interval
            vci_from_float = False
            if isinstance(vci, float) and vci == 1.0:
                vci = None  # PTL: 1.0 == once per epoch (the default path)
            elif vci is not None and 0 < float(vci) < 1:
                vci_from_float = True
                if n_batches is None:
                    raise ValueError(
                        "float val_check_interval needs a sized dataset; "
                        "streaming (IterableDataset) loaders have no "
                        "length — use an int interval"
                    )
                vci = max(1, int(n_batches * float(vci)))
            elif vci is not None:
                vci = int(vci)
                if n_batches is not None and vci > n_batches > 0:
                    raise ValueError(
                        f"val_check_interval ({vci}) exceeds the number of "
                        f"training batches per epoch ({n_batches}); use a "
                        "smaller interval or a float epoch fraction"
                    )
            if vci is not None and fold > 1 and int(vci) % fold:
                if vci_from_float:
                    # A fraction promises a cadence, not an exact count:
                    # quantize to the nearest chunk boundary (docs/api.md
                    # 'cadences quantize to chunk boundaries'), clamped to
                    # the epoch so rounding UP can't push the cadence past
                    # the last batch and silently disable mid-epoch val.
                    vci = max(fold, round(int(vci) / fold) * fold)
                    if n_batches is not None and vci > n_batches >= fold:
                        vci = (n_batches // fold) * fold
                else:
                    raise ValueError(
                        f"val_check_interval ({vci}) must be a multiple of "
                        f"steps_per_execution ({fold}): the host only sees "
                        "chunk boundaries, so an unaligned int interval "
                        "would silently validate late (float fractions "
                        "quantize instead)"
                    )
            if (
                fold > 1
                and n_batches is not None
                and fold > n_batches > 0
                and not getattr(self, "_fold_warned", False)
            ):
                from ray_lightning_tpu.utils.rank_zero import rank_zero_warn

                self._fold_warned = True  # epoch-invariant; warn once
                rank_zero_warn(
                    f"steps_per_execution ({fold}) exceeds the batches per "
                    f"epoch ({n_batches}); every chunk is an epoch tail, so "
                    "no dispatch is ever folded — lower it to at most the "
                    "epoch length to get the amortization"
                )
            # Mid-epoch vals obey the same epoch cadence as epoch-end ones.
            val_epoch = (epoch + 1) % self.spec.check_val_every_n_epoch == 0
            last_val_step = -1

            # Exact-batch resume after checkpoint-on-notice: skip the
            # batches the preempted attempt already trained and continue
            # the epoch where it stopped (batch_idx stays epoch-absolute
            # so val cadences and epoch-end checks are unchanged).
            skip = 0
            if epoch == start_epoch:
                skip = int(getattr(self, "_resume_batch", 0) or 0)
                self._resume_batch = 0
            # Bound the epoch's batch pull by the step budget so the
            # stacked staging below is budget-exact: a folded chunk can
            # never overshoot max_steps (the tail arrives as singles).
            n_iter = None if n_batches is None else max(0, n_batches - skip)
            if self.spec.max_steps is not None:
                remaining = max(0, self.spec.max_steps - self.global_step)
                n_iter = (
                    remaining if n_iter is None else min(n_iter, remaining)
                )
                if remaining == 0:
                    stop = True
            staged = self.strategy.stage_batches(
                itertools.islice(
                    self._train_loader.iter_batches(mult),
                    skip,
                    None if n_iter is None else skip + n_iter,
                ),
                # Depth counts STAGING UNITS (a whole stacked chunk when
                # folding): 3 keeps one executing + two in flight without
                # multiplying in-flight buffers by the fold.
                depth=3,
                # stack=K: K host batches leave the host as ONE
                # (K, batch, ...) transfer; epoch tails shorter than K
                # arrive as singles for the single-step executable.
                stack=fold if fold > 1 else 0,
            )
            batch_idx = skip - 1
            # Explicit iterator so each chunk's wall time splits into the
            # three host-observable segments (obs.telemetry), each one a
            # span (obs.trace.span) whose clock reads the telemetry
            # shares: data wait (fit.stage: blocking on the staged
            # pipeline), the step call (fit.dispatch) and the drain
            # (fit.callbacks: callbacks and mid-epoch val, with the
            # blocking log fetch inside it as fit.drain_wait — where the
            # device's compute surfaces when logs drain every dispatch).
            stream = iter(() if stop else staged)
            end_of_stream = object()
            try:
                while True:
                    with span(spans, "fit.stage") as staged_s:
                        item = next(stream, end_of_stream)
                    if item is end_of_stream:
                        break
                    n_chunk, payload = item if fold > 1 else (1, item)
                    start_step = self.global_step
                    with span(
                        spans, "fit.dispatch", step=start_step, n=n_chunk
                    ) as dispatch_s, step_annotation("fit", start_step):
                        if n_chunk > 1:
                            self.params, self.opt_state, logs = train_step(
                                self.params,
                                self.opt_state,
                                payload,
                                self._rng,
                                start_step,
                            )
                            pending_logs.append((logs, n_chunk))  # no sync here
                        else:
                            self.params, self.opt_state, logs = single_step(
                                self.params,
                                self.opt_state,
                                payload,
                                self._rng,
                                start_step,
                            )
                            pending_logs.append((logs, 1))
                    with span(spans, "fit.callbacks") as drain_s:
                        batch_idx += n_chunk
                        self.global_step += n_chunk
                        if self._update_count is not None:
                            self._mini_host += n_chunk
                            self._update_count += (
                                self._mini_host // self.spec.accumulate_grad_batches
                            )
                            self._mini_host %= self.spec.accumulate_grad_batches
                        if (
                            # Crossed a log boundary within this chunk (for
                            # fold=1 this is exactly `global_step % N == 0`).
                            self.global_step // self.spec.log_every_n_steps
                            != start_step // self.spec.log_every_n_steps
                            # Streaming epochs (n_batches None) have no known
                            # final batch; the post-loop drain covers the tail.
                            or (n_batches is not None and batch_idx == n_batches - 1)
                        ):
                            host_logs = _drain_logs()
                            self.logged_metrics.update(host_logs)
                            self._call_callbacks(
                                "on_train_batch_end", host_logs, batch_idx
                            )
                        if (
                            val_step is not None
                            and vci
                            and val_epoch
                            and (batch_idx + 1) % vci == 0
                        ):
                            if (
                                n_batches is not None
                                and batch_idx == n_batches - 1
                                and self._mini_host == 0
                            ):
                                # Final batch, nothing left to flush: any
                                # checkpoint this val writes is epoch-complete.
                                self._epoch_complete = True
                            self._run_eval_epoch(val_step, self._val_loader, "val")
                            self._call_callbacks("on_validation_end")
                            last_val_step = self.global_step
                            # Every rank just finished the same val epoch: a
                            # safe point for the max_time consensus check
                            # (and the multi-process preemption consensus).
                            if self._out_of_time(synced=True):
                                self.should_stop = True
                            if not self._preempt_per_step and (
                                self._preempt_pending(synced=True)
                            ):
                                self._preempt_exit(batch_idx + 1)
                    self.telemetry.record_chunk(
                        n_chunk,
                        data_wait=staged_s.ns * 1e-9,
                        step=dispatch_s.ns * 1e-9,
                        drain=drain_s.ns * 1e-9,
                    )
                    if self._preempt_per_step and self._preempt_pending(
                        synced=False
                    ):
                        # Consume the notice NOW: a validated checkpoint
                        # at this exact step boundary, then a clean exit
                        # the max_restarts loop resumes from bit-exactly.
                        self._preempt_exit(batch_idx + 1)
                    if (
                        (
                            self.spec.max_steps is not None
                            and self.global_step >= self.spec.max_steps
                        )
                        or self.should_stop
                        or (self._time_check_per_step and self._out_of_time(False))
                    ):
                        # should_stop: a mid-epoch val's EarlyStopping must
                        # end training NOW, not at the epoch boundary —
                        # stopping inside very long epochs is the point of
                        # val_check_interval.
                        stop = True
                        break
            finally:
                staged.close()

            # Apply any partial grad-accumulation window before val sees
            # (and checkpoints capture) the epoch's params — but only when
            # the epoch ran all its batches: PTL's flush is a
            # last-batch-of-epoch semantic, so a max_steps stop that landed
            # ON the final batch still flushes, while an earlier stop must
            # not advance params past the requested step budget.
            flushed = False
            if not stop or (
                n_batches is not None and batch_idx == n_batches - 1
            ):
                flushed = self._mini_host > 0  # flush will change params
                self._flush_accumulation()
                self._epoch_complete = True

            # Drain any steps since the last boundary (early max_steps/
            # should_stop breaks), then reduce the epoch means on host.
            _drain_logs()
            if epoch_host_vals:
                epoch_means = {
                    k: float(np.mean(vals))
                    for k, vals in epoch_host_vals.items()
                }
                self.callback_metrics.update(epoch_means)
                # _step-forked keys, like PTL's `loss_step`/`loss_epoch`
                # metric fidelity the reference asserts (test_ddp.py:326-352)
                self.callback_metrics.update(
                    {f"{k}_epoch": v for k, v in epoch_means.items()}
                )

            if (
                val_step is not None
                and val_epoch
                # A mid-epoch val that landed exactly on the final batch
                # already validated these params — unless the accumulation
                # flush just changed them.
                and (last_val_step != self.global_step or flushed)
                # A callback-requested stop means stop NOW — don't pay a
                # final val epoch on the way out (max_steps stops keep it:
                # the budgeted run still wants its terminal metrics).
                and not self.should_stop
            ):
                self._run_eval_epoch(val_step, self._val_loader, "val")
                self._call_callbacks("on_validation_end")

            self.module.on_train_epoch_end(epoch, dict(self.callback_metrics))
            self._call_callbacks("on_train_epoch_end")
            self._events.record(
                "trainer", "epoch_end", epoch=epoch, step=self.global_step
            )
            # Epoch end is the multi-process max_time boundary (and catches
            # budget expiry during the val epoch in any topology).
            if self._out_of_time(synced=True):
                self.should_stop = True
            if not self._preempt_per_step and self._preempt_pending(
                synced=True
            ):
                # Epoch-complete exit: resume starts the NEXT epoch.
                self._preempt_exit(None)

        self._record_fit_throughput(mult)
        self.telemetry.fit_done = True  # the fit-stall watchdog stands down
        self._events.record(
            "trainer", "fit_end", epochs=self.current_epoch + 1,
            step=self.global_step,
        )
        self.state = {"status": "finished", "stage": "fit"}
        self.module.params = self.params
        self.module.on_fit_end()
        self._call_callbacks("on_fit_end")
        # Drain any in-flight async save (collective: every rank) so the
        # last checkpoint is finalized before workers exit.
        self.finalize_checkpoints()
        self.strategy.teardown_worker()
        return self._collect_rank_zero_results(results=None)

    def _record_fit_throughput(self, mult: int) -> None:
        """Tokens/s + MFU into the telemetry when the module's shape is
        known (duck-typed: ``batch_size`` + ``config.max_seq``, i.e. LM
        modules). MFU additionally needs a known chip peak
        (utils/flops); on CPU it is omitted, never fabricated."""
        tel = getattr(self, "telemetry", None)
        if tel is None or tel.wall_s <= 0 or tel.steps == 0:
            return
        bs = getattr(self.module, "batch_size", None)
        seq = getattr(getattr(self.module, "config", None), "max_seq", None)
        if not bs or not seq:
            return
        tokens = int(bs) * max(1, int(mult)) * int(seq) * tel.steps
        fpt = peak = None
        if self.params is not None:
            import jax

            from ray_lightning_tpu.obs.telemetry import (
                flops_per_token,
                peak_flops_total,
            )

            n_params = sum(
                int(np.prod(np.shape(x)))
                for x in jax.tree_util.tree_leaves(self.params)
            )
            cfg = self.module.config
            n_layer = getattr(cfg, "n_layer", None)
            d_model = getattr(cfg, "d_model", None)
            if n_layer and d_model:
                fpt = flops_per_token(n_params, n_layer, d_model, int(seq))
                devs = jax.local_devices()
                if devs:
                    peak = peak_flops_total(
                        devs[0].device_kind, jax.device_count()
                    )
        tel.record_throughput(tokens, tel.wall_s, fpt, peak)

    def _ema_params(self) -> Optional[Any]:
        """Debias-corrected EMA weights from opt_state (None when EMA is
        off, no update has run, or opt_state is absent — eval-only restores
        ship params alone)."""
        if not self.spec.ema_decay or self.opt_state is None:
            return None
        from ray_lightning_tpu.trainer.ema import ema_params

        return ema_params(self.opt_state, float(self.spec.ema_decay))

    def _eval_params(self) -> Any:
        """Weights the eval/predict steps should see: the EMA copy when
        ``eval_ema`` is set, else the live params.

        In standalone validate/test/predict the EMA arrives from the
        checkpoint (module-state ``ema_params`` or the resume-format
        ``opt_state``) or the module's own recovered copy; asking for
        ``eval_ema`` with no EMA anywhere is an error, not a silent
        live-weights eval. During fit, a zero-update EMA (sanity val)
        falls back to live weights.
        """
        if not self.spec.eval_ema:
            return self.params
        ema = self._ema_params()
        if ema is None and getattr(self, "_eval_ema_src", None) is not None:
            ema = self.strategy.place_params(self._eval_ema_src)
        if ema is not None:
            return ema
        if self.spec.ema_decay and self.opt_state is not None:
            # Fit-time EMA pending its first update (sanity val): live
            # weights ARE the average so far.
            return self.params
        raise RuntimeError(
            "eval_ema=True but no EMA weights are available (fit with "
            "ema_decay=... first, or evaluate a checkpoint that carries "
            "the average; sharded eval-only restores don't materialize "
            "optimizer state, so use a state-stream checkpoint)"
        )

    def _run_eval_epoch(
        self,
        eval_step,
        loader,
        prefix: str,
        max_batches: Optional[int] = None,
    ) -> Dict[str, float]:
        import jax

        events = getattr(self, "_events", None)  # None outside a fit
        if events is not None:
            events.record(
                "trainer", "eval_epoch", stage=prefix,
                epoch=self.current_epoch, step=self.global_step,
            )
        mult = self.strategy.batch_multiplier
        limit = (
            self.spec.limit_test_batches
            if prefix == "test"
            else self.spec.limit_val_batches
        )
        n_batches = _limit(loader.num_batches(mult), limit)
        if max_batches is not None:
            n_batches = (
                max_batches if n_batches is None else min(n_batches, max_batches)
            )
        if n_batches is None and not getattr(self, "_warned_stream_eval", False):
            # Train epochs over unbounded streams are boundable with
            # max_steps; an eval epoch has no such brake.
            self._warned_stream_eval = True
            warnings.warn(
                "evaluating over a streaming (IterableDataset) loader with "
                "no batch limit: the eval epoch runs until the stream "
                "ends — set limit_val_batches/limit_test_batches (int) if "
                "the stream is unbounded",
                RuntimeWarning,
                stacklevel=2,
            )
        # Each step returns (per-key masked sums, real-sample count) — device
        # scalars, fetched once at the end. The weighted combine makes epoch
        # metrics exact on non-divisible datasets (padding rows carry zero
        # weight), matching the reference's exact-value contract
        # (test_ddp.py:326-352) without dynamic tail shapes.
        all_pairs: List[Any] = []
        # (batch, mask) tuples are one pytree: the stager transfers both in
        # the same overlapped H2D pipeline as the train path. islice bounds
        # the HOST iterator so the stager never prefetches (and transfers)
        # batches past the cutoff.
        import itertools

        # Eval folding (steps_per_execution): masked (sums, count) pairs
        # accumulate associatively, so scanning K eval batches in one
        # dispatch preserves the epoch means (up to fp32 summation order;
        # see compile_folded_eval_step) — pure dispatch amortization, no
        # cadence caveats. Folded executables cache per compiled eval
        # step (one per loop lifetime; shape-polymorphic in the fold).
        fold = max(1, int(self.spec.steps_per_execution))
        folded = None
        if fold > 1:
            cache = getattr(self, "_folded_eval_cache", None)
            if cache is None:
                cache = self._folded_eval_cache = {}
            folded = cache.get(eval_step)
            if folded is None:
                folded = cache[eval_step] = (
                    self.strategy.compile_folded_eval_step(eval_step)
                )
        staged = self.strategy.stage_batches(
            itertools.islice(
                loader.iter_batches(mult, with_mask=True), n_batches
            ),
            stack=fold if folded is not None else 0,
        )
        eval_params = self._eval_params()
        try:
            if folded is not None:
                for n, payload in staged:
                    step_fn = folded if n > 1 else eval_step
                    all_pairs.append(
                        step_fn(eval_params, payload[0], payload[1])
                    )
            else:
                for batch, gmask in staged:
                    all_pairs.append(eval_step(eval_params, batch, gmask))
        finally:
            staged.close()
        if not all_pairs:
            return {}
        fetched = jax.device_get(all_pairs)
        total = sum(float(count) for _, count in fetched)
        keys = fetched[0][0].keys()
        means = {
            k: float(sum(float(sums[k]) for sums, _ in fetched) / max(total, 1.0))
            for k in keys
        }
        self.callback_metrics.update(means)
        self.logged_metrics.update(means)
        if prefix in ("val", "validate"):
            self.module.on_validation_epoch_end(means)
        return means

    def run_evaluate(
        self, stage: str, ckpt_stream: Optional[bytes] = None
    ) -> Optional[WorkerOutput]:
        with self._anomaly_guard():
            return self._run_evaluate_impl(stage, ckpt_stream)

    def _run_evaluate_impl(
        self, stage: str, ckpt_stream: Optional[bytes] = None
    ) -> Optional[WorkerOutput]:
        self.state = {"status": "running", "stage": stage}
        self._setup_common()
        source = self.datamodule if self.datamodule is not None else self.module
        loader = (
            self._val_loader
            if stage in ("val", "validate")
            else source.test_dataloader()
        )
        if loader is not None and hasattr(loader, "with_sampler") and stage not in ("val", "validate"):
            skw = self.strategy.sampler_kwargs()
            loader = loader.with_sampler(
                num_replicas=skw["num_replicas"], rank=skw["rank"], seed=0
            )
        if loader is None:
            raise RuntimeError(f"{stage} requires a dataloader")
        self._restore_or_adopt(ckpt_stream)
        eval_step = self.strategy.compile_eval_step(self.module, stage)
        metrics = self._run_eval_epoch(eval_step, loader, stage)
        self.state = {"status": "finished", "stage": stage}
        self.strategy.teardown_worker()
        return self._collect_rank_zero_results(results=[metrics])

    def run_predict(
        self, ckpt_stream: Optional[bytes] = None
    ) -> Optional[WorkerOutput]:
        with self._anomaly_guard():
            return self._run_predict_impl(ckpt_stream)

    def _run_predict_impl(
        self, ckpt_stream: Optional[bytes] = None
    ) -> Optional[WorkerOutput]:
        self.state = {"status": "running", "stage": "predict"}
        self._setup_common()
        source = self.datamodule if self.datamodule is not None else self.module
        loader = source.predict_dataloader()
        if loader is not None and hasattr(loader, "with_sampler"):
            skw = self.strategy.sampler_kwargs()
            loader = loader.with_sampler(
                num_replicas=skw["num_replicas"], rank=skw["rank"], seed=0
            )
        if loader is None:
            raise RuntimeError("predict requires predict_dataloader()")
        self._restore_or_adopt(ckpt_stream)
        predict_step = self.strategy.compile_eval_step(self.module, "predict")
        import jax

        import itertools

        mult = self.strategy.batch_multiplier
        n_batches = _limit(
            loader.num_batches(mult), self.spec.limit_predict_batches
        )
        keep = self.spec.return_predictions
        # on_predict_end receives THIS RANK's predictions (PTL's
        # write_on_epoch_end contract): accumulate the local shards only
        # when some callback actually overrides the hook, independent of
        # whether the full set rides the rank-0 return channel.
        from ray_lightning_tpu.trainer.callbacks import Callback as _CB

        wants_end = any(
            type(cb).on_predict_end is not _CB.on_predict_end
            for cb in self.callbacks
            if isinstance(cb, _CB)
        )
        preds = []
        local_preds = []
        own_rows = None
        eval_params = self._eval_params()
        for bi, (host_batch, host_mask) in enumerate(
            itertools.islice(
                loader.iter_batches(mult, with_mask=True), n_batches
            )
        ):
            batch = self.strategy.make_global_batch(host_batch)
            gmask = self.strategy.make_global_batch(host_mask)
            out, mask = jax.device_get(predict_step(eval_params, batch, gmask))
            # Trim wrap-around padding rows so predictions line up 1:1 with
            # the dataset (mask comes back replicated alongside preds).
            mask = np.asarray(mask).astype(bool)
            if own_rows is None or len(own_rows) != len(mask):
                own_rows = self._owner_rows(gmask)
            # Callbacks receive THIS process's disjoint share of the rows
            # (PredictionWriter shards then partition the dataset exactly
            # once across ranks); the rank-0 result channel still carries
            # the full set when predictions are kept.
            local = jax.tree_util.tree_map(
                lambda p: np.asarray(p)[own_rows & mask], out
            )
            self._call_callbacks("on_predict_batch_end", local, bi)
            if wants_end:
                local_preds.append(local)
            # return_predictions=False: the full prediction dies here —
            # per-rank memory stays O(1 batch) (or O(local shard) with an
            # epoch-end consumer) and nothing crosses the rank-0 result
            # channel (the callbacks above already consumed it, e.g. a
            # PredictionWriter streaming shards to disk).
            if keep:
                preds.append(
                    local
                    if bool(own_rows.all())
                    else jax.tree_util.tree_map(
                        lambda p: np.asarray(p)[mask], out
                    )
                )
        self._call_callbacks(
            "on_predict_end", local_preds if wants_end else None
        )
        self.state = {"status": "finished", "stage": "predict"}
        self.strategy.teardown_worker()
        return self._collect_rank_zero_results(results=preds if keep else None)

    @staticmethod
    def _owner_rows(gmask: Any) -> "np.ndarray":
        """Boolean mask of global batch rows THIS process canonically owns.

        Derived from the assembled mask array's own sharding
        (``devices_indices_map``), so it makes no assumption about mesh
        device ordering; rows replicated across processes (model axes
        spanning hosts) go to the lowest-index owner. The per-process masks
        partition [0, G) exactly — PredictionWriter shards are disjoint and
        complete by construction.
        """
        import jax

        g = gmask.shape[0]
        if jax.process_count() == 1:
            return np.ones(g, dtype=bool)
        owner = np.full(g, np.iinfo(np.int32).max, dtype=np.int32)
        for d, idx in gmask.sharding.devices_indices_map(gmask.shape).items():
            sl = idx[0]
            owner[sl] = np.minimum(owner[sl], d.process_index)
        return owner == jax.process_index()

    def _restore_or_adopt(self, ckpt_stream: Optional[Any]) -> None:
        """Load params from a checkpoint (stream bytes or sharded orbax
        directory marker) or adopt the module's own."""
        sharded_path = (
            ckpt_stream.get("orbax_path")
            if isinstance(ckpt_stream, dict)
            else None
        )
        if sharded_path is not None:
            # Need placed abstract params to restore into; init a fresh tree
            # for shapes, then read the checkpoint over it.
            import jax

            sample_batch = next(
                iter(self._train_or_any_loader().iter_batches(1, prefetch=0))
            )
            init_rng, self._rng = jax.random.split(self._rng)
            params = self.module.init_params(init_rng, sample_batch)
            placed = self.strategy.place_params(params)
            from ray_lightning_tpu.trainer.checkpoint_io import (
                OrbaxCheckpointIO,
            )

            # On-disk tree also carries opt_state — eval only needs params,
            # so restore partially rather than materialising optimizer
            # shards we'd immediately drop.
            restored, _ = OrbaxCheckpointIO().restore(
                sharded_path, {"params": placed}, partial=True
            )
            self.params = restored["params"]
            return
        if ckpt_stream is not None:
            state = load_state_stream(ckpt_stream)
            params = state["params"] if "params" in state else state
            if isinstance(state, dict):
                if state.get("ema_params") is not None:
                    self._eval_ema_src = state["ema_params"]
                elif self.spec.eval_ema and "opt_state" in state:
                    # Resume-format checkpoints carry the EMA inside the
                    # optimizer state; debiasing materializes a full
                    # param-sized copy, so only do it when eval will
                    # actually read it.
                    from ray_lightning_tpu.trainer.ema import ema_params

                    self._eval_ema_src = ema_params(state["opt_state"])
        elif self.module.params is not None:
            params = self.module.params
            self._eval_ema_src = self.module.ema_params
        else:
            raise RuntimeError(
                "no parameters available: fit first, or pass ckpt_path"
            )
        self.params = self.strategy.place_params(params)

    def _train_or_any_loader(self) -> Any:
        """A loader usable as an init-shape probe (train if defined, else
        val/test/predict)."""
        if self._train_loader is not None:
            return self._train_loader
        source = self.datamodule if self.datamodule is not None else self.module
        for name in ("val_dataloader", "test_dataloader", "predict_dataloader"):
            loader = getattr(source, name, lambda: None)()
            if loader is not None:
                return loader
        raise RuntimeError("no dataloader available to probe init shapes")

    def _gathered_module_state_stream(self) -> Optional[bytes]:
        """Gather module state on EVERY rank; serialize on rank 0 only.

        ``gather_state`` is a jitted all-gather — under multi-process
        sharding (ZeRO/GSPMD spanning hosts) it is a collective that every
        rank must enter. For plain-device_get strategies (DP/ring) the
        non-zero ranks skip the gather entirely: participating would only
        copy full state to host and discard it.
        """
        if self.params is None:
            return None
        if self.global_rank != 0 and not self.strategy.gather_is_collective:
            return None
        module_state = dict(self.module.state_dict())
        module_state["params"] = self.strategy.gather_state(self.params)
        ema_dev = self._ema_params()
        if ema_dev is not None:
            module_state["ema_params"] = self.strategy.gather_state(ema_dev)
        elif getattr(self, "_eval_ema_src", None) is not None:
            # Eval-only run restored the average from a checkpoint:
            # re-ship it (already host-side) so recovery keeps it.
            module_state["ema_params"] = self._eval_ema_src
        if (
            self.opt_state is not None
            and self.state.get("stage") == "fit"
            and self.spec.ship_optimizer_state
        ):
            # Ship optimizer state so the driver's save_checkpoint()
            # writes resumable files (Adam moments + embedded LR
            # schedule continue instead of silently restarting).
            module_state["opt_state"] = self.strategy.gather_state(
                self.opt_state
            )
        if self.global_rank != 0:
            return None
        return to_state_stream(module_state)

    # ------------------------------------------------------------------
    def _collect_rank_zero_results(self, results: Any) -> Optional[WorkerOutput]:
        """Package rank-0 state for the driver (the reference's
        ``_collect_rank_zero_results``, ray_launcher.py:312-349: rank!=0
        returns None; weights go host-side as bytes; metrics cross as
        numpy).

        The state gathers run on EVERY rank before the rank gate:
        ``gather_state`` is a jitted all-gather, which under multi-process
        sharding (ZeRO/GSPMD spanning hosts) is a collective — a
        rank-0-only call would deadlock waiting for peers that already
        moved on.
        """
        state_stream = self._gathered_module_state_stream()
        if self.global_rank != 0:
            return None
        best_model_path = None
        callback_states: Dict[str, Any] = {}
        for cb in self.callbacks:
            callback_states[type(cb).__name__] = cb.state_dict()
            if hasattr(cb, "best_model_path") and cb.best_model_path:
                best_model_path = cb.best_model_path
        trainer_state = dict(
            self.state,
            epoch=self.current_epoch,
            global_step=self.global_step,
            update_count=self._update_count,
        )
        if self.state.get("stage") == "fit":
            # Whether the fit stopped mid-epoch (max_steps/should_stop):
            # the driver records it so its save_checkpoint() files resume
            # with the same re-run-the-epoch semantics as worker-written
            # checkpoints (incl. the MultiSteps window reset).
            trainer_state["mid_epoch"] = not getattr(
                self, "_epoch_complete", True
            )
            if getattr(self, "telemetry", None) is not None:
                # Step-time breakdown + compile events + throughput; the
                # driver surfaces it as trainer.state["telemetry"].
                trainer_state["telemetry"] = self.telemetry.snapshot()
        return WorkerOutput(
            best_model_path=best_model_path,
            state_stream=state_stream,
            trainer_state=dict(
                trainer_state,
                # Evaluated HERE because the worker owns a live backend;
                # the driver must not init one (on TPU hosts the chips
                # belong to worker processes — driver init would bind them).
                current_lr=self.current_lr,
            ),
            results=results,
            callback_metrics={
                k: np.asarray(v) for k, v in self.callback_metrics.items()
            },
            logged_metrics={
                k: np.asarray(v) for k, v in self.logged_metrics.items()
            },
            callback_states=callback_states,
        )
