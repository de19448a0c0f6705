"""Callbacks: host-side hooks at step/epoch boundaries.

The reference reuses PTL callbacks (ModelCheckpoint/EarlyStopping are
exercised by test_ddp.py:241-247,289-308); this framework defines its own,
with the TPU-specific constraint that callbacks run *between* compiled steps
— they can read aggregated metrics (already on host) but never reach inside
the jitted step. Checkpoint IO is rank-0 only, mirroring the reference's
rank-zero discipline (ray_ddp.py:169).
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional

import numpy as np


class Callback:
    def on_fit_start(self, trainer: Any, module: Any) -> None: ...

    def on_train_epoch_start(self, trainer: Any, module: Any) -> None: ...

    def on_train_batch_end(
        self, trainer: Any, module: Any, logs: Dict[str, float], batch_idx: int
    ) -> None: ...

    def on_train_epoch_end(self, trainer: Any, module: Any) -> None: ...

    def on_validation_end(self, trainer: Any, module: Any) -> None: ...

    def on_fit_end(self, trainer: Any, module: Any) -> None: ...

    def on_predict_batch_end(
        self, trainer: Any, module: Any, prediction: Any, batch_idx: int
    ) -> None: ...

    def on_predict_end(
        self, trainer: Any, module: Any, predictions: Any
    ) -> None: ...

    def state_dict(self) -> Dict[str, Any]:
        return {}

    def load_state_dict(self, state: Dict[str, Any]) -> None: ...


def _remove_checkpoint(path: str) -> None:
    """Delete a checkpoint file or sharded checkpoint directory."""
    import shutil

    try:
        if os.path.isdir(path):
            shutil.rmtree(path)
        else:
            os.remove(path)
    except OSError:
        pass


def _metric_value(trainer: Any, monitor: str) -> Optional[float]:
    val = trainer.callback_metrics.get(monitor)
    if val is None:
        return None
    return float(np.asarray(val))


class ModelCheckpoint(Callback):
    """Save the training state each validation/epoch end; track the best.

    Files are state-stream checkpoints (utils/state_stream.py) containing
    params + optimizer state + loop counters, so resume restores exactly;
    ``save_weights_only=True`` leaves the optimizer state out (a third of
    the bytes under Adam; enough to serve or evaluate from).
    ``best_model_path`` propagates to the driver in the worker output, like
    the reference's (ray_launcher.py:319-321, :357-360).
    """

    def __init__(
        self,
        dirpath: Optional[str] = None,
        filename: str = "epoch={epoch}-step={step}",
        monitor: Optional[str] = None,
        mode: str = "min",
        save_top_k: int = 1,
        save_last: bool = False,
        save_sharded: bool = False,
        save_weights_only: bool = False,
    ) -> None:
        assert mode in ("min", "max")
        if save_sharded and save_weights_only:
            raise ValueError(
                "save_weights_only applies to state-stream files; a sharded "
                "(orbax) checkpoint always carries the optimizer state"
            )
        self.save_sharded = save_sharded
        self.save_weights_only = save_weights_only
        self.dirpath = dirpath
        self.filename = filename
        self.monitor = monitor
        self.mode = mode
        self.save_top_k = save_top_k
        self.save_last = save_last
        self.best_model_path: str = ""
        self.best_model_score: Optional[float] = None
        self.last_model_path: str = ""
        self._saved: list[tuple[float, str]] = []

    def _is_better(self, score: float) -> bool:
        if self.best_model_score is None:
            return True
        if self.mode == "min":
            return score < self.best_model_score
        return score > self.best_model_score

    def on_validation_end(self, trainer: Any, module: Any) -> None:
        # PTL semantics: the pre-train sanity pass must not checkpoint —
        # its metrics are discarded, so a "best" score from 2 sanity batches
        # would pin best_model_path at untrained params.
        if getattr(trainer, "sanity_checking", False):
            return
        self._save(trainer, module)

    def on_train_epoch_end(self, trainer: Any, module: Any) -> None:
        # Only save here when there is no val loop (val end already saved).
        if not trainer.has_validation:
            self._save(trainer, module)

    def _save(self, trainer: Any, module: Any) -> None:
        if self.save_top_k == 0:
            return
        if (
            trainer.global_rank != 0
            and not self.save_sharded
            and not getattr(trainer, "gather_is_collective", False)
        ):
            # Plain-device_get strategies: nothing for non-zero ranks to
            # do. (Collective gathers need every rank below.)
            return
        dirpath = self.dirpath or os.path.join(trainer.default_root_dir, "checkpoints")
        os.makedirs(dirpath, exist_ok=True)
        name = self.filename.format(epoch=trainer.current_epoch, step=trainer.global_step)
        if self.save_sharded:
            # Directory checkpoint; every rank writes its shards (the
            # orbax save is collective), rank 0 keeps the bookkeeping.
            path = os.path.join(dirpath, name)
            trainer.save_checkpoint(path, sharded=True)
            if self.save_last:
                last = os.path.join(dirpath, "last")
                trainer.save_checkpoint(last, sharded=True)
                self.last_model_path = last
            if self.monitor is not None and self.save_top_k >= 0:
                # Pruning may delete the save just dispatched (worst
                # score). EVERY rank must drain its async writes BEFORE
                # rank 0 rmtree's — only rank 0 reaches _prune, so a
                # drain there would leave ranks >0 writing into a
                # deleted directory. (No-monitor mode prunes only the
                # PREVIOUS save, which the next dispatch already
                # finalized — full overlap is kept there.)
                getattr(trainer, "finalize_checkpoints", lambda: None)()
            if trainer.global_rank != 0:
                return
        else:
            # EVERY rank enters save_checkpoint: its state gather is a
            # collective under multi-process sharding (a rank-0-only call
            # deadlocks); rank 0 alone writes bytes and keeps bookkeeping.
            path = os.path.join(dirpath, name + ".ckpt")
            trainer.save_checkpoint(path, weights_only=self.save_weights_only)
            last = None
            if self.save_last:
                last = os.path.join(dirpath, "last.ckpt")
                trainer.save_checkpoint(last, weights_only=self.save_weights_only)
            if trainer.global_rank != 0:
                return
            if last:
                self.last_model_path = last
        score = _metric_value(trainer, self.monitor) if self.monitor else None
        if self.monitor is None:
            # No monitor: latest checkpoint is "best" (Lightning behavior)
            # and the previous one is pruned so only save_top_k remain.
            # (prev predates the save that just ran, so with async IO it
            # was finalized when this save started — safe to delete.)
            prev = self.best_model_path
            self.best_model_path = path
            if (
                self.save_top_k == 1
                and prev
                and prev != path
                and os.path.exists(prev)
            ):
                _remove_checkpoint(prev)
        elif score is not None and not math.isnan(score):
            if self._is_better(score):
                self.best_model_score = score
                self.best_model_path = path
            self._saved.append((score, path))
            self._prune(trainer)
        # (Non-sharded save_last happens above, before the rank gate — the
        # collective gather needs every rank.)

    def _prune(self, trainer: Any = None) -> None:
        # Deletion targets are always durable here: the monitored sharded
        # path drains every rank's async writes in _save before rank 0
        # gets this far.
        if self.save_top_k < 0:
            return
        reverse = self.mode == "max"
        self._saved.sort(key=lambda t: t[0], reverse=reverse)
        while len(self._saved) > self.save_top_k:
            _, path = self._saved.pop()
            if path != self.best_model_path and os.path.exists(path):
                _remove_checkpoint(path)

    def state_dict(self) -> Dict[str, Any]:
        return {
            "best_model_path": self.best_model_path,
            "best_model_score": self.best_model_score,
            "last_model_path": self.last_model_path,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.best_model_path = state.get("best_model_path", "")
        self.best_model_score = state.get("best_model_score")
        self.last_model_path = state.get("last_model_path", "")


class EarlyStopping(Callback):
    """Stop training when a monitored metric stops improving.

    PTL-parity knobs beyond patience: ``stopping_threshold`` stops as soon
    as the metric is at least this good (the goal is reached),
    ``divergence_threshold`` stops as soon as it is at least this BAD (the
    run is unrecoverable), and ``check_finite`` stops on NaN/inf instead
    of skipping the reading.
    """

    def __init__(
        self,
        monitor: str = "val_loss",
        patience: int = 3,
        mode: str = "min",
        min_delta: float = 0.0,
        stopping_threshold: Optional[float] = None,
        divergence_threshold: Optional[float] = None,
        check_finite: bool = False,
    ) -> None:
        assert mode in ("min", "max")
        self.monitor = monitor
        self.patience = patience
        self.mode = mode
        self.min_delta = abs(min_delta)
        self.stopping_threshold = stopping_threshold
        self.divergence_threshold = divergence_threshold
        self.check_finite = check_finite
        self.wait = 0
        self.best: Optional[float] = None

    def _improved(self, score: float) -> bool:
        if self.best is None:
            return True
        if self.mode == "min":
            return score < self.best - self.min_delta
        return score > self.best + self.min_delta

    def _beats(self, score: float, threshold: float) -> bool:
        return score <= threshold if self.mode == "min" else score >= threshold

    def on_validation_end(self, trainer: Any, module: Any) -> None:
        if getattr(trainer, "sanity_checking", False):
            return  # discarded sanity metrics must not seed best/wait
        score = _metric_value(trainer, self.monitor)
        if score is None:
            return
        if not math.isfinite(score):
            if self.check_finite:
                trainer.should_stop = True
            return
        if self.stopping_threshold is not None and self._beats(
            score, self.stopping_threshold
        ):
            trainer.should_stop = True
            return
        if self.divergence_threshold is not None and not self._beats(
            score, self.divergence_threshold
        ):
            trainer.should_stop = True
            return
        if self._improved(score):
            self.best = score
            self.wait = 0
        else:
            self.wait += 1
            if self.wait >= self.patience:
                trainer.should_stop = True

    def state_dict(self) -> Dict[str, Any]:
        return {"wait": self.wait, "best": self.best}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.wait = state.get("wait", 0)
        self.best = state.get("best")


class TPUStatsCallback(Callback):
    """Per-epoch wall time and device memory stats, averaged across hosts.

    TPU-native answer to the reference's example-level ``CUDACallback``
    (examples/ray_ddp_sharded_example.py:16-46), which measured epoch time and
    peak CUDA memory. Uses ``device.memory_stats()`` where the PJRT backend
    exposes it.

    ``flops_per_step`` — model FLOPs per EXECUTED training step, i.e. per
    micro-batch across all workers (e.g. ``6 * n_params *
    tokens_per_global_micro_batch`` for a transformer; with
    ``accumulate_grad_batches`` each micro-batch still runs a full
    fwd+bwd, so this is the honest compute unit) — additionally reports
    per-epoch MFU against the published bf16 peak of ALL the run's chips
    (``trainer.world_size``; ``utils/flops.py``). Skipped on devices with
    no known peak (CPU).
    """

    def __init__(
        self, verbose: bool = True, flops_per_step: Optional[float] = None
    ) -> None:
        self.flops_per_step = flops_per_step
        self.verbose = verbose
        self.epoch_times: list[float] = []
        self.peak_memory: list[float] = []
        self.mfu: list[float] = []
        self.steps_per_sec: list[float] = []
        self._t0 = 0.0
        self._step0 = 0

    @staticmethod
    def _fence(trainer: Any) -> None:
        # Drain in-flight device work so the timer is honest. effects_barrier
        # alone is NOT enough: it only waits for effectful ops, while the
        # loop's async step dispatches can still be queued — blocking on the
        # live params fences the real computation stream.
        import jax

        if getattr(trainer, "params", None) is not None:
            jax.block_until_ready(trainer.params)
        jax.effects_barrier()

    def on_train_epoch_start(self, trainer: Any, module: Any) -> None:
        import time

        self._fence(trainer)
        self._t0 = time.perf_counter()
        self._step0 = trainer.global_step

    def on_train_epoch_end(self, trainer: Any, module: Any) -> None:
        import time

        import jax

        self._fence(trainer)
        dt = time.perf_counter() - self._t0
        self.epoch_times.append(dt)
        steps_done = trainer.global_step - self._step0
        if dt > 0 and steps_done > 0:
            # Per-host step rate; a user-facing throughput number without
            # extra syncs (the fence above already paid the only one).
            sps = steps_done / dt
            self.steps_per_sec.append(sps)
            trainer.callback_metrics["steps_per_sec"] = sps
        peak = 0.0
        for dev in jax.local_devices():
            try:
                stats = dev.memory_stats() or {}
                peak = max(peak, float(stats.get("peak_bytes_in_use", 0)))
            except Exception:  # noqa: BLE001 - CPU backend has no stats
                pass
        self.peak_memory.append(peak)
        mfu = None
        if self.flops_per_step and dt > 0:
            from ray_lightning_tpu.utils.flops import peak_flops_for

            devs = jax.local_devices()
            peak_fl = peak_flops_for(devs[0].device_kind) if devs else None
            if peak_fl:
                # flops_per_step covers the GLOBAL micro-batch, so the
                # denominator is the peak of every chip in the run, not
                # just this process's.
                chips = max(
                    int(getattr(trainer, "world_size", 0) or 0), len(devs)
                )
                steps = trainer.global_step - self._step0
                mfu = (steps * float(self.flops_per_step) / dt) / (
                    peak_fl * chips
                )
                self.mfu.append(mfu)
                trainer.callback_metrics["mfu"] = mfu
        if self.verbose and trainer.global_rank == 0:
            print(
                f"[epoch {trainer.current_epoch}] time {dt:.3f}s"
                + (f", peak device mem {peak / 2**20:.1f} MiB" if peak else "")
                + (f", MFU {mfu:.3f}" if mfu is not None else "")
            )

    def state_dict(self) -> Dict[str, Any]:
        # Measurements ride the callback-state sync back to the driver.
        return {
            "epoch_times": self.epoch_times,
            "peak_memory": self.peak_memory,
            "mfu": self.mfu,
            "steps_per_sec": self.steps_per_sec,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.epoch_times = list(state.get("epoch_times", []))
        self.peak_memory = list(state.get("peak_memory", []))
        self.mfu = list(state.get("mfu", []))
        self.steps_per_sec = list(state.get("steps_per_sec", []))


class JaxProfilerCallback(Callback):
    """Capture a ``jax.profiler`` trace for selected training epochs.

    TPU-native profiling (SURVEY.md §5 tracing): writes TensorBoard-loadable
    traces (XLA ops, fusion, HBM transfers, ICI collectives) under
    ``dirpath/plugins/profile``. Runs on worker rank 0 only; epoch 1 by
    default — epoch 0 is dominated by compilation.

    View with: ``tensorboard --logdir <dirpath>`` (Profile tab), or feed the
    ``.trace.json.gz`` to Perfetto.
    """

    def __init__(
        self,
        dirpath: str = "jax_trace",
        epochs: tuple = (1,),
        create_perfetto_trace: bool = False,
    ) -> None:
        self.dirpath = dirpath
        self.epochs = tuple(epochs)
        self.create_perfetto_trace = create_perfetto_trace
        self.trace_dirs: list[str] = []
        self._active = False

    def on_train_epoch_start(self, trainer: Any, module: Any) -> None:
        if trainer.global_rank != 0 or trainer.current_epoch not in self.epochs:
            return
        import jax

        os.makedirs(self.dirpath, exist_ok=True)
        # Fence so the trace contains only this epoch's work.
        TPUStatsCallback._fence(trainer)
        jax.profiler.start_trace(
            self.dirpath, create_perfetto_trace=self.create_perfetto_trace
        )
        self._active = True

    def on_train_epoch_end(self, trainer: Any, module: Any) -> None:
        if not self._active:
            return
        import jax

        TPUStatsCallback._fence(trainer)
        jax.profiler.stop_trace()
        self._active = False
        self.trace_dirs.append(self.dirpath)

    def state_dict(self) -> Dict[str, Any]:
        return {"trace_dirs": self.trace_dirs}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.trace_dirs = list(state.get("trace_dirs", []))


class LearningRateMonitor(Callback):
    """Log the schedule-driven learning rate as a ``lr`` metric.

    Works with modules whose ``configure_optimizers`` declares a schedule
    (``{"optimizer": tx, "lr_schedule": fn}`` or ``(tx, fn)`` — see
    ``TPUModule.configure_optimizers``). optax embeds schedules inside the
    gradient transform, so this reads the declared ``step -> lr`` callable at
    the loop's current optimizer-update index; no device sync. PTL-parity
    for the ``LearningRateMonitor`` users attach to the reference's Trainer.
    """

    def __init__(self, key: str = "lr") -> None:
        self.key = key

    def on_train_batch_end(
        self, trainer: Any, module: Any, logs: Dict[str, float], batch_idx: int
    ) -> None:
        lr = getattr(trainer, "current_lr", None)
        if lr is not None:
            trainer.logged_metrics[self.key] = lr
            # Also publish to callback_metrics here so epoch-end consumers
            # (CSVLogger, ModelCheckpoint monitors) see this epoch's lr
            # regardless of their position in the callbacks list.
            trainer.callback_metrics[self.key] = lr

    def on_train_epoch_end(self, trainer: Any, module: Any) -> None:
        lr = getattr(trainer, "current_lr", None)
        if lr is not None:
            trainer.callback_metrics[self.key] = lr


class TensorBoardLogger(Callback):
    """Scalar metrics to TensorBoard event files (rank 0 only).

    The PTL-style logger reference users attach for dashboards; pairs
    with ``JaxProfilerCallback``, whose traces land in the same
    TensorBoard UI. Per-step train metrics are written at the
    ``log_every_n_steps`` cadence (the host values the loop already
    fetched — no extra device syncs); validation metrics at each val end.
    Requires the ``tensorboard`` package (present in this image); raises
    a clear ImportError otherwise.
    """

    def __init__(
        self, dirpath: Optional[str] = None, name: str = "tb"
    ) -> None:
        try:
            from tensorboard.summary.writer.event_file_writer import (  # noqa: F401
                EventFileWriter,
            )
        except ImportError as exc:  # pragma: no cover - baked into image
            raise ImportError(
                "TensorBoardLogger needs the 'tensorboard' package"
            ) from exc
        self.dirpath = dirpath
        self.name = name
        self._writer: Any = None
        self._log_dir: Optional[str] = None

    @property
    def log_dir(self) -> Optional[str]:
        """Directory holding the event file (resolved at fit start)."""
        return self._log_dir

    def _ensure_writer(self, trainer: Any) -> Any:
        if self._writer is None:
            from tensorboard.summary.writer.event_file_writer import (
                EventFileWriter,
            )

            base = self.dirpath or os.path.join(
                trainer.default_root_dir, "tensorboard"
            )
            self._log_dir = os.path.join(base, self.name)
            os.makedirs(self._log_dir, exist_ok=True)
            self._writer = EventFileWriter(self._log_dir)
        return self._writer

    def _write_scalars(
        self, trainer: Any, metrics: Dict[str, Any], step: int
    ) -> None:
        import time

        from tensorboard.compat.proto.event_pb2 import Event
        from tensorboard.compat.proto.summary_pb2 import Summary

        values = []
        for k, v in metrics.items():
            try:
                values.append(
                    Summary.Value(tag=k, simple_value=float(np.asarray(v)))
                )
            except (TypeError, ValueError):
                continue
        if not values:
            return
        self._ensure_writer(trainer).add_event(
            Event(
                wall_time=time.time(), step=step, summary=Summary(value=values)
            )
        )

    def on_train_batch_end(
        self, trainer: Any, module: Any, logs: Dict[str, float], batch_idx: int
    ) -> None:
        if trainer.global_rank == 0 and logs:
            self._write_scalars(trainer, logs, trainer.global_step)

    def on_validation_end(self, trainer: Any, module: Any) -> None:
        if trainer.global_rank != 0 or getattr(
            trainer, "sanity_checking", False
        ):
            return
        # "val_loss" and namespaced forms like "ptl/val_loss" — but NOT
        # train metrics that merely contain the substring (eval_loss,
        # interval_loss).
        val = {
            k: v
            for k, v in trainer.callback_metrics.items()
            if k.split("/")[-1].startswith("val")
        }
        self._write_scalars(trainer, val, trainer.global_step)

    def on_fit_end(self, trainer: Any, module: Any) -> None:
        if self._writer is not None:
            self._writer.flush()
            self._writer.close()
            self._writer = None

    def state_dict(self) -> Dict[str, Any]:
        # The log dir rides the callback sync so the DRIVER-side object
        # can point users at the files the worker wrote.
        return {"log_dir": self._log_dir}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self._log_dir = state.get("log_dir") or self._log_dir


class CSVLogger(Callback):
    """Append one metrics row per epoch to ``dirpath/metrics.csv``.

    Lightweight stand-in for the PTL loggers reference users attach to their
    Trainer; rank-0 only, header grows with newly-seen metric keys (rows are
    rewritten when the key set expands).
    """

    def __init__(self, dirpath: Optional[str] = None, name: str = "metrics.csv") -> None:
        self.dirpath = dirpath
        self.name = name
        self.rows: list[Dict[str, Any]] = []
        self._resolved_dir: Optional[str] = dirpath

    @property
    def log_path(self) -> str:
        """Path of the written CSV (resolved against the trainer's root dir
        once a fit has run)."""
        return os.path.join(self._resolved_dir or self.dirpath or ".", self.name)

    def on_train_epoch_end(self, trainer: Any, module: Any) -> None:
        if trainer.global_rank != 0:
            return
        row: Dict[str, Any] = {
            "epoch": trainer.current_epoch,
            "step": trainer.global_step,
        }
        for k, v in trainer.callback_metrics.items():
            try:
                row[k] = float(np.asarray(v))
            except (TypeError, ValueError):
                continue
        self.rows.append(row)
        self._write(trainer)

    def _write(self, trainer: Any = None) -> None:
        import csv

        dirpath = self.dirpath or (
            trainer.default_root_dir
            if trainer is not None
            else self._resolved_dir or "."
        )
        self._resolved_dir = dirpath
        os.makedirs(dirpath, exist_ok=True)
        path = os.path.join(dirpath, self.name)
        keys: list[str] = []
        for row in self.rows:
            for k in row:
                if k not in keys:
                    keys.append(k)
        with open(path, "w", newline="") as f:
            writer = csv.DictWriter(f, fieldnames=keys)
            writer.writeheader()
            writer.writerows(self.rows)

    def state_dict(self) -> Dict[str, Any]:
        # Rows ride the callback sync so the DRIVER-side logger instance can
        # rewrite the file locally after a distributed fit.
        return {"rows": self.rows, "dirpath": self._resolved_dir}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.rows = list(state.get("rows", []))
        self._resolved_dir = self.dirpath or state.get("dirpath")
        if self.rows:
            # Rewrite locally: in client mode the worker's file lives on the
            # remote head's filesystem; the driver needs its own copy.
            self._write()


class StochasticWeightAveraging(Callback):
    """Equal-weight average of params along the training trajectory
    (Izmailov et al. 2018), PTL's ``StochasticWeightAveraging`` analog.

    From ``swa_epoch_start`` on, the end-of-epoch params are folded into a
    host-side running average (``avg += (params - avg) / n``); at fit end
    the averaged weights replace the live ones (``swap_params=False`` keeps
    them aside as ``.swa_params`` instead). Three averaging flavors now
    exist, picked by cadence: in-step decayed EMA (``Trainer(ema_decay=)``),
    epoch-cadence equal SWA (this), and post-hoc checkpoint soups
    (``average_checkpoints``).

    TPU notes: the average lives on HOST memory (no HBM cost); collection
    runs at epoch cadence so the gather never blocks the step stream. Every
    rank computes the same average — ``gather_state`` is a collective under
    sharded strategies, mirroring ModelCheckpoint's every-rank discipline.
    """

    def __init__(
        self, swa_epoch_start: Any = 0.8, swap_params: bool = True
    ) -> None:
        if isinstance(swa_epoch_start, float) and not 0 <= swa_epoch_start <= 1:
            raise ValueError(
                f"float swa_epoch_start must be in [0, 1], got {swa_epoch_start}"
            )
        if isinstance(swa_epoch_start, int) and swa_epoch_start < 0:
            raise ValueError(
                f"int swa_epoch_start must be >= 0, got {swa_epoch_start}"
            )
        self.swa_epoch_start = swa_epoch_start
        self.swap_params = swap_params
        self.n_models = 0
        self.swa_params: Any = None

    def _start_epoch(self, trainer: Any) -> int:
        if isinstance(self.swa_epoch_start, float):
            max_epochs = getattr(
                getattr(trainer, "spec", trainer), "max_epochs", 1
            )
            return int(self.swa_epoch_start * max_epochs)
        return int(self.swa_epoch_start)

    def on_train_epoch_end(self, trainer: Any, module: Any) -> None:
        if trainer.current_epoch < self._start_epoch(trainer):
            return
        import jax

        params = trainer.strategy.gather_state(trainer.params)
        self.n_models += 1
        n = self.n_models
        if self.swa_params is None:
            self.swa_params = params
        else:
            self.swa_params = jax.tree_util.tree_map(
                lambda avg, p: avg + (np.asarray(p, avg.dtype) - avg) / n,
                self.swa_params,
                params,
            )

    def on_fit_end(self, trainer: Any, module: Any) -> None:
        if self.swa_params is None or not self.swap_params:
            return
        # The fit is over (no steps follow), so host arrays are fine here;
        # the rank-0 result collection device_gets them unchanged.
        trainer.params = self.swa_params
        module.params = self.swa_params

    def state_dict(self) -> Dict[str, Any]:
        # The running average rides checkpoints so fault-tolerant restarts
        # (Trainer(max_restarts=)) keep collecting instead of starting over.
        return {"n_models": self.n_models, "swa_params": self.swa_params}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.n_models = int(state.get("n_models", 0))
        self.swa_params = state.get("swa_params")


class PredictionWriter(Callback):
    """Per-rank streaming prediction writer (PTL's BasePredictionWriter).

    Large-scale inference on a pod can't funnel every prediction through
    the rank-0 result channel; each rank instead writes ITS shard of
    predictions (the loop hands callbacks disjoint per-process row sets
    that partition each batch exactly once) to ``output_dir`` as
    state-stream files readable with :meth:`read`. ``write_interval="batch"`` streams one file per batch —
    pair it with ``predict(return_predictions=False)`` and per-rank memory
    stays O(1 batch), with nothing shipped through the result channel;
    ``"epoch"`` writes a single file per rank at the end (this rank's
    accumulated shard — O(dataset/world) memory, independent of
    return_predictions).
    """

    def __init__(self, output_dir: str, write_interval: str = "batch") -> None:
        if write_interval not in ("batch", "epoch"):
            raise ValueError(
                f"write_interval must be 'batch' or 'epoch', got "
                f"{write_interval!r}"
            )
        self.output_dir = output_dir
        self.write_interval = write_interval
        self.written_paths: list = []

    def _write(self, tree: Any, path: str) -> None:
        from ray_lightning_tpu.utils.state_stream import (
            state_stream_to_file,
            to_state_stream,
        )

        os.makedirs(self.output_dir, exist_ok=True)
        state_stream_to_file(to_state_stream(tree), path)
        self.written_paths.append(path)

    def on_predict_batch_end(
        self, trainer: Any, module: Any, prediction: Any, batch_idx: int
    ) -> None:
        if self.write_interval != "batch":
            return
        self._write(
            prediction,
            os.path.join(
                self.output_dir,
                f"predictions_rank{trainer.global_rank}"
                f"_batch{batch_idx:05d}.npz",
            ),
        )

    def on_predict_end(self, trainer: Any, module: Any, predictions: Any) -> None:
        if self.write_interval != "epoch":
            return
        if predictions is None:
            return
        self._write(
            predictions,
            os.path.join(
                self.output_dir,
                f"predictions_rank{trainer.global_rank}.npz",
            ),
        )

    @staticmethod
    def read(path: str) -> Any:
        """Load one written prediction file back as its host pytree."""
        from ray_lightning_tpu.utils.state_stream import load_state_stream

        with open(path, "rb") as f:
            return load_state_stream(f.read())

    def state_dict(self) -> Dict[str, Any]:
        # Paths ride the callback sync so the driver can locate per-rank
        # shards after a distributed predict (shared-FS assumption, same
        # as best_model_path propagation).
        return {"written_paths": self.written_paths}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.written_paths = list(state.get("written_paths", []))
