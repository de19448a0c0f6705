"""In-process trainer tests: loops, metrics, checkpointing, callbacks.

These cover the loop engine without spawning actors (fast), the way the
reference leans on PTL's own tested loop; here the loop is ours so it needs
first-party coverage.
"""
import os

import numpy as np
import pytest

from ray_lightning_tpu.models import BoringModule, MNISTClassifier, XORModule
from ray_lightning_tpu.models.xor import XORDataModule
from ray_lightning_tpu.trainer import (
    EarlyStopping,
    ModelCheckpoint,
    Trainer,
)
from tests.utils import get_trainer, train_test, predict_test


def test_fit_changes_weights():
    train_test(get_trainer(max_epochs=1), BoringModule())


def test_validation_and_test_and_predict():
    module = BoringModule()
    trainer = get_trainer(max_epochs=1)
    trainer.fit(module)
    assert "val_loss" in trainer.callback_metrics
    res = trainer.test(module)
    assert "test_loss" in res[0]
    preds = trainer.predict(module)
    assert len(preds) > 0 and preds[0].shape[-1] == 2


def test_prediction_writer_streams_per_rank_files(tmp_path):
    """PredictionWriter streams each rank's prediction shard to disk:
    per-batch files whose concatenation round-trips to the returned
    predictions, or one per-rank file in epoch mode."""
    from ray_lightning_tpu.trainer import PredictionWriter

    module = BoringModule()
    get_trainer(max_epochs=1).fit(module)  # params to predict with
    out_b = str(tmp_path / "batchwise")
    pw = PredictionWriter(out_b, write_interval="batch")
    trainer = get_trainer(max_epochs=1, callbacks=[pw])
    preds = trainer.predict(module)
    assert pw.written_paths and all(os.path.exists(p) for p in pw.written_paths)
    assert len(pw.written_paths) == len(preds)
    loaded = np.concatenate(
        [PredictionWriter.read(p) for p in sorted(pw.written_paths)]
    )
    np.testing.assert_allclose(loaded, np.concatenate(preds), rtol=1e-6)

    out_e = str(tmp_path / "epochwise")
    pw_e = PredictionWriter(out_e, write_interval="epoch")
    trainer2 = get_trainer(max_epochs=1, callbacks=[pw_e])
    preds2 = trainer2.predict(module)
    assert len(pw_e.written_paths) == 1
    loaded2 = PredictionWriter.read(pw_e.written_paths[0])
    np.testing.assert_allclose(
        np.concatenate(loaded2), np.concatenate(preds2), rtol=1e-6
    )

    with pytest.raises(ValueError, match="write_interval"):
        PredictionWriter(out_b, write_interval="step")

    # Streaming mode: return_predictions=False keeps nothing in memory and
    # returns None, but the batch files still carry everything.
    out_s = str(tmp_path / "streaming")
    pw_s = PredictionWriter(out_s, write_interval="batch")
    trainer3 = get_trainer(max_epochs=1, callbacks=[pw_s])
    res = trainer3.predict(module, return_predictions=False)
    assert res is None
    loaded3 = np.concatenate(
        [PredictionWriter.read(p) for p in sorted(pw_s.written_paths)]
    )
    np.testing.assert_allclose(loaded3, loaded, rtol=1e-6)
    # Epoch mode works independently of return_predictions: the writer
    # receives this rank's accumulated shard even when nothing is returned.
    pw_n = PredictionWriter(str(tmp_path / "none"), write_interval="epoch")
    res_n = get_trainer(max_epochs=1, callbacks=[pw_n]).predict(
        module, return_predictions=False
    )
    assert res_n is None and len(pw_n.written_paths) == 1
    loaded_n = PredictionWriter.read(pw_n.written_paths[0])
    np.testing.assert_allclose(
        np.concatenate(loaded_n), loaded, rtol=1e-6
    )


def test_mnist_accuracy_bound():
    predict_test(
        get_trainer(max_epochs=2, seed=1),
        MNISTClassifier(batch_size=8, n_train=256, lr=1e-2),
    )


def test_exact_metric_values_epoch_means():
    """Metrics must be exact batch-means (reference test_ddp.py:326-352)."""
    module = XORModule(batch_size=2)
    trainer = get_trainer(max_epochs=1, seed=0)
    trainer.fit(module)
    # val_acc is the mean over 4 equal batches of {0,0.5,1} values -> the
    # stored value must be one of the representable exact means.
    acc = trainer.callback_metrics["val_acc"]
    assert acc in [i / 8 for i in range(9)]
    # _epoch forked key present for train metrics
    assert "loss_epoch" in trainer.callback_metrics


def test_max_steps_stops_early():
    module = BoringModule()
    trainer = get_trainer(max_epochs=10, max_steps=3)
    trainer.fit(module)
    assert trainer.global_step == 3


def test_limit_train_batches():
    module = BoringModule()
    trainer = get_trainer(max_epochs=1, limit_train_batches=2)
    trainer.fit(module)
    assert trainer.global_step == 2


def test_checkpoint_roundtrip(tmp_path):
    module = BoringModule()
    ckpt = ModelCheckpoint(dirpath=str(tmp_path), monitor="val_loss")
    trainer = get_trainer(max_epochs=2, callbacks=[ckpt], enable_checkpointing=True)
    trainer.fit(module)
    assert ckpt.best_model_path and os.path.exists(ckpt.best_model_path)
    # Reload into a fresh module via validate(ckpt_path=...)
    fresh = BoringModule()
    trainer2 = get_trainer(max_epochs=1)
    res = trainer2.validate(fresh, ckpt_path=ckpt.best_model_path)
    assert "val_loss" in res[0]
    # Params identical after restore
    ref = np.asarray(module.params["w"])
    got = np.asarray(fresh.params["w"])
    np.testing.assert_array_equal(ref, got)


def test_checkpoint_weights_only(tmp_path):
    """save_weights_only leaves the optimizer state out of the file; what
    is left still loads for evaluation. Sharded saves refuse the option."""
    from ray_lightning_tpu.utils.state_stream import load_state_stream

    paths = {}
    for weights_only in (False, True):
        module = BoringModule()
        ckpt = ModelCheckpoint(
            dirpath=str(tmp_path / str(weights_only)),
            save_weights_only=weights_only,
        )
        get_trainer(callbacks=[ckpt], enable_checkpointing=True).fit(module)
        paths[weights_only] = ckpt.best_model_path
    with open(paths[False], "rb") as f:
        assert "opt_state" in load_state_stream(f.read())
    with open(paths[True], "rb") as f:
        state = load_state_stream(f.read())
    assert "opt_state" not in state and state["global_step"] > 0
    np.testing.assert_array_equal(
        np.asarray(state["params"]["w"]), np.asarray(module.params["w"])
    )
    assert os.path.getsize(paths[True]) < os.path.getsize(paths[False])
    res = get_trainer().validate(BoringModule(), ckpt_path=paths[True])
    assert "val_loss" in res[0]
    with pytest.raises(ValueError, match="save_weights_only"):
        ModelCheckpoint(save_sharded=True, save_weights_only=True)


def test_resume_from_checkpoint(tmp_path):
    module = BoringModule()
    ckpt = ModelCheckpoint(dirpath=str(tmp_path), monitor="val_loss")
    trainer = get_trainer(max_epochs=1, callbacks=[ckpt], enable_checkpointing=True)
    trainer.fit(module)
    first_steps = trainer.global_step
    # Resume continues epoch counting
    module2 = BoringModule()
    trainer2 = get_trainer(max_epochs=2)
    trainer2.fit(module2, ckpt_path=ckpt.best_model_path)
    assert trainer2.current_epoch == 1
    assert trainer2.global_step > first_steps


def test_early_stopping():
    module = BoringModule(lr=0.0)  # loss never improves
    es = EarlyStopping(monitor="val_loss", patience=1)
    trainer = get_trainer(max_epochs=20, callbacks=[es])
    trainer.fit(module)
    assert trainer.current_epoch < 19  # stopped well before max_epochs


def test_average_checkpoints_soup(tmp_path):
    """Model-soup averaging: the written soup holds the element-wise mean
    of the input params, loads through the normal eval path, and rejects
    mismatched inputs."""
    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.trainer.checkpoint_io import average_checkpoints

    paths = []
    mods = []
    for seed in (0, 1):
        m = _DetModule(batch_size=4, n=96)
        t = Trainer(
            max_epochs=1, enable_checkpointing=False, seed=seed,
            num_sanity_val_steps=0,
        )
        t.fit(m)
        p = str(tmp_path / f"m{seed}.ckpt")
        t.save_checkpoint(p)
        paths.append(p)
        mods.append(np.asarray(m.params["w"]))

    soup_path = str(tmp_path / "soup.ckpt")
    soup = average_checkpoints(paths, out_path=soup_path)
    np.testing.assert_allclose(
        np.asarray(soup["params"]["w"]), (mods[0] + mods[1]) / 2, rtol=1e-7
    )
    fresh = _DetModule(batch_size=4, n=96)
    res = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    ).validate(fresh, ckpt_path=soup_path)
    assert np.isfinite(res[0]["val_loss"])
    np.testing.assert_allclose(
        np.asarray(fresh.params["w"]), (mods[0] + mods[1]) / 2, rtol=1e-7
    )

    with pytest.raises(ValueError, match="two inputs"):
        average_checkpoints(paths[:1])


def test_lr_find_range_test():
    """The LR range test descends on a well-posed problem, suggests an lr
    inside the swept range, early-stops past the divergence cliff, and
    validates its inputs."""
    from ray_lightning_tpu.trainer import lr_find

    m = _DetModule(batch_size=8, n=96)
    res = lr_find(m, min_lr=1e-5, max_lr=10.0, num_steps=60)
    assert res.suggestion is not None
    assert 1e-5 <= res.suggestion <= 10.0
    assert len(res.lrs) == len(res.losses) == len(res.raw_losses)
    # The sweep should have found the cliff before max_lr (sgd on a linear
    # regression diverges well before lr=10) OR run out of steps.
    assert len(res.lrs) <= 60
    assert res.suggestion_or(1e-3) == res.suggestion

    import pytest as _pytest

    with _pytest.raises(ValueError, match="min_lr"):
        lr_find(m, min_lr=1.0, max_lr=0.1)
    with _pytest.raises(ValueError, match="num_steps"):
        lr_find(m, num_steps=1)


def test_multi_transform_per_group_optimizers():
    """PTL's multiple-optimizers story maps to optax.multi_transform
    through the existing single-transform contract: per-group transforms
    (here: frozen head vs trained body) ride one compiled step and one
    checkpointable opt_state."""
    import jax.numpy as jnp
    import optax

    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.trainer.data import ArrayDataset, DataLoader
    from ray_lightning_tpu.trainer.module import TPUModule

    class M(TPUModule):
        def __init__(self):
            super().__init__()
            g = np.random.default_rng(0)
            self.x = g.standard_normal((64, 3)).astype(np.float32)
            self.y = self.x @ np.array([1.0, -2.0, 0.5], np.float32)

        def init_params(self, rng, batch):
            return {"body": jnp.zeros((3,)), "head": jnp.ones(())}

        def training_step(self, params, batch, rng):
            bx, by = batch
            pred = (bx @ params["body"]) * params["head"]
            loss = ((pred - by) ** 2).mean()
            return loss, {"loss": loss}

        def configure_optimizers(self):
            return optax.multi_transform(
                {"train": optax.adam(5e-2), "freeze": optax.set_to_zero()},
                {"body": "train", "head": "freeze"},
            )

        def train_dataloader(self):
            return DataLoader(ArrayDataset(self.x, self.y), batch_size=8)

    m = M()
    # 8 virtual devices make the host batch 64 = the whole set: 1 step
    # per epoch, so epochs ~= optimizer steps here.
    t = Trainer(
        max_epochs=120, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, check_val_every_n_epoch=10**9,
    )
    t.fit(m)
    body = np.asarray(m.params["body"])
    head = float(np.asarray(m.params["head"]))
    assert head == 1.0  # frozen group untouched
    np.testing.assert_allclose(
        body, [1.0, -2.0, 0.5], atol=0.15
    )  # trained group converged


def test_model_summary_printed_and_suppressible(capsys):
    """enable_model_summary prints a rank-0 parameter table at fit start
    (PTL behavior); False silences it; the util itself reports exact
    counts, bytes, and dtypes per group."""
    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.utils.summary import summarize_params

    import jax.numpy as jnp

    table = summarize_params(
        {"enc": {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))},
         "head": jnp.zeros((8, 2), jnp.bfloat16)}
    )
    assert "enc" in table and "head" in table and "total" in table
    assert "40" in table  # enc: 4*8 + 8 params
    assert "bfloat16" in table

    m = _DetModule(batch_size=4, n=96)
    Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, check_val_every_n_epoch=10**9,
    ).fit(m)
    err = capsys.readouterr().err
    assert "total" in err and "params" in err

    m2 = _DetModule(batch_size=4, n=96)
    Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        enable_model_summary=False,
        num_sanity_val_steps=0, check_val_every_n_epoch=10**9,
    ).fit(m2)
    assert "total" not in capsys.readouterr().err


def test_overfit_batches_trains_and_validates_same_slice():
    """overfit_batches fixes one unshuffled train slice and points the val
    loop at it: a val set with shifted targets no longer influences
    val_loss (it is computed on TRAIN data), and mixing with batch limits
    is rejected."""
    import jax.numpy as jnp
    import optax

    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.trainer.data import ArrayDataset, DataLoader
    from ray_lightning_tpu.trainer.module import TPUModule

    class M(TPUModule):
        def __init__(self):
            super().__init__()
            g = np.random.default_rng(0)
            self.x = g.standard_normal((96, 3)).astype(np.float32)
            self.y = self.x @ np.array([1.0, -2.0, 0.5], np.float32)

        def init_params(self, rng, batch):
            return {"w": jnp.zeros((3,))}

        def training_step(self, params, batch, rng):
            bx, by = batch
            loss = ((bx @ params["w"] - by) ** 2).mean()
            return loss, {"loss": loss}

        def validation_step(self, params, batch):
            bx, by = batch
            return {"val_loss": ((bx @ params["w"] - by) ** 2).mean()}

        def configure_optimizers(self):
            return optax.adam(5e-2)

        def train_dataloader(self):
            return DataLoader(
                ArrayDataset(self.x, self.y), batch_size=4, shuffle=True
            )

        def val_dataloader(self):
            # Poisoned val targets: any val_loss computed on THIS data is
            # >= ~100^2; overfit mode must never see it.
            return DataLoader(
                ArrayDataset(self.x, self.y + 100.0), batch_size=4
            )

    m = M()
    t = Trainer(
        max_epochs=60,
        overfit_batches=2,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
    )
    t.fit(m)
    # Val ran on the train slice: loss is the (near-converged) train loss,
    # not the ~10^4 the poisoned val set would produce.
    assert float(t.callback_metrics["val_loss"]) < 1.0
    # And only 2 batches per epoch were consumed.
    assert t.global_step == 60 * 2

    with pytest.raises(ValueError, match="overfit_batches"):
        Trainer(overfit_batches=2, limit_train_batches=4)
    with pytest.raises(ValueError, match="overfit_batches"):
        Trainer(overfit_batches=-1)
    with pytest.raises(ValueError, match="overfit_batches"):
        Trainer(overfit_batches=1.5)


def test_detect_anomaly_raises_at_nan():
    """detect_anomaly surfaces a NaN produced inside the compiled step as
    an immediate FloatingPointError instead of silently training on."""
    import jax
    import jax.numpy as jnp

    m = _DetModule(batch_size=4, n=96)
    orig = m.training_step

    def nan_step(params, batch, rng):
        loss, logs = orig(params, batch, rng)
        # Param-dependent log(negative) -> NaN that reaches the compiled
        # step's OUTPUTS (a constant NaN with zero gradient and finite
        # logs would — correctly — never trip debug_nans).
        bad = jnp.log(-jnp.abs(params["w"]).sum() - 1.0)
        return loss + bad, {"loss": loss + bad}

    m.training_step = nan_step
    from ray_lightning_tpu.trainer import Trainer

    t = Trainer(
        max_epochs=1,
        detect_anomaly=True,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        check_val_every_n_epoch=10**9,
    )
    with pytest.raises(FloatingPointError):
        t.fit(m)
    # The anomaly guard restores the process-global even on the raise
    # path — the raise IS the feature's normal outcome.
    assert not jax.config.jax_debug_nans

    # Without the flag the same NaN step runs to completion (and the next
    # run's _setup_common owns the global back to False).
    m2 = _DetModule(batch_size=4, n=96)
    m2.training_step = nan_step
    t2 = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        check_val_every_n_epoch=10**9,
    )
    t2.fit(m2)  # no raise
    assert not jax.config.jax_debug_nans


def test_swa_averages_trajectory_and_swaps():
    """SWA folds end-of-epoch params (from swa_epoch_start on) into an
    equal-weight average and swaps it in at fit end; the running state
    rides state_dict for restart resume."""
    from ray_lightning_tpu.trainer import StochasticWeightAveraging, Trainer
    from ray_lightning_tpu.trainer.callbacks import Callback

    class Recorder(Callback):
        def __init__(self):
            self.per_epoch = []

        def on_train_epoch_end(self, trainer, module):
            w = trainer.strategy.gather_state(trainer.params)["w"]
            self.per_epoch.append(np.asarray(w).copy())

    rec = Recorder()
    swa = StochasticWeightAveraging(swa_epoch_start=2)
    m = _DetModule(batch_size=4, n=96)
    t = Trainer(
        max_epochs=4,
        callbacks=[rec, swa],
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        check_val_every_n_epoch=10**9,
    )
    t.fit(m)
    assert swa.n_models == 2  # epochs 2 and 3
    expected = (rec.per_epoch[2] + rec.per_epoch[3]) / 2
    np.testing.assert_allclose(np.asarray(m.params["w"]), expected, rtol=1e-6)
    # The average differs from the raw final params (the trajectory moved).
    assert not np.allclose(rec.per_epoch[3], expected)

    state = swa.state_dict()
    fresh = StochasticWeightAveraging(swa_epoch_start=2)
    fresh.load_state_dict(state)
    assert fresh.n_models == 2
    np.testing.assert_allclose(fresh.swa_params["w"], swa.swa_params["w"])

    # Float start: fraction of max_epochs; swap_params=False keeps live
    # weights and leaves the average on .swa_params.
    swa2 = StochasticWeightAveraging(swa_epoch_start=0.5, swap_params=False)
    m2 = _DetModule(batch_size=4, n=96)
    t2 = Trainer(
        max_epochs=2,
        callbacks=[swa2],
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        check_val_every_n_epoch=10**9,
    )
    t2.fit(m2)
    assert swa2.n_models == 1  # epoch 1 only (start = int(0.5*2))
    # One collected model and no swap: the average IS the final epoch's
    # params, and the live weights were left alone.
    np.testing.assert_allclose(
        np.asarray(swa2.swa_params["w"]), np.asarray(m2.params["w"]), rtol=1e-6
    )

    with pytest.raises(ValueError, match="swa_epoch_start"):
        StochasticWeightAveraging(swa_epoch_start=1.5)
    with pytest.raises(ValueError, match="swa_epoch_start"):
        StochasticWeightAveraging(swa_epoch_start=-1)


def test_max_time_parsing():
    """max_time accepts seconds / timedelta / kwargs dict / clock strings
    and rejects malformed or non-positive specs."""
    import datetime

    from ray_lightning_tpu.trainer.trainer import _parse_max_time

    assert _parse_max_time(None) is None
    assert _parse_max_time(90) == 90.0
    assert _parse_max_time(datetime.timedelta(minutes=2)) == 120.0
    assert _parse_max_time({"hours": 1, "minutes": 30}) == 5400.0
    assert _parse_max_time("00:01:30") == 90.0
    assert _parse_max_time("01:00:00:05") == 86405.0
    for bad in ("90", "1:2", "a:b:c", 0, -5, True, object()):
        with pytest.raises(ValueError):
            _parse_max_time(bad)


def test_max_time_stops_fit_early():
    """A wall-clock budget ends the fit long before max_epochs: the loop
    checks the deadline at step boundaries (single process) and flags
    should_stop, like PTL's Trainer(max_time=...)."""
    import time

    from ray_lightning_tpu.trainer import Trainer

    m = _DetModule(batch_size=4, n=96)
    t = Trainer(
        max_epochs=100000,
        max_time=2.0,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        check_val_every_n_epoch=10**9,
    )
    t0 = time.monotonic()
    t.fit(m)
    elapsed = time.monotonic() - t0
    # Compile eats part of the budget; the stop must land within a
    # generous multiple of it, far before 100k epochs' worth of steps.
    assert elapsed < 60
    assert 1 <= t.global_step < 100000 * 3


def test_scale_batch_size_power_and_throughput():
    """The power ramp doubles to max_val, records samples/s per fitting
    size, and suggests the largest fit (Lightning semantics) alongside a
    throughput-optimal size."""
    from ray_lightning_tpu.trainer import scale_batch_size

    m = _DetModule(batch_size=4, n=96)
    res = scale_batch_size(m, init_val=2, max_val=32, steps_per_trial=2)
    assert res.sizes == [2, 4, 8, 16, 32]
    assert res.largest == 32
    assert res.failed_at is None
    assert res.suggestion == 32
    assert set(res.samples_per_sec) == {2, 4, 8, 16, 32}
    assert all(v > 0 for v in res.samples_per_sec.values())
    assert res.throughput_optimal in res.samples_per_sec
    assert res.suggestion_or(7) == 32

    # A non-power-of-two ceiling is probed itself, not skipped past.
    res48 = scale_batch_size(m, init_val=2, max_val=48, steps_per_trial=1)
    assert res48.sizes == [2, 4, 8, 16, 32, 48]
    assert res48.largest == 48

    import pytest as _pytest

    with _pytest.raises(ValueError, match="mode"):
        scale_batch_size(m, mode="bogus")
    with _pytest.raises(ValueError, match="init_val"):
        scale_batch_size(m, init_val=0)


def test_scale_batch_size_binsearch_on_oom():
    """A trace-time RESOURCE_EXHAUSTED is classified as OOM (not re-raised);
    binsearch tightens between the last fit and first failure. Non-OOM
    errors propagate unchanged."""
    from ray_lightning_tpu.trainer import scale_batch_size

    def oom_module(threshold):
        m = _DetModule(batch_size=4, n=96)
        orig = m.training_step

        def step(params, batch, rng):
            if batch[0].shape[0] > threshold:
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory allocating probe"
                )
            return orig(params, batch, rng)

        m.training_step = step
        return m

    res = scale_batch_size(
        oom_module(20), mode="binsearch", init_val=2, steps_per_trial=1
    )
    assert res.failed_at is not None and res.failed_at <= 32
    assert res.largest == 20  # binsearch closes the [16, 32) gap
    assert 20 in res.samples_per_sec and 32 not in res.samples_per_sec

    # Power mode stops at the first failure without refinement.
    res_p = scale_batch_size(oom_module(20), init_val=2, steps_per_trial=1)
    assert res_p.largest == 16 and res_p.failed_at == 32

    # Even init_val failing -> largest is None, suggestion_or falls back.
    res_0 = scale_batch_size(oom_module(1), init_val=2, steps_per_trial=1)
    assert res_0.largest is None and res_0.suggestion_or(4) == 4

    class Boom(RuntimeError):
        pass

    m = _DetModule(batch_size=4, n=96)

    def bad_step(params, batch, rng):
        raise Boom("shape bug, not memory")

    m.training_step = bad_step
    import pytest as _pytest

    with _pytest.raises(Boom):
        scale_batch_size(m, init_val=2, steps_per_trial=1)


def test_early_stopping_thresholds():
    """stopping_threshold stops on goal reached; divergence_threshold stops
    on unrecoverable runs; check_finite stops on NaN metrics."""
    # Goal reached: loss drops under the threshold almost immediately.
    m = BoringModule()
    es = EarlyStopping(monitor="val_loss", patience=100,
                       stopping_threshold=1e6)
    t = get_trainer(max_epochs=20, callbacks=[es])
    t.fit(m)
    assert t.current_epoch == 0  # any finite loss beats 1e6

    # Divergence: a threshold any loss exceeds stops on the first val.
    m2 = BoringModule()
    es2 = EarlyStopping(monitor="val_loss", patience=100,
                        divergence_threshold=-1e6)
    t2 = get_trainer(max_epochs=20, callbacks=[es2])
    t2.fit(m2)
    assert t2.current_epoch == 0  # any loss > -1e6 counts as diverged

    # check_finite: a NaN metric stops instead of being skipped.
    m3 = BoringModule()
    orig = m3.validation_step
    m3.validation_step = lambda params, batch: {
        "val_loss": orig(params, batch)["val_loss"] * float("nan")
    }
    es3 = EarlyStopping(monitor="val_loss", patience=100, check_finite=True)
    t3 = get_trainer(max_epochs=20, callbacks=[es3])
    t3.fit(m3)
    assert t3.current_epoch == 0


def test_datamodule_path():
    module = XORModule(batch_size=2)
    dm = XORDataModule(batch_size=2)
    trainer = get_trainer(max_epochs=1)
    trainer.fit(module, datamodule=dm)
    assert "val_loss" in trainer.callback_metrics


def test_trainer_save_checkpoint_driver_side(tmp_path):
    module = BoringModule()
    trainer = get_trainer(max_epochs=1)
    trainer.fit(module)
    path = str(tmp_path / "driver.ckpt")
    trainer.save_checkpoint(path)
    assert os.path.exists(path)
    fresh = BoringModule()
    trainer.validate(fresh, ckpt_path=path)
    np.testing.assert_array_equal(
        np.asarray(module.params["b"]), np.asarray(fresh.params["b"])
    )


def test_driver_save_checkpoint_resumes_optimizer_state(tmp_path):
    """Driver-side save_checkpoint carries gathered optimizer state, so a
    fit resumed from it continues Adam momentum exactly (equals an
    uninterrupted run); a legacy params-only file warns loudly instead of
    silently restarting the optimizer."""
    import optax

    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.utils import load_state_stream, to_state_stream

    def adam_module():
        m = _DetModule(batch_size=4, n=96)
        m.configure_optimizers = lambda: optax.adam(1e-2)
        return m

    m1 = adam_module()
    t1 = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    t1.fit(m1)
    # Eval WITHOUT a checkpoint leaves params untouched, so the fit's
    # gathered opt_state must survive it (save_checkpoint stays resumable).
    t1.validate(m1)
    assert m1.opt_state is not None
    path = str(tmp_path / "driver.ckpt")
    t1.save_checkpoint(path)

    m2 = adam_module()
    t2 = Trainer(
        max_epochs=2, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    t2.fit(m2, ckpt_path=path)

    m3 = adam_module()
    t3 = Trainer(
        max_epochs=2, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    t3.fit(m3)
    np.testing.assert_allclose(
        np.asarray(m2.params["w"]), np.asarray(m3.params["w"]), rtol=1e-6
    )

    # Legacy params-only file (pre-opt_state format): resume must warn.
    with open(path, "rb") as f:
        state = load_state_stream(f.read())
    assert "opt_state" in state  # the fix under test
    del state["opt_state"]
    legacy = str(tmp_path / "legacy.ckpt")
    with open(legacy, "wb") as f:
        f.write(to_state_stream(state))
    t4 = Trainer(
        max_epochs=2, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    with pytest.warns(RuntimeWarning, match="no optimizer state"):
        t4.fit(adam_module(), ckpt_path=legacy)

    # Opt-out skips the gather/transfer entirely.
    m5 = adam_module()
    t5 = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, ship_optimizer_state=False,
    )
    t5.fit(m5)
    assert m5.opt_state is None


def test_epoch_metrics_identical_across_log_cadences():
    """Windowed draining of step logs (at the log_every_n_steps boundary)
    must not change the epoch reduction: per-step values accumulate on the
    host, so every cadence yields the same epoch mean."""
    from ray_lightning_tpu.trainer import Trainer

    results = {}
    for cadence in (1, 2, 10**9):
        m = _DetModule(batch_size=4, n=96)
        t = Trainer(
            max_epochs=2, enable_checkpointing=False, seed=0,
            num_sanity_val_steps=0, log_every_n_steps=cadence,
        )
        t.fit(m)
        results[cadence] = t.callback_metrics["loss_epoch"]
    assert results[1] == results[2] == results[10**9]


def test_driver_save_checkpoint_mid_epoch_semantics(tmp_path):
    """A driver file saved after a mid-epoch stop records mid_epoch, so
    resume re-runs the epoch with the partial accumulation window cleared —
    identical to the worker-written-checkpoint semantics."""
    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.utils import load_state_stream

    common = dict(
        max_epochs=1, seed=0, num_sanity_val_steps=0,
        accumulate_grad_batches=2, enable_checkpointing=False,
    )
    m_ref = _DetModule(batch_size=4, n=96)
    Trainer(**common).fit(m_ref)

    # Stop after batch 1: mini_step=1 pending in opt_state.
    m1 = _DetModule(batch_size=4, n=96)
    t1 = Trainer(max_steps=1, **common)
    t1.fit(m1)
    path = str(tmp_path / "mid.ckpt")
    t1.save_checkpoint(path)
    with open(path, "rb") as f:
        st = load_state_stream(f.read())
    assert st["mid_epoch"] is True and "opt_state" in st

    # Resume re-runs the epoch from batch 0; with the restored partial
    # window cleared the result equals the straight run exactly.
    m2 = _DetModule(batch_size=4, n=96)
    Trainer(**common).fit(m2, ckpt_path=path)
    np.testing.assert_allclose(
        np.asarray(m2.params["w"]), np.asarray(m_ref.params["w"]), atol=0
    )


def test_tensorboard_logger(tmp_path):
    """TensorBoardLogger writes event files TensorBoard's own loader reads
    back: per-step train scalars at the log cadence plus val metrics, and
    the log dir propagates to the driver-side callback object."""
    import glob

    from ray_lightning_tpu.trainer import TensorBoardLogger, Trainer

    tb = TensorBoardLogger(dirpath=str(tmp_path))
    m = _DetModule(batch_size=4, n=96)
    t = Trainer(
        max_epochs=2, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, log_every_n_steps=1, callbacks=[tb],
    )
    t.fit(m)
    assert tb.log_dir and os.path.isdir(tb.log_dir)
    files = glob.glob(os.path.join(tb.log_dir, "events.out.tfevents.*"))
    assert files, os.listdir(tb.log_dir)

    import struct

    from tensorboard.compat.proto.event_pb2 import Event

    scalars = {}
    for f in files:
        data = open(f, "rb").read()
        off = 0
        while off < len(data):
            (length,) = struct.unpack("<Q", data[off : off + 8])
            off += 12  # len + len-crc
            ev = Event()
            ev.ParseFromString(data[off : off + length])
            off += length + 4  # payload + payload-crc
            for v in ev.summary.value:
                scalars.setdefault(v.tag, []).append((ev.step, v.simple_value))
    assert "loss" in scalars and "val_loss" in scalars, scalars.keys()
    # One train point per step at cadence 1 (3 steps/epoch x 2 epochs).
    assert len(scalars["loss"]) == t.global_step
    # Written values match what the trainer reported.
    last_step, last_val = max(scalars["val_loss"])
    assert abs(last_val - t.callback_metrics["val_loss"]) < 1e-6


def test_jax_profiler_callback(tmp_path):
    """JaxProfilerCallback writes a TensorBoard-loadable trace for the
    selected epoch (SURVEY.md §5 tracing/profiling coverage)."""
    import glob

    from ray_lightning_tpu.models import BoringModule
    from ray_lightning_tpu.trainer import JaxProfilerCallback, Trainer

    prof = JaxProfilerCallback(dirpath=str(tmp_path / "trace"), epochs=(1,))
    trainer = Trainer(
        max_epochs=2,
        enable_checkpointing=False,
        callbacks=[prof],
        seed=0,
        num_sanity_val_steps=0,
    )
    trainer.fit(BoringModule())
    assert prof.trace_dirs  # state carried back through callback sync
    files = glob.glob(
        str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*")
    )
    assert files, "no profiler artifacts written"


class _DetModule:
    """Deterministic linear-regression module for optimizer-option tests."""

    def __new__(cls, batch_size=4, n=32):
        import jax.numpy as jnp
        import numpy as np
        import optax

        from ray_lightning_tpu.trainer.data import ArrayDataset, DataLoader
        from ray_lightning_tpu.trainer.module import TPUModule

        class M(TPUModule):
            def __init__(self):
                super().__init__()
                g = np.random.default_rng(0)
                self.x = g.standard_normal((n, 3)).astype(np.float32)
                self.y = (self.x @ np.array([1.0, -2.0, 0.5], np.float32))
                self.batch_size = batch_size

            def init_params(self, rng, batch):
                return {"w": jnp.zeros((3,))}

            def training_step(self, params, batch, rng):
                bx, by = batch
                pred = bx @ params["w"]
                loss = ((pred - by) ** 2).mean()
                return loss, {"loss": loss}

            def validation_step(self, params, batch):
                bx, by = batch
                return {"val_loss": ((bx @ params["w"] - by) ** 2).mean()}

            def configure_optimizers(self):
                return optax.sgd(1e-2)

            def train_dataloader(self):
                return DataLoader(
                    ArrayDataset(self.x, self.y), batch_size=self.batch_size
                )

            def val_dataloader(self):
                return DataLoader(
                    ArrayDataset(self.x, self.y), batch_size=self.batch_size
                )

        return M()


def test_accumulate_grad_batches_matches_bigger_batch():
    """K micro-batches with accumulation == one K-times-larger batch
    (grads averaged on device via optax.MultiSteps)."""
    import numpy as np

    from ray_lightning_tpu.trainer import Trainer

    # conftest forces 8 virtual devices, so the host batch is batch_size*8:
    # n=128 gives the accumulation run 4 micro-steps (2 updates) and the
    # big-batch run 2 steps over identical sample order (shuffle off).
    m_acc = _DetModule(batch_size=4, n=128)
    t_acc = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        accumulate_grad_batches=2,
    )
    t_acc.fit(m_acc)

    m_big = _DetModule(batch_size=8, n=128)
    t_big = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0, num_sanity_val_steps=0
    )
    t_big.fit(m_big)
    np.testing.assert_allclose(
        np.asarray(m_acc.params["w"]),
        np.asarray(m_big.params["w"]),
        atol=1e-6,
    )
    # global_step counts micro-batches (documented semantics).
    assert t_acc.global_step == 4
    assert t_big.global_step == 2


def test_gradient_clip_val_limits_update():
    """With a tiny clip norm, the first SGD update's magnitude is bounded by
    lr * clip_val."""
    import numpy as np

    from ray_lightning_tpu.trainer import Trainer

    module = _DetModule(batch_size=32)  # one big step
    trainer = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        max_steps=1,
        gradient_clip_val=0.1,
    )
    trainer.fit(module)
    w = np.asarray(module.params["w"])
    assert np.linalg.norm(w) <= 1e-2 * 0.1 + 1e-8  # lr * clip + eps

    module2 = _DetModule(batch_size=32)
    t2 = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        max_steps=1,
    )
    t2.fit(module2)
    assert np.linalg.norm(np.asarray(module2.params["w"])) > np.linalg.norm(w)


def test_csv_logger(tmp_path):
    from ray_lightning_tpu.trainer import CSVLogger, Trainer

    logger = CSVLogger(dirpath=str(tmp_path))
    module = _DetModule()
    trainer = Trainer(
        max_epochs=3,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        callbacks=[logger],
    )
    trainer.fit(module)
    import csv

    with open(tmp_path / "metrics.csv") as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 3
    assert {"epoch", "step", "val_loss"} <= set(rows[0].keys())
    assert float(rows[-1]["val_loss"]) < float(rows[0]["val_loss"])


def test_accumulation_partial_window_flushed():
    """A trailing micro-batch that doesn't fill the accumulation window must
    still produce an optimizer step at epoch end (PTL last-batch semantics)."""
    import numpy as np

    from ray_lightning_tpu.trainer import Trainer

    # 8 devices x batch 4 = 32/step; n=96 -> 3 micro-steps; K=2 leaves one
    # dangling micro-batch that only the flush can apply.
    m = _DetModule(batch_size=4, n=96)
    t = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        accumulate_grad_batches=2,
    )
    t.fit(m)
    assert t.global_step == 3

    # Reference: identical sample stream as [64-batch step, 32-batch step].
    import jax.numpy as jnp
    import optax

    g = np.random.default_rng(0)
    x = g.standard_normal((96, 3)).astype(np.float32)
    y = x @ np.array([1.0, -2.0, 0.5], np.float32)
    tx = optax.sgd(1e-2)
    w = jnp.zeros((3,))
    state = tx.init({"w": w})
    for sl in (slice(0, 64), slice(64, 96)):
        bx, by = jnp.asarray(x[sl]), jnp.asarray(y[sl])

        def loss_fn(p):
            return ((bx @ p["w"] - by) ** 2).mean()

        import jax

        grads = jax.grad(loss_fn)({"w": w})
        updates, state = tx.update(grads, state, {"w": w})
        w = optax.apply_updates({"w": w}, updates)["w"]
    np.testing.assert_allclose(
        np.asarray(m.params["w"]), np.asarray(w), atol=1e-6
    )


def test_precision_bf16_mixed():
    """precision='bf16' casts the compute graph (params+batch as seen by the
    module step) to bfloat16 while master params stay float32."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.trainer.data import ArrayDataset, DataLoader
    from ray_lightning_tpu.trainer.module import TPUModule

    seen = {}

    class Probe(TPUModule):
        def init_params(self, rng, batch):
            return {"w": jnp.zeros((3,), jnp.float32)}

        def training_step(self, params, batch, rng):
            x, y = batch
            seen["param_dtype"] = params["w"].dtype
            seen["batch_dtype"] = x.dtype
            loss = ((x @ params["w"] - y) ** 2).mean()
            return loss, {"loss": loss}

        def validation_step(self, params, batch):
            x, y = batch
            seen["eval_dtype"] = x.dtype
            return {"val_loss": ((x @ params["w"] - y) ** 2).mean()}

        def configure_optimizers(self):
            return optax.sgd(1e-2)

        def _loader(self):
            g = np.random.default_rng(0)
            x = g.standard_normal((64, 3)).astype(np.float32)
            return DataLoader(
                ArrayDataset(x, (x @ np.ones(3, np.float32))), batch_size=4
            )

        def train_dataloader(self):
            return self._loader()

        def val_dataloader(self):
            return self._loader()

    module = Probe()
    trainer = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        precision="bf16",
    )
    trainer.fit(module)
    assert seen["param_dtype"] == jnp.bfloat16
    assert seen["batch_dtype"] == jnp.bfloat16
    assert seen["eval_dtype"] == jnp.bfloat16
    # Master params stay fp32 and were actually updated.
    w = module.params["w"]
    assert np.asarray(w).dtype == np.float32
    assert np.abs(np.asarray(w)).sum() > 0
    assert np.isfinite(trainer.callback_metrics["val_loss"])


def test_precision_fp32_untouched():
    import jax.numpy as jnp

    from ray_lightning_tpu.strategies.base import Strategy

    class M:
        precision = "fp32"

    assert Strategy._compute_dtype(M()) is None

    class B:
        precision = "16-mixed"

    assert Strategy._compute_dtype(B()) == jnp.bfloat16


def test_max_steps_stop_does_not_flush_partial_window():
    """Stopping via max_steps mid-accumulation-window must NOT apply the
    dangling micro-batch (PTL drops it; only epoch end flushes)."""
    import numpy as np

    from ray_lightning_tpu.trainer import Trainer

    m = _DetModule(batch_size=4, n=128)  # 4 micro-steps/epoch
    t = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        accumulate_grad_batches=2,
        max_steps=3,  # stops with one dangling micro-batch
    )
    t.fit(m)
    assert t.global_step == 3

    # Reference: exactly ONE update from micro-batches 1-2 (64 samples).
    import jax
    import jax.numpy as jnp
    import optax

    g = np.random.default_rng(0)
    x = g.standard_normal((128, 3)).astype(np.float32)
    y = x @ np.array([1.0, -2.0, 0.5], np.float32)
    bx, by = jnp.asarray(x[:64]), jnp.asarray(y[:64])
    grads = jax.grad(lambda p: ((bx @ p["w"] - by) ** 2).mean())(
        {"w": jnp.zeros(3)}
    )
    tx = optax.sgd(1e-2)
    updates, _ = tx.update(grads, tx.init({"w": jnp.zeros(3)}))
    w_ref = optax.apply_updates({"w": jnp.zeros(3)}, updates)["w"]
    np.testing.assert_allclose(
        np.asarray(m.params["w"]), np.asarray(w_ref), atol=1e-6
    )


def test_resume_with_changed_optimizer_options_rejected(tmp_path):
    import pytest as _pytest

    from ray_lightning_tpu.trainer import ModelCheckpoint, Trainer

    m = _DetModule(batch_size=4, n=128)
    ckpt = ModelCheckpoint(dirpath=str(tmp_path), monitor="val_loss")
    t = Trainer(
        max_epochs=1,
        enable_checkpointing=True,
        seed=0,
        num_sanity_val_steps=0,
        callbacks=[ckpt],
    )
    t.fit(m)
    assert ckpt.best_model_path

    m2 = _DetModule(batch_size=4, n=128)
    t2 = Trainer(
        max_epochs=2,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        accumulate_grad_batches=2,  # changes opt_state structure
    )
    with _pytest.raises(RuntimeError, match="optimizer"):
        t2.fit(m2, ckpt_path=ckpt.best_model_path)


def test_precision_true_half_rejected():
    import pytest as _pytest

    from ray_lightning_tpu.strategies.base import Strategy

    class M:
        precision = "bf16-true"

    with _pytest.raises(ValueError, match="true half"):
        Strategy._compute_dtype(M())


def test_max_steps_on_final_batch_still_flushes():
    """max_steps landing exactly on the epoch's last batch IS an epoch end:
    the partial window must flush, matching the same run without max_steps."""
    import numpy as np

    from ray_lightning_tpu.trainer import Trainer

    def run(**kw):
        m = _DetModule(batch_size=4, n=96)  # 3 micro-steps/epoch
        t = Trainer(
            max_epochs=1,
            enable_checkpointing=False,
            seed=0,
            num_sanity_val_steps=0,
            accumulate_grad_batches=2,
            **kw,
        )
        t.fit(m)
        return np.asarray(m.params["w"])

    np.testing.assert_allclose(run(), run(max_steps=3), atol=0)


class _SchedModule:
    """Linear-regression module declaring an lr schedule for monitoring.

    ``form`` selects the configure_optimizers return shape: "dict",
    "tuple", or "plain" (no declared schedule).
    """

    def __new__(cls, form="dict", batch_size=4, n=96):
        import optax

        base = _DetModule(batch_size=batch_size, n=n)
        sched = optax.linear_schedule(1e-2, 0.0, 100)

        def configure_optimizers():
            tx = optax.sgd(sched)
            if form == "dict":
                return {"optimizer": tx, "lr_schedule": sched}
            if form == "tuple":
                return (tx, sched)
            return tx

        base.configure_optimizers = configure_optimizers
        base._sched = sched
        return base


def test_lr_monitor_follows_schedule():
    """LearningRateMonitor logs the schedule value at the loop's current
    optimizer-update index (epoch end -> callback_metrics['lr'])."""
    import numpy as np

    from ray_lightning_tpu.trainer import LearningRateMonitor, Trainer

    m = _SchedModule(form="dict")
    t = Trainer(
        max_epochs=2,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        callbacks=[LearningRateMonitor()],
    )
    t.fit(m)
    assert t.global_step == 6  # 96 / (4 * 8 devices) = 3 steps x 2 epochs
    np.testing.assert_allclose(
        t.callback_metrics["lr"], float(m._sched(6)), rtol=1e-6
    )
    assert "lr" in t.logged_metrics


def test_lr_monitor_tuple_form_and_plain():
    from ray_lightning_tpu.trainer import LearningRateMonitor, Trainer

    m = _SchedModule(form="tuple")
    t = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        callbacks=[LearningRateMonitor()],
    )
    t.fit(m)
    assert "lr" in t.callback_metrics

    # Plain GradientTransformation (itself a 2-tuple of callables) must NOT
    # be mistaken for the (tx, schedule) form: fit works, no lr metric.
    m2 = _SchedModule(form="plain")
    t2 = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        callbacks=[LearningRateMonitor()],
    )
    t2.fit(m2)
    assert "lr" not in t2.callback_metrics


def test_lr_monitor_accumulation_indexes_updates():
    """With accumulate_grad_batches=K the schedule is indexed by the ACTUAL
    optimizer-update count: full windows plus epoch-end partial-window
    flushes, both of which advance the embedded schedule."""
    import numpy as np

    from ray_lightning_tpu.trainer import LearningRateMonitor, Trainer

    m = _SchedModule(form="dict")
    t = Trainer(
        max_epochs=2,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        accumulate_grad_batches=2,
        callbacks=[LearningRateMonitor()],
    )
    t.fit(m)
    assert t.global_step == 6
    # 3 micro-steps/epoch, K=2: each epoch = 1 window update + 1 flush
    # update -> 4 inner updates total (global_step // K = 3 would lag).
    np.testing.assert_allclose(
        t.callback_metrics["lr"], float(m._sched(4)), rtol=1e-6
    )
    np.testing.assert_allclose(t.current_lr, float(m._sched(4)), rtol=1e-6)


def test_driver_trainer_current_lr_and_ptl_key():
    """Driver-side Trainer.current_lr mirrors the loop's; the PTL dict key
    'lr_scheduler' is accepted as an alias of 'lr_schedule'."""
    import numpy as np
    import optax

    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.trainer.module import unpack_optimizers

    m = _SchedModule(form="dict")
    t = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0, num_sanity_val_steps=0
    )
    t.fit(m)
    np.testing.assert_allclose(t.current_lr, float(m._sched(t.global_step)))

    sched = optax.linear_schedule(1.0, 0.0, 10)
    tx, s = unpack_optimizers({"optimizer": optax.sgd(sched), "lr_scheduler": sched})
    assert s is sched and hasattr(tx, "init")


def test_unpack_optimizers_rejects_ptl_tuple_and_trainer_reuse():
    import optax
    import pytest

    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.trainer.module import unpack_optimizers

    with pytest.raises(TypeError, match="Accepted forms"):
        unpack_optimizers(([optax.sgd(1e-2)], ["not-a-schedule"]))

    # Reusing one Trainer across modules must not report a stale schedule.
    m1 = _SchedModule(form="dict")
    t = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0, num_sanity_val_steps=0
    )
    t.fit(m1)
    assert t.current_lr is not None
    m2 = _SchedModule(form="plain")
    t.fit(m2)
    assert t.current_lr is None


def test_params_ema_transform_math():
    """params_ema tracks the post-update weights: closed-form check."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_lightning_tpu.trainer.ema import ema_params, params_ema

    d = 0.9
    tx = optax.chain(optax.sgd(0.5), params_ema(d))
    params = {"w": jnp.asarray([1.0, 2.0])}
    state = tx.init(params)
    grads = [{"w": jnp.asarray([1.0, 0.0])}, {"w": jnp.asarray([0.0, 2.0])}]
    seen = []
    for g in grads:
        updates, state = tx.update(g, state, params)
        params = optax.apply_updates(params, updates)
        seen.append(np.asarray(params["w"]))
    # debiased EMA after t updates = (sum_i (1-d) d^(t-1-i) p_i) / (1-d^t)
    t = len(seen)
    num = sum((1 - d) * d ** (t - 1 - i) * p for i, p in enumerate(seen))
    expected = num / (1 - d**t)
    got = ema_params(state, d)
    np.testing.assert_allclose(np.asarray(got["w"]), expected, rtol=1e-6)


def test_trainer_ema_fit_and_eval():
    """Trainer(ema_decay=...): averaged weights recovered on the driver;
    eval_ema evaluates with them (different val_loss than live weights)."""
    import numpy as np

    from ray_lightning_tpu.trainer import Trainer

    def run(**kw):
        m = _DetModule(batch_size=4, n=96)
        t = Trainer(
            max_epochs=2,
            enable_checkpointing=False,
            seed=0,
            num_sanity_val_steps=0,
            **kw,
        )
        t.fit(m)
        return t, m

    t_ema, m_ema = run(ema_decay=0.8)
    assert t_ema.ema_params is not None and m_ema.ema_params is not None
    w = np.asarray(m_ema.params["w"])
    we = np.asarray(m_ema.ema_params["w"])
    assert np.isfinite(we).all() and not np.allclose(w, we)
    # Same seed without EMA: identical training trajectory (EMA is an
    # observer, not a modifier).
    t_plain, m_plain = run()
    np.testing.assert_allclose(w, np.asarray(m_plain.params["w"]), atol=0)
    assert t_plain.ema_params is None

    # eval_ema: val_loss computed with the (lagging) averaged weights
    # differs from the live-weight val_loss.
    t_ev, _ = run(ema_decay=0.8, eval_ema=True)
    assert (
        abs(
            t_ev.callback_metrics["val_loss"]
            - t_ema.callback_metrics["val_loss"]
        )
        > 1e-9
    )


def test_trainer_ema_survives_resume(tmp_path):
    """EMA state rides opt_state, so checkpoint resume keeps the average."""
    import numpy as np

    from ray_lightning_tpu.trainer import ModelCheckpoint, Trainer

    m = _DetModule(batch_size=4, n=96)
    ck = ModelCheckpoint(dirpath=str(tmp_path), save_last=True)
    t = Trainer(
        max_epochs=1,
        enable_checkpointing=True,
        callbacks=[ck],
        seed=0,
        num_sanity_val_steps=0,
        ema_decay=0.8,
    )
    t.fit(m)

    m2 = _DetModule(batch_size=4, n=96)
    t2 = Trainer(
        max_epochs=2,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        ema_decay=0.8,
    )
    t2.fit(m2, ckpt_path=ck.last_model_path)

    # Reference: straight 2-epoch run with EMA from scratch.
    m3 = _DetModule(batch_size=4, n=96)
    t3 = Trainer(
        max_epochs=2,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        ema_decay=0.8,
    )
    t3.fit(m3)
    np.testing.assert_allclose(
        np.asarray(m2.ema_params["w"]), np.asarray(m3.ema_params["w"]),
        rtol=1e-6,
    )


def test_ema_guards_and_standalone_eval(tmp_path):
    """decay-mismatch resume is rejected; standalone validate honors
    eval_ema from a checkpoint; eval_ema with no EMA anywhere raises."""
    import numpy as np
    import pytest

    from ray_lightning_tpu.trainer import ModelCheckpoint, Trainer

    with pytest.raises(ValueError, match="ema_decay"):
        Trainer(ema_decay=1.5)

    m = _DetModule(batch_size=4, n=96)
    ck = ModelCheckpoint(dirpath=str(tmp_path), save_last=True)
    t = Trainer(
        max_epochs=1, enable_checkpointing=True, callbacks=[ck], seed=0,
        num_sanity_val_steps=0, ema_decay=0.8,
    )
    t.fit(m)

    # Resume with a different decay must fail loudly.
    t_bad = Trainer(
        max_epochs=2, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, ema_decay=0.9,
    )
    with pytest.raises(RuntimeError, match="decay"):
        t_bad.fit(_DetModule(batch_size=4, n=96), ckpt_path=ck.last_model_path)

    # Standalone validate from the resume-format checkpoint: EMA lives in
    # its opt_state; eval_ema picks it up even with ema_decay unset.
    t_eval = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, eval_ema=True,
    )
    res_ema = t_eval.validate(
        _DetModule(batch_size=4, n=96), ckpt_path=ck.last_model_path
    )
    t_live = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    res_live = t_live.validate(
        _DetModule(batch_size=4, n=96), ckpt_path=ck.last_model_path
    )
    assert abs(res_ema[0]["val_loss"] - res_live[0]["val_loss"]) > 1e-12

    # eval_ema with nothing to average from: loud error.
    m_plain = _DetModule(batch_size=4, n=96)
    t_plain = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    t_plain.fit(m_plain)
    t_none = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, eval_ema=True,
    )
    with pytest.raises(RuntimeError, match="no EMA"):
        t_none.validate(m_plain)


def test_ema_driver_save_and_stale_clear(tmp_path):
    """Driver-side save_checkpoint carries the average; re-fitting without
    EMA clears the stale one from the module."""
    import numpy as np
    import pytest

    from ray_lightning_tpu.trainer import Trainer

    m = _DetModule(batch_size=4, n=96)
    t = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, ema_decay=0.8,
    )
    t.fit(m)
    path = str(tmp_path / "driver.ckpt")
    t.save_checkpoint(path)

    # eval_ema straight from the driver-saved checkpoint
    t_eval = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, eval_ema=True,
    )
    res = t_eval.validate(_DetModule(batch_size=4, n=96), ckpt_path=path)
    assert np.isfinite(res[0]["val_loss"])

    # Re-fit the same module WITHOUT ema: stale average must not survive.
    t2 = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    t2.fit(m)
    assert m.ema_params is None and t2.ema_params is None
    with pytest.raises(RuntimeError, match="no EMA"):
        Trainer(
            max_epochs=1, enable_checkpointing=False, seed=0,
            num_sanity_val_steps=0, eval_ema=True,
        ).validate(m)


def test_token_bin_dataset_roundtrip_and_fit(tmp_path):
    """write_token_bin -> TokenBinDataset windows -> distributed GPT fit."""
    import cloudpickle
    import numpy as np

    from ray_lightning_tpu.models import GPTConfig, GPTLM
    from ray_lightning_tpu.trainer import (
        DataLoader, TokenBinDataset, Trainer, write_token_bin,
    )

    toks = np.arange(0, 1000) % 64
    path = write_token_bin(str(tmp_path / "corpus.bin"), toks)
    ds = TokenBinDataset(path, seq_len=16)
    # windows: (1000 - 17) // 16 + 1 = 62
    assert len(ds) == 62
    np.testing.assert_array_equal(ds[0], toks[:17] % 64)
    np.testing.assert_array_equal(ds[1], toks[16:33] % 64)
    assert ds[0].dtype == np.int32

    # overlap stride + pickle (ships to actors without the mmap handle)
    ds2 = TokenBinDataset(path, seq_len=16, stride=8)
    assert len(ds2) > len(ds)
    clone = cloudpickle.loads(cloudpickle.dumps(ds))
    np.testing.assert_array_equal(clone[5], ds[5])

    import pytest

    with pytest.raises(ValueError, match="fit dtype"):
        write_token_bin(str(tmp_path / "bad.bin"), np.array([70000]), "uint16")
    with pytest.raises(ValueError, match="window"):
        TokenBinDataset(path, seq_len=2000)

    cfg = GPTConfig(
        vocab_size=64, n_layer=1, n_head=2, d_model=16, max_seq=16,
        attn_impl="reference",
    )
    m = GPTLM(config=cfg, batch_size=2, dataset=ds)
    t = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, log_grad_norm=True,
    )
    t.fit(m)
    assert t.global_step > 0
    assert np.isfinite(t.callback_metrics["grad_norm"])
    assert t.callback_metrics["grad_norm"] > 0


def test_val_check_interval():
    """Mid-epoch validation: int = every N batches; the epoch-end val is
    skipped only when an interval val already covered the final params."""
    import numpy as np
    import pytest

    from ray_lightning_tpu.trainer import Callback, Trainer

    class CountVal(Callback):
        def __init__(self):
            self.steps_at_val = []

        def on_validation_end(self, trainer, module):
            if not trainer.sanity_checking:
                self.steps_at_val.append(trainer.global_step)

    def run(n=96, **kw):
        # 96 / (4 * 8 devices) = 3 batches per epoch
        cb = CountVal()
        m = _DetModule(batch_size=4, n=n)
        t = Trainer(
            max_epochs=2, enable_checkpointing=False, seed=0,
            num_sanity_val_steps=0, callbacks=[cb], **kw,
        )
        t.fit(m)
        return cb.steps_at_val

    # Baseline: epoch-end only.
    assert run() == [3, 6]
    # Every batch: 3 per epoch, epoch-end dedup'd (batch 3 == epoch end).
    assert run(val_check_interval=1) == [1, 2, 3, 4, 5, 6]
    # Every 2 batches: mid-epoch at step 2/5, epoch end still runs.
    assert run(val_check_interval=2) == [2, 3, 5, 6]
    # Fraction: int(3 * 0.67) = 2 -> same as the every-2 cadence.
    assert run(val_check_interval=0.67) == [2, 3, 5, 6]
    # Tiny fraction clamps to every batch (max(1, int(3*0.1)=0)).
    assert run(val_check_interval=0.1) == [1, 2, 3, 4, 5, 6]
    # PTL: float 1.0 means once per epoch, NOT every batch.
    assert run(val_check_interval=1.0) == [3, 6]
    # Mid-epoch vals obey check_val_every_n_epoch (only epoch 2 here).
    assert run(val_check_interval=1, check_val_every_n_epoch=2) == [4, 5, 6]

    with pytest.raises(ValueError, match="val_check_interval"):
        Trainer(val_check_interval=1.5)
    with pytest.raises(ValueError, match="val_check_interval"):
        Trainer(val_check_interval=0)


def test_val_check_interval_flush_revalidates():
    """A final-batch mid-epoch val does NOT suppress the epoch-end val when
    the accumulation flush changes params right after it."""
    from ray_lightning_tpu.trainer import Callback, Trainer

    class CountVal(Callback):
        def __init__(self):
            self.steps_at_val = []

        def on_validation_end(self, trainer, module):
            if not trainer.sanity_checking:
                self.steps_at_val.append(trainer.global_step)

    cb = CountVal()
    # 3 batches/epoch, K=2: batch 3 leaves a partial window -> flush.
    m = _DetModule(batch_size=4, n=96)
    t = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, accumulate_grad_batches=2,
        val_check_interval=3, callbacks=[cb],
    )
    t.fit(m)
    # Interval val at step 3 (pre-flush) AND epoch-end val (post-flush).
    assert cb.steps_at_val == [3, 3]


def test_ckpt_path_last_and_stage_limits(tmp_path):
    """ckpt_path='last' resolves the rolling/newest checkpoint; test and
    predict honor their own batch limits."""
    import numpy as np
    import pytest

    from ray_lightning_tpu.models import BoringModule
    from ray_lightning_tpu.trainer import ModelCheckpoint, Trainer

    m = BoringModule()
    ck = ModelCheckpoint(dirpath=str(tmp_path), save_last=True)
    t = Trainer(
        max_epochs=2, enable_checkpointing=True, callbacks=[ck], seed=0,
        num_sanity_val_steps=0,
    )
    t.fit(m)

    m2 = BoringModule()
    t2 = Trainer(
        max_epochs=3, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
        callbacks=[ModelCheckpoint(dirpath=str(tmp_path), save_top_k=0)],
    )
    t2.fit(m2, ckpt_path="last")
    assert t2.current_epoch == 2  # resumed at epoch 2 of 3
    np.testing.assert_array_equal(
        np.asarray(m2.params["w"]).shape, np.asarray(m.params["w"]).shape
    )

    # ckpt_path="best": the monitored best from the fit's callback.
    m_best = BoringModule()
    res = t.validate(m_best, ckpt_path="best")
    assert np.isfinite(res[0]["val_loss"])
    with pytest.raises(FileNotFoundError, match="best"):
        Trainer(
            max_epochs=1, enable_checkpointing=False, seed=0,
            num_sanity_val_steps=0,
        ).validate(BoringModule(), ckpt_path="best")

    with pytest.raises(FileNotFoundError, match="last"):
        Trainer(
            max_epochs=1, enable_checkpointing=False, seed=0,
            num_sanity_val_steps=0,
            default_root_dir=str(tmp_path / "empty"),
        ).fit(BoringModule(), ckpt_path="last")

    # Stage limits: 64 samples / batch 2 / 8 devices = 4 batches total.
    t3 = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
        limit_test_batches=2,
        limit_predict_batches=1,
    )
    m3 = BoringModule()
    t3.fit(m3)
    t3.test(m3)  # runs (bounded); metrics finite
    preds = t3.predict(m3)
    # 1 global batch x (2 per-chip x 8 devices) = 16 rows
    assert sum(len(p) for p in preds) == 16


def test_val_check_interval_early_stop_mid_epoch():
    """EarlyStopping triggered by a mid-epoch val ends training inside the
    epoch (the point of val_check_interval on very long epochs)."""
    import pytest

    from ray_lightning_tpu.trainer import EarlyStopping, Trainer

    es = EarlyStopping(monitor="val_loss", patience=0)
    t = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, val_check_interval=2, callbacks=[es],
    )
    # Frozen model (lr 0): val_loss never improves, so patience=0 trips
    # on the second mid-epoch val.
    m_frozen = _DetModule(batch_size=4, n=512)  # 16 batches/epoch
    m_frozen.configure_optimizers = lambda: __import__("optax").sgd(0.0)
    t.fit(m_frozen)
    # Stopped after the patience ran out mid-epoch, well before 16 steps.
    assert t.global_step < 16, t.global_step

    with pytest.raises(ValueError, match="exceeds"):
        Trainer(
            max_epochs=1, enable_checkpointing=False, seed=0,
            num_sanity_val_steps=0, val_check_interval=99,
        ).fit(_DetModule(batch_size=4, n=96))

    with pytest.raises(ValueError, match="val_check_interval"):
        Trainer(val_check_interval=float("nan"))


def test_mid_epoch_checkpoint_reruns_epoch(tmp_path):
    """A checkpoint written by a mid-epoch val resumes by RE-RUNNING that
    epoch (never skipping its remaining batches)."""
    from ray_lightning_tpu.trainer import ModelCheckpoint, Trainer

    # 3 batches/epoch; interval val at batch 1 saves mid-epoch.
    m = _DetModule(batch_size=4, n=96)
    ck = ModelCheckpoint(
        dirpath=str(tmp_path), monitor="val_loss", save_top_k=-1
    )
    t = Trainer(
        max_epochs=1, enable_checkpointing=True, callbacks=[ck], seed=0,
        num_sanity_val_steps=0, val_check_interval=1,
    )
    t.fit(m)
    # Saves at steps 1, 2, 3 (epoch end). The step-1 checkpoint is
    # mid-epoch: resuming from it re-runs epoch 0.
    mid = sorted(
        p for p in os.listdir(tmp_path) if p.endswith("step=1.ckpt")
    )
    assert mid, os.listdir(tmp_path)
    m2 = _DetModule(batch_size=4, n=96)
    t2 = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    t2.fit(m2, ckpt_path=str(tmp_path / mid[0]))
    assert t2.current_epoch == 0  # re-ran epoch 0, did not skip to "done"
    assert t2.global_step == 1 + 3  # restored step + full epoch re-run

    # The epoch-END checkpoint still resumes at the next epoch.
    end = [p for p in os.listdir(tmp_path) if p.endswith("step=3.ckpt")]
    m3 = _DetModule(batch_size=4, n=96)
    t3 = Trainer(
        max_epochs=2, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    t3.fit(m3, ckpt_path=str(tmp_path / end[0]))
    assert t3.current_epoch == 1 and t3.global_step == 6


def test_mid_epoch_resume_resets_accumulation_window(tmp_path):
    """Resuming a mid-epoch checkpoint re-runs the epoch from batch 0, so
    the restored partial accumulation window must be cleared — keeping it
    shifts the window phase (and with non-deterministic data would
    double-count gradients)."""
    import numpy as np

    from ray_lightning_tpu.trainer import ModelCheckpoint, Trainer

    common = dict(
        max_epochs=1, seed=0, num_sanity_val_steps=0,
        accumulate_grad_batches=2,
    )
    # Straight run: 3 batches -> window {b1,b2} updates, b3 flushes.
    m_ref = _DetModule(batch_size=4, n=96)
    Trainer(enable_checkpointing=False, **common).fit(m_ref)

    # Save mid-epoch at batch 1 (mini_step=1 pending in opt_state).
    m1 = _DetModule(batch_size=4, n=96)
    ck = ModelCheckpoint(
        dirpath=str(tmp_path), monitor="val_loss", save_top_k=-1
    )
    Trainer(
        enable_checkpointing=True, callbacks=[ck], val_check_interval=1,
        **common,
    ).fit(m1)
    mid = [p for p in os.listdir(tmp_path) if p.endswith("step=1.ckpt")]
    assert mid

    # Resume: re-runs the epoch from init params; with the window cleared
    # the result is identical to the straight run.
    m2 = _DetModule(batch_size=4, n=96)
    Trainer(enable_checkpointing=False, **common).fit(
        m2, ckpt_path=str(tmp_path / mid[0])
    )
    np.testing.assert_allclose(
        np.asarray(m2.params["w"]), np.asarray(m_ref.params["w"]), atol=0
    )


def test_token_bin_sharded_dir_and_stats_mfu(tmp_path):
    """Directory-of-shards corpora concatenate without straddling shard
    boundaries; TPUStatsCallback computes MFU only on known chips."""
    import cloudpickle
    import numpy as np

    from ray_lightning_tpu.trainer import (
        TokenBinDataset, TPUStatsCallback, Trainer, write_token_bin,
    )

    d = tmp_path / "corpus"
    d.mkdir()
    a = np.arange(0, 500) % 64
    b = np.arange(500, 1000) % 64
    write_token_bin(str(d / "00.bin"), a)
    write_token_bin(str(d / "01.bin"), b)
    ds = TokenBinDataset(str(d), seq_len=16)
    per = (500 - 17) // 16 + 1  # windows per shard
    assert len(ds) == 2 * per
    np.testing.assert_array_equal(ds[0], a[:17])
    np.testing.assert_array_equal(ds[per], b[:17])  # first window of shard 2
    # Last window of shard 1 stays inside shard 1 (no straddle).
    np.testing.assert_array_equal(
        ds[per - 1], a[(per - 1) * 16 : (per - 1) * 16 + 17]
    )
    clone = cloudpickle.loads(cloudpickle.dumps(ds))
    np.testing.assert_array_equal(clone[per + 3], ds[per + 3])
    import pytest

    with pytest.raises(IndexError):
        ds[len(ds)]

    # MFU: on CPU there's no known peak -> skipped, everything else intact.
    stats = TPUStatsCallback(verbose=False, flops_per_step=1e9)
    m = _DetModule(batch_size=4, n=96)
    t = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0, callbacks=[stats],
    )
    t.fit(m)
    assert stats.epoch_times and stats.mfu == []
    assert "mfu" not in t.callback_metrics


# ---------------------------------------------------------------------------
# steps_per_execution (folded dispatch): per-step math must be identical
# to the single-step loop — only host dispatch cadence changes.
# ---------------------------------------------------------------------------


def _fit_det(start_fabric, *, n=32, batch_size=4, **trainer_kw):
    import numpy as np

    from ray_lightning_tpu.strategies import RayTPUStrategy
    from ray_lightning_tpu.trainer import Trainer

    start_fabric(num_cpus=2)
    m = _DetModule(batch_size=batch_size, n=n)
    trainer = Trainer(
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        strategy=RayTPUStrategy(num_workers=2, use_tpu=False),
        **trainer_kw,
    )
    trainer.fit(m)
    return trainer, np.asarray(m.params["w"])


def test_steps_per_execution_matches_single(start_fabric):
    """K=4 folding: final params, step count, and epoch-mean loss equal
    the single-step loop (8 batches/epoch divide evenly)."""
    import numpy as np

    t1, w1 = _fit_det(start_fabric, max_epochs=2)
    t4, w4 = _fit_det(start_fabric, max_epochs=2, steps_per_execution=4)
    np.testing.assert_allclose(w4, w1, rtol=1e-6, atol=1e-7)
    # 32 rows shard to 16 per worker -> 4 batches/epoch x 2 epochs.
    assert t4.global_step == t1.global_step == 8
    np.testing.assert_allclose(
        float(t4.callback_metrics["loss"]),
        float(t1.callback_metrics["loss"]),
        rtol=1e-6,
    )


def test_steps_per_execution_tail_remainder(start_fabric):
    """5 batches/epoch (40 rows -> 20/worker) with K=4: one folded chunk
    + a 1-step tail via the single-step executable; equivalence holds."""
    import numpy as np

    t1, w1 = _fit_det(start_fabric, n=40, max_epochs=1)
    tk, wk = _fit_det(start_fabric, n=40, max_epochs=1, steps_per_execution=4)
    np.testing.assert_allclose(wk, w1, rtol=1e-6, atol=1e-7)
    assert tk.global_step == t1.global_step == 5


def test_steps_per_execution_max_steps_exact(start_fabric):
    """max_steps=6 with K=4: the second chunk is capped to 2 single
    steps — the budget is exact, never overshot by folding."""
    import numpy as np

    t1, w1 = _fit_det(start_fabric, max_epochs=5, max_steps=6)
    tk, wk = _fit_det(
        start_fabric, max_epochs=5, max_steps=6, steps_per_execution=4
    )
    assert tk.global_step == t1.global_step == 6
    np.testing.assert_allclose(wk, w1, rtol=1e-6, atol=1e-7)


def test_steps_per_execution_composes_with_accumulation(start_fabric):
    """K=4 folding x accumulate_grad_batches=2: the on-device MultiSteps
    window rides inside the scan; params match the single-step loop."""
    import numpy as np

    t1, w1 = _fit_det(start_fabric, max_epochs=2, accumulate_grad_batches=2)
    tk, wk = _fit_det(
        start_fabric,
        max_epochs=2,
        accumulate_grad_batches=2,
        steps_per_execution=4,
    )
    np.testing.assert_allclose(wk, w1, rtol=1e-6, atol=1e-7)
    assert tk.global_step == t1.global_step


def test_steps_per_execution_vci_alignment(start_fabric):
    """An unaligned val_check_interval fails fast."""
    import pytest

    with pytest.raises(ValueError, match="multiple of steps_per_execution"):
        _fit_det(
            start_fabric,
            max_epochs=1,
            steps_per_execution=4,
            val_check_interval=3,
        )


def test_steps_per_execution_validation():
    import pytest

    from ray_lightning_tpu.trainer import Trainer

    with pytest.raises(ValueError, match="steps_per_execution"):
        Trainer(steps_per_execution=0)


def test_steps_per_execution_ring_and_sharded(start_fabric):
    """Folding through the OTHER compiled-step builders: ring's explicit
    shard_map/pmean override and ZeRO's sharded optimizer both produce
    params identical to their single-step runs."""
    import numpy as np

    from ray_lightning_tpu.strategies import RayShardedStrategy, RingTPUStrategy
    from ray_lightning_tpu.trainer import Trainer

    start_fabric(num_cpus=2)
    for make in (
        lambda: RingTPUStrategy(num_workers=2, use_tpu=False),
        lambda: RayShardedStrategy(num_workers=2, use_tpu=False, zero_stage=3),
    ):
        ws = []
        for k in (1, 4):
            m = _DetModule(batch_size=4, n=32)
            t = Trainer(
                max_epochs=2,
                enable_checkpointing=False,
                seed=0,
                num_sanity_val_steps=0,
                steps_per_execution=k,
                strategy=make(),
            )
            t.fit(m)
            ws.append((t.global_step, np.asarray(m.params["w"])))
        (s1, w1), (s4, w4) = ws
        assert s1 == s4
        np.testing.assert_allclose(w4, w1, rtol=1e-6, atol=1e-7)


def test_fast_dev_run(start_fabric):
    """fast_dev_run=True: one train batch + one val batch, one epoch, no
    sanity val, no checkpoints — and metrics still come back."""
    import numpy as np
    import pytest

    from ray_lightning_tpu.strategies import RayTPUStrategy
    from ray_lightning_tpu.trainer import Trainer

    start_fabric(num_cpus=2)
    m = _DetModule(batch_size=4, n=32)
    trainer = Trainer(
        fast_dev_run=True,
        max_epochs=50,  # overridden to 1
        seed=0,
        strategy=RayTPUStrategy(num_workers=2, use_tpu=False),
    )
    trainer.fit(m)
    assert trainer.global_step == 1
    assert trainer.current_epoch == 0
    assert np.isfinite(float(trainer.callback_metrics["loss"]))
    assert np.isfinite(float(trainer.callback_metrics["val_loss"]))

    m3 = _DetModule(batch_size=4, n=32)
    t3 = Trainer(
        fast_dev_run=3,
        seed=0,
        strategy=RayTPUStrategy(num_workers=2, use_tpu=False),
    )
    t3.fit(m3)
    assert t3.global_step == 3

    # PTL semantics: budgets/cadences silently overridden...
    t5 = Trainer(fast_dev_run=True, max_steps=50, limit_val_batches=0)
    assert t5.max_steps == 1 and t5.limit_val_batches == 1
    # ...but conflicting DEBUG modes and invalid values fail fast.
    with pytest.raises(ValueError, match="fast_dev_run"):
        Trainer(fast_dev_run=-1)
    with pytest.raises(ValueError, match="fast_dev_run"):
        Trainer(fast_dev_run=2.7)
    with pytest.raises(ValueError, match="mutually"):
        Trainer(fast_dev_run=True, overfit_batches=2)
    # Cadences reset so the one-epoch run still validates; checkpoint,
    # early-stopping, and logger callbacks (incl. user-supplied) drop.
    from ray_lightning_tpu.trainer import (
        CSVLogger,
        EarlyStopping,
        ModelCheckpoint,
    )

    t = Trainer(
        fast_dev_run=True,
        check_val_every_n_epoch=5,
        val_check_interval=10,
        callbacks=[
            ModelCheckpoint(dirpath="/tmp/nope"),
            EarlyStopping(monitor="nope"),
            CSVLogger("/tmp/nope"),
        ],
    )
    assert t.check_val_every_n_epoch == 1
    assert t.val_check_interval is None
    assert not t.callbacks


def test_steps_per_execution_folds_eval_exactly(start_fabric):
    """Folded eval epochs match unfolded metrics to float tolerance —
    masked (sums, count) accumulation is associative (the on-device
    chunk partials only reassociate fp32 summation order), including a
    non-divisible tail (fold 4 -> chunks + singles)."""
    import numpy as np

    t1, _ = _fit_det(start_fabric, n=40, max_epochs=1)
    tk, _ = _fit_det(start_fabric, n=40, max_epochs=1, steps_per_execution=4)
    v1 = float(t1.callback_metrics["val_loss"])
    vk = float(tk.callback_metrics["val_loss"])
    np.testing.assert_allclose(vk, v1, rtol=1e-6)


def test_fold_mid_epoch_checkpoint_and_resume(tmp_path):
    """Folding x checkpointing: a vci-aligned mid-chunk-boundary save
    under steps_per_execution=2 resumes with the mid-epoch re-run
    semantics, and resuming a folded run into an UNFOLDED trainer (and
    vice versa) converges to the same params — the fold is an execution
    detail, invisible to checkpoints."""
    import os

    import numpy as np

    from ray_lightning_tpu.trainer import ModelCheckpoint, Trainer

    # In-process (no strategy, 8 virtual devices -> global batch 32):
    # 6 batches/epoch (n=192).
    def fit(fold, resume=None, epochs=1, ckpt_dir=None):
        m = _DetModule(batch_size=4, n=192)
        cbs = []
        if ckpt_dir:
            cbs = [ModelCheckpoint(
                dirpath=str(ckpt_dir), monitor="val_loss", save_top_k=-1
            )]
        t = Trainer(
            max_epochs=epochs, enable_checkpointing=bool(ckpt_dir),
            callbacks=cbs, seed=0, num_sanity_val_steps=0,
            steps_per_execution=fold,
            val_check_interval=2 if ckpt_dir else None,
        )
        t.fit(m, ckpt_path=resume)
        return t, np.asarray(m.params["w"])

    t, _ = fit(2, ckpt_dir=tmp_path)
    assert t.global_step == 6
    mid = [p for p in os.listdir(tmp_path) if p.endswith("step=2.ckpt")]
    assert mid, os.listdir(tmp_path)

    # Folded-save -> unfolded-resume and folded-resume: identical params.
    t1, w1 = fit(1, resume=str(tmp_path / mid[0]))
    t2, w2 = fit(2, resume=str(tmp_path / mid[0]))
    assert t1.current_epoch == t2.current_epoch == 0  # epoch re-run
    assert t1.global_step == t2.global_step == 2 + 6
    np.testing.assert_allclose(w2, w1, rtol=1e-6, atol=1e-7)
