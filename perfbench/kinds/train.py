"""The ``train`` kind of cell: ``Trainer.fit`` under the strategy the mix
names, timed from a callback inside the fit worker, and its first
dispatch held against the plain reference.

The parent process never touches JAX. The worker's one compiled step with
its one state is driven from the seed through its first dispatch (whose
losses, first-moment norms and parameter change are the evidence the
reference is held against), through the warm-up dispatches, and then —
the same object — through the timed window.

A kind is ``run(ctx) -> run`` (``perfbench/README.md`` has the keys);
the harness finds it by the mix's ``kind``. A variant of this one — a
test's planted fault, say — is a file of its own that calls ``run`` with
its own ``Tap`` or module class.
"""
from __future__ import annotations

import itertools
import json
import math
import os
import time
from typing import Any, Dict, List, Optional

from ray_lightning_tpu.models.gpt import GPTLM
from ray_lightning_tpu.trainer.callbacks import Callback

from pb import traffic, weights
from pb.harness import check_line, run_reference, say, teardown


# -- the program's objects, fed by the benchmark -------------------------
class BenchGPTLM(GPTLM):
    """The program's LM module with the benchmark's seeded weights and
    corpus in place of its own unseeded ones."""

    def __init__(self, dims: Dict[str, Any], seed: int, corpus: Dict[str, Any], **kw: Any) -> None:
        super().__init__(**kw)
        self._bench_dims = dims
        self._bench_seed = int(seed)
        self._bench_corpus = dict(corpus)

    def init_params(self, rng: Any, batch: Any) -> Any:
        import jax

        # One jitted call on the first accelerator, whatever default
        # device the loop set around this hook; handed back as host
        # arrays, which is where the loop wants the unsharded state.
        tree = weights.make_params(
            self._bench_seed, self._bench_dims, self.config.max_seq,
            "float32", device=jax.devices()[0],
        )
        return jax.device_get(tree)

    def _data(self) -> Any:
        from ray_lightning_tpu.trainer.data import ArrayDataset

        if self._dataset is None:
            self._dataset = ArrayDataset(traffic.fake_text(
                int(self._bench_corpus["rows"]), self.config.max_seq,
                self.config.vocab_size, self._bench_seed,
                float(self._bench_corpus.get("noise", 0.05)),
            ))
        return self._dataset

    def val_dataloader(self) -> Any:
        return None


class Tap:
    """Mixed into the strategy class the mix names: keeps what the first
    dispatch fed the compiled step and what it logged (device arrays;
    nothing is fetched here)."""

    KEEP = 1

    def dispatch(self, step: Any, params: Any, opt_state: Any, payload: Any, rng: Any, step_idx: Any) -> Any:
        """One folded dispatch of the compiled step, as the loop calls it."""
        return step(params, opt_state, payload, rng, step_idx)

    def compile_train_step(self, module: Any, tx: Any, **kw: Any) -> Any:
        step = super().compile_train_step(module, tx, **kw)  # type: ignore[misc]
        if kw.get("fold_steps", 1) <= 1:
            return step  # the tail executable: never dispatched in a cell
        self.bench_tapped: List[Any] = []

        def tapped(params, opt_state, payload, rng, step_idx):
            out = self.dispatch(step, params, opt_state, payload, rng, step_idx)
            if len(self.bench_tapped) < self.KEEP:
                self.bench_tapped.append((payload, out[2]))
            return out

        return tapped


def tapped_strategy(name: str, tap: type = Tap) -> type:
    """The program's strategy class ``name`` with ``tap`` mixed in. The
    class is made here and belongs to no module, so it travels to the
    worker by value, its two bases by reference."""
    from ray_lightning_tpu import strategies

    return type("Bench" + name, (tap, getattr(strategies, name)), {})


def _adam_state(opt_state: Any) -> Any:
    import jax

    for node in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(node, "mu"):
            return node
    raise RuntimeError("no Adam state (a node with .mu) in the optimizer state")


class WindowCallback(Callback):
    """Evidence after the first dispatch, then the timed window."""

    def __init__(self, out_dir: str, seconds: float, trace: bool, fold: int,
                 warm_dispatches: int, trace_dispatches: int, chips: int,
                 require_tpu: bool, dims: Dict[str, Any]) -> None:
        self.out_dir = out_dir
        self.dims = dict(dims)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.fold = int(fold)
        self.warm = int(warm_dispatches)
        self.trace_dispatches = int(trace_dispatches)
        self.chips = int(chips)
        self.require_tpu = bool(require_tpu)
        self.n_disp = 0
        self.evidence_s = 0.0
        self.t0: Optional[float] = None
        self.closed = False
        self.res: Dict[str, Any] = {}
        self.window_losses: List[float] = []
        self.dispatch_done: List[float] = []

    # -- hooks -------------------------------------------------------------
    def on_fit_start(self, trainer: Any, module: Any) -> None:
        import jax

        devs = jax.devices()
        self.res["worker_ready_wall"] = time.time()
        self.res["device"] = {
            "platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
        }
        if self.require_tpu and devs[0].platform != "tpu":
            raise RuntimeError(f"fit worker runs on {devs[0].platform!r}, not on a TPU")
        if len(devs) != self.chips:
            raise RuntimeError(f"fit worker sees {len(devs)} devices, the cell asks for {self.chips}")
        t = time.monotonic()
        self.p0 = jax.device_get(trainer.params)
        mult = trainer.strategy.batch_multiplier
        # The rows of the first dispatch, re-read from the loop's own
        # loader (same sampler, same epoch: the same order).
        trainer._train_loader.set_epoch(0)
        rows = list(itertools.islice(trainer._train_loader.iter_batches(mult), self.fold))
        import numpy as np

        self.first_rows = np.stack([np.asarray(r[0] if isinstance(r, (tuple, list)) else r) for r in rows])
        self.evidence_s += time.monotonic() - t

    def on_train_batch_end(self, trainer: Any, module: Any, logs: Dict[str, float], batch_idx: int) -> None:
        now = time.monotonic()
        self.n_disp += 1
        if self.n_disp == 1:
            self._first_dispatch(trainer)
        if self.t0 is None:
            if self.n_disp >= self.warm:
                self._open(trainer)
            return
        if self.closed:
            return
        self.window_losses.append(float(logs.get("loss", float("nan"))))
        self.dispatch_done.append(now - self.t0)
        if self.trace and self._tracing and len(self.dispatch_done) >= self.trace_dispatches:
            # Writing the trace takes seconds in which nothing is dispatched:
            # the traced dispatches give the trace's metrics, and the window
            # that the counters and the rate are taken over starts afresh.
            self._stop_trace()
            self._open(trainer, trace=False)
            return
        if now - self.t0 >= self.seconds:
            self._close(trainer, now)

    def on_fit_end(self, trainer: Any, module: Any) -> None:
        if self.t0 is not None and not self.closed:
            self._close(trainer, time.monotonic())
        self.res["evidence_s"] = self.evidence_s
        with open(os.path.join(self.out_dir, "program.json"), "w") as f:
            json.dump(self.res, f)

    # -- pieces --------------------------------------------------------------
    def _first_dispatch(self, trainer: Any) -> None:
        import jax
        import numpy as np

        t = time.monotonic()
        # The program's peak, read after its first real dispatch and before
        # the check puts anything of its own on the device: every later
        # dispatch is the same program over the same buffers.
        self.res["memory_peak_bytes"] = [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.local_devices()
        ]
        tapped = getattr(trainer.strategy, "bench_tapped", [])
        if not tapped:
            raise RuntimeError("the strategy tap saw no folded dispatch")
        payload, logs = tapped[0]
        toks = payload[0] if isinstance(payload, (tuple, list)) else payload
        fed = np.asarray(jax.device_get(toks))
        ev: Dict[str, Any] = {
            "losses": [float(x) for x in np.asarray(jax.device_get(logs["loss"])).reshape(-1)],
            "fed_equals_loader": bool(np.array_equal(fed, self.first_rows)),
            "rows_all_differ": bool(len({r.tobytes() for r in fed.reshape(-1, fed.shape[-1])}) == fed.shape[0] * fed.shape[1]),
            "batch_shards": len(toks.addressable_shards),
            "batch_devices": len({s.device for s in toks.addressable_shards}),
        }
        np.save(os.path.join(self.out_dir, "first_rows.npy"), fed)
        adam = _adam_state(trainer.opt_state)
        from pb.plug import family_of
        from pb.reference import leaf_names, leaf_norms, leaf_sketches, part_norms

        ev["mu_norms"] = leaf_norms(adam.mu)
        ev["mu_sketch"] = leaf_sketches(adam.mu)
        leaves = jax.tree_util.tree_leaves(adam.mu)
        ev["opt_sharded_leaves"] = sum(
            1 for x in leaves if x.addressable_shards[0].data.size < x.size
        )
        # The change of each leaf since the fit began, on the device, a
        # leaf at a time (the copy of the start lives on the host); fused
        # leaves part by part, as the family splits them.
        split = getattr(family_of(self.dims), "SPLIT", {})
        ev["delta_norms"] = {}
        for n, a, b in zip(leaf_names(trainer.params), jax.tree_util.tree_leaves(trainer.params),
                           jax.tree_util.tree_leaves(self.p0)):
            ev["delta_norms"].update(part_norms(n, a - jax.device_put(b, a.sharding), split))
        self.p0 = None
        if self.chips > 1:
            # Every device holds the same parameters after a real step.
            same = True
            for leaf in jax.tree_util.tree_leaves(trainer.params):
                sums = {float(jax.numpy.sum(s.data.astype(jax.numpy.float32)))
                        for s in leaf.addressable_shards if s.data.size == leaf.size}
                same = same and len(sums) <= 1
            ev["devices_hold_same_params"] = same
        tapped.clear()
        self.res["evidence"] = ev
        self.evidence_s += time.monotonic() - t

    def _open(self, trainer: Any, trace: bool = True) -> None:
        from ray_lightning_tpu.obs.jaxmon import compile_stats

        self._tracing = False
        self.window_losses, self.dispatch_done = [], []
        if self.trace and trace:
            import jax

            self._trace_dir = os.path.join(self.out_dir, "trace")
            jax.profiler.start_trace(self._trace_dir)
            self._tracing = True
            self._trace_t0 = time.monotonic()
        tel = trainer.telemetry
        self._tel0 = (tel.data_wait_s, tel.step_s, tel.drain_s, tel.chunks)
        self._compiles0 = compile_stats().count("backend_compile")
        self._step0 = trainer.global_step
        self.res.setdefault("window_open_wall", time.time())
        self.t0 = time.monotonic()

    def _stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self._tracing = False
        self.res["trace"] = {
            "dir": self._trace_dir,
            "window_s": time.monotonic() - self._trace_t0,
            "dispatches": len(self.dispatch_done),
        }

    def _close(self, trainer: Any, now: float) -> None:
        import jax

        from ray_lightning_tpu.obs.jaxmon import compile_stats

        if self.trace and self._tracing:
            self._stop_trace()
        self.closed = True
        trainer.should_stop = True
        tel = trainer.telemetry
        steps = trainer.global_step - self._step0
        self.res["window"] = {
            "seconds": now - self.t0,
            "steps": steps,
            "dispatches": len(self.dispatch_done),
            "dispatch_done_s": self.dispatch_done,
            "losses": self.window_losses,
            # The chunk that closes the window is recorded by the loop
            # after this hook: the split covers all but that last one.
            "data_wait_s": tel.data_wait_s - self._tel0[0],
            "dispatch_s": tel.step_s - self._tel0[1],
            "drain_s": tel.drain_s - self._tel0[2],
            "chunks": tel.chunks - self._tel0[3],
            "compiles": compile_stats().count("backend_compile") - self._compiles0,
        }
        self.res["memory_peak_bytes_at_close"] = [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.local_devices()
        ]


# -- the parent's side -----------------------------------------------------
def fit(ctx: Dict[str, Any], tap: type = Tap, module_cls: type = BenchGPTLM) -> Dict[str, Any]:
    """Fit once; return what the worker wrote (``program.json``)."""
    import dataclasses

    from ray_lightning_tpu.models.gpt import GPTConfig
    from ray_lightning_tpu.trainer import Trainer

    mix, cfg, dims = ctx["mix"], ctx["config"], ctx["dims"]
    chips, seed = ctx["chips"], ctx["seed"]
    fold = int(mix["steps_per_execution"])
    pc = GPTConfig(**cfg["program_config"])
    if pc.max_seq != int(mix["seq"]):
        pc = dataclasses.replace(pc, max_seq=int(mix["seq"]))
    opt = mix["optimizer"]
    module = module_cls(
        dims, seed, mix["corpus"], config=pc, batch_size=int(mix["per_chip_batch"]),
        n_train=int(mix["corpus"]["rows"]), lr=float(opt["lr"]),
        warmup_steps=int(opt["warmup_steps"]), weight_decay=float(opt["weight_decay"]),
    )
    strategy = tapped_strategy(mix["strategy"]["class"], tap)(
        num_workers=chips, use_tpu=not ctx["rehearse"], **mix["strategy"].get("args", {})
    )
    cb = WindowCallback(
        ctx["out_dir"], ctx["seconds"], ctx["trace"], fold,
        int(mix["warmup_dispatches"]), int(mix.get("trace_dispatches", 3)),
        chips, require_tpu=not ctx["rehearse"], dims=dims,
    )
    trainer = Trainer(
        max_epochs=int(mix.get("max_epochs", 1000)),
        strategy=strategy,
        steps_per_execution=fold,
        log_every_n_steps=fold,
        callbacks=[cb],
        limit_val_batches=0,
        num_sanity_val_steps=0,
        enable_model_summary=False,
        enable_checkpointing=False,
        ship_optimizer_state=False,
        default_root_dir=ctx["out_dir"],
        seed=seed & 0x7FFFFFFF,
    )
    t_fit = time.time()
    trainer.fit(module)
    with open(os.path.join(ctx["out_dir"], "program.json")) as f:
        res = json.load(f)
    res["fit_call_wall"] = t_fit
    res["fit_return_wall"] = time.time()
    return res


def run(ctx: Dict[str, Any], tap: type = Tap, module_cls: type = BenchGPTLM) -> Dict[str, Any]:
    """One run of the cell: the fit, the end of its processes, the
    reference, and each number compared beside its limit."""
    from pb import reference

    mix = ctx["mix"]
    res = fit(ctx, tap, module_cls)
    leftovers = teardown()
    ref = run_reference(ctx, {
        "kind": "train", "max_seq": int(mix["seq"]), "optimizer": mix["optimizer"],
        "micro": int(mix.get("reference_micro_batch", 2)),
    })
    ev, win, lim = res["evidence"], res["window"], ctx["limits"]
    checks: List[Dict[str, Any]] = []
    r = ref["reference"]
    noise = tuple(reference.noise_leaves(r["mu_view_norms"]))
    mu_gap, mu_leaf = reference.norm_gap(ev["mu_norms"], r["mu_norms"])
    d_gap, d_leaf = reference.norm_gap(ev["delta_norms"], r["delta_norms"], skip=noise)
    sk_gap, sk_leaf = reference.sketch_diff(ev["mu_sketch"], r["mu_sketch"])
    numbers = {
        "loss_abs": max(abs(a - b) for a, b in zip(ev["losses"], r["losses"])),
        "mu_sketch_diff": sk_gap,
        "mu_norm_gap": mu_gap,
        "delta_norm_gap": d_gap,
    }
    say(f"losses program={ev['losses']} reference={r['losses']}")
    say(f"worst leaves: first moment {mu_leaf!r} (sketch {sk_leaf!r}), parameter change {d_leaf!r}; "
        f"left out of the parameter change, their reference gradient being rounding alone: {list(noise)}")
    for k, v in numbers.items():
        check_line(checks, k, v, lim[k], v <= lim[k])
    # every leaf's gap, for whoever sets a limit: the largest few
    by_leaf = sorted(reference.norm_gaps(ev["delta_norms"], r["delta_norms"], skip=noise).items(), key=lambda kv: -kv[1])
    ctx["delta_leaf_gaps"] = by_leaf[:6]
    say("parameter change, largest gaps by leaf: " + json.dumps(by_leaf[:6]))
    if "control" in ref:
        c = ref["control"]
        ctl = {
            "loss_abs": max(abs(a - b) for a, b in zip(c["losses"], r["losses"])),
            "mu_sketch_diff": reference.sketch_diff(c["mu_sketch"], r["mu_sketch"])[0],
            "mu_norm_gap": reference.norm_gap(c["mu_norms"], r["mu_norms"])[0],
            "delta_norm_gap": reference.norm_gap(c["delta_norms"], r["delta_norms"], skip=noise)[0],
        }
        say("control " + json.dumps(ctl))
        ctx["control_numbers"] = ctl
    check_line(checks, "steps_followed", len(ev["losses"]), len(r["losses"]), len(ev["losses"]) == len(r["losses"]))
    check_line(checks, "fed_rows_are_the_loaders", ev["fed_equals_loader"], True, ev["fed_equals_loader"])
    check_line(checks, "rows_all_differ", ev["rows_all_differ"], True, ev["rows_all_differ"])
    finite = all(math.isfinite(x) for x in win["losses"])
    check_line(checks, "window_losses_finite", finite, True, finite)
    if ctx["chips"] > 1:
        check_line(checks, "batch_shards", ev["batch_devices"], ctx["chips"], ev["batch_devices"] == ctx["chips"])
        check_line(checks, "optimizer_state_sharded_leaves", ev["opt_sharded_leaves"], ">0", ev["opt_sharded_leaves"] > 0)
        check_line(checks, "devices_hold_same_params", ev["devices_hold_same_params"], True, ev["devices_hold_same_params"])
    fold = int(mix["steps_per_execution"])
    tokens = win["steps"] * int(mix["per_chip_batch"]) * ctx["chips"] * int(mix["seq"])
    e2e = {
        "train_tokens_per_s_per_chip": tokens / win["seconds"] / ctx["chips"] if win["seconds"] > 0 else 0.0,
        "setup_s": res["window_open_wall"] - ctx["t_start"] - res["evidence_s"],
    }
    say(f"peak HBM per device (memory_stats peak_bytes_in_use) after the first dispatch: {res['memory_peak_bytes']}; at window close, the check's own arrays included: {res['memory_peak_bytes_at_close']}")
    say(f"window: {win['steps']} steps in {win['seconds']:.3f} s; evidence {res['evidence_s']:.2f} s and "
        f"reference {ref['wall_s']:.1f} s are outside set-up and window; leftovers: {leftovers}")
    return {
        "numbers": numbers, "checks": checks, "e2e": e2e, "program": res, "reference": ref,
        "attempted": win["steps"],
        "failed": fold * sum(1 for x in win["losses"] if not math.isfinite(x)),
        # as jax reports it when the window closes (the check's sketch of the
        # first dispatch included; the line above says what the program held alone)
        "device": res["device"], "memory_peak_bytes": max(res["memory_peak_bytes_at_close"] or [0]),
        "trace": res.get("trace"),
        "between": "between dispatches: log drain, callbacks, next staged batch",
    }
