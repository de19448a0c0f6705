"""Serving replica: a fabric actor hosting one DecodeEngine + Scheduler.

One replica = one actor process owning one compiled engine. The actor's
RPC surface (``submit`` / ``result`` / ``cancel`` / ``stats``) only
touches host-side queues; a daemon loop thread drives the scheduler so
ALL jax work happens on one thread while requests stream in through the
fabric connection. Multi-replica gangs are spawned through
``serve.client.start_replicas`` (placement groups on the existing
fabric); this module stays import-light so the actor process configures
jax from its env before anything heavy loads.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_lightning_tpu.obs.trace import span


def load_serve_params(
    ckpt_path: str, model_config: Optional[Dict[str, Any]] = None
) -> tuple:
    """Load (params, GPTConfig) for serving from a checkpoint path.

    Accepts the three checkpoint shapes the repo produces:
    - ``convert-hf`` / serve-native state streams: ``{"params", "gpt_config"}``
      (``model_config`` entries override the stored config);
    - trainer state streams: ``{"params": ...}`` (+ optimizer state,
      ignored) — needs ``model_config``;
    - sharded orbax dirs: restored host-side against a fresh param tree —
      needs ``model_config``.
    """
    from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
    from ray_lightning_tpu.trainer.checkpoint_io import is_sharded_checkpoint

    overrides = dict(model_config or {})
    if is_sharded_checkpoint(ckpt_path):
        if not overrides:
            raise ValueError(
                "serving a sharded (orbax) checkpoint needs the model "
                "config (serve.config) to build the parameter tree"
            )
        import jax

        from ray_lightning_tpu.trainer.checkpoint_io import OrbaxCheckpointIO

        cfg = GPTConfig(**overrides)
        placed = {"params": init_gpt_params(jax.random.PRNGKey(0), cfg)}
        restored, _ = OrbaxCheckpointIO().restore(
            ckpt_path, placed, partial=True
        )
        return restored["params"], cfg
    from ray_lightning_tpu.trainer.trainer import Trainer
    from ray_lightning_tpu.utils.state_stream import load_state_stream

    tree = load_state_stream(Trainer._read_ckpt(ckpt_path))
    stored = dict(tree.get("gpt_config") or {})
    if stored:
        stored.update(overrides)
        cfg_fields = stored
    elif overrides:
        cfg_fields = overrides
    else:
        raise ValueError(
            f"checkpoint {ckpt_path} carries no gpt_config; pass the model "
            "config (serve.config)"
        )
    params = tree["params"] if "params" in tree else tree
    return params, GPTConfig(**cfg_fields)


#: Engine-facing construction kwargs a sharded-gang follower consumes —
#: leader-only knobs (scheduler, watchdog, obs, blackbox, RPC plumbing)
#: are absent from this set and are dropped before a follower builds its
#: engine mirror. ``kvstore_dir``/``kvstore_mb`` are deliberately
#: leader-only too: a follower writing its shard subset under the same
#: content digest would clobber the leader's store entry, so followers
#: run with no store and their broadcast ``evict_prefix_chain`` calls
#: are pure pool bookkeeping.
ENGINE_KEYS = frozenset((
    "ckpt_path", "model_config", "params", "int8", "num_slots", "max_seq",
    "prefill_buckets", "decode_fold", "pipeline", "prefill_chunk",
    "prefix_blocks", "prefix_block", "prefix_host_mb", "prefix_disk_dir",
    "prefix_disk_mb", "kv_page", "kv_pages", "spec", "spec_depth",
    "spec_draft_ckpt", "spec_draft_config", "spec_draft_int8",
    "spec_window", "mesh", "piggyback_chunks", "fold_ladder",
))


def build_engine(
    ckpt_path: Optional[str] = None,
    model_config: Optional[Dict[str, Any]] = None,
    params: Any = None,
    int8: bool = False,
    num_slots: int = 4,
    max_seq: Optional[int] = None,
    prefill_buckets: Optional[Sequence[int]] = None,
    decode_fold: int = 1,
    pipeline: bool = True,
    prefill_chunk: int = 0,
    prefix_blocks: int = 0,
    prefix_block: int = 16,
    prefix_host_mb: float = 0.0,
    prefix_disk_dir: Optional[str] = None,
    prefix_disk_mb: float = 0.0,
    kvstore_dir: Optional[str] = None,
    kvstore_mb: float = 0.0,
    kvstore_namespace: Optional[str] = None,
    kv_page: int = 0,
    kv_pages: int = 0,
    spec: str = "off",
    spec_depth: int = 4,
    spec_draft_ckpt: Optional[str] = None,
    spec_draft_config: Optional[Dict[str, Any]] = None,
    spec_draft_int8: bool = False,
    spec_window: int = 32,
    mesh: Optional[str] = None,
    piggyback_chunks: int = 0,
    fold_ladder: Optional[Sequence[int]] = None,
) -> Any:
    """Load weights (+ optional draft model) and construct the engine.

    Shared by the replica leader AND sharded-gang followers, so every
    process in a gang builds a bit-identical engine from the same
    checkpoint. ``mesh`` is a ``"MODELxDATA"`` spec string
    (``parallel.mesh.mesh_from_spec``); ``"1x1"``/None is the
    single-device engine.
    """
    from ray_lightning_tpu.models.gpt import GPTConfig
    from ray_lightning_tpu.parallel.mesh import mesh_from_spec
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.kvstore import (
        kvstore_namespace as _kvstore_namespace,
    )

    if params is None:
        if ckpt_path is None:
            raise ValueError("need ckpt_path or params")
        params, cfg = load_serve_params(ckpt_path, model_config)
    else:
        if model_config is None:
            raise ValueError("explicit params need model_config")
        cfg = (
            model_config
            if isinstance(model_config, GPTConfig)
            else GPTConfig(**model_config)
        )
    if int8:
        from ray_lightning_tpu.utils.quantize import quantize_params_int8

        params = quantize_params_int8(params)
    # Speculative decoding: the draft model (spec='model') loads like
    # the main checkpoint — state stream with embedded config, or
    # spec_draft_config overrides — and may quantize to int8 (draft
    # quality only gates the accept rate, never correctness).
    spec_params = None
    spec_cfg = None
    if spec == "model":
        if spec_draft_ckpt is None:
            raise ValueError(
                "spec='model' needs spec_draft_ckpt (the draft "
                "model's checkpoint)"
            )
        spec_params, spec_cfg = load_serve_params(
            spec_draft_ckpt, spec_draft_config
        )
        if spec_draft_int8:
            from ray_lightning_tpu.utils.quantize import (
                quantize_params_int8,
            )

            spec_params = quantize_params_int8(spec_params)
    return DecodeEngine(
        params,
        cfg,
        num_slots=num_slots,
        max_seq=max_seq,
        prefill_buckets=prefill_buckets,
        decode_fold=decode_fold,
        pipeline=pipeline,
        prefill_chunk=prefill_chunk,
        prefix_blocks=prefix_blocks,
        prefix_block=prefix_block,
        prefix_host_mb=prefix_host_mb,
        prefix_disk_dir=prefix_disk_dir,
        prefix_disk_mb=prefix_disk_mb,
        kvstore_dir=kvstore_dir,
        kvstore_mb=kvstore_mb,
        # Model-identity namespace for the persistent store. Derived
        # from the RAW (ckpt_path, model_config) kwargs — not the
        # loaded config — so the driver-side directory (serve_fleet)
        # and every gang member compute the identical string from the
        # identical inputs without loading the checkpoint.
        kvstore_namespace=(
            kvstore_namespace
            or _kvstore_namespace(ckpt_path, model_config)
        ),
        kv_page=kv_page,
        kv_pages=kv_pages,
        spec=spec,
        spec_depth=spec_depth,
        spec_params=spec_params,
        spec_config=spec_cfg,
        spec_window=spec_window,
        mesh=mesh_from_spec(mesh),
        piggyback_chunks=piggyback_chunks,
        fold_ladder=fold_ladder,
    )


def _setup_gang_rendezvous(dist: Dict[str, Any]) -> None:
    """Rendezvous this process with its gang peers (multi-host sharded
    serving): after ``jax.distributed.initialize`` every gang member
    sees the global device list the serve mesh spans. Must run before
    ANY jax work in the process."""
    if int(dist.get("num_hosts", 1)) <= 1:
        return
    from ray_lightning_tpu.parallel import mesh as mesh_lib
    from ray_lightning_tpu.parallel.env import DistEnv

    mesh_lib.setup_distributed(
        DistEnv(
            num_hosts=int(dist["num_hosts"]),
            host_rank=int(dist.get("host_rank", 0)),
            coordinator_address=dist.get("coordinator_address"),
        )
    )


class _GangLeaderEngine:
    """Leader-side engine proxy for a multi-host sharded serving gang.

    The multi-controller SPMD contract: every process in the gang must
    issue the IDENTICAL sequence of compiled dispatches against its
    shard of the mesh. The scheduler mutates the engine through exactly
    four methods (``admit_many`` / ``prefill_step`` / ``step`` /
    ``release``, plus the ``admit`` convenience wrapper); the leader
    ships each call's name + args to every follower BEFORE executing it
    locally, and followers replay the stream on bit-identical engines —
    all host-side bookkeeping (slot choice, prefix-pool walk, LRU) is a
    deterministic function of the op sequence alone, so the gang stays
    in lockstep without sharing any state. Reads delegate without
    broadcasting.
    """

    def __init__(self, engine: Any, queues: Sequence[Any]) -> None:
        self._engine = engine
        self._queues = list(queues)

    def __getattr__(self, name: str) -> Any:
        return getattr(self._engine, name)

    def _broadcast(self, name: str, args: tuple, kwargs: dict) -> None:
        for q in self._queues:
            q.put((name, args, kwargs))

    def admit(self, *args: Any, **kwargs: Any) -> Any:
        self._broadcast("admit", args, kwargs)
        return self._engine.admit(*args, **kwargs)

    def admit_many(self, *args: Any, **kwargs: Any) -> Any:
        self._broadcast("admit_many", args, kwargs)
        return self._engine.admit_many(*args, **kwargs)

    def prefill_step(self, *args: Any, **kwargs: Any) -> Any:
        self._broadcast("prefill_step", args, kwargs)
        return self._engine.prefill_step(*args, **kwargs)

    def step(self, *args: Any, **kwargs: Any) -> Any:
        self._broadcast("step", args, kwargs)
        return self._engine.step(*args, **kwargs)

    def release(self, *args: Any, **kwargs: Any) -> Any:
        self._broadcast("release", args, kwargs)
        return self._engine.release(*args, **kwargs)

    def import_prefix_blocks(self, *args: Any, **kwargs: Any) -> Any:
        # Pool mutation: followers must apply the identical import so
        # later alloc/promote choices stay in lockstep.
        self._broadcast("import_prefix_blocks", args, kwargs)
        return self._engine.import_prefix_blocks(*args, **kwargs)

    def export_blocks_by_digest(self, *args: Any, **kwargs: Any) -> Any:
        # Like export_prefix_blocks: a read that RUNS the compiled pool
        # read — the whole gang must issue the same dispatch sequence
        # (followers discard the result; the fleet KV fetch ships the
        # leader's view, same leader-shards-only caveat).
        self._broadcast("export_blocks_by_digest", args, kwargs)
        return self._engine.export_blocks_by_digest(*args, **kwargs)

    def export_prefix_blocks(self, *args: Any, **kwargs: Any) -> Any:
        # A read, but it RUNS the compiled pool read — under a real
        # multi-host mesh every process must issue the same dispatch
        # sequence, so the export is broadcast too (followers discard
        # the result). Each process serializes only its own shards;
        # cross-gang KV handoff therefore ships the LEADER's view — a
        # complete block single-host, leader-shards-only on a true
        # multi-host gang (documented caveat; the migration itself
        # stays correct either way).
        self._broadcast("export_prefix_blocks", args, kwargs)
        return self._engine.export_prefix_blocks(*args, **kwargs)

    def evict_prefix_chain(self, *args: Any, **kwargs: Any) -> Any:
        # Pool mutation (session parking frees the chain's pages):
        # followers must free the identical pages so later
        # alloc/promote choices stay in lockstep. The persistent-store
        # write happened BEFORE this call, leader-side only — followers
        # hold no kvstore (ENGINE_KEYS drops the config), so their
        # eviction is pure bookkeeping.
        self._broadcast("evict_prefix_chain", args, kwargs)
        return self._engine.evict_prefix_chain(*args, **kwargs)

    def close(self) -> None:
        """End-of-life sentinel: followers drain and exit their loops."""
        for q in self._queues:
            try:
                q.put(None)
            except Exception:  # noqa: BLE001 - best-effort drain
                pass


class ServeShardFollower:
    """``host_rank > 0`` member of a sharded serving gang (fabric actor).

    Rendezvouses with the gang (``setup_distributed``), builds the SAME
    engine under the SAME global mesh as the leader, then replays the
    leader's op stream (see :class:`_GangLeaderEngine`) on a daemon
    thread, so every process issues the identical SPMD dispatch
    sequence. No request surface — traffic enters through the leader
    only; a follower exists to hold its shard of the weights/KV and run
    its slice of every collective.
    """

    def __init__(
        self,
        op_queue: Any,
        dist: Optional[Dict[str, Any]] = None,
        faults: Any = None,
        **engine_kwargs: Any,
    ) -> None:
        from ray_lightning_tpu.obs.trace import RequestTracer
        from ray_lightning_tpu.serve.faults import FaultInjector

        # Fault injection (chaos tests): explicit plan or the RLT_FAULTS
        # env gate — the `follower_op` point wedges this op loop.
        self.faults = (
            FaultInjector.parse(faults) or FaultInjector.from_env()
        )
        _setup_gang_rendezvous(dict(dist or {}))
        self.engine = build_engine(
            **{k: v for k, v in engine_kwargs.items() if k in ENGINE_KEYS}
        )
        # Follower-side trace ring: the replayed op stream carries each
        # request's id (admit_many kwargs), so the engine's admission /
        # prefix-seed / chunk events land here under the SAME ids the
        # leader and client recorded — trace_dump() feeds them into the
        # stitched export as this process's track.
        self.tracer = RequestTracer(capacity=4096)
        self.engine.tracer = self.tracer
        self._queue = op_queue
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="serve-shard-follower", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        import queue as _q
        import sys

        while not self._stop.is_set():
            try:
                op = self._queue.get(timeout=0.25)
            except (_q.Empty, EOFError, BrokenPipeError, ConnectionError):
                continue
            if op is None:
                break
            name, args, kwargs = op
            if self.faults is not None:
                # Named wedge point: a chaos plan can hang this follower
                # mid-stream (the gang's collectives stop completing)
                # without killing its process — the failure mode a
                # watchdog must distinguish from a clean death.
                self.faults.hit("follower_op")
            try:
                getattr(self.engine, name)(*args, **kwargs)
            except Exception as exc:  # noqa: BLE001 - gang is broken
                # A desynced follower cannot be healed in place (every
                # subsequent collective would hang the gang); stop loud.
                print(
                    f"serve shard follower desync on {name}: "
                    f"{type(exc).__name__}: {exc}",
                    file=sys.stderr,
                    flush=True,
                )
                break

    def ping(self) -> str:
        return "ok"

    def trace_dump(self, n: int = 16) -> Dict[str, Any]:
        """This follower's trace ring in the stitching wire form."""
        return self.tracer.dump(n)

    def inject_fault(self, plan: Any) -> list:
        """Arm (or disarm with None) a fault plan on this LIVE follower
        — how a chaos test preempts/wedges ONE gang member of a fleet
        (the env gate arms every process identically). Replaces any
        previous plan; returns the armed rules."""
        from ray_lightning_tpu.serve.faults import FaultInjector

        inj = FaultInjector.parse(plan)
        self.faults = inj
        return [] if inj is None else inj.describe()

    def preempt_state(self) -> Dict[str, Any]:
        """This follower's preemption-monitor state (the RPC mirror of
        what its fabric heartbeats carry)."""
        from ray_lightning_tpu.serve.preempt import peek_state

        return peek_state() or {"pending": False}

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)


class ServeReplica:
    """One serving replica (designed to run as a fabric actor).

    ``params`` may be passed directly (tests, the benchmark) or loaded from
    ``ckpt_path``; ``int8=True`` quantizes the tree at load
    (utils.quantize_params_int8), which the engine consumes directly.
    ``mesh`` ("MODELxDATA", e.g. "4x1") makes the engine mesh-sharded
    over this process's devices; ``dist``/``gang_queues`` wire a
    multi-host gang (one process group per mesh — see
    ``serve.client.start_replicas`` ``hosts_per_replica``).
    """

    def __init__(
        self,
        ckpt_path: Optional[str] = None,
        model_config: Optional[Dict[str, Any]] = None,
        params: Any = None,
        int8: bool = False,
        num_slots: int = 4,
        max_seq: Optional[int] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        max_prefills_per_step: int = 1,
        decode_fold: int = 1,
        fold_ladder: Optional[Sequence[int]] = None,
        piggyback_chunks: int = 0,
        pipeline: bool = True,
        prefill_chunk: int = 0,
        prefix_blocks: int = 0,
        prefix_block: int = 16,
        prefix_host_mb: float = 0.0,
        prefix_disk_dir: Optional[str] = None,
        prefix_disk_mb: float = 0.0,
        kv_page: int = 0,
        kv_pages: int = 0,
        max_prefill_chunks_per_step: int = 1,
        spec: str = "off",
        spec_depth: int = 4,
        spec_draft_ckpt: Optional[str] = None,
        spec_draft_config: Optional[Dict[str, Any]] = None,
        spec_draft_int8: bool = False,
        spec_window: int = 32,
        priority_age_s: Optional[float] = None,
        tick_s: float = 0.002,
        tracing: bool = True,
        trace_capacity: int = 8192,
        journal: bool = True,
        journal_dir: Optional[str] = None,
        journal_capacity: int = 4096,
        router_config: Optional[Dict[str, Any]] = None,
        watchdog: bool = True,
        watchdog_interval_s: float = 1.0,
        stall_s: float = 10.0,
        slo: Optional[Dict[str, Any]] = None,
        blackbox_dir: Optional[str] = None,
        blackbox_keep: int = 3,
        mesh: Optional[str] = None,
        dist: Optional[Dict[str, Any]] = None,
        gang_queues: Optional[Sequence[Any]] = None,
        faults: Any = None,
        preempt_grace_s: float = 30.0,
        preempt_sigterm: bool = True,
        preempt_metadata: bool = False,
        role: str = "mixed",
        kv_self: Optional[int] = None,
        kv_inbox: Any = None,
        kv_peers: Optional[Dict[int, Any]] = None,
        kvfleet_timeout_s: float = 5.0,
        kvfleet_inflight_mb: float = 64.0,
        kvfleet_bandwidth_mbps: float = 0.0,
        kvfleet_layerwise: bool = False,
        kvstore_dir: Optional[str] = None,
        kvstore_mb: float = 0.0,
        kvstore_namespace: Optional[str] = None,
        kvstore_writethrough: bool = False,
    ) -> None:
        from ray_lightning_tpu.obs import blackbox as obs_blackbox
        from ray_lightning_tpu.obs import health as obs_health
        from ray_lightning_tpu.obs.events import get_event_log
        from ray_lightning_tpu.obs.jaxmon import (
            install_compile_listener,
            install_gc_hook,
        )
        from ray_lightning_tpu.obs.registry import get_registry
        from ray_lightning_tpu.serve.metrics import ServeMetrics
        from ray_lightning_tpu.serve.scheduler import Scheduler
        from ray_lightning_tpu.obs.trace import RequestTracer, SpanTotals

        # Gang leader on a multi-host mesh: rendezvous FIRST — after
        # jax.distributed.initialize every gang member sees the global
        # device list the serve mesh spans.
        self._dist = dict(dist or {})
        _setup_gang_rendezvous(self._dist)
        # Before anything compiles: the listener turns the engine's
        # frozen-compile contract into a metric (stats() ships
        # compiles_since_init, which must stay 0 in steady state).
        self._compile_stats = install_compile_listener()

        self.engine = build_engine(
            ckpt_path=ckpt_path,
            model_config=model_config,
            params=params,
            int8=int8,
            num_slots=num_slots,
            max_seq=max_seq,
            prefill_buckets=prefill_buckets,
            decode_fold=decode_fold,
            fold_ladder=fold_ladder,
            piggyback_chunks=piggyback_chunks,
            pipeline=pipeline,
            prefill_chunk=prefill_chunk,
            prefix_blocks=prefix_blocks,
            prefix_block=prefix_block,
            prefix_host_mb=prefix_host_mb,
            prefix_disk_dir=prefix_disk_dir,
            prefix_disk_mb=prefix_disk_mb,
            kvstore_dir=kvstore_dir,
            kvstore_mb=kvstore_mb,
            kvstore_namespace=kvstore_namespace,
            kv_page=kv_page,
            kv_pages=kv_pages,
            spec=spec,
            spec_depth=spec_depth,
            spec_draft_ckpt=spec_draft_ckpt,
            spec_draft_config=spec_draft_config,
            spec_draft_int8=spec_draft_int8,
            spec_window=spec_window,
            mesh=mesh,
        )
        self.int8 = bool(int8)
        # Multi-host gang: the scheduler drives a proxy that ships every
        # device-mutating call to the follower hosts before running it
        # locally (multi-controller lockstep); reads and stats stay on
        # the real engine.
        self._gang_queues = list(gang_queues or [])
        self._sched_engine: Any = self.engine
        if self._gang_queues:
            self._sched_engine = _GangLeaderEngine(
                self.engine, self._gang_queues
            )
        # Fleet KV plane: this replica's role (mixed | prefill |
        # decode) plus the cross-replica transfer wiring (its own inbox
        # queue + every peer's). A prefill replica ships every finished
        # prefill's KV pages, which only exist with a prefix pool —
        # reject the pointless config up front.
        from ray_lightning_tpu.serve.kvfleet import ROLES, KVFleetPlane

        self.role = str(role)
        if self.role not in ROLES:
            raise ValueError(
                f"unknown replica role {role!r}; valid roles: {ROLES}"
            )
        if self.role == "prefill" and not self.engine.prefix_blocks:
            raise ValueError(
                "role='prefill' needs a prefix pool to ship from: set "
                "prefix_blocks/prefix_cache (dense) or kv_pages (paged)"
            )
        self._registry = get_registry()
        self._registry.gauge(
            "rlt_serve_compiled_executables",
            "Engine executables compiled at construction",
        ).set(self.engine.compiled_count)
        # What the host does, by name (obs.trace.span): the loop
        # thread's totals are the engine's (it reckons exposed host time
        # from its own in-flight state); the RPC thread has its own, so
        # the two threads never share a clock of open spans.
        self.spans: SpanTotals = self.engine.spans
        self._rpc_spans = SpanTotals()
        self._span_seconds = self._registry.counter(
            "rlt_serve_loop_seconds_total",
            "Replica host seconds by span (loop thread and RPC surface)",
        )
        self._span_count = self._registry.counter(
            "rlt_serve_loop_spans_total",
            "Replica host spans completed, by span",
        )
        self._rider_seconds = self._registry.counter(
            "rlt_serve_loop_rider_seconds_total",
            "Request-seconds spent behind each loop span, by span and by "
            "kind (waiting for a first token / decoding)",
        )
        self._gc_seconds = self._registry.counter(
            "rlt_gc_pause_seconds_total",
            "Seconds the cyclic collector paused this process, by generation",
        )
        # The expert layers' totals (engine.moe_totals) and the dense KV
        # cache's bytes by layer kind, as registry series.
        self._moe_counters = {
            key: self._registry.counter(
                f"rlt_serve_moe_{key}_total", help_
            )
            for key, help_ in (
                ("pairs_routed", "(token, expert) pairs the router chose, over all experts, by phase"),
                ("pairs_held", "(token, expert) pairs that landed on experts this replica holds, by phase"),
                ("experts_hit", "Held experts that received a token, summed over expert layers, by phase"),
                ("token_steps", "Decode iterations with a live slot (phase=decode) and admissions (phase=prefill)"),
            )
        }
        self._ssm_counters = {
            key: self._registry.counter(f"rlt_serve_ssm_{key}_total", help_)
            for key, help_ in (
                ("slot_steps", "Slot-steps of the decode folds of a model with state layers (every slot, every iteration)"),
                ("slot_steps_live", "Slot-steps of those that belonged to a live request"),
                ("slot_steps_visited", "Slot-steps of those whose running state the step read and wrote (the live ones on a TPU, else all)"),
                ("rows_scanned", "Rows the admissions' chunked scans ran over (their buckets)"),
                ("rows_real", "Rows of those that were prompt"),
            )
        }
        self._attn_counters = {
            key: self._registry.counter(f"rlt_serve_attn_{key}_total", help_)
            for key, help_ in (
                ("rows_allocated", "Cache rows allocated to the slots, summed over decode token steps and layers"),
                ("rows_visited", "Cache rows of those the decode attention read (all of them on the XLA read; the decode kernel's blocks up to each live slot's position)"),
                ("rows_live", "Cache rows of those that held a position of a live request"),
                ("prefill_rows", "Rows of the admissions' buckets, summed over the attention layers of mixed layer kinds"),
                ("prefill_rows_kernel", "Rows of those whose attention the forward flash kernel read (the full and latent kinds on a TPU, from the crossing up)"),
                ("prefill_tiles", "Score tiles of the admissions' padded causal squares, summed over the full and latent layers"),
                ("prefill_tiles_visited", "Score tiles of those a read visited (all on the XLA read; under the kernel those of query blocks that hold a prompt's row)"),
            )
        }
        self._moe_mirrored: Dict[Tuple[str, str], int] = {}
        kv_bytes = self._registry.gauge(
            "rlt_serve_kv_bytes",
            "Dense per-request state bytes (K and V; a state layer's states and conv tails) by layer kind",
        )
        for kind, row in self.engine.cache_stats().items():
            kv_bytes.set(float(row["bytes"]), kind=kind)
        self._capture: Any = None
        # Warm the PRNGKey builder before the compile baseline: the first
        # submit would otherwise compile it in a fresh process and
        # spuriously trip compiles_since_init.
        import jax

        jax.random.PRNGKey(0)
        self._compiles_at_init = self._compile_stats.count("backend_compile")
        self._compile_s_at_init = (
            self._compile_stats.snapshot()
            .get("backend_compile", {})
            .get("total_s", 0.0)
        )
        self.metrics = ServeMetrics(
            self.engine.num_slots, registry=self._registry
        )
        # Resident-footprint gauges (rlt_serve_hbm_bytes{component=}):
        # shapes freeze at construction, so record once — the per-device
        # series is how a tp=N mesh proves it divided the footprint.
        self.metrics.record_memory(self.engine.memory_stats())
        self.tracer = RequestTracer(
            capacity=trace_capacity, enabled=bool(tracing)
        )
        self.events = get_event_log()
        # Preemption signal plane (serve.preempt): SIGTERM, the optional
        # metadata poller, and the `preempt` fault action all funnel
        # into one process monitor; health()/stats() ship its state so
        # the supervisor can flip this replica to PREEMPTING and drive
        # the graceful drain inside the grace window. SIGTERM records
        # the notice WITHOUT exiting (the drain is the response; fabric
        # kill()'s shutdown message / SIGKILL escalation still end the
        # process), and the notice wakes the loop thread so a drain on
        # an idle replica starts immediately.
        from ray_lightning_tpu.serve.preempt import get_monitor

        self.preempt = get_monitor(
            grace_s=float(preempt_grace_s), events=self.events
        )
        self.preempt.add_callback(lambda _m: self._work.set())
        if preempt_sigterm:
            self.preempt.install_sigterm()
        if preempt_metadata:
            self.preempt.start_metadata_poller()
        # Workload journal: the deterministic capture of this replica's
        # externally-sourced request stream (ring always on by default —
        # the hot-path cost is one dict append per lifecycle event;
        # journal_dir adds the streaming JSONL spill). The header pins
        # the config/checkpoint identity a replay rebuilds from.
        self.journal = None
        if journal:
            from ray_lightning_tpu.obs.journal import (
                WorkloadJournal,
                engine_header,
            )

            self.journal = WorkloadJournal(
                capacity=int(journal_capacity), spill_dir=journal_dir
            )
            self.journal.set_header(engine_header(
                self.engine,
                ckpt_path=ckpt_path,
                int8=self.int8,
                spec_draft_ckpt=spec_draft_ckpt,
                spec_draft_config=spec_draft_config,
                spec_draft_int8=spec_draft_int8,
                max_prefills_per_step=max_prefills_per_step,
                max_prefill_chunks_per_step=max_prefill_chunks_per_step,
                priority_age_s=priority_age_s,
                # The driver-side router/autoscaler knobs (provenance:
                # the policy that shaped this replica's traffic rides
                # the journal a replay rebuilds from).
                router=router_config,
                # Fleet-KV/disagg provenance: the role and transfer
                # knobs that shaped this capture (shipped outcomes
                # replay as their recorded truncations; `rlt replay`
                # surfaces the section as kvfleet_config).
                kvfleet=(
                    {
                        "role": self.role,
                        "peers": len(kv_peers or {}),
                        "timeout_s": float(kvfleet_timeout_s),
                        "max_inflight_mb": float(kvfleet_inflight_mb),
                        "bandwidth_mbps": float(kvfleet_bandwidth_mbps),
                        "layerwise": bool(kvfleet_layerwise),
                    }
                    if (kv_inbox is not None or self.role != "mixed")
                    else None
                ),
                # Persistent-store provenance: `rlt replay` rebuilds an
                # engine with the same store wiring (the dir/budget live
                # in the engine section via _ENGINE_REBUILD_KEYS).
                kvstore=(
                    {
                        "dir": self.engine.kvstore_dir,
                        "budget_mb": float(kvstore_mb),
                        "writethrough": bool(kvstore_writethrough),
                        "namespace": self.engine.kvstore_namespace,
                    }
                    if self.engine.kvstore is not None
                    else None
                ),
            ))
        # Deterministic fault injection (serve.faults): an explicit plan
        # beats the RLT_FAULTS env gate; armed rules fire at named
        # lifecycle points in the scheduler loop and this RPC surface.
        # A live replica can be (re)armed via the inject_fault RPC —
        # how a chaos test targets ONE replica of a fleet.
        from ray_lightning_tpu.serve.faults import FaultInjector

        self.faults = FaultInjector.parse(
            faults, events=self.events
        ) or FaultInjector.from_env(events=self.events)
        # The fleet KV plane proper: built only when transfer wiring
        # was handed in (start_replicas creates one inbox per replica
        # when fleet sharing is on); a lone replica or an isolated
        # fleet runs without it at zero cost.
        # The persistent store was built inside the engine ctor (it has
        # no event log yet at that point); hand it the replica's event
        # stream now so GC drops / write errors land in obs.
        if self.engine.kvstore is not None:
            self.engine.kvstore._events = self.events
        self.kvfleet = None
        if kv_inbox is not None:
            self.kvfleet = KVFleetPlane(
                index=0 if kv_self is None else int(kv_self),
                role=self.role,
                inbox=kv_inbox,
                peers=kv_peers,
                block_bytes=self.engine.prefix_block_nbytes,
                timeout_s=float(kvfleet_timeout_s),
                max_inflight_mb=float(kvfleet_inflight_mb),
                bandwidth_mbps=float(kvfleet_bandwidth_mbps),
                layerwise_ship=bool(kvfleet_layerwise),
                registry=self._registry,
                events=self.events,
                store=self.engine.kvstore,
            )
        self.scheduler = Scheduler(
            self._sched_engine,
            metrics=self.metrics,
            max_prefills_per_step=max_prefills_per_step,
            max_prefill_chunks_per_step=max_prefill_chunks_per_step,
            priority_age_s=priority_age_s,
            tracer=self.tracer,
            events=self.events,
            journal=self.journal,
            faults=self.faults,
            kvfleet=self.kvfleet,
            role=self.role,
            kvstore=self.engine.kvstore,
            kvstore_writethrough=bool(kvstore_writethrough),
        )
        self._serve_config: Dict[str, Any] = {
            "num_slots": self.engine.num_slots,
            "max_seq": self.engine.max_seq,
            "decode_fold": self.engine.decode_fold,
            "fold_ladder": list(self.engine.fold_ladder),
            "piggyback_chunks": self.engine.piggyback_chunks,
            "pipeline": self.engine.pipeline,
            "prefill_chunk": self.engine.prefill_chunk,
            "prefix_blocks": self.engine.prefix_blocks,
            "kv_page": self.engine.kv_page,
            "kv_pages": self.engine.kv_pages,
            "prefix_host_mb": self.engine.prefix_host_mb,
            "prefix_disk_dir": self.engine.prefix_disk_dir,
            "prefix_disk_mb": self.engine.prefix_disk_mb,
            "spec": self.engine.spec,
            "spec_depth": self.engine.spec_depth,
            "int8": self.int8,
            "mesh": self.engine.mesh_desc,
            "role": self.role,
            "kvfleet": self.kvfleet is not None,
            "kvfleet_layerwise": bool(kvfleet_layerwise),
            "kvstore_dir": self.engine.kvstore_dir,
            "kvstore_mb": self.engine.kvstore_mb,
            "kvstore_namespace": self.engine.kvstore_namespace,
            "kvstore_writethrough": bool(kvstore_writethrough),
            "gang_hosts": int(self._dist.get("num_hosts", 1)),
            "watchdog": bool(watchdog),
            "stall_s": float(stall_s),
            "slo": dict(slo or {}),
            "journal": self.journal is not None,
            "preempt_grace_s": float(preempt_grace_s),
        }
        self.events.record(
            "serve", "replica_init",
            slots=self.engine.num_slots,
            compiled=self.engine.compiled_count,
        )
        # -- the active half: flight recorder + watchdog ------------------
        self.blackbox = obs_blackbox.FlightRecorder(
            outdir=blackbox_dir,
            keep=blackbox_keep,
            registry=self._registry,
            events=self.events,
            tracer=self.tracer,
            journal=self.journal,
            # The LAST report, not a fresh evaluation: a dump triggered
            # from inside evaluate() (on_unhealthy) must capture the
            # verdict that fired it, and must not recurse.
            health_fn=lambda: (
                self.watchdog.report().to_dict()
                if self.watchdog is not None
                else self.health()
            ),
            config=self._serve_config,
        )
        self.watchdog: Optional[Any] = None
        if watchdog:
            reg = self._registry
            tokens = reg.counter("rlt_serve_tokens_emitted_total")
            lifecycle = reg.counter("rlt_serve_requests_total")
            wd = obs_health.Watchdog(
                interval_s=float(watchdog_interval_s),
                registry=reg,
                events=self.events,
                on_unhealthy=lambda comp, rep: self.blackbox.maybe_dump(
                    f"unhealthy:{comp}"
                ),
            )
            # Every check only READS state the hot paths already publish
            # (registry counters, slot counts) — zero hot-loop cost.
            wd.add_check(obs_health.engine_stall_check(
                lambda: self.engine.num_active, tokens.value, float(stall_s)
            ))
            wd.add_check(obs_health.admission_wedge_check(
                self.scheduler.queue_depth,
                lambda: lifecycle.value(kind="admitted"),
                float(stall_s),
                free_slots_fn=lambda: len(self.engine.free_slots()),
            ))
            wd.add_check(obs_health.compile_storm_check(
                lambda: (
                    self._compile_stats.count("backend_compile")
                    - self._compiles_at_init
                ),
            ))
            if slo:
                wd.add_check(obs_health.slo_check(
                    obs_health.parse_slo_rules(dict(slo)),
                    self.metrics.snapshot,
                    registry=reg,
                    events=self.events,
                ))
            self.watchdog = wd.start()
        self._tick = float(tick_s)
        #: request_id -> {"tokens": [...], "done": bool, "status": str}
        self._buffers: Dict[str, Dict[str, Any]] = {}
        self._cond = threading.Condition()
        self._stop = threading.Event()
        self._work = threading.Event()
        # The collector's pauses stall the loop from inside the runtime
        # just as a compile does: count them from here on (stop() takes
        # the hook out again).
        self._gc: Any = install_gc_hook()
        self._gc_hooked = True
        self._thread = threading.Thread(
            target=self._loop, name="serve-replica-loop", daemon=True
        )
        self._thread.start()

    # -- loop thread (owns all jax work) ----------------------------------
    def _loop(self) -> None:
        spans = self.spans
        while not self._stop.is_set():
            if not self.scheduler.has_work():
                with span(spans, "serve.loop.idle"):
                    self._work.wait(timeout=0.1)
                self._work.clear()
                continue
            with spans.work():
                events = self.scheduler.step()
                with span(spans, "serve.loop.publish", events=len(events)):
                    if events:
                        self._publish(events)
                    self.metrics.maybe_log()
                if self._tick:
                    with span(spans, "serve.loop.tick"):
                        self._stop.wait(self._tick)

    def _publish(self, events: Sequence[Any]) -> None:
        with self._cond:
            for ev in events:
                buf = self._buffers.setdefault(
                    ev.request_id,
                    {"tokens": [], "done": False, "status": "running"},
                )
                if ev.token is not None:
                    buf["tokens"].append(ev.token)
                if ev.done:
                    buf["done"] = True
                    buf["status"] = (
                        "finished" if ev.reason in ("token", "finished")
                        else ev.reason
                    )
                    target = getattr(ev, "ship_to", None)
                    if target is not None:
                        # Disagg handoff: the client resubmits to this
                        # decode replica and the stream continues warm
                        # there.
                        buf["ship_to"] = int(target)
                        buf["ship_digests"] = list(
                            getattr(ev, "ship_digests", None) or []
                        )
            self._cond.notify_all()

    # -- RPC surface ------------------------------------------------------
    def ping(self) -> str:
        return "ok"

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        seed: int = 0,
        eos_token: Optional[int] = None,
        priority: int = 0,
        deadline_s: Optional[float] = None,
        request_id: Optional[str] = None,
        tenant: Optional[str] = None,
        kv_hint: Optional[Dict[str, Any]] = None,
        ship_to: Optional[int] = None,
    ) -> str:
        """``request_id`` lets the CLIENT mint the id before the RPC —
        the trace-stitching anchor: its client_submit span and this
        replica's spans share the id, so the merged export ties them.
        ``tenant`` labels the request's cost-ledger record.
        ``kv_hint``/``ship_to`` are the router's fleet-KV placement
        hints (fetch the prefix chain from a warm peer / ship the
        finished prefill's pages to that decode replica)."""
        from ray_lightning_tpu.serve.scheduler import SamplingParams

        if self.faults is not None:
            self.faults.hit("rpc_submit")
        with span(
            self._rpc_spans, "serve.rpc.submit", request_id=request_id or ""
        ):
            rid = self.scheduler.submit(
                prompt,
                SamplingParams(
                    max_new_tokens=max_new_tokens,
                    temperature=temperature,
                    top_k=top_k,
                    top_p=top_p,
                    seed=seed,
                    eos_token=eos_token,
                ),
                request_id=request_id,
                priority=priority,
                deadline_s=deadline_s,
                tenant=tenant,
                kv_hint=kv_hint,
                ship_to=ship_to,
            )
            with self._cond:
                self._buffers[rid] = {
                    "tokens": [], "done": False, "status": "queued",
                }
            self._work.set()
        return rid

    def submit_many(
        self, requests: Sequence[Dict[str, Any]]
    ) -> List[str]:
        """Batched admission: ONE RPC admits every request in
        ``requests`` (each a dict of :meth:`submit` kwargs plus
        ``prompt``), seeding all result buffers under one lock pass and
        waking the serve loop once. Per-request semantics are identical
        to ``submit`` — same scheduler admission, same fault hook, same
        client-minted ids — only the per-RPC overhead amortizes (the
        client-side micro-batching window's wire call)."""
        from ray_lightning_tpu.serve.scheduler import SamplingParams

        rids: List[str] = []
        with span(self._rpc_spans, "serve.rpc.submit", n=len(requests)):
            for req in requests:
                if self.faults is not None:
                    self.faults.hit("rpc_submit")
                rids.append(self.scheduler.submit(
                    req["prompt"],
                    SamplingParams(
                        max_new_tokens=req.get("max_new_tokens", 32),
                        temperature=req.get("temperature", 0.0),
                        top_k=req.get("top_k"),
                        top_p=req.get("top_p"),
                        seed=req.get("seed", 0),
                        eos_token=req.get("eos_token"),
                    ),
                    request_id=req.get("request_id"),
                    priority=req.get("priority", 0),
                    deadline_s=req.get("deadline_s"),
                    tenant=req.get("tenant"),
                    kv_hint=req.get("kv_hint"),
                    ship_to=req.get("ship_to"),
                ))
            with self._cond:
                for rid in rids:
                    self._buffers[rid] = {
                        "tokens": [], "done": False, "status": "queued",
                    }
            self._work.set()
        return rids

    def result(
        self, request_id: str, cursor: int = 0, wait_s: float = 0.0
    ) -> Dict[str, Any]:
        """Tokens past ``cursor`` plus done/status. ``wait_s > 0`` blocks
        (briefly — the actor handles calls serially) until new tokens or
        completion, which keeps streaming polls cheap."""
        import time as _time

        if self.faults is not None:
            self.faults.hit("rpc_result")
        if wait_s > 0:
            # A long poll sleeps on the condition by design: a span of
            # its own, so serve.rpc.result stays work and lock wait.
            deadline = _time.monotonic() + wait_s
            with span(
                self._rpc_spans, "serve.rpc.result_wait",
                request_id=request_id,
            ), self._cond:
                while True:
                    buf = self._buffers.get(request_id)
                    if (
                        buf is None or buf["done"]
                        or len(buf["tokens"]) > cursor
                    ):
                        break
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
        with span(
            self._rpc_spans, "serve.rpc.result", request_id=request_id
        ), self._cond:
            buf = self._buffers.get(request_id)
            if buf is None:
                raise KeyError(f"unknown request {request_id!r}")
            out = {
                "tokens": list(buf["tokens"][cursor:]),
                "done": buf["done"],
                "status": buf["status"],
            }
            if "ship_to" in buf:
                out["ship_to"] = buf["ship_to"]
                out["ship_digests"] = buf.get("ship_digests") or []
            return out

    def cancel(self, request_id: str) -> bool:
        ok = self.scheduler.cancel(request_id)
        self._work.set()
        return ok

    def device_check(
        self, prompt: Sequence[int], max_new_tokens: int = 32
    ) -> Dict[str, Any]:
        """What only this process can say about the programs it runs
        (``chip_smoke.py`` asks once, after its requests): greedy
        ``gpt_generate`` over this replica's weights on ONE device,
        outside the engine — the reference the engine's bit-exactness
        contract is stated against — and the count of Mosaic kernels in
        each compiled prefill bucket. It compiles its own programs, so
        read ``compiles_since_init`` first."""
        import jax
        import numpy as np

        from ray_lightning_tpu.models.gpt import gpt_generate

        out = gpt_generate(
            jax.device_get(self.engine.params),
            self.engine.cfg,
            np.asarray([list(prompt)], np.int32),
            int(max_new_tokens),
        )
        return {
            "solo_tokens": [int(t) for t in np.asarray(out)[0, len(prompt):]],
            "prefill_mosaic_calls": self.engine.prefill_mosaic_calls(),
        }

    def stats(self) -> Dict[str, Any]:
        """The stats endpoint: metrics snapshot + engine anatomy +
        embedded registry values."""
        with span(self._rpc_spans, "serve.rpc.stats"):
            return self._stats()

    def _mirror_spans(self) -> None:
        """Bring the registry's span and collector counters up to the
        totals (the hot paths feed the totals only)."""
        self.spans.mirror(
            self._span_seconds, self._span_count, self._rider_seconds
        )
        self._rpc_spans.mirror(self._span_seconds, self._span_count)
        self._gc.mirror(self._gc_seconds)
        for phase, row in self.engine.moe_totals.items():
            for key, total in row.items():
                name = "token_steps" if key == "admissions" else key
                done = self._moe_mirrored.get((phase, key), 0)
                if total != done:
                    self._moe_counters[name].inc(total - done, phase=phase)
                    self._moe_mirrored[(phase, key)] = total
        for name, counters, row in (
            ("ssm", self._ssm_counters, self.engine.ssm_totals["decode"]),
            ("ssm", self._ssm_counters, self.engine.ssm_totals["prefill"]),
            ("attn", self._attn_counters, self.engine.attn_totals),
        ):
            for key, total in row.items():
                done = self._moe_mirrored.get((name, key), 0)
                if total != done:
                    counters[key].inc(total - done)
                    self._moe_mirrored[(name, key)] = total

    def _spans_snapshot(self) -> Dict[str, Any]:
        """``stats()["spans"]``: what the host did, all monotone since
        construction, so the difference of two calls is exactly the time
        between them. ``segments`` holds both threads' spans; the loop
        thread's are those not named ``serve.rpc.*``. ``riders_s`` is
        the loop thread's: request-seconds behind each of its spans, by
        kind; ``riders_open_s`` what the requests still open have
        accrued of them (with the closed requests' phases, what
        ``riders_s`` adds up to)."""
        out = self.spans.snapshot()
        out["riders_open_s"] = self.scheduler.riders_open()
        out["segments"].update(self._rpc_spans.snapshot()["segments"])
        out["folds"] = int(sum(self.engine.fold_dispatches.values()))
        out["gc"] = self._gc.snapshot()
        return out

    def _stats(self) -> Dict[str, Any]:
        import jax

        devs = jax.devices()
        self._mirror_spans()
        snap = self.metrics.snapshot()
        snap.update(
            {
                # The device as jax reports it INSIDE this process — a
                # replica that fell to another platform says so itself.
                "device": {
                    "platform": devs[0].platform,
                    "kind": devs[0].device_kind,
                    "count": len(devs),
                },
                "active_slots": self.engine.num_active,
                "compiled_count": self.engine.compiled_count,
                # Backend compiles the listener saw while the engine was
                # built: zero means the listener is not live, and then
                # compiles_since_init below proves nothing.
                "compiles_at_init": self._compiles_at_init,
                "compile_s_at_init": self._compile_s_at_init,
                # The frozen-compile contract as a metric: backend
                # compiles observed since construction ended. Non-zero in
                # steady state means a shape leaked into the hot path.
                "compiles_since_init": (
                    self._compile_stats.count("backend_compile")
                    - self._compiles_at_init
                ),
                "max_seq": self.engine.max_seq,
                "prefill_buckets": list(self.engine.prefill_buckets),
                "decode_fold": self.engine.decode_fold,
                "pipeline": self.engine.pipeline,
                "prefill_chunk": self.engine.prefill_chunk,
                "prefix_cache": self.engine.prefix_blocks > 0,
                # Resolved paged-KV config (the kv_pages STATS BLOCK —
                # a dict — is set separately below on paged engines).
                "paged": self.engine.paged,
                "kv_page": self.engine.kv_page,
                "kv_pages_total": self.engine.kv_pages,
                "int8": self.int8,
                "mesh": self.engine.mesh_desc,
                # Per-component resident bytes (total + per-device after
                # sharding): the row that validates tp=N divides the
                # footprint by ~N.
                "memory": self.engine.memory_stats(),
                # The dense KV cache by layer kind (full / window): layers,
                # rows a slot, bytes.
                "cache": self.engine.cache_stats(),
                "tracing": self.tracer.enabled,
                "metrics": self._registry.to_dict(),
            }
        )
        snap["role"] = self.role
        moe = self.engine.moe_stats()
        if moe:
            # Expert layers of a configuration that holds a share of the
            # experts: pairs routed, pairs on held experts, held experts
            # hit, token steps — monotone totals.
            snap["moe"] = moe
        ssm = self.engine.ssm_stats()
        if ssm:
            # State layers: slot-steps, those live and those visited, rows
            # scanned and real — monotone totals.
            snap["ssm"] = ssm
        attn = self.engine.attn_stats()
        if attn:
            # Cache rows the decode attention had allocated, visited and
            # live, over token steps and layers — monotone totals.
            snap["attn"] = attn
        if self.kvfleet is not None:
            snap["kvfleet"] = self.kvfleet.stats()
        if self.engine.kvstore is not None:
            # Persistent-store block: counters + the write/drop rings
            # the driver-side directory feeds its store-held half from.
            snap["kvstore"] = self.engine.kvstore.stats()
        # SLO-breach total (rlt_slo_breaches_total over every rule):
        # the router/autoscaler's quality signal next to raw queue
        # depth — summed here so the fleet rows need no registry walk.
        snap["slo_breaches"] = int(sum(
            self._registry.counter(
                "rlt_slo_breaches_total"
            ).samples().values()
        ))
        if self.engine.prefix_blocks:
            snap["prefix"] = self.engine.prefix_stats()
            # Eviction-invalidation feed for the driver-side fleet
            # directory: digests this engine dropped from EVERY tier
            # (bounded ring + lifetime count; idempotent to re-read).
            snap["kv_dropped"] = {
                "total": self.engine.kv_dropped_total,
                "recent": self.engine.dropped_digests(),
            }
        if self.engine.paged:
            # The allocator's live state (the scheduler-refreshed metrics
            # copy can lag a step; this one is read straight off the
            # engine for the stats RPC).
            snap["kv_pages"] = self.engine.kv_page_stats()
        # Fold-depth ladder: every dispatch picked one pre-lowered rung
        # (zero compiles — the whole ladder lowered at construction);
        # the per-K histogram is how an operator sees queue pressure
        # translate into dispatch depth.
        snap["fold_k"] = {
            "ladder": list(self.engine.fold_ladder),
            "dispatches": {
                str(k): int(n)
                for k, n in self.engine.fold_dispatches.items()
            },
        }
        if self.engine.piggyback_chunks:
            # Fused prefill+decode dispatches: chunk rows that rode a
            # decode fold instead of a separate prefill_step dispatch.
            snap["piggyback"] = {
                "chunks": self.engine.piggyback_chunks,
                "dispatches": int(self.engine.piggyback_dispatches),
                "chunk_rows": int(self.engine.piggyback_chunk_rows),
            }
        snap["spec"] = self.engine.spec
        if self.engine.spec != "off":
            snap["spec_stats"] = self.engine.spec_stats()
        snap["health"] = self.health()["verdict"]
        snap["preempt"] = self.preempt.state()
        snap["spans"] = self._spans_snapshot()
        snap["latency"] = self.metrics.latency()
        return snap

    # -- health / forensics RPCs ------------------------------------------
    def health(self) -> Dict[str, Any]:
        """This replica's health report (obs.health): per-component
        verdicts with reasons, evaluated FRESH — the RPC is the
        aggregation surface the driver's /healthz pulls, so it must not
        serve a stale verdict at a recovery boundary."""
        if self.watchdog is None:
            out = {
                "verdict": "healthy", "healthy": True, "reasons": [],
                "components": {}, "watchdog": False,
            }
        else:
            out = self.watchdog.evaluate().to_dict()
            out["watchdog"] = True
        # Preemption is NOT unhealthiness (the process still serves) —
        # it rides the report as its own field so the supervisor can
        # flip to PREEMPTING and start the deadline-driven drain.
        out["preempt"] = self.preempt.state()
        return out

    def debug_dump(
        self, reason: str = "rpc", pull: bool = False
    ) -> Dict[str, Any]:
        """Write a flight-recorder bundle NOW (not rate-limited — an
        operator asked); returns its manifest, plus the bundle files
        inline when ``pull`` (the ``rlt doctor`` transport)."""
        from ray_lightning_tpu.obs import blackbox as obs_blackbox

        manifest = self.blackbox.dump(reason=reason)
        if pull:
            manifest["files_content"] = obs_blackbox.read_bundle(
                manifest["dir"]
            )
        return manifest

    def recent_events(self, n: int = 64) -> list:
        """Tail of this process's structured event log (obs.events)."""
        return self.events.tail(n)

    def inject_fault(self, plan: Any) -> list:
        """Arm (or disarm with None) a deterministic fault plan on this
        LIVE replica (serve.faults) — the chaos tests' way of targeting
        one replica of a fleet; returns the armed rules. Replaces any
        previous plan."""
        from ray_lightning_tpu.serve.faults import FaultInjector

        inj = FaultInjector.parse(plan, events=self.events)
        self.faults = inj
        self.scheduler.faults = inj
        return [] if inj is None else inj.describe()

    # -- preemption drain RPCs --------------------------------------------
    def preempt_now(self, grace_s: Optional[float] = None) -> float:
        """Record a preemption notice on this replica (tests, manual
        drills, an external node-drainer); returns the deadline's
        remaining seconds. The supervisor picks the state up on its next
        probe and drives the drain."""
        self.preempt.notice(grace_s=grace_s, source="rpc")
        return float(self.preempt.remaining() or 0.0)

    def begin_drain(
        self,
        budget_s: Optional[float] = None,
        wait_s: float = 15.0,
    ) -> Dict[str, Any]:
        """Run the graceful-drain classification: requests that can
        finish inside ``budget_s`` (default: the monitor's remaining
        grace) keep running; the rest are cancelled at the next step
        boundary and returned as the MIGRATE set, each with its cached
        prefix blocks serialized for the survivor. Blocks until the loop
        thread publishes the plan (it does engine work)."""
        if budget_s is None:
            budget_s = self.preempt.remaining()
        if budget_s is None:
            budget_s = self.preempt.grace_s
        self.scheduler.request_drain(float(budget_s))
        self._work.set()  # an idle loop must still produce the plan
        plan = self.scheduler.drain_result(timeout=float(wait_s))
        if plan is None:
            raise TimeoutError(
                f"drain plan not produced within {wait_s}s (loop thread "
                "wedged?)"
            )
        return plan

    def import_prefix_blocks(self, blocks: Any) -> int:
        """Accept a dying peer's exported prefix blocks (the
        cross-replica KV handoff): queued here, imported into the engine
        pool at the top of the next scheduler step (engine mutations
        stay on the loop thread). Returns blocks queued."""
        n = self.scheduler.enqueue_prefix_import(blocks)
        self._work.set()
        return n

    def park_session(
        self,
        tokens: Sequence[int],
        request_id: Optional[str] = None,
        wait_s: float = 15.0,
    ) -> Dict[str, Any]:
        """Park an idle conversation: export ``tokens``' cached chain
        to the persistent store and free its local pages (only when
        EVERY block stored — a partial write keeps the pages, lost
        loudly via ``kvstore_write_errors_total``, never silently).
        Blocks until the loop thread publishes the result (export and
        evict are engine work). The next submit of the same prefix
        restores it bit-exactly through the store-fetch path — on ANY
        replica."""
        if self.engine.kvstore is None:
            raise RuntimeError(
                "park_session needs a persistent store: start the "
                "replica with kvstore_dir (--serve.kvstore_dir)"
            )
        self.scheduler.request_park(tokens, request_id=request_id)
        self._work.set()  # an idle loop must still produce the result
        out = self.scheduler.park_result(timeout=float(wait_s))
        if out is None:
            raise TimeoutError(
                f"park result not produced within {wait_s}s (loop "
                "thread wedged?)"
            )
        return out

    def register_kv_peer(self, idx: int, queue: Any) -> bool:
        """Adopt a new fleet member's KV inbox (autoscale-up wires the
        grown fleet without respawning anyone). No-op without a fleet
        KV plane."""
        if self.kvfleet is None:
            return False
        self.kvfleet.register_peer(int(idx), queue)
        return True

    def journal_dump(self, n: Optional[int] = None) -> Dict[str, Any]:
        """This replica's workload journal in the wire form (header +
        newest ``n`` entries; all when None) — the replay substrate
        behind ``/journal``, ``journal.jsonl`` bundles, and
        ``rlt replay``. Empty when journaling is off."""
        if self.journal is None:
            return {"header": None, "entries": []}
        return self.journal.dump(n)

    # -- observability RPCs ----------------------------------------------
    def trace(self, request_id: str) -> list:
        """One request's recorded spans (oldest first); [] when unknown
        or already rotated out of the ring buffer."""
        return self.tracer.trace(request_id)

    def recent_traces(self, n: int = 8) -> Dict[str, list]:
        return self.tracer.recent_traces(n)

    def trace_dump(self, n: int = 16) -> Dict[str, Any]:
        """This process's trace ring in the stitching wire form (recent
        traces + wall-clock offset) — ``ServeClient.trace_dumps`` pulls
        one per process and merges them into ONE cross-process trace."""
        return self.tracer.dump(n)

    def export_trace(
        self, request_id: Optional[str] = None, n: int = 8
    ) -> Dict[str, Any]:
        """Chrome trace-event JSON (a dict — ``json.dump`` it and open in
        Perfetto) of one request, or the ``n`` most recent."""
        from ray_lightning_tpu.obs.trace import to_chrome_trace

        traces = (
            {request_id: self.tracer.trace(request_id)}
            if request_id is not None
            else self.tracer.recent_traces(n)
        )
        return to_chrome_trace(
            {rid: evs for rid, evs in traces.items() if evs}
        )

    def metrics_text(self) -> str:
        """This replica process's registry in Prometheus text format."""
        self._mirror_spans()
        return self._registry.render()

    def profile(
        self, duration_s: float = 1.0, outdir: Optional[str] = None
    ) -> Dict[str, Any]:
        """Start a ``duration_s`` jax.profiler capture in a thread of
        this replica and return at once: the actor keeps answering
        submits and polls while the profiler starts, runs and writes, so
        the trace shows a replica that is serving, with the loop's and
        the RPC surface's spans on its host plane. Collect the artifact
        paths with :meth:`profile_result`. One capture at a time."""
        from ray_lightning_tpu.obs.profiling import BackgroundCapture

        if self._capture is not None and self._capture.result() is None:
            return {
                "ok": False,
                "error": "a profile capture is already running",
            }
        self._capture = BackgroundCapture(duration_s, outdir)
        return {"ok": True, "started": True, "duration_s": float(duration_s)}

    def profile_result(self, wait_s: float = 0.0) -> Dict[str, Any]:
        """The last :meth:`profile` capture's report (``{ok, dir, files,
        duration_s}`` or ``{ok: False, error}``), or ``{"ok": False,
        "pending": True}`` while it runs. ``wait_s`` blocks this actor
        for up to that long; a client polls with 0."""
        if self._capture is None:
            return {"ok": False, "error": "no profile capture was started"}
        res = self._capture.result(wait_s)
        return {"ok": False, "pending": True} if res is None else res

    def stop(self) -> None:
        from ray_lightning_tpu.obs.jaxmon import remove_gc_hook

        if self._gc_hooked:
            self._gc_hooked = False
            remove_gc_hook()
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.journal is not None:
            self.journal.close()  # flush/close any open spill file
        if isinstance(self._sched_engine, _GangLeaderEngine):
            self._sched_engine.close()  # followers drain and exit
        self._stop.set()
        self._work.set()
        self._thread.join(timeout=5.0)
