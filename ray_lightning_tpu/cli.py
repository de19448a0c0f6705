"""Command-line interface: build Trainer + strategy + model from flags/YAML.

Parity target: the reference keeps its strategies LightningCLI/jsonargparse-
constructible — plain typed ctor kwargs instantiated from CLI flags
(/root/reference/ray_lightning/tests/test_lightning_cli.py:11-27,
SURVEY.md §5 config/flag system). jsonargparse is not in this environment,
so the CLI is self-contained: argparse + constructor introspection, with
Lightning's ``{class_path, init_args}`` YAML convention and dotted CLI
overrides.

Usage:
    python -m ray_lightning_tpu.cli fit \
        --model ray_lightning_tpu.models.MNISTClassifier --model.lr 3e-4 \
        --strategy RayTPUStrategy --strategy.num_workers 4 \
        --trainer.max_epochs 2 [--config run.yaml]

YAML config (merged under CLI overrides):
    model:
      class_path: ray_lightning_tpu.models.GPTLM
      init_args: {batch_size: 8}
    strategy:
      class_path: ray_lightning_tpu.strategies.GSPMDStrategy
      init_args: {num_workers: 8, mesh_shape: {data: 4, model: 2}}
    trainer: {max_epochs: 3}
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import sys
from typing import Any, Dict, List, Optional, Tuple

import yaml

_SUBCOMMANDS = (
    "fit", "validate", "test", "predict", "generate", "convert-hf",
    "tokenize", "serve", "doctor", "top", "replay", "why", "plot",
    "alerts",
)


def import_class(path: str) -> type:
    """Resolve ``pkg.mod.Class`` (or a bare name from the strategies /
    models namespaces) to a class object."""
    if "." in path:
        module_name, _, cls_name = path.rpartition(".")
        return getattr(importlib.import_module(module_name), cls_name)
    for ns in ("ray_lightning_tpu.strategies", "ray_lightning_tpu.models"):
        mod = importlib.import_module(ns)
        if hasattr(mod, path):
            return getattr(mod, path)
    raise ValueError(f"cannot resolve class {path!r}")


def _target_type(annotation: Any, default: Any) -> Optional[type]:
    """Best-effort scalar type from a ctor annotation (which is usually a
    *string* — the package uses ``from __future__ import annotations``) or
    the default value."""
    if isinstance(annotation, type):
        return annotation
    if isinstance(annotation, str):
        for name, typ in (("bool", bool), ("int", int), ("float", float),
                          ("str", str)):
            if name in annotation:
                return typ
    if annotation is inspect.Parameter.empty and default is not None:
        if isinstance(default, (bool, int, float, str)):
            return type(default)
    return None


def _coerce(value: str, annotation: Any, default: Any) -> Any:
    """Parse a CLI string with YAML, then bend it toward the ctor's type
    (YAML alone keeps e.g. '3e-4' a string — its float resolver wants a
    dot)."""
    parsed = yaml.safe_load(value)
    target = _target_type(annotation, default)
    if target is bool:
        return parsed if isinstance(parsed, bool) else str(parsed).lower() in (
            "1", "true", "yes",
        )
    if target in (int, float) and isinstance(parsed, (int, float, str)):
        try:
            return target(parsed)
        except (TypeError, ValueError):
            return parsed
    return parsed


def instantiate_class(spec: Any, default_class: Optional[str] = None) -> Any:
    """Instantiate Lightning-style ``{class_path, init_args}`` (or a bare
    class-path string)."""
    if isinstance(spec, str):
        spec = {"class_path": spec, "init_args": {}}
    class_path = spec.get("class_path") or default_class
    if class_path is None:
        raise ValueError(f"missing class_path in {spec!r}")
    cls = import_class(class_path)
    kwargs = dict(spec.get("init_args") or {})
    _validate_ctor_kwargs(cls, kwargs)
    return cls(**kwargs)


def _validate_ctor_kwargs(cls: type, kwargs: Dict[str, Any]) -> None:
    sig = inspect.signature(cls.__init__)
    accepts_var_kw = any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in sig.parameters.values()
    )
    if accepts_var_kw:
        return
    valid = set(sig.parameters) - {"self"}
    unknown = set(kwargs) - valid
    if unknown:
        raise ValueError(
            f"{cls.__name__} does not accept {sorted(unknown)}; "
            f"valid args: {sorted(valid)}"
        )


def _apply_dotted(
    config: Dict[str, Any], dotted: List[Tuple[str, str]]
) -> Dict[str, Any]:
    """Merge ``--section.key value`` overrides into the config tree, coercing
    through the target constructor's signature where known.

    Two passes so coercion is order-independent: class paths (from YAML or
    any ``--model X`` flag, in either position) are all known before any
    field value is typed.
    """
    # Pass 1: class paths + normalize bare-string YAML nodes to dict form.
    field_overrides: List[Tuple[str, str, str]] = []
    for key, raw in dotted:
        section, _, field = key.partition(".")
        if section in ("src", "out", "family"):  # convert-hf scalar options
            config[section] = raw
            continue
        if section == "overrides":  # convert-hf GPTConfig overrides
            config.setdefault("overrides", {})[field] = yaml.safe_load(raw)
            continue
        if section not in (
            "model", "strategy", "trainer", "data", "generate", "tokenize",
            "serve", "doctor", "top", "replay", "why", "plot", "alerts",
        ):
            raise ValueError(f"unknown config section {section!r} in --{key}")
        node = config.get(section)
        if isinstance(node, str):  # YAML bare class-path form
            config[section] = {"class_path": node, "init_args": {}}
        elif node is None:
            config[section] = {}
        if not field:  # bare --model X == class path
            config[section]["class_path"] = raw
        else:
            field_overrides.append((section, field, raw))
    # Pass 2: typed field values.
    for section, field, raw in field_overrides:
        node = config[section]
        if section in (
            "trainer", "generate", "tokenize", "serve", "doctor", "top",
            "replay", "why", "plot", "alerts",
        ):  # plain dicts
            node[field] = yaml.safe_load(raw)
            continue
        init_args = node.setdefault("init_args", {})
        cls_path = node.get("class_path")
        annotation: Any = inspect.Parameter.empty
        default: Any = None
        if cls_path:
            try:
                sig = inspect.signature(import_class(cls_path).__init__)
                if field in sig.parameters:
                    annotation = sig.parameters[field].annotation
                    default = sig.parameters[field].default
            except Exception:  # noqa: BLE001 - fall back to yaml typing
                pass
        init_args[field] = _coerce(raw, annotation, default)
    return config


def parse_args(argv: Optional[List[str]] = None) -> Tuple[str, Dict[str, Any]]:
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = argparse.ArgumentParser(
        prog="ray_lightning_tpu", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("subcommand", choices=_SUBCOMMANDS)
    parser.add_argument("--config", action="append", default=[])
    parser.add_argument(
        "--address",
        default=None,
        help="fabric head address (host:port) for client mode — start one "
        "with `python -m ray_lightning_tpu.fabric.server`",
    )
    known, rest = parser.parse_known_args(argv)

    config: Dict[str, Any] = {}
    for path in known.config:
        with open(path) as f:
            loaded = yaml.safe_load(f) or {}
        for section, value in loaded.items():
            if isinstance(value, dict) and isinstance(config.get(section), dict):
                merged = dict(config[section])
                merged.update(value)
                config[section] = merged
            else:
                config[section] = value

    # CLI flag wins over any fabric: section from YAML (same precedence as
    # the dotted overrides, which also apply after the YAML merge).
    if known.address:
        fabric_cfg = dict(config.get("fabric") or {})
        fabric_cfg["address"] = known.address
        config["fabric"] = fabric_cfg

    dotted: List[Tuple[str, str]] = []
    i = 0
    while i < len(rest):
        arg = rest[i]
        if not arg.startswith("--"):
            # ``rlt doctor <addr>`` / ``rlt top <addr>`` /
            # ``rlt replay <journal>`` / ``rlt why <addr|journal> <id>``:
            # bare positionals fill the subcommand's keys in order (the
            # explicit dotted flag always wins over a positional).
            pos_keys = {
                "doctor": ("addr",), "top": ("addr",),
                "replay": ("journal",), "why": ("target", "id"),
                "plot": ("addr", "series"), "alerts": ("addr",),
            }.get(known.subcommand) or ()
            taken = config.get(known.subcommand) or {}
            pos_key = next((k for k in pos_keys if k not in taken), None)
            if pos_key is not None:
                config.setdefault(known.subcommand, {})[pos_key] = arg
                i += 1
                continue
            raise ValueError(f"unexpected argument {arg!r}")
        if arg == "--follow" and known.subcommand == "alerts":
            # Ergonomic alias: `rlt alerts <addr> --follow` ==
            # `--alerts.follow true` (the only bare flag the dotted
            # grammar admits — it takes no value).
            dotted.append(("alerts.follow", "true"))
            i += 1
            continue
        key = arg[2:]
        if "=" in key:
            key, _, value = key.partition("=")
        else:
            i += 1
            if i >= len(rest):
                raise ValueError(f"missing value for --{key}")
            value = rest[i]
        dotted.append((key, value))
        i += 1
    return known.subcommand, _apply_dotted(config, dotted)


def build(config: Dict[str, Any]) -> Tuple[Any, Any, Optional[Any]]:
    """(trainer, model, datamodule) from a parsed config tree."""
    from ray_lightning_tpu.trainer import Trainer

    if "model" not in config:
        raise ValueError("a --model (or model: section) is required")
    model = instantiate_class(config["model"])
    datamodule = (
        instantiate_class(config["data"]) if config.get("data") else None
    )
    strategy = None
    if config.get("strategy"):
        strategy = instantiate_class(config["strategy"])
    trainer_kwargs = dict(config.get("trainer") or {})
    _validate_ctor_kwargs(Trainer, trainer_kwargs)
    trainer = Trainer(strategy=strategy, **trainer_kwargs)
    return trainer, model, datamodule


def run_generate(config: Dict[str, Any]) -> Any:
    """``generate``: restore params from a checkpoint and decode.

    Config section (``--generate.<key>`` or ``generate:`` in YAML):
      ckpt_path (required, state-stream checkpoint), prompt (token ids —
      "1,2,3" or a YAML list), max_new_tokens, temperature, top_k, top_p,
      seed. Prints one comma-separated id line per sequence and returns
      the (B, P+N) array. Sharded checkpoint dirs need a live mesh — use
      ``validate``/``test`` for those; generation is a single-program path.
    """
    import numpy as np

    gen = dict(config.pop("generate", None) or {})
    model = instantiate_class(config["model"])
    if not hasattr(model, "generate"):
        raise ValueError(
            f"{type(model).__name__} has no generate(); the generate "
            "subcommand needs an autoregressive model (e.g. GPTLM)"
        )
    ckpt_path = gen.pop("ckpt_path", None)
    if ckpt_path is None:
        raise ValueError("generate requires --generate.ckpt_path")
    from ray_lightning_tpu.trainer.checkpoint_io import is_sharded_checkpoint
    from ray_lightning_tpu.utils.state_stream import load_state_stream

    if is_sharded_checkpoint(ckpt_path):
        raise ValueError(
            "generate restores state-stream checkpoints only; restore "
            "sharded dirs through validate/test first"
        )
    from ray_lightning_tpu.trainer.trainer import Trainer

    model.load_state_dict(load_state_stream(Trainer._read_ckpt(ckpt_path)))
    prompt = gen.pop("prompt", None)
    if prompt is None:
        raise ValueError("generate requires --generate.prompt (token ids)")
    if isinstance(prompt, str):
        prompt = [int(t) for t in prompt.replace(",", " ").split()]
    arr = np.atleast_2d(np.asarray(prompt, np.int32))
    # Pop every known option BEFORE decoding so a typo'd flag fails
    # instantly instead of after a long decode.
    seed = int(gen.pop("seed", 0))
    max_new_tokens = int(gen.pop("max_new_tokens", 32))
    temperature = float(gen.pop("temperature", 0.0))
    top_k = gen.pop("top_k", None)
    top_p = gen.pop("top_p", None)
    if gen:
        raise ValueError(f"unknown generate options: {sorted(gen)}")
    import jax

    out = model.generate(
        arr,
        max_new_tokens=max_new_tokens,
        temperature=temperature,
        rng=jax.random.PRNGKey(seed),
        top_k=top_k,
        top_p=top_p,
    )
    out = np.asarray(out)
    for row in out:
        print(",".join(str(int(t)) for t in row))
    return out


def run_convert_hf(config: Dict[str, Any]) -> str:
    """``convert-hf``: local Hugging Face GPT-2/Llama checkpoint -> a native
    params checkpoint usable as ``fit/validate/generate`` ckpt_path.

    Options (``--src``/``--out`` or a ``convert_hf:`` YAML section):
      src (required, HF checkpoint directory), out (required, .ckpt file),
      plus GPTConfig overrides under ``overrides:`` (e.g.
      ``--overrides.attn_impl reference``).
    """
    section = dict(config.pop("convert_hf", None) or {})
    src = config.pop("src", None) or section.pop("src", None)
    out = config.pop("out", None) or section.pop("out", None)
    family = (
        config.pop("family", None) or section.pop("family", None) or "gpt2"
    )
    overrides = dict(
        (config.pop("overrides", None) or section.pop("overrides", None) or {})
    )
    leftovers = {k: v for k, v in {**config, **section}.items()}
    if leftovers:
        raise ValueError(f"unknown convert-hf options: {sorted(leftovers)}")
    if not src or not out:
        raise ValueError("convert-hf requires --src <hf_dir> and --out <file.ckpt>")
    import dataclasses

    import jax
    import numpy as np

    from ray_lightning_tpu.models import load_hf_gpt2, load_hf_llama
    from ray_lightning_tpu.utils import to_state_stream
    from ray_lightning_tpu.utils.state_stream import state_stream_to_file

    if family not in ("gpt2", "llama"):
        raise ValueError(
            f"unknown convert-hf family {family!r}; use 'gpt2' or 'llama'"
        )
    loader = load_hf_llama if family == "llama" else load_hf_gpt2
    params, cfg = loader(src, **overrides)
    state_stream_to_file(
        to_state_stream(
            {"params": params, "gpt_config": dataclasses.asdict(cfg)}
        ),
        out,
    )
    n_params = sum(
        int(np.prod(np.shape(x)))
        for x in jax.tree_util.tree_leaves(params)
    )
    print(
        f"wrote {out}: {n_params:,} params, "
        f"n_layer={cfg.n_layer} d_model={cfg.d_model} vocab={cfg.vocab_size}"
    )
    return out


#: Every option ``rlt serve`` accepts (``--serve.<key>`` / YAML
#: ``serve:``). Validated UP FRONT so a typo'd flag fails instantly with
#: the valid vocabulary, instead of being silently swallowed or erroring
#: after replicas spawned. ``slo.<metric>`` rules are open-ended.
_SERVE_KEYS = frozenset((
    "ckpt_path", "config", "int8", "prompts",
    "max_new_tokens", "temperature", "top_k", "top_p", "seed",
    "eos_token", "replicas", "num_slots", "max_seq", "mesh",
    "hosts_per_replica",
    "prefill_buckets", "max_prefills_per_step", "decode_fold",
    "fold_ladder", "piggyback_chunks",
    "pipeline", "prefill_chunk", "prefix_cache", "prefix_block",
    "prefix_host_mb", "prefix_disk_dir", "prefix_disk_mb",
    "kv_page", "kv_pages",
    "max_prefill_chunks_per_step", "priority_age_s",
    "spec", "spec_depth", "spec_draft_ckpt", "spec_draft_config",
    "spec_draft_int8", "spec_window",
    "metrics_port", "tracing", "trace_out", "profile_s",
    "watchdog", "watchdog_interval_s", "stall_s", "slo",
    "blackbox_dir", "blackbox_keep",
    "fleet", "fleet_interval_s", "fleet_history",
    "journal", "journal_capacity",
    "supervisor", "restart_limit", "restart_backoff_s", "rpc_timeout_s",
    "preempt_grace_s", "preempt_sigterm", "preempt_metadata",
    "router", "router_refresh_s", "router_affinity", "router_shed",
    "shed_queue_factor", "retry_budget", "hedge_after_s",
    "submit_batch_ms", "directory_shards",
    "autoscale_min", "autoscale_max", "autoscale_interval_s",
    "prefill_replicas", "kvfleet", "kvfleet_timeout_s",
    "kvfleet_inflight_mb", "kvfleet_bandwidth_mbps",
    "kvfleet_layerwise",
    "kvstore_dir", "kvstore_mb", "kvstore_writethrough",
    "alerts", "alerts_interval_s", "alerts_rules", "alerts_webhook",
    "canary", "canary_interval_s", "canary_baseline",
))


def _serve_obs_server(
    client: Any,
    metrics_port: int,
    fleet: bool = True,
    fleet_interval_s: float = 2.0,
    fleet_history: int = 128,
    supervisor: Any = None,
    router: Any = None,
    alerts: bool = True,
    alerts_interval_s: Optional[float] = None,
    alerts_rules: Any = None,
    alerts_webhook: Optional[str] = None,
    canary: bool = False,
    canary_interval_s: float = 10.0,
    canary_baseline: Optional[str] = None,
) -> Tuple[Any, Optional[Any], Optional[Any]]:
    """Build (started) the driver-side obs HTTP server ``rlt serve``
    runs next to a replica gang, plus its FleetPoller (None when
    ``fleet`` is off). Routes:

    - ``/metrics``: every replica's registry (replica-labelled) + the
      driver's own (fabric heartbeat gauges, ``rlt_fleet_*``);
    - ``/stats``: per-replica stats snapshots;
    - ``/healthz``: the FLEET readiness probe an external load balancer
      points at: 503 only when NO replica can serve (every replica
      unhealthy/unreachable — a single sick replica is the
      supervisor's problem, not the LB's); the JSON body lists every
      replica's verdict plus the driver's own (fabric heartbeat)
      report, and the top-level verdict degrades while any replica is
      out;
    - ``/fleet``: the latest FleetSnapshot + history ring (``rlt top``'s
      feed), plus the supervisor's per-replica state table when a
      :class:`serve.supervisor.FleetSupervisor` is wired;
    - ``/events``: the merged structured event rings as JSONL
      (``?level=``/``?subsystem=``/``?n=`` filter server-side);
    - ``/traces``: the stitched cross-process Chrome trace;
    - ``/journal``: the workload journal(s) as JSONL — save it and
      ``rlt replay`` it (multi-replica output is replica-tagged);
    - ``/why?id=<request_id>``: one request's cross-process anatomy
      phase ledger (``rlt why``'s feed) — every tracer ring + the
      driver journal + the event rings stitched under one id;
    - ``/debug/bundle``: a replica flight-recorder bundle augmented
      driver-side with ``fleet.json`` + ``trace_stitched.json`` so a
      pulled post-mortem shows the whole fleet, not one process;
    - ``/query?series=&since=&step=``: one retained watchtower TSDB
      series (``rlt plot``'s feed);
    - ``/alerts``: the alert engine's rules/states/firing payload plus
      the canary summary (``rlt alerts``'s feed).

    The watchtower (PR 20) rides the fleet plane: when ``fleet`` and
    ``alerts`` are both on, a :class:`obs.watchtower.Watchtower`
    samples every FleetPoller snapshot into the ring TSDB, evaluates
    the alert rules on its own cadence, and (with ``canary``) runs the
    fixed-seed probe lane. Returns ``(server, fleet_poller,
    watchtower)`` — each None when its plane is off.

    Factored out of run_serve so the wire path is testable against any
    client-shaped object without spawning the CLI.
    """
    import json as _json

    from ray_lightning_tpu import obs
    from ray_lightning_tpu.fabric import core as fabric_core
    from ray_lightning_tpu.obs import health as obs_health
    from ray_lightning_tpu.obs import watchtower as obs_wt
    from ray_lightning_tpu.obs.fleet import FleetPoller
    from ray_lightning_tpu.obs.tsdb import RingTSDB

    driver_reg = obs.get_registry()
    driver_wd = obs_health.Watchdog(registry=driver_reg)
    driver_wd.add_check(obs_health.heartbeat_check(fabric_core.heartbeats))

    fleet_poller = None
    if fleet:
        fleet_poller = FleetPoller(
            pull_fn=lambda: (
                client.stats(), client.health(), fabric_core.heartbeats()
            ),
            interval_s=float(fleet_interval_s),
            history=int(fleet_history),
            registry=driver_reg,
            events=obs.get_event_log(),
            supervisor_fn=(
                supervisor.rows if supervisor is not None else None
            ),
            router_fn=(router.rows if router is not None else None),
        ).start()

    watchtower = None
    if fleet_poller is not None and (alerts or canary):
        if isinstance(alerts_rules, str):
            with open(alerts_rules) as f:
                alerts_rules = yaml.safe_load(f)
        rules = (
            obs_wt.parse_alert_rules(alerts_rules)
            if alerts_rules is not None else obs_wt.default_rules()
        )
        if not alerts:
            rules = []  # canary-only: just the lane's own rules
        sinks: List[Any] = [obs_wt.LogSink()]
        if alerts_webhook:
            sinks.append(obs_wt.WebhookSink(alerts_webhook))
        tsdb = RingTSDB(registry=driver_reg)
        lane = None
        if canary:
            baseline = canary_baseline
            if isinstance(baseline, str):
                with open(baseline) as f:
                    baseline = yaml.safe_load(f)
            lane = obs_wt.CanaryLane(
                client, tsdb,
                interval_s=float(canary_interval_s),
                baseline=baseline,
                events=obs.get_event_log(),
                registry=driver_reg,
            )
        watchtower = obs_wt.Watchtower(
            tsdb=tsdb,
            rules=rules,
            fleet_latest_fn=fleet_poller.latest,
            metrics_text_fn=client.metrics_text,
            canary=lane,
            sinks=sinks,
            events=obs.get_event_log(),
            registry=driver_reg,
            interval_s=float(
                alerts_interval_s if alerts_interval_s is not None
                else fleet_interval_s
            ),
        ).start()
        # Late-bound: the poller was built before the watchtower (its
        # snapshots are the watchtower's feed), so the /fleet payload's
        # alerts block is wired after the fact.
        fleet_poller._alerts_fn = watchtower.fleet_block

    def _collect() -> str:
        obs.heartbeats_to_registry(fabric_core.heartbeats(), driver_reg)
        return client.metrics_text() + driver_reg.render()

    def _collect_health():
        # FLEET readiness, not per-process health: an external LB gets
        # ONE probe endpoint and should keep routing while ANY replica
        # can serve — a single dead/unhealthy replica is the
        # supervisor's job (drain, restart, fail over), and pulling the
        # whole fleet for it would turn one replica crash into an
        # outage. 503 only when every replica is out; the body always
        # lists per-replica verdicts so operators see exactly who is
        # sick, plus the driver's own (fabric heartbeat) report.
        report = driver_wd.evaluate()
        payload = report.to_dict()
        replicas = client.health()
        payload["replicas"] = replicas
        # Retired replicas are deliberate scale-downs, not failures:
        # they stay visible in the body but never count against the
        # fleet's readiness.
        live = [r for r in replicas if not r.get("retired")]
        up = sum(1 for r in live if r.get("healthy", True))
        payload["replicas_total"] = len(live)
        payload["replicas_healthy"] = up
        if supervisor is not None:
            payload["supervisor"] = supervisor.rows()
        if router is not None:
            payload["router"] = router.rows()
        healthy = up > 0 if live else report.healthy
        payload["healthy"] = healthy
        if not healthy:
            payload["verdict"] = "unhealthy"
        elif (live and up < len(live)) or not report.healthy:
            payload["verdict"] = "degraded"
        return healthy, payload

    def _collect_events() -> str:
        rows = client.recent_events(512)
        rows += [
            dict(ev, replica="driver")
            for ev in obs.get_event_log().tail(128)
        ]
        rows.sort(key=lambda e: e.get("ts", 0))
        return "\n".join(
            _json.dumps(r, default=str) for r in rows
        ) + ("\n" if rows else "")

    def _collect_bundle() -> Dict[str, Any]:
        manifest = client.debug_dump(reason="http", pull=True)
        files = manifest.setdefault("files_content", {})
        extra = []
        # Fleet context rides INTO the bundle driver-side: the replica
        # wrote its own process's forensics; the driver is the only one
        # holding the fleet snapshot and the cross-process trace.
        if fleet_poller is not None:
            try:
                files["fleet.json"] = _json.dumps(
                    fleet_poller.to_dict(), default=str
                )
                extra.append("fleet.json")
            except Exception as exc:  # noqa: BLE001 - record, keep bundle
                manifest.setdefault("errors", {})["fleet.json"] = repr(exc)
        try:
            files["trace_stitched.json"] = _json.dumps(
                client.export_stitched_trace(n=16)
            )
            extra.append("trace_stitched.json")
        except Exception as exc:  # noqa: BLE001
            manifest.setdefault("errors", {})[
                "trace_stitched.json"
            ] = repr(exc)
        if extra:
            manifest["files"] = sorted(
                set(manifest.get("files", [])) | set(extra)
            )
        return manifest

    server = obs.MetricsHTTPServer(
        collect_text=_collect,
        collect_json=lambda: {"serve_stats": client.stats()},
        collect_health=_collect_health,
        collect_bundle=_collect_bundle,
        collect_fleet=(
            fleet_poller.to_dict if fleet_poller is not None else None
        ),
        collect_events=_collect_events,
        collect_traces=lambda: client.export_stitched_trace(n=16),
        collect_journal=client.journal_jsonl,
        collect_why=lambda rid: obs.anatomy_from_client(client, rid),
        collect_query=(
            watchtower.query if watchtower is not None else None
        ),
        collect_alerts=(
            watchtower.alerts_payload if watchtower is not None else None
        ),
        port=int(metrics_port),
    ).start()
    return server, fleet_poller, watchtower


def run_serve(config: Dict[str, Any]) -> Dict[str, Any]:
    """``serve``: spawn replica actors on the fabric and serve prompts.

    Config section (``--serve.<key>`` or ``serve:`` in YAML):
      ckpt_path (required): state-stream checkpoint (convert-hf native
        form with an embedded gpt_config, or a trainer checkpoint) or a
        sharded orbax dir (then ``config`` is required).
      config: GPTConfig field dict (overrides/completes the stored one).
      int8: quantize weights at load (weight-only int8 decode).
      replicas, num_slots, max_seq, max_prefills_per_step: topology knobs.
      mesh: "MODELxDATA" serving mesh (e.g. 4x1) — tensor-parallel
        decode: attention heads, the KV cache, and the prefix pool shard
        over MODEL devices (head counts must be divisible; greedy output
        stays bit-identical to 1x1); MODEL*DATA must equal the replica
        process's device count. Per-device footprint lands in stats
        "memory" and rlt_serve_hbm_bytes{component=}.
      hosts_per_replica: gang-launch one replica PROCESS GROUP per mesh
        on multi-host topologies (leader + followers rendezvoused via
        jax.distributed; single-host default 1).
      decode_fold: decode iterations per compiled dispatch (K tokens per
        slot per engine step; amortizes dispatch/sync, admissions land at
        fold boundaries). pipeline: double-buffer fold dispatch (default
        on).
      fold_ladder: pre-lowered fold depths, e.g. "1,2,8" (comma list or
        YAML list; every rung >= 1, must include decode_fold). Each
        dispatch picks the deepest rung the current queue pressure
        allows — short folds while admissions wait, deep folds on a
        quiet queue — with zero steady-state compiles (the whole
        ladder compiles at construction). Dispatch counts land in
        stats fold_k and rlt_serve_fold_depth.
      prefill_chunk: chunked prefill (tokens per chunk, 0 = monolithic):
        long prompts prefill in chunks interleaved between decode folds.
        max_prefill_chunks_per_step: chunk-vs-fold interleave budget.
      piggyback_chunks: fuse prefill into the decode dispatch (Sarathi
        -style chunked piggybacking): up to C chunked-prefill rows ride
        INSIDE each decode fold instead of issuing separate
        prefill_step dispatches (0 = off; 1 <= C <= num_slots; needs
        prefill_chunk > 0). Resident decodes stop stalling behind
        admissions; outputs stay bit-exact. Traffic lands in
        rlt_serve_piggyback_*_total and stats piggyback.
      prefix_cache: "off" (default), "on" (64 blocks), or a block count
        — device-resident prefix KV reuse for shared prompt prefixes
        (implies chunked prefill). prefix_block: tokens per pool block.
      prefix_host_mb: host-RAM spill tier below the device prefix pool
        (MiB; 0 = off): LRU-evicted pool blocks spill D2H instead of
        dying, and a host hit promotes the block back through one
        compiled H2D copy — cache capacity grows from spare HBM to
        machine RAM with greedy outputs unchanged. prefix_disk_dir /
        prefix_disk_mb: an optional disk tier below the host tier
        (.npy block files under the directory, default budget 1024
        MiB) absorbing host-tier evictions. Tier traffic lands in
        rlt_serve_prefix_*_total{tier=} and stats prefix.tiers.
      kv_pages / kv_page: paged KV (block-table attention) — kv_pages
        arms it and sets the page budget, kv_page the tokens per page
        (default 16; must divide max_seq). KV capacity becomes the
        token budget kv_pages x kv_page instead of slots x max_seq, a
        prefix hit aliases cached pages copy-free (refcounted; the
        prefix cache and slot KV share ONE allocator, so
        prefix_cache must stay off), spill tiers and preemption
        handoff operate on the same pages, and admission parks when
        pages run out instead of deadlocking. Greedy output stays
        bit-identical to the dense engine; pool state lands in
        rlt_serve_kv_pages{state=} and stats kv_pages. Leave unset
        for the dense cache.
      priority_age_s: queued requests age toward priority 0 at this rate
        (seconds per priority level); unset = strict priority order.
      spec: speculative decoding — "off" (default), "ngram" (in-graph
        prompt-lookup drafter, zero extra weights), or "model" (small
        draft model); bare off/on parse as YAML booleans and normalize
        to "off"/"ngram". spec_depth: draft tokens proposed per verify
        forward (accepted prefix advances up to depth+1 tokens per
        forward). spec_draft_ckpt / spec_draft_config /
        spec_draft_int8: the draft model's checkpoint (spec=model),
        config overrides, and weight-only int8. spec_window: history
        window the draft model conditions on. Greedy output stays
        bit-identical to spec off; accept rates land in
        stats.spec_stats and the spec_accept_rate metric.
      metrics_port: serve a Prometheus /metrics endpoint (plus /stats
        JSON, /healthz, /debug/bundle, /fleet, /events, /traces,
        /alerts, /query) on
        this driver-side port for the duration of the run, aggregating
        every replica's registry (0 picks a free port; the chosen URL
        prints to stderr). Point `rlt top <host:port>` at it for a live
        fleet dashboard.
      fleet: drive the driver-side fleet aggregator behind /fleet
        (default on; needs metrics_port to be reachable).
        fleet_interval_s: poll cadence (default 2s); fleet_history:
        snapshots retained in the history ring (default 128).
      alerts: drive the watchtower (default on; rides the fleet
        plane) — fleet snapshots are sampled into bounded
        multi-resolution telemetry rings (obs.tsdb) and declarative
        alert rules (threshold / absence / multi-window burn-rate over
        the SLO-breach ratio) evaluate each tick with a
        pending->firing->resolved lifecycle behind /alerts and
        /query (rlt alerts / rlt plot). alerts_interval_s: evaluation
        cadence (default = fleet_interval_s); alerts_rules: rule
        overrides (a YAML/JSON file path or inline list — see
        docs/observability.md for the grammar); alerts_webhook: an
        http(s) URL notifications are shaped for (webhook-shaped stub
        sink — payloads recorded, no socket opened in this build).
      canary: run the canary probe lane (default off) — a tiny
        fixed-seed probe submitted every canary_interval_s (default
        10s) under the reserved _canary tenant at floor priority;
        TTFT / decode rate / exactness land in dedicated canary.*
        series and alert on deviation from the recorded baseline
        envelope (canary_baseline: a JSON file in the format
        obs.watchtower.CanaryLane documents).
        Canary traffic is excluded from organic accounting (cost
        ledger, goodput, autoscaler pressure, tenant rows).
      supervisor: drive the driver-side FleetSupervisor (default on) —
        the detect->decide->recover loop: unhealthy replicas drain
        (no new submissions, in-flight work finishes), dead replicas
        restart through the fabric from the same resolved config, and
        their incomplete requests fail over onto survivors by
        replaying the client journal's submit records (bit-identical
        token streams for greedy/seeded requests; already-streamed
        prefixes deduplicate client-side). restart_limit: consecutive
        failed restarts before a replica is parked as failed (default
        3); restart_backoff_s: base of the capped exponential restart
        backoff (default 1s). Restart/failover traffic lands in
        rlt_fleet_replica_restarts_total, rlt_serve_failover_*, and
        replica_lost/failover/replica_restarted events.
      rpc_timeout_s: per-RPC timeout for every client->replica call
        (default none — block); transient failures retry with capped
        exponential backoff + jitter before the replica is declared
        lost.
      router: the front-door routing policy (default on) — submit
        consults serve.router.Router instead of round-robin:
        supervisor states (draining/preempting/dead) and health
        verdicts demote or exclude replicas, shared-prefix traffic
        lands on the replica holding the warm blocks/pages
        (router_affinity, default on — digests match the engines'
        prefix_block/kv_page), and admission control sheds work at the
        door (router_shed, default on): a deadline the fleet's
        windowed decode rate cannot meet, or lowest-priority work on a
        saturated fleet (every routable queue >= shed_queue_factor x
        its slots, default 4.0), is rejected with a typed outcome and
        a retry-after hint instead of queueing to collapse.
        router_refresh_s: replica-view staleness bound (default 1s).
      retry_budget: aggregate client retry cap — transient-RPC retries
        across ALL calls are limited to this fraction of recent
        submits (default 0.5; false disables), so a sick fleet gets
        backpressure instead of a retry storm; exhaustion counts in
        rlt_serve_retry_budget_exhausted_total.
      hedge_after_s: hedged streaming reads — a stream with no new
        token for this long (while its replica still answers) is
        re-driven on a peer under the same id/seed, bit-exact with the
        delivered prefix deduplicated (default off; covers gray
        failures liveness probes cannot see).
      autoscale_min / autoscale_max / autoscale_interval_s: queue-
        driven replica autoscaling within [min, max] (autoscale_max
        arms it; min defaults to the initial replica count): sustained
        queue depth, shedding, or SLO breaches spawn replicas through
        the retained spawn recipes (role-aware — a disaggregated
        fleet's prefill and decode pools scale independently); a
        sustained-idle fleet retires them gracefully (drained +
        leftovers migrated — no request lost at retire).
      prefill_replicas: dedicate the FIRST N of `replicas` to chunked
        prefill only (disaggregated prefill/decode; needs a prefix
        cache or paged KV and at least one decode replica left over):
        the router lands new prompts on the prefill pool, each
        finished prefill's KV pages ship to a router-chosen decode
        replica over fabric queues, and the request decodes there
        warm — greedy output bit-identical to a fully local run.
        Long prompts stop stealing fold time from resident decodes.
      kvfleet: cross-replica KV sharing (default: auto — on for a
        multi-replica fleet with a prefix cache/paged KV). When the
        router must steer a request away from the replica holding its
        prefix chain, the target fetches the pages from that peer
        (digest-keyed, shard-aware) instead of re-prefilling cold —
        N caches become one fleet cache. kvfleet_timeout_s bounds a
        fetch (timeout/staleness degrade to cold prefill, never a
        lost request); kvfleet_inflight_mb bounds in-flight transfer
        bytes; kvfleet_bandwidth_mbps caps transfer throughput
        (0 = uncapped). Traffic lands in
        rlt_serve_kvfleet_*_total{role=} and the fleet rows.
        kvfleet_layerwise: stream a disaggregated prefill's shipped
        pages to the decode target PER LAYER as each ships, instead
        of one whole-prompt blob at completion — the decode replica
        imports layer l while layer l+1 is in flight, cutting
        ship-to-first-decode latency. A target dying mid-stream
        aborts the staged partial (cold prefill, nothing lost).
      kvstore_dir: fleet-shared persistent KV store (tier of last
        resort, content-addressed by the engines' chained page
        digests): evictions falling off the bottom of a replica's
        local tiers write through here instead of dying, a chain no
        live peer holds fetches from here through the same
        park->import->admit-warm path, a restarted fleet pre-seeds
        its routing directory from the store manifest (yesterday's
        system prompts hit on the first request), and park_session
        exports an idle conversation here and frees its pages —
        restored bit-exactly on the next turn, on any replica.
        kvstore_mb bounds the store (LRU-by-last-access GC on
        measured bytes; 0 = unbounded); kvstore_writethrough
        additionally writes EVERY completed prefill through (pages
        survive autoscale-retire, at extra write amplification).
        Corrupt/vanished entries degrade to cold prefill, never a
        crash. Traffic lands in rlt_serve_kvstore_*_total and the
        fleet rows. NOTE: one store dir per single-host fleet —
        multi-host gang processes would each hold only their own
        shard subset.
      tracing: record request traces on the replicas (default on);
        trace_out: after serving, write the replicas' recent traces as
        Chrome trace-event JSON to this path (opens in Perfetto).
      profile_s: capture an on-demand jax.profiler trace of replica 0
        for this many seconds while the submitted prompts decode; the
        artifact directory prints to stderr.
      watchdog: per-replica health watchdog (default on) — engine
        stall / admission wedge / compile-storm detection driving the
        health() RPC, rlt_health gauges, and automatic flight-recorder
        bundles. stall_s: seconds of no progress before a stall verdict
        (default 10); watchdog_interval_s: evaluation cadence.
      slo.<metric> <limit>: declarative SLO upper bounds evaluated
        against the replica stats snapshot (e.g. --serve.slo.ttft_p95_s
        0.5, --serve.slo.inter_token_p95_s 0.05, --serve.slo.error_rate
        0.01); breaches flip /healthz to 503 and count in
        rlt_slo_breaches_total{rule=...}.
      blackbox_dir / blackbox_keep: where automatic forensic bundles
        land (default RLT_BLACKBOX_DIR or the tempdir) and how many to
        retain. Inspect with `rlt doctor <host:port>` against
        metrics_port.
      journal: workload capture for deterministic replay (default on —
        a bounded in-memory ring of every submit/cancel + per-request
        emitted tokens). Pass a DIRECTORY to additionally stream the
        journal as rotated JSONL there; `false` disables capture.
        journal_capacity: ring size (default 4096 entries). Export via
        the /journal route, journal.jsonl in doctor bundles, or the
        journal_dump RPC; re-drive with `rlt replay <journal>`.
      prompts: path to a prompts file ("-" = stdin), one request per
        line as comma/space-separated token ids.
      max_new_tokens, temperature, top_k, top_p, seed, eos_token:
        sampling defaults applied to every request.

    All prompts are submitted up front (they overlap inside the engine —
    that is the point), streamed to completion, and printed as
    ``<request_id><TAB><prompt+generated ids csv>`` lines. One final JSON
    line carries the per-replica stats-endpoint snapshots.
    """
    import json as _json

    from ray_lightning_tpu import fabric
    from ray_lightning_tpu.serve import start_replicas

    serve_cfg = dict(config.pop("serve", None) or {})
    # Reject mistyped --serve.* keys FIRST, naming the valid vocabulary
    # — before any checkpoint loads or replicas spawn.
    unknown = sorted(
        k for k in serve_cfg
        if k not in _SERVE_KEYS and not k.startswith("slo.")
    )
    if unknown:
        raise ValueError(
            f"unknown serve option(s) {unknown}; valid --serve.* keys: "
            f"{sorted(_SERVE_KEYS)} (plus slo.<metric> rules)"
        )
    # Mesh spec: validated up front like the key vocabulary — a
    # malformed --serve.mesh must fail before a checkpoint loads or a
    # replica spawns, naming the valid format. Normalized to the
    # canonical "MODELxDATA" string (YAML coerces a bare "8" to int).
    from ray_lightning_tpu.parallel.mesh import parse_mesh_spec

    mesh_raw = serve_cfg.pop("mesh", None)
    mesh_spec = None
    if mesh_raw is not None:
        mesh_spec = "{}x{}".format(*parse_mesh_spec(mesh_raw))
    hosts_per_replica = int(serve_cfg.pop("hosts_per_replica", 1))
    if hosts_per_replica < 1:
        raise ValueError("--serve.hosts_per_replica must be >= 1")
    ckpt_path = serve_cfg.pop("ckpt_path", None)
    if ckpt_path is None:
        raise ValueError("serve requires --serve.ckpt_path")
    prompts_src = serve_cfg.pop("prompts", None)
    if prompts_src is None:
        raise ValueError(
            "serve requires --serve.prompts (file of token-id lines, or -)"
        )
    sampling = {
        "max_new_tokens": int(serve_cfg.pop("max_new_tokens", 32)),
        "temperature": float(serve_cfg.pop("temperature", 0.0)),
        "top_k": serve_cfg.pop("top_k", None),
        "top_p": serve_cfg.pop("top_p", None),
        "eos_token": serve_cfg.pop("eos_token", None),
    }
    seed = int(serve_cfg.pop("seed", 0))
    replicas = int(serve_cfg.pop("replicas", 1))
    replica_kwargs = {
        "ckpt_path": ckpt_path,
        "model_config": serve_cfg.pop("config", None),
        "int8": bool(serve_cfg.pop("int8", False)),
        "num_slots": int(serve_cfg.pop("num_slots", 4)),
        "max_seq": serve_cfg.pop("max_seq", None),
        "max_prefills_per_step": int(
            serve_cfg.pop("max_prefills_per_step", 1)
        ),
        "decode_fold": int(serve_cfg.pop("decode_fold", 1)),
        "pipeline": bool(serve_cfg.pop("pipeline", True)),
        "prefill_chunk": int(serve_cfg.pop("prefill_chunk", 0)),
        "prefix_block": int(serve_cfg.pop("prefix_block", 16)),
        "max_prefill_chunks_per_step": int(
            serve_cfg.pop("max_prefill_chunks_per_step", 1)
        ),
    }
    # Fused-dispatch knobs, validated up front with named ranges (the
    # engine re-validates, but a fleet launch should die on the driver
    # with the flag name, not in replica 3's traceback).
    ladder = serve_cfg.pop("fold_ladder", None)
    if ladder is not None:
        if isinstance(ladder, str):
            ladder = [r for r in ladder.replace(",", " ").split() if r]
        elif isinstance(ladder, (int, float)):
            ladder = [ladder]
        rungs = sorted({int(r) for r in ladder})
        bad = [r for r in rungs if r < 1]
        if bad:
            raise ValueError(
                f"--serve.fold_ladder rungs {bad} out of range: every "
                "rung must be >= 1 (decode iterations per dispatch)"
            )
        if replica_kwargs["decode_fold"] not in rungs:
            raise ValueError(
                f"--serve.fold_ladder {rungs} must include decode_fold="
                f"{replica_kwargs['decode_fold']} (the rung a "
                "full-runway dispatch uses)"
            )
        replica_kwargs["fold_ladder"] = rungs
    pbc = int(serve_cfg.pop("piggyback_chunks", 0))
    if not 0 <= pbc <= replica_kwargs["num_slots"]:
        raise ValueError(
            f"--serve.piggyback_chunks {pbc} out of range: need 0 <= C "
            f"<= num_slots={replica_kwargs['num_slots']} (each "
            "piggyback row targets one slot; 0 = off)"
        )
    if pbc and replica_kwargs["prefill_chunk"] <= 0:
        raise ValueError(
            "--serve.piggyback_chunks needs --serve.prefill_chunk > 0 "
            "(piggyback rows are chunked-prefill rows riding the "
            "decode fold)"
        )
    if pbc:
        replica_kwargs["piggyback_chunks"] = pbc
    if mesh_spec is not None:
        replica_kwargs["mesh"] = mesh_spec
    age = serve_cfg.pop("priority_age_s", None)
    if age is not None:
        replica_kwargs["priority_age_s"] = float(age)
    # Speculative decoding: --serve.spec {off|ngram|model} with
    # --serve.spec_depth draft tokens per verify; spec=model drafts with
    # the (optionally int8) checkpoint at --serve.spec_draft_ckpt.
    # Dotted values parse as YAML, where bare off/on are 1.1 booleans —
    # map them back to the words the flag documents (on = the
    # zero-weight n-gram drafter).
    spec_raw = serve_cfg.pop("spec", "off")
    if spec_raw is False:
        spec_raw = "off"
    elif spec_raw is True:
        spec_raw = "ngram"
    replica_kwargs["spec"] = str(spec_raw)
    replica_kwargs["spec_depth"] = int(serve_cfg.pop("spec_depth", 4))
    replica_kwargs["spec_window"] = int(serve_cfg.pop("spec_window", 32))
    replica_kwargs["spec_draft_int8"] = bool(
        serve_cfg.pop("spec_draft_int8", False)
    )
    draft_ckpt = serve_cfg.pop("spec_draft_ckpt", None)
    if draft_ckpt is not None:
        replica_kwargs["spec_draft_ckpt"] = str(draft_ckpt)
    draft_cfg = serve_cfg.pop("spec_draft_config", None)
    if draft_cfg is not None:
        replica_kwargs["spec_draft_config"] = dict(draft_cfg)
    replica_kwargs["tracing"] = bool(serve_cfg.pop("tracing", True))
    replica_kwargs["watchdog"] = bool(serve_cfg.pop("watchdog", True))
    # Workload journal: the ring is on by default; --serve.journal DIR
    # additionally spills JSONL there (rotated), --serve.journal false
    # turns capture off entirely. YAML parses bare off/on as booleans.
    jr = serve_cfg.pop("journal", True)
    if jr is False or jr in ("off",):
        replica_kwargs["journal"] = False
    elif jr is not True and jr not in ("on",):
        replica_kwargs["journal_dir"] = str(jr)
    jc = serve_cfg.pop("journal_capacity", None)
    if jc is not None:
        replica_kwargs["journal_capacity"] = int(jc)
    for knob, cast in (
        ("watchdog_interval_s", float),
        ("stall_s", float),
        ("blackbox_dir", str),
        ("blackbox_keep", int),
        # Preemption signal plane: grace window for the drain,
        # SIGTERM-as-notice (on by default), and the GCE-shaped
        # maintenance-event metadata poller (off by default — only
        # meaningful on metadata-served hosts).
        ("preempt_grace_s", float),
        ("preempt_sigterm", bool),
        ("preempt_metadata", bool),
    ):
        val = serve_cfg.pop(knob, None)
        if val is not None:
            replica_kwargs[knob] = cast(val)
    # SLO rules: YAML ``serve: {slo: {metric: limit}}`` and/or dotted
    # ``--serve.slo.<metric> <limit>`` flags (all upper bounds).
    slo_cfg = dict(serve_cfg.pop("slo", None) or {})
    for key in [k for k in serve_cfg if k.startswith("slo.")]:
        slo_cfg[key[len("slo."):]] = serve_cfg.pop(key)
    if slo_cfg:
        replica_kwargs["slo"] = {
            str(m): float(v) for m, v in slo_cfg.items()
        }
    metrics_port = serve_cfg.pop("metrics_port", None)
    trace_out = serve_cfg.pop("trace_out", None)
    profile_s = serve_cfg.pop("profile_s", None)
    # Fleet aggregation (rides the metrics endpoint): the driver-side
    # puller behind /fleet, rlt top, and the fleet.json bundle file.
    fleet_enabled = bool(serve_cfg.pop("fleet", True))
    fleet_interval_s = float(serve_cfg.pop("fleet_interval_s", 2.0))
    fleet_history = int(serve_cfg.pop("fleet_history", 128))
    # Watchtower (rides the fleet plane): retained telemetry rings +
    # the burn-rate alert engine behind /alerts, /query, and rlt
    # alerts/plot; the canary lane submits fixed-seed probes under the
    # reserved _canary tenant (excluded from organic accounting).
    alerts_enabled = bool(serve_cfg.pop("alerts", True))
    alerts_interval_s = serve_cfg.pop("alerts_interval_s", None)
    if alerts_interval_s is not None:
        alerts_interval_s = float(alerts_interval_s)
    alerts_rules = serve_cfg.pop("alerts_rules", None)
    alerts_webhook = serve_cfg.pop("alerts_webhook", None)
    canary_enabled = bool(serve_cfg.pop("canary", False))
    canary_interval_s = float(serve_cfg.pop("canary_interval_s", 10.0))
    canary_baseline = serve_cfg.pop("canary_baseline", None)
    # Fault tolerance: the driver-side supervisor (drain/restart/fail
    # over) and the client's per-RPC timeout knob.
    supervisor_enabled = bool(serve_cfg.pop("supervisor", True))
    restart_limit = int(serve_cfg.pop("restart_limit", 3))
    restart_backoff_s = float(serve_cfg.pop("restart_backoff_s", 1.0))
    rpc_timeout_s = serve_cfg.pop("rpc_timeout_s", None)
    if rpc_timeout_s is not None:
        rpc_timeout_s = float(rpc_timeout_s)
    # Front-door router (default on): health/state-aware + prefix-
    # affinity routing with admission control; the autoscaler arms when
    # autoscale_max is set. retry_budget caps the client's aggregate
    # transient-RPC retries as a fraction of recent submits (false
    # disables the cap); hedge_after_s arms hedged streaming reads.
    router_enabled = bool(serve_cfg.pop("router", True))
    router_refresh_s = float(serve_cfg.pop("router_refresh_s", 1.0))
    router_affinity = bool(serve_cfg.pop("router_affinity", True))
    router_shed = bool(serve_cfg.pop("router_shed", True))
    shed_queue_factor = float(serve_cfg.pop("shed_queue_factor", 4.0))
    retry_budget = serve_cfg.pop("retry_budget", 0.5)
    retry_budget = (
        None if retry_budget in (False, None) else float(retry_budget)
    )
    hedge_after_s = serve_cfg.pop("hedge_after_s", None)
    if hedge_after_s is not None:
        hedge_after_s = float(hedge_after_s)
    # Control-plane throughput knobs (validated up front with named
    # ranges — a fleet launch dies on the driver with the flag name):
    # submit_batch_ms arms the client's micro-batching window (one
    # vectorized plan + one submit_many RPC per target per window),
    # directory_shards lock-stripes the fleet KV directory.
    submit_batch_ms = float(serve_cfg.pop("submit_batch_ms", 0.0))
    if not 0.0 <= submit_batch_ms <= 1000.0:
        raise ValueError(
            f"--serve.submit_batch_ms {submit_batch_ms} out of range: "
            "need 0 <= ms <= 1000 (micro-batching window; 0 = off, the "
            "serial submit path)"
        )
    directory_shards = int(serve_cfg.pop("directory_shards", 1))
    if not 1 <= directory_shards <= 256:
        raise ValueError(
            f"--serve.directory_shards {directory_shards} out of "
            "range: need 1 <= N <= 256 (lock stripes over the fleet KV "
            "directory; 1 = the single-shard structure)"
        )
    autoscale_min = serve_cfg.pop("autoscale_min", None)
    autoscale_max = serve_cfg.pop("autoscale_max", None)
    autoscale_interval_s = float(
        serve_cfg.pop("autoscale_interval_s", 2.0)
    )
    if autoscale_max is not None and int(autoscale_max) < replicas:
        raise ValueError(
            f"--serve.autoscale_max {autoscale_max} is below the "
            f"initial replica count {replicas}"
        )
    # Fleet KV plane: disaggregated prefill/decode pools + the
    # cross-replica transfer knobs (validated below once the prefix
    # cache / paged-KV config is resolved).
    prefill_replicas = int(serve_cfg.pop("prefill_replicas", 0))
    if not 0 <= prefill_replicas < replicas:
        raise ValueError(
            f"--serve.prefill_replicas {prefill_replicas} must leave "
            f"at least one decode replica (0 <= N < replicas="
            f"{replicas})"
        )
    kvfleet = serve_cfg.pop("kvfleet", None)
    if kvfleet is not None:
        kvfleet = bool(kvfleet)
    kvfleet_timeout_s = float(serve_cfg.pop("kvfleet_timeout_s", 5.0))
    kvfleet_inflight_mb = float(
        serve_cfg.pop("kvfleet_inflight_mb", 64.0)
    )
    kvfleet_bandwidth_mbps = float(
        serve_cfg.pop("kvfleet_bandwidth_mbps", 0.0)
    )
    kvfleet_layerwise = bool(serve_cfg.pop("kvfleet_layerwise", False))
    if kvfleet_layerwise and not (kvfleet or prefill_replicas):
        raise ValueError(
            "--serve.kvfleet_layerwise streams shipped KV pages per "
            "layer over the fleet plane: enable --serve.kvfleet or "
            "set --serve.prefill_replicas first"
        )
    if kvfleet_layerwise:
        replica_kwargs["kvfleet_layerwise"] = True
    # Persistent KV store (fleet-shared tier of last resort):
    # --serve.kvstore_dir mounts it, --serve.kvstore_mb bounds it (LRU
    # GC; 0 = unbounded), --serve.kvstore_writethrough makes prefill
    # replicas write every completed prefill through so pages survive
    # autoscale-retire.
    kvstore_dir = serve_cfg.pop("kvstore_dir", None)
    kvstore_mb = float(serve_cfg.pop("kvstore_mb", 0.0))
    if kvstore_mb < 0:
        raise ValueError(
            f"--serve.kvstore_mb {kvstore_mb} must be >= 0 (MiB budget; "
            "0 = unbounded)"
        )
    kvstore_writethrough = bool(
        serve_cfg.pop("kvstore_writethrough", False)
    )
    if kvstore_writethrough and kvstore_dir is None:
        raise ValueError(
            "--serve.kvstore_writethrough needs --serve.kvstore_dir "
            "(the store to write through to)"
        )
    if kvstore_dir is not None:
        replica_kwargs["kvstore_dir"] = str(kvstore_dir)
        replica_kwargs["kvstore_mb"] = kvstore_mb
        replica_kwargs["kvstore_writethrough"] = kvstore_writethrough
    pc = serve_cfg.pop("prefix_cache", "off")
    if isinstance(pc, str):
        pc_norm = pc.strip().lower()
        if pc_norm in ("off", "false", "0", ""):
            blocks = 0
        elif pc_norm in ("on", "true"):
            blocks = 64
        else:
            blocks = int(pc_norm)
    else:
        blocks = (64 if pc else 0) if isinstance(pc, bool) else int(pc)
    replica_kwargs["prefix_blocks"] = blocks
    # Spill tiers below the device pool (host RAM, then disk). Budgets
    # are MiB floats; the engine rejects tiers without a device pool.
    replica_kwargs["prefix_host_mb"] = float(
        serve_cfg.pop("prefix_host_mb", 0.0)
    )
    pdd = serve_cfg.pop("prefix_disk_dir", None)
    if pdd is not None:
        replica_kwargs["prefix_disk_dir"] = str(pdd)
    replica_kwargs["prefix_disk_mb"] = float(
        serve_cfg.pop("prefix_disk_mb", 0.0)
    )
    # Paged KV: --serve.kv_pages arms block-table attention (capacity =
    # kv_pages * kv_page tokens instead of slots * max_seq);
    # --serve.kv_page sets the page size (default 16). Validated up
    # front: the page budget must be real, the page size must be a
    # token count, and the DENSE prefix cache cannot ride along — the
    # paged allocator IS the prefix cache (copy-free aliasing), so a
    # combined config would silently double-provision; reject it loudly
    # instead.
    kv_pages = serve_cfg.pop("kv_pages", None)
    kv_page = serve_cfg.pop("kv_page", None)
    if kv_pages is not None:
        kv_pages = int(kv_pages)
        if kv_pages < 2:
            raise ValueError(
                f"--serve.kv_pages {kv_pages} is not a usable page "
                "budget: need >= 2 (one scratch page + at least one "
                "real page; the engine additionally requires the "
                "budget to hold one max_seq-length request)"
            )
        replica_kwargs["kv_pages"] = kv_pages
    if kv_page is not None:
        kv_page = int(kv_page)
        if kv_page < 1:
            raise ValueError(
                f"--serve.kv_page {kv_page} must be >= 1 (tokens per "
                "KV page; it must also divide the engine's max_seq)"
            )
        if kv_pages is None:
            raise ValueError(
                "--serve.kv_page needs --serve.kv_pages (the paged-KV "
                "page budget); dense mode takes neither"
            )
        replica_kwargs["kv_page"] = kv_page
    if kv_pages and replica_kwargs.get("prefix_blocks"):
        raise ValueError(
            "--serve.kv_pages (paged KV) unifies the prefix pool into "
            "the page allocator — prefix sharing is built in and "
            "copy-free; drop --serve.prefix_cache/--serve.prefix_block "
            "(tune the page size with --serve.kv_page instead)"
        )
    pb = serve_cfg.pop("prefill_buckets", None)
    if pb is not None:
        replica_kwargs["prefill_buckets"] = [int(b) for b in pb]
    if prefill_replicas and not (blocks or kv_pages):
        raise ValueError(
            "--serve.prefill_replicas (disaggregated prefill) ships KV "
            "pages through the prefix pool: set --serve.prefix_cache "
            "(dense) or --serve.kv_pages (paged)"
        )
    roles = None
    if prefill_replicas:
        roles = (
            ["prefill"] * prefill_replicas
            + ["decode"] * (replicas - prefill_replicas)
        )
    # Resolved router policy: built once — it constructs the Router
    # below AND rides into every replica's journal header (provenance a
    # replayed capture carries). Affinity digests must use the engines'
    # block/page size, and only pay when a prefix cache exists at all.
    router_cfg = None
    if router_enabled:
        aff_block = int(replica_kwargs.get("prefix_block", 16))
        if replica_kwargs.get("kv_pages"):
            aff_block = int(replica_kwargs.get("kv_page", 16) or 16)
        router_cfg = {
            "refresh_s": router_refresh_s,
            "affinity": bool(
                router_affinity
                and (blocks > 0 or replica_kwargs.get("kv_pages"))
            ),
            "prefix_block": aff_block,
            "shed": router_shed,
            "shed_queue_factor": shed_queue_factor,
            "retry_budget_ratio": retry_budget,
            "hedge_after_s": hedge_after_s,
            "autoscale_min": autoscale_min,
            "autoscale_max": autoscale_max,
            "autoscale_interval_s": autoscale_interval_s,
            "submit_batch_ms": submit_batch_ms,
            "directory_shards": directory_shards,
        }
        replica_kwargs["router_config"] = router_cfg
    if serve_cfg:
        # _SERVE_KEYS said these were valid but nothing consumed them:
        # the vocabulary and the pops drifted apart — a bug here, not a
        # user typo (those were rejected up front).
        raise RuntimeError(
            f"serve options {sorted(serve_cfg)} are listed in _SERVE_KEYS "
            "but unhandled"
        )

    if prompts_src == "-":
        lines = [ln.strip() for ln in sys.stdin]
    else:
        with open(prompts_src) as f:
            lines = [ln.strip() for ln in f]
    prompts = [
        [int(t) for t in ln.replace(",", " ").split()] for ln in lines if ln
    ]
    if not prompts:
        raise ValueError(f"no prompts in {prompts_src!r}")

    if not fabric.is_initialized():
        fabric.init()
    # Each replica process reserves the chips its mesh spans (the fabric
    # pins them, so replicas sharing a host never open each other's
    # chips). Only a fabric that says it has no chips decodes on CPU: the
    # platform is pinned so the actor never probes for devices, and a mesh
    # spec additionally forces that many VIRTUAL host devices in the
    # replica process — a "4x2" mesh needs 8 devices wherever it runs.
    model, data = parse_mesh_spec(mesh_spec)
    env: Dict[str, str] = {}
    chips_per_process = 0
    if fabric.cluster_resources().get("TPU", 0) >= 1:
        chips_per_process = max(1, model * data // hosts_per_replica)
    else:
        env["JAX_PLATFORMS"] = "cpu"
        if model * data > 1:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={model * data}"
            )
    client = start_replicas(
        replicas,
        num_tpus_per_replica=chips_per_process,
        env=env,
        hosts_per_replica=hosts_per_replica,
        rpc_timeout_s=rpc_timeout_s,
        retry_budget_ratio=retry_budget,
        hedge_after_s=hedge_after_s,
        submit_batch_ms=submit_batch_ms,
        roles=roles,
        kvfleet=kvfleet,
        kvfleet_timeout_s=kvfleet_timeout_s,
        kvfleet_inflight_mb=kvfleet_inflight_mb,
        kvfleet_bandwidth_mbps=kvfleet_bandwidth_mbps,
        **replica_kwargs,
    )
    metrics_server = None
    fleet_poller = None
    watchtower = None
    supervisor = None
    router = None
    autoscaler = None
    if supervisor_enabled:
        # Close the detect->decide->recover loop for the run's duration:
        # unhealthy replicas drain, dead ones restart (same resolved
        # config) within the backoff budget, and their incomplete
        # requests fail over onto survivors bit-exactly.
        from ray_lightning_tpu.serve.supervisor import FleetSupervisor

        supervisor = FleetSupervisor(
            client,
            restart_limit=restart_limit,
            restart_backoff_s=restart_backoff_s,
        ).start()
    if router_cfg is not None:
        # The front door: submit consults this policy instead of the
        # bare round-robin — supervisor states and health verdicts
        # demote/exclude, shared prefixes land on the warm replica, and
        # an overloaded fleet sheds at the door instead of collapsing
        # its queues.
        from ray_lightning_tpu.serve.router import (
            Router,
            RouterAutoscaler,
        )

        router = Router(
            client=client,
            state_fn=(
                supervisor.rows if supervisor is not None else None
            ),
            refresh_s=router_refresh_s,
            affinity=router_cfg["affinity"],
            prefix_block=router_cfg["prefix_block"],
            shed=router_shed,
            shed_queue_factor=shed_queue_factor,
            directory_shards=directory_shards,
        )
        client.router = router
        # Warm-start: a fresh fleet inherits the persistent store's
        # manifest as store-held directory routes, so yesterday's
        # prefixes hit (via a store fetch) on the FIRST request.
        client.seed_store_directory(router)
        if autoscale_max is not None:
            autoscaler = RouterAutoscaler(
                client,
                router=router,
                min_replicas=int(autoscale_min or replicas),
                max_replicas=int(autoscale_max),
                interval_s=autoscale_interval_s,
            ).start()
    try:
        if metrics_port is not None:
            # Driver-side Prometheus endpoint for the run's duration:
            # each scrape pulls every replica's registry live (plus the
            # driver's own, which carries fabric heartbeat gauges), and
            # /healthz aggregates fabric heartbeat verdicts + every
            # replica's health() RPC — 200 only while nothing is
            # unhealthy, so an external LB can act on it. /fleet,
            # /events, and /traces serve the fleet plane (rlt top,
            # post-mortems, the stitched cross-process trace).
            metrics_server, fleet_poller, watchtower = _serve_obs_server(
                client,
                int(metrics_port),
                fleet=fleet_enabled,
                fleet_interval_s=fleet_interval_s,
                fleet_history=fleet_history,
                supervisor=supervisor,
                router=router,
                alerts=alerts_enabled,
                alerts_interval_s=alerts_interval_s,
                alerts_rules=alerts_rules,
                alerts_webhook=alerts_webhook,
                canary=canary_enabled,
                canary_interval_s=canary_interval_s,
                canary_baseline=canary_baseline,
            )
            if supervisor is not None and fleet_poller is not None:
                # Share PR 8's pull: the supervisor reads heartbeat ages
                # from the poller's latest snapshot instead of its own
                # fabric read.
                supervisor.poller = fleet_poller
            if router is not None and fleet_poller is not None:
                # Same for the router: its replica views ride the
                # poller's snapshot instead of issuing their own pulls.
                router.poller = fleet_poller
            print(
                f"serve metrics endpoint: {metrics_server.url}",
                file=sys.stderr,
                flush=True,
            )
        handles = [
            client.submit(p, seed=seed + i, **sampling)
            for i, p in enumerate(prompts)
        ]
        if profile_s is not None:
            # Capture while the submitted prompts decode on the loop
            # thread (the RPC itself only sleeps replica-side).
            prof = client.profile(float(profile_s))
            print(
                "serve profile: "
                + (prof.get("dir", "") if prof.get("ok") else str(prof)),
                file=sys.stderr,
                flush=True,
            )
        outputs = []
        for p, h in zip(prompts, handles):
            toks = list(client.stream_handle(h))
            outputs.append(
                {"request_id": h.request_id, "tokens": p + toks}
            )
            print(
                h.request_id
                + "\t"
                + ",".join(str(t) for t in p + toks)
            )
        if trace_out:
            trace_json = client.export_trace(n=len(prompts))
            with open(trace_out, "w") as f:
                _json.dump(trace_json, f)
            print(f"serve trace written: {trace_out}", file=sys.stderr,
                  flush=True)
        stats = client.stats()
        print(_json.dumps({"serve_stats": stats}))
        return {"outputs": outputs, "stats": stats}
    finally:
        if autoscaler is not None:
            autoscaler.stop()  # before shutdown: no scaling mid-teardown
        if supervisor is not None:
            supervisor.stop()  # before shutdown: no restarts mid-teardown
        if watchtower is not None:
            watchtower.stop()  # before the poller: its snapshot feed
        if fleet_poller is not None:
            fleet_poller.stop()
        if metrics_server is not None:
            metrics_server.close()
        client.shutdown()


def run_doctor(config: Dict[str, Any]) -> Dict[str, Any]:
    """``doctor``: interrogate a live serve obs endpoint.

    Usage: ``rlt doctor <host:port> [--doctor.bundle DIR]`` where
    ``<host:port>`` is the ``--serve.metrics_port`` endpoint (or any
    :class:`obs.MetricsHTTPServer` with a health collector). Prints the
    health report — overall verdict, per-component verdicts with
    reasons, per-replica sections — and, with ``--doctor.bundle``,
    pulls a flight-recorder bundle over ``/debug/bundle`` into DIR.
    Returns ``{"status": <http code>, "report": ..., "bundle": ...}``;
    status 200 means healthy, 503 carries the reason.
    """
    import json as _json
    import urllib.error
    import urllib.request

    cfg = dict(config.pop("doctor", None) or {})
    addr = cfg.pop("addr", None) or cfg.pop("url", None)
    bundle_dir = cfg.pop("bundle", None)
    timeout = float(cfg.pop("timeout_s", 30.0))
    if cfg:
        raise ValueError(f"unknown doctor options: {sorted(cfg)}")
    if not addr:
        raise ValueError(
            "doctor requires the serve obs endpoint: rlt doctor <host:port>"
        )
    base = str(addr) if "://" in str(addr) else f"http://{addr}"
    base = base.rstrip("/")

    def fetch(path: str):
        try:
            resp = urllib.request.urlopen(base + path, timeout=timeout)
            return resp.status, resp.read()
        except urllib.error.HTTPError as exc:
            # 503 is an ANSWER (unhealthy + JSON reason), not a failure.
            return exc.code, exc.read()

    status, body = fetch("/healthz")
    try:
        report = _json.loads(body)
    except ValueError:
        report = {
            "raw": body.decode(errors="replace").strip(),
            "healthy": status == 200,
        }

    def show(rep: Dict[str, Any], indent: str = "") -> None:
        verdict = rep.get("verdict", "healthy" if status == 200 else "?")
        print(f"{indent}overall: {verdict}")
        for name, comp in sorted((rep.get("components") or {}).items()):
            reasons = "; ".join(comp.get("reasons") or [])
            line = f"{indent}  {name:<28} {comp.get('verdict', '?')}"
            print(line + (f"   {reasons}" if reasons else ""))

    print(f"doctor {base} -> HTTP {status}")
    show(report)
    for i, rep in enumerate(report.get("replicas") or []):
        print(f"replica {i}:")
        show(rep, indent="  ")

    out: Dict[str, Any] = {"status": status, "report": report}
    if bundle_dir:
        b_status, b_body = fetch("/debug/bundle")
        if b_status != 200:
            raise RuntimeError(
                f"bundle pull failed: HTTP {b_status} "
                f"{b_body[:200].decode(errors='replace')}"
            )
        manifest = _json.loads(b_body)
        files = manifest.get("files_content") or {}
        import os as _os

        dest = _os.path.join(
            str(bundle_dir),
            _os.path.basename(manifest.get("dir", "bundle")),
        )
        _os.makedirs(dest, exist_ok=True)
        for name, content in files.items():
            with open(_os.path.join(dest, name), "w") as f:
                f.write(content)
        print(f"bundle pulled: {dest} ({len(files)} files)")
        out["bundle"] = dest
    return out


def run_replay(config: Dict[str, Any]) -> Dict[str, Any]:
    """``replay``: re-drive a captured workload journal bit-exactly.

    Usage: ``rlt replay <journal> [--replay.*]`` where ``<journal>`` is
    a journal JSONL file (a doctor bundle's ``journal.jsonl``, a saved
    ``/journal`` body, or a ``--serve.journal`` spill file/directory).
    The engine + scheduler rebuild from the journal's recorded
    config/checkpoint header and the recorded request stream is
    re-driven; per-request token output must match the recorded
    outcomes bit-exactly, with a first-divergence report (request id,
    token index, expected vs got) on mismatch. Exit status: 0 exact,
    1 diverged (the scriptable regression probe).

    Options (``--replay.<key>``):
      ckpt: checkpoint path override (benchmark a DIFFERENT engine
        build against the captured trace; default: the recorded path).
      config: model-config dict override (with ckpt overrides).
      timing: "virtual" (default — as fast as the engine goes, recorded
        cancels fire deterministically at their recorded token counts)
        or "wall" (recorded inter-arrivals honored; emits a perf
        comparison — tokens/s, TTFT p50/p95, goodput — against the
        recorded run's ledger, so the trace doubles as a benchmark).
      replica: which replica's stream to replay from a replica-tagged
        multi-replica journal (default: lowest tag).
      router: re-drive the capture through the ROUTER instead of the
        single-engine path — every replica stream merges, every submit
        routes through a Router.plan rebuilt from the header's recorded
        policy knobs, and the verdict additionally asserts zero lost
        (shedding is forced off: a replay must place every request).
      speed: wall-pace multiplier for --replay.router (1.0 = recorded
        pace, 10.0 = ten times faster; truncations stay deterministic
        so exactness holds at any speed). Router mode only.
      max_steps: scheduler-step budget (default 200000).
      out: also write the verdict JSON to this path.
    """
    import json as _json

    from ray_lightning_tpu.obs.journal import (
        load_journal,
        load_journal_streams,
        replay_journal,
        replay_journal_router,
    )

    cfg = dict(config.pop("replay", None) or {})
    journal_path = cfg.pop("journal", None)
    ckpt = cfg.pop("ckpt", None)
    model_cfg = cfg.pop("config", None)
    timing = str(cfg.pop("timing", "virtual"))
    replica = cfg.pop("replica", None)
    use_router = bool(cfg.pop("router", False))
    speed = float(cfg.pop("speed", 1.0))
    max_steps = int(cfg.pop("max_steps", 200_000))
    out_path = cfg.pop("out", None)
    if cfg:
        raise ValueError(f"unknown replay options: {sorted(cfg)}")
    if not journal_path:
        raise ValueError(
            "replay requires a journal path: rlt replay <journal.jsonl>"
        )
    if speed <= 0:
        raise ValueError(
            f"--replay.speed {speed} out of range: need > 0 "
            "(wall-pace multiplier; 1.0 = recorded pace)"
        )
    if speed != 1.0 and not use_router:
        raise ValueError(
            "--replay.speed only applies to --replay.router (the "
            "single-engine path paces with --replay.timing)"
        )
    if use_router:
        result = replay_journal_router(
            load_journal_streams(str(journal_path)),
            ckpt_path=None if ckpt is None else str(ckpt),
            model_config=(
                None if model_cfg is None else dict(model_cfg)
            ),
            speed=speed,
            max_steps=max_steps,
        )
        verdict = "EXACT" if result["exact"] else "DIVERGED"
        print(
            f"router replay {journal_path} -> {verdict}: "
            f"{result['compared']}/{result['requests']} requests "
            f"compared over {result['streams']} stream(s), "
            f"{result['planned']} planned, {result['lost']} lost, "
            f"{result['tokens_compared']} tokens, "
            f"speed={result['speed']}x",
            file=sys.stderr,
            flush=True,
        )
    else:
        journal = load_journal(
            str(journal_path),
            replica=None if replica is None else int(replica),
        )
        result = replay_journal(
            journal,
            ckpt_path=None if ckpt is None else str(ckpt),
            model_config=None if model_cfg is None else dict(model_cfg),
            timing=timing,
            max_steps=max_steps,
        )
        verdict = "EXACT" if result["exact"] else "DIVERGED"
        print(
            f"replay {journal_path} -> {verdict}: "
            f"{result['compared']}/{result['requests']} requests "
            f"compared, {result['tokens_compared']} tokens, "
            f"{result['open']} open at capture, "
            f"timing={result['timing']}",
            file=sys.stderr,
            flush=True,
        )
    div = result.get("divergence")
    if div is not None:
        print(
            f"first divergence: request {div['request_id']} token "
            f"{div['token_index']}: expected {div['expected']} got "
            f"{div['got']}",
            file=sys.stderr,
            flush=True,
        )
    perf = result.get("perf")
    if perf is not None:
        rec, rep = perf["recorded"], perf["replayed"]
        print(
            "perf recorded vs replayed: "
            f"tok/s {rec['tokens_per_sec']} -> {rep['tokens_per_sec']}  "
            f"ttft_p50 {rec['ttft_p50_s']} -> {rep['ttft_p50_s']}  "
            f"ttft_p95 {rec['ttft_p95_s']} -> {rep['ttft_p95_s']}  "
            f"goodput {rec['goodput_tokens_per_device_s']} -> "
            f"{rep['goodput_tokens_per_device_s']}",
            file=sys.stderr,
            flush=True,
        )
        # Phase-level diff (wall mode, when the capture carried the
        # anatomy ledgers): recorded vs replayed p95 per phase —
        # pinpoints WHICH phase the incident lost its time to.
        ph = perf.get("phases") or {}
        rec_p, rep_p = ph.get("recorded") or {}, ph.get("replayed") or {}
        if rec_p or rep_p:
            from ray_lightning_tpu.obs.anatomy import PHASES

            def _p95(block: Dict[str, Any], phase: str) -> str:
                row = block.get(phase)
                return f"{row['p95_s']:g}" if row else "-"

            cells = [
                f"{phase} {_p95(rec_p, phase)}->{_p95(rep_p, phase)}"
                for phase in PHASES
                if phase in rec_p or phase in rep_p
            ]
            if cells:
                print(
                    "phase p95 recorded vs replayed: "
                    + "  ".join(cells),
                    file=sys.stderr,
                    flush=True,
                )
    if out_path:
        with open(str(out_path), "w") as f:
            _json.dump(result, f, indent=2, default=str)
    print(_json.dumps(
        {k: v for k, v in result.items() if k != "rows"}, default=str
    ))
    return result


def _fmt_cell(v: Any, width: int, digits: int = 3) -> str:
    if v is None:
        s = "-"
    elif isinstance(v, float):
        s = f"{v:.{digits}f}"
    else:
        s = str(v)
    return s.rjust(width)


def render_fleet(payload: Dict[str, Any]) -> str:
    """One terminal frame of the fleet dashboard from a ``/fleet``
    payload (latest snapshot + history ring): a header line, one row
    per replica, and the fleet roll-up. Plain text — the same string
    pipes cleanly and paints a tty frame."""
    import datetime as _dt

    latest = payload.get("latest") or {}
    rows = latest.get("replicas") or []
    fleet = latest.get("fleet") or {}
    ts = latest.get("ts")
    when = (
        _dt.datetime.fromtimestamp(ts).strftime("%H:%M:%S")
        if ts else "-"
    )
    history = payload.get("history") or []
    out = [
        f"rlt top — {len(rows)} replica(s) @ {when}  "
        f"(polls={payload.get('polls', 0)} "
        f"errors={payload.get('errors', 0)} "
        f"history={len(history)})",
        (
            f"{'replica':>7} {'health':>9} {'role':>7} {'queue':>5} "
            f"{'slots':>7} "
            f"{'tok/s':>9} {'ttft_p50':>9} {'ttft_p95':>9} "
            f"{'accept':>7} {'hit':>6} {'hit d/h/k':>14} "
            f"{'pages f/r/a':>12} {'fetch/ship':>11} {'store h/m/w':>12} "
            f"{'pb d/r':>9} {'goodput':>9} {'weight':>7} {'phase':>13}"
        ),
    ]
    # Router weights keyed by replica (absent without a router).
    router_block = payload.get("router") or {}
    weights = {
        w.get("replica"): w.get("weight")
        for w in router_block.get("replicas") or []
    }
    for r in rows:
        # Tiered prefix cache: fraction of block probes each tier served
        # (device/host/disk) — "-" when the replica runs no tiers.
        th = r.get("prefix_tier_hit_rate") or {}
        tier_cell = (
            "{:.2f}/{:.2f}/{:.2f}".format(
                th.get("device", 0.0), th.get("host", 0.0),
                th.get("disk", 0.0),
            )
            if th
            else None
        )
        # Paged KV pool: free/resident/aliased pages — "-" on dense
        # replicas.
        kvp = r.get("kv_pages") or {}
        page_cell = (
            "{}/{}/{}".format(
                kvp.get("free", 0), kvp.get("resident", 0),
                kvp.get("aliased", 0),
            )
            if kvp
            else None
        )
        # Fleet KV plane: cross-replica fetches / ships — "-" on
        # fleets without the plane.
        kvf = r.get("kvfleet") or {}
        kvf_cell = (
            "{}/{}".format(kvf.get("fetches", 0), kvf.get("ships", 0))
            if kvf
            else None
        )
        # Persistent object-store tier: hits/misses/writes — "-" when
        # the replica runs without a store.
        kvs = r.get("kvstore") or {}
        kvs_cell = (
            "{}/{}/{}".format(
                kvs.get("hits", 0), kvs.get("misses", 0),
                kvs.get("writes", 0),
            )
            if kvs
            else None
        )
        # Fused dispatches: piggyback dispatches / chunk rows that rode
        # decode folds — "-" when piggybacking is off.
        pb = r.get("piggyback") or {}
        pb_cell = (
            "{}/{}".format(
                pb.get("dispatches", 0), pb.get("chunk_rows", 0)
            )
            if pb
            else None
        )
        # Anatomy hot spot: the replica's single largest p95 phase —
        # "-" when the phase ledger is off or idle.
        rph = r.get("phases") or {}
        phase_cell = (
            f"{rph['hot_phase']}"
            if rph.get("hot_phase")
            else None
        )
        out.append(
            f"{_fmt_cell(r.get('replica'), 7)} "
            f"{_fmt_cell(r.get('health'), 9)} "
            f"{_fmt_cell(r.get('role', 'mixed'), 7)} "
            f"{_fmt_cell(r.get('queue_depth'), 5)} "
            + _fmt_cell(
                f"{r.get('active_slots', 0)}/{r.get('num_slots', 0)}", 7
            )
            + f" {_fmt_cell(r.get('tokens_per_sec'), 9, 1)} "
            f"{_fmt_cell(r.get('ttft_p50_s'), 9, 4)} "
            f"{_fmt_cell(r.get('ttft_p95_s'), 9, 4)} "
            f"{_fmt_cell(r.get('spec_accept_rate'), 7, 2)} "
            f"{_fmt_cell(r.get('prefix_hit_rate'), 6, 2)} "
            f"{_fmt_cell(tier_cell, 14)} "
            f"{_fmt_cell(page_cell, 12)} "
            f"{_fmt_cell(kvf_cell, 11)} "
            f"{_fmt_cell(kvs_cell, 12)} "
            f"{_fmt_cell(pb_cell, 9)} "
            f"{_fmt_cell(r.get('goodput_tokens_per_device_s'), 9, 1)} "
            f"{_fmt_cell(weights.get(r.get('replica')), 7, 2)} "
            f"{_fmt_cell(phase_cell, 13)}"
        )
    if fleet:
        out.append(
            f"fleet: healthy={fleet.get('healthy', 0)}"
            f"/{fleet.get('replicas', 0)} "
            f"queue={fleet.get('queue_depth', 0)} "
            f"tok/s={fleet.get('tokens_per_sec', 0.0)} "
            f"goodput={fleet.get('goodput_tokens_per_device_s', 0.0)} "
            f"ttft_p95_worst={fleet.get('ttft_p95_s_worst')}"
        )
        # Anatomy decomposition: the fleet's hot phase (largest p95)
        # plus the per-phase p95 spread — only rendered once the phase
        # ledger has a window.
        fph = fleet.get("phases") or {}
        if fph.get("hot_phase"):
            spread = "  ".join(
                f"{p}={row['p95_s']:g}"
                for p, row in sorted(
                    (fph.get("by_phase") or {}).items(),
                    key=lambda kv: -kv[1]["p95_s"],
                )[:6]
            )
            out.append(
                f"phases: hot={fph['hot_phase']} "
                f"p95={fph['hot_phase_p95_s']:g}s  {spread}"
            )
        # Active SLO-breach attribution — the "where is the breach
        # coming from" line; absent while nothing is breaching.
        if fleet.get("breach_attribution"):
            out.append(f"why: {fleet['breach_attribution']}")
        # Fleet KV plane roll-up: only rendered once the plane moved
        # anything (a homogeneous isolated fleet stays clean).
        if fleet.get("kvfleet_fetches") or fleet.get("kvfleet_ships"):
            out.append(
                f"kvfleet: fetches={fleet.get('kvfleet_fetches', 0)} "
                f"timeouts={fleet.get('kvfleet_fetch_timeouts', 0)} "
                f"ships={fleet.get('kvfleet_ships', 0)}"
            )
        # Persistent store roll-up: only rendered once the store saw
        # traffic (a storeless fleet stays clean).
        if (fleet.get("kvstore_hits") or fleet.get("kvstore_misses")
                or fleet.get("kvstore_writes")):
            out.append(
                f"kvstore: hits={fleet.get('kvstore_hits', 0)} "
                f"misses={fleet.get('kvstore_misses', 0)} "
                f"writes={fleet.get('kvstore_writes', 0)} "
                f"write_errors={fleet.get('kvstore_write_errors', 0)} "
                f"evictions={fleet.get('kvstore_evictions', 0)}"
            )
    # Alert plane (when the watchtower is wired): firing count + names
    # worst-first — "all quiet" renders too, so the line's absence
    # means the watchtower is OFF, never that nothing is firing.
    alerts_block = payload.get("alerts")
    if alerts_block is not None:
        names = alerts_block.get("names") or []
        out.append(
            f"alerts: firing={alerts_block.get('firing', 0)}"
            + (" " + " ".join(names) if names else " (all quiet)")
        )
    # Recovery plane (when a FleetSupervisor is wired): one cell per
    # replica — state, lifetime restarts, pending attempts.
    sup = payload.get("supervisor") or []
    if sup:
        cells = []
        for s in sup:
            cell = f"r{s.get('replica')}={s.get('state')}"
            extras = []
            if s.get("restarts"):
                extras.append(f"restarts={s['restarts']}")
            if s.get("attempts"):
                extras.append(f"attempts={s['attempts']}")
            if extras:
                cell += "(" + ",".join(extras) + ")"
            cells.append(cell)
        out.append("supervisor: " + " ".join(cells))
    # Routing plane (when a Router is wired): decision totals + any
    # replicas currently excluded from the routable set.
    if router_block:
        parts = [
            f"routed={router_block.get('routed', 0)}",
            f"shed={router_block.get('shed', 0)}",
            f"affinity_entries={router_block.get('affinity_entries', 0)}",
        ]
        # Plan throughput: requests planned per µs of planning wall (the
        # control-plane speedometer) + the mean vectorized batch size.
        plan = router_block.get("plan") or {}
        if plan.get("requests"):
            parts.append(f"plan b/µs={plan.get('per_us', 0.0)}")
            parts.append(f"plan_batch={plan.get('mean_batch', 1.0)}")
        shards = (router_block.get("directory") or {}).get("shards")
        if shards and int(shards) > 1:
            parts.append(f"dir_shards={shards}")
        out_of_rotation = [
            f"r{w.get('replica')}"
            for w in router_block.get("replicas") or []
            if not w.get("routable", True)
        ]
        if out_of_rotation:
            parts.append("excluded=" + ",".join(out_of_rotation))
        out.append("router: " + " ".join(parts))
    return "\n".join(out)


def run_top(config: Dict[str, Any]) -> Dict[str, Any]:
    """``top``: live terminal dashboard over a serve fleet endpoint.

    Usage: ``rlt top <host:port>`` where ``<host:port>`` is the
    ``--serve.metrics_port`` endpoint (its ``/fleet`` route feeds the
    dashboard). On a tty it repaints every ``--top.interval_s`` (default
    2s) until Ctrl-C; piped (or with ``--top.plain true``) it prints
    one plain-text frame and exits, so ``rlt top addr | grep unhealthy``
    works in scripts. ``--top.iterations N`` bounds the refresh loop.
    ``--top.once`` forces exactly one frame regardless of tty, and
    ``--top.json`` prints the raw ``/fleet`` payload (the latest
    FleetSnapshot + history ring) as ONE JSON line instead of the
    rendered frame — the machine-readable form for scripts/CI
    (``rlt top addr --top.once --top.json | jq .latest.fleet``).
    Returns ``{"snapshot": <last /fleet payload>}``.
    """
    import json as _json
    import time as _time
    import urllib.request

    cfg = dict(config.pop("top", None) or {})
    addr = cfg.pop("addr", None) or cfg.pop("url", None)
    interval_s = float(cfg.pop("interval_s", 2.0))
    iterations = cfg.pop("iterations", None)
    plain = bool(cfg.pop("plain", False))
    once = bool(cfg.pop("once", False))
    json_out = bool(cfg.pop("json", False))
    timeout = float(cfg.pop("timeout_s", 10.0))
    if cfg:
        raise ValueError(f"unknown top options: {sorted(cfg)}")
    if not addr:
        raise ValueError(
            "top requires the serve obs endpoint: rlt top <host:port>"
        )
    base = str(addr) if "://" in str(addr) else f"http://{addr}"
    base = base.rstrip("/")
    plain = plain or json_out or not sys.stdout.isatty()
    if once:
        iterations = 1
    if iterations is None:
        iterations = 1 if plain else 0  # 0 = refresh until Ctrl-C
    iterations = int(iterations)
    count = 0
    last: Optional[Dict[str, Any]] = None
    try:
        while True:
            body = urllib.request.urlopen(
                base + "/fleet", timeout=timeout
            ).read()
            last = _json.loads(body)
            if json_out:
                # ONE machine-readable line per poll: the raw /fleet
                # payload (latest FleetSnapshot + history), no framing.
                print(_json.dumps(last, default=str))
                count += 1
                if iterations and count >= iterations:
                    break
                _time.sleep(interval_s)
                continue
            frame = render_fleet(last)
            if plain:
                print(frame)
            else:
                # Clear + home, one repaint per poll — a dumb-terminal
                # dashboard, no curses dependency.
                sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
                sys.stdout.flush()
            count += 1
            if iterations and count >= iterations:
                break
            _time.sleep(interval_s)
    except KeyboardInterrupt:
        pass
    return {"snapshot": last}


def run_why(config: Dict[str, Any]) -> Dict[str, Any]:
    """``why``: where one request's latency went — its phase ledger.

    Usage: ``rlt why <target> <request_id>`` where ``<target>`` is
    either a live serve obs endpoint (``host:port`` — the ledger is
    assembled from every process's tracer ring via ``/why?id=``, full
    cross-process timeline) or a captured journal JSONL path (offline
    autopsy — the outcome record's compact scheduler-local phases, no
    live fleet needed). Renders the timeline: per-phase durations, the
    replica/process each phase ran on, the outcome chain, and the
    coverage line (phases + unaccounted == observed, exactly).
    ``--why.json true`` prints the raw ledger as one JSON line instead.
    Exit status: 0 when the request was found, 1 when no ring/journal
    knows the id. Returns the ledger dict.
    """
    import json as _json
    import os as _os
    import urllib.error
    import urllib.request
    from urllib.parse import quote

    from ray_lightning_tpu.obs.anatomy import (
        ledger_from_phase_map,
        render_anatomy,
    )

    cfg = dict(config.pop("why", None) or {})
    target = (
        cfg.pop("target", None) or cfg.pop("addr", None)
        or cfg.pop("journal", None)
    )
    rid = cfg.pop("id", None) or cfg.pop("request_id", None)
    json_out = bool(cfg.pop("json", False))
    timeout = float(cfg.pop("timeout_s", 10.0))
    if cfg:
        raise ValueError(f"unknown why options: {sorted(cfg)}")
    if not target or rid is None:
        raise ValueError(
            "why requires a target and a request id: "
            "rlt why <host:port|journal.jsonl> <request_id>"
        )
    rid = str(rid)
    if _os.path.exists(str(target)):
        # Offline journal autopsy: the newest outcome record's compact
        # phase ledger (scheduler-local phases; no live fleet).
        from ray_lightning_tpu.obs.journal import load_journal

        entries = load_journal(str(target)).get("entries") or []
        outcome = next(
            (
                e for e in reversed(entries)
                if e.get("kind") == "outcome"
                and str(e.get("request_id")) == rid
            ),
            None,
        )
        if outcome is None:
            ledger: Dict[str, Any] = {"request_id": rid, "found": False}
        else:
            ledger = ledger_from_phase_map(
                rid, outcome.get("phases") or {},
                outcome=str(outcome.get("outcome", "unknown")),
            )
    else:
        base = (
            str(target) if "://" in str(target)
            else f"http://{target}"
        )
        url = base.rstrip("/") + "/why?id=" + quote(rid)
        try:
            body = urllib.request.urlopen(url, timeout=timeout).read()
        except urllib.error.HTTPError as exc:
            if exc.code != 404:
                raise
            body = exc.read()  # found:false rides the 404 body
        except urllib.error.URLError as exc:
            raise ValueError(
                f"why target {target!r} is neither a readable journal "
                f"file nor a reachable obs endpoint: {exc.reason}"
            ) from exc
        ledger = _json.loads(body)
    if json_out:
        print(_json.dumps(ledger, default=str))
    else:
        print(render_anatomy(ledger))
    return ledger


#: Unicode block ramp for the `rlt plot` sparkline (8 heights).
_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def render_sparkline(
    points: List[Any], width: int = 60
) -> str:
    """One-line terminal sparkline over ``[(ts, value), ...]`` points.

    The window is resampled to ``width`` columns (last-value-wins per
    column, gaps rendered as spaces) and values are mapped onto the
    eight-block ramp between the window's min and max. A flat series
    renders as a run of the lowest block — still visibly "present".
    """
    vals = [float(v) for _, v in points]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = hi - lo
    n = len(vals)
    cols: List[str] = []
    if n <= width:
        take = vals
    else:
        # Downsample: each column shows the max of its slice (spikes
        # must survive resampling — that's what the plot is FOR).
        take = [
            max(vals[int(i * n / width): max(int(i * n / width) + 1,
                                             int((i + 1) * n / width))])
            for i in range(width)
        ]
    for v in take:
        idx = 0 if span <= 0 else int((v - lo) / span * 7.999)
        cols.append(_SPARK_BLOCKS[idx])
    return "".join(cols)


def run_plot(config: Dict[str, Any]) -> Dict[str, Any]:
    """``plot``: terminal sparkline of one retained watchtower series.

    Usage: ``rlt plot <host:port> <series>`` against a serve obs
    endpoint running the watchtower (``/query`` route). Renders the
    series name, the covered window, min/mean/max/last, and a unicode
    sparkline. Options (``--plot.*``): ``since_s`` (window, default the
    finest rung that has data), ``step_s`` (bucket width — picks the
    matching TSDB rung), ``width`` (sparkline columns, default 60),
    ``json`` (raw ``/query`` payload as one JSON line). Exit status:
    0 when the series exists, 1 for an unknown series (the 404 body's
    ``available`` sample is printed so you can fix the name).
    """
    import json as _json
    import urllib.error
    import urllib.request
    from urllib.parse import quote

    cfg = dict(config.pop("plot", None) or {})
    target = cfg.pop("addr", None) or cfg.pop("target", None)
    series = cfg.pop("series", None)
    since_s = cfg.pop("since_s", None)
    step_s = cfg.pop("step_s", None)
    width = int(cfg.pop("width", 60))
    json_out = bool(cfg.pop("json", False))
    timeout = float(cfg.pop("timeout_s", 10.0))
    if cfg:
        raise ValueError(f"unknown plot options: {sorted(cfg)}")
    if not target or not series:
        raise ValueError(
            "plot requires a target and a series name: "
            "rlt plot <host:port> <series>"
        )
    base = str(target) if "://" in str(target) else f"http://{target}"
    url = base.rstrip("/") + "/query?series=" + quote(str(series))
    if since_s is not None:
        url += f"&since={float(since_s)}"
    if step_s is not None:
        url += f"&step={float(step_s)}"
    try:
        body = urllib.request.urlopen(url, timeout=timeout).read()
    except urllib.error.HTTPError as exc:
        if exc.code != 404:
            raise
        body = exc.read()  # found:false + available sample ride the 404
    except urllib.error.URLError as exc:
        raise ValueError(
            f"plot target {target!r} is not a reachable obs endpoint "
            f"(needs --serve.metrics_port + watchtower): {exc.reason}"
        ) from exc
    result = _json.loads(body)
    if json_out:
        print(_json.dumps(result, default=str))
        return result
    if not result.get("found"):
        available = result.get("available") or []
        print(f"series {series!r} unknown")
        if available:
            print("available: " + " ".join(available))
        return result
    points = result.get("points") or []
    vals = [float(v) for _, v in points]
    header = f"{series}  step={result.get('step_s')}s  n={len(points)}"
    if vals:
        header += (
            f"  min={min(vals):.4g} mean={sum(vals) / len(vals):.4g}"
            f" max={max(vals):.4g} last={vals[-1]:.4g}"
        )
    print(header)
    print(render_sparkline(points, width=width) or "(no samples)")
    return result


def render_alerts(payload: Dict[str, Any]) -> str:
    """Human rendering of the ``/alerts`` payload: one row per rule
    (state, severity, value vs threshold, firing duration), firing
    rules first, then the canary line when the lane is running."""
    alerts = payload.get("alerts") or {}
    states: Dict[str, Any] = alerts.get("states") or {}
    rules = {r["name"]: r for r in alerts.get("rules") or []}
    out: List[str] = []
    firing = alerts.get("firing") or []
    firing_names = [
        f.get("rule", "?") if isinstance(f, dict) else str(f)
        for f in firing
    ]
    out.append(
        f"alerts: firing={len(firing)}"
        + ((" " + " ".join(firing_names)) if firing_names else
           " (all quiet)")
    )
    order = sorted(
        states,
        key=lambda nm: (states[nm].get("state") != "firing", nm),
    )
    for nm in order:
        st = states[nm]
        rule = rules.get(nm, {})
        line = (
            f"  {st.get('state', '?'):>7}  {nm}"
            f" [{rule.get('severity', '?')}/{rule.get('kind', '?')}]"
        )
        if st.get("value") is not None:
            line += f" value={st['value']:.4g}"
        if st.get("detail"):
            line += f" ({st['detail']})"
        out.append(line)
    canary = payload.get("canary")
    if canary:
        last = canary.get("last") or {}
        out.append(
            "canary: probes={} exact={} ttft_s={} decode_tok_s={}".format(
                canary.get("probes", 0),
                last.get("exact", "n/a"),
                last.get("ttft_s", "n/a"),
                last.get("decode_tokens_per_s", "n/a"),
            )
        )
    return "\n".join(out)


def run_alerts(config: Dict[str, Any]) -> Dict[str, Any]:
    """``alerts``: the watchtower's alert state — and a live tail.

    Usage: ``rlt alerts <host:port>`` against a serve obs endpoint
    running the watchtower. One-shot mode renders every rule's state
    (firing first), values/details, and the canary lane summary.
    ``--follow`` (or ``--alerts.follow true``) switches to a live tail
    of ``alert_firing``/``alert_resolved``/``canary_*`` events via the
    ``/events?since=<seq>`` cursor — each poll fetches only events
    newer than the last seen sequence (deduped per replica ring, since
    sequences are per-ring monotonic, not global). Options:
    ``interval_s`` (follow poll period, default 2), ``iterations``
    (stop after N polls; 0 = forever), ``json`` (raw payload / JSONL
    passthrough). Exit status: 0 quiet, 1 when any rule is firing.
    """
    import json as _json
    import time as _time
    import urllib.error
    import urllib.request

    cfg = dict(config.pop("alerts", None) or {})
    target = cfg.pop("addr", None) or cfg.pop("target", None)
    follow = bool(cfg.pop("follow", False))
    interval_s = float(cfg.pop("interval_s", 2.0))
    iterations = int(cfg.pop("iterations", 0))
    json_out = bool(cfg.pop("json", False))
    timeout = float(cfg.pop("timeout_s", 10.0))
    if cfg:
        raise ValueError(f"unknown alerts options: {sorted(cfg)}")
    if not target:
        raise ValueError(
            "alerts requires a target: rlt alerts <host:port> [--follow]"
        )
    base = str(target) if "://" in str(target) else f"http://{target}"

    def _fetch_payload() -> Dict[str, Any]:
        url = base.rstrip("/") + "/alerts"
        try:
            body = urllib.request.urlopen(url, timeout=timeout).read()
        except urllib.error.URLError as exc:
            raise ValueError(
                f"alerts target {target!r} is not a reachable obs "
                f"endpoint (needs --serve.metrics_port + watchtower): "
                f"{getattr(exc, 'reason', exc)}"
            ) from exc
        return _json.loads(body)

    payload = _fetch_payload()
    if not follow:
        if json_out:
            print(_json.dumps(payload, default=str))
        else:
            print(render_alerts(payload))
        return payload

    # Live tail: poll /events with the ?since= cursor. Sequences are
    # per-RING monotonic (each replica's EventLog counts its own), so
    # the cursor is kept per (replica, ) origin via a seen-set keyed on
    # (replica, seq) with the max seq per origin driving ?since= — one
    # shared cursor at the MIN of the per-origin maxima would refetch,
    # so dedup client-side and advance since only when safe (single
    # origin: plain max).
    seen: set = set()
    cursor = 0
    count = 0
    try:
        while True:
            url = base.rstrip("/") + (
                "/events?subsystem=watchtower&since=" + str(cursor)
            )
            try:
                body = urllib.request.urlopen(url, timeout=timeout).read()
            except urllib.error.URLError:
                body = b""
            new_max = cursor
            for ln in body.decode().splitlines():
                if not ln.strip():
                    continue
                try:
                    ev = _json.loads(ln)
                except ValueError:
                    continue
                key = (ev.get("replica"), ev.get("seq"))
                if key in seen:
                    continue
                seen.add(key)
                if isinstance(ev.get("seq"), int):
                    new_max = max(new_max, ev["seq"])
                if json_out:
                    print(_json.dumps(ev, default=str))
                else:
                    print(
                        "{} {:>5} {} {}".format(
                            _time.strftime(
                                "%H:%M:%S",
                                _time.localtime(float(ev.get("ts", 0))),
                            ),
                            ev.get("level", "?"),
                            ev.get("name", "?"),
                            " ".join(
                                f"{k}={v}" for k, v in sorted(ev.items())
                                if k not in (
                                    "ts", "level", "subsystem", "name",
                                    "seq",
                                )
                            ),
                        )
                    )
                sys.stdout.flush()
            cursor = new_max
            count += 1
            if iterations and count >= iterations:
                break
            _time.sleep(interval_s)
    except KeyboardInterrupt:
        pass
    payload = _fetch_payload()
    return payload


def run_tokenize(config: Dict[str, Any]) -> Dict[str, Any]:
    """``tokenize``: train (or load) a ByteBPETokenizer and optionally
    encode the corpus into a pretraining shard.

    Config section (YAML ``tokenize:`` or ``--tokenize.*`` flags):
      input: text file path or list of paths (each non-empty LINE is one
        document — merges never span documents)
      vocab_size: target vocab (default 512)
      out: tokenizer JSON path (default tokenizer.json)
      tokenizer: existing tokenizer JSON to reuse instead of training
      encode_to: token-bin shard path; when set, the corpus is encoded
        and written for TokenBinDataset
    Prints one JSON summary line on stdout.
    """
    import json as _json

    from ray_lightning_tpu.tokenizer import ByteBPETokenizer

    cfg = dict(config.get("tokenize") or {})
    inputs = cfg.get("input")
    if isinstance(inputs, str):
        inputs = [inputs]
    if not inputs:
        raise ValueError("tokenize needs tokenize.input (text file path[s])")
    docs: List[str] = []
    for path in inputs:
        with open(path, "r", encoding="utf-8") as f:
            docs.extend(line for line in (ln.strip("\n") for ln in f) if line)
    if not docs:
        raise ValueError(f"no non-empty lines in {inputs}")

    existing = cfg.get("tokenizer")
    if existing:
        tok = ByteBPETokenizer.load(str(existing))
    else:
        tok = ByteBPETokenizer.train(docs, vocab_size=int(cfg.get("vocab_size", 512)))
    out_path = str(cfg.get("out", "tokenizer.json"))
    if not existing:
        tok.save(out_path)

    summary: Dict[str, Any] = {
        "vocab_size": tok.vocab_size,
        "documents": len(docs),
        "tokenizer": str(existing) if existing else out_path,
    }
    encode_to = cfg.get("encode_to")
    if encode_to:
        from ray_lightning_tpu.trainer.data import write_token_bin

        ids = tok.encode_corpus(docs)
        shard = write_token_bin(str(encode_to), ids)
        summary["shard"] = shard
        summary["n_tokens"] = int(ids.size)
        summary["bytes_per_token"] = round(
            sum(len(d.encode()) for d in docs) / max(1, ids.size), 3
        )
    print(_json.dumps(summary))
    return summary


def main(argv: Optional[List[str]] = None) -> Any:
    subcommand, config = parse_args(argv)
    fabric_cfg = config.pop("fabric", None) or {}
    if fabric_cfg:
        from ray_lightning_tpu import fabric

        fabric.init(**fabric_cfg)
    if subcommand == "tokenize":
        return run_tokenize(config)
    if subcommand == "convert-hf":
        return run_convert_hf(config)
    if subcommand == "generate":
        return run_generate(config)
    if subcommand == "serve":
        return run_serve(config)
    if subcommand == "doctor":
        return run_doctor(config)
    if subcommand == "top":
        return run_top(config)
    if subcommand == "replay":
        return run_replay(config)
    if subcommand == "why":
        return run_why(config)
    if subcommand == "plot":
        return run_plot(config)
    if subcommand == "alerts":
        return run_alerts(config)
    trainer, model, datamodule = build(config)
    fn = getattr(trainer, subcommand)
    if datamodule is not None:
        return fn(model, datamodule=datamodule)
    return fn(model)


def cli_entry(argv: Optional[List[str]] = None) -> Any:
    """Actual command-line entrypoint (console script / ``python -m``).

    Places the persistent compile cache (``utils.compile_cache``) before
    any worker is spawned, so every process of the run shares it.
    Programmatic callers use :func:`main`, which leaves the environment
    alone.
    """
    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    out = main(argv)
    args = sys.argv[1:] if argv is None else argv
    if args and args[0] == "doctor":
        # The EXIT STATUS is doctor's contract (scriptable health
        # probe): 0 healthy, 1 unhealthy.
        return 0 if out.get("status") == 200 else 1
    if args and args[0] == "replay":
        # Replay's contract mirrors doctor: 0 bit-exact, 1 diverged —
        # `rlt replay journal.jsonl && deploy` is the regression gate.
        return 0 if out.get("exact") else 1
    if args and args[0] == "why":
        # 0 when some ring/journal knew the request, 1 when nothing did.
        return 0 if out.get("found") else 1
    if args and args[0] == "plot":
        # 0 when the series exists in the TSDB, 1 for an unknown name.
        return 0 if out.get("found") else 1
    if args and args[0] == "alerts":
        # 0 all quiet, 1 when any rule is firing — `rlt alerts $ADDR
        # && deploy` gates a rollout on the watchtower's verdict.
        firing = (out.get("alerts") or {}).get("firing") or []
        return 1 if firing else 0
    # The console wrapper sys.exit()s our return value; any other
    # command's result dict is already on stdout, and a truthy
    # sys.exit(dict) would dump it to stderr and exit 1 — a successful
    # `rlt serve` must exit 0.
    return 0


if __name__ == "__main__":
    # Mirror the console-script wrapper (which sys.exit()s the return
    # value): `python -m ray_lightning_tpu.cli doctor|replay ...` must
    # carry the same exit-status contract as `rlt doctor|replay`.
    sys.exit(cli_entry())
