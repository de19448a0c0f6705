"""Obs artifact snapshot: scrape a live metrics endpoint + export a trace.

Spins an in-process ServeReplica over a tiny randomly-initialized GPT,
serves a handful of shared-prefix prompts through the chunked-prefill +
prefix-cache path, then:

- starts the obs HTTP endpoint and scrapes it over real HTTP (the same
  bytes Prometheus would ingest) into ``--out-metrics``;
- exports the requests' traces as Chrome trace-event JSON (opens in
  Perfetto) into ``--out-trace``;
- with ``--out-bundle DIR``: runs the real ``rlt doctor`` CLI against
  the live endpoint (health report over /healthz, flight-recorder
  bundle over /debug/bundle) and leaves the pulled bundle in DIR — the
  `doctor` manifest stage's artifact;
- with ``--out-journal PATH``: serves from a real (temp) checkpoint so
  the workload journal carries a replayable config/checkpoint header,
  saves the captured journal JSONL to PATH, then runs the real
  ``rlt replay`` CLI against it and writes the exactness verdict JSON
  to ``--out-replay`` — the `replay` manifest stage's artifact (a
  recorded serve smoke proven bit-exactly replayable on this host);
- prints a one-line JSON summary (span counts, prefix hit rate,
  compiles_since_init — which must be 0 — health verdict, bundle path)
  to stdout.

With ``--out-fleet`` (+ ``--out-stitched``) it runs the FLEET path
instead: a local fabric with TWO replica actors behind a ServeClient,
the driver-side fleet poller and obs endpoint exactly as ``rlt serve
--serve.metrics_port`` wires them, and archives one ``/fleet``
snapshot plus one stitched cross-process ``/traces`` export fetched
over real HTTP.
(Replica actors are pinned to CPU: the artifact records the
aggregation plane, not chip throughput.)

``--out-why PATH`` (fleet path only) additionally runs the real
``rlt why <addr> <request_id>`` CLI against the live endpoint for one
completed request and archives its rendered phase-ledger timeline
(the request anatomy wire path end to end: replica rings -> /why ->
rendered decomposition).

``--out-alerts PATH`` (fleet path only) additionally starts the
watchtower (retained TSDB + alert engine) on the driver, lets it
ingest a few fleet snapshots, and archives the ``/alerts`` payload
plus one ``/query`` series pull fetched over real HTTP as one JSON
file.

Runs on CPU: it records the wire paths, not chip throughput.
"""
import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time
import urllib.request


def fleet_main(args) -> None:
    """The fleet artifact: 2 replicas, one /fleet snapshot, one
    stitched cross-process trace, both over real HTTP."""
    import dataclasses

    import jax
    import numpy as np

    from ray_lightning_tpu import fabric
    from ray_lightning_tpu.cli import _serve_obs_server
    from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
    from ray_lightning_tpu.serve import start_replicas
    from ray_lightning_tpu.utils.state_stream import (
        state_stream_to_file,
        to_state_stream,
    )

    cfg = GPTConfig(
        vocab_size=257, n_layer=2, n_head=4, d_model=64, max_seq=128,
        attn_impl="reference",
    )
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tmp = tempfile.mkdtemp(prefix="rlt_fleet_")
    ckpt = os.path.join(tmp, "fleet.ckpt")
    state_stream_to_file(
        to_state_stream(
            {"params": params, "gpt_config": dataclasses.asdict(cfg)}
        ),
        ckpt,
    )
    if not fabric.is_initialized():
        fabric.init(num_cpus=4)
    client = start_replicas(
        2,
        ckpt_path=ckpt,
        num_slots=2,
        prefill_buckets=[16, 64],
        decode_fold=2,
        env={"JAX_PLATFORMS": "cpu"},
    )
    server = poller = watchtower = None
    try:
        g = np.random.default_rng(0)
        handles = [
            client.submit(
                g.integers(0, 257, size=12).tolist(),
                max_new_tokens=args.new_tokens,
            )
            for _ in range(args.requests)
        ]
        for h in handles:
            for _ in client.stream_handle(h, timeout_s=300.0):
                pass
        server, poller, watchtower = _serve_obs_server(
            client, 0, fleet=True, fleet_interval_s=0.2,
            alerts=bool(args.out_alerts),
        )
        poller.poll_now()  # at least one snapshot before the fetch
        if watchtower is not None:
            # A few manual ticks so the retained rings hold real fleet
            # samples and every alert rule has been evaluated before
            # the /alerts + /query fetches below.
            for _ in range(3):
                poller.poll_now()
                watchtower.tick()
                time.sleep(0.05)
        base = f"http://{server.host}:{server.port}"
        fleet_body = urllib.request.urlopen(
            base + "/fleet", timeout=30
        ).read()
        trace_body = urllib.request.urlopen(
            base + "/traces", timeout=30
        ).read()
        with open(args.out_fleet, "wb") as f:
            f.write(fleet_body)
        with open(args.out_stitched, "wb") as f:
            f.write(trace_body)
        why = None
        if args.out_why:
            # The real `rlt why` CLI over the live /why route: one
            # completed request's rendered phase-ledger timeline.
            from ray_lightning_tpu.cli import main as cli_main

            why_rid = handles[0].request_id
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                why = cli_main([
                    "why", f"{server.host}:{server.port}", why_rid,
                ])
            with open(args.out_why, "w") as f:
                f.write(buf.getvalue())
        alerts = None
        if args.out_alerts:
            # The watchtower plane over real HTTP: the /alerts payload
            # (rules/states/firing + retained-ring inventory) plus one
            # /query series pull — both archived in one JSON file.
            alerts_body = urllib.request.urlopen(
                base + "/alerts", timeout=30
            ).read()
            alerts = json.loads(alerts_body)
            query = json.loads(urllib.request.urlopen(
                base + "/query?series=fleet.replicas", timeout=30
            ).read())
            with open(args.out_alerts, "w") as f:
                json.dump({"alerts": alerts, "query": query}, f)
        fleet = json.loads(fleet_body)
        trace = json.loads(trace_body)
        procs = sorted(
            e["args"]["name"]
            for e in trace["traceEvents"]
            if e.get("name") == "process_name"
        )
        summary = {
            "requests": args.requests,
            "fleet_replicas": fleet["latest"]["fleet"]["replicas"],
            "fleet_goodput": fleet["latest"]["fleet"][
                "goodput_tokens_per_device_s"
            ],
            "history": len(fleet["history"]),
            "trace_processes": procs,
            "trace_events": len(trace["traceEvents"]),
            "out_fleet": args.out_fleet,
            "out_stitched": args.out_stitched,
        }
        if why is not None:
            summary["why_found"] = bool(why.get("found"))
            summary["why_coverage"] = why.get("coverage")
            summary["why_phases"] = sorted(why.get("totals") or {})
            summary["out_why"] = args.out_why
        if alerts is not None:
            summary["alert_rules"] = len(
                (alerts.get("alerts") or {}).get("rules") or []
            )
            summary["alerts_firing"] = (
                (alerts.get("alerts") or {}).get("firing") or []
            )
            summary["tsdb_series"] = (
                (alerts.get("tsdb") or {}).get("series")
            )
            summary["out_alerts"] = args.out_alerts
        print(json.dumps(summary))
    finally:
        if watchtower is not None:
            watchtower.stop()
        if poller is not None:
            poller.stop()
        if server is not None:
            server.close()
        client.shutdown()


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--out-metrics", default="/tmp/obs_metrics.prom")
    p.add_argument("--out-trace", default="/tmp/obs_trace.json")
    p.add_argument(
        "--out-bundle", default="",
        help="run `rlt doctor` against the live endpoint and pull a "
        "flight-recorder bundle into this directory",
    )
    p.add_argument(
        "--out-journal", default="",
        help="save the captured workload journal JSONL here and run the "
        "real `rlt replay` CLI against it (bit-exactness proof)",
    )
    p.add_argument(
        "--out-replay", default="/tmp/replay_verdict.json",
        help="where the replay verdict JSON lands (with --out-journal)",
    )
    p.add_argument(
        "--out-fleet", default="",
        help="run the 2-replica FLEET path instead and save the /fleet "
        "snapshot JSON here",
    )
    p.add_argument(
        "--out-stitched", default="/tmp/fleet_trace.json",
        help="where the fleet path saves the stitched /traces export",
    )
    p.add_argument(
        "--out-why", default="",
        help="(fleet path) run the real `rlt why` CLI against the live "
        "endpoint for one completed request and save its rendered "
        "phase-ledger timeline here",
    )
    p.add_argument(
        "--out-alerts", default="",
        help="(fleet path) start the watchtower, fetch the /alerts "
        "payload plus one /query series over real HTTP, and archive "
        "both as one JSON file here",
    )
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--new-tokens", type=int, default=16)
    args = p.parse_args()

    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if args.out_fleet:
        fleet_main(args)
        return

    import jax
    import numpy as np

    from ray_lightning_tpu import obs
    from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
    from ray_lightning_tpu.serve.server import ServeReplica

    cfg = GPTConfig(
        vocab_size=257, n_layer=2, n_head=4, d_model=64, max_seq=128,
        attn_impl="reference",
    )
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    rep_kwargs = dict(params=params, model_config=cfg)
    if args.out_journal:
        # The journal path serves from a REAL checkpoint so the journal
        # header carries a checkpoint identity `rlt replay` can rebuild
        # from — the production capture shape, not the test shortcut.
        import dataclasses

        from ray_lightning_tpu.utils.state_stream import (
            state_stream_to_file,
            to_state_stream,
        )

        ckpt = os.path.join(
            tempfile.mkdtemp(prefix="rlt_replay_"), "serve.ckpt"
        )
        state_stream_to_file(
            to_state_stream(
                {"params": params, "gpt_config": dataclasses.asdict(cfg)}
            ),
            ckpt,
        )
        rep_kwargs = dict(ckpt_path=ckpt)
    rep = ServeReplica(
        num_slots=4,
        prefill_chunk=16,
        prefix_blocks=16,
        prefix_block=16,
        decode_fold=4,
        max_prefills_per_step=2,
        watchdog_interval_s=0.25,
        blackbox_dir=args.out_bundle or None,
        **rep_kwargs,
    )
    try:
        g = np.random.default_rng(0)
        prefix = g.integers(0, 257, size=48).tolist()

        def submit_one():
            return rep.submit(
                prefix + g.integers(0, 257, size=8).tolist(),
                max_new_tokens=args.new_tokens,
            )

        deadline = time.monotonic() + 300

        def wait(rid):
            while not rep.result(rid, wait_s=1.0)["done"]:
                if time.monotonic() > deadline:
                    print("timeout waiting for decode", file=sys.stderr)
                    sys.exit(1)

        # First request completes alone so its prefix blocks are in the
        # pool before the rest arrive — the trace then shows both a cold
        # chunked prefill and genuine prefix_seed hits.
        first = submit_one()
        wait(first)
        rids = [first] + [submit_one() for _ in range(args.requests - 1)]
        for rid in rids[1:]:
            wait(rid)

        # Scrape over real HTTP — the artifact is what Prometheus sees.
        # The endpoint carries the full active surface (health + bundle)
        # so `rlt doctor` below exercises the real wire path.
        srv = obs.MetricsHTTPServer(
            collect_text=rep.metrics_text,
            collect_health=lambda: (
                rep.health()["healthy"], rep.health(),
            ),
            collect_bundle=lambda: rep.debug_dump(
                reason="doctor", pull=True
            ),
        ).start()
        doctor = None
        try:
            body = urllib.request.urlopen(srv.url, timeout=10).read()
            if args.out_bundle:
                from ray_lightning_tpu.cli import main as cli_main

                # The real CLI path; its human-readable report goes to
                # stderr-adjacent capture so stdout stays one JSON line.
                buf = io.StringIO()
                with contextlib.redirect_stdout(buf):
                    doctor = cli_main([
                        "doctor", f"{srv.host}:{srv.port}",
                        "--doctor.bundle", args.out_bundle,
                    ])
                print(buf.getvalue(), file=sys.stderr, end="")
        finally:
            srv.close()
        with open(args.out_metrics, "wb") as f:
            f.write(body)

        if args.out_journal:
            # One mid-flight cancel rides the captured session so the
            # replay artifact proves truncated streams replay too.
            crid = rep.submit(
                g.integers(0, 257, size=12).tolist(), max_new_tokens=64
            )
            while len(rep.result(crid, wait_s=1.0)["tokens"]) < 2:
                if time.monotonic() > deadline:
                    print("timeout waiting for cancel target",
                          file=sys.stderr)
                    sys.exit(1)
            rep.cancel(crid)
            while not rep.result(crid, wait_s=1.0)["done"]:
                pass
            with open(args.out_journal, "w") as f:
                f.write(rep.journal.to_jsonl())

        chrome = rep.export_trace(n=args.requests)
        with open(args.out_trace, "w") as f:
            json.dump(chrome, f)

        stats = rep.stats()
        replay = None
        if args.out_journal:
            # Replay AFTER stats: the replay rebuilds a second engine in
            # this process, and its construction compiles must not bleed
            # into the replica's compiles_since_init reading above.
            from ray_lightning_tpu.cli import run_replay

            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                replay = run_replay({
                    "replay": {
                        "journal": args.out_journal,
                        "out": args.out_replay,
                    }
                })
            print(buf.getvalue(), file=sys.stderr, end="")
        parsed = obs.parse_prometheus_text(body.decode())
        summary = {
            "requests": args.requests,
            "trace_events": len(chrome["traceEvents"]),
            "metrics_series": len(parsed),
            "finished": parsed.get(
                "rlt_serve_requests_total", {}
            ).get('{kind="finished"}'),
            "prefix_hit_rate": stats.get("prefix_hit_rate"),
            "compiles_since_init": stats["compiles_since_init"],
            "health": stats.get("health"),
            "out_metrics": args.out_metrics,
            "out_trace": args.out_trace,
        }
        if doctor is not None:
            summary["doctor_status"] = doctor["status"]
            summary["bundle"] = doctor.get("bundle")
        if replay is not None:
            summary["replay_exact"] = replay["exact"]
            summary["replay_compared"] = replay["compared"]
            summary["out_journal"] = args.out_journal
            summary["out_replay"] = args.out_replay
        print(json.dumps(summary))
    finally:
        rep.stop()


if __name__ == "__main__":
    main()
