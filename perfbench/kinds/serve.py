"""The ``serve`` kind of cell: one replica of the program under open-loop
traffic, and a sample of what it served held against the plain reference.

The replica is the program's ``ServeReplica`` with one thing added: it
makes its weights on the device from the seed, inside its own process
(no checkpoint file, no pickle of the weights through the fabric).
Requests go through ``ServeClient.submit`` and are read back through
``ServeClient.result``, from one thread, on a schedule that does not
wait for the server.

A kind is ``run(ctx) -> run`` (``perfbench/README.md`` has the keys);
the harness finds it by the mix's ``kind``. A variant of this one — a
test's planted fault, say — is a file of its own that calls ``run`` with
its own replica class.
"""
from __future__ import annotations

import heapq
import json
import os
import time
from typing import Any, Dict, List, Optional

from ray_lightning_tpu.serve.server import ServeReplica

from pb import weights
from pb.harness import check_line, run_reference, say, teardown, wait_children_gone


class BenchReplica(ServeReplica):
    """``ServeReplica`` over seeded weights made in this process."""

    def __init__(self, bench: Dict[str, Any], **kw: Any) -> None:
        import jax

        t0 = time.time()
        devs = jax.devices()
        if bench["require_tpu"] and devs[0].platform != "tpu":
            raise RuntimeError(f"replica runs on {devs[0].platform!r}, not on a TPU")
        if len(devs) != bench["chips"]:
            raise RuntimeError(f"replica sees {len(devs)} devices, the cell asks for {bench['chips']}")
        # entries this process writes to the persistent compile cache: programs
        # it compiled itself and that the next process will load
        written = [0]

        def on_event(event: str, **_: Any) -> None:
            written[0] += event == "/jax/compilation_cache/cache_misses"

        jax.monitoring.register_event_listener(on_event)
        params = self.make_params(bench, int(kw["max_seq"]))
        jax.block_until_ready(params)
        t1 = time.time()
        super().__init__(params=params, **kw)
        self._bench_times = {"process_in_ctor_wall": t0, "weights_s": t1 - t0, "ready_wall": time.time(),
                             "cache_writes_at_init": self.cache_writes_at_init(written[0])}

    def cache_writes_at_init(self, counted: int) -> int:
        return counted

    def make_params(self, bench: Dict[str, Any], max_seq: int) -> Any:
        """The seeded weights, on the device, in the type they are served in."""
        return weights.make_params(bench["seed"], bench["dims"], max_seq, bench["dtype"])

    def bench_info(self) -> Dict[str, Any]:
        import jax

        return {
            **self._bench_times,
            "memory_peak_bytes": [
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.local_devices()
            ],
            "param_dtypes": sorted({str(x.dtype) for x in jax.tree_util.tree_leaves(self.engine.params)}),
        }

    def bench_trace(self, outdir: str, seconds: float) -> bool:
        """Trace this process for ``seconds`` from a thread of its own: the
        profiler takes seconds to start and to write, and an actor that
        sat in this call meanwhile would answer no submit and no poll."""
        import threading

        import jax

        def work() -> None:
            os.makedirs(outdir, exist_ok=True)
            t = time.monotonic()
            jax.profiler.start_trace(outdir)
            self._bench_trace["start_took_s"] = time.monotonic() - t
            time.sleep(seconds)
            t = time.monotonic()
            jax.profiler.stop_trace()
            self._bench_trace["stop_took_s"] = time.monotonic() - t
            self._bench_trace["done"] = True

        self._bench_trace: Dict[str, Any] = {"done": False}
        self._bench_trace_thread = threading.Thread(target=work, name="bench-trace", daemon=True)
        self._bench_trace_thread.start()
        return True

    def bench_trace_result(self, wait_s: float = 60.0) -> Dict[str, Any]:
        self._bench_trace_thread.join(timeout=wait_s)
        return dict(self._bench_trace)


def start(ctx: Dict[str, Any], replica_cls: type = BenchReplica) -> Any:
    """Spawn the replica; returns ``(client, actor, spawn_wall)``. The
    mix's ``replica`` group is what ``ServeReplica`` is given."""
    from ray_lightning_tpu import fabric
    from ray_lightning_tpu.serve.client import ServeClient

    mix, cfg = ctx["mix"], ctx["config"]
    rep = dict(mix["replica"])
    env: Dict[str, str] = {}
    if ctx["rehearse"]:
        env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": f"--xla_force_host_platform_device_count={ctx['chips']}"}
    bench = {
        "seed": ctx["seed"], "dims": ctx["dims"], "chips": ctx["chips"],
        "dtype": cfg.get("weights_dtype", "bfloat16"),
        "require_tpu": not ctx["rehearse"],
    }
    opts: Dict[str, Any] = {"num_cpus": 1, "env": env, "init_timeout": 1100.0}
    if not ctx["rehearse"]:
        opts["num_tpus"] = ctx["chips"]

    def spawn() -> Any:
        actor = fabric.remote(replica_cls).options(**opts).remote(
            bench=bench, model_config=dict(cfg["program_config"]), **rep,
        )
        fabric.get(actor.ping.remote(), timeout=1100.0)
        return actor

    t_spawn = time.time()
    actor = spawn()
    wrote = fabric.get(actor.bench_info.remote())["cache_writes_at_init"]
    if wrote:
        # A replica that compiled its programs itself is not the one measured:
        # what compiling leaves on its heap makes the collector stall its
        # scheduler loop for as long as it lives (PERF.md §6; the program's to
        # cure). It has filled the cache; the replica that every later run of
        # this checkout measures starts from it. Counted as set-up.
        fabric.kill(actor)
        wait_children_gone(30)
        say(f"set-up: the first replica compiled {wrote} programs into the cache (started in "
            f"{time.time() - t_spawn:.1f} s) and was replaced by one that loads them")
        t_spawn = time.time()
        actor = spawn()
    client = ServeClient([actor], init_timeout=1100.0, retry_budget_ratio=None)
    return client, actor, t_spawn


def next_poll_in(rec: Dict[str, Any], now: float, fine: float, coarse: float, burst: int = 1) -> float:
    """Seconds from ``now``, the return of a poll, to the request's next one.

    Until the first token has been seen: ``fine``, so ``recv_s[0]`` is the
    first token's time to within ``fine`` and one call. From then on the
    step doubles from ``fine`` up to ``coarse``: a poll in mid-stream times
    no token, it only says at what rate this request's tokens come. From
    that rate and the tokens still due (``want`` less those held) the
    harness reckons when the last will come and goes half of the way
    there, never by less than ``fine``. Tokens come ``burst`` at a time
    (the replica's decode fold), so the last ``burst`` may come with the
    next: they count as due now. The last polls are then ``fine`` apart,
    and ``recv_s[-1]`` is the last token's time to within ``fine`` and one
    call. With ``fine`` equal to ``coarse`` every step is that one."""
    held = len(rec["tokens"])
    if not held:
        return fine
    rec["step"] = step = min(coarse, 2.0 * rec.get("step", fine / 2.0))
    seen_over = rec["recv_s"][-1] - rec["recv_s"][0]
    if seen_over > 0.0:
        per_s = (held - rec["recv_n"][0]) / seen_over
        last_due_in = rec["recv_s"][-1] + max(0, rec["want"] - held - burst) / per_s - now
        step = min(step, last_due_in / 2.0)
    return max(fine, step)


def poll_steps(mix: Dict[str, Any]) -> Any:
    """``(fine, coarse)`` in seconds: the mix's ``fine_poll_ms`` and
    ``poll_ms``. A serve mix names both."""
    from pb.spec import SpecError

    for key in ("fine_poll_ms", "poll_ms"):
        if key not in mix:
            raise SpecError(f"a serve mix names {key!r}: the client's poll has a fine and a coarse step")
    coarse = float(mix["poll_ms"]) / 1000.0
    return min(coarse, float(mix["fine_poll_ms"]) / 1000.0), coarse


def poll_phases(mix: Dict[str, Any], n: int) -> List[float]:
    """For each of ``n`` requests the share of ``fine_poll_ms``, in (0, 1],
    after which its first poll comes once ``submit`` has returned; the
    later ones follow ``fine_poll_ms`` apart. Drawn from the mix's
    ``arrangement_seed``, so every seed and every run polls request ``i``
    in the same phase. Without it every request is polled on one lattice
    of ``fine_poll_ms`` from its ``submit``, every first-token time is a
    point of that lattice, and the 95th percentile of the window steps from
    one point to the next (10 ms of 150: PERF.md, PR 37); with it the times
    are as spread as the tokens' own."""
    import numpy as np

    return (1.0 - np.random.default_rng([int(mix.get("arrangement_seed", 0)), 0xF1A5E]).random(n)).tolist()


def drive(ctx: Dict[str, Any], client: Any, actor: Any, schedule: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Offer ``schedule`` at its due times and read every stream back, each
    request by its own ``client.result`` on the schedule of
    ``next_poll_in``, from the phase ``poll_phases`` gives it. Times in
    the result are seconds relative to the window's start. A request that
    is due goes before a poll that is due, so a generator with more polls
    than it can make is late with polls (``poll_late_s``) before it is
    late with requests."""
    from ray_lightning_tpu import fabric

    mix = ctx["mix"]
    seconds = float(ctx["seconds"])
    lead = float(mix.get("lead_in_s", 0.0))
    drain = float(mix.get("drain_s", 30.0))
    fine, coarse = poll_steps(mix)
    burst = int(mix["replica"].get("decode_fold", 1))  # the replica's own default
    phases = poll_phases(mix, len(schedule))
    trace_s = float(mix.get("trace_s", 3.0))
    recs: List[Dict[str, Any]] = []
    heap: List[Any] = []  # (next_poll, idx)
    poll_late: List[float] = []
    marks: Dict[str, Any] = {}
    stats0 = stats1 = None
    tracing = False
    trace_info: Dict[str, Any] = {}
    t0 = time.monotonic() + lead + 0.05
    ctx["window_open_wall"] = time.time() + lead + 0.05
    i, n = 0, len(schedule)
    open_count = 0
    while True:
        now = time.monotonic() - t0
        if stats0 is None and now >= 0.0:
            stats0 = client.stats()[0]
            marks["stats0_s"] = time.monotonic() - t0
            if ctx["trace"]:
                fabric.get(actor.bench_trace.remote(os.path.join(ctx["out_dir"], "trace"), trace_s))
                tracing = True
            continue
        if stats1 is None and now >= seconds:
            stats1 = client.stats()[0]
            marks["stats1_s"] = time.monotonic() - t0
            continue
        if i < n and schedule[i]["due_s"] <= now:
            r = schedule[i]
            t_call = time.monotonic()
            handle = client.submit(r["prompt"], max_new_tokens=r["max_new_tokens"], temperature=0.0)
            t_ret = time.monotonic()
            recs.append({
                "due_s": r["due_s"], "counted": r["counted"], "prompt_len": len(r["prompt"]),
                "want": r["max_new_tokens"], "submit_s": t_call - t0, "rpc_s": t_ret - t_call,
                "handle": handle, "tokens": [], "recv_s": [], "recv_n": [], "polls": 0,
                "done": False, "status": "open",
            })
            heapq.heappush(heap, (t_ret - t0 + fine * phases[i], i))
            open_count += 1
            i += 1
            continue
        if heap and heap[0][0] <= now:
            due, idx = heapq.heappop(heap)
            rec = recs[idx]
            poll_late.append(now - due)
            rec["polls"] += 1
            try:
                res = client.result(rec["handle"], len(rec["tokens"]))
            except Exception as exc:  # noqa: BLE001 - a lost request is a failed request
                rec["done"], rec["status"] = True, f"error:{type(exc).__name__}"
                open_count -= 1
                continue
            t_poll = time.monotonic() - t0
            if res["tokens"]:
                toks = [int(t) for t in res["tokens"]]
                rec["tokens"].extend(toks)
                rec["recv_s"].append(t_poll)
                rec["recv_n"].append(len(toks))
            if res["done"]:
                rec["done"], rec["status"] = True, str(res["status"])
                open_count -= 1
            else:
                heapq.heappush(heap, (t_poll + next_poll_in(rec, t_poll, fine, coarse, burst), idx))
            continue
        if i >= n and open_count == 0 and stats1 is not None:
            break
        if now > seconds + drain:
            break
        nxt = min(
            schedule[i]["due_s"] if i < n else float("inf"),
            heap[0][0] if heap else float("inf"),
            seconds if stats1 is None else float("inf"),
            0.0 if stats0 is None else float("inf"),
        )
        time.sleep(max(0.0, min(nxt - now, 0.02)))
    if tracing:
        trace_info = fabric.get(actor.bench_trace_result.remote())
    end_s = time.monotonic() - t0
    for rec in recs:
        rec.pop("handle")
        rec.pop("step", None)
    return {
        "records": recs, "stats0": stats0, "stats1": stats1 or client.stats()[0],
        "marks": marks, "end_s": end_s, "trace": trace_info,
        "offered": n, "submitted": i, "poll_late_s": poll_late,
    }


def end_to_end(recs: List[Dict[str, Any]], seconds: float, drain_s: float) -> Any:
    """The window's three end-to-end metrics from the client's records:
    ``(e2e, counted, done_ok, tokens_in_window)``. A request that failed,
    was lost in mid-stream or did not finish whole by the drain limit
    enters both latency tails at that limit, the longest it could have
    waited; it is never dropped from the sample."""
    from pb import stats

    counted = [r for r in recs if r["counted"]]
    done_ok = [r for r in counted if r["done"] and r["status"] == "finished" and len(r["tokens"]) == r["want"]]
    missing = seconds + drain_s
    ok_ids = {id(r) for r in done_ok}
    ttft = stats.latency_with_missing(
        [(r["recv_s"][0] - r["due_s"]) if id(r) in ok_ids else None for r in counted], missing)
    # time per output token: (last token - first token) / (tokens - 1), at the client
    tpot = stats.latency_with_missing(
        [((r["recv_s"][-1] - r["recv_s"][0]) / (len(r["tokens"]) - 1)) if id(r) in ok_ids and len(r["tokens"]) > 1
         else None for r in counted if r["want"] > 1], missing)
    # every output token the client received inside the window, whichever
    # request it belongs to (lead-in requests that are still decoding too):
    # all the work of the window over all its time. A token lands in the
    # window by the time of the poll that brought it.
    in_window = sum(n for r in recs for t, n in zip(r["recv_s"], r["recv_n"]) if 0.0 <= t < seconds)
    e2e = {
        "ttft_p95_ms": 1000.0 * stats.percentile(ttft, 95),
        "tpot_p95_ms": 1000.0 * stats.percentile(tpot, 95),
        "serve_tokens_per_s": in_window / seconds,
    }
    return e2e, counted, done_ok, in_window


def run(ctx: Dict[str, Any], replica_cls: type = BenchReplica) -> Dict[str, Any]:
    """One run of the cell: the replica under the mix's traffic, the end of
    its processes, the reference over a sample of what was served, and
    each number compared beside its limit."""
    import numpy as np

    from pb import stats, traffic

    mix, dims = ctx["mix"], ctx["dims"]
    seconds = float(ctx["seconds"])
    poll_steps(mix)  # a mix that leaves a step out is refused before anything is started
    schedule = traffic.serve_schedule(mix, ctx["seed"], seconds, dims["vocab"])
    client, actor, t_spawn = start(ctx, replica_cls)
    try:
        from ray_lightning_tpu import fabric

        info0 = fabric.get(actor.bench_info.remote())
        out = drive(ctx, client, actor, schedule)
        info1 = fabric.get(actor.bench_info.remote())
    finally:
        client.shutdown()
    leftovers = teardown()
    recs = out["records"]
    drain_s = float(mix.get("drain_s", 30.0))
    e2e, counted, done_ok, in_window = end_to_end(recs, seconds, drain_s)
    e2e["setup_s"] = ctx["window_open_wall"] - ctx["t_start"]
    if not stats.tail_supported(len(counted), 95):
        say(f"note: {len(counted)} requests leave fewer than ten beyond the 95th percentile")
    # -- the check: a seeded sample of finished requests, the longest among them
    checks: List[Dict[str, Any]] = []
    rng = np.random.default_rng([int(ctx["seed"]), 0xC4EC])
    k = int(mix.get("check_sample", 4))
    pool = sorted(done_ok, key=lambda r: -(r["prompt_len"] + len(r["tokens"])))
    sample = pool[:1] + [pool[1:][j] for j in rng.permutation(max(0, len(pool) - 1))[: k]]
    by_due = {r["due_s"]: s for r, s in zip(recs, schedule)}
    pad_to = -(-(int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])) // 128) * 128
    ref: Dict[str, Any] = {"reference": {}, "wall_s": 0.0}
    # printed in every result line, compared with nothing: a run that reads over 50 ms in the
    # first offered other traffic than its mix describes, and one that reads over the mix's
    # fine_poll_ms in the second timed its tokens more coarsely than that (perfbench/README.md)
    numbers: Dict[str, float] = {
        "gen_late_p95_ms": 1000.0 * stats.percentile([r["submit_s"] - r["due_s"] for r in counted], 95),
        "poll_late_p95_ms": 1000.0 * stats.percentile(out["poll_late_s"] or [0.0], 95),
    }
    if sample:
        ref = run_reference(ctx, {
            "kind": "serve", "max_seq": int(mix["replica"]["max_seq"]),
            "dtype": ctx["config"].get("weights_dtype", "bfloat16"), "pad_to": pad_to,
            "samples": [{"prompt": by_due[r["due_s"]]["prompt"], "tokens": r["tokens"]} for r in sample],
        })
        r = ref["reference"]
        numbers["widest_gap"] = r["widest_gap"]
        say(f"reference: {r['tokens_compared']} served tokens of {len(sample)} requests compared; "
            f"mean gap {r['mean_gap']:.6g}; share equal to the reference's first choice {r['greedy_agree_share']:.4f}")
        check_line(checks, "widest_gap", r["widest_gap"], ctx["limits"]["widest_gap"],
                   r["widest_gap"] <= ctx["limits"]["widest_gap"])
        if "control_widest_gap" in r:
            ctx["control_numbers"] = {"widest_gap": r["control_widest_gap"], "mean_gap": r["control_mean_gap"]}
            say("control " + json.dumps(ctx["control_numbers"]))
    check_line(checks, "requests_sampled", len(sample), ">=1", len(sample) >= 1)
    finished = [r for r in counted if r["done"] and r["status"] == "finished"]
    exact = all(len(r["tokens"]) == r["want"] for r in finished)
    check_line(checks, "finished_requests_have_their_token_count", exact, True, exact)
    in_vocab = all(0 <= t < dims["vocab"] for r in recs for t in r["tokens"])
    check_line(checks, "token_ids_in_vocabulary", in_vocab, True, in_vocab)
    say(f"window: {len(counted)} requests due, {len(done_ok)} finished whole, {in_window} tokens received in "
        f"{seconds:.0f} s; drained to {out['end_s']:.1f} s; reference {ref['wall_s']:.1f} s is outside "
        f"set-up and window; leftovers: {leftovers}")
    dev = out["stats1"]["device"]
    say(f"peak HBM per device (memory_stats peak_bytes_in_use): {info1['memory_peak_bytes']}; "
        f"weights held as {info1['param_dtypes']}")
    return {
        "numbers": numbers, "checks": checks, "e2e": e2e, "reference": ref,
        "program": {"records": recs, "stats0": out["stats0"], "stats1": out["stats1"], "marks": out["marks"],
                    "info0": info0, "info1": info1, "spawn_wall": t_spawn, "schedule_n": len(schedule)},
        "attempted": len(counted), "failed": len(counted) - len(done_ok),
        "device": {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"]},
        "memory_peak_bytes": max(info1["memory_peak_bytes"] or [0]),
        "trace": ({"dir": os.path.join(ctx["out_dir"], "trace"), **out["trace"]} if out["trace"] else None),
        "between": "between dispatches: scheduler step, harvest, admission, result RPCs",
    }
