"""ray_lightning_tpu.serve — continuous-batching inference serving.

The L5 layer over the decode path (models/gpt.py: prefill + GQA KV cache
+ int8 trees) and the fabric (actors, queues, placement groups):

- :class:`DecodeEngine` — slot-based decode over one compiled step
  (engine.py): iteration-level admission, bucketed prefill, per-slot
  sampling, zero per-request recompilation.
- :class:`Scheduler` / :class:`SamplingParams` — continuous batching
  policy: FIFO/priority queue, prefill/decode interleave, deadlines,
  cancellation (scheduler.py).
- :class:`ServeReplica` / :func:`start_replicas` / :class:`ServeClient`
  — replica actors on the fabric with a blocking + streaming client
  (server.py, client.py); ``rlt serve`` is the CLI front end.
- :class:`ServeMetrics` — queue depth, TTFT, occupancy, tokens/s
  (metrics.py), exposed through the replicas' ``stats()`` endpoint.
- :class:`FleetSupervisor` — the driver-side detect->decide->recover
  loop (supervisor.py): drains unhealthy replicas, restarts dead ones
  through the fabric, and fails their incomplete requests over
  (journal-backed, bit-exact) onto survivors.
- :class:`Router` / :class:`RouterAutoscaler` (router.py) — the
  front-door routing policy ``ServeClient.submit`` consults:
  health/state-aware weighting, prefix-affinity (the engines' chained
  block digests, driver-side), admission control with graceful
  shedding (:class:`RequestRejectedError` + retry-after), a shared
  client :class:`RetryBudget`, hedged streaming reads, and
  queue-driven replica autoscaling within ``[min, max]`` bounds.
- :class:`FleetKVDirectory` / :class:`KVFleetPlane` (kvfleet.py) — the
  fleet KV plane: one driver-side digest→replica directory (shared
  with the router's prefix affinity, one invalidation path incl.
  evicted blocks) plus per-replica transfer planes over fabric inbox
  queues — cross-replica prefix fetches on miss, and disaggregated
  prefill/decode (``start_replicas(roles=...)``: prefill replicas
  ship each finished prefill's KV pages to a router-chosen decode
  replica; bit-exact end to end).
- :class:`FaultInjector` — deterministic fault injection (faults.py):
  kill/delay/drop/wedge/preempt at named lifecycle points, driving the
  chaos tests (tests/test_failover.py, tests/test_preempt.py).
- :class:`PreemptionMonitor` (preempt.py) — the per-process preemption
  signal plane: SIGTERM, a metadata poller, and the ``preempt`` fault
  action funnel into one ``preemption_pending(deadline)`` state the
  supervisor drains gracefully (finish-in-grace + live-migration with
  cross-replica KV handoff) and the trainer answers with
  checkpoint-on-notice.

Heavy deps load lazily: the engine (jax) and the replica/client layer
(fabric) import on first attribute access, not at package import.
(Replica actors are exec'd fresh interpreters, so their platform env is
applied before anything heavy loads regardless.)
"""
from ray_lightning_tpu.serve.metrics import ServeMetrics
from ray_lightning_tpu.serve.scheduler import (
    Request,
    SamplingParams,
    Scheduler,
    TokenEvent,
)

from ray_lightning_tpu.serve.faults import FaultInjector, FaultRule
from ray_lightning_tpu.serve.preempt import (
    PreemptionMonitor,
    get_monitor,
    reset_monitor,
)
from ray_lightning_tpu.serve.kvfleet import (
    FleetKVDirectory,
    KVFleetPlane,
)
from ray_lightning_tpu.serve.router import (
    RequestRejectedError,
    RetryBudget,
    Router,
    RouterAutoscaler,
)

__all__ = [
    "DecodeEngine",
    "ServeMetrics",
    "SamplingParams",
    "Request",
    "Scheduler",
    "TokenEvent",
    "ServeReplica",
    "ServeClient",
    "start_replicas",
    "load_serve_params",
    "FleetSupervisor",
    "Router",
    "RouterAutoscaler",
    "RequestRejectedError",
    "RetryBudget",
    "FleetKVDirectory",
    "KVFleetPlane",
    "FaultInjector",
    "FaultRule",
    "PreemptionMonitor",
    "get_monitor",
    "reset_monitor",
]

_LAZY = {
    # jax-importing (engine) or fabric-importing (server/client) names.
    "DecodeEngine": "ray_lightning_tpu.serve.engine",
    "ServeReplica": "ray_lightning_tpu.serve.server",
    "load_serve_params": "ray_lightning_tpu.serve.server",
    "ServeClient": "ray_lightning_tpu.serve.client",
    "start_replicas": "ray_lightning_tpu.serve.client",
    "FleetSupervisor": "ray_lightning_tpu.serve.supervisor",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(
        f"module 'ray_lightning_tpu.serve' has no attribute {name!r}"
    )
