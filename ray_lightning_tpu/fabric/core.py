"""Fabric core: session, logical nodes, object store, actors, futures.

Native replacement for the Ray-core features the reference consumes
(SURVEY.md §2b): actor creation with per-worker resources
(ray_launcher.py:105-114), ``ray.put`` model shipping (:235), ``ray.get`` /
``ray.wait`` driver loops (util.py:57-70), and ``ray.kill(no_restart=True)``
teardown (:125-127). Implementation is process-based and from scratch.
"""
from __future__ import annotations

import atexit
import itertools
import multiprocessing as mp
import os
import threading
import time
import uuid
from dataclasses import dataclass, field
from multiprocessing import shared_memory
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import cloudpickle

from ray_lightning_tpu.utils.ports import find_free_port, get_node_ip


def _record_event(name: str, level: str = "info", **kv: Any) -> None:
    """Driver-side actor lifecycle into the process event log
    (obs.events) — best-effort: the reader threads also reach here
    during interpreter teardown, where imports can fail."""
    try:
        from ray_lightning_tpu.obs.events import get_event_log

        get_event_log().record("fabric", name, level=level, **kv)
    except Exception:  # noqa: BLE001 - forensics must never break fabric
        pass


class FabricError(RuntimeError):
    pass


class InsufficientResourcesError(FabricError):
    pass


class ActorDiedError(FabricError):
    pass


# --------------------------------------------------------------------------
# Logical nodes & resources
# --------------------------------------------------------------------------
@dataclass
class Node:
    node_id: str
    node_ip: str
    capacity: Dict[str, float]
    used: Dict[str, float] = field(default_factory=dict)
    #: actor_id -> chip indices of actors that reserved PART of this host's
    #: chips (see :func:`_pin_chips`); whole-host actors are not listed.
    pinned: Dict[str, Tuple[int, ...]] = field(default_factory=dict)

    def available(self) -> Dict[str, float]:
        return {
            k: self.capacity.get(k, 0.0) - self.used.get(k, 0.0)
            for k in self.capacity
        }

    def fits(self, req: Dict[str, float]) -> bool:
        avail = self.available()
        return all(avail.get(k, 0.0) >= v - 1e-9 for k, v in req.items() if v)

    def acquire(self, req: Dict[str, float]) -> None:
        for k, v in req.items():
            if v:
                self.used[k] = self.used.get(k, 0.0) + v

    def release(self, req: Dict[str, float]) -> None:
        for k, v in req.items():
            if v:
                self.used[k] = max(0.0, self.used.get(k, 0.0) - v)


#: ``TPU_CHIPS_PER_PROCESS_BOUNDS`` for a process that owns k chips of one
#: host — the sub-slice shapes jax's own multi-process TPU tests use. Other
#: counts have no rectangular sub-slice and are refused.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def _pin_chips(
    node: Node, actor_id: str, request: Dict[str, float]
) -> Dict[str, str]:
    """Exec environment confining an actor to the chips it reserved.

    libtpu opens EVERY chip of the host unless told otherwise, so an actor
    that reserves k of N chips would otherwise see N devices and contend
    with its neighbours for all of them. An actor reserving the whole host
    needs nothing. A partial reservation gets an aligned group of k free
    chip indices and the variables libtpu reads to run as a one-process
    slice of exactly those chips, with its own slice-builder port so
    neighbours on the host do not collide. A reservation that cannot be
    isolated (fractional, or a count with no sub-slice shape) is refused
    here, before anything is spawned. Caller holds ``sess.lock``.
    """
    k = request.get("TPU", 0.0)
    total = int(node.capacity.get("TPU", 0.0))
    if not k or k >= total:
        return {}
    if k != int(k) or int(k) not in _CHIP_BOUNDS or total % int(k):
        raise FabricError(
            f"cannot isolate TPU={k:g} of node {node.node_id}'s {total} "
            "chips: libtpu gives a process whole chips in groups of "
            f"{sorted(_CHIP_BOUNDS)} that divide the host; two processes "
            "cannot share a chip. Reserve one of those counts or the whole "
            "host."
        )
    k = int(k)
    taken = {c for chips in node.pinned.values() for c in chips}
    group = next(
        (
            g
            for g in (tuple(range(i, i + k)) for i in range(0, total, k))
            if not taken.intersection(g)
        ),
        None,
    )
    if group is None:
        raise InsufficientResourcesError(
            f"no aligned group of {k} free chips on node {node.node_id} "
            f"(pinned: {sorted(taken)})"
        )
    node.pinned[actor_id] = group
    port = find_free_port()
    return {
        "TPU_VISIBLE_CHIPS": ",".join(map(str, group)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[k],
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # The older spellings of the two bounds, which a TPU VM's own
        # environment may carry with the whole host's values.
        "TPU_CHIPS_PER_HOST_BOUNDS": _CHIP_BOUNDS[k],
        "TPU_HOST_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


#: What the probe child runs: the count on the last line of stdout.
_PROBE_SRC = (
    "import jax; "
    "print(len([d for d in jax.devices() if d.platform == 'tpu']))"
)
_PROBE_TIMEOUT_S = 90.0


def _stderr_tail(text: Any, limit: int = 1500) -> str:
    if isinstance(text, bytes):
        text = text.decode(errors="replace")
    return (text or "").strip()[-limit:]


def _probe_tpu_chips() -> int:
    """Count this host's TPU chips in a short-lived child process.

    Initializing the TPU runtime in the *driver* would hold the host's
    chips for the whole process lifetime (libtpu is exclusive), starving
    the worker actors. A child that runs and counts zero means a CPU host.
    A child that crashes or times out means the device could not be asked,
    which is an error — never a CPU plan under a TPU's name.
    """
    import subprocess
    import sys

    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE_SRC],
            capture_output=True,
            timeout=_PROBE_TIMEOUT_S,
            text=True,
        )
    except subprocess.TimeoutExpired as exc:
        raise FabricError(
            f"TPU probe timed out after {_PROBE_TIMEOUT_S:.0f}s (is another "
            "process holding the chips?); set RLT_NUM_TPU_CHIPS to skip the "
            f"probe. Child stderr: {_stderr_tail(exc.stderr)}"
        ) from exc
    if out.returncode != 0:
        raise FabricError(
            f"TPU probe exited with code {out.returncode}; set "
            "RLT_NUM_TPU_CHIPS to skip the probe. Child stderr: "
            f"{_stderr_tail(out.stderr)}"
        )
    try:
        return int(out.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError) as exc:
        raise FabricError(
            f"TPU probe printed no chip count (stdout {out.stdout[-200:]!r}); "
            f"child stderr: {_stderr_tail(out.stderr)}"
        ) from exc


def _detect_local_capacity() -> Dict[str, float]:
    cap: Dict[str, float] = {"CPU": float(os.cpu_count() or 1)}
    env_chips = os.environ.get("RLT_NUM_TPU_CHIPS")
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if env_chips is not None:
        # Explicit override (tests, TPU VM metadata): no probe.
        chips = float(env_chips)
    elif platforms and "tpu" not in [p.strip() for p in platforms.split(",")]:
        # JAX_PLATFORMS names the platforms jax may use; without "tpu" in
        # it no process of this run can reach a chip.
        chips = 0.0
    else:
        chips = float(_probe_tpu_chips())
    if chips or env_chips is not None:
        cap["TPU"] = chips
    if not chips and os.environ.get("RLT_REQUIRE_TPU") == "1":
        raise FabricError(
            "RLT_REQUIRE_TPU=1 but this host has no TPU chips "
            f"(JAX_PLATFORMS={platforms!r}, RLT_NUM_TPU_CHIPS={env_chips!r})"
        )
    return cap


# --------------------------------------------------------------------------
# Session
# --------------------------------------------------------------------------
class _Session:
    # Retained finished-call results per session: enough for any realistic
    # set of simultaneously-live futures, bounded so a long Tuner run's
    # completed calls don't accumulate forever.
    RESULTS_CAP = int(os.environ.get("RLT_FABRIC_RESULTS_CAP", "4096"))

    def __init__(self) -> None:
        from collections import OrderedDict

        self.nodes: List[Node] = []
        self.actors: Dict[str, "ActorHandle"] = {}
        self.store: Dict[str, Tuple[shared_memory.SharedMemory, int]] = {}
        self.lock = threading.RLock()
        self.cv = threading.Condition(self.lock)
        self.results: "OrderedDict[Tuple[str, int], Tuple[bool, Any]]" = (
            OrderedDict()
        )
        # Keys evicted from `results` (bounded ring): lets get()/wait() on a
        # stale ref fail loudly instead of blocking forever.
        self.evicted: "OrderedDict[Tuple[str, int], None]" = OrderedDict()
        self.dead_actors: Dict[str, str] = {}  # actor_id -> reason
        self.mp_ctx = mp.get_context("spawn")
        self._manager: Optional[Any] = None
        self._counter = itertools.count()

    def add_result(self, key: Tuple[str, int], value: Tuple[bool, Any]) -> None:
        """Record a call result, evicting the oldest beyond RESULTS_CAP.

        Results stay cached so repeated get()/wait() on the same ref keep
        working (Ray-like contract; the driver poll loop re-waits refs);
        the cap bounds growth — refs are consumed promptly in practice, so
        evicting ancient entries is safe."""
        self.results[key] = value
        while len(self.results) > self.RESULTS_CAP:
            old_key, _ = self.results.popitem(last=False)
            self.evicted[old_key] = None
            while len(self.evicted) > 4 * self.RESULTS_CAP:
                self.evicted.popitem(last=False)

    @property
    def manager(self):
        if self._manager is None:
            self._manager = self.mp_ctx.Manager()
        return self._manager

    def next_id(self) -> int:
        return next(self._counter)


_session: Optional[_Session] = None


def _client_mode():
    """The connected FabricClient module, or None (local mode)."""
    from ray_lightning_tpu.fabric import client

    return client if client.is_connected() else None


def is_initialized() -> bool:
    return _session is not None or _client_mode() is not None


def init(
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    ignore_reinit_error: bool = True,
    address: Optional[str] = None,
    authkey: Optional[str] = None,
) -> None:
    """Start the fabric session with a single local head node.

    ``resources`` adds custom logical resources (the reference tests this
    passthrough with ``ray.init(resources={"extra": 4})``, test_ddp.py:34-39).
    ``address="host:port"`` enters client mode: connect to a remote
    :class:`~ray_lightning_tpu.fabric.server.FabricServer` head and proxy
    every fabric call there (the Ray Client "infinite laptop" analog,
    reference test_client.py:17-30). ``authkey`` is the server's shared
    secret (from its ready line or its ``RLT_FABRIC_AUTHKEY``); defaults
    to this process's ``RLT_FABRIC_AUTHKEY``.
    """
    global _session
    if address is not None:
        from ray_lightning_tpu.fabric import client

        client.connect(address, authkey=authkey)
        return
    if _client_mode() is not None:
        return  # already connected to a head; local init is a no-op
    if _session is not None:
        if ignore_reinit_error:
            return
        raise FabricError("fabric already initialized")
    # Detect BEFORE publishing the session: if detection raises (e.g.
    # RLT_REQUIRE_TPU with a wedged probe), no half-built session must
    # linger — a retrying caller would otherwise hit the reinit fast-path
    # and silently run with zero resources.
    cap = _detect_local_capacity()
    if num_cpus is not None:
        cap["CPU"] = float(num_cpus)
    if num_tpus is not None:
        cap["TPU"] = float(num_tpus)
    if resources:
        cap.update({k: float(v) for k, v in resources.items()})
    session = _Session()
    session.nodes.append(Node("node-0", get_node_ip(), cap))
    _session = session


def _require_session() -> _Session:
    if _session is None:
        init()
    assert _session is not None
    return _session


def shutdown() -> None:
    _c = _client_mode()
    if _c is not None:
        from ray_lightning_tpu.fabric import client

        client.disconnect()
        return
    global _session
    if _session is None:
        return
    sess = _session
    with sess.lock:
        handles = list(sess.actors.values())
    for handle in handles:
        try:
            kill(handle)
        except Exception:  # noqa: BLE001
            pass
    for shm, _ in sess.store.values():
        try:
            shm.close()
            shm.unlink()
        except Exception:  # noqa: BLE001
            pass
    sess.store.clear()
    if sess._manager is not None:
        try:
            sess._manager.shutdown()
        except Exception:  # noqa: BLE001
            pass
    _session = None


atexit.register(shutdown)


def _add_node(capacity: Dict[str, float], node_ip: Optional[str] = None) -> Node:
    """Register an extra logical node (used by cluster_utils for fake clusters)."""
    sess = _require_session()
    with sess.lock:
        node_id = f"node-{len(sess.nodes)}"
        ip = node_ip or f"10.77.{len(sess.nodes)}.1"
        node = Node(node_id, ip, dict(capacity))
        sess.nodes.append(node)
        return node


def nodes() -> List[Dict[str, Any]]:
    _c = _client_mode()
    if _c is not None:
        return _c.nodes()
    sess = _require_session()
    with sess.lock:
        return [
            {
                "NodeID": n.node_id,
                "NodeManagerAddress": n.node_ip,
                "Resources": dict(n.capacity),
                "Available": n.available(),
                "alive": True,
            }
            for n in sess.nodes
        ]


def cluster_resources() -> Dict[str, float]:
    _c = _client_mode()
    if _c is not None:
        return _c.cluster_resources()
    sess = _require_session()
    with sess.lock:
        total: Dict[str, float] = {}
        for n in sess.nodes:
            for k, v in n.capacity.items():
                total[k] = total.get(k, 0.0) + v
        return total


def available_resources() -> Dict[str, float]:
    _c = _client_mode()
    if _c is not None:
        return _c.available_resources()
    sess = _require_session()
    with sess.lock:
        total: Dict[str, float] = {}
        for n in sess.nodes:
            for k, v in n.available().items():
                total[k] = total.get(k, 0.0) + v
        return total


def heartbeats() -> Dict[str, Dict[str, Any]]:
    """Latest heartbeat per live actor: worker-pushed process stats
    (rss_bytes, cpu_s, uptime_s, calls_handled, calls_in_flight,
    last_call_age_s) plus the driver-side ``age_s`` of the push. Workers
    push every ``RLT_HEARTBEAT_S`` seconds (default 10; <= 0 disables),
    so an empty dict just means no interval has elapsed yet.
    ``obs.heartbeats_to_registry`` folds this into Prometheus gauges."""
    if _session is None:
        return {}
    with _session.cv:
        handles = list(_session.actors.values())
    now = time.monotonic()
    out: Dict[str, Dict[str, Any]] = {}
    for h in handles:
        hb = h._last_heartbeat
        if hb is None:
            continue
        t_recv, stats = hb
        entry = dict(stats)
        entry["age_s"] = round(now - t_recv, 3)
        out[h.actor_id] = entry
    return out


# --------------------------------------------------------------------------
# Placement groups (gang scheduling)
# --------------------------------------------------------------------------
@dataclass
class _Bundle:
    """One reserved resource bundle of a placement group."""

    index: int
    request: Dict[str, float]
    node: Node
    remaining: Dict[str, float]


class PlacementGroup:
    """A gang reservation: N resource bundles acquired atomically.

    The fabric analog of ``ray.util.placement_group`` as the reference's
    Tune integration consumes it (tune.py:50-55: ``PlacementGroupFactory(
    [head] + N*[worker], strategy="PACK")``): the bundles are RESERVED on
    logical nodes at creation; actors then schedule INTO a bundle via
    ``options(placement_group=pg, placement_group_bundle_index=i)``,
    drawing from the reservation instead of free node capacity.

    Strategies (Ray semantics):
      - ``"PACK"``: all bundles on one node when possible, else spill to
        as few nodes as needed (best effort).
      - ``"STRICT_PACK"``: all bundles on one node, or placement fails.
      - ``"SPREAD"``: bundles across distinct nodes where possible.
    """

    def __init__(self, pg_id: str, bundles: List[_Bundle], strategy: str):
        self.id = pg_id
        self.bundles = bundles
        self.strategy = strategy
        self.removed = False

    @property
    def bundle_node_ids(self) -> List[str]:
        return [b.node.node_id for b in self.bundles]


def placement_group(
    bundles: List[Dict[str, float]], strategy: str = "PACK"
) -> Any:
    """Atomically reserve ``bundles`` on the cluster's logical nodes.

    Raises :class:`InsufficientResourcesError` when the bundles cannot be
    placed under ``strategy`` with current availability (nothing is leaked:
    partial acquisitions roll back). In client mode the reservation lives
    on the fabric head and a lightweight proxy is returned."""
    _c = _client_mode()
    if _c is not None:
        return _c.placement_group(bundles, strategy=strategy)
    if strategy not in ("PACK", "STRICT_PACK", "SPREAD"):
        raise ValueError(f"unknown placement strategy {strategy!r}")
    reqs = [
        {k: float(v) for k, v in b.items() if float(v)} for b in bundles
    ]
    if not reqs:
        raise ValueError("placement group needs at least one bundle")
    sess = _require_session()
    with sess.lock:
        total: Dict[str, float] = {}
        for r in reqs:
            for k, v in r.items():
                total[k] = total.get(k, 0.0) + v
        assigned: List[Node] = []
        one_node = (
            next((n for n in sess.nodes if n.fits(total)), None)
            if strategy in ("PACK", "STRICT_PACK")
            else None
        )
        if one_node is not None:
            assigned = [one_node] * len(reqs)
        elif strategy == "STRICT_PACK":
            raise InsufficientResourcesError(
                f"STRICT_PACK placement of {reqs} (total {total}) fits no "
                f"single node; available per node: "
                f"{[n.available() for n in sess.nodes]}"
            )
        else:
            # Greedy spill (PACK) / distribution (SPREAD). Acquire as we
            # assign so same-node bundles see each other's reservations;
            # roll back on failure.
            placed_count: Dict[str, int] = {}
            acquired: List[Tuple[Node, Dict[str, float]]] = []
            try:
                for r in reqs:
                    fitting = [n for n in sess.nodes if n.fits(r)]
                    if not fitting:
                        raise InsufficientResourcesError(
                            f"cannot place bundle {r}; available per node: "
                            f"{[n.available() for n in sess.nodes]}"
                        )
                    key = (
                        min
                        if strategy == "SPREAD"
                        else max
                    )
                    node = key(
                        fitting,
                        key=lambda n: (
                            placed_count.get(n.node_id, 0),
                            # tie-break: keep node order deterministic
                            -sess.nodes.index(n),
                        ),
                    )
                    node.acquire(r)
                    acquired.append((node, r))
                    assigned.append(node)
                    placed_count[node.node_id] = (
                        placed_count.get(node.node_id, 0) + 1
                    )
            except InsufficientResourcesError:
                for node, r in acquired:
                    node.release(r)
                raise
        if one_node is not None:
            for r in reqs:
                one_node.acquire(r)
        pg = PlacementGroup(
            f"pg-{uuid.uuid4().hex[:8]}",
            [
                _Bundle(i, dict(r), node, dict(r))
                for i, (r, node) in enumerate(zip(reqs, assigned))
            ],
            strategy,
        )
        return pg


def remove_placement_group(pg: Any) -> None:
    """Release a placement group's reservations, killing any actors still
    scheduled into its bundles first (Ray semantics: removing a group
    terminates its occupants).

    Ordering matters: releasing node capacity while occupants still hold
    bundle reservations would let a new actor double-book the node — the
    freed CPUs/chips would be promised twice until the occupant died. So
    the group is tombstoned first (new spawns into it fail fast), the
    occupants are killed (their resources return to the bundle, not the
    node), and only then do the bundle reservations go back to the nodes.
    """
    _c = _client_mode()
    if _c is not None:
        _c.remove_placement_group(pg)
        return
    sess = _require_session()
    with sess.lock:
        # Check-and-set under the lock: concurrent removals (user cleanup
        # racing Tuner teardown) must not double-release node capacity.
        if pg.removed:
            return
        pg.removed = True
        bundle_ids = {id(b) for b in pg.bundles}
        occupants = [
            h
            for h in sess.actors.values()
            if h._pg_bundle is not None and id(h._pg_bundle) in bundle_ids
        ]
    for handle in occupants:
        try:
            kill(handle)
        except Exception:  # noqa: BLE001 - the actor may already be dead
            pass
    with sess.lock:
        for b in pg.bundles:
            b.node.release(b.request)
    with sess.cv:
        sess.cv.notify_all()


def _release_actor_resources(handle: "ActorHandle") -> None:
    """Return an actor's resources to its placement-group bundle (if it was
    gang-scheduled) or to its node's free pool. Caller holds sess.lock."""
    handle._node.pinned.pop(handle.actor_id, None)
    bundle = handle._pg_bundle
    if bundle is not None:
        for k, v in handle._request.items():
            if v:
                bundle.remaining[k] = bundle.remaining.get(k, 0.0) + v
    else:
        handle._node.release(handle._request)


# --------------------------------------------------------------------------
# Object store (shared memory)
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class ObjectRef:
    """Reference to an object in the driver's shared-memory store.

    Picklable: workers receiving a ref attach to the shm segment by name and
    deserialize in place — the fabric equivalent of plasma-store transport
    behind ``ray.put`` (ray_launcher.py:235).
    """

    id: str
    shm_name: str
    size: int

    def __reduce__(self):
        return (_objectref_from_wire, (self.id, self.shm_name, self.size))


def _objectref_from_wire(id: str, shm_name: str, size: int) -> "ObjectRef":
    return ObjectRef(id=id, shm_name=shm_name, size=size)


def put(obj: Any) -> ObjectRef:
    _c = _client_mode()
    if _c is not None:
        return _c.put(obj)
    sess = _require_session()
    payload = cloudpickle.dumps(obj, protocol=5)
    ref_id = uuid.uuid4().hex[:16]
    shm = shared_memory.SharedMemory(create=True, size=max(1, len(payload)))
    shm.buf[: len(payload)] = payload
    with sess.lock:
        sess.store[ref_id] = (shm, len(payload))
    return ObjectRef(id=ref_id, shm_name=shm.name, size=len(payload))


def _get_object(ref: ObjectRef) -> Any:
    sess = _session
    if sess is not None:
        with sess.lock:
            entry = sess.store.get(ref.id)
        if entry is not None:
            shm, size = entry
            return cloudpickle.loads(bytes(shm.buf[:size]))
    # Not the owner (we are inside a worker): attach read-only by name.
    shm = shared_memory.SharedMemory(name=ref.shm_name)
    try:
        # Python <=3.12 registers ATTACHED segments with this process's
        # resource_tracker as if it owned them; at worker exit the tracker
        # would then unlink driver-owned segments and print "leaked
        # shared_memory objects" warnings. Deregister — the creating session
        # owns cleanup (free()/shutdown()).
        try:
            from multiprocessing import resource_tracker

            resource_tracker.unregister("/" + ref.shm_name.lstrip("/"), "shared_memory")
        except Exception:  # noqa: BLE001 - tracker API/registration varies
            pass
        return cloudpickle.loads(bytes(shm.buf[: ref.size]))
    finally:
        shm.close()


def free(refs: Sequence[ObjectRef]) -> None:
    _c = _client_mode()
    if _c is not None:
        _c.free(refs)
        return
    sess = _require_session()
    with sess.lock:
        for ref in refs:
            entry = sess.store.pop(ref.id, None)
            if entry is not None:
                shm, _ = entry
                try:
                    shm.close()
                    shm.unlink()
                except Exception:  # noqa: BLE001
                    pass


# --------------------------------------------------------------------------
# Futures
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class TaskRef:
    """Future for an in-flight actor method call."""

    actor_id: str
    call_id: int


def _task_done(sess: _Session, ref: TaskRef) -> bool:
    key = (ref.actor_id, ref.call_id)
    return (
        key in sess.results
        or key in sess.evicted
        or ref.actor_id in sess.dead_actors
    )


def get(refs: Any, timeout: Optional[float] = None) -> Any:
    """Resolve ObjectRef/TaskRef (or a list of them) to values."""
    _c = _client_mode()
    if _c is not None:
        return _c.get(refs, timeout=timeout)
    if isinstance(refs, (list, tuple)):
        return type(refs)(get(r, timeout=timeout) for r in refs)
    if isinstance(refs, ObjectRef):
        return _get_object(refs)
    if not isinstance(refs, TaskRef):
        return refs  # plain value passthrough
    sess = _require_session()
    deadline = None if timeout is None else time.monotonic() + timeout
    with sess.cv:
        while not _task_done(sess, refs):
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                raise TimeoutError("fabric.get timed out")
            sess.cv.wait(timeout=remaining if remaining is not None else 1.0)
        key = (refs.actor_id, refs.call_id)
        if key not in sess.results:
            if key in sess.evicted:
                raise FabricError(
                    f"result for {refs} was evicted from the bounded results "
                    f"cache (RLT_FABRIC_RESULTS_CAP={sess.RESULTS_CAP}) before "
                    "it was consumed; fetch results promptly or raise the cap"
                )
            raise ActorDiedError(
                f"actor {refs.actor_id} died: {sess.dead_actors.get(refs.actor_id)}"
            )
        # Cached (bounded — see _Session.add_result) so repeated get()/wait()
        # on the same ref keep working.
        ok, value = sess.results[key]
    if ok:
        return value
    exc, tb = value
    if hasattr(exc, "add_note"):
        exc.add_note(f"[worker traceback]\n{tb}")
    raise exc


def wait(
    refs: Sequence[TaskRef],
    num_returns: int = 1,
    timeout: Optional[float] = None,
) -> Tuple[List[TaskRef], List[TaskRef]]:
    """Split ``refs`` into (done, pending); blocks until ``num_returns`` done
    or ``timeout`` elapses. ``timeout=0`` polls — the driver's result loop uses
    this exactly like the reference's ``ray.wait(timeout=0)`` poll
    (util.py:57-70)."""
    _c = _client_mode()
    if _c is not None:
        return _c.wait(refs, num_returns=num_returns, timeout=timeout)
    sess = _require_session()
    deadline = None if timeout is None else time.monotonic() + timeout
    with sess.cv:
        while True:
            done = [r for r in refs if _task_done(sess, r)]
            if len(done) >= min(num_returns, len(refs)):
                break
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                break
            sess.cv.wait(timeout=min(0.25, remaining) if remaining is not None else 0.25)
        done_set = {(r.actor_id, r.call_id) for r in done}
        pending = [r for r in refs if (r.actor_id, r.call_id) not in done_set]
    return done, pending


# --------------------------------------------------------------------------
# Actors
# --------------------------------------------------------------------------
class _RemoteMethod:
    def __init__(self, handle: "ActorHandle", name: str) -> None:
        self._handle = handle
        self._name = name

    def remote(self, *args: Any, **kwargs: Any) -> TaskRef:
        return self._handle._call(self._name, args, kwargs)

    def __repr__(self) -> str:
        return f"<RemoteMethod {self._handle.actor_id}.{self._name}>"


class ActorHandle:
    """Driver-side handle to a spawned actor process."""

    def __init__(
        self,
        actor_id: str,
        process: Any,
        conn: Any,
        node: Node,
        request: Dict[str, float],
        options: Dict[str, Any],
        pg_bundle: Optional[_Bundle] = None,
    ) -> None:
        self.actor_id = actor_id
        self._process = process
        self._conn = conn
        self._node = node
        self._request = request
        self._options = options
        self._pg_bundle = pg_bundle
        self._send_lock = threading.Lock()
        self._alive = True
        #: (monotonic receive time, stats dict) of the worker's newest
        #: heartbeat push (fabric/worker.py's heartbeat thread); None
        #: until the first one lands. Read via :func:`heartbeats`.
        self._last_heartbeat: Optional[Tuple[float, Dict[str, Any]]] = None
        self._reader = threading.Thread(
            target=self._reader_loop, name=f"fabric-reader-{actor_id}", daemon=True
        )
        self._reader.start()

    # -- introspection used by tests / launcher ---------------------------
    @property
    def node_id(self) -> str:
        return self._node.node_id

    @property
    def node_ip(self) -> str:
        return self._node.node_ip

    @property
    def allocated_resources(self) -> Dict[str, float]:
        return dict(self._request)

    @property
    def actor_options(self) -> Dict[str, Any]:
        return dict(self._options)

    def is_alive(self) -> bool:
        return self._alive and self._process.is_alive()

    # -- plumbing ---------------------------------------------------------
    def _reader_loop(self) -> None:
        sess = _session
        while True:
            try:
                msg = cloudpickle.loads(self._conn.recv_bytes())
            except (EOFError, OSError):
                break
            except Exception:  # noqa: BLE001 - deserialization failure
                break
            if msg[0] == "result":
                _, call_id, ok, value = msg
                if sess is not None:
                    with sess.cv:
                        sess.add_result((self.actor_id, call_id), (ok, value))
                        sess.cv.notify_all()
            elif msg[0] in ("ready", "ready_error"):
                if sess is not None:
                    with sess.cv:
                        sess.add_result(
                            (self.actor_id, -1), (msg[0] == "ready", msg[1])
                        )
                        sess.cv.notify_all()
            elif msg[0] == "heartbeat":
                # Worker-initiated health push (rss, cpu, call counters):
                # stored on the handle, surfaced via heartbeats() and the
                # obs registry — no call_id, nothing blocks on it.
                self._last_heartbeat = (time.monotonic(), msg[1])
                if msg[1].get("terminating"):
                    # The worker's SIGTERM handler ran: a CLEAN
                    # terminate, distinguishable from a heartbeat
                    # flatline (crash/SIGKILL) in the event log.
                    _record_event(
                        "worker_terminating",
                        actor=self.actor_id,
                        reason=str(msg[1].get("reason", "")),
                    )
        # Pipe closed: mark actor dead so blocked getters wake up, and release
        # its node resources so a relaunch after a crash can be placed.
        self._alive = False
        if sess is not None:
            with sess.cv:
                exitcode = self._process.exitcode
                # Only the FIRST death record is news: kill() already
                # logged an intentional termination.
                fresh = self.actor_id not in sess.dead_actors
                sess.dead_actors.setdefault(
                    self.actor_id, f"process exited (exitcode={exitcode})"
                )
                if sess.actors.pop(self.actor_id, None) is not None:
                    _release_actor_resources(self)
                sess.cv.notify_all()
            if fresh:
                _record_event(
                    "actor_death", level="warn",
                    actor=self.actor_id, exitcode=exitcode,
                )

    def _send(self, msg: Any) -> None:
        if not self._alive:
            raise ActorDiedError(f"actor {self.actor_id} is dead")
        payload = cloudpickle.dumps(msg, protocol=5)
        with self._send_lock:
            self._conn.send_bytes(payload)

    def _call(self, name: str, args: Tuple, kwargs: Dict) -> TaskRef:
        sess = _require_session()
        call_id = sess.next_id()
        blob = cloudpickle.dumps((name, args, kwargs), protocol=5)
        self._send(("call", call_id, blob))
        return TaskRef(actor_id=self.actor_id, call_id=call_id)

    def __getattr__(self, name: str) -> _RemoteMethod:
        if name.startswith("_"):
            raise AttributeError(name)
        return _RemoteMethod(self, name)

    def _shutdown(self, force: bool = False) -> None:
        if self._alive:
            try:
                self._send(("shutdown",))
            except Exception:  # noqa: BLE001
                pass
        self._process.join(timeout=0.1 if force else 5.0)
        if self._process.is_alive():
            self._process.terminate()
            # Generous grace: SIGTERM triggers the worker's atexit teardown,
            # which may itself be shutting down nested actors; SIGKILL too
            # early would orphan them.
            self._process.join(timeout=15.0)
            if self._process.is_alive():
                self._process.kill()
                self._process.join(timeout=2.0)
        self._alive = False


class ActorClass:
    """Result of ``fabric.remote(cls)``; spawn with ``.options(...).remote()``."""

    def __init__(self, cls: type, default_options: Optional[Dict[str, Any]] = None):
        self._cls = cls
        self._default_options = default_options or {}

    def options(self, **opts: Any) -> "ActorClass":
        merged = dict(self._default_options)
        merged.update(opts)
        return ActorClass(self._cls, merged)

    def remote(self, *args: Any, **kwargs: Any) -> ActorHandle:
        return _spawn_actor(self._cls, args, kwargs, self._default_options)


def remote(cls: type) -> "ActorClass":
    """Decorator/wrapper turning a class into a spawnable actor class."""
    _c = _client_mode()
    if _c is not None:
        return _c.remote(cls)
    return ActorClass(cls)


def _spawn_actor(
    cls: type,
    args: Tuple,
    kwargs: Dict,
    opts: Dict[str, Any],
) -> ActorHandle:
    sess = _require_session()
    request: Dict[str, float] = {}
    request["CPU"] = float(opts.get("num_cpus", 1) or 0)
    if opts.get("num_tpus"):
        request["TPU"] = float(opts["num_tpus"])
    for k, v in (opts.get("resources") or {}).items():
        request[k] = float(v)

    pg: Optional[PlacementGroup] = opts.get("placement_group")
    pg_bundle: Optional[_Bundle] = None
    actor_id = f"actor-{uuid.uuid4().hex[:8]}"

    def _give_back() -> None:
        """Undo the reservation of a spawn that produced no handle.
        Caller holds ``sess.lock``."""
        node.pinned.pop(actor_id, None)
        if pg_bundle is not None:
            for k, v in request.items():
                if v:
                    pg_bundle.remaining[k] = (
                        pg_bundle.remaining.get(k, 0.0) + v
                    )
        else:
            node.release(request)

    with sess.lock:
        if pg is not None:
            # Gang-scheduled: draw from the bundle's reservation, land on
            # the bundle's node (Ray's placement_group/bundle_index opts).
            idx = int(opts.get("placement_group_bundle_index", 0))
            if pg.removed:
                raise FabricError(f"placement group {pg.id} was removed")
            if not 0 <= idx < len(pg.bundles):
                raise ValueError(
                    f"bundle index {idx} out of range for {len(pg.bundles)}"
                    " bundles"
                )
            pg_bundle = pg.bundles[idx]
            short = {
                k: v
                for k, v in request.items()
                if v and pg_bundle.remaining.get(k, 0.0) < v - 1e-9
            }
            if short:
                raise InsufficientResourcesError(
                    f"actor requiring {request} does not fit bundle {idx} "
                    f"of {pg.id} (remaining {pg_bundle.remaining})"
                )
            for k, v in request.items():
                if v:
                    pg_bundle.remaining[k] -= v
            node = pg_bundle.node
        else:
            node = None
            for cand in sess.nodes:
                if cand.fits(request):
                    node = cand
                    break
            if node is None:
                raise InsufficientResourcesError(
                    f"cannot place actor requiring {request}; "
                    f"available per node: {[n.available() for n in sess.nodes]}"
                )
            node.acquire(request)
        try:
            # The caller's own env wins over the pin (an operator who sets
            # TPU_VISIBLE_CHIPS by hand is taken at their word).
            env = {
                **_pin_chips(node, actor_id, request),
                **(opts.get("env") or {}),
            }
        except BaseException:
            _give_back()
            raise

    try:
        proc, parent_conn = _boot_worker_process(actor_id, env, node)
    except BaseException:
        # Boot never produced a handle; hand the reservation back directly.
        with sess.lock:
            _give_back()
        raise
    handle = ActorHandle(
        actor_id, proc, parent_conn, node, request, opts, pg_bundle=pg_bundle
    )
    with sess.lock:
        sess.actors[actor_id] = handle

    # Ship the class + ctor args (after env application in the child).
    blob = cloudpickle.dumps((cls, args, kwargs), protocol=5)
    handle._send(("init", blob))
    if opts.get("lazy_init"):
        # Deferred construction: return the handle NOW and let the
        # caller barrier on readiness itself (a ping). Required for
        # gang spawns whose __init__s rendezvous with EACH OTHER
        # (jax.distributed.initialize blocks until every member
        # registers) — waiting for member 1's ctor before spawning
        # member 2 deadlocks by construction. A failed ctor still
        # surfaces: the worker answers every later call with
        # "actor not initialized", so the readiness ping raises.
        _record_event(
            "actor_start", actor=actor_id, node=node.node_id,
            cls=cls.__name__, lazy=True,
        )
        return handle
    # Wait for construction so init errors surface eagerly on the driver.
    try:
        get(TaskRef(actor_id=actor_id, call_id=-1), timeout=opts.get("init_timeout", 300.0))
    except BaseException:
        kill(handle)
        raise
    _record_event(
        "actor_start", actor=actor_id, node=node.node_id,
        cls=cls.__name__,
    )
    return handle


class _ProcHandle:
    """subprocess.Popen wrapped in the multiprocessing.Process API surface
    ActorHandle expects (is_alive/exitcode/join/terminate/kill)."""

    def __init__(self, popen: Any) -> None:
        self._p = popen

    def is_alive(self) -> bool:
        return self._p.poll() is None

    @property
    def exitcode(self) -> Optional[int]:
        return self._p.poll()

    def join(self, timeout: Optional[float] = None) -> None:
        import subprocess

        try:
            self._p.wait(timeout)
        except subprocess.TimeoutExpired:
            pass

    def terminate(self) -> None:
        self._p.terminate()

    def kill(self) -> None:
        self._p.kill()


def _boot_worker_process(actor_id: str, env: Dict[str, Any], node: Node):
    """Exec a fresh worker interpreter and hand back (process, connection).

    Uses ``python -m ray_lightning_tpu.fabric.worker`` + an AF_UNIX
    Listener — NOT multiprocessing.Process — so the child never replays the
    driver's ``__main__`` module (mp spawn would, re-running unguarded user
    scripts recursively). Env overrides are applied to the exec environment,
    i.e. strictly before the child interpreter (and thus jax) starts.
    """
    import secrets
    import subprocess
    import sys
    from multiprocessing.connection import Listener

    child_env = dict(os.environ)
    # Propagate the driver's import roots (mp spawn used to ship sys.path in
    # its preparation data; exec'd workers need it via PYTHONPATH so classes
    # cloudpickled *by reference* — e.g. from a test module or a script's
    # package — resolve in the child).
    driver_paths = [p for p in sys.path if p]
    inherited = child_env.get("PYTHONPATH", "")
    child_env["PYTHONPATH"] = os.pathsep.join(
        driver_paths + ([inherited] if inherited else [])
    )
    for key, value in env.items():
        if value is None:
            child_env.pop(key, None)
        elif key == "PYTHONPATH":
            # Merge rather than clobber: the driver sys.path entries above
            # are what let by-reference cloudpickles resolve in the child.
            child_env[key] = os.pathsep.join(
                [str(value), child_env.get("PYTHONPATH", "")]
            ).rstrip(os.pathsep)
        else:
            child_env[key] = str(value)
    # Logical node identity for actor code (rank math, IPs).
    child_env["RLT_NODE_ID"] = str(node.node_id)
    child_env["RLT_NODE_IP"] = str(node.node_ip)

    authkey = secrets.token_bytes(32)
    listener = Listener(family="AF_UNIX", authkey=authkey)
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_lightning_tpu.fabric.worker",
             str(listener.address)],
            env=child_env,
            stdin=subprocess.PIPE,
        )
        proc.stdin.write(authkey.hex().encode() + b"\n")
        # Second line: the driver's multiprocessing authkey. Manager/Queue
        # proxies authenticate with current_process().authkey, which
        # mp.Process children inherit automatically but exec'd workers do
        # not; the worker restores it so driver-owned proxies (tune queues)
        # keep working across any nesting depth.
        proc.stdin.write(mp.current_process().authkey.hex().encode() + b"\n")
        proc.stdin.flush()
        proc.stdin.close()
        # accept() has no timeout; run it in a thread and watch for the
        # child dying pre-connect so a boot crash can't hang the driver.
        box: Dict[str, Any] = {}

        def _accept() -> None:
            try:
                box["conn"] = listener.accept()
            except BaseException as exc:  # noqa: BLE001
                box["err"] = exc

        t = threading.Thread(target=_accept, daemon=True)
        t.start()
        deadline = time.monotonic() + 120.0
        while "conn" not in box and "err" not in box:
            if proc.poll() is not None:
                raise ActorDiedError(
                    f"actor {actor_id} worker process exited during boot "
                    f"(exitcode={proc.returncode})"
                )
            if time.monotonic() > deadline:
                proc.kill()
                raise ActorDiedError(f"actor {actor_id} boot timed out")
            t.join(timeout=0.05)
        if "err" in box:
            proc.kill()
            raise box["err"]
        return _ProcHandle(proc), box["conn"]
    finally:
        listener.close()


def kill(handle: ActorHandle, no_restart: bool = True) -> None:
    """Terminate an actor and release its resources (no restart semantics,
    matching ``ray.kill(no_restart=True)`` in ray_launcher.py:126).

    ``no_restart=False`` is REJECTED loudly: fabric actors have no
    restart machinery (no retained spawn spec, no supervision), so
    silently accepting the flag would promise a restart that never
    comes. Restartable serving replicas are the serve layer's job —
    ``serve.supervisor.FleetSupervisor`` re-runs a dead replica's
    original spawn via ``ServeClient.respawn_replica``.
    """
    if not no_restart:
        raise ValueError(
            "fabric.kill(no_restart=False) is unsupported: fabric actors "
            "are never restarted in place. For restartable serving "
            "replicas use serve.supervisor.FleetSupervisor (which "
            "re-runs the original spawn), then kill with the default "
            "no_restart=True."
        )
    _c = _client_mode()
    if _c is not None:
        _c.kill(handle)
        return
    sess = _require_session()
    # Record the intent BEFORE the process dies, so the reader thread's
    # subsequent death record is recognizably a consequence of this kill.
    if handle._alive:
        _record_event("actor_kill", actor=handle.actor_id)
    handle._shutdown(force=True)
    with sess.lock:
        if handle.actor_id in sess.actors:
            _release_actor_resources(handle)
            del sess.actors[handle.actor_id]
        sess.dead_actors.setdefault(handle.actor_id, "killed")
    with sess.cv:
        sess.cv.notify_all()
