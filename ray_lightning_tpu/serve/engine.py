"""Slot-based continuous-batching decode engine — folded, device-resident.

The bridge from ``gpt_generate`` (one static-shape batch, one user) to a
serving system: ONE compiled decode executable runs over a fixed
``(num_slots, max_seq)`` KV cache; requests are admitted into free slots
at fold boundaries (a bucketed prefill writes the slot's cache range),
finished slots are evicted and recycled — all without recompilation
(Orca-style iteration-level scheduling over vLLM-style slot-managed
caches).

Three compounding optimisations close the gap to the fused one-shot
``gpt_generate`` scan (which pays one dispatch for the whole decode,
while a naive engine pays dispatch + H2D state ship + blocking D2H token
sync per token):

- **Device-resident slot state.** ``cur``/``pos``/``temps``/``top_ks``/
  ``top_ps``/``keys`` plus the in-graph termination state (``active``
  mask, ``remaining`` token budget, per-slot ``eos``) live as donated
  device arrays threaded through the compiled step and updated in-graph
  — steady-state decode ships ZERO per-step H2D traffic. Admission and
  eviction update the device state through one small compiled slot-write
  executable (the same pattern as the per-bucket cache writes), so
  ``compiled_count`` stays frozen after construction.
- **Folded decode (``decode_fold=K``).** One compiled ``lax.scan``
  (``models/gpt.py:gpt_decode_fold``) executes K decode iterations per
  dispatch and returns a ``(K, num_slots)`` token block plus an emit
  mask. Length/EOS detection runs IN-GRAPH: a slot self-freezes mid-fold
  (cur/pos/rng stop advancing), so post-EOS tokens are never emitted and
  kept tokens' rng chains match an unfolded run bit-for-bit. K=1
  reproduces the unfolded engine exactly; larger K amortizes the
  dispatch + sync cost over K tokens at the price of admission latency
  (new requests join at fold boundaries).
- **Async double-buffered dispatch (``pipeline=True``).** ``step()``
  dispatches fold N+1 against the donated device state BEFORE blocking
  on fold N's token block (JAX async dispatch makes this free once the
  state is device-resident), so host token fan-out, streaming callbacks,
  and scheduler bookkeeping overlap device compute. Slots cancelled
  between dispatch and harvest may still decode one zombie fold; their
  tokens are dropped at harvest by identity against the dispatch-time
  snapshot, and the deactivate/admission writes queue AFTER the in-flight
  fold, so a recycled slot can never inherit a stale token.

The cache's layout follows who reads it. The single-device dense engine
keeps its slot cache as ``(L, slots, max_seq, Hkv * hd)``: a position's
KV heads side by side in one row. The decode step's scatter of new rows
and its two cache matmuls (queries laid out block-diagonally over the KV
heads, ``models/gpt.py:_attend_layer_cache``) then both take a layer's
cache where it lies; with the KV heads on an axis of their own the
scatter wants (slot, row, head, d), the matmul wants the rows minor, and
the TPU compiler copies the layer (268 MB at 64 slots x 2048) out of the
stack before every attention read (PERF.md §6, PR 28 and PR 29). The step
decides from the rank of the cache it is given, and on a TPU reads rows
through the decode kernel, which visits only the blocks up to each live
slot's position (``ops/decode_attention.py``; ``stats()["attn"]``). Every
other program of this engine (admission, chunked and piggybacked prefill,
the prefix pool's copy) converts the prompt's rows, one slot's strip or
one block at its boundary (``cache_strip`` / ``cache_strip_put``), never the
cache. Under a mesh the cache keeps ``(L, slots, max_seq, Hkv, hd)`` —
the head axis is what shards — and a paged engine has no dense cache;
pool blocks, spilled and exported blocks are ``(L, 1, block, Hkv, hd)``
in every engine. ``cache_stats()[kind]["row_layout"]`` says which read an
engine runs.

Exactness contract: a request decodes token-identically to a solo
``gpt_generate`` call (greedy), no matter which batchmates share its
steps and no matter the fold. Two properties deliver it, both asserted
in tests/test_serve.py:

- **Slot masks.** The shared step (``models/gpt.py:gpt_decode_step``)
  attends each slot only to ``position <= pos[slot]`` with exact ``-inf``
  masking — masked cache rows contribute exactly zero through the
  softmax, so cache length and stale rows from evicted tenants are
  invisible to the numerics.
- **Bucketed prefill.** Prompts are right-padded to a fixed bucket
  length; attention is causal, so the padded rows never influence the
  real rows, and only row ``len-1``'s logits are consumed. Compiles are
  per-bucket (all warmed at construction), never per-request.

Sampling is per-slot and traced (temperature/top-k/top-p/rng arrive as
arrays), so one executable serves any mix of sampling params, and each
request's rng chain is independent of its batchmates. Weight-only int8
parameter trees (utils/quantize.py) are consumed directly.

With decode folded and device-resident, admission is the remaining
head-of-line hazard: a long prompt's fused prefill is one monolithic
dispatch that stalls every resident decode slot until it completes, and
identical prompt prefixes are re-prefilled from scratch. Two mechanisms
remove both (Sarathi-Serve-style chunked prefill; RadixAttention-style
prefix reuse, pool-of-blocks form):

- **Chunked prefill (``prefill_chunk=C``).** Admission becomes a per-slot
  state machine: each :meth:`prefill_step` call extends the slot's KV by
  one C-token chunk (``models/gpt.py:gpt_prefill_chunk`` — a cache-seeded
  causal forward, one compiled executable per chunk bucket), so the
  scheduler interleaves chunks between decode folds instead of freezing
  them behind a whole-prompt dispatch. Mid-prefill the slot is parked
  inactive with its device ``pos`` pointing at the next chunk's first row
  — the only row an interleaved fold's idle-lane write can touch, and the
  next chunk overwrites it before reading — so interleaving never
  perturbs the numerics. The final chunk samples the first token and arms
  the slot in-graph, exactly like the fused admit.
- **Prefix caching (``prefix_blocks=N``).** A device-resident block pool
  (L, N, ``prefix_block``, Hkv, hd) — that layout in every engine, so
  spilled and exported blocks have one format — keyed by chained block
  digests of the token prefix. Admission walks the longest cached prefix, seeds the
  slot's KV rows through ONE compiled bidirectional cache-to-cache copy
  executable, and chunk-prefills only the suffix; completed prefills
  insert their new full blocks back (same executable, reversed). Blocks
  are ref-counted while a matching prefill is in flight and evicted LRU
  under pool pressure. K/V per position are a pure function of the token
  prefix, so a seeded slot decodes bit-identically to a cold prefill.
- **Tiered spill (``prefix_host_mb`` / ``prefix_disk_dir``).** The pool's
  capacity is spare HBM, so LRU eviction caps the cache at the top
  handful of prefixes. With tiers on, an evicted block SPILLS instead of
  dying: one compiled D2H pool read captures its K/V into a host-RAM
  tier (byte-budgeted, its own LRU), whose own evictions fall into an
  optional disk tier (``.npy`` files under ``prefix_disk_dir``, read
  back memory-mapped). Both tiers reuse the same chained digests as the
  tier-wide key; the admission walk falls through device -> host -> disk,
  and a cold hit PROMOTES the block back into the device pool through
  one compiled H2D pool write before the seeding copy runs. Both
  transfer executables are lowered at construction, so steady-state
  tier traffic never compiles; spilled bytes are bit-identical to the
  device originals (K/V are a pure function of the token prefix), so a
  promoted block decodes exactly like a device-resident one. Under a
  mesh, spill captures each block's per-device SHARDS and refill
  rebuilds the sharded array via ``make_array_from_callback`` — the
  full block never lands on one device, and a multi-host gang member
  only ever touches its own shards.

Both paths keep the contracts above: the compile count is frozen at
construction (chunk executables replace the per-bucket fused admits; one
copy executable), and greedy outputs stay bit-identical to solo
``gpt_generate`` across chunking x hit/miss x mid-prefill cancel
(asserted in tests/test_serve.py).

With admission fixed, the fold itself is the last per-token ceiling:
every emitted token still pays one full forward. Speculative decoding
(``spec='ngram'|'model'``, Leviathan-style propose-then-verify) converts
one forward into up to ``spec_depth + 1`` tokens per slot per fold
iteration: a cheap drafter proposes ``spec_depth`` tokens, ONE batched
verify forward (``models/gpt.py:gpt_decode_verify``) scores positions
``pos..pos+depth`` against the slot cache, and an in-graph accept scan
keeps the longest exactly-matching prefix — per-slot variable advance of
``pos``/``remaining``, masked row writes, rejected rows never touching
real state (the chunked-prefill masked-gather discipline). Two drafters
share the interface: ``ngram`` matches the tail of the slot's own token
history (``models/gpt.py:ngram_propose`` — zero extra weights, wins on
repetitive/code/chat suffixes), ``model`` runs a small separate GPT
(optionally int8) over a sliding history window. The token history the
drafters read is a device-resident (slots, max_seq) int32 array
maintained like the KV cache: one compiled write seeds the prompt at
admission, chunk executables heal their ranges, and the fold appends
accepted tokens in-graph. Both contracts hold by construction: drafter +
verify live INSIDE the one folded step executable (compile count frozen
at construction, ``compiles_since_init`` 0 in steady state), and every
emitted token is sampled from verify logits computed against
already-verified inputs — greedy accepts only exact argmax matches, so
outputs stay bit-identical to solo ``gpt_generate``, sampled slots
consume the identical rng chain, and a drafter can only ever change HOW
FAST tokens arrive, never WHICH tokens (asserted in tests/test_serve.py
across spec x depth x fold, mid-fold EOS inside an accepted block, and
cancel + recycle with a verify in flight).

All of the above is single-device; ``mesh=`` makes the engine
MESH-NATIVE (tensor-parallel decode across chips — the serving-side
analogue of the training meshes in ``parallel/``): attention heads, the
KV cache — ``(L, slots, max_seq, Hkv, hd)`` here, the head axis kept for
this — and the prefix pool shard over the mesh's "model"
axis (``models/gpt.py:DECODE_CACHE_AXES`` resolved through the same
``spec_from_logical`` rules the trainer uses; weights through
``gpt_param_shardings``), while slot metadata and the token history stay
replicated so admission bookkeeping and the per-fold harvest never cross
devices. Every executable above is lowered ONCE under the mesh with
donated sharded buffers — the compile count stays frozen at construction
with sharding on, and the per-fold D2H sync still moves only the
replicated token block. Exactness carries over: the sharded engine's
greedy output is bit-identical to the single-device engine for the same
model/config (the sharded contractions reassociate partial sums at the
~1e-7 level, orders of magnitude under greedy argmax margins; asserted
under the fp32 reference config in tests/test_serve_sharded.py across
plain x chunked-prefill-with-prefix-hit x spec=ngram). ``memory_stats()``
reports per-component resident bytes per device — the tp=N footprint
division, measured from the live shards.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_lightning_tpu.models.gpt import GPTConfig, engine_weights
from ray_lightning_tpu.obs.trace import SpanTotals, span


@dataclasses.dataclass
class SlotInfo:
    """Host-side record of one occupied slot."""

    request_id: str
    max_new_tokens: int
    n_generated: int
    eos_token: int  # -1 = disabled
    #: Prompt positions in the slot's cache: with ``n_generated`` the
    #: position a decode step stands on (``stats()["attn"]``).
    prompt_len: int = 0
    #: Host-side eviction marker: tokens an in-flight fold produced for a
    #: released tenant are dropped at harvest (the device keeps decoding a
    #: cancelled slot until its deactivate write lands).
    released: bool = False


@dataclasses.dataclass
class PrefillTask:
    """Host-side state machine of one in-progress chunked admission."""

    request_id: str
    tokens: np.ndarray  # (P,) int32 prompt
    next: int  # first position not yet prefilled (cache rows [0, next) live)
    max_new_tokens: int
    eos_token: int
    temperature: float
    top_k: int
    top_p: float
    key0: np.ndarray  # (2,) uint32 request PRNG key
    #: Tokens seeded from the prefix pool (suffix prefill starts there).
    matched_tokens: int = 0
    #: Pool block indices pinned (ref-counted) for this prefill's lifetime.
    block_refs: List[int] = dataclasses.field(default_factory=list)
    chunks: int = 0  # chunk dispatches so far


@dataclasses.dataclass
class _PoolBlock:
    """Host metadata of one occupied prefix-pool block."""

    digest: bytes
    refs: int = 0
    stamp: int = 0  # LRU clock (higher = more recently used)


def default_buckets(max_seq: int, lo: int = 16) -> Tuple[int, ...]:
    """Power-of-two prefill buckets up to ``max_seq`` (inclusive)."""
    out: List[int] = []
    b = lo
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(sorted(set(out)))


class DecodeEngine:
    """Continuous-batching decode over a fixed slot-indexed KV cache.

    Construction compiles everything (one FUSED admission per bucket —
    prefill + cache write + first-token sample + slot-state write in a
    single dispatch — one folded decode step, one slot-state write for
    eviction); admissions and steps afterwards only EXECUTE —
    ``compiled_count`` must not move, and the test suite asserts it
    doesn't.

    Host/device split: the caches AND all per-slot scalar state (current
    token, position, sampling knobs, rng keys, active/remaining/eos) live
    on device across calls, donated through the compiled executables —
    steady-state decode ships no per-step H2D traffic and syncs D2H once
    per fold (the token block). The host keeps only request bookkeeping
    (``SlotInfo``); :meth:`device_state` is the explicit sync point that
    materializes host mirrors. All methods must be driven from one
    thread (the scheduler loop).
    """

    def __init__(
        self,
        params: Any,
        config: GPTConfig | Dict[str, Any],
        num_slots: int = 4,
        max_seq: Optional[int] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
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
        kvstore_dir: Optional[str] = None,
        kvstore_mb: float = 0.0,
        kv_page: int = 0,
        kv_pages: int = 0,
        kvstore_namespace: Optional[str] = None,
        spec: str = "off",
        spec_depth: int = 4,
        spec_params: Any = None,
        spec_config: Any = None,
        spec_window: int = 32,
        mesh: Any = None,
    ) -> None:
        """``params`` (and ``spec_params``) come in the STORED layout of
        ``models/gpt.py:init_gpt_params`` — the interface to checkpoints,
        ``hf_import``, the trainer and the benchmark, int8 nodes
        included — as host or device arrays. The engine places them as
        stored and then holds, in ``self.params``, the tree
        ``models/gpt.py:engine_weights`` makes of that ONCE: SwiGLU's
        gate and up as two matrices, the GQA projections flat, so that
        none of the programs compiled below re-lays its weights out at
        its entry (PERF.md §6, PR 43). The model code takes either form
        and says which by the leaves it is given; no argument here
        chooses. Stored copies the engine placed itself are freed at the
        re-forming, before any cache is allocated; arrays the CALLER
        placed are never donated or deleted (the same tree may feed a
        solo ``gpt_generate`` or another engine), so a caller that wants
        their memory back drops its own reference."""
        import jax
        import jax.numpy as jnp

        if isinstance(config, dict):
            config = GPTConfig(**config)
        config.validate_variants()
        self.cfg = config
        if config.mixed:
            # Mixed layer kinds / held experts run through the dense
            # engine's bucketed admission and decode fold only. Every other
            # mode has a restatement of the block that does not know them:
            # refuse by name, before anything is placed or compiled.
            from ray_lightning_tpu.models.mixed import refuse_mixed
            from ray_lightning_tpu.utils.quantize import is_quantized

            for on, mechanism in (
                (kv_pages or kv_page, "a paged KV cache (kv_pages)"),
                (prefix_blocks or prefix_host_mb or prefix_disk_dir,
                 "the prefix pool (prefix_blocks)"),
                (kvstore_dir, "the KV store / KV fleet export (kvstore_dir)"),
                (prefill_chunk, "chunked prefill (prefill_chunk)"),
                (piggyback_chunks, "piggybacked prefill chunks (piggyback_chunks)"),
                (spec != "off", f"speculative decoding (spec={spec!r})"),
                (mesh is not None and mesh.size > 1,
                 "a serve mesh of more than one device"),
                (any(is_quantized(x) for x in jax.tree_util.tree_leaves(
                    params, is_leaf=is_quantized)), "int8 weights"),
            ):
                if on:
                    refuse_mixed(config, mechanism)
            mesh = None
        self.num_slots = int(num_slots)
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        self.decode_fold = int(decode_fold)
        if self.decode_fold < 1:
            raise ValueError("decode_fold must be >= 1")
        # Dynamic fold depth: a small ladder of fold-K rungs, ALL
        # pre-lowered at construction; _pick_fold_k chooses a rung per
        # dispatch from queue pressure, so ladder switches never compile.
        if fold_ladder:
            ladder = tuple(sorted({int(k) for k in fold_ladder}))
            if ladder[0] < 1:
                raise ValueError(
                    f"fold_ladder {list(fold_ladder)} rungs must be "
                    ">= 1 (decode iterations per dispatch)"
                )
            if self.decode_fold not in ladder:
                raise ValueError(
                    f"fold_ladder {list(ladder)} must include decode_fold"
                    f" {self.decode_fold} (the default rung)"
                )
        else:
            ladder = (self.decode_fold,)
        self.fold_ladder = ladder
        self.piggyback_chunks = int(piggyback_chunks)
        if not 0 <= self.piggyback_chunks <= self.num_slots:
            raise ValueError(
                f"piggyback_chunks {self.piggyback_chunks} must be in "
                f"[0, num_slots={self.num_slots}] (prefill-chunk rows "
                "fused into each decode dispatch; one row per slot)"
            )
        self.pipeline = bool(pipeline)
        self.max_seq = int(max_seq or config.max_seq)
        if self.max_seq > config.max_seq:
            raise ValueError(
                f"engine max_seq {self.max_seq} exceeds model max_seq "
                f"{config.max_seq}"
            )
        buckets = tuple(
            sorted(set(prefill_buckets or default_buckets(self.max_seq)))
        )
        if not buckets or buckets[-1] > self.max_seq:
            raise ValueError(
                f"prefill buckets {buckets} must be non-empty and <= "
                f"max_seq {self.max_seq}"
            )
        self.prefill_buckets = buckets
        # Paged KV (kv_pages > 0): the dense per-slot KV strips and the
        # prefix pool UNIFY into one refcounted page pool — slots hold
        # page-index tables into it, attention gathers pages in-graph,
        # a prefix hit is a table alias (refcount bump, zero copy), and
        # capacity becomes the token budget kv_pages * kv_page instead
        # of slots * max_seq. Pool page 0 is the reserved scratch page
        # (released slots' tables point there, absorbing the dense
        # paths' harmless garbage writes). Validated before anything is
        # placed or compiled, with errors naming the valid ranges.
        self.kv_pages = int(kv_pages)
        self.kv_page = int(kv_page) if kv_page else (16 if kv_pages else 0)
        self.paged = self.kv_pages > 0
        if kv_page and not self.paged:
            raise ValueError(
                "kv_page needs kv_pages > 0 (the paged-KV page budget); "
                "the dense engine takes neither"
            )
        if self.paged:
            if prefix_blocks:
                raise ValueError(
                    "paged KV (kv_pages > 0) unifies the prefix pool "
                    "into the page allocator — prefix sharing is built "
                    "in and keyed per kv_page-sized page; drop "
                    "prefix_blocks/prefix_block"
                )
            if not 1 <= self.kv_page <= self.max_seq or (
                self.max_seq % self.kv_page
            ):
                raise ValueError(
                    f"kv_page {self.kv_page} must divide the bucket "
                    f"sizes: a divisor of max_seq {self.max_seq} in "
                    f"[1, {self.max_seq}]"
                )
            min_pages = self.max_seq // self.kv_page + 1
            if self.kv_pages < min_pages:
                raise ValueError(
                    f"kv_pages {self.kv_pages} cannot hold one "
                    f"max-length request: need >= {min_pages} "
                    f"(max_seq {self.max_seq} / kv_page {self.kv_page} "
                    "+ the reserved scratch page)"
                )
        # Chunked-prefill mode: prefill_chunk > 0 (or any prefix pool /
        # paged KV — suffix-only prefill needs the cache-seeded chunk
        # path). Chunk lengths are bucketed like prompts, so compiles
        # stay per-bucket.
        if self.paged:
            # The unified pool rides the existing prefix-pool machinery:
            # the digest map, LRU, refcounts, spill tiers, and handoff
            # all operate on kv_page-sized pages.
            self.prefix_blocks = self.kv_pages
            self.prefix_block = self.kv_page
        else:
            self.prefix_blocks = int(prefix_blocks)
            self.prefix_block = int(prefix_block)
        if self.prefix_blocks and not prefill_chunk:
            prefill_chunk = buckets[-1]
        self.prefill_chunk = int(prefill_chunk)
        self.chunked = self.prefill_chunk > 0
        if self.piggyback_chunks and not self.chunked:
            raise ValueError(
                f"piggyback_chunks {self.piggyback_chunks} needs chunked "
                "prefill (prefill_chunk > 0, or any prefix pool / paged "
                "KV): only chunk-state-machine admissions can ride a "
                "decode dispatch"
            )
        if self.chunked:
            if self.prefill_chunk > self.max_seq:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} exceeds max_seq "
                    f"{self.max_seq}"
                )
            self.chunk_buckets = default_buckets(
                self.prefill_chunk, lo=min(16, self.prefill_chunk)
            )
        else:
            self.chunk_buckets = ()
        if self.prefix_blocks:
            if not 1 <= self.prefix_block <= self.max_seq:
                raise ValueError(
                    f"prefix_block {self.prefix_block} must be in "
                    f"[1, max_seq={self.max_seq}]"
                )
        # Spill tiers below the device pool: host RAM (prefix_host_mb
        # MiB), then an optional disk tier (prefix_disk_dir; its budget
        # defaults to 1 GiB when only the directory is given). Validated
        # before anything is placed or compiled.
        self.prefix_host_mb = float(prefix_host_mb)
        self.prefix_disk_dir = (
            str(prefix_disk_dir) if prefix_disk_dir else None
        )
        self.prefix_disk_mb = float(prefix_disk_mb)
        if self.prefix_host_mb < 0 or self.prefix_disk_mb < 0:
            raise ValueError("prefix tier budgets must be >= 0")
        if self.prefix_disk_dir and self.prefix_disk_mb == 0:
            self.prefix_disk_mb = 1024.0
        if (
            self.prefix_host_mb > 0 or self.prefix_disk_dir
        ) and not self.prefix_blocks:
            raise ValueError(
                "prefix tiers (prefix_host_mb / prefix_disk_dir) need a "
                "device prefix pool (prefix_blocks > 0) to spill from"
            )
        # Persistent object-store tier (tier of last resort, fleet
        # shared): evictions that would otherwise die at the bottom of
        # the local tier walk write through here instead, and the fleet
        # plane fetches from it when no live peer holds a chain. Unlike
        # the disk tier the store is NOT adopted into this engine's own
        # maps at startup (gang op-stream determinism — see
        # _disk_prune_stale); warm content re-enters only through the
        # directory + fetch path.
        self.kvstore_dir = str(kvstore_dir) if kvstore_dir else None
        self.kvstore_mb = float(kvstore_mb)
        self.kvstore: Any = None
        self.kvstore_namespace: Optional[str] = None
        if self.kvstore_dir:
            from ray_lightning_tpu.obs.registry import get_registry
            from ray_lightning_tpu.serve.kvstore import (
                FleetKVStore,
                kvstore_namespace as _kvs_ns,
            )

            # Store identity: the shared store is content-addressed by
            # token digests, which do NOT encode the model — namespace
            # every key by the checkpoint identity (path + config hash
            # when build_engine supplies it; config hash alone
            # otherwise) so one store can never serve pages across
            # model versions.
            self.kvstore_namespace = (
                str(kvstore_namespace)
                if kvstore_namespace
                else _kvs_ns(None, config)
            )
            self.kvstore = FleetKVStore(
                self.kvstore_dir,
                budget_mb=self.kvstore_mb,
                registry=get_registry(),
                namespace=self.kvstore_namespace,
            )
        # Mesh-native serving (tensor-parallel decode): with a mesh
        # bound, every per-slot device tensor becomes a mesh-sharded
        # jax.Array — attention heads (and the Hkv-headed KV cache +
        # prefix pool) split over the "model" axis, slot metadata and
        # token history replicated so harvest/bookkeeping never cross
        # devices — and every executable below is lowered ONCE under the
        # mesh with donated sharded buffers. ``mesh=None`` is the
        # single-device engine, unchanged byte for byte.
        self.mesh = mesh
        self._rep_sh = None
        self._cache_sh = None
        self._pool_sh = None
        self._blk_sh = None
        self._params_sh = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ray_lightning_tpu.models.gpt import (
                DECODE_CACHE_AXES,
                check_decode_mesh,
                gpt_param_shardings,
            )
            from ray_lightning_tpu.parallel.logical import (
                DEFAULT_RULES,
                spec_from_logical,
            )

            # Before anything is placed or compiled: a mesh that cannot
            # shard this config's heads must reject instantly.
            check_decode_mesh(config, mesh)
            self._rep_sh = NamedSharding(mesh, P())
            L_, Hkv_, hd_ = config.n_layer, config.kv_head, config.head_dim
            self._cache_sh = NamedSharding(
                mesh,
                spec_from_logical(
                    (L_, self.num_slots, self.max_seq, Hkv_, hd_),
                    DECODE_CACHE_AXES,
                    DEFAULT_RULES,
                    mesh,
                ),
            )
            if self.prefix_blocks:
                self._pool_sh = NamedSharding(
                    mesh,
                    spec_from_logical(
                        (L_, self.prefix_blocks, self.prefix_block, Hkv_,
                         hd_),
                        DECODE_CACHE_AXES,
                        DEFAULT_RULES,
                        mesh,
                    ),
                )
                # One pool block's sharding (same logical axes, block
                # dim 1): the spill/refill transfer unit — captured
                # shards and rebuilt arrays both carry it.
                self._blk_sh = NamedSharding(
                    mesh,
                    spec_from_logical(
                        (L_, 1, self.prefix_block, Hkv_, hd_),
                        DECODE_CACHE_AXES,
                        DEFAULT_RULES,
                        mesh,
                    ),
                )
            self._params_sh = gpt_param_shardings(params, config, mesh)
        # Speculative decoding: drafter + depth, validated before any
        # compile so a bad spec rejects instantly.
        self.spec = str(spec)
        if self.spec not in ("off", "ngram", "model"):
            raise ValueError(
                f"unknown spec mode {spec!r}; use 'off', 'ngram', or "
                "'model'"
            )
        self.spec_depth = int(spec_depth)
        if self.spec != "off" and self.spec_depth < 1:
            raise ValueError("spec_depth must be >= 1")
        self.spec_window = int(spec_window)
        self._spec_params = None
        self._spec_cfg: Optional[GPTConfig] = None
        if self.spec == "model":
            if spec_params is None or spec_config is None:
                raise ValueError(
                    "spec='model' needs spec_params and spec_config (the "
                    "draft model's weights and GPTConfig)"
                )
            if isinstance(spec_config, dict):
                spec_config = GPTConfig(**spec_config)
            spec_config.validate_variants()
            if spec_config.vocab_size != config.vocab_size:
                raise ValueError(
                    f"draft model vocab {spec_config.vocab_size} != main "
                    f"vocab {config.vocab_size}"
                )
            if self.spec_window < 1:
                raise ValueError("spec_window must be >= 1")
            if self.spec_window + self.spec_depth > spec_config.max_seq:
                raise ValueError(
                    f"spec_window ({self.spec_window}) + spec_depth "
                    f"({self.spec_depth}) exceeds the draft model's "
                    f"max_seq ({spec_config.max_seq})"
                )
            self._spec_cfg = spec_config
            # Draft weights stay REPLICATED under a mesh: the drafter is
            # small by design, and a replicated draft keeps its proposals
            # (and therefore the accept scan) a pure per-device SPMD
            # computation with zero collective traffic. Re-formed like
            # the main tree (below): the draft runs the same prefill and
            # decode step.
            self._spec_params = engine_weights(
                jax.tree_util.tree_map(
                    (
                        (lambda a: jax.device_put(
                            jnp.asarray(a), self._rep_sh))
                        if mesh is not None
                        else jnp.asarray
                    ),
                    spec_params,
                ),
                spec_config,
            )
        # Host accept accounting (read by spec_stats / the scheduler).
        self.spec_verifies = 0
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_emitted_tokens = 0
        # Placed as stored, held re-formed (the docstring); what was
        # placed here dies with ``placed``.
        if mesh is not None:
            placed = jax.tree_util.tree_map(
                lambda a, s: jax.device_put(jnp.asarray(a), s),
                params,
                self._params_sh,
            )
        else:
            placed = jax.tree_util.tree_map(jnp.asarray, params)
        self.params = engine_weights(placed, config)
        del placed

        cdt = jnp.dtype(config.compute_dtype)
        L, Hkv, hd = config.n_layer, config.kv_head, config.head_dim
        B, S = self.num_slots, self.max_seq
        if self.paged:
            # No dense slot strips: the page pool below IS the KV cache,
            # and each slot's view of it is its page table row — zeros
            # (the scratch page) until admission allocates real pages.
            self._k = None
            self._v = None
            self._table = self._dfull(
                (B, S // self.kv_page), jnp.int32, self._rep_sh
            )
        elif config.mixed:
            # Caches side by side, one a mixer kind: the full layers keep
            # max_seq rows a slot, the window layers a ring of
            # ring_rows(cfg), the latent layers max_seq latents and
            # rotary keys, a state layer one state and its conv tail
            # (models/mixed.py).
            from ray_lightning_tpu.models.mixed import empty_caches

            self._k, self._v = empty_caches(config, B, S, cdt)
            self._table = None
        else:
            # One device: a position's KV heads side by side in one row.
            # The decode step's scatter and its two cache matmuls then
            # both take a layer's cache where it lies (models/gpt.py:
            # _attend_layer_cache); with a head axis the TPU compiler
            # copies the layer out of the stack every token step. Under a
            # mesh the KV heads keep that axis: DECODE_CACHE_AXES shards
            # it over "model", and each device contracts its own heads.
            shape = (
                (L, B, S, Hkv * hd) if mesh is None else (L, B, S, Hkv, hd)
            )
            self._k = self._dfull(shape, cdt, self._cache_sh)
            self._v = self._dfull(shape, cdt, self._cache_sh)
            self._table = None
        # Prefix pool: device-resident K/V blocks + host digest map/LRU.
        if self.prefix_blocks:
            self._pool_k = self._dfull(
                (L, self.prefix_blocks, self.prefix_block, Hkv, hd), cdt,
                self._pool_sh,
            )
            self._pool_v = self._dfull(
                (L, self.prefix_blocks, self.prefix_block, Hkv, hd), cdt,
                self._pool_sh,
            )
        self._pool_map: Dict[bytes, int] = {}
        self._pool_meta: List[Optional[_PoolBlock]] = [None] * self.prefix_blocks
        # Paged mode reserves pool page 0 as the scratch sink — never
        # allocated, never read; its meta stays None forever.
        self._pool_free: List[int] = list(
            range(1 if self.paged else 0, self.prefix_blocks)
        )
        self._pool_tick = 0
        #: Paged bookkeeping: per-slot page lists (table entries that
        #: are real, aliased prefix pages first), the token span each
        #: slot's allocation must cover (min(P + new, S - 1) + 1 — the
        #: fragmentation stat's denominator), and the QUARANTINE of
        #: freed private pages that the one in-flight fold (dispatched
        #: before their slot's table reset) may still scribble —
        #: recycled only after that fold's harvest has synced.
        self._slot_pages: List[List[int]] = [[] for _ in range(self.num_slots)]
        self._slot_span: List[int] = [0] * self.num_slots
        self._quarantine: List[int] = []
        self.page_allocs = 0
        self.page_frees = 0
        self.page_alias_hits = 0
        self.prefix_lookups = 0
        self.prefix_hit_tokens = 0
        self.prefix_prompt_tokens = 0
        self.prefix_inserts = 0
        self.prefix_evictions = 0
        # -- spill tiers (host RAM, then disk) ---------------------------
        # Budgets are enforced on LOGICAL block bytes (one K + one V
        # block), so a byte budget means the same cache capacity whether
        # or not a mesh splits the resident shards across processes.
        self._blk_shape = (L, 1, self.prefix_block, Hkv, hd)
        self._blk_dtype = np.dtype(cdt)
        self._blk_nbytes = (
            2 * int(np.prod(self._blk_shape)) * cdt.itemsize
        )
        self._host_budget = int(self.prefix_host_mb * (1 << 20))
        self._disk_budget = (
            int(self.prefix_disk_mb * (1 << 20))
            if self.prefix_disk_dir
            else 0
        )
        self._tiered = self._host_budget > 0 or self._disk_budget > 0
        #: digest -> (k_payload, v_payload), oldest first (the tier's
        #: LRU). A payload is the full np block single-device, or
        #: {shard_index: np_shard} of THIS process's shards under a mesh.
        self._host_map: "OrderedDict[bytes, Tuple[Any, Any]]" = (
            OrderedDict()
        )
        #: digest -> on-disk bytes, oldest first; files live under
        #: ``prefix_disk_dir`` as ``<digest-hex>.{keys,k,v}.npy``.
        self._disk_map: "OrderedDict[bytes, int]" = OrderedDict()
        self._disk_bytes = 0
        if self._disk_budget:
            os.makedirs(self.prefix_disk_dir, exist_ok=True)
            self._disk_prune_stale()
        #: Cumulative per-tier accounting (the scheduler diffs these into
        #: ServeMetrics): hits/misses are digest-walk probes; spills are
        #: blocks moved one tier colder (still alive); promotions are
        #: blocks moved back into the device pool; evictions are blocks
        #: dropped from the tier entirely.
        self.tier_counters: Dict[str, Dict[str, int]] = {
            t: {
                "hits": 0, "misses": 0, "spills": 0,
                "promotions": 0, "evictions": 0,
            }
            for t in ("device", "host", "disk")
        }
        #: Host-side seconds spent refilling promoted blocks (payload
        #: assembly + the compiled H2D dispatch): ``refill_s`` of
        #: :meth:`prefix_stats`, hence of the replica's ``stats()``.
        self.refill_s = 0.0
        #: Cross-replica KV handoff accounting: blocks this engine
        #: serialized out for a migrating request (export) and blocks it
        #: accepted from a dying peer (import). Read by
        #: tests/test_preempt.py and tests/test_kvfleet.py as exact counts.
        self.prefix_handoff_exports = 0
        self.prefix_handoff_imports = 0
        #: Digests DROPPED from every tier (evicted with nowhere to
        #: spill, pruned from disk, unreadable): the fleet directory's
        #: eviction-invalidation feed. A bounded ring of recent hexes +
        #: a lifetime count ride the stats endpoint; the driver forgets
        #: them idempotently, so re-reporting across scrapes is safe.
        self._dropped_ring: "deque[str]" = deque(maxlen=256)
        self.kv_dropped_total = 0

        # Per-slot DEVICE state (fixed shapes: one step signature forever;
        # replicated under a mesh — slot writes and the per-fold harvest
        # stay device-local).
        rep = self._rep_sh
        self._cur = self._dfull((B,), jnp.int32, rep)
        self._pos = self._dfull((B,), jnp.int32, rep)
        self._temps = self._dfull((B,), jnp.float32, rep)
        self._top_ks = self._dfull((B,), jnp.int32, rep)
        self._top_ps = self._dfull((B,), jnp.float32, rep, fill=1)
        self._keys = self._dfull((B, 2), jnp.uint32, rep)
        self._active = self._dfull((B,), jnp.bool_, rep)
        self._remaining = self._dfull((B,), jnp.int32, rep)
        self._eos = self._dfull((B,), jnp.int32, rep, fill=-1)
        #: Device-resident per-slot token history (hist[b, p] = token at
        #: position p) — what the spec drafters read. Maintained like the
        #: KV cache: prompt seeded by a compiled write at admission,
        #: chunk executables heal their ranges, the fold appends accepted
        #: tokens in-graph. None when spec is off (zero cost).
        self._hist = (
            self._dfull((B, S), jnp.int32, rep)
            if self.spec != "off"
            else None
        )
        self._slots: List[Optional[SlotInfo]] = [None] * B
        #: slot -> in-progress chunked admission (chunked mode only).
        self._prefills: Dict[int, PrefillTask] = {}
        #: Chunk completions of piggybacked FINAL rows, REPLACED at each
        #: harvest (bounded by piggyback_chunks; the scheduler drains it
        #: via pop_chunk_events — a host-side read, never broadcast, so
        #: gang followers that never pop cannot leak).
        self._pb_events: List[Tuple[int, PrefillTask, int, bool]] = []
        #: Layer-pipelined imports in flight: digest -> staging record
        #: {"idx": pool block, "next": layer expected, "n": n_layers}.
        #: Staged blocks are UNKEYED (meta.digest None) and ref-pinned —
        #: invisible to prefix matching and safe from eviction until the
        #: last layer lands or the transfer aborts.
        self._layer_imports: Dict[bytes, Dict[str, int]] = {}
        self.layer_block_imports = 0
        self.layer_import_aborts = 0
        #: Fused-dispatch accounting (stats blocks + registry metrics).
        self.piggyback_dispatches = 0
        self.piggyback_chunk_rows = 0
        self.fold_dispatches: Dict[int, int] = {
            k: 0 for k in self.fold_ladder
        }
        #: The expert layers' counts, monotone since construction: they
        #: leave the device with the tokens (fold) or the first token
        #: (admission) and are added up where those are fetched. Mixed
        #: configurations with expert layers only; else it stays zeros.
        self.moe_totals: Dict[str, Dict[str, int]] = {
            "decode": {"pairs_routed": 0, "pairs_held": 0,
                       "experts_hit": 0, "token_steps": 0},
            "prefill": {"pairs_routed": 0, "pairs_held": 0,
                        "experts_hit": 0, "admissions": 0},
        }
        #: The state layers' counts, fetched with the same vectors:
        #: slot-steps of the folds (every slot, every iteration), those of
        #: live requests and those whose state the step read and wrote
        #: (the live ones under the kernel that walks them, else all);
        #: rows an admission scanned (its bucket) and those that were
        #: prompt. Zeros without state layers.
        self.ssm_totals: Dict[str, Dict[str, int]] = {
            "decode": {"slot_steps": 0, "slot_steps_live": 0, "slot_steps_visited": 0},
            "prefill": {"rows_scanned": 0, "rows_real": 0},
        }
        from ray_lightning_tpu.models.gpt import decode_reads
        from ray_lightning_tpu.models.mixed import count_kind, prefill_reads

        self._state_layers = count_kind(config, "ssm")
        #: The decode reads ``attn_totals`` counts, by the kind of cache
        #: read: ``(layers, rows of the decode kernel's block, or 0 where
        #: the read is XLA's over every allocated row)`` — the model's own
        #: answer (models/gpt.py:decode_reads) for the step this engine
        #: folds: every layer of a uniform configuration (under None); of
        #: mixed layer kinds the full and the latent ones, whose rows are a
        #: request's positions (a window kind's ring is read whole, a
        #: slot's last ``attn_window`` positions, and not counted).
        self._attn_reads: Dict[Optional[str], Tuple[int, int]] = decode_reads(
            config, 1 if self.spec == "off" else self.spec_depth + 1,
            self._k, self._v,
        )
        #: What the decode steps' cached attention read, in cache rows
        #: summed over token steps and layers: the rows allocated to the
        #: slots, those the read visited and those of live requests'
        #: positions. Counted on the host at each harvest from the
        #: positions the slots' records hold (no fetch, no sync).
        self.attn_totals: Dict[str, int] = {
            "rows_allocated": 0, "rows_visited": 0, "rows_live": 0,
            "prefill_rows": 0, "prefill_rows_kernel": 0,
            "prefill_tiles": 0, "prefill_tiles_visited": 0,
        }
        #: What an admission of mixed layer kinds adds to the four prefill
        #: counts, by bucket (``_count_prefill``): the attention layers, those
        #: of them whose read is the causal square, those of them the forward
        #: flash kernel reads and the rows of a score tile — the model's own
        #: answer (models/mixed.py:prefill_reads).
        self._prefill_layers: Dict[int, Tuple[int, int, int, int]] = (
            {pb: prefill_reads(config, pb) for pb in buckets}
            if config.mixed else {}
        )
        from ray_lightning_tpu.obs.registry import get_registry as _greg

        _reg = _greg()
        self._m_pb_dispatches = _reg.counter(
            "rlt_serve_piggyback_dispatches_total",
            "Decode dispatches that carried >= 1 piggybacked prefill "
            "chunk row",
        )
        self._m_pb_rows = _reg.counter(
            "rlt_serve_piggyback_chunk_rows_total",
            "Prefill chunk rows run inside decode dispatches",
        )
        self._m_fold_depth = _reg.histogram(
            "rlt_serve_fold_depth",
            "Fold depth K chosen per decode dispatch",
            buckets=(1, 2, 4, 8, 16, 32, 64),
        )
        #: Double buffer: ((tok_block, emit_block, pb_toks|None, moe|None),
        #: dispatch-time slot snapshot, piggybacked finals, fold K) of
        #: the fold currently executing on device.
        self._inflight: Optional[
            Tuple[
                Tuple[Any, Any, Any, Any],
                List[Optional[SlotInfo]],
                List[Tuple[int, int, PrefillTask, Optional[SlotInfo]]],
                int,
            ]
        ] = None
        #: Optional obs.trace.RequestTracer: the engine records the spans
        #: only it can see (prefill dispatches, chunk advances, prefix
        #: seeds). Set by the Scheduler/ServeReplica after construction;
        #: None keeps the hot paths branch-only.
        self.tracer: Optional[Any] = None
        #: Optional obs.events.EventLog: coarse engine happenings only a
        #: forensic log cares about (prefix-pool evictions). Set by the
        #: Scheduler/ServeReplica after construction; None = off.
        self.events: Optional[Any] = None
        #: What the driving thread does, by name (obs.trace.span), and
        #: the host time the device waits for: this engine says when a
        #: program goes in flight and when a sync shows the queue empty.
        #: The Scheduler and the ServeReplica time their own parts of
        #: the loop into the same totals.
        self.spans = SpanTotals()

        self.compiled_count = 0
        self._compile()

    @staticmethod
    def _dfull(shape, dtype, sharding, fill=0):
        """Fresh device state, placed: plain ``jnp.full`` single-device,
        or a sharded jax.Array assembled shard-by-shard under a mesh —
        the full tensor is never materialized on one device (holding
        state bigger than one chip's HBM is the point of the mesh), and
        the buffers are fresh, so donation can never free a caller's
        array."""
        import jax
        import jax.numpy as jnp

        dtype = jnp.dtype(dtype)
        if sharding is None:
            return jnp.full(shape, fill, dtype)

        def shard(idx):
            dims = []
            for dim, sl in zip(shape, idx):
                start, stop, _ = sl.indices(dim)
                dims.append(stop - start)
            return np.full(tuple(dims), fill, dtype)

        return jax.make_array_from_callback(tuple(shape), sharding, shard)

    # -- compilation (all of it, up front) -------------------------------
    def _compile(self) -> None:
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.models.gpt import (
            cache_strip,
            cache_strip_put,
            gpt_decode_fold,
            gpt_decode_fold_spec,
            gpt_final_norm,
            gpt_logits,
            gpt_prefill,
            gpt_prefill_chunk,
            model_propose,
            ngram_propose,
            sample_logits_batched,
        )

        cfg = self.cfg
        # Mesh mode: every aval carries its array's sharding, so each
        # executable lowers ONCE under the mesh with the partitioner
        # seeing exactly the layouts the donated buffers will arrive in;
        # out_shardings pin the round-tripped state to the same layouts
        # (donation aliasing + a stable call signature forever).
        mesh_on = self.mesh is not None
        p_spec = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if mesh_on else None
            ),
            self.params,
        )

        def spec(arr):
            return jax.ShapeDtypeStruct(
                np.shape(arr),
                arr.dtype,
                sharding=arr.sharding if mesh_on else None,
            )

        def jit_exec(fn, donate, out_sh):
            kw: Dict[str, Any] = {"donate_argnums": donate}
            if mesh_on:
                kw["out_shardings"] = out_sh
            return jax.jit(fn, **kw)

        rep_sh = self._rep_sh  # None single-device; unused then
        cache_out = self._cache_sh
        pool_out = self._pool_sh
        state_out = (rep_sh,) * 9

        def admit_impl(
            params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps,
            keys, active, remaining, eos_toks, prompt, last_idx, slot,
            key0, temp, tk, tp, n_new, eos,
        ):
            # The WHOLE admission in one dispatch: bucketed prefill, cache
            # write into the slot's rows [0, Pb), first-token sample, and
            # the slot's full scalar-state write — one executable chain
            # per admit instead of four, so a burst of admissions doesn't
            # pay 4x the dispatch latency per request. The slot
            # deactivates itself in-graph when the request is already
            # done at its first token (n_new == 1 or eos).
            if cfg.mixed:
                # Rows past the prompt's end route to no expert and leave
                # a state layer's state as it was; the full layers take all
                # Pb rows, the window layers' ring the prompt's last rows, a
                # state layer's state and conv tail are written whole; the
                # layers' counts come out with the first token.
                from ray_lightning_tpu.models.mixed import (
                    mixed_rows,
                    write_prefill_rows,
                )

                h, pf_k, pf_v, moe = mixed_rows(
                    params, cfg, prompt, true_len=last_idx + 1, prefill=True
                )
                k_cache, v_cache = write_prefill_rows(
                    k_cache, v_cache, pf_k, pf_v, slot, last_idx + 1
                )
            else:
                h, pf_k, pf_v = gpt_prefill(
                    params, cfg, prompt, mesh=self.mesh
                )
                k_cache = cache_strip_put(k_cache, pf_k, slot, 0)
                v_cache = cache_strip_put(v_cache, pf_v, slot, 0)
            h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)
            h_last = gpt_final_norm(params, cfg, h_last)[:, 0]
            logits = gpt_logits(params, cfg, h_last)
            key, sub = jax.random.split(key0)
            tok = sample_logits_batched(
                sub[None], logits, temp[None], tk[None], tp[None]
            )[0]
            live = (n_new > 1) & (tok != eos)

            def upd(arr, v):
                return jax.lax.dynamic_update_index_in_dim(arr, v, slot, 0)

            return (
                k_cache,
                v_cache,
                upd(cur, tok),
                upd(pos, last_idx + 1),
                upd(temps, temp),
                upd(top_ks, tk),
                upd(top_ps, tp),
                upd(keys, key),
                upd(active, live),
                upd(remaining, n_new - 1),
                upd(eos_toks, eos),
                tok,
            ) + ((moe,) if cfg.mixed else ())

        # The fold factories take fold-K explicitly: one executable per
        # ladder rung, all pre-lowered below, so _pick_fold_k switches
        # depth per dispatch with zero steady-state compiles. The *pb
        # tail (empty when piggyback is off) carries the fused
        # prefill-chunk rows — appended AFTER the existing args so the
        # donation indices never move.
        def make_step_impl(fold_k):
            def step_impl(
                params, k_cache, v_cache, cur, pos, temps, top_ks,
                top_ps, keys, active, remaining, eos_toks, *pb,
            ):
                return gpt_decode_fold(
                    params, cfg, cur, pos, keys, temps, top_ks, top_ps,
                    active, remaining, eos_toks, k_cache, v_cache,
                    fold=fold_k, piggyback=pb or None,
                )

            return step_impl

        # Speculative step: drafter + verify + accept live INSIDE the one
        # folded executable — one dispatch per fold iteration, compile
        # count unchanged by the drafter choice.
        def make_step_spec_impl(fold_k):
            def step_spec_impl(
                params, k_cache, v_cache, cur, pos, temps, top_ks,
                top_ps, keys, active, remaining, eos_toks, hist, *pb,
            ):
                return gpt_decode_fold_spec(
                    params, cfg, cur, pos, keys, temps, top_ks, top_ps,
                    active, remaining, eos_toks, hist, k_cache, v_cache,
                    fold=fold_k, depth=self.spec_depth,
                    draft_fn=lambda h, p, c: ngram_propose(
                        h, p, c, depth=self.spec_depth
                    ),
                    piggyback=pb or None,
                )

            return step_spec_impl

        def make_step_spec_model_impl(fold_k):
            def step_spec_model_impl(
                params, dparams, k_cache, v_cache, cur, pos, temps,
                top_ks, top_ps, keys, active, remaining, eos_toks, hist,
                *pb,
            ):
                return gpt_decode_fold_spec(
                    params, cfg, cur, pos, keys, temps, top_ks, top_ps,
                    active, remaining, eos_toks, hist, k_cache, v_cache,
                    fold=fold_k, depth=self.spec_depth,
                    draft_fn=lambda h, p, c: model_propose(
                        dparams, self._spec_cfg, h, p, c,
                        depth=self.spec_depth, window=self.spec_window,
                        mesh=self.mesh,
                    ),
                    piggyback=pb or None,
                )

            return step_spec_model_impl

        def hist_write_impl(hist, slot, row, length):
            # Seed one slot's token history rows [0, length) from a
            # padded (1, S) prompt row — the history analog of the
            # per-bucket cache writes (one executable, any prompt len).
            S_ = hist.shape[1]
            rows_ = jnp.arange(S_, dtype=jnp.int32)
            old = jax.lax.dynamic_slice(hist, (slot, 0), (1, S_))
            new = jnp.where((rows_ < length)[None], row, old)
            return jax.lax.dynamic_update_slice(hist, new, (slot, 0))

        def slot_write_impl(
            cur, pos, temps, top_ks, top_ps, keys, active, remaining,
            eos_toks, slot, cur_v, pos_v, temp_v, tk_v, tp_v, key_v,
            active_v, rem_v, eos_v,
        ):
            # One slot's full scalar state in one tiny executable —
            # admission (active_v=True) and eviction (active_v=False)
            # share it, so occupancy changes never recompile.
            def upd(arr, v):
                return jax.lax.dynamic_update_index_in_dim(arr, v, slot, 0)

            return (
                upd(cur, cur_v),
                upd(pos, pos_v),
                upd(temps, temp_v),
                upd(top_ks, tk_v),
                upd(top_ps, tp_v),
                upd(keys, key_v),
                upd(active, active_v),
                upd(remaining, rem_v),
                upd(eos_toks, eos_v),
            )

        # K and V alike but for a configuration with mixed layer kinds,
        # whose caches are dicts and whose v rows are narrower.
        cache_spec = (
            jax.tree_util.tree_map(spec, self._k)
            if self._k is not None
            else None
        )
        vcache_spec = (
            jax.tree_util.tree_map(spec, self._v)
            if self._v is not None
            else None
        )
        state_specs = (
            spec(self._cur),
            spec(self._pos),
            spec(self._temps),
            spec(self._top_ks),
            spec(self._top_ps),
            spec(self._keys),
            spec(self._active),
            spec(self._remaining),
            spec(self._eos),
        )
        sc_sh = rep_sh if mesh_on else None  # host scalars: replicated
        i32 = jax.ShapeDtypeStruct((), np.int32, sharding=sc_sh)
        f32 = jax.ShapeDtypeStruct((), np.float32, sharding=sc_sh)
        b1 = jax.ShapeDtypeStruct((), np.bool_, sharding=sc_sh)
        key_spec = jax.ShapeDtypeStruct((2,), np.uint32, sharding=sc_sh)

        L = cfg.n_layer
        Hkv, hd = cfg.kv_head, cfg.head_dim
        S = self.max_seq

        def chunk_impl(
            params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps,
            keys, active, remaining, eos_toks, chunk, start, true_len,
            slot, key0, temp, tk, tp, n_new, eos, is_final,
        ):
            # One prefill chunk of one slot, fused: cache-seeded causal
            # forward over the chunk, masked K/V write into the slot's
            # rows [start, start+true_len), and — on the FINAL chunk —
            # the first-token sample plus the slot's arming state write
            # (the chunked analog of admit_impl). Non-final chunks park
            # the slot inactive with pos = start+true_len: the only row
            # an interleaved fold's idle-lane write can scribble on, and
            # the next chunk overwrites it before any read.
            k_slot = cache_strip(k_cache, slot, 0, S, (Hkv, hd))
            v_slot = cache_strip(v_cache, slot, 0, S, (Hkv, hd))
            h, k_slot, v_slot = gpt_prefill_chunk(
                params, cfg, chunk, k_slot, v_slot, start, true_len
            )
            k_cache = cache_strip_put(k_cache, k_slot, slot, 0)
            v_cache = cache_strip_put(v_cache, v_slot, slot, 0)
            h_last = jax.lax.dynamic_slice_in_dim(h, true_len - 1, 1, axis=1)
            h_last = gpt_final_norm(params, cfg, h_last)[:, 0]
            logits = gpt_logits(params, cfg, h_last)
            key, sub = jax.random.split(key0)
            tok = sample_logits_batched(
                sub[None], logits, temp[None], tk[None], tp[None]
            )[0]
            live = is_final & (n_new > 1) & (tok != eos)
            end = start + true_len

            def upd(arr, v):
                return jax.lax.dynamic_update_index_in_dim(arr, v, slot, 0)

            return (
                k_cache,
                v_cache,
                upd(cur, jnp.where(is_final, tok, 0)),
                upd(pos, end),
                upd(temps, temp),
                upd(top_ks, tk),
                upd(top_ps, tp),
                upd(keys, jnp.where(is_final, key, key0)),
                upd(active, live),
                upd(remaining, jnp.where(is_final, n_new - 1, 0)),
                upd(eos_toks, eos),
                tok,
            )

        def chunk_spec_impl(
            params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps,
            keys, active, remaining, eos_toks, hist, chunk, start,
            true_len, slot, key0, temp, tk, tp, n_new, eos, is_final,
        ):
            # chunk_impl plus the token-history heal: rewrite hist rows
            # [start, start + true_len) from the chunk, so a parked
            # slot's row an interleaved fold scribbled on is refreshed
            # before any drafter reads it — the history analog of the
            # chunk's own KV rewrite of its parked row.
            out = chunk_impl(
                params, k_cache, v_cache, cur, pos, temps, top_ks,
                top_ps, keys, active, remaining, eos_toks, chunk, start,
                true_len, slot, key0, temp, tk, tp, n_new, eos, is_final,
            )
            S_ = hist.shape[1]
            rows_ = jnp.arange(S_, dtype=jnp.int32)
            hidx = rows_ - start
            hvalid = (hidx >= 0) & (hidx < true_len)
            vals = chunk[0][jnp.clip(hidx, 0, chunk.shape[1] - 1)]
            old = jax.lax.dynamic_slice(hist, (slot, 0), (1, S_))
            new = jnp.where(hvalid[None], vals[None], old)
            hist = jax.lax.dynamic_update_slice(hist, new, (slot, 0))
            return out + (hist,)

        bs = self.prefix_block

        def copy_impl(pool_k, pool_v, k_cache, v_cache, block, slot, row,
                      to_slot):
            # The ONE bidirectional cache-to-cache copy: pool block ->
            # slot rows [row, row+bs) when to_slot (prefix-hit seeding),
            # slot rows -> pool block otherwise (insertion). The
            # non-target side is written back to itself, so both
            # directions share one executable and one donation pattern.
            src_k = jax.lax.dynamic_slice(
                pool_k, (0, block, 0, 0, 0), (L, 1, bs, Hkv, hd)
            )
            src_v = jax.lax.dynamic_slice(
                pool_v, (0, block, 0, 0, 0), (L, 1, bs, Hkv, hd)
            )
            dst_k = cache_strip(k_cache, slot, row, bs, (Hkv, hd))
            dst_v = cache_strip(v_cache, slot, row, bs, (Hkv, hd))
            new_k = jnp.where(to_slot, src_k, dst_k)
            new_v = jnp.where(to_slot, src_v, dst_v)
            k_cache = cache_strip_put(k_cache, new_k, slot, row)
            v_cache = cache_strip_put(v_cache, new_v, slot, row)
            pool_k = jax.lax.dynamic_update_slice(
                pool_k, new_k, (0, block, 0, 0, 0)
            )
            pool_v = jax.lax.dynamic_update_slice(
                pool_v, new_v, (0, block, 0, 0, 0)
            )
            return pool_k, pool_v, k_cache, v_cache

        # -- paged-KV impls: block-table attention over the page pool ----
        # The chunk/step bodies run the UNCHANGED dense math over an
        # in-graph page gather (models/gpt.py paged primitives), so the
        # paged engine is bit-identical to the dense one by construction;
        # only the cache plumbing (pool + table instead of slot strips)
        # differs. The table is a read-only input here — it mutates only
        # through the tiny table-write executable below.
        page = self.kv_page

        def chunk_paged_impl(
            params, pool_k, pool_v, table, cur, pos, temps, top_ks,
            top_ps, keys, active, remaining, eos_toks, chunk, start,
            true_len, slot, key0, temp, tk, tp, n_new, eos, is_final,
        ):
            from ray_lightning_tpu.models.gpt import gpt_prefill_chunk_paged

            trow = jax.lax.dynamic_slice(
                table, (slot, 0), (1, table.shape[1])
            )
            h, pool_k, pool_v = gpt_prefill_chunk_paged(
                params, cfg, chunk, pool_k, pool_v, trow, start,
                true_len, page=page,
            )
            h_last = jax.lax.dynamic_slice_in_dim(h, true_len - 1, 1, axis=1)
            h_last = gpt_final_norm(params, cfg, h_last)[:, 0]
            logits = gpt_logits(params, cfg, h_last)
            key, sub = jax.random.split(key0)
            tok = sample_logits_batched(
                sub[None], logits, temp[None], tk[None], tp[None]
            )[0]
            live = is_final & (n_new > 1) & (tok != eos)
            end = start + true_len

            def upd(arr, v):
                return jax.lax.dynamic_update_index_in_dim(arr, v, slot, 0)

            return (
                pool_k,
                pool_v,
                upd(cur, jnp.where(is_final, tok, 0)),
                upd(pos, end),
                upd(temps, temp),
                upd(top_ks, tk),
                upd(top_ps, tp),
                upd(keys, jnp.where(is_final, key, key0)),
                upd(active, live),
                upd(remaining, jnp.where(is_final, n_new - 1, 0)),
                upd(eos_toks, eos),
                tok,
            )

        def chunk_paged_spec_impl(
            params, pool_k, pool_v, table, cur, pos, temps, top_ks,
            top_ps, keys, active, remaining, eos_toks, hist, chunk,
            start, true_len, slot, key0, temp, tk, tp, n_new, eos,
            is_final,
        ):
            # chunk_paged_impl plus the token-history heal (identical to
            # chunk_spec_impl's — the history stays dense either way).
            out = chunk_paged_impl(
                params, pool_k, pool_v, table, cur, pos, temps, top_ks,
                top_ps, keys, active, remaining, eos_toks, chunk, start,
                true_len, slot, key0, temp, tk, tp, n_new, eos, is_final,
            )
            S_ = hist.shape[1]
            rows_ = jnp.arange(S_, dtype=jnp.int32)
            hidx = rows_ - start
            hvalid = (hidx >= 0) & (hidx < true_len)
            vals = chunk[0][jnp.clip(hidx, 0, chunk.shape[1] - 1)]
            old = jax.lax.dynamic_slice(hist, (slot, 0), (1, S_))
            new = jnp.where(hvalid[None], vals[None], old)
            hist = jax.lax.dynamic_update_slice(hist, new, (slot, 0))
            return out + (hist,)

        def make_step_paged_impl(fold_k):
            def step_paged_impl(
                params, pool_k, pool_v, table, cur, pos, temps, top_ks,
                top_ps, keys, active, remaining, eos_toks, *pb,
            ):
                return gpt_decode_fold(
                    params, cfg, cur, pos, keys, temps, top_ks, top_ps,
                    active, remaining, eos_toks, pool_k, pool_v,
                    fold=fold_k, page_table=table, page_size=page,
                    piggyback=pb or None,
                )

            return step_paged_impl

        def make_step_paged_spec_impl(fold_k):
            def step_paged_spec_impl(
                params, pool_k, pool_v, table, cur, pos, temps, top_ks,
                top_ps, keys, active, remaining, eos_toks, hist, *pb,
            ):
                return gpt_decode_fold_spec(
                    params, cfg, cur, pos, keys, temps, top_ks, top_ps,
                    active, remaining, eos_toks, hist, pool_k, pool_v,
                    fold=fold_k, depth=self.spec_depth,
                    draft_fn=lambda h, p, c: ngram_propose(
                        h, p, c, depth=self.spec_depth
                    ),
                    page_table=table, page_size=page,
                    piggyback=pb or None,
                )

            return step_paged_spec_impl

        def make_step_paged_spec_model_impl(fold_k):
            def step_paged_spec_model_impl(
                params, dparams, pool_k, pool_v, table, cur, pos, temps,
                top_ks, top_ps, keys, active, remaining, eos_toks, hist,
                *pb,
            ):
                return gpt_decode_fold_spec(
                    params, cfg, cur, pos, keys, temps, top_ks, top_ps,
                    active, remaining, eos_toks, hist, pool_k, pool_v,
                    fold=fold_k, depth=self.spec_depth,
                    draft_fn=lambda h, p, c: model_propose(
                        dparams, self._spec_cfg, h, p, c,
                        depth=self.spec_depth, window=self.spec_window,
                        mesh=self.mesh,
                    ),
                    page_table=table, page_size=page,
                    piggyback=pb or None,
                )

            return step_paged_spec_model_impl

        def table_write_impl(table, slot, row):
            # One slot's whole page-table row in one tiny executable —
            # admission (real pages) and release (all-scratch) share it,
            # so table changes never recompile and always queue in
            # donation order behind any in-flight fold.
            return jax.lax.dynamic_update_slice(table, row, (slot, 0))

        spec_on = self.spec != "off"
        hist_spec = spec(self._hist) if spec_on else None
        paged = self.paged
        table_spec = spec(self._table) if paged else None
        self._admit_exec: Dict[int, Any] = {}
        self._chunk_exec: Dict[int, Any] = {}
        if self.chunked:
            # Chunked mode: admission flows through the chunk state
            # machine exclusively — one executable per CHUNK bucket
            # replaces the per-prompt-bucket fused admits. With spec on
            # the chunk executable also heals its token-history range.
            if paged:
                pool_spec = spec(self._pool_k)
                admit_out = None
                if mesh_on:
                    admit_out = (
                        (pool_out, pool_out) + state_out + (rep_sh,)
                    )
                scalar_tail = (
                    i32, i32, i32, key_spec, f32, i32, f32, i32, i32, b1,
                )
                for cb in self.chunk_buckets:
                    chunk_tok_spec = jax.ShapeDtypeStruct(
                        (1, cb), np.int32, sharding=sc_sh
                    )
                    if spec_on:
                        self._chunk_exec[cb] = (
                            jit_exec(
                                chunk_paged_spec_impl,
                                (1, 2) + tuple(range(4, 14)),
                                admit_out + (rep_sh,) if mesh_on else None,
                            )
                            .lower(
                                p_spec, pool_spec, pool_spec, table_spec,
                                *state_specs, hist_spec, chunk_tok_spec,
                                *scalar_tail,
                            )
                            .compile()
                        )
                    else:
                        self._chunk_exec[cb] = (
                            jit_exec(
                                chunk_paged_impl,
                                (1, 2) + tuple(range(4, 13)),
                                admit_out,
                            )
                            .lower(
                                p_spec, pool_spec, pool_spec, table_spec,
                                *state_specs, chunk_tok_spec,
                                *scalar_tail,
                            )
                            .compile()
                        )
                    self.compiled_count += 1
            else:
                admit_out = None
                if mesh_on:
                    admit_out = (
                        (cache_out, cache_out) + state_out + (rep_sh,)
                    )
                for cb in self.chunk_buckets:
                    chunk_tok_spec = jax.ShapeDtypeStruct(
                        (1, cb), np.int32, sharding=sc_sh
                    )
                    if spec_on:
                        self._chunk_exec[cb] = (
                            jit_exec(
                                chunk_spec_impl,
                                tuple(range(1, 13)),
                                admit_out + (rep_sh,) if mesh_on else None,
                            )
                            .lower(
                                p_spec,
                                cache_spec,
                                cache_spec,
                                *state_specs,
                                hist_spec,
                                chunk_tok_spec,
                                i32,
                                i32,
                                i32,
                                key_spec,
                                f32,
                                i32,
                                f32,
                                i32,
                                i32,
                                b1,
                            )
                            .compile()
                        )
                    else:
                        self._chunk_exec[cb] = (
                            jit_exec(
                                chunk_impl, tuple(range(1, 12)), admit_out
                            )
                            .lower(
                                p_spec,
                                cache_spec,
                                cache_spec,
                                *state_specs,
                                chunk_tok_spec,
                                i32,
                                i32,
                                i32,
                                key_spec,
                                f32,
                                i32,
                                f32,
                                i32,
                                i32,
                                b1,
                            )
                            .compile()
                        )
                    self.compiled_count += 1
        else:
            admit_out = None
            if mesh_on:
                admit_out = (cache_out, cache_out) + state_out + (rep_sh,)
            for pb in self.prefill_buckets:
                prompt_spec = jax.ShapeDtypeStruct(
                    (1, pb), np.int32, sharding=sc_sh
                )
                self._admit_exec[pb] = (
                    jit_exec(admit_impl, tuple(range(1, 12)), admit_out)
                    .lower(
                        p_spec,
                        cache_spec,
                        vcache_spec,
                        *state_specs,
                        prompt_spec,
                        i32,
                        i32,
                        key_spec,
                        f32,
                        i32,
                        f32,
                        i32,
                        i32,
                    )
                    .compile()
                )
                self.compiled_count += 1
        if self.prefix_blocks:
            pool_spec = spec(self._pool_k)
        if self.prefix_blocks and not paged:
            # Paged mode has no pool->slot copy at all: a prefix hit is
            # a table alias (refcount bump), the copy-free path this
            # executable existed to approximate.
            self._copy_exec = (
                jit_exec(
                    copy_impl,
                    (0, 1, 2, 3),
                    (pool_out, pool_out, cache_out, cache_out)
                    if mesh_on
                    else None,
                )
                .lower(
                    pool_spec, pool_spec, cache_spec, cache_spec,
                    i32, i32, i32, b1,
                )
                .compile()
            )
            self.compiled_count += 1
        if self.prefix_blocks:
            # Compiled whenever a pool exists (not just with spill tiers
            # on): the same two transfers also serve the cross-replica
            # KV handoff — a preempting replica pool-reads a request's
            # prefix blocks out, the survivor pool-writes them in.
            blk_out = self._blk_sh  # None single-device

            def pool_read_impl(pool_k, pool_v, block):
                # The D2H half of a spill: slice one block out of the
                # pool (no donation — the pool stays live); the host
                # copies the result out before the block's metadata dies.
                src_k = jax.lax.dynamic_slice(
                    pool_k, (0, block, 0, 0, 0), (L, 1, bs, Hkv, hd)
                )
                src_v = jax.lax.dynamic_slice(
                    pool_v, (0, block, 0, 0, 0), (L, 1, bs, Hkv, hd)
                )
                return src_k, src_v

            def pool_write_impl(pool_k, pool_v, kblk, vblk, block):
                # The H2D half of a refill: write one host-sourced block
                # into the pool (donated) — the ONE compiled transfer a
                # cold-tier promotion pays, lowered here so steady-state
                # tier traffic never compiles.
                pool_k = jax.lax.dynamic_update_slice(
                    pool_k, kblk, (0, block, 0, 0, 0)
                )
                pool_v = jax.lax.dynamic_update_slice(
                    pool_v, vblk, (0, block, 0, 0, 0)
                )
                return pool_k, pool_v

            blk_spec = jax.ShapeDtypeStruct(
                self._blk_shape,
                jnp.dtype(cfg.compute_dtype),
                sharding=blk_out if mesh_on else None,
            )
            self._pool_read_exec = (
                jit_exec(
                    pool_read_impl,
                    (),
                    (blk_out, blk_out) if mesh_on else None,
                )
                .lower(pool_spec, pool_spec, i32)
                .compile()
            )
            self.compiled_count += 1
            self._pool_write_exec = (
                jit_exec(
                    pool_write_impl,
                    (0, 1),
                    (pool_out, pool_out) if mesh_on else None,
                )
                .lower(pool_spec, pool_spec, blk_spec, blk_spec, i32)
                .compile()
            )
            self.compiled_count += 1
            self._pool_layer_write_exec = None
            if not mesh_on:
                # Layer-pipelined imports: one LAYER of one block lands
                # per write, so a disaggregated prefill's pages start
                # streaming in while upper layers are still computing.
                # Single-device only — mesh shard-dict payloads arrive
                # whole-block and fall back to _pool_write_exec.
                def pool_layer_write_impl(
                    pool_k, pool_v, kl, vl, block, layer
                ):
                    pool_k = jax.lax.dynamic_update_slice(
                        pool_k, kl, (layer, block, 0, 0, 0)
                    )
                    pool_v = jax.lax.dynamic_update_slice(
                        pool_v, vl, (layer, block, 0, 0, 0)
                    )
                    return pool_k, pool_v

                lyr_spec = jax.ShapeDtypeStruct(
                    (1, 1, bs, Hkv, hd), jnp.dtype(cfg.compute_dtype)
                )
                self._pool_layer_write_exec = (
                    jit_exec(pool_layer_write_impl, (0, 1), None)
                    .lower(
                        pool_spec, pool_spec, lyr_spec, lyr_spec, i32,
                        i32,
                    )
                    .compile()
                )
                self.compiled_count += 1
        # The folded step: caches + in-graph-updated state donated; the
        # sampling knobs and eos table are read-only inputs (slot writes
        # own their updates). With spec on the token history rides the
        # same donation chain, and the drafter (n-gram search or draft
        # model) compiles INTO this one executable. One executable per
        # fold_ladder rung; with piggyback on, each also carries the
        # C-row prefill-chunk tail (read-only, replicated) and returns
        # the piggybacked first-token samples appended to its outputs.
        pbC = self.piggyback_chunks
        pb_specs: Tuple[Any, ...] = ()
        if pbC:
            i32C = jax.ShapeDtypeStruct((pbC,), np.int32, sharding=sc_sh)
            f32C = jax.ShapeDtypeStruct(
                (pbC,), np.float32, sharding=sc_sh
            )
            b1C = jax.ShapeDtypeStruct((pbC,), np.bool_, sharding=sc_sh)
            pb_specs = (
                jax.ShapeDtypeStruct(
                    (pbC, self.prefill_chunk), np.int32, sharding=sc_sh
                ),
                i32C, i32C, i32C,
                jax.ShapeDtypeStruct((pbC, 2), np.uint32, sharding=sc_sh),
                f32C, i32C, f32C, i32C, i32C, b1C, b1C,
            )
        step_out = None
        step_spec_out = None
        if mesh_on:
            tail = (pool_out, pool_out) if paged else (cache_out, cache_out)
            pb_tail = (rep_sh,) if pbC else ()
            step_out = (rep_sh,) * 7 + tail + pb_tail
            step_spec_out = (rep_sh,) * 8 + tail + pb_tail
        dp_spec = None
        if self.spec == "model":
            dp_spec = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape,
                    a.dtype,
                    sharding=a.sharding if mesh_on else None,
                ),
                self._spec_params,
            )
        self._step_exec: Dict[int, Any] = {}
        for fk in self.fold_ladder:
            if paged:
                # Paged fold: the pools + the (read-only) page table
                # replace the dense caches; donation covers pools +
                # in-graph state.
                if not spec_on:
                    self._step_exec[fk] = (
                        jit_exec(
                            make_step_paged_impl(fk),
                            (1, 2, 4, 5, 9, 10, 11),
                            step_out,
                        )
                        .lower(p_spec, pool_spec, pool_spec, table_spec,
                               *state_specs, *pb_specs)
                        .compile()
                    )
                elif self.spec == "ngram":
                    self._step_exec[fk] = (
                        jit_exec(
                            make_step_paged_spec_impl(fk),
                            (1, 2, 4, 5, 9, 10, 11, 13),
                            step_spec_out,
                        )
                        .lower(p_spec, pool_spec, pool_spec, table_spec,
                               *state_specs, hist_spec, *pb_specs)
                        .compile()
                    )
                else:
                    self._step_exec[fk] = (
                        jit_exec(
                            make_step_paged_spec_model_impl(fk),
                            (2, 3, 5, 6, 10, 11, 12, 14),
                            step_spec_out,
                        )
                        .lower(p_spec, dp_spec, pool_spec, pool_spec,
                               table_spec, *state_specs, hist_spec,
                               *pb_specs)
                        .compile()
                    )
            elif not spec_on:
                self._step_exec[fk] = (
                    jit_exec(
                        make_step_impl(fk), (1, 2, 3, 4, 8, 9, 10),
                        step_out,
                    )
                    .lower(p_spec, cache_spec, vcache_spec, *state_specs,
                           *pb_specs)
                    .compile()
                )
            elif self.spec == "ngram":
                self._step_exec[fk] = (
                    jit_exec(
                        make_step_spec_impl(fk),
                        (1, 2, 3, 4, 8, 9, 10, 12),
                        step_spec_out,
                    )
                    .lower(p_spec, cache_spec, cache_spec, *state_specs,
                           hist_spec, *pb_specs)
                    .compile()
                )
            else:
                self._step_exec[fk] = (
                    jit_exec(
                        make_step_spec_model_impl(fk),
                        (2, 3, 4, 5, 9, 10, 11, 13),
                        step_spec_out,
                    )
                    .lower(p_spec, dp_spec, cache_spec, cache_spec,
                           *state_specs, hist_spec, *pb_specs)
                    .compile()
                )
            self.compiled_count += 1
        if paged:
            self._table_write_exec = (
                jit_exec(table_write_impl, (0,), rep_sh if mesh_on else None)
                .lower(
                    table_spec,
                    i32,
                    jax.ShapeDtypeStruct(
                        (1, self._table.shape[1]), np.int32, sharding=sc_sh
                    ),
                )
                .compile()
            )
            self.compiled_count += 1
        if spec_on:
            self._hist_write_exec = (
                jit_exec(hist_write_impl, (0,), rep_sh if mesh_on else None)
                .lower(
                    hist_spec,
                    i32,
                    jax.ShapeDtypeStruct(
                        (1, self.max_seq), np.int32, sharding=sc_sh
                    ),
                    i32,
                )
                .compile()
            )
            self.compiled_count += 1
        self._slot_write_exec = (
            jit_exec(
                slot_write_impl,
                tuple(range(9)),
                state_out if mesh_on else None,
            )
            .lower(
                *state_specs,
                i32,
                i32,
                i32,
                f32,
                i32,
                f32,
                key_spec,
                b1,
                i32,
                i32,
            )
            .compile()
        )
        self.compiled_count += 1

    # -- device state plumbing -------------------------------------------
    def _slot_write(
        self, slot, cur_v, pos_v, temp_v, tk_v, tp_v, key_v, active_v,
        rem_v, eos_v,
    ) -> None:
        (
            self._cur, self._pos, self._temps, self._top_ks, self._top_ps,
            self._keys, self._active, self._remaining, self._eos,
        ) = self._slot_write_exec(
            self._cur, self._pos, self._temps, self._top_ks, self._top_ps,
            self._keys, self._active, self._remaining, self._eos,
            np.int32(slot), np.int32(cur_v), np.int32(pos_v),
            np.float32(temp_v), np.int32(tk_v), np.float32(tp_v),
            key_v, np.bool_(active_v), np.int32(rem_v), np.int32(eos_v),
        )

    def _hist_seed(self, slot: int, prompt: np.ndarray) -> None:
        """Seed one slot's token history with its prompt (spec only):
        one compiled write, queued after any in-flight fold through the
        history's donation chain."""
        row = np.zeros((1, self.max_seq), np.int32)
        row[0, : len(prompt)] = prompt
        self._hist = self._hist_write_exec(
            self._hist, np.int32(slot), row, np.int32(len(prompt))
        )

    # -- paged-KV plumbing -------------------------------------------------
    def _table_write(self, slot: int, pages: Sequence[int]) -> None:
        """Rewrite one slot's page-table row: ``pages`` fill the leading
        entries, the rest point at the scratch page (0). One compiled
        dispatch, queued after any in-flight fold (donation order)."""
        row = np.zeros((1, self._table.shape[1]), np.int32)
        row[0, : len(pages)] = pages
        self._table = self._table_write_exec(
            self._table, np.int32(slot), row
        )

    def pages_for(self, prompt_len: int, max_new_tokens: int) -> int:
        """Pages one request needs for its WHOLE life: prompt + every
        generated token + the frozen slot's final (masked) write at
        position ``min(P + new, S - 1)`` — the admission budget's unit
        (prompt + decode reserve, reserved up front so decode can never
        run out of pages mid-request)."""
        last = min(prompt_len + max_new_tokens, self.max_seq - 1)
        return last // self.kv_page + 1

    def free_pages(self) -> int:
        """Immediately-allocatable pages (free list only)."""
        return len(self._pool_free)

    def pages_available(self) -> int:
        """Allocatable pages: the free list plus evictable cache pages
        (digest-keyed, unreferenced — the LRU victims an allocation may
        spill/drop). Quarantined pages are excluded (they free at the
        next harvest), so the scheduler's admission check is
        conservative and parks for at most one step on their account."""
        evictable = sum(
            1
            for m in self._pool_meta
            if m is not None and m.refs == 0 and m.digest is not None
        )
        return len(self._pool_free) + evictable

    def _flush_quarantine(self) -> None:
        """Recycle quarantined private pages. Only call when every fold
        dispatched BEFORE their slots' table resets has completed (at
        release time with no fold in flight, or at the top of a harvest
        after its sync) — the in-flight fold is the one writer that can
        still scribble them."""
        if self._quarantine:
            self._pool_free.extend(self._quarantine)
            self._quarantine = []

    def _release_pages(self, slot: int) -> None:
        """Drop one slot's claim on its pages (paged mode): every page's
        refcount falls; private (digestless) pages that hit zero die
        into the quarantine, digest-keyed pages stay resident as
        evictable cache — the copy-free afterlife of a completed
        prompt's prefix. The slot's table row is reset to scratch so no
        LATER-dispatched fold can write its old pages."""
        if not self.paged:
            return
        pages = self._slot_pages[slot]
        self._slot_pages[slot] = []
        self._slot_span[slot] = 0
        for pg in pages:
            m = self._pool_meta[pg]
            if m is None:
                continue
            m.refs -= 1
            if m.refs <= 0 and m.digest is None:
                self._pool_meta[pg] = None
                self._quarantine.append(pg)
                self.page_frees += 1
        self._table_write(slot, ())
        if self._inflight is None:
            self._flush_quarantine()

    def kv_page_counters(self) -> Dict[str, int]:
        """Cumulative page-allocator event counters — the scheduler
        diffs consecutive snapshots into per-step ServeMetrics deltas
        (the ``rlt_serve_kv_page_*_total`` series)."""
        return {
            "allocs": self.page_allocs,
            "frees": self.page_frees,
            "alias_hits": self.page_alias_hits,
        }

    def kv_page_stats(self) -> Dict[str, Any]:
        """The ``kv_pages`` stats block: pool occupancy by state (free /
        resident / aliased), the token budget, and fragmentation —
        tokens inside allocated pages no position of their slot's span
        can ever use (partial-page tails; the capacity paging cannot
        reclaim)."""
        usable = self.kv_pages - 1  # minus the scratch page
        aliased = sum(
            1 for m in self._pool_meta if m is not None and m.refs > 1
        )
        allocated = sum(1 for m in self._pool_meta if m is not None)
        free = len(self._pool_free) + len(self._quarantine)
        frag = 0
        for slot in range(self.num_slots):
            span = self._slot_span[slot]
            if span:
                frag += len(self._slot_pages[slot]) * self.kv_page - span
        return {
            "page_size": self.kv_page,
            "pages_total": usable,
            "token_budget": usable * self.kv_page,
            "free": free,
            "resident": allocated - aliased,
            "aliased": aliased,
            "occupancy": round(allocated / usable, 4) if usable else 0.0,
            "fragmentation_tokens": frag,
            "allocs": self.page_allocs,
            "frees": self.page_frees,
            "alias_hits": self.page_alias_hits,
        }

    def device_state(self) -> Dict[str, np.ndarray]:
        """Host snapshot of the device-resident per-slot state. This is a
        SYNC POINT: it blocks on any in-flight fold (debug/tests only —
        the steady-state loop never calls it)."""
        if self.spec != "off":
            return {**self._base_device_state(),
                    "hist": np.asarray(self._hist)}
        return self._base_device_state()

    def _base_device_state(self) -> Dict[str, np.ndarray]:
        return {
            "cur": np.asarray(self._cur),
            "pos": np.asarray(self._pos),
            "temps": np.asarray(self._temps),
            "top_ks": np.asarray(self._top_ks),
            "top_ps": np.asarray(self._top_ps),
            "keys": np.asarray(self._keys),
            "active": np.asarray(self._active),
            "remaining": np.asarray(self._remaining),
            "eos": np.asarray(self._eos),
        }

    # -- introspection ---------------------------------------------------
    def prefill_mosaic_calls(self) -> Dict[int, int]:
        """Mosaic (Pallas) custom calls in each prefill bucket's compiled
        admission. Zero for a bucket means its attention did not run the
        TPU kernel: interpret mode off-chip, or the reference fallback."""
        return {
            pb: ex.as_text().count("tpu_custom_call")
            for pb, ex in self._admit_exec.items()
        }

    @property
    def mesh_desc(self) -> str:
        """``"MODELxDATA"`` of the bound mesh; ``"1x1"`` single-device."""
        if self.mesh is None:
            return "1x1"
        return "{}x{}".format(
            self.mesh.shape.get("model", 1), self.mesh.shape.get("data", 1)
        )

    def memory_stats(self) -> Dict[str, Dict[str, int]]:
        """Resident device-state footprint by component: logical
        ``bytes`` plus ``per_device_bytes`` — what one device actually
        holds, measured from the live shards (not inferred from the
        spec). The KV cache and prefix pool shard their head axis over
        the mesh's model axis, so their per-device bytes must shrink
        ~linearly in it; the token history and slot scalars replicate.
        Metadata only — reads buffer sizes, never syncs values."""
        import jax

        def row(*arrs) -> Dict[str, int]:
            live = jax.tree_util.tree_leaves([a for a in arrs if a is not None])
            total = sum(int(a.nbytes) for a in live)
            if self.mesh is None:
                return {"bytes": total, "per_device_bytes": total}
            per = 0.0
            for a in live:
                n_local = max(1, len(a.sharding.addressable_devices))
                per += (
                    sum(int(s.data.nbytes) for s in a.addressable_shards)
                    / n_local
                )
            return {"bytes": total, "per_device_bytes": int(per)}

        out = {
            # Paged mode: the page pool IS the KV cache (kv_cache reads
            # 0 — there are no dense slot strips) and the unified pool
            # reports under prefix_pool; the table rides its own row.
            "kv_cache": row(self._k, self._v),
            "prefix_pool": row(
                getattr(self, "_pool_k", None), getattr(self, "_pool_v", None)
            ),
            "token_history": row(self._hist),
        }
        if self.paged:
            out["page_table"] = row(self._table)
        out["total"] = {
            "bytes": sum(r["bytes"] for r in out.values()),
            "per_device_bytes": sum(
                r["per_device_bytes"] for r in out.values()
            ),
        }
        return out

    def cache_stats(self) -> Dict[str, Dict[str, int]]:
        """The dense per-request state by layer kind: layers, rows a slot,
        bytes (K and V; a latent layer's latents and rotary keys) and
        ``row_layout`` — whether a position's KV heads lie side by side
        in one cache row, which is the read every decode step of this
        engine then runs (models/gpt.py:_attend_layer_cache; false under
        a mesh, where the heads keep an axis to shard). Every
        layer of a uniform configuration is ``full``; a state layer's kind
        is ``state``: one running state a slot whatever the request's
        length (``rows_per_slot`` 1), its bytes the recurrent states' and
        the conv tails'. A paged engine has no dense cache and reports
        ``{}``."""
        if self._k is None:
            return {}
        k, v = self._k, self._v
        if not isinstance(k, dict):
            k, v = {"full": k}, {"full": v}
        out = {
            kind: {
                "layers": int(k[kind].shape[0]),
                "rows_per_slot": int(k[kind].shape[2]),
                "bytes": int(k[kind].nbytes + v[kind].nbytes),
                "row_layout": k[kind].ndim == 4,
            }
            for kind in k if kind != "ssm"
        }
        if "ssm" in k:
            out["state"] = {
                "layers": len(k["ssm"]),
                "rows_per_slot": 1,
                "bytes": sum(int(a.nbytes) for a in k["ssm"] + v["ssm"]),
                "row_layout": False,
            }
        return out

    def moe_stats(self) -> Dict[str, Any]:
        """``stats()["moe"]``: what the expert layers of a mixed
        configuration routed and computed, monotone since construction
        (``{}`` for any other configuration)."""
        from ray_lightning_tpu.models.mixed import count_kind, experts_held

        if not (self.cfg.mixed and count_kind(self.cfg, "experts")):
            return {}
        return {
            "n_experts": self.cfg.n_experts,
            "experts_held": list(experts_held(self.cfg)),
            "top_k": self.cfg.moe_top_k,
            "expert_layers": count_kind(self.cfg, "experts"),
            "decode": dict(self.moe_totals["decode"]),
            "prefill": dict(self.moe_totals["prefill"]),
        }

    def _count_prefill(self, bucket: int, prompt_len: int) -> None:
        """One admission of ``prompt_len`` tokens in ``bucket`` rows into
        ``attn_totals``: row·layers of attention prefilled and those the
        forward flash kernel read; score tiles of the padded causal square
        over the layers that attend it, and those a read visited — all of
        them on the XLA read, on the kernel those of the query blocks that
        hold a real row. From the host's own numbers: no device read."""
        if bucket not in self._prefill_layers:
            return
        layers, square, kernel, tile = self._prefill_layers[bucket]
        n, m = -(-bucket // tile), -(-prompt_len // tile)
        t = self.attn_totals
        t["prefill_rows"] += bucket * layers
        t["prefill_rows_kernel"] += bucket * kernel
        t["prefill_tiles"] += square * n * (n + 1) // 2
        t["prefill_tiles_visited"] += (kernel * m * (m + 1) + (square - kernel) * n * (n + 1)) // 2

    def attn_stats(self) -> Dict[str, int]:
        """``stats()["attn"]``: cache rows the decode steps' attention
        had allocated, visited and live, summed over token steps and
        layers, monotone since construction. ``rows_visited`` equals
        ``rows_allocated`` on the XLA read (every row of every slot is
        multiplied and masked afterwards); under the decode kernel
        (``ops/decode_attention.py``) it is the blocks up to each live
        slot's position. Of mixed layer kinds, whose caches differ by
        kind, the rows of the kinds that keep a request's every position
        are counted, the full and the latent layers' (read by the same
        kernel on a TPU: models/mixed.py:_attention_part, _latent_part),
        each kind by the read its layers take; ``{}`` without such a
        layer. Beside them the admissions of mixed layer kinds:
        ``prefill_rows`` / ``prefill_rows_kernel`` and ``prefill_tiles`` /
        ``prefill_tiles_visited`` (:meth:`_count_prefill`; zeros for a
        uniform configuration, whose prefill is ``gpt_prefill``'s)."""
        return dict(self.attn_totals) if self._attn_reads else {}

    def ssm_stats(self) -> Dict[str, Any]:
        """``stats()["ssm"]``: what the state layers of a mixed
        configuration advanced and scanned, monotone since construction
        (``{}`` for a configuration without state layers)."""
        if not self._state_layers:
            return {}
        return {
            "state_layers": self._state_layers,
            "decode": dict(self.ssm_totals["decode"]),
            "prefill": dict(self.ssm_totals["prefill"]),
        }

    @property
    def num_active(self) -> int:
        """Occupied slots: decoding residents PLUS in-progress chunked
        prefills (both hold their slot and still need engine work)."""
        return sum(1 for s in self._slots if s is not None) + len(
            self._prefills
        )

    @property
    def num_prefilling(self) -> int:
        return len(self._prefills)

    def free_slots(self) -> List[int]:
        return [
            i
            for i, s in enumerate(self._slots)
            if s is None and i not in self._prefills
        ]

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prefill_buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds largest prefill bucket "
            f"{self.prefill_buckets[-1]}"
        )

    def check_prompt_len(self, prompt_len: int) -> None:
        """Raise when a prompt can never be admitted: over every bucket
        (monolithic) or leaving no room for a generated token (chunked —
        chunking lifts the bucket cap; prompts go up to max_seq - 1)."""
        if self.chunked:
            if prompt_len >= self.max_seq:
                raise ValueError(
                    f"prompt length {prompt_len} leaves no room for a "
                    f"generated token (engine max_seq {self.max_seq})"
                )
            return
        self.bucket_for(prompt_len)

    def _chunk_bucket_for(self, n: int) -> int:
        for b in self.chunk_buckets:
            if b >= n:
                return b
        raise ValueError(
            f"chunk length {n} exceeds largest chunk bucket "
            f"{self.chunk_buckets[-1]}"
        )

    # -- request lifecycle -----------------------------------------------
    def admit(
        self,
        prompt: Sequence[int],
        *,
        request_id: str,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        seed: int = 0,
        eos_token: Optional[int] = None,
    ) -> Tuple[int, Optional[int], bool]:
        """Prefill ``prompt`` into a free slot; returns (slot, first_token,
        done). Raises when no slot is free or the request cannot fit.

        With a fold in flight, the prefill/cache/slot writes queue AFTER
        it (donation order), so the new tenant's first decode lands in
        the NEXT dispatched fold — admission is a fold-boundary event.

        Chunked mode (``prefill_chunk > 0``): admission only SEEDS the
        slot (prefix-cache copies + state machine) and returns
        ``(slot, None, False)``; the first token arrives from a later
        :meth:`prefill_step` once the final chunk runs.
        """
        return self.admit_many(
            [
                dict(
                    prompt=prompt,
                    request_id=request_id,
                    max_new_tokens=max_new_tokens,
                    temperature=temperature,
                    top_k=top_k,
                    top_p=top_p,
                    seed=seed,
                    eos_token=eos_token,
                )
            ]
        )[0]

    def admit_many(
        self, requests: Sequence[Dict[str, Any]]
    ) -> List[Tuple[int, Optional[int], bool]]:
        """Admit a burst of requests at one fold boundary; returns
        ``(slot, first_token, done)`` per request, in order.

        Monolithic mode: each request is one fused dispatch (prefill +
        cache write + first-token sample + slot-state write), and ALL
        chains are dispatched before the first D2H token sync — the host
        round trip of request i overlaps the device work of requests
        i+1..n instead of fencing it. (Each request's PRNG-key fetch is
        a device sync of its own, ahead of its dispatch: see
        :meth:`_request_key`.) Chunked mode: each request walks
        the prefix pool, dispatches its seeding copies + parking state
        write, and returns ``(slot, None, False)``; chunks then advance
        through :meth:`prefill_step`. Requests are validated up front, so
        a bad spec rejects the whole burst before any device state moves.
        """
        free = self.free_slots()
        if len(requests) > len(free):
            raise RuntimeError(
                f"{len(requests)} admissions but only {len(free)} free "
                "slots (check free_slots() first)"
            )
        staged = []
        for r, slot in zip(requests, free):
            prompt = np.asarray(r["prompt"], np.int32).reshape(-1)
            P = int(prompt.shape[0])
            n_new = int(r["max_new_tokens"])
            if P < 1 or n_new < 1:
                raise ValueError(
                    "need a non-empty prompt and max_new_tokens >= 1"
                )
            if P + n_new > self.max_seq:
                raise ValueError(
                    f"prompt ({P}) + max_new_tokens ({n_new}) exceeds "
                    f"engine max_seq {self.max_seq}"
                )
            pb = None if self.chunked else self.bucket_for(P)
            eos_token = r.get("eos_token")
            staged.append((slot, r, prompt, P, n_new, pb,
                           -1 if eos_token is None else int(eos_token)))
        if self.chunked:
            out: List[Tuple[int, Optional[int], bool]] = []
            for slot, r, prompt, P, n_new, _, eos in staged:
                key0 = self._request_key(r)
                matched_idxs, matched_tiers = self._match_prefix(prompt)
                matched = len(matched_idxs) * self.prefix_block
                if self.prefix_blocks:
                    self.prefix_lookups += 1
                    self.prefix_hit_tokens += matched
                    self.prefix_prompt_tokens += P
                if self.paged:
                    # Copy-free prefix hit: the matched pages are ALIASED
                    # into this slot's table (refcount bump below covers
                    # the slot's whole lifetime), and only the private
                    # remainder — suffix prompt pages + the decode
                    # reserve — is allocated. The scheduler admits only
                    # when pages_available() covers pages_for(), so the
                    # allocation loop cannot come up short mid-burst.
                    total = self.pages_for(P, n_new)
                    avoid = set(matched_idxs)
                    private: List[int] = []
                    for _ in range(total - len(matched_idxs)):
                        pg = self._pool_alloc(frozenset(avoid))
                        if pg is None:
                            break
                        avoid.add(pg)
                        private.append(pg)
                    if len(matched_idxs) + len(private) < total:
                        self._pool_free.extend(private)
                        self.page_frees += len(private)
                        raise RuntimeError(
                            f"out of KV pages: request needs {total}, "
                            f"only {len(matched_idxs) + len(private)} "
                            "allocatable (check pages_available() "
                            "before admitting)"
                        )
                    for b in matched_idxs:
                        self._pool_meta[b].refs += 1
                        self.page_alias_hits += 1
                    for pg in private:
                        self._pool_tick += 1
                        self._pool_meta[pg] = _PoolBlock(
                            digest=None, refs=1, stamp=self._pool_tick
                        )
                    pages = list(matched_idxs) + private
                    self._slot_pages[slot] = pages
                    self._slot_span[slot] = (
                        min(P + n_new, self.max_seq - 1) + 1
                    )
                    self._table_write(slot, pages)
                else:
                    for b in matched_idxs:
                        # pinned until done/cancel
                        self._pool_meta[b].refs += 1
                # Park the slot: inactive, pos at the first unseeded row
                # (the only row interleaved folds can scribble on; the
                # first chunk rewrites it before reading). The REAL
                # sampling knobs + eos go in now: the piggybacked chunk
                # path reads them from device state (the fused fold's
                # knob arrays are read-only inputs), while the separate
                # chunk executables overwrite them redundantly — same
                # values, bit-identical either way.
                top_k = r.get("top_k")
                top_p = r.get("top_p")
                self._slot_write(
                    slot, 0, matched, float(r.get("temperature", 0.0)),
                    0 if top_k is None else int(top_k),
                    1.0 if top_p is None else float(top_p),
                    key0, False, 0, eos,
                )
                if self.spec != "off":
                    # The whole prompt (matched prefix included — the
                    # KV copy/alias carries no tokens) enters the
                    # drafters' history up front; chunk executables
                    # re-heal their own ranges against fold scribbles.
                    self._hist_seed(slot, prompt)
                if not self.paged:
                    for j, b in enumerate(matched_idxs):
                        self._copy_block(
                            b, slot, j * self.prefix_block, to_slot=True
                        )
                if self.tracer is not None and matched:
                    from ray_lightning_tpu.obs.trace import SPAN_PREFIX_SEED

                    self.tracer.event(
                        r["request_id"], SPAN_PREFIX_SEED,
                        attrs={
                            "tokens": matched,
                            "blocks": len(matched_idxs),
                            "slot": slot,
                            # Where each seeded block came from: a
                            # host/disk count > 0 means this admission
                            # paid a promotion (H2D refill) for it.
                            "tiers": {
                                t: matched_tiers.count(t)
                                for t in ("device", "host", "disk")
                            },
                        },
                    )
                self._prefills[slot] = PrefillTask(
                    request_id=r["request_id"],
                    tokens=prompt,
                    next=matched,
                    max_new_tokens=n_new,
                    eos_token=eos,
                    temperature=float(r.get("temperature", 0.0)),
                    top_k=0 if top_k is None else int(top_k),
                    top_p=1.0 if top_p is None else float(top_p),
                    key0=key0,
                    matched_tokens=matched,
                    # Paged: the slot's page list (not the prefill task)
                    # owns the alias refcounts — they persist until
                    # release, not merely until the prefill completes.
                    block_refs=[] if self.paged else list(matched_idxs),
                )
                out.append((slot, None, False))
            return out
        pending = []
        moe_counts: List[Any] = []  # mixed configurations: one a request
        for slot, r, prompt, P, n_new, pb, eos in staged:
            if self.spec != "off":
                # Prompt into the drafters' history; the fold writes the
                # admission-sampled token itself (hist[pos] = cur at the
                # top of every iteration).
                self._hist_seed(slot, prompt)
            padded = np.zeros((1, pb), np.int32)
            padded[0, :P] = prompt
            temp = np.float32(r.get("temperature", 0.0))
            top_k = r.get("top_k")
            top_p = r.get("top_p")
            tk = np.int32(0 if top_k is None else top_k)
            tp = np.float32(1.0 if top_p is None else top_p)
            key0 = self._request_key(r)
            (
                self._k, self._v, self._cur, self._pos, self._temps,
                self._top_ks, self._top_ps, self._keys, self._active,
                self._remaining, self._eos, tok, *moe,
            ) = self._admit_exec[pb](
                self.params, self._k, self._v, self._cur, self._pos,
                self._temps, self._top_ks, self._top_ps, self._keys,
                self._active, self._remaining, self._eos,
                padded, np.int32(P - 1), np.int32(slot), key0,
                temp, tk, tp, np.int32(n_new), np.int32(eos),
            )
            pending.append((slot, r, n_new, eos, tok))
            self._count_prefill(pb, P)
            moe_counts.extend(moe)
            self.spans.device_busy()
            if self.tracer is not None:
                from ray_lightning_tpu.obs.trace import SPAN_PREFILL

                self.tracer.event(
                    r["request_id"], SPAN_PREFILL,
                    attrs={"bucket": pb, "tokens": P, "slot": slot},
                )
        # The first-token syncs: the host blocked on the device, behind
        # the fold in flight and the prefills just enqueued. They are the
        # last programs enqueued, so once they return the device is idle
        # (the fold in flight has finished too) until the next dispatch.
        with span(self.spans, "serve.engine.admit_wait", n=len(pending)):
            first = [int(np.asarray(tok)) for *_, tok in pending]
            for m in moe_counts:
                self._count_moe("prefill", np.asarray(m), 1)
        self.spans.device_idle()
        out: List[Tuple[int, int, bool]] = []
        for (slot, r, n_new, eos, _), tok in zip(pending, first):
            # Mirrors the in-graph `live` predicate: a request done at
            # its first token never occupies the slot (the device wrote
            # its own active=False).
            done = n_new == 1 or tok == eos
            if not done:
                self._slots[slot] = SlotInfo(
                    request_id=r["request_id"],
                    max_new_tokens=n_new,
                    n_generated=1,
                    eos_token=eos,
                    prompt_len=int(np.size(r["prompt"])),
                )
            out.append((slot, tok, done))
        return out

    def _request_key(self, r: Dict[str, Any]) -> np.ndarray:
        """The request's PRNG key as the host's two words. The key is
        made on the device and fetched, and that fetch queues behind
        whatever is in flight: the host blocked on the device, named as
        such (``serve.engine.key_wait``), and once it returns the device
        is idle until the host's next enqueue."""
        import jax

        with span(self.spans, "serve.engine.key_wait"):
            key0 = np.asarray(
                jax.random.PRNGKey(int(r.get("seed", 0))), np.uint32
            ).reshape(2)
        self.spans.device_idle()
        return key0

    def prefill_step(
        self, max_chunks: int = 1
    ) -> List[Tuple[int, PrefillTask, int, bool]]:
        """Advance up to ``max_chunks`` prefill chunks, round-robin across
        prefilling slots; returns ``(slot, task, first_token, done)`` for
        every prefill that COMPLETED (its final chunk sampled the first
        token and armed the slot for the next decode fold, or finished the
        request outright). The scheduler calls this between decode folds —
        the chunk-vs-fold interleave that keeps a long prompt from
        freezing resident decodes for its whole prefill."""
        out: List[Tuple[int, PrefillTask, int, bool]] = []
        budget = int(max_chunks)
        while budget > 0 and self._prefills:
            progressed = False
            for slot in sorted(self._prefills):
                if budget <= 0:
                    break
                task = self._prefills.get(slot)
                if task is None:  # completed earlier in this sweep
                    continue
                progressed = True
                budget -= 1
                P = len(task.tokens)
                this_len = min(self.prefill_chunk, P - task.next)
                cb = self._chunk_bucket_for(this_len)
                padded = np.zeros((1, cb), np.int32)
                padded[0, :this_len] = task.tokens[
                    task.next : task.next + this_len
                ]
                is_final = task.next + this_len >= P
                scalars = (
                    padded, np.int32(task.next), np.int32(this_len),
                    np.int32(slot), task.key0,
                    np.float32(task.temperature), np.int32(task.top_k),
                    np.float32(task.top_p), np.int32(task.max_new_tokens),
                    np.int32(task.eos_token), np.bool_(is_final),
                )
                spec_on = self.spec != "off"
                if self.paged:
                    args = [
                        self.params, self._pool_k, self._pool_v,
                        self._table, self._cur, self._pos, self._temps,
                        self._top_ks, self._top_ps, self._keys,
                        self._active, self._remaining, self._eos,
                    ]
                    if spec_on:
                        args.append(self._hist)
                    res = self._chunk_exec[cb](*args, *scalars)
                    (
                        self._pool_k, self._pool_v, self._cur, self._pos,
                        self._temps, self._top_ks, self._top_ps,
                        self._keys, self._active, self._remaining,
                        self._eos, tok,
                    ) = res[:12]
                    if spec_on:
                        self._hist = res[12]
                elif spec_on:
                    (
                        self._k, self._v, self._cur, self._pos,
                        self._temps, self._top_ks, self._top_ps,
                        self._keys, self._active, self._remaining,
                        self._eos, tok, self._hist,
                    ) = self._chunk_exec[cb](
                        self.params, self._k, self._v, self._cur,
                        self._pos, self._temps, self._top_ks,
                        self._top_ps, self._keys, self._active,
                        self._remaining, self._eos, self._hist, *scalars,
                    )
                else:
                    (
                        self._k, self._v, self._cur, self._pos,
                        self._temps, self._top_ks, self._top_ps,
                        self._keys, self._active, self._remaining,
                        self._eos, tok,
                    ) = self._chunk_exec[cb](
                        self.params, self._k, self._v, self._cur,
                        self._pos, self._temps, self._top_ks,
                        self._top_ps, self._keys, self._active,
                        self._remaining, self._eos, *scalars,
                    )
                self.spans.device_busy()
                task.next += this_len
                task.chunks += 1
                if self.tracer is not None:
                    from ray_lightning_tpu.obs.trace import SPAN_PREFILL_CHUNK

                    self.tracer.event(
                        task.request_id, SPAN_PREFILL_CHUNK,
                        attrs={
                            "index": task.chunks - 1,
                            "tokens": this_len,
                            "start": task.next - this_len,
                            "slot": slot,
                            "final": is_final,
                        },
                    )
                if not is_final:
                    continue
                del self._prefills[slot]
                self._unref_blocks(task)
                # Insert the finished prompt's full blocks BEFORE any new
                # tenant can overwrite the slot's rows (decode only
                # writes at pos >= P, so the prompt rows stay intact).
                self._insert_prefix(slot, task.tokens)
                # the one D2H sync per admit: behind everything enqueued
                with span(self.spans, "serve.engine.admit_wait", n=1):
                    tok = int(np.asarray(tok))
                self.spans.device_idle()
                done = task.max_new_tokens == 1 or tok == task.eos_token
                if not done:
                    self._slots[slot] = SlotInfo(
                        request_id=task.request_id,
                        max_new_tokens=task.max_new_tokens,
                        n_generated=1,
                        eos_token=task.eos_token,
                        prompt_len=len(task.tokens),
                    )
                out.append((slot, task, tok, done))
            if not progressed:
                break
        return out

    # -- prefix pool -----------------------------------------------------
    def _block_digests(self, tokens: np.ndarray) -> List[bytes]:
        """Chained digests of the prompt's FULL blocks: digest i commits
        to tokens[0:(i+1)*bs], so block i can only hit behind its exact
        prefix chain."""
        bs = self.prefix_block
        out: List[bytes] = []
        d = b""
        for i in range(len(tokens) // bs):
            d = hashlib.blake2b(
                d + np.asarray(
                    tokens[i * bs : (i + 1) * bs], np.int32
                ).tobytes(),
                digest_size=16,
            ).digest()
            out.append(d)
        return out

    def _match_prefix(
        self, tokens: np.ndarray
    ) -> Tuple[List[int], List[str]]:
        """Longest cached prefix walk across ALL tiers: device-pool hits
        are free; host/disk hits PROMOTE the block back into the device
        pool (one compiled H2D pool write) before the seeding copies
        run. Returns (pool block indices, source tier per block), capped
        so the final chunk always runs (the first-token logits need the
        last prompt position's hidden state, which no tier stores).
        Blocks matched earlier in the walk are shielded from eviction by
        a mid-walk promotion (their refs are only taken by the caller
        after the walk returns)."""
        if not self.prefix_blocks:
            return [], []
        matched: List[int] = []
        tiers: List[str] = []
        pinned: set = set()
        tc = self.tier_counters
        for d in self._block_digests(tokens):
            idx = self._pool_map.get(d)
            tier = "device"
            if idx is not None:
                tc["device"]["hits"] += 1
            else:
                tc["device"]["misses"] += 1
                tier = None
                if self._host_budget:
                    if d in self._host_map:
                        tc["host"]["hits"] += 1
                        tier = "host"
                    else:
                        tc["host"]["misses"] += 1
                if tier is None and self._disk_budget:
                    if d in self._disk_map:
                        tc["disk"]["hits"] += 1
                        tier = "disk"
                    else:
                        tc["disk"]["misses"] += 1
                if tier is None:
                    break
                idx = self._promote(d, tier, frozenset(pinned))
                if idx is None:
                    # No allocatable device block (everything pinned by
                    # in-flight prefills) or an unreadable disk entry:
                    # the walk stops and admission prefills the rest
                    # uncached — never a deadlock, never a spurious
                    # eviction of a referenced block.
                    break
            matched.append(idx)
            tiers.append(tier)
            pinned.add(idx)
        while matched and len(matched) * self.prefix_block >= len(tokens):
            matched.pop()
            tiers.pop()
        for idx in matched:
            self._pool_tick += 1
            self._pool_meta[idx].stamp = self._pool_tick
        return matched, tiers

    def _pool_alloc(
        self, avoid: frozenset = frozenset()
    ) -> Optional[int]:
        """A free pool block, evicting the LRU unreferenced block under
        pressure (the victim SPILLS one tier down instead of dying when
        tiers are on); None when every block is pinned. ``avoid``
        shields blocks matched earlier in an in-progress digest walk,
        whose refs are not yet taken."""
        if self._pool_free:
            self.page_allocs += 1
            return self._pool_free.pop()
        victim = None
        for i, m in enumerate(self._pool_meta):
            if m is None or m.refs > 0 or i in avoid:
                continue
            if victim is None or m.stamp < self._pool_meta[victim].stamp:
                victim = i
        if victim is None:
            return None
        vm = self._pool_meta[victim]
        if self._tiered:
            self._spill_block(victim, vm.digest)
        else:
            self._note_dropped(vm.digest)
        del self._pool_map[vm.digest]
        self._pool_meta[victim] = None
        self.prefix_evictions += 1
        self.tier_counters["device"]["evictions"] += 1
        if self.events is not None:
            self.events.record(
                "engine", "prefix_evict", block=victim,
                evictions=self.prefix_evictions, spilled=self._tiered,
            )
        # An evicted-and-reused page is one free plus one alloc in the
        # page ledger (allocs - frees = live pages stays an invariant).
        self.page_frees += 1
        self.page_allocs += 1
        return victim

    # -- spill tiers (host RAM + disk) -----------------------------------
    @staticmethod
    def _norm_index(idx, shape) -> Tuple[Tuple[int, int], ...]:
        """Canonical key of one shard's position: (start, stop) per dim
        — the join between captured shards (``Shard.index``) and the
        indices ``make_array_from_callback`` asks for at refill."""
        return tuple(
            sl.indices(dim)[:2] for sl, dim in zip(idx, shape)
        )

    def _capture_block(self, arr: Any) -> Any:
        """Host payload of one pool-block array: the full np block
        single-device, or THIS process's per-device shards under a mesh
        (a multi-host gang member never materializes remote shards)."""
        if self.mesh is None:
            return np.asarray(arr)
        return {
            self._norm_index(s.index, self._blk_shape): np.asarray(s.data)
            for s in arr.addressable_shards
        }

    def _device_block(self, payload: Any) -> Any:
        """The refill direction: a host payload back to a device-placed
        block — a plain array single-device (the compiled pool write
        does the H2D), or a sharded jax.Array rebuilt shard-by-shard via
        ``make_array_from_callback`` under a mesh (each device receives
        exactly its shard; the full block never lands on one device)."""
        if self.mesh is None:
            return np.ascontiguousarray(payload)
        import jax

        return jax.make_array_from_callback(
            self._blk_shape,
            self._blk_sh,
            lambda idx: payload[self._norm_index(idx, self._blk_shape)],
        )

    def _spill_block(self, victim: int, digest: bytes) -> None:
        """D2H the evicted block (compiled pool read, synced here — off
        the decode hot path; eviction only fires at admission/insert
        time) and push it one tier down: host RAM, else disk."""
        k, v = self._pool_read_exec(
            self._pool_k, self._pool_v, np.int32(victim)
        )
        kp, vp = self._capture_block(k), self._capture_block(v)
        self.tier_counters["device"]["spills"] += 1
        if self._host_budget:
            self._host_insert(digest, kp, vp)
        else:
            self._disk_insert(digest, kp, vp)

    def _host_bytes(self) -> int:
        return len(self._host_map) * self._blk_nbytes

    def _host_insert(self, digest: bytes, kp: Any, vp: Any) -> None:
        """Insert one spilled block into the host tier, evicting oldest
        blocks down to disk (or dropping them) until the byte budget
        holds — the tier is never over budget."""
        self._host_map.pop(digest, None)
        if self._blk_nbytes > self._host_budget:
            # A block the tier can never hold skips straight down.
            if self._disk_budget:
                self.tier_counters["host"]["spills"] += 1
                self._disk_insert(digest, kp, vp)
            else:
                self.tier_counters["host"]["evictions"] += 1
                self._store_sink(digest, kp, vp)
                self._note_dropped(digest)
            return
        while self._host_map and (
            self._host_bytes() + self._blk_nbytes > self._host_budget
        ):
            old_d, (ok, ov) = self._host_map.popitem(last=False)
            if self._disk_budget:
                self.tier_counters["host"]["spills"] += 1
                self._disk_insert(old_d, ok, ov)
            else:
                self.tier_counters["host"]["evictions"] += 1
                self._store_sink(old_d, ok, ov)
                self._note_dropped(old_d)
        self._host_map[digest] = (kp, vp)

    def _disk_paths(self, digest: bytes) -> Tuple[str, str, str]:
        hexd = digest.hex()
        return tuple(
            os.path.join(self.prefix_disk_dir, f"{hexd}.{part}.npy")
            for part in ("keys", "k", "v")
        )

    def _disk_prune_stale(self) -> None:
        """Start the disk tier EMPTY: leftover block files from an
        earlier engine are removed, not adopted — adoption would make
        pool decisions depend on external disk state, breaking the
        multi-host gang's op-stream determinism (every process must make
        identical alloc/promote choices from the op sequence alone)."""
        for name in os.listdir(self.prefix_disk_dir):
            if name.endswith((".keys.npy", ".k.npy", ".v.npy")):
                try:
                    os.remove(os.path.join(self.prefix_disk_dir, name))
                except OSError:
                    pass

    @staticmethod
    def _stack_payload(payload: Any, shape) -> Tuple[np.ndarray, np.ndarray]:
        """(keys, stacked shards) of one payload — shards sorted by
        index so the on-disk form is deterministic; a single-device
        payload is one whole-block 'shard'."""
        if isinstance(payload, dict):
            keys = sorted(payload)
            return (
                np.asarray(keys, np.int64),
                np.stack([payload[k] for k in keys]),
            )
        key = tuple((0, dim) for dim in shape)
        return np.asarray([key], np.int64), payload[None]

    def _disk_insert(self, digest: bytes, kp: Any, vp: Any) -> None:
        """Write one block to the disk tier (atomic per file: tmp +
        rename), then enforce the byte budget on MEASURED file sizes —
        oldest entries drop first, and the tier is never over budget."""
        if not self._disk_budget:
            return
        if digest in self._disk_map:
            self._disk_map.move_to_end(digest)
            return
        keys, kstack = self._stack_payload(kp, self._blk_shape)
        _, vstack = self._stack_payload(vp, self._blk_shape)
        # Store a canonical uint8 byte view: np.save cannot round-trip
        # extension dtypes (bfloat16 comes back as raw void); the load
        # views the bytes back to the engine dtype, which is fixed for
        # the engine's lifetime.
        kstack = np.ascontiguousarray(kstack).view(np.uint8)
        vstack = np.ascontiguousarray(vstack).view(np.uint8)
        size = 0
        paths = self._disk_paths(digest)
        try:
            for path, arr in zip(paths, (keys, kstack, vstack)):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    np.save(f, arr)
                os.replace(tmp, path)
                size += os.path.getsize(path)
        except OSError:
            # Best-effort tier: a full/failing disk drops the block
            # (after a write-through attempt to the persistent store).
            for path in paths:
                try:
                    os.remove(path)
                except OSError:
                    pass
            self.tier_counters["disk"]["evictions"] += 1
            self._store_sink(digest, kp, vp)
            self._note_dropped(digest)
            return
        while self._disk_map and (
            self._disk_bytes + size > self._disk_budget
        ):
            oldest = next(iter(self._disk_map))
            if self.kvstore is not None:
                # Read the victim back before its files go: this is
                # the bottom of the local tier walk, the ONLY copy.
                payload = self._disk_load(oldest)
                if payload is not None:
                    self._store_sink(oldest, payload[0], payload[1])
            self._disk_drop(oldest)
            self.tier_counters["disk"]["evictions"] += 1
            self._note_dropped(oldest)
        if self._disk_bytes + size > self._disk_budget:
            # One block alone exceeds the whole budget: it cannot live
            # here.
            for path in paths:
                try:
                    os.remove(path)
                except OSError:
                    pass
            self.tier_counters["disk"]["evictions"] += 1
            self._store_sink(digest, kp, vp)
            self._note_dropped(digest)
            return
        self._disk_map[digest] = size
        self._disk_bytes += size

    def _disk_drop(self, digest: bytes) -> None:
        size = self._disk_map.pop(digest, 0)
        self._disk_bytes -= size
        for path in self._disk_paths(digest):
            try:
                os.remove(path)
            except OSError:
                pass

    def _disk_load(self, digest: bytes) -> Optional[Tuple[Any, Any]]:
        """Read one block back (memory-mapped; only the needed shards
        are copied out); an unreadable entry is dropped and reported as
        a promotion failure, never an exception on the admission path."""
        kpath, kfile, vfile = self._disk_paths(digest)
        try:
            keys = np.load(kpath)
            kmm = np.load(kfile, mmap_mode="r")
            vmm = np.load(vfile, mmap_mode="r")

            def shard(mm, i):
                # uint8 on disk -> the engine dtype (last axis folds
                # back by itemsize); only the touched rows leave the
                # mmap.
                return np.asarray(mm[i]).view(self._blk_dtype)

            if self.mesh is None:
                return shard(kmm, 0), shard(vmm, 0)
            # The file holds exactly this process's shards (that is what
            # _capture_block spilled), so every entry comes back.
            kd: Dict[Any, np.ndarray] = {}
            vd: Dict[Any, np.ndarray] = {}
            for i, key in enumerate(keys):
                nk = tuple((int(a), int(b)) for a, b in key)
                kd[nk] = shard(kmm, i)
                vd[nk] = shard(vmm, i)
            return kd, vd
        except (OSError, ValueError):
            self._disk_drop(digest)
            self._note_dropped(digest)
            return None

    def _promote(
        self, digest: bytes, tier: str, avoid: frozenset
    ) -> Optional[int]:
        """Move one cold-tier block back into the device pool through
        the compiled H2D pool write; returns the pool index, or None
        when no device block can be allocated (every block pinned) or
        the disk entry is unreadable — the admission then proceeds
        uncached from this point."""
        # Pop the payload BEFORE allocating: the alloc's spill cascade
        # can itself evict this digest from the host map (budget
        # pressure), so holding the payload by reference is the only
        # safe order. On alloc failure it goes back as the tier's MRU.
        if tier == "host":
            payload = self._host_map.pop(digest, None)
        else:
            payload = self._disk_load(digest)
        if payload is None:
            return None
        idx = self._pool_alloc(avoid)
        if idx is None:
            if tier == "host":
                self._host_map[digest] = payload
            elif digest in self._disk_map:
                self._disk_map.move_to_end(digest)
            return None
        t0 = time.monotonic()
        kp, vp = payload
        self._pool_k, self._pool_v = self._pool_write_exec(
            self._pool_k, self._pool_v,
            self._device_block(kp), self._device_block(vp),
            np.int32(idx),
        )
        if tier != "host":
            self._disk_drop(digest)
        self._pool_tick += 1
        self._pool_map[digest] = idx
        self._pool_meta[idx] = _PoolBlock(
            digest=digest, refs=0, stamp=self._pool_tick
        )
        self.tier_counters[tier]["promotions"] += 1
        self.refill_s += time.monotonic() - t0
        return idx

    def _store_sink(self, digest: bytes, kp: Any, vp: Any) -> None:
        """Tier of last resort: a block falling off the bottom of the
        local tier walk writes through to the persistent store (when
        configured) instead of dying. A failed put counts in the
        store's ``write_errors`` and the drop proceeds regardless —
        pages are lost loudly, never silently."""
        if self.kvstore is not None:
            self.kvstore.put_block(digest.hex(), kp, vp)

    # -- cross-replica KV handoff (preempt drain + fleet KV plane) --------
    def _note_dropped(self, digest: bytes) -> None:
        """A digest left EVERY tier (nowhere to spill / disk pruned /
        unreadable): record it for the fleet directory's eviction feed."""
        self.kv_dropped_total += 1
        self._dropped_ring.append(digest.hex())

    def dropped_digests(self) -> List[str]:
        """Recent fully-dropped digest hexes (bounded ring, NOT
        drained): the stats row the driver-side fleet directory prunes
        from — idempotent by construction, so multiple consumers can
        read the same ring."""
        return list(self._dropped_ring)

    def evict_prefix_chain(self, digests_hex: Sequence[str]) -> int:
        """Free a parked chain's blocks from EVERY local tier — the
        session-parking back half (the caller persisted the chain to
        the object store first; this reclaims the pages). Pool pages
        free only when unreferenced (a resident request's pins win —
        same safe-to-free invariant as _pool_alloc's eviction scan);
        freed digests go through the dropped ring so the fleet
        directory forgets this replica's now-stale route, while the
        store's write feed keeps the store-held route alive. Returns
        the number of blocks freed across all tiers."""
        freed = 0
        for hexd in digests_hex:
            try:
                digest = bytes.fromhex(hexd)
            except (ValueError, TypeError):
                continue
            dropped = False
            idx = (
                self._pool_map.get(digest)
                if self.prefix_blocks else None
            )
            if idx is not None:
                meta = self._pool_meta[idx]
                if meta is not None and meta.refs == 0:
                    del self._pool_map[digest]
                    self._pool_meta[idx] = None
                    self._pool_free.append(idx)
                    self.page_frees += 1
                    self.prefix_evictions += 1
                    self.tier_counters["device"]["evictions"] += 1
                    dropped = True
            if self._host_map.pop(digest, None) is not None:
                self.tier_counters["host"]["evictions"] += 1
                dropped = True
            if digest in self._disk_map:
                self._disk_drop(digest)
                self.tier_counters["disk"]["evictions"] += 1
                dropped = True
            if dropped:
                self._note_dropped(digest)
                freed += 1
        return freed

    @property
    def prefix_block_nbytes(self) -> int:
        """Logical bytes of one pool block/page (K + V) — the fleet KV
        plane's transfer-budget unit."""
        return int(self._blk_nbytes) if self.prefix_blocks else 0

    def cached_prefix_blocks(self, tokens: Sequence[int]) -> int:
        """How many leading FULL blocks of ``tokens`` some local tier
        already holds — a pure host-side probe (no promotion, no
        refcounts, no counters): the fleet plane's is-a-fetch-worth-it
        check, capped like the real walk so the final chunk's block
        never counts."""
        if not self.prefix_blocks:
            return 0
        tokens = np.asarray(tokens, np.int32)
        matched = 0
        for d in self._block_digests(tokens):
            if (
                d in self._pool_map
                or d in self._host_map
                or d in self._disk_map
            ):
                matched += 1
            else:
                break
        while matched and matched * self.prefix_block >= len(tokens):
            matched -= 1
        return matched

    def export_blocks_by_digest(
        self, digests_hex: Sequence[str]
    ) -> List[Tuple[str, Any, Any]]:
        """Serialize a digest CHAIN for a fetching peer (the fleet KV
        plane's fetch service): same wire form as
        :meth:`export_prefix_blocks`, but addressed by the digests the
        requester's hint carried instead of by tokens — the export path
        generalized beyond the preempt drain. Chain order, stopping at
        the first digest no tier holds (the requester learns staleness
        from the short reply, not a timeout). Runs the compiled pool
        read — engine driving thread only."""
        if not self.prefix_blocks:
            return []
        out: List[Tuple[str, Any, Any]] = []
        for hexd in digests_hex:
            try:
                d = bytes.fromhex(hexd)
            except ValueError:
                break
            idx = self._pool_map.get(d)
            if idx is not None:
                k, v = self._pool_read_exec(
                    self._pool_k, self._pool_v, np.int32(idx)
                )
                kp, vp = self._capture_block(k), self._capture_block(v)
            elif d in self._host_map:
                kp, vp = self._host_map[d]
            elif d in self._disk_map:
                payload = self._disk_load(d)
                if payload is None:
                    break
                kp, vp = payload
            else:
                break
            out.append((hexd, kp, vp))
            self.prefix_handoff_exports += 1
        return out

    def export_prefix_blocks(
        self, tokens: Sequence[int]
    ) -> List[Tuple[str, Any, Any]]:
        """Serialize the cached prefix of ``tokens`` for a peer engine:
        ``[(digest_hex, k_payload, v_payload), ...]`` in chain order,
        stopping at the first block no tier holds (a later block without
        its ancestors can never be matched). Payloads are the same host
        form the spill tiers keep (full np block single-device, shard
        dict under a mesh), so a same-config peer's
        :meth:`import_prefix_blocks` rebuilds them verbatim. Read-only
        (tiers keep their copies) but it runs the compiled pool read —
        call it from the engine's driving thread only, like every other
        engine method."""
        if not self.prefix_blocks:
            return []
        out: List[Tuple[str, Any, Any]] = []
        for d in self._block_digests(np.asarray(tokens, np.int32)):
            idx = self._pool_map.get(d)
            if idx is not None:
                k, v = self._pool_read_exec(
                    self._pool_k, self._pool_v, np.int32(idx)
                )
                kp, vp = self._capture_block(k), self._capture_block(v)
            elif d in self._host_map:
                kp, vp = self._host_map[d]
            elif d in self._disk_map:
                payload = self._disk_load(d)
                if payload is None:
                    break
                kp, vp = payload
            else:
                break
            out.append((d.hex(), kp, vp))
            self.prefix_handoff_exports += 1
        return out

    def import_prefix_blocks(
        self, blocks: Sequence[Tuple[str, Any, Any]]
    ) -> int:
        """Accept a dying peer's serialized prefix blocks (chain order,
        :meth:`export_prefix_blocks` wire form) into the device pool via
        the compiled H2D pool write, so a migrated request's admission
        walk gets a warm hit instead of a cold re-prefill. Blocks the
        pool already holds are touched (LRU), not rewritten (K/V are a
        pure function of the token prefix, so the bytes are identical);
        when no device block can be allocated the block lands in the
        host tier instead (still one promotion away from warm), and
        with no host tier the chain stops — descendants without this
        ancestor could never match. Returns blocks accepted. Mutates
        pool state: must run on the engine's driving thread (the
        scheduler applies queued imports inside ``step()``)."""
        if not self.prefix_blocks:
            return 0
        accepted = 0
        for hexd, kp, vp in blocks:
            d = bytes.fromhex(hexd)
            idx = self._pool_map.get(d)
            if idx is not None:
                self._pool_tick += 1
                self._pool_meta[idx].stamp = self._pool_tick
                accepted += 1
                continue
            idx = self._pool_alloc()
            if idx is None:
                if self._host_budget:
                    self._host_insert(d, kp, vp)
                    accepted += 1
                    self.prefix_handoff_imports += 1
                    continue
                break
            self._pool_k, self._pool_v = self._pool_write_exec(
                self._pool_k, self._pool_v,
                self._device_block(kp), self._device_block(vp),
                np.int32(idx),
            )
            self._pool_tick += 1
            self._pool_map[d] = idx
            self._pool_meta[idx] = _PoolBlock(
                digest=d, refs=0, stamp=self._pool_tick
            )
            # An imported device copy supersedes any colder local copy
            # (same reasoning as _insert_prefix's dedup).
            if self._tiered:
                self._host_map.pop(d, None)
                if d in self._disk_map:
                    self._disk_drop(d)
            accepted += 1
            self.prefix_handoff_imports += 1
        if accepted and self.events is not None:
            self.events.record(
                "engine", "prefix_handoff_import", blocks=accepted,
            )
        return accepted

    def import_prefix_block_layer(
        self, hexd: str, kp: Any, vp: Any, layer: int, n_layers: int
    ) -> bool:
        """Accept ONE LAYER of a peer's prefix block (layer-pipelined
        shipping): the block stages into an UNKEYED, refs-pinned pool
        slot — invisible to prefix matching (``digest=None``) and safe
        from eviction — and only gains its digest when the last layer
        lands, so a half-shipped block can never serve a hit. Layers
        must arrive in order (the sender streams them in order; a gap
        means a lost/aborted transfer) — out-of-order arrival aborts the
        staging and returns False so the caller falls back to
        whole-prompt shipping or cold prefill. Returns True when the
        layer was absorbed (including the block-already-resident case,
        where the rest of the stream is dropped as a no-op)."""
        if not self.prefix_blocks or self._pool_layer_write_exec is None:
            return False
        d = bytes.fromhex(hexd)
        resident = self._pool_map.get(d)
        if resident is not None:
            # Already keyed (alias admitted it, a local prefill finished
            # first, or a concurrent import won): LRU-touch, swallow the
            # stream — and drop any half-staged twin so its pin can't
            # leak.
            self._pool_tick += 1
            self._pool_meta[resident].stamp = self._pool_tick
            if d in self._layer_imports:
                self.abort_layer_imports([hexd])
            return True
        st = self._layer_imports.get(d)
        if st is None:
            if layer != 0:
                return False
            idx = self._pool_alloc()
            if idx is None:
                return False
            self._pool_tick += 1
            self._pool_meta[idx] = _PoolBlock(
                digest=None, refs=1, stamp=self._pool_tick
            )
            st = {"idx": idx, "next": 0, "n": int(n_layers)}
            self._layer_imports[d] = st
        if layer != st["next"]:
            self.abort_layer_imports([hexd])
            return False
        kl = np.ascontiguousarray(kp)
        vl = np.ascontiguousarray(vp)
        self._pool_k, self._pool_v = self._pool_layer_write_exec(
            self._pool_k, self._pool_v, kl, vl,
            np.int32(st["idx"]), np.int32(layer),
        )
        st["next"] += 1
        if st["next"] < st["n"]:
            return True
        # Last layer: key the digest — the block becomes matchable and
        # evictable in the same instant, exactly like a whole-block
        # import landing.
        idx = st["idx"]
        meta = self._pool_meta[idx]
        meta.digest = d
        meta.refs = 0
        self._pool_map[d] = idx
        if self._tiered:
            self._host_map.pop(d, None)
            if d in self._disk_map:
                self._disk_drop(d)
        del self._layer_imports[d]
        self.layer_block_imports += 1
        self.prefix_handoff_imports += 1
        return True

    def abort_layer_imports(self, digests_hex: Sequence[str]) -> None:
        """Tear down half-staged layer imports (sender died mid-stream,
        out-of-order layer, deadline passed): the pinned unkeyed slots go
        straight back to the free list — nothing was ever matchable, so
        nothing can dangle."""
        for hexd in digests_hex:
            st = self._layer_imports.pop(bytes.fromhex(hexd), None)
            if st is None:
                continue
            idx = st["idx"]
            self._pool_meta[idx] = None
            self._pool_free.append(idx)
            self.page_frees += 1
            self.layer_import_aborts += 1

    def _insert_prefix(self, slot: int, tokens: np.ndarray) -> None:
        """Insert the freshly-prefilled prompt's full blocks (slot rows ->
        pool, compiled copy). Chain-ordered: stop at the first block that
        cannot be allocated — a later block without its ancestors can
        never be matched.

        Paged mode: ZERO copies — the slot's own prompt pages simply
        gain digests in the pool map (they hold exactly the bytes a
        pool insert would have copied), becoming shareable immediately
        and surviving the slot's release as evictable cache pages."""
        if not self.prefix_blocks:
            return
        if self.paged:
            pages = self._slot_pages[slot]
            for i, d in enumerate(self._block_digests(tokens)):
                existing = self._pool_map.get(d)
                if existing is not None:
                    # Already registered: the alias this slot admitted
                    # with, or a concurrent identical prefill that
                    # finished first (its page wins; ours stays a
                    # private twin and dies at release).
                    self._pool_tick += 1
                    self._pool_meta[existing].stamp = self._pool_tick
                    continue
                pg = pages[i]
                meta = self._pool_meta[pg]
                if meta is None or meta.digest is not None:
                    continue
                self._pool_tick += 1
                meta.digest = d
                meta.stamp = self._pool_tick
                self._pool_map[d] = pg
                self.prefix_inserts += 1
                # A fresh device page supersedes any spilled copy of the
                # same digest (identical bytes); dropping it keeps tier
                # budgets honest.
                if self._tiered:
                    self._host_map.pop(d, None)
                    if d in self._disk_map:
                        self._disk_drop(d)
            return
        bs = self.prefix_block
        for i, d in enumerate(self._block_digests(tokens)):
            idx = self._pool_map.get(d)
            if idx is not None:
                self._pool_tick += 1
                self._pool_meta[idx].stamp = self._pool_tick
                continue
            idx = self._pool_alloc()
            if idx is None:
                break
            self._copy_block(idx, slot, i * bs, to_slot=False)
            self._pool_tick += 1
            self._pool_map[d] = idx
            self._pool_meta[idx] = _PoolBlock(
                digest=d, refs=0, stamp=self._pool_tick
            )
            self.prefix_inserts += 1
            # A fresh device insert supersedes any spilled copy of the
            # same digest (identical bytes — K/V are a pure function of
            # the token prefix); dropping it keeps tier budgets honest.
            if self._tiered:
                self._host_map.pop(d, None)
                if d in self._disk_map:
                    self._disk_drop(d)

    def _copy_block(self, block: int, slot: int, row: int,
                    to_slot: bool) -> None:
        (self._pool_k, self._pool_v, self._k, self._v) = self._copy_exec(
            self._pool_k, self._pool_v, self._k, self._v,
            np.int32(block), np.int32(slot), np.int32(row),
            np.bool_(to_slot),
        )

    def _unref_blocks(self, task: PrefillTask) -> None:
        for b in task.block_refs:
            meta = self._pool_meta[b]
            if meta is not None:
                meta.refs -= 1
        task.block_refs = []

    def _pool_used(self) -> int:
        """Occupied pool blocks/pages (paged mode excludes the scratch
        page and the quarantine - neither holds live data)."""
        used = self.prefix_blocks - len(self._pool_free)
        if self.paged:
            used -= 1 + len(self._quarantine)
        return max(0, used)

    def prefix_stats(self) -> Dict[str, Any]:
        """Pool counters for the stats endpoint; with tiers on,
        a per-tier breakdown and the cumulative refill seconds ride
        along."""
        out: Dict[str, Any] = {
            "lookups": self.prefix_lookups,
            "hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens": self.prefix_prompt_tokens,
            "inserts": self.prefix_inserts,
            "evictions": self.prefix_evictions,
            "blocks_used": self._pool_used(),
            "blocks_total": self.prefix_blocks,
        }
        if self.prefix_blocks:
            out["tiers"] = self.prefix_tier_stats()
        if self._tiered:
            out["refill_s"] = round(self.refill_s, 6)
        return out

    def prefix_tier_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-tier cumulative counters plus resident/budget bytes
        (device always; host/disk only when budgeted) — the stats-
        endpoint face of the tier walk."""
        used = self._pool_used()
        out: Dict[str, Dict[str, int]] = {
            "device": {
                **self.tier_counters["device"],
                "bytes": used * self._blk_nbytes,
                "budget_bytes": self.prefix_blocks * self._blk_nbytes,
            }
        }
        if self._host_budget:
            out["host"] = {
                **self.tier_counters["host"],
                "bytes": self._host_bytes(),
                "budget_bytes": self._host_budget,
            }
        if self._disk_budget:
            out["disk"] = {
                **self.tier_counters["disk"],
                "bytes": self._disk_bytes,
                "budget_bytes": self._disk_budget,
            }
        return out

    def prefix_tier_counters(self) -> Dict[str, Dict[str, int]]:
        """Cumulative per-tier event counters (all three tiers, zeros
        for disabled ones) — the scheduler diffs consecutive snapshots
        into per-step ServeMetrics deltas."""
        return {t: dict(c) for t, c in self.tier_counters.items()}

    def prefix_tier_bytes(self) -> Dict[str, int]:
        """Resident bytes per ENABLED tier (the
        ``rlt_serve_prefix_bytes{tier=}`` gauge values)."""
        used = self._pool_used()
        out = {"device": used * self._blk_nbytes}
        if self._host_budget:
            out["host"] = self._host_bytes()
        if self._disk_budget:
            out["disk"] = self._disk_bytes
        return out

    def release(self, slot: int) -> None:
        """Evict a slot (cancelled, or host-observed finished); it is
        immediately reusable — the stale cache rows are invisible behind
        the slot masks and get overwritten by the next tenant. A
        host-initiated eviction also deactivates the slot ON DEVICE
        (queued after any in-flight fold, whose tokens for this tenant
        are dropped at harvest via the ``released`` marker). A slot
        cancelled MID-PREFILL drops its state machine and unpins its
        prefix blocks; the partially-written rows are invisible behind
        the next tenant's own prefill."""
        task = self._prefills.pop(slot, None)
        if task is not None:
            self._unref_blocks(task)
            self._release_pages(slot)
            self._deactivate(slot)
            return
        info = self._slots[slot]
        if info is None:
            return
        info.released = True
        self._slots[slot] = None
        self._release_pages(slot)
        self._deactivate(slot)

    def _deactivate(self, slot: int) -> None:
        self._slot_write(
            slot, 0, 0, 0.0, 0, 1.0,
            np.zeros(2, np.uint32), False, 0, -1,
        )

    def _release_synced(self, slot: int, info: SlotInfo) -> None:
        # Device-detected completion: the fold already froze the slot
        # in-graph at exactly this token, so no deactivate write is
        # needed — host bookkeeping only. Paged mode still resets the
        # page table (frozen slots keep issuing masked garbage writes at
        # their final position; pointing them at scratch lets the pages
        # recycle safely).
        info.released = True
        self._slots[slot] = None
        self._release_pages(slot)

    # -- the hot loop ----------------------------------------------------
    def _pick_fold_k(self) -> int:
        """Choose this dispatch's fold depth from the pre-lowered ladder —
        a pure function of the op stream (slot bookkeeping + prefill
        queue), so every gang member picks the same rung without any
        cross-host chatter. Shallow under pressure (pending prefills want
        frequent piggyback rows; short-remaining slots would waste deep
        folds on frozen iterations), deep when every resident has runway.
        Ladder switches hit pre-compiled executables: zero steady-state
        compiles by construction."""
        ladder = self.fold_ladder
        if len(ladder) == 1:
            return ladder[0]
        if self._prefills:
            # Admissions in flight: shallowest rung so piggybacked chunk
            # rows (and, without piggyback, interleaved chunk dispatches)
            # get a slice of the device as often as possible.
            return ladder[0]
        runway = 0
        for info in self._slots:
            if info is None or info.released:
                continue
            runway = max(runway, info.max_new_tokens - info.n_generated)
        best = ladder[0]
        for k in ladder:
            if k <= runway and k > best:
                best = k
        return best

    def _plan_piggyback(
        self,
    ) -> Tuple[
        Tuple[Any, ...],
        List[Tuple[int, int, PrefillTask, Optional[SlotInfo]]],
        List[Tuple[int, np.ndarray]],
        int,
    ]:
        """Build the piggyback tail for one fused dispatch: up to
        ``piggyback_chunks`` rows of prefill-chunk work, one per
        prefilling slot in slot order (the same round-robin key
        ``prefill_step`` uses, so the op stream stays gang-deterministic).
        Host bookkeeping advances NOW — tasks step forward, finals leave
        ``_prefills`` and arm their ``SlotInfo`` — because by the time the
        fused executable is enqueued the device work is as committed as a
        separate chunk dispatch would be; only the final's first TOKEN is
        deferred to harvest. Returns ``(pb_args, finals, inserts, n_on)``
        where ``inserts`` are prefix-pool insertions that MUST run after
        the fold is enqueued (their copy executables chain on the donated
        caches and must read post-chunk bytes)."""
        C = self.piggyback_chunks
        cb = self.prefill_chunk
        chunk = np.zeros((C, cb), np.int32)
        start = np.zeros(C, np.int32)
        length = np.zeros(C, np.int32)
        slot_ix = np.zeros(C, np.int32)
        key0 = np.zeros((C, 2), np.uint32)
        temp = np.zeros(C, np.float32)
        tks = np.zeros(C, np.int32)
        tps = np.ones(C, np.float32)
        n_new = np.zeros(C, np.int32)
        eos = np.full(C, -1, np.int32)
        final = np.zeros(C, np.bool_)
        on = np.zeros(C, np.bool_)
        finals: List[Tuple[int, int, PrefillTask, Optional[SlotInfo]]] = []
        inserts: List[Tuple[int, np.ndarray]] = []
        r = 0
        for slot in sorted(self._prefills):
            if r >= C:
                break
            task = self._prefills[slot]
            P = len(task.tokens)
            this_len = min(cb, P - task.next)
            is_final = task.next + this_len >= P
            chunk[r, :this_len] = task.tokens[
                task.next : task.next + this_len
            ]
            start[r] = task.next
            length[r] = this_len
            slot_ix[r] = slot
            key0[r] = task.key0
            temp[r] = task.temperature
            tks[r] = task.top_k
            tps[r] = task.top_p
            n_new[r] = task.max_new_tokens
            eos[r] = task.eos_token
            final[r] = is_final
            on[r] = True
            task.next += this_len
            task.chunks += 1
            if self.tracer is not None:
                from ray_lightning_tpu.obs.trace import SPAN_PREFILL_CHUNK

                self.tracer.event(
                    task.request_id, SPAN_PREFILL_CHUNK,
                    attrs={
                        "index": task.chunks - 1,
                        "tokens": this_len,
                        "start": task.next - this_len,
                        "slot": slot,
                        "final": is_final,
                        "piggyback": True,
                    },
                )
            if is_final:
                del self._prefills[slot]
                self._unref_blocks(task)
                inserts.append((slot, task.tokens))
                # Arm the slot NOW (the device's own `live` predicate
                # already froze done-at-first-token requests) so a
                # pipelined fold N+1 snapshot carries the tenant; the
                # first token itself is harvested from pb_toks later.
                info = SlotInfo(
                    request_id=task.request_id,
                    max_new_tokens=task.max_new_tokens,
                    n_generated=1,
                    eos_token=task.eos_token,
                    prompt_len=len(task.tokens),
                )
                self._slots[slot] = info
                finals.append((r, slot, task, info))
            r += 1
        pb_args = (
            chunk, start, length, slot_ix, key0, temp, tks, tps,
            n_new, eos, final, on,
        )
        return pb_args, finals, inserts, r

    def _dispatch(
        self,
    ) -> Tuple[
        Tuple[Any, Any, Any, Any],
        List[Optional[SlotInfo]],
        List[Tuple[int, int, PrefillTask, Optional[SlotInfo]]],
        int,
    ]:
        """Launch one fold against the current device state (async); the
        donated state arrays are replaced by the fold's outputs, so
        subsequent writes (admission, eviction) queue after it. With
        spec on the fold is propose-then-verify: the token block grows to
        ``fold * (spec_depth + 1)`` rows, most of them non-emitted. With
        piggyback on, up to C prefill-chunk rows ride the SAME dispatch
        (their first-token samples come back appended), and the fold
        depth K is picked per dispatch from the pre-lowered ladder."""
        k = self._pick_fold_k()
        with span(
            self.spans, "serve.engine.dispatch",
            fold=k, slots=self.num_active,
        ):
            out = self._enqueue_fold(k)
        self.spans.device_busy()
        return out

    def _enqueue_fold(
        self, k: int
    ) -> Tuple[
        Tuple[Any, Any, Any, Any],
        List[Optional[SlotInfo]],
        List[Tuple[int, int, PrefillTask, Optional[SlotInfo]]],
        int,
    ]:
        self.fold_dispatches[k] = self.fold_dispatches.get(k, 0) + 1
        self._m_fold_depth.observe(float(k))
        pb_args: Tuple[Any, ...] = ()
        pb_finals: List[
            Tuple[int, int, PrefillTask, Optional[SlotInfo]]
        ] = []
        inserts: List[Tuple[int, np.ndarray]] = []
        if self.piggyback_chunks:
            pb_args, pb_finals, inserts, n_on = self._plan_piggyback()
            if n_on:
                self.piggyback_dispatches += 1
                self.piggyback_chunk_rows += n_on
                self._m_pb_dispatches.inc()
                self._m_pb_rows.inc(float(n_on))
        spec_on = self.spec != "off"
        args: List[Any] = [self.params]
        if self.spec == "model":
            args.append(self._spec_params)
        if self.paged:
            # Same shapes of state in and out; the pools + the read-only
            # page table stand in for the dense caches.
            args += [self._pool_k, self._pool_v, self._table]
        else:
            args += [self._k, self._v]
        args += [
            self._cur, self._pos, self._temps, self._top_ks,
            self._top_ps, self._keys, self._active, self._remaining,
            self._eos,
        ]
        if spec_on:
            args.append(self._hist)
        res = self._step_exec[k](*args, *pb_args)
        pb_toks = moe = None
        if self.piggyback_chunks:
            pb_toks = res[-1]
            res = res[:-1]
        if self.cfg.mixed:
            moe = res[-1]
            res = res[:-1]
        if spec_on:
            (
                tok_block, emit_block, self._cur, self._pos, self._keys,
                self._active, self._remaining, self._hist, c0, c1,
            ) = res
        else:
            (
                tok_block, emit_block, self._cur, self._pos, self._keys,
                self._active, self._remaining, c0, c1,
            ) = res
        if self.paged:
            self._pool_k, self._pool_v = c0, c1
        else:
            self._k, self._v = c0, c1
        # Deferred prefix inserts: their copy/registration executables
        # chain on the caches just donated to the fold above, so they
        # read the post-chunk bytes — never the pre-chunk ones.
        for slot, tokens in inserts:
            self._insert_prefix(slot, tokens)
        return (
            (tok_block, emit_block, pb_toks, moe),
            list(self._slots),
            pb_finals,
            k,
        )

    def _want_next(
        self, snapshot: List[Optional[SlotInfo]], k_used: int
    ) -> bool:
        """Speculation predicate: dispatch fold N+1 before harvesting fold
        N iff some occupied slot can outlive fold N by token count, or a
        prefill is pending and piggyback is on (each fused dispatch
        advances the prefill queue, so this terminates). (An EOS inside
        fold N can still idle the speculative fold — frozen slots emit
        nothing, so it only costs compute, never correctness.) With spec
        on, fold N consumes AT LEAST ``k_used`` tokens per live slot
        (each verify emits >= 1) and up to (depth+1)x that; speculating
        on the minimum keeps the pipeline full on low-accept workloads at
        the price of an occasional idle fold on high-accept ones.
        """
        if self.piggyback_chunks and self._prefills:
            return True
        K = k_used
        for slot, info in enumerate(self._slots):
            if info is None:
                continue
            consumed = K if snapshot[slot] is info else 0
            if info.max_new_tokens - info.n_generated > consumed:
                return True
        return False

    def step(self) -> List[Tuple[int, str, int, bool]]:
        """One fold boundary: dispatch (double-buffered) and fan out up to
        ``fold K`` tokens per occupied slot, in fold order; returns
        ``(slot, request_id, token, done)`` per emitted token. Finished
        slots are evicted and recycled before returning. Piggybacked
        prefill completions are NOT returned here — the scheduler reads
        them via :meth:`pop_chunk_events` right after this call."""
        if self._inflight is None:
            # Only DECODING residents (or, with piggyback on, pending
            # prefill chunks) warrant a fold — otherwise parked slots
            # emit nothing and the dispatch would be pure waste.
            if not any(s is not None for s in self._slots) and not (
                self.piggyback_chunks and self._prefills
            ):
                return []
            self._inflight = self._dispatch()
        outs, snapshot, pb_finals, k_used = self._inflight
        self._inflight = (
            self._dispatch()
            if self.pipeline and self._want_next(snapshot, k_used)
            else None
        )
        return self._harvest(outs, snapshot, pb_finals)

    def pop_chunk_events(self) -> List[Tuple[int, PrefillTask, int, bool]]:
        """Drain the piggybacked prefill completions of the LAST harvested
        fold — same ``(slot, task, first_token, done)`` rows
        ``prefill_step`` returns, so the scheduler's completion plumbing
        is shared verbatim. Host-side read, never broadcast: gang
        followers that don't pop still converge because the buffer is
        REPLACED (not appended) every harvest."""
        out = self._pb_events
        self._pb_events = []
        return out

    def _harvest(
        self,
        outs: Tuple[Any, Any, Any, Any],
        snapshot: List[Optional[SlotInfo]],
        pb_finals: Sequence[
            Tuple[int, int, PrefillTask, Optional[SlotInfo]]
        ] = (),
    ) -> List[Tuple[int, str, int, bool]]:
        # The ONE D2H sync per fold: the (K, B) token block + emit mask
        # (K = fold * (spec_depth + 1) with spec on).
        with span(self.spans, "serve.engine.harvest_wait"):
            toks = np.asarray(outs[0])
            emits = np.asarray(outs[1])
            if outs[3] is not None:
                # the layers' counts of this fold: six or seven numbers
                # that were ready with the tokens
                m = np.asarray(outs[3])
                self._count_moe("decode", m, int(m[3]))
        if self._inflight is None:
            # nothing was dispatched behind this fold: the device is
            # idle until the host enqueues again
            self.spans.device_idle()
        with span(self.spans, "serve.engine.harvest", rows=toks.shape[0]):
            return self._fan_out(toks, emits, outs, snapshot, pb_finals)

    def _count_moe(self, phase: str, counts: np.ndarray, n: int) -> None:
        """Add one fold's or one admission's counts to the totals:
        ``[pairs routed, pairs on held experts, held experts hit]``, then
        (decode) ``[live iterations, slot-steps, live slot-steps]`` — with
        state layers a fourth, the slot-steps visited — or (an admission)
        ``[rows scanned, real rows]``; ``n`` is its token steps (decode)
        or 1 (an admission)."""
        row = self.moe_totals[phase]
        row["pairs_routed"] += int(counts[0])
        row["pairs_held"] += int(counts[1])
        row["experts_hit"] += int(counts[2])
        row["token_steps" if phase == "decode" else "admissions"] += n
        if self._state_layers:
            row = self.ssm_totals[phase]  # the vector's last entries, in the row's order
            for key, c in zip(row, counts[-len(row):]):
                row[key] += int(c)

    def _fan_out(
        self,
        toks: np.ndarray,
        emits: np.ndarray,
        outs: Tuple[Any, Any, Any, Any],
        snapshot: List[Optional[SlotInfo]],
        pb_finals: Sequence[
            Tuple[int, int, PrefillTask, Optional[SlotInfo]]
        ],
    ) -> List[Tuple[int, str, int, bool]]:
        # The harvest's sync proves every fold dispatched up to this one has
        # finished on device — pages quarantined BEFORE this harvest can
        # no longer be scribbled and recycle now. Pages quarantined
        # DURING it (_release_synced below) wait for the next harvest:
        # the already-dispatched next fold may still write them.
        self._flush_quarantine()
        out: List[Tuple[int, str, int, bool]] = []
        spec_on = self.spec != "off"
        group = self.spec_depth + 1 if spec_on else 1
        #: (fold_iteration, slot) -> tokens this verify emitted; feeds
        #: the accept-rate accounting (zombie tokens of released tenants
        #: are dropped above AND excluded here).
        counts: Dict[Tuple[int, int], int] = {}
        # rows the kernel visits, by the block it walks in (a block a
        # counted kind at most: one of them in every configuration so far)
        rows_live, rows_visited = 0, {b: 0 for _, b in self._attn_reads.values() if b}
        for kk in range(toks.shape[0]):
            for slot, info in enumerate(snapshot):
                if info is None or info.released or not emits[kk, slot]:
                    continue
                tok = int(toks[kk, slot])
                if kk % group == 0:
                    # the rows 0 .. pos this token step's query saw, and
                    # the kernel's blocks that hold them
                    rows = info.prompt_len + info.n_generated
                    rows_live += rows
                    for blk in rows_visited:
                        rows_visited[blk] += -(-rows // blk) * blk
                info.n_generated += 1
                done = (
                    info.n_generated >= info.max_new_tokens
                    or tok == info.eos_token
                )
                out.append((slot, info.request_id, tok, done))
                if spec_on:
                    key = (kk // group, slot)
                    counts[key] = counts.get(key, 0) + 1
                if done:
                    self._release_synced(slot, info)
        for layers, blk in self._attn_reads.values():
            allocated = (
                (toks.shape[0] // group) * layers * self.num_slots
                * self.max_seq
            )
            self.attn_totals["rows_allocated"] += allocated
            self.attn_totals["rows_visited"] += (
                layers * rows_visited[blk] if blk else allocated
            )
            self.attn_totals["rows_live"] += layers * rows_live
        if counts:
            # Per (verify, slot): depth tokens proposed, emitted - 1 of
            # them accepted (the final emission is the verify's own
            # sample — a mismatch, a bonus token, or an EOS).
            self.spec_verifies += len(counts)
            self.spec_drafted_tokens += self.spec_depth * len(counts)
            self.spec_emitted_tokens += sum(counts.values())
            self.spec_accepted_tokens += sum(
                m - 1 for m in counts.values()
            )
        if pb_finals:
            # Piggybacked prefill completions: their first tokens rode
            # back in the SAME sync as the token block above. Buffered
            # (replaced, not appended) for pop_chunk_events.
            events: List[Tuple[int, PrefillTask, int, bool]] = []
            pb_toks_np = np.asarray(outs[2])
            for r, slot, task, info in pb_finals:
                if info is not None and info.released:
                    # Cancel raced the fused dispatch: release() already
                    # tore the slot down and its queued deactivate write
                    # wins over the in-graph arm. Drop the token.
                    continue
                tok = int(pb_toks_np[r])
                done = task.max_new_tokens == 1 or tok == task.eos_token
                if done and info is not None:
                    self._release_synced(slot, info)
                events.append((slot, task, tok, done))
            self._pb_events = events
        return out

    def spec_stats(self) -> Dict[str, Any]:
        """Speculative-decoding counters for the stats endpoint: accept_rate =
        accepted draft tokens / proposed draft tokens in [0, 1];
        tokens_per_verify = emitted tokens per verify forward in
        [1, spec_depth + 1] (the per-forward multiplier spec buys)."""
        v, d = self.spec_verifies, self.spec_drafted_tokens
        return {
            "mode": self.spec,
            "depth": self.spec_depth,
            "verifies": v,
            "drafted_tokens": d,
            "accepted_tokens": self.spec_accepted_tokens,
            "emitted_tokens": self.spec_emitted_tokens,
            "accept_rate": (
                round(self.spec_accepted_tokens / d, 4) if d else 0.0
            ),
            "tokens_per_verify": (
                round(self.spec_emitted_tokens / v, 4) if v else 0.0
            ),
        }
