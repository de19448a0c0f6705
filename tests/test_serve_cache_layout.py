"""Which engine keeps which KV cache layout, and that ``cache_stats()``
(``stats()["cache"]``) says so.

One device, dense: ``(L, slots, max_seq, Hkv * hd)`` — a position's KV
heads side by side in one row, the read every decode step then runs
(``models/gpt.py:_attend_layer_cache``). Under a mesh the heads keep an
axis of their own to shard over "model"; a paged engine has no dense
cache. The prefix pool keeps its blocks ``(L, N, block, Hkv, hd)`` in
every engine, so spilled and exported blocks have one format.
"""
import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import GPTConfig, gpt_generate, init_gpt_params

CFG = GPTConfig.llama(
    vocab_size=97, n_layer=2, n_head=4, n_kv_head=2, d_model=32, max_seq=32,
    attn_impl="reference", compute_dtype="float32",
)
L, B, S, HKV, HD = 2, 2, 32, 2, 8
BASE = dict(num_slots=B, prefill_buckets=[8])

ENGINES = {
    "one-device": (dict(BASE), (L, B, S, HKV * HD)),
    "one-device-spec-ngram": (dict(BASE, spec="ngram", spec_depth=2), (L, B, S, HKV * HD)),
    "one-device-chunked-prefix-pool": (
        dict(BASE, prefill_chunk=4, prefix_blocks=4, prefix_block=4), (L, B, S, HKV * HD)),
    "mesh": (dict(BASE), (L, B, S, HKV, HD)),
    "paged": (dict(BASE, prefill_chunk=4, kv_page=4, kv_pages=24), None),
}


@pytest.fixture(scope="module")
def params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), CFG)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_engine_cache_layout_and_what_cache_stats_says(kind, params):
    import jax

    from ray_lightning_tpu.serve.engine import DecodeEngine

    kw, shape = ENGINES[kind]
    mesh = None
    if kind == "mesh":
        n = len(jax.devices())
        if n < 2 or n % 2:
            pytest.skip(f"needs an even number of devices (xla_force_host_platform_device_count), have {n}")
        from ray_lightning_tpu.parallel.mesh import build_mesh

        mesh = build_mesh((2, n // 2), ("model", "data"))
    eng = DecodeEngine(params, CFG, mesh=mesh, **kw)
    if shape is None:
        assert eng._k is None and eng.cache_stats() == {}
    else:
        assert eng._k.shape == eng._v.shape == shape
        assert eng.cache_stats() == {"full": {
            "layers": L, "rows_per_slot": S, "bytes": 2 * L * B * S * HKV * HD * 4,
            "row_layout": len(shape) == 4,
        }}
    if kw.get("prefix_blocks"):
        assert eng._pool_k.shape == (L, 4, 4, HKV, HD)
    # and the layout is the engine's own business: the tokens are gpt_generate's
    prompt = [5, 17, 3, 44, 9, 9, 2]
    _, tok, _ = eng.admit(prompt, request_id="r", max_new_tokens=6)
    got = [] if tok is None else [tok]
    while eng.num_active:
        got += [tok for _, _, tok, _ in eng.prefill_step(1)]
        got += [tok for _, _, tok, _ in eng.step()]
    want = np.asarray(gpt_generate(params, CFG, np.asarray(prompt, np.int32)[None], 6))[0].tolist()
    assert got == want[len(prompt):]


# -- the read that goes with the rows, and what stats()["attn"] says of it ------------
KERNEL_CFG = GPTConfig.llama(
    vocab_size=97, n_layer=2, n_head=4, n_kv_head=2, d_model=256, max_seq=384, compute_dtype="float32",
)  # rows of 2 x 64 = 128 lanes, three blocks of 128 a slot: shapes the decode kernel takes


@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_replica_counts_the_cache_rows_its_decode_attention_visits(read, monkeypatch):
    """``stats()["attn"]`` and the three ``rlt_serve_attn_rows_*_total``
    series: every allocated row on the XLA read; under the decode kernel
    (its selection told "tpu": it interprets here) the blocks up to each live
    slot's position, and nothing for the idle slot. Counted on the host from
    the slots' records: no program is added."""
    import time

    import jax

    from ray_lightning_tpu.serve.server import ServeReplica
    from tests.utils import force_decode_kernel

    if read == "kernel":
        force_decode_kernel(monkeypatch)
    params = init_gpt_params(jax.random.PRNGKey(0), KERNEL_CFG)
    rep = ServeReplica(params=params, model_config=KERNEL_CFG.__dict__.copy(), num_slots=3, max_seq=384,
                       prefill_buckets=[16, 256], decode_fold=4, watchdog=False)
    try:
        rng = np.random.default_rng(1)
        # one slot idle throughout; a request inside block 0, one that crosses into block 1 and 2
        work = [(10, 20), (250, 12)]
        rids = [rep.submit(rng.integers(0, 96, size=p).tolist(), max_new_tokens=n) for p, n in work]
        deadline = time.monotonic() + 120
        for rid in rids:
            while not rep.result(rid, wait_s=0.2)["done"]:
                assert time.monotonic() < deadline, "request did not finish"
        st = rep.stats()
        attn, layers = st["attn"], KERNEL_CFG.n_layer
        # a token step at position pos sees rows 0 .. pos; the first token came from the prefill
        assert attn["rows_live"] == layers * sum(p + g for p, n in work for g in range(1, n))
        assert attn["rows_allocated"] % (layers * 3 * 384 * 4) == 0  # whole folds of 4 steps, 3 slots
        assert attn["rows_live"] <= attn["rows_visited"] <= attn["rows_allocated"]
        if read == "xla":
            assert attn["rows_visited"] == attn["rows_allocated"]
        else:
            assert attn["rows_visited"] == layers * 128 * sum(-(-(p + g) // 128) for p, n in work for g in range(1, n))
            assert attn["rows_visited"] < 0.45 * attn["rows_allocated"]
        assert st["compiles_since_init"] == 0
        text = rep.metrics_text()
        for key in ("rows_allocated", "rows_visited", "rows_live"):
            # the registry is the process's: a replica before this one has counted into it too
            line = next(ln for ln in text.splitlines() if ln.startswith(f"rlt_serve_attn_{key}_total "))
            assert float(line.split()[1]) >= attn[key] > 0
    finally:
        rep.stop()
