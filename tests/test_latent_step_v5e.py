"""Compile-only guard for a described ``v5e:2x2`` (no chip attached; nothing
runs), beside ``tests/test_state_step_v5e.py`` and built as it builds its
program, from the cell's own files.

**The latent cell's decode fold** (``kanana-2-30b-a3b-d16-ep8.serve-docqa``:
64 slots x 6656 positions, sixteen latent layers): a layer's latents are one
array of 436 MB that a token step reads twice (scores, then the weighted
sum) and writes one row a slot into. The fold donates the caches, the
latents and the shared rotary keys are two arrays that each matmul takes
where they lie, and ``lat_wkv_b`` is stored in the order both of its uses
multiply in: temporaries of 0.03 GiB in a program of 10.7 GiB (PERF.md §4).
As one array of rows ``[latent; key]`` the same fold did not fit the chip:
the compiler kept a padded copy of the whole cache (8.1 GiB) and re-laid
one layer out for the second matmul (0.46 GiB a layer and step). This is
the guard that no such copy comes back — under the XLA read and under the
decode kernel (``ops/decode_attention.py:latent_decode_attention``), which
takes both stacked caches whole and the rotary keys with the positions
minor (as rows of 64 Mosaic refuses the slice): the compiler keeps the
64-wide rows ``{2,3,1,0}``, so the ``swapaxes`` that hands them over has to
stay a bitcast of the bytes and not 0.87 GB re-laid out a call.
"""
import os
import re
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2**30
CELL = "kanana-2-30b-a3b-d16-ep8.serve-docqa"


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # see tests/test_decode_rows_v5e.py: this file asks for no lock of the TPU's library
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _decode_fold(v5e, cell_name, read, sizes, k_shapes, v_shapes):
    """A cell's decode fold, lowered as ``serve/engine.py`` lowers
    ``step_impl``: ``(compiled, slots, positions, seconds it took, read)``.
    ``xla``: the read the engine takes off the TPU (``jax.default_backend()``
    is the CPU here); ``kernel``: the read it takes on the chip — said
    "tpu" where the program asks, as ``tests/test_decode_rows_v5e.py``
    does. ``sizes`` (slots, positions) and the caches' shapes by kind are
    what the caller's numbers were read at."""
    import sys

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    try:
        from pb import weights
        from pb.spec import Spec
    finally:
        sys.path.remove(os.path.join(ROOT, "perfbench"))

    from ray_lightning_tpu.models.gpt import GPTConfig, gpt_decode_fold
    from ray_lightning_tpu.models.mixed import empty_caches

    mp = pytest.MonkeyPatch()
    if read == "kernel":
        mp.setattr(jax, "default_backend", lambda: "tpu")
    t0 = time.monotonic()
    spec = Spec(ROOT)
    cell = spec.cell(cell_name)
    cfg, rep = spec.config(cell["config"]), spec.traffic(cell["traffic"])["replica"]
    dims = spec.dims(cfg)
    pc = GPTConfig(**cfg["program_config"])
    one, dt = SingleDeviceSharding(v5e), jnp.dtype(cfg["weights_dtype"])

    def sds(shape, d):
        return jax.ShapeDtypeStruct(shape, d, sharding=one)

    shapes = weights.param_shapes(dims, pc.max_seq)
    params = {k: sds(v[0], dt) for k, v in shapes.items() if k != "blocks"}
    params["blocks"] = {k: sds(v[0], dt) for k, v in shapes["blocks"].items()}
    B, S = int(rep["num_slots"]), int(rep["max_seq"])
    assert (B, S) == sizes, "the sizes below are this cell's"
    k_cache, v_cache = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda: empty_caches(pc, B, S, dt)))
    assert {k: a.shape for k, a in k_cache.items()} == k_shapes
    assert {k: a.shape for k, a in v_cache.items()} == v_shapes
    i32, f32 = (lambda: sds((B,), jnp.int32)), (lambda: sds((B,), jnp.float32))

    def step(params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps, keys, active, remaining, eos):
        return gpt_decode_fold(params, pc, cur, pos, keys, temps, top_ks, top_ps, active, remaining, eos,
                               k_cache, v_cache, fold=int(rep["decode_fold"]))

    # donated as serve/engine.py donates them: caches and the state the fold moves
    try:
        compiled = jax.jit(step, donate_argnums=(1, 2, 3, 4, 8, 9, 10)).lower(
            params, k_cache, v_cache, i32(), i32(), f32(), i32(), f32(), sds((B, 2), jnp.uint32),
            sds((B,), jnp.bool_), i32(), i32(),
        ).compile()
    finally:
        mp.undo()
    return compiled, B, S, time.monotonic() - t0, read


@pytest.fixture(scope="module", params=["xla", "kernel"])
def fold(v5e, request):
    """The latent cell's decode fold."""
    return _decode_fold(v5e, CELL, request.param, (64, 6656),
                        {"latent": (16, 64, 6656, 512)}, {"latent": (16, 64, 6656, 64)})


def test_the_latent_cells_decode_fold_keeps_its_sizes(fold):
    compiled, B, S, took, read = fold
    m = compiled.memory_analysis()
    whole = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    cache = 16 * B * S * (512 + 64) * 2
    print(f"latent cell's decode fold at {B} x {S}, {read} read: temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, "
          f"whole program {whole / GIB:.2f} GiB, built in {took:.0f} s")
    assert m.alias_size_in_bytes >= cache  # the caches are updated where they lie
    assert m.temp_size_in_bytes < 0.3 * GIB  # 0.027 read; one layer's latents copied would be 0.41 more
    assert whole < 11.2 * GIB  # 10.70 read: 3.36 of weights, 7.31 of latents and keys
    assert took < 300, "the guard's own time limit: 35 s read"
    mosaic = [ln for ln in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    # the fold is a scan: its body, one token step, is in the program once, a call a latent layer
    assert len(mosaic) == (16 if read == "kernel" else 0), mosaic
    assert all("decode_attention" in ln.split(" = ")[0] for ln in mosaic), mosaic


def test_the_fold_copies_no_latent_layers_cache(fold):
    """No instruction of the compiled fold copies or transposes an array the
    size of one layer's latents or keys, or of the stack of them."""
    compiled, B, S, _, read = fold
    text = compiled.as_text().splitlines()
    size = re.compile(rf"= bf16\[(16,)?{B},({S},512|{S},64|64,{S})\]\S* (copy|transpose)\(")
    hits = [ln.strip()[:160] for ln in text if size.search(ln)]
    assert not hits, hits
    # the keys with the positions minor, as the kernel takes them: the cache's own bytes, a call a layer
    turned = [ln.strip()[:160] for ln in text if re.search(rf"= bf16\[16,{B},64,{S}\]", ln)]
    assert len(turned) == (16 if read == "kernel" else 0) and all(" bitcast(" in ln for ln in turned), turned


# -- the mixed cell's full layers: K rows of 768, V rows of 512 -----------------------------------------
MIMO = "mimo-v2-flash-d7-ep16.serve-mixedlen"


@pytest.fixture(scope="module", params=["xla", "kernel"])
def mimo_fold(v5e, request):
    """``mimo-v2-flash-d7-ep16.serve-mixedlen``'s decode fold: 64 slots x
    5,120 positions, two full layers (4 KV heads of 192 / 128: a layer's K
    and V are 503 + 336 MB) and five window layers on rings of 128 rows."""
    return _decode_fold(
        v5e, MIMO, request.param, (64, 5120),
        {"full": (2, 64, 5120, 4 * 192), "window": (5, 64, 128, 8 * 192)},
        {"full": (2, 64, 5120, 4 * 128), "window": (5, 64, 128, 8 * 128)})


def test_the_mixed_cells_decode_fold_walks_its_full_layers_and_copies_none(mimo_fold):
    """Under the kernel the token step holds two ``decode_attention`` custom
    calls, one a full layer (the five window layers keep the XLA read of
    their rings, sink and all), handed the stacked K and V whole: no
    instruction copies or transposes an array the size of a layer's K or V
    (503 / 336 MB), or of the stack of them, under either read."""
    compiled, B, S, took, read = mimo_fold
    m = compiled.memory_analysis()
    whole = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    cache = 2 * B * S * (768 + 512) * 2 + 5 * B * 128 * (1536 + 1024) * 2
    print(f"mixed cell's decode fold at {B} x {S}, {read} read: temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, "
          f"whole program {whole / GIB:.2f} GiB, built in {took:.0f} s")
    assert m.alias_size_in_bytes >= cache  # the caches are updated where they lie
    assert m.temp_size_in_bytes < 0.3 * GIB  # 0.054 / 0.044 read; one layer's V copied would be 0.31 more
    assert whole < 8.6 * GIB  # 8.20 / 8.19 read: 6.4 of weights, 1.76 of K, V and rings; the chip has 16
    assert took < 300, "the guard's own time limit: 30 s read"
    text = compiled.as_text().splitlines()
    mosaic = [ln for ln in text if 'custom_call_target="tpu_custom_call"' in ln]
    # the fold is a scan: its body, one token step, is in the program once, a call a full layer
    assert len(mosaic) == (2 if read == "kernel" else 0), mosaic
    assert all("decode_attention" in ln.split(" = ")[0] for ln in mosaic), mosaic
    size = re.compile(rf"= bf16\[(2,)?{B},{S},(768|512)\]\S* (copy|transpose)\(")
    hits = [ln.strip()[:160] for ln in text if size.search(ln)]
    assert not hits, hits
