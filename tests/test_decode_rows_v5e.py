"""Compile-only guards for a described ``v5e:2x2`` (no chip attached;
nothing runs), in one file so that one worker describes the chip once.

**The flash kernels** (``ops/flash_attention.py``) with bf16 operands, forward
and backward, at the train cell's shape and the chat prefill's: Mosaic has to
take the packed bf16 tiles as they are, the transposed left sides of
``flash_dkv`` (pT.do, dsT.q) among them — interpret mode on the CPU proves
nothing about that.

**The chat cell's decode fold** on the cache the single-device engine keeps
— ``(L, slots, max_seq, Hkv * hd)``, a position's KV heads side by side in
one row. With the KV heads on an axis of their own the chip's compiler copied a layer of the cache (268 MB) out of the stacked
array before every attention read: temporaries of 2.38 GiB in a program of
10.12 GiB (PERF.md §4). This is the guard that the copy does not come back.

Built as ``tests/perfbench/test_compile_v5e.py`` builds its programs, from
the cell's own files; the topology is described in a fixture, never at
import.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2**30


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # tests/perfbench/test_compile_v5e.py describes the same chip from another
    # worker process, and the TPU's library lets one process at a time take its
    # lock: this file asks for none, so it neither waits for that one nor is in
    # its way.
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


def test_mistral_decode_fold_on_a_cache_of_rows_copies_no_layer(v5e, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench"))
    from pb import weights
    from pb.spec import Spec

    from ray_lightning_tpu.models.gpt import GPTConfig, gpt_decode_fold

    spec = Spec(ROOT)
    cell = spec.cell("mistral-7b-v0.1-d8.serve-chat")
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
    assert (B, S) == (64, 2048), "the sizes below are this cell's"
    cache = sds((dims["layers"], B, S, dims["kv_heads"] * dims["head_dim"]), jnp.bfloat16)
    i32, f32 = (lambda: sds((B,), jnp.int32)), (lambda: sds((B,), jnp.float32))

    def step(params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps, keys, active, remaining, eos):
        return gpt_decode_fold(params, pc, cur, pos, keys, temps, top_ks, top_ps, active, remaining, eos,
                               k_cache, v_cache, fold=int(rep["decode_fold"]))

    # donated as serve/engine.py donates them: caches and the state the fold moves
    m = jax.jit(step, donate_argnums=(1, 2, 3, 4, 8, 9, 10)).lower(
        params, cache, cache, i32(), i32(), f32(), i32(), f32(), sds((B, 2), jnp.uint32),
        sds((B,), jnp.bool_), i32(), i32(),
    ).compile().memory_analysis()
    whole = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    print(f"decode fold on rows at {B} x {S}: temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, "
          f"whole program {whole / GIB:.2f} GiB")
    assert m.temp_size_in_bytes < 2.3 * GIB  # 2.138 read; 2.383 with the KV heads on an axis of their own
    assert whole < 10.0 * GIB  # 9.88 read; 10.12


@pytest.mark.parametrize(
    "shape,kw",
    [((4, 1024, 16, 64), {}), ((1, 1024, 32, 128), {"window": 4096})],
    ids=["train_4x1024x16x64", "chat_prefill_1x1024x32x128"],
)
def test_flash_kernels_lower_with_bf16_operands(v5e, shape, kw):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_lightning_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=SingleDeviceSharding(v5e))

    def fwd_bwd(q, k, v, do):
        # interpret=False: jax.default_backend() is the CPU here
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False, **kw), q, k, v
        )
        return (out,) + vjp(do)

    text = jax.jit(fwd_bwd).lower(x, x, x, x).compile().as_text()
    mosaic = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(mosaic) == 3, mosaic
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert sum(kernel in ln.split(" = ")[0] for ln in mosaic) == 1, kernel
