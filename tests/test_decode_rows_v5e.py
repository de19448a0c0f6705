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
10.12 GiB (PERF.md §4). This is the guard that the copy does not come back — under the XLA read and
under the decode kernel (``ops/decode_attention.py``), which takes the stacked
cache whole for the same reason. **And on the weights the engine holds**
(``models/gpt.py:engine_weights``, PR 43): multiplied as stored, ``wi``
(L, D, 2, F), ``wq`` and ``wkv`` were re-laid-out whole at the fold's entry,
2.13 GiB of the fold's 2.138 GiB of temporaries; re-formed once at engine build
the fold reads 0.012 / 7.75 GiB. **The cell's 1024-row admission** on the
same tree: its ``lax.scan`` over the stored leaves copied a layer's ``wi``,
``wq`` and ``wkv`` out of the stack, re-laid-out, at every step.

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


def _chat_cell(v5e, monkeypatch):
    """The chat cell from its own files, described for the chip:
    ``(program config, dims, replica group, weights, sds)``. The weights are
    the tree the engine holds — the stored tree (``pb/weights.py``) through
    ``engine_weights``, as ``DecodeEngine.__init__`` takes it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench"))
    from pb import weights
    from pb.spec import Spec

    from ray_lightning_tpu.models.gpt import GPTConfig, engine_weights

    spec = Spec(ROOT)
    cell = spec.cell("mistral-7b-v0.1-d8.serve-chat")
    cfg, rep = spec.config(cell["config"]), spec.traffic(cell["traffic"])["replica"]
    dims = spec.dims(cfg)
    pc = GPTConfig(**cfg["program_config"])
    one, dt = SingleDeviceSharding(v5e), jnp.dtype(cfg["weights_dtype"])

    def sds(shape, d):
        return jax.ShapeDtypeStruct(shape, d, sharding=one)

    shapes = weights.param_shapes(dims, pc.max_seq)
    stored = {k: sds(v[0], dt) for k, v in shapes.items() if k != "blocks"}
    stored["blocks"] = {k: sds(v[0], dt) for k, v in shapes["blocks"].items()}
    assert stored["blocks"]["wi"].shape == (dims["layers"], dims["d"], 2, dims["ff"]), "the stored layout"
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda p: engine_weights(p, pc), stored))
    assert (int(rep["num_slots"]), int(rep["max_seq"])) == (64, 2048), "the sizes below are this cell's"
    return pc, dims, rep, params, sds


def _compile_chat_fold(v5e, monkeypatch):
    """The chat cell's decode fold, compiled for the described chip:
    (compiled, slots, rows, layers, fold)."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_decode_fold

    pc, dims, rep, params, sds = _chat_cell(v5e, monkeypatch)
    B, S = int(rep["num_slots"]), int(rep["max_seq"])
    cache = sds((dims["layers"], B, S, dims["kv_heads"] * dims["head_dim"]), jnp.bfloat16)
    i32, f32 = (lambda: sds((B,), jnp.int32)), (lambda: sds((B,), jnp.float32))
    fold = int(rep["decode_fold"])

    def step(params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps, keys, active, remaining, eos):
        return gpt_decode_fold(params, pc, cur, pos, keys, temps, top_ks, top_ps, active, remaining, eos,
                               k_cache, v_cache, fold=fold)

    # donated as serve/engine.py donates them: caches and the state the fold moves
    compiled = jax.jit(step, donate_argnums=(1, 2, 3, 4, 8, 9, 10)).lower(
        params, cache, cache, i32(), i32(), f32(), i32(), f32(), sds((B, 2), jnp.uint32),
        sds((B,), jnp.bool_), i32(), i32(),
    ).compile()
    return compiled, B, S, dims["layers"], fold


@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_mistral_decode_fold_on_a_cache_of_rows_copies_no_layer(v5e, monkeypatch, read):
    """``xla``: the read the engine takes off the TPU (``jax.default_backend()``
    is the CPU here). ``kernel``: the read it takes on the chip — the test says
    "tpu" where the program asks, so the decode kernel is in and Mosaic compiles
    it: one custom call a layer of the fold's one traced token step, the stacked
    cache handed to it whole (a copy of a layer, or of the cache, would show in
    the temporaries)."""
    import jax

    if read == "kernel":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, B, S, layers, fold = _compile_chat_fold(v5e, monkeypatch)
    m = compiled.memory_analysis()
    whole = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    print(f"decode fold on rows at {B} x {S}, {read} read: temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, "
          f"whole program {whole / GIB:.2f} GiB")
    # 2.138 / 9.88 read while the fold multiplied the stored tree: wi, wq and wkv re-laid-out at its entry
    assert m.temp_size_in_bytes < 0.3 * GIB
    assert whole < 8.2 * GIB
    mosaic = [ln for ln in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    # the fold is a scan: its body, one token step, is in the program once
    assert len(mosaic) == (layers if read == "kernel" else 0), mosaic
    assert all("decode_attention" in ln.split(" = ")[0] for ln in mosaic), mosaic


def test_mistral_admission_copies_no_layer_of_weights_out_of_the_stack(v5e, monkeypatch):
    """The cell's largest admission (the 1024 bucket: prefill, its rows
    into one slot of the donated caches, the head over the last position),
    as ``serve/engine.py`` builds ``admit_impl``. ``gpt_prefill`` scans the
    stacked leaves, and on the STORED tree every step of the scan
    materialised a re-laid-out copy of its layer's ``wi``, ``wq`` and
    ``wkv`` (three ``constant_dynamic-slice_fusion``s whose results are
    those leaves; temporaries 0.305 GiB, a layer's ``wi`` 0.219 of them). On
    the tree the engine holds no such fusion yields a weight, and all the
    temporaries together (0.059 GiB read: activations) are under ONE of a
    layer's gate and up matrices."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import cache_strip_put, gpt_prefill

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash kernel, as on the chip
    pc, dims, rep, params, sds = _chat_cell(v5e, monkeypatch)
    B, S, bucket = int(rep["num_slots"]), int(rep["max_seq"]), max(rep["prefill_buckets"])
    cache = sds((dims["layers"], B, S, dims["kv_heads"] * dims["head_dim"]), jnp.bfloat16)

    def admit(params, k_cache, v_cache, prompt, last_idx, slot):
        h, pf_k, pf_v = gpt_prefill(params, pc, prompt)
        k_cache, v_cache = cache_strip_put(k_cache, pf_k, slot, 0), cache_strip_put(v_cache, pf_v, slot, 0)
        h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)[:, 0]
        return k_cache, v_cache, jnp.argmax(h_last @ params["lm_head"].T, axis=-1)

    scalar = sds((), jnp.int32)
    compiled = jax.jit(admit, donate_argnums=(1, 2)).lower(
        params, cache, cache, sds((1, bucket), jnp.int32), scalar, scalar).compile()
    m = compiled.memory_analysis()
    print(f"{bucket} admission at {B} x {S}: temporaries {m.temp_size_in_bytes / GIB:.3f} GiB")
    assert m.temp_size_in_bytes < dims["d"] * dims["ff"] * 2  # 0.109 GiB
    assert _weights_sliced_out(compiled.as_text(), dims) == []


def _weights_sliced_out(text, dims):
    """The instructions of a compiled program that MAKE a copy of one layer's
    weight leaf out of the stack: a ``dynamic-slice``, or a fusion named after
    one, whose result has a leaf's shape (stored, flat or split, with or
    without the leading 1 of the layer axis) and is materialised — it stands
    in the entry computation or a loop's body, not inside a fusion, where a
    slice is a view that the consuming matmul reads through."""
    import re

    D, F, H, Hkv, hd = (dims[k] for k in ("d", "ff", "heads", "kv_heads", "head_dim"))
    leaves = {(D, F), (F, D), (D, 2, F), (D, H, hd), (D, H * hd), (D, 2, Hkv, hd), (D, 2 * Hkv * hd),
              (H, hd, D), (H * hd, D)}
    fused = set(re.findall(r" fusion\(.*calls=%([\w.\-]+)", text))
    found, inside = [], None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            inside = head.group(1)
            continue
        name, _, rest = ln.strip().partition(" = ")
        name = name.replace("ROOT ", "").replace("_", "-")
        sliced = " dynamic-slice(" in rest or ("dynamic-slice" in name and " fusion(" in rest)
        if inside in fused or not sliced or "update" in name:
            continue
        got = re.match(r"\w+\[([\d,]*)\]", rest)
        shape = tuple(int(x) for x in got.group(1).split(",") if x) if got else ()
        if shape in leaves or (shape[:1] == (1,) and shape[1:] in leaves):
            found.append(ln.strip()[:160])
    return found


@pytest.mark.parametrize(
    "shape",
    [(8, 64, 2048, 32, 8, 128), (24, 16, 1024, 16, 16, 64)],
    ids=["chat_8x64x2048_32q8kvx128", "gpt2_medium_24x16x1024_16x64"],
)
def test_decode_kernel_lowers_at_the_rows_of_both_dense_families(v5e, shape):
    """The kernel alone, with the block its shapes choose: Llama's grouped
    rows (lane-aligned heads of 128) and GPT-2's (heads of 64, two to a lane
    tile: the query layout is concatenated and the output's own blocks are
    sliced across the tile)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_lightning_tpu.ops.decode_attention import decode_attention

    L, B, S, H, Hkv, hd = shape
    one = SingleDeviceSharding(v5e)
    cache = jax.ShapeDtypeStruct((L, B, S, Hkv * hd), jnp.bfloat16, sharding=one)
    text = jax.jit(
        lambda q, k, v, pos, live: decode_attention(q, k, v, L - 1, pos, live, interpret=False)
    ).lower(
        jax.ShapeDtypeStruct((B, H, hd), jnp.bfloat16, sharding=one), cache, cache,
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one), jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one),
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


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


@pytest.mark.parametrize(
    "shape",
    [(6144, 32, 32, 192, 128), (4096, 64, 4, 192, 128), (1024, 20, 4, 128, 128)],
    ids=["docqa_latent_6144x32x192v128", "mixedlen_full_4096x64on4x192v128", "burstchat_full_1024x20on4x128"],
)
def test_the_forward_flash_kernel_lowers_at_the_mixed_layers_prefill_shapes(v5e, shape):
    """The forward-only kernel (PR 49) with a v of its own width, grouped KV
    heads and the prefetched count of real rows, at the largest bucket of
    each cell whose prefill may take it: one Mosaic call, K and V whole in
    VMEM a head beside the 512 x 512 score tile."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_lightning_tpu.ops.flash_attention import flash_attention

    S, H, Hkv, dqk, dv = shape
    one = SingleDeviceSharding(v5e)

    def sds(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    text = jax.jit(
        lambda q, k, v, n: flash_attention(q, k, v, true_len=n, interpret=False)
    ).lower(sds(1, S, H, dqk), sds(1, S, Hkv, dqk), sds(1, S, Hkv, dv), sds(dtype=jnp.int32)).compile().as_text()
    mosaic = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(mosaic) == 1 and "flash_fwd" in mosaic[0].split(" = ")[0], mosaic
