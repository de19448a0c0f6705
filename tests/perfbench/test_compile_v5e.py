"""Compile-only checks of the benchmark's cells at their real widths, for
a described ``v5e:2x2`` (no chip attached; nothing runs). They guard the
sizes written in ``perfbench/traffic/*.json``: each program must fit a
16 GB chip, hold the Mosaic flash kernels, and — on four chips — the
collectives ZeRO-1 needs.

The topology is described inside a module-scoped fixture, never at
import, and everything built from it is built in a fixture or a test.
All of these live in this one file: one process may load the TPU's
library.
"""
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

HBM = 15.75 * 2**30  # what the compiler allows a v5e program
FOLD = 4


@pytest.fixture(autouse=True)
def kernels_as_on_the_chip(monkeypatch):
    """The program picks the compiled flash kernel where the default
    backend is a TPU and Pallas' interpreter elsewhere. These tests
    compile for a described chip from a CPU process, so they steer that
    one question here, in the test, not through an option of the program."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _spec():
    from pb.spec import Spec

    s = Spec(ROOT)
    return s, s.dims


#: The four-chip cell that waits under PERF.md's Open questions has no data file in the benchmark
#: yet; its sizes (huggingface.co/openai-community/gpt2-large config.json, and the mix ISSUE 23
#: names) are guarded here all the same, so that the PR which registers it starts from a step
#: that compiles.
GPT2_LARGE_4CHIP = (
    {"model_type": "gpt2", "n_embd": 1280, "n_head": 20, "n_layer": 36, "n_positions": 1024, "vocab_size": 50257,
     "layer_norm_epsilon": 1e-05,
     "program_config": {"vocab_size": 50257, "n_layer": 36, "n_head": 20, "d_model": 1280, "max_seq": 1024,
                        "compute_dtype": "bfloat16", "attn_impl": "flash", "loss_chunk": 128}},
    {"strategy": {"class": "RayShardedStrategy", "args": {"zero_stage": 1}}, "per_chip_batch": 2, "seq": 1024,
     "optimizer": {"lr": 0.0003, "warmup_steps": 2, "weight_decay": 0.01}},
    4,
)


def _cell_files(spec, cell_name):
    """(configuration, traffic mix, chips) of a cell of BENCHMARK.json."""
    if cell_name == "gpt2-large.train-4chip-zero1":
        return GPT2_LARGE_4CHIP
    cell = spec.cell(cell_name)
    return spec.config(cell["config"]), spec.traffic(cell["traffic"]), int(cell["chips"])


def _compile_train(topo, cell_name):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pb import weights
    from ray_lightning_tpu.models.gpt import GPTConfig, GPTLM
    from ray_lightning_tpu.strategies import RayShardedStrategy
    from ray_lightning_tpu.trainer.module import unpack_optimizers

    spec, model_dims = _spec()
    cfg, mix, chips = _cell_files(spec, cell_name)
    dims = model_dims(cfg)
    pc = GPTConfig(**cfg["program_config"])
    opt = mix["optimizer"]
    module = GPTLM(config=pc, batch_size=int(mix["per_chip_batch"]), lr=opt["lr"],
                   warmup_steps=opt["warmup_steps"], weight_decay=opt["weight_decay"])
    st = RayShardedStrategy(num_workers=chips, use_tpu=False, **mix["strategy"]["args"])
    st.mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    st.bind_module(module)
    tx, _ = unpack_optimizers(module.configure_optimizers())
    shapes = weights.param_shapes(dims, pc.max_seq)
    p_shape = {k: jax.ShapeDtypeStruct(v[0], jnp.float32) for k, v in shapes.items() if k != "blocks"}
    p_shape["blocks"] = {k: jax.ShapeDtypeStruct(v[0], jnp.float32) for k, v in shapes["blocks"].items()}
    o_shape = jax.eval_shape(tx.init, p_shape)

    def with_sh(tree, sh):
        if isinstance(sh, jax.sharding.Sharding):
            return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), tree)
        return jax.tree_util.tree_map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, sh)

    rep = NamedSharding(st.mesh, P())
    gb = int(mix["per_chip_batch"]) * chips
    step = st.compile_train_step(module, tx, fold_steps=FOLD, fold_stacked=True)
    compiled = step.lower(
        with_sh(p_shape, st.param_sharding(p_shape)),
        with_sh(o_shape, st.opt_sharding(o_shape, p_shape)),
        (jax.ShapeDtypeStruct((FOLD, gb, int(mix["seq"]) + 1), np.int32, sharding=st.stacked_batch_sharding()),),
        jax.ShapeDtypeStruct((2,), np.uint32, sharding=rep),
        0,
    ).compile()
    return compiled, dims, mix


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


def _mosaic_calls(text):
    return [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]


def test_gpt2_medium_step_fits_one_chip(topo):
    compiled, dims, mix = _compile_train(topo, "gpt2-medium.train-1chip")
    used = _device_bytes(compiled)
    print(f"gpt2-medium one-chip step: {used / 1e9:.2f} GB on the device")
    assert used < HBM
    assert used > 0.5 * HBM, "the cell should fill most of the chip"
    assert len(_mosaic_calls(compiled.as_text())) >= 3


def test_gpt2_large_zero1_step_fits_four_chips(topo):
    """The four-chip cell waits under PERF.md's Open questions; the sizes it
    would run at are guarded all the same."""
    compiled, dims, mix = _compile_train(topo, "gpt2-large.train-4chip-zero1")
    used = _device_bytes(compiled)
    text = compiled.as_text()
    print(f"gpt2-large ZeRO-1 step: {used / 1e9:.2f} GB on each device")
    assert used < HBM
    assert used > 0.25 * HBM
    calls = _mosaic_calls(text)
    assert len(calls) >= 3
    rows = int(mix["per_chip_batch"]) * dims["heads"]
    assert any(re.search(rf"bf16\[{rows},1024,64\]", ln) for ln in calls), "flash kernels carry the per-device batch"
    assert re.search(r"all-reduce|reduce-scatter", text), "gradient reduction across the chips"
    assert "all-gather" in text, "ZeRO-1 gathers the updated parameters"


def _mistral(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pb import weights
    from ray_lightning_tpu.models.gpt import GPTConfig

    spec, model_dims = _spec()
    cell = spec.cell("mistral-7b-v0.1-d8.serve-chat")
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    dims = model_dims(cfg)
    pc = GPTConfig(**cfg["program_config"])
    one = SingleDeviceSharding(topo.devices[0])
    dt = jnp.dtype(cfg["weights_dtype"])
    shapes = weights.param_shapes(dims, pc.max_seq)
    sds = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=one)  # noqa: E731
    params = {k: sds(v[0], dt) for k, v in shapes.items() if k != "blocks"}
    params["blocks"] = {k: sds(v[0], dt) for k, v in shapes["blocks"].items()}
    return pc, dims, mix, params, sds


def _mistral_fold_and_admission(topo, slots):
    """The chat cell's two largest programs on the cache the engine runs,
    rows ``(L, slots, S, Hkv * hd)`` (PR 29): the decode fold, and the
    admission of the largest prefill bucket (prefill, its rows written into
    one slot of the donated caches, the head over the last position), built
    as ``serve/engine.py`` builds ``step_impl`` and ``admit_impl``. They share
    weights and caches, so what the replica needs is the arguments once and
    the larger of the two programs' temporaries."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import cache_strip_put, gpt_decode_fold, gpt_prefill

    pc, dims, mix, params, sds = _mistral(topo)
    rep = mix["replica"]
    B, S = int(slots), int(rep["max_seq"])
    cache = sds((dims["layers"], B, S, dims["kv_heads"] * dims["head_dim"]), jnp.bfloat16)
    i32, f32 = (lambda: sds((B,), jnp.int32)), (lambda: sds((B,), jnp.float32))

    def step(params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps, keys, active, remaining, eos):
        return gpt_decode_fold(params, pc, cur, pos, keys, temps, top_ks, top_ps, active, remaining, eos,
                               k_cache, v_cache, fold=int(rep["decode_fold"]))

    def admit(params, k_cache, v_cache, prompt, last_idx, slot):
        h, pf_k, pf_v = gpt_prefill(params, pc, prompt)
        k_cache, v_cache = cache_strip_put(k_cache, pf_k, slot, 0), cache_strip_put(v_cache, pf_v, slot, 0)
        h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)[:, 0]
        return k_cache, v_cache, jnp.argmax(h_last @ params["lm_head"].T, axis=-1)

    fold = jax.jit(step, donate_argnums=(1, 2, 3, 4, 8, 9, 10)).lower(
        params, cache, cache, i32(), i32(), f32(), i32(), f32(), sds((B, 2), jnp.uint32),
        sds((B,), jnp.bool_), i32(), i32(),
    ).compile()
    bucket = max(rep["prefill_buckets"])
    scalar = sds((), jnp.int32)
    admission = jax.jit(admit, donate_argnums=(1, 2)).lower(
        params, cache, cache, sds((1, bucket), jnp.int32), scalar, scalar).compile()
    return fold, admission, B, S, bucket


def _program_gib(compiled):
    m = compiled.memory_analysis()
    return _device_bytes(compiled) / 2**30, m.temp_size_in_bytes / 2**30


def test_mistral_decode_fold_fits_one_chip(topo):
    """At the cell's own slot count, on the layout the engine runs, with the
    1024 admission beside the fold."""
    _, _, mix, _, _ = _mistral(topo)
    fold, admission, B, S, bucket = _mistral_fold_and_admission(topo, mix["replica"]["num_slots"])
    (f_all, f_tmp), (a_all, a_tmp) = _program_gib(fold), _program_gib(admission)
    print(f"mistral-d8 at {B} slots x {S} on rows: decode fold {f_all:.2f} GiB (temporaries {f_tmp:.3f}), "
          f"{bucket} admission {a_all:.2f} GiB (temporaries {a_tmp:.3f})")
    assert max(f_all, a_all) * 2**30 < HBM - 2**30, "the cell keeps 1 GiB free"
    assert f_all * 2**30 > 0.5 * HBM, "weights and cache should hold most of the chip"
    assert len(_mosaic_calls(admission.as_text())) >= 1, "the admission's prefill holds the flash kernel"


@pytest.mark.parametrize("slots", [96, 128])
def test_mistral_programs_at_more_slots_say_what_is_left(topo, slots):
    """Sizes, not times, for the PR that moves the cell's slot count (PERF.md
    section 4 has the readings): what the fold and the 1024 admission take at
    96 and 128 slots on the rows layout, and that both still compile."""
    fold, admission, B, S, bucket = _mistral_fold_and_admission(topo, slots)
    (f_all, f_tmp), (a_all, a_tmp) = _program_gib(fold), _program_gib(admission)
    free = HBM / 2**30 - max(f_all, a_all)
    print(f"mistral-d8 at {B} slots x {S} on rows: decode fold {f_all:.2f} GiB (temporaries {f_tmp:.3f}), "
          f"{bucket} admission {a_all:.2f} GiB (temporaries {a_tmp:.3f}); {free:.2f} GiB of {HBM / 2**30:.2f} free")
    assert max(f_all, a_all) * 2**30 < HBM


def test_mistral_prefill_bucket_holds_flash_kernel(topo):
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_prefill

    pc, dims, mix, params, sds = _mistral(topo)
    bucket = max(mix["replica"]["prefill_buckets"])
    compiled = jax.jit(lambda p, t: gpt_prefill(p, pc, t)).lower(params, sds((1, bucket), jnp.int32)).compile()
    assert _device_bytes(compiled) < HBM
    assert len(_mosaic_calls(compiled.as_text())) >= 1
