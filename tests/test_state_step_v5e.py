"""Compile-only guard for a described ``v5e:2x2`` (no chip attached; nothing
runs), beside ``tests/test_decode_rows_v5e.py`` and built as it builds its
program, from the cell's own files.

**The state cell's decode fold** (``nemotron-3-super-d11-ep4.serve-shortchat``:
128 slots, five state layers): each layer's running state is one array of
537 MB that a token step reads once and writes once. The fold donates the
caches and every layer's state is a leaf of its own that the step replaces
whole, so the compiler updates it where it lies: temporaries of 0.08 GiB
in a program of 11.5 GiB (PERF.md §4). One copy of one layer's state would
be 0.5 GiB of temporaries, a copy of all five 2.5 GiB, and the fold would
no longer fit beside a 1024-row admission: this is the guard that no such
copy comes in — under the kernels the chip takes (``jax.default_backend()``
said "tpu" where the program asks, as ``tests/test_parallel_step_v5e.py``
does): the one full layer's decode read and, a state layer, the update that
walks the live slots (``ops/ssm_step.py``), whose output IS its argument
(``input_output_aliases``). Were the alias lost, each layer's call would
bring the copy this guard is for.
"""
import os
import re
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2**30


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


def test_the_state_cells_decode_fold_copies_no_state(v5e, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench"))
    from pb import weights
    from pb.spec import Spec

    from ray_lightning_tpu.models.gpt import GPTConfig, gpt_decode_fold
    from ray_lightning_tpu.models.mixed import empty_caches

    t0 = time.monotonic()
    spec = Spec(ROOT)
    cell = spec.cell("nemotron-3-super-d11-ep4.serve-shortchat")
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
    assert (B, S) == (128, 2048), "the sizes below are this cell's"
    k_cache, v_cache = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda: empty_caches(pc, B, S, dt)))
    state = sum(a.size * a.dtype.itemsize for a in k_cache["ssm"])
    assert len(k_cache["ssm"]) == 5 and k_cache["ssm"][0].dtype == jnp.float32 and 2.6e9 < state < 2.7e9
    i32, f32 = (lambda: sds((B,), jnp.int32)), (lambda: sds((B,), jnp.float32))

    def step(params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps, keys, active, remaining, eos):
        return gpt_decode_fold(params, pc, cur, pos, keys, temps, top_ks, top_ps, active, remaining, eos,
                               k_cache, v_cache, fold=int(rep["decode_fold"]))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")  # what the chip takes: the decode kernel, the state's walk
        # donated as serve/engine.py donates them: caches and the state the fold moves
        compiled = jax.jit(step, donate_argnums=(1, 2, 3, 4, 8, 9, 10)).lower(
            params, k_cache, v_cache, i32(), i32(), f32(), i32(), f32(), sds((B, 2), jnp.uint32),
            sds((B,), jnp.bool_), i32(), i32(),
        ).compile()
    m = compiled.memory_analysis()
    whole = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    took = time.monotonic() - t0
    print(f"state cell's decode fold at {B} x {S}: temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, "
          f"whole program {whole / GIB:.2f} GiB, built in {took:.0f} s")
    assert m.temp_size_in_bytes < 0.4 * GIB  # 0.081 read; one layer's state copied would be 0.5 more
    assert m.alias_size_in_bytes >= state  # every layer's state is updated where it lies
    text = compiled.as_text().splitlines()
    mosaic = [ln for ln in text if 'custom_call_target="tpu_custom_call"' in ln]
    # the fold is a scan: its body, one token step, is in the program once — Mosaic took the state's update at
    # 128 x 128 x 64 x 128 in blocks of 16 heads, a call a state layer, beside the one full layer's read
    names = sorted(ln.split(" = ")[0].strip().lstrip("%").split(".")[0] for ln in mosaic)
    assert names == ["decode_attention"] + ["ssm_step"] * 5, mosaic
    copies = [ln.strip()[:160] for ln in text if re.search(r"= f32\[128,128,64,128\]\S* (copy|transpose)\(", ln)]
    assert not copies, copies
    assert whole < 12.0 * GIB  # 11.53 read
    assert took < 240, "the guard's own time limit: 15 s read"
