"""Compile-only guard for a described ``v5e:2x2`` (no chip attached; nothing
runs), beside ``tests/test_state_step_v5e.py`` and built as it builds its
program, from the cell's own files.

**The parallel cell's decode fold and its largest admission**
(``falcon-h1-34b-d6.serve-burstchat``: 64 slots x 2048, six layers, each a
state mixer AND a full attention on one normed input). Every layer keeps a
recurrent state of 268 MB (64 x 32 x 128 x 256 float32: a leaf of its own,
which the step replaces whole) and K and V rows of 134 MB each in the two
stacked arrays; the fold donates both halves, so the compiler updates them
where they lie. Beside 10.5 GB of weights and 3.2 GB of state and rows
there is no room for a copy: one layer's state copied would be 0.25 GiB of
temporaries, one layer's K or V 0.125. The configuration's depth was set
from this build (six layers if it leaves 1 GiB of the 15.75 free, else
five: ``perfbench/configs/falcon-h1-34b-d6.json``); this is the guard
that it still does, and that no such copy comes in — under the two
kernels the chip takes, the decode attention's read and the state's update
over the live slots (``jax.default_backend()`` said "tpu" where the program
asks, as ``tests/test_latent_step_v5e.py`` does; under XLA's read and
update the fold read the same sizes: 0.224 GiB / 13.02 GiB). The state's
update is the repo's first custom call whose output IS an argument
(``ops/ssm_step.py``: ``input_output_aliases``): were the alias lost, or
the state handed over as anything but the leaf the fold was given, each
layer's call would bring a copy of 0.25 GiB.
"""
import os
import re
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2**30
CELL = "falcon-h1-34b-d6.serve-burstchat"
LAYERS, SLOTS, ROWS = 6, 64, 2048


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


@pytest.fixture(scope="module")
def cell(v5e):
    """The cell's sizes and its arguments as shapes on the described chip:
    ``(program config, replica group, params, k_cache, v_cache, sds)``."""
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

    from ray_lightning_tpu.models.gpt import GPTConfig
    from ray_lightning_tpu.models.mixed import empty_caches

    spec = Spec(ROOT)
    c = spec.cell(CELL)
    cfg, rep = spec.config(c["config"]), spec.traffic(c["traffic"])["replica"]
    pc = GPTConfig(**cfg["program_config"])
    one, dt = SingleDeviceSharding(v5e), jnp.dtype(cfg["weights_dtype"])

    def sds(shape, d):
        return jax.ShapeDtypeStruct(shape, d, sharding=one)

    shapes = weights.param_shapes(spec.dims(cfg), pc.max_seq)
    params = {k: sds(v[0], dt) for k, v in shapes.items() if k != "blocks"}
    params["blocks"] = {k: sds(v[0], dt) for k, v in shapes["blocks"].items()}
    B, S = int(rep["num_slots"]), int(rep["max_seq"])
    assert (pc.n_layer, B, S) == (LAYERS, SLOTS, ROWS), "the sizes below are this cell's"
    k_cache, v_cache = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda: empty_caches(pc, B, S, dt)))
    assert k_cache["full"].shape == v_cache["full"].shape == (LAYERS, B, S, 4 * 128)
    assert len(k_cache["ssm"]) == LAYERS and k_cache["ssm"][0].shape == (B, 32, 128, 256)
    assert k_cache["ssm"][0].dtype == jnp.float32 and v_cache["ssm"][0].shape == (3, B, 4096 + 2 * 2 * 256)
    return pc, rep, params, k_cache, v_cache, sds


def _sizes(compiled):
    m = compiled.memory_analysis()
    return m, m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


#: an instruction that copies or transposes one layer's state, or one layer's (or the stack's) K or V rows
_COPIES = re.compile(
    rf"= (f32\[{SLOTS},32,128,256\]|bf16\[({LAYERS},)?{SLOTS},{ROWS},512\])\S* (copy|transpose)\(")


def test_the_parallel_cells_decode_fold_copies_no_state_and_no_rows(cell):
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_decode_fold

    pc, rep, params, k_cache, v_cache, sds = cell
    B = SLOTS
    i32, f32 = (lambda: sds((B,), jnp.int32)), (lambda: sds((B,), jnp.float32))

    def step(params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps, keys, active, remaining, eos):
        return gpt_decode_fold(params, pc, cur, pos, keys, temps, top_ks, top_ps, active, remaining, eos,
                               k_cache, v_cache, fold=int(rep["decode_fold"]))

    t0 = time.monotonic()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")  # what the chip takes: the decode kernel, the state's walk
        # donated as serve/engine.py donates them: caches and the state the fold moves
        compiled = jax.jit(step, donate_argnums=(1, 2, 3, 4, 8, 9, 10)).lower(
            params, k_cache, v_cache, i32(), i32(), f32(), i32(), f32(), sds((B, 2), jnp.uint32),
            sds((B,), jnp.bool_), i32(), i32(),
        ).compile()
    took = time.monotonic() - t0
    m, whole = _sizes(compiled)
    print(f"parallel cell's decode fold at {B} x {ROWS}, kernel read: temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, "
          f"whole program {whole / GIB:.2f} GiB of 15.75, built in {took:.0f} s")
    state_and_rows = LAYERS * B * (32 * 128 * 256 * 4 + 3 * 5120 * 2 + 2 * ROWS * 512 * 2)
    assert m.alias_size_in_bytes >= state_and_rows  # both halves are updated where they lie
    assert m.temp_size_in_bytes < 0.4 * GIB  # 0.223 read
    assert whole < 14.75 * GIB  # 13.02 read: 1 GiB of the chip's 15.75 stays free, and 1.7 more
    text = compiled.as_text().splitlines()
    mosaic = [ln for ln in text if 'custom_call_target="tpu_custom_call"' in ln]
    # the fold is a scan: its body, one token step, is in the program once, two calls a layer — Mosaic took the
    # state's update at 64 x 32 x 128 x 256 in blocks of 8 heads
    names = sorted(ln.split(" = ")[0].strip().lstrip("%").split(".")[0] for ln in mosaic)
    assert names == ["decode_attention"] * LAYERS + ["ssm_step"] * LAYERS, mosaic
    hits = [ln.strip()[:160] for ln in text if _COPIES.search(ln)]
    assert not hits, hits
    assert took < 300, "the guard's own time limit"


def test_the_largest_admission_fits_beside_the_caches(cell):
    """The 1024-row admission as ``serve/engine.py:admit_impl`` makes it:
    the rows' pass, both halves written into the slot, the head on the
    last real row."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.layers import _rmsnorm
    from ray_lightning_tpu.models.mixed import mixed_logits, mixed_rows, write_prefill_rows

    pc, rep, params, k_cache, v_cache, sds = cell
    Pb = max(rep["prefill_buckets"])
    assert Pb == 1024

    def admit(params, k_cache, v_cache, prompt, last_idx, slot):
        h, pf_k, pf_v, counts = mixed_rows(params, pc, prompt, true_len=last_idx + 1)
        k_cache, v_cache = write_prefill_rows(k_cache, v_cache, pf_k, pf_v, slot, last_idx + 1)
        h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)
        logits = mixed_logits(_rmsnorm(h_last, params["lnf_g"], pc.norm_eps)[:, 0], params, pc)
        return k_cache, v_cache, jnp.argmax(logits, -1), counts

    t0 = time.monotonic()
    scalar = sds((), jnp.int32)
    compiled = jax.jit(admit, donate_argnums=(1, 2)).lower(
        params, k_cache, v_cache, sds((1, Pb), jnp.int32), scalar, scalar).compile()
    took = time.monotonic() - t0
    m, whole = _sizes(compiled)
    print(f"parallel cell's {Pb}-row admission at {SLOTS} x {ROWS}: temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, "
          f"whole program {whole / GIB:.2f} GiB of 15.75, built in {took:.0f} s")
    assert whole < 14.75 * GIB  # 12.99 read (temporaries 0.187)
    hits = [ln.strip()[:160] for ln in compiled.as_text().splitlines() if _COPIES.search(ln)]
    assert not hits, hits
    assert took < 300, "the guard's own time limit"
