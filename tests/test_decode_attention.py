"""The decode kernel (``ops/decode_attention.py``) in interpret mode against
the XLA rows read of ``models/gpt.py:_attend_layer_cache``: the same result
to rounding over the dense families' head layouts, at the positions where a
block count can be off by one, and — blocks past a slot's position filled
with NaN — the proof that the kernel reads no row that holds no live
position. Mosaic's own view of the kernel is ``tests/test_decode_rows_v5e.py``.
The second half does the same for the latent kind's call
(``latent_decode_attention`` against ``models/mixed.py:_attend_latent_cache``'s
XLA read), whose view by Mosaic is ``tests/test_latent_step_v5e.py``. The
third for a mixed configuration's full K/V kind, whose K and V rows differ
in width (heads of 192 against 128), against ``models/mixed.py:_attend_cache``;
Mosaic's view of it is ``tests/test_latent_step_v5e.py`` too.
"""
import numpy as np
import pytest

from ray_lightning_tpu.models import layers
from ray_lightning_tpu.models.gpt import GPTConfig

BLOCK, S, L = 128, 384, 2  # three blocks a slot: 512 and 256 do not divide 384

#: name -> config (only heads, widths, window and sinks reach the read)
VARIANTS = {
    "gpt2_mha_hd64": GPTConfig(vocab_size=97, n_layer=L, n_head=4, d_model=256, max_seq=S),
    "llama_gqa_rope_hd128_8of32": GPTConfig.llama(
        vocab_size=97, n_layer=L, n_head=32, n_kv_head=8, d_model=4096, max_seq=S),
    "gqa_window_sinks": GPTConfig.llama(
        vocab_size=97, n_layer=L, n_head=8, n_kv_head=2, d_model=1024, max_seq=S,
        attn_window=150, attn_sinks=4),
}

#: where a block count is off by one first: row 0, a block's last row, the
#: next block's first row, the cache's last row; and somewhere inside
POSITIONS = {
    "row0": 0, "block_last_row": BLOCK - 1, "block_first_row": BLOCK,
    "cache_last_row": S - 1, "inside": 2 * BLOCK + 37,
}


def _inputs(cfg, pos, seed=0):
    import jax
    import jax.numpy as jnp

    B = len(pos)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    width = cfg.kv_head * cfg.head_dim
    q = jax.random.normal(ks[0], (B, 1, cfg.n_head, cfg.head_dim), jnp.float32)
    kc = jax.random.normal(ks[1], (L, B, S, width), jnp.float32)
    vc = jax.random.normal(ks[2], (L, B, S, width), jnp.float32)
    return q, kc, vc, jnp.asarray(pos, jnp.int32)[:, None]


def _read(monkeypatch, kernel: bool):
    """``_attend_layer_cache`` with the decode kernel in (its selection told
    "tpu"; the kernel then interprets, as it does off the chip) or out."""
    from ray_lightning_tpu.models import gpt as G
    from tests.utils import force_decode_kernel

    if kernel:
        force_decode_kernel(monkeypatch)
    return G._attend_layer_cache


@pytest.mark.parametrize("where", sorted(POSITIONS))
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_kernel_equals_the_xla_rows_read(variant, where, monkeypatch):
    """Slot 0 at the named position, slot 1 elsewhere, slot 2 NOT live
    (zeros from the kernel, whatever from the XLA read: nobody reads it),
    slot 3 behind an idle one."""
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    cfg = VARIANTS[variant]
    q, kc, vc, positions = _inputs(cfg, [POSITIONS[where], 200, 300, 5])
    live = jnp.asarray([True, True, False, True])
    want = G._attend_layer_cache(cfg, q, kc, vc, 1, positions)
    assert layers.decode_rows_block(cfg, 1, kc, vc) == 0, "off the TPU the engine keeps the XLA read"
    got = _read(monkeypatch, kernel=True)(cfg, q, kc, vc, 1, positions, live)
    assert layers.decode_rows_block(cfg, 1, kc, vc) == BLOCK
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got)[[0, 1, 3]], np.asarray(want)[[0, 1, 3]], atol=1e-5, rtol=0)
    assert not np.asarray(got)[2].any()


def _poisoned(kc, vc, positions, live):
    """Every block that lies wholly past a slot's position, and every row of
    a slot that is not live, set to NaN."""
    import jax.numpy as jnp

    rows = jnp.arange(S)[None, :]
    first_dead = jnp.where(live[:, None], (positions // BLOCK + 1) * BLOCK, 0)
    dead = (rows >= first_dead)[None, :, :, None]
    return jnp.where(dead, jnp.nan, kc), jnp.where(dead, jnp.nan, vc)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_blocks_past_a_slots_position_are_not_read(variant, monkeypatch):
    """p·V over a masked row is 0 x NaN = NaN: the XLA read, which multiplies
    every allocated row, returns NaN here; the kernel returns the clean
    cache's result."""
    import jax.numpy as jnp

    cfg = VARIANTS[variant]
    q, kc, vc, positions = _inputs(cfg, [0, BLOCK - 1, 300, BLOCK, 2 * BLOCK - 1])
    live = jnp.asarray([True, True, False, True, True])
    pk, pv = _poisoned(kc, vc, positions, live)
    assert bool(jnp.isnan(pk[:, 0, BLOCK:]).all()) and not bool(jnp.isnan(pk[:, 0, :BLOCK]).any())
    xla = _read(monkeypatch, kernel=False)
    assert np.isnan(np.asarray(xla(cfg, q, pk, pv, 0, positions))).any()
    want = xla(cfg, q, kc, vc, 0, positions)
    got = _read(monkeypatch, kernel=True)(cfg, q, pk, pv, 0, positions, live)
    assert np.isfinite(np.asarray(got)).all()
    keep = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep], atol=1e-5, rtol=0)


@pytest.mark.parametrize("off", [-1, 1], ids=["one_block_short", "one_block_far"])
def test_a_block_count_off_by_one_fails(off, monkeypatch):
    """The planted fault. One block short, the slot's newest rows go unread
    and the result misses; one block far, a dead block is fetched and its
    NaN reaches the output."""
    import jax.numpy as jnp

    from ray_lightning_tpu.ops import decode_attention as D

    real = D._last_block
    monkeypatch.setattr(
        D, "_last_block", lambda pos, block, seq: jnp.clip(real(pos, block, seq) + off, 0, seq // block - 1))
    cfg = VARIANTS["llama_gqa_rope_hd128_8of32"]
    q, kc, vc, positions = _inputs(cfg, [BLOCK + 3, BLOCK + 90])
    live = jnp.asarray([True, True])
    pk, pv = _poisoned(kc, vc, positions, live)
    want = _read(monkeypatch, kernel=False)(cfg, q, kc, vc, 0, positions)
    got = np.asarray(_read(monkeypatch, kernel=True)(cfg, q, pk, pv, 0, positions, live))
    if off > 0:
        assert np.isnan(got).any()
    else:
        assert np.isfinite(got).all() and np.abs(got - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("case,kw", [
    ("head_axis_cache", {}), ("verify_q3", {}), ("attn_impl_reference", {"attn_impl": "reference"}),
    ("row_width_96", {"n_head": 4, "n_kv_head": 2, "d_model": 192}), ("rows_100", {}),
])
def test_everything_else_keeps_the_xla_read(case, kw):
    """What the selection observes, one condition a case, each told "tpu"."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    cfg = GPTConfig.llama(**{**dict(vocab_size=97, n_layer=L, n_head=8, n_kv_head=2, d_model=1024, max_seq=S), **kw})
    rows = 100 if case == "rows_100" else S
    shape = (L, 2, rows, cfg.kv_head * cfg.head_dim)
    if case == "head_axis_cache":
        shape = (L, 2, rows, cfg.kv_head, cfg.head_dim)
    cache = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    q_len = 3 if case == "verify_q3" else 1
    assert layers.decode_rows_block(cfg, q_len, cache, cache, backend="tpu") == 0
    assert layers.decode_rows_block(cfg, q_len, cache, cache) == 0


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_decode_step_with_the_kernel_gives_the_xla_steps_logits(variant, monkeypatch):
    """The whole token step (projections, rotary, the cache write, the read,
    the MLP) on a cache of rows: logits of the live slots and both caches."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G
    from tests.utils import force_decode_kernel

    # the variant's kind of heads at a width the CPU builds in a second
    cfg = VARIANTS[variant]
    heads = dict(n_head=4, d_model=256) if cfg.kv_head == cfg.n_head else dict(
        n_head=8, n_kv_head=2, d_model=512)
    cfg = GPTConfig(**{**cfg.__dict__, **heads, "compute_dtype": "float32"})
    assert cfg.kv_head * cfg.head_dim % 128 == 0
    params = G.init_gpt_params(jax.random.PRNGKey(1), cfg)
    B, width = 3, cfg.kv_head * cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    kc = 0.3 * jax.random.normal(ks[0], (L, B, S, width), jnp.float32)
    vc = 0.3 * jax.random.normal(ks[1], (L, B, S, width), jnp.float32)
    cur = jnp.asarray([5, 17, 44], jnp.int32)
    pos = jnp.asarray([BLOCK, 2 * BLOCK - 1, 9], jnp.int32)
    active = jnp.asarray([True, True, False])
    want = G.gpt_decode_step(params, cfg, cur, pos, kc, vc, active)
    force_decode_kernel(monkeypatch)
    got = G.gpt_decode_step(params, cfg, cur, pos, kc, vc, active)
    np.testing.assert_allclose(np.asarray(got[0])[:2], np.asarray(want[0])[:2], atol=2e-4, rtol=0)
    for a, b in zip(got[1:], want[1:]):
        # layer 0's write is the same; layer 1's differs by the read's rounding, and only in the live slots' rows
        np.testing.assert_allclose(np.asarray(a)[:, :2], np.asarray(b)[:, :2], atol=2e-4, rtol=0)


# -- the latent kind: one row a position is keys and values of every head --------------------
#: name -> (layers, rows, latent width, rotary width, block): a cut shape, and the docqa cell's rows and widths
LATENT_SHAPES = {"cut_384x128+64": (2, 384, 128, 64, 128), "cell_6656x512+64": (1, 6656, 512, 64, 512)}


def _latent_cfg(attn_impl="flash"):
    from ray_lightning_tpu.models.gpt import GPTConfig

    # only the scale (1 / sqrt(192)) and attn_impl reach the read
    return GPTConfig(qk_head_dim=192, attn_impl=attn_impl)


def _latent_positions(S, block):
    return {"row0": 0, "block_last_row": block - 1, "block_first_row": block, "cache_last_row": S - 1,
            "inside": 2 * block + 37}


def _latent_inputs(shape, pos, seed=0, heads=4):
    import jax
    import jax.numpy as jnp

    L, S, rank, rope, _ = LATENT_SHAPES[shape]
    B = len(pos)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_lat = jax.random.normal(ks[0], (B, heads, rank), jnp.float32) / np.sqrt(rank)
    q_rope = jax.random.normal(ks[1], (B, heads, rope), jnp.float32)
    cc = {"latent": jax.random.normal(ks[2], (L, B, S, rank), jnp.float32)}
    rc = {"latent": jax.random.normal(ks[3], (L, B, S, rope), jnp.float32)}
    return q_lat, q_rope, cc, rc, jnp.asarray(pos, jnp.int32)


def _latent_read(monkeypatch, kernel: bool):
    from ray_lightning_tpu.models import mixed as M
    from tests.utils import force_decode_kernel

    if kernel:
        force_decode_kernel(monkeypatch)
    return M._attend_latent_cache


@pytest.mark.parametrize("where", ["row0", "block_last_row", "block_first_row", "cache_last_row", "inside"])
@pytest.mark.parametrize("shape", sorted(LATENT_SHAPES))
def test_latent_kernel_equals_the_xla_read_of_latents(shape, where, monkeypatch):
    """``models/mixed.py:_attend_latent_cache``: slot 0 at the named position,
    slot 1 elsewhere, slot 2 NOT live (zeros from the kernel), slot 3 behind
    an idle one."""
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    L, S, rank, _, block = LATENT_SHAPES[shape]
    cfg = _latent_cfg()
    q_lat, q_rope, cc, rc, pos = _latent_inputs(shape, [_latent_positions(S, block)[where], 200, 300, 5])
    live = jnp.asarray([True, True, False, True])
    want = _latent_read(monkeypatch, kernel=False)(cfg, q_lat, q_rope, cc, rc, L - 1, pos)
    assert layers.decode_rows_block(cfg, 1, cc, rc, "latent") == 0, "off the TPU the engine keeps the XLA read"
    got = _latent_read(monkeypatch, kernel=True)(cfg, q_lat, q_rope, cc, rc, L - 1, pos, live)
    assert layers.decode_rows_block(cfg, 1, cc, rc, "latent") == block
    assert got.shape == want.shape == (4, 4, rank) and got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got)[[0, 1, 3]], np.asarray(want)[[0, 1, 3]], atol=1e-5, rtol=0)
    assert not np.asarray(got)[2].any()


def _latent_poisoned(cc, rc, pos, live, block):
    """Every block that lies wholly past a slot's position, and every
    position of a slot that is not live, NaN in the latents and the keys."""
    import jax.numpy as jnp

    S = cc["latent"].shape[2]
    first_dead = jnp.where(live, (pos // block + 1) * block, 0)
    dead = jnp.arange(S)[None, :] >= first_dead[:, None]  # (B, S)
    return (
        {"latent": jnp.where(dead[None, :, :, None], jnp.nan, cc["latent"])},
        {"latent": jnp.where(dead[None, :, :, None], jnp.nan, rc["latent"])},
    )


def test_latent_blocks_past_a_slots_position_are_not_read(monkeypatch):
    """The XLA read multiplies every allocated latent, so a NaN behind a
    slot's position reaches its output (0 x NaN); the kernel returns the
    clean cache's result."""
    import jax.numpy as jnp

    shape, block = "cut_384x128+64", 128
    cfg = _latent_cfg()
    q_lat, q_rope, cc, rc, pos = _latent_inputs(shape, [0, block - 1, 300, block, 2 * block - 1])
    live = jnp.asarray([True, True, False, True, True])
    pc, pr = _latent_poisoned(cc, rc, pos, live, block)
    assert bool(jnp.isnan(pc["latent"][:, 0, block:]).all()) and not bool(jnp.isnan(pc["latent"][:, 0, :block]).any())
    assert bool(jnp.isnan(pr["latent"][:, 0, block:]).all()) and not bool(jnp.isnan(pr["latent"][:, 0, :block]).any())
    xla = _latent_read(monkeypatch, kernel=False)
    assert np.isnan(np.asarray(xla(cfg, q_lat, q_rope, pc, pr, 0, pos))).any()
    want = xla(cfg, q_lat, q_rope, cc, rc, 0, pos)
    got = _latent_read(monkeypatch, kernel=True)(cfg, q_lat, q_rope, pc, pr, 0, pos, live)
    assert np.isfinite(np.asarray(got)).all()
    keep = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep], atol=1e-5, rtol=0)


@pytest.mark.parametrize("off", [-1, 1], ids=["one_block_short", "one_block_far"])
def test_a_latent_block_count_off_by_one_fails(off, monkeypatch):
    """The planted fault of the walk both kernels share, seen through the
    latent one: a block short, the newest latents go unread; a block far, a
    dead block's NaN reaches the output."""
    import jax.numpy as jnp

    from ray_lightning_tpu.ops import decode_attention as D

    shape, block = "cut_384x128+64", 128
    real = D._last_block
    monkeypatch.setattr(
        D, "_last_block", lambda pos, block, seq: jnp.clip(real(pos, block, seq) + off, 0, seq // block - 1))
    cfg = _latent_cfg()
    q_lat, q_rope, cc, rc, pos = _latent_inputs(shape, [block + 3, block + 90])
    live = jnp.asarray([True, True])
    pc, pr = _latent_poisoned(cc, rc, pos, live, block)
    want = _latent_read(monkeypatch, kernel=False)(cfg, q_lat, q_rope, cc, rc, 0, pos)
    got = np.asarray(_latent_read(monkeypatch, kernel=True)(cfg, q_lat, q_rope, pc, pr, 0, pos, live))
    if off > 0:
        assert np.isnan(got).any()
    else:
        assert np.isfinite(got).all() and np.abs(got - np.asarray(want)).max() > 1e-2


def test_a_latent_block_is_copied_once_for_both_products(monkeypatch):
    """The kernel's structure, counted while it is traced: each of the three
    places that fetch a block (the first live slot's first, a slot's next,
    the next live slot's first — one of them runs a block) starts ONE copy
    of the latents and one of the keys, a block's step waits for each once,
    and the latents' buffer is loaded once for the scores and ``p · c``."""
    import jax.numpy as jnp

    from ray_lightning_tpu.ops import decode_attention as D

    started, waited, loaded = [], [], []
    real = D.pltpu.make_async_copy

    class Counted:
        def __init__(self, src, dst, sem):
            self.copy = real(src, dst, sem)

        def start(self):
            started.append(self)
            self.copy.start()

        def wait(self):
            waited.append(self)
            self.copy.wait()

    monkeypatch.setattr(D.pltpu, "make_async_copy", Counted)
    kernel = D._latent_kernel

    def spy(pos_ref, next_ref, ql_ref, qr_ref, c_hbm, r_hbm, o_ref, c_buf, r_buf, sem, **kw):
        class Loads:  # the latents' buffer, its loads counted
            shape, dtype = c_buf.shape, c_buf.dtype

            def __getitem__(self, i):
                loaded.append(i)
                return c_buf[i]

            at = c_buf.at

        return kernel(pos_ref, next_ref, ql_ref, qr_ref, c_hbm, r_hbm, o_ref, Loads(), r_buf, sem, **kw)

    monkeypatch.setattr(D, "_latent_kernel", spy)
    shape, block = "cut_384x128+64", 128
    q_lat, q_rope, cc, rc, pos = _latent_inputs(shape, [block + 3, 5])
    D.latent_decode_attention(q_lat, q_rope, cc["latent"], jnp.swapaxes(rc["latent"], 2, 3), 0, pos,
                              jnp.asarray([True, True]), scale=0.1, interpret=True)
    # a pair a place: the latents' copy and the keys'
    assert len(started) == 2 * 3 and len(waited) == 2 * 1
    assert len(loaded) == 1


@pytest.mark.parametrize("case", [
    "cpu", "attn_impl_reference", "verify_q3", "latent_width_96", "rope_width_48", "rows_100", "no_latent_kind",
])
def test_everything_else_keeps_the_xla_read_of_latents(case):
    """What the selection observes of a mixed configuration's caches, one
    condition a case; ``"tpu"`` and the cell's shapes give the block."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    cfg = _latent_cfg("reference" if case == "attn_impl_reference" else "flash")
    rows = 100 if case == "rows_100" else 6656
    rank, rope = (96 if case == "latent_width_96" else 512), (48 if case == "rope_width_48" else 64)
    kind = "full" if case == "no_latent_kind" else "latent"
    cc = {kind: jax.ShapeDtypeStruct((16, 64, rows, rank), jnp.bfloat16)}
    rc = {kind: jax.ShapeDtypeStruct((16, 64, rows, rope), jnp.bfloat16)}
    q_len = 3 if case == "verify_q3" else 1
    assert layers.decode_rows_block(cfg, q_len, cc, rc, "latent", backend=None if case == "cpu" else "tpu") == 0
    sound = {"latent": jax.ShapeDtypeStruct((16, 64, 6656, 512), jnp.bfloat16)}, {
        "latent": jax.ShapeDtypeStruct((16, 64, 6656, 64), jnp.bfloat16)}
    assert layers.decode_rows_block(_latent_cfg(), 1, *sound, "latent", backend="tpu") == 512


# -- a mixed configuration's full kind: K rows and V rows of different widths ---------------
#: 8 query heads on 2 KV heads, q·k heads of 192 (one and a half lane tiles) and v heads of 128: K rows of 384,
#: V rows of 256, three blocks of 128 positions a slot. Only heads, widths, the sink and attn_impl reach the read.
KV = dict(
    vocab_size=96, n_layer=2, n_head=8, n_kv_head=2, n_kv_head_window=2, d_model=64, d_ff=64, qk_head_dim=192,
    v_head_dim=128, max_seq=S, pos_embed="rope", rope_dim=64, norm_impl="rmsnorm", mlp_variant="swiglu",
    tie_word_embeddings=False, attn_window=128, attn_sink_logit=["window"], attn_value_scale=0.707,
    layer_types=[["full", "dense"], ["window", "dense"]], compute_dtype="float32",
)


def _kv_inputs(pos, seed=0, layers=L):
    import jax
    import jax.numpy as jnp

    B, (H, G, dk, dv) = len(pos), (KV[k] for k in ("n_head", "n_kv_head", "qk_head_dim", "v_head_dim"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, G, H // G, dk), jnp.float32)
    kc = jax.random.normal(ks[1], (layers, B, S, G * dk), jnp.float32)
    vc = jax.random.normal(ks[2], (layers, B, S, G * dv), jnp.float32)
    return q, kc, vc, jnp.asarray(pos, jnp.int32)


def _kv_xla(q, kc, vc, li, pos):
    """The read the kernel takes the place of, on the same inputs: (B, H, dv)."""
    from ray_lightning_tpu.models.mixed import _attend_cache

    return np.asarray(_attend_cache(q, kc[li], vc[li], pos, None, 0, False))[:, 0]


def _kv_kernel(q, kc, vc, li, pos, live):
    from ray_lightning_tpu.ops.decode_attention import decode_attention

    B = q.shape[0]
    return np.asarray(decode_attention(q[:, 0].reshape(B, KV["n_head"], -1), kc, vc, li, pos, live, block=BLOCK))


@pytest.mark.parametrize("where", sorted(POSITIONS))
def test_kernel_equals_the_xla_read_of_k_and_v_rows_of_different_widths(where):
    """Slot 0 at the named position, slot 1 elsewhere, slot 2 NOT live
    (zeros), slot 3 behind an idle one."""
    import jax.numpy as jnp

    q, kc, vc, pos = _kv_inputs([POSITIONS[where], 200, 300, 5])
    want = _kv_xla(q, kc, vc, 1, pos)
    got = _kv_kernel(q, kc, vc, 1, pos, jnp.asarray([True, True, False, True]))
    assert got.shape == want.shape == (4, 8, 128) and got.dtype == np.float32
    np.testing.assert_allclose(got[[0, 1, 3]], want[[0, 1, 3]], atol=1e-5, rtol=0)
    assert not got[2].any()


def test_k_and_v_blocks_past_a_slots_position_are_not_read():
    import jax.numpy as jnp

    q, kc, vc, pos = _kv_inputs([0, BLOCK - 1, 300, BLOCK, 2 * BLOCK - 1])
    live = jnp.asarray([True, True, False, True, True])
    pk, pv = _poisoned(kc, vc, pos[:, None], live)
    assert np.isnan(_kv_xla(q, pk, pv, 0, pos)).any()  # 0 x NaN behind the mask
    got = _kv_kernel(q, pk, pv, 0, pos, live)
    assert np.isfinite(got).all()
    keep = np.asarray(live)
    np.testing.assert_allclose(got[keep], _kv_xla(q, kc, vc, 0, pos)[keep], atol=1e-5, rtol=0)


@pytest.mark.parametrize("off", [-1, 1], ids=["one_block_short", "one_block_far"])
def test_a_k_and_v_block_count_off_by_one_fails(off, monkeypatch):
    """The planted fault of the walk the three callers share, seen through
    rows of two widths."""
    import jax.numpy as jnp

    from ray_lightning_tpu.ops import decode_attention as D

    real = D._last_block
    monkeypatch.setattr(
        D, "_last_block", lambda pos, block, seq: jnp.clip(real(pos, block, seq) + off, 0, seq // block - 1))
    q, kc, vc, pos = _kv_inputs([BLOCK + 3, BLOCK + 90])
    live = jnp.asarray([True, True])
    pk, pv = _poisoned(kc, vc, pos[:, None], live)
    got = _kv_kernel(q, pk, pv, 0, pos, live)
    if off > 0:
        assert np.isnan(got).any()
    else:
        assert np.isfinite(got).all() and np.abs(got - _kv_xla(q, kc, vc, 0, pos)).max() > 1e-2


@pytest.mark.parametrize("case,kind,want", [
    ("full_without_a_sink", "full", BLOCK), ("the_window_ring", "window", 0), ("full_with_a_sink", "full", 0),
    ("verify_q3", "full", 0), ("cpu", "full", 0), ("attn_impl_reference", "full", 0), ("v_rows_of_96", "full", 0),
    ("a_kind_the_model_has_no_layer_of", "latent", 0), ("the_state_layers_tuples", "ssm", 0),
])
def test_the_selection_answers_kind_by_kind(case, kind, want):
    """``decode_rows_block`` on a mixed configuration's caches: the full
    kind's rows walk where no sink joins its softmax; the ring, whose rows
    are not positions ``0 .. pos``, and everything the uniform selection
    refuses keep the XLA read."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    cfg = GPTConfig(**dict(
        KV, attn_sink_logit=["window", "full"] if case == "full_with_a_sink" else ["window"],
        attn_impl="reference" if case == "attn_impl_reference" else "flash"))
    dv = 48 if case == "v_rows_of_96" else 128

    def rows(n, width):
        return jax.ShapeDtypeStruct((1, 4, n, width), jnp.bfloat16)

    kc = {"full": rows(S, 2 * 192), "window": rows(128, 2 * 192), "ssm": (rows(S, 384),)}
    vc = {"full": rows(S, 2 * dv), "window": rows(128, 2 * dv), "ssm": (rows(S, 256),)}
    q_len = 3 if case == "verify_q3" else 1
    assert layers.decode_rows_block(cfg, q_len, kc, vc, kind, backend=None if case == "cpu" else "tpu") == want
    assert layers.decode_rows_block(cfg, q_len, kc, vc, kind) == 0


def test_a_mixed_decode_step_with_the_kernel_gives_the_xla_steps_logits(monkeypatch):
    """The whole token step of a full layer and a window layer (projections,
    rotary, the value scale, the row writes, the reads, the MLPs): the full
    layer's read through the kernel, the ring's through XLA in both."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G
    from ray_lightning_tpu.models.mixed import empty_caches
    from ray_lightning_tpu.ops import decode_attention as D
    from tests.utils import force_decode_kernel

    cfg = GPTConfig(**KV)
    params = G.init_gpt_params(jax.random.PRNGKey(1), cfg)
    B = 3
    kc, vc = empty_caches(cfg, B, S, jnp.float32)
    ks = iter(jax.random.split(jax.random.PRNGKey(2), 4))
    kc = {kind: 0.3 * jax.random.normal(next(ks), a.shape, jnp.float32) for kind, a in kc.items()}
    vc = {kind: 0.3 * jax.random.normal(next(ks), a.shape, jnp.float32) for kind, a in vc.items()}
    cur = jnp.asarray([5, 17, 44], jnp.int32)
    pos = jnp.asarray([BLOCK, S - 1, 9], jnp.int32)  # a block's first row, the cache's last, a slot that is not live
    active = jnp.asarray([True, True, False])
    want = G.gpt_decode_step(params, cfg, cur, pos, kc, vc, active)
    force_decode_kernel(monkeypatch)
    real, calls = D.decode_attention, []

    def spy(*a, **kw):
        calls.append(a[3])
        return real(*a, **kw)

    monkeypatch.setattr(D, "decode_attention", spy)
    got = G.gpt_decode_step(params, cfg, cur, pos, kc, vc, active)
    assert calls == [0], "the full kind's one layer, by its index in the stack; the ring keeps the XLA read"
    np.testing.assert_allclose(np.asarray(got[0])[:2], np.asarray(want[0])[:2], atol=2e-4, rtol=0)
    for a, b in zip(got[1:], want[1:]):
        for kind in ("full", "window"):
            np.testing.assert_allclose(np.asarray(a[kind])[:, :2], np.asarray(b[kind])[:, :2], atol=2e-4, rtol=0)


@pytest.mark.parametrize("seq,k_width,v_width,latent,want", [
    (2048, 1024, 1024, False, 256), (1024, 1024, 1024, False, 256), (5120, 768, 512, False, 256),
    (2048, 256, 256, False, 512), (6656, 512, 64, True, 512), (384, 384, 256, False, 128), (2048, 2048, 2048, False, 128),
    (2048, 4096, 4096, False, 0), (2048, 192, 128, False, 0), (100, 256, 256, False, 0),
], ids=["chat_rows", "gpt2_rows", "mixedlen_k768_v512", "shortchat_rows_256", "docqa_latent_pair", "three_blocks_of_128",
        "rows_2048_wide", "rows_too_wide", "a_width_off_the_lanes", "no_block_divides_the_rows"])
def test_the_block_follows_the_rows_width(seq, k_width, v_width, latent, want):
    """``decode_block``: the largest of 512 / 256 / 128 rows whose wider
    block stays within 256K elements — what the chip's tables chose for each
    cell's rows (its docstring has them)."""
    from ray_lightning_tpu.ops.decode_attention import decode_block

    assert decode_block(seq, k_width, v_width, latent=latent) == want
