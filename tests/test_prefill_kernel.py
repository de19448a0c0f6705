"""The prefill read of a mixed configuration's full and latent layers
(PR 49): the forward flash kernel with a v of its own width, grouped KV
heads and a count of real rows (``ops/flash_attention.py``), the choice
between it and the blocked XLA read (``models/mixed.py:prefill_kernel``),
``mixed_rows`` under both, and the four counters of ``stats()["attn"]``.
Off the chip the kernel runs interpreted."""
import numpy as np
import pytest
from test_latent_layers import LATENT
from test_mixed_layers import MIXED

from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params

#: both files' configurations at head widths Mosaic takes and three tiles of 128 rows
WIDE = {
    "latent": dict(LATENT, qk_head_dim=128, v_head_dim=64, rope_dim=64, max_seq=512),
    "mixed": dict(MIXED, n_head=8, n_kv_head=2, qk_head_dim=192, v_head_dim=128, rope_dim=64, max_seq=512),
}


def _qkv(heads, S=64, dtype="float32"):
    import jax
    import jax.numpy as jnp

    H, Hkv, dqk, dv = heads
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(H + dqk), 3)
    return (jax.random.normal(kq, (1, S, H, dqk), jnp.dtype(dtype)), jax.random.normal(kk, (1, S, Hkv, dqk), jnp.dtype(dtype)),
            jax.random.normal(kv, (1, S, Hkv, dv), jnp.dtype(dtype)))


def _xla(q, k, v):
    from ray_lightning_tpu.models.mixed import _attend_rows_full

    B, S, H, d = q.shape
    return _attend_rows_full(q.reshape(B, S, k.shape[2], H // k.shape[2], d), k, v, None)


HEADS = {"unequal_widths": (4, 4, 24, 16), "grouped_kv_heads": (6, 2, 16, 16), "both": (6, 2, 24, 16)}


@pytest.mark.parametrize("true_len", [None, 1, 17, 32, 33, 64],
                         ids=["every_row", "one_row", "inside_a_block", "a_blocks_end", "a_blocks_first_row", "the_buckets_end"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_the_forward_kernel_gives_the_blocked_xla_reads_rows(heads, true_len):
    """Blocks of 16 over 64 rows: the real rows are the XLA read's to
    float32 rounding, and a query block wholly past ``true_len`` is zeros."""
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(HEADS[heads])
    got = flash_attention(q, k, v, block_q=16, block_k=16, true_len=None if true_len is None else jnp.int32(true_len))
    n = true_len or 64
    assert got.shape == (1, 64, HEADS[heads][0], 16) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got[:, :n]), np.asarray(_xla(q, k, v)[:, :n]), atol=2e-6, rtol=0)
    past = -(-n // 16) * 16
    assert not np.asarray(got[:, past:]).any()
    if n % 16:
        assert np.asarray(got[:, n:past]).any()  # the rest of the last real block is computed, as before


def test_the_forward_kernel_rounds_p_once_in_bfloat16_as_the_xla_read_does():
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(HEADS["both"], dtype="bfloat16")
    got = np.asarray(flash_attention(q, k, v, block_q=16, block_k=16, true_len=jnp.int32(40)), np.float32)[:, :40]
    want = np.asarray(_xla(q, k, v), np.float32)[:, :40]
    assert np.abs(got - want).max() <= 2.0 ** -6 * np.abs(want).max()


def test_equal_widths_and_one_kv_head_a_query_head_keep_their_bits_and_their_program():
    """The differentiable entry's forward is the program it was: no
    prefetched scalar, and the forward-only entry with every row real
    computes the same bits."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv((4, 4, 16, 16))

    def prefetched(**kw):
        jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, block_q=16, block_k=16, interpret=True, **kw))(q, k, v)
        call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "custom_vjp_call"]
        inner, = [e for e in call.params["call_jaxpr"].jaxpr.eqns if e.primitive.name == "pallas_call"]
        return inner.params["grid_mapping"].num_index_operands

    assert prefetched() == 0 and prefetched(true_len=jnp.int32(64)) == 1
    plain = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_array_equal(np.asarray(flash_attention(q, k, v, block_q=16, block_k=16, true_len=jnp.int32(64))), np.asarray(plain))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.grad(
        lambda q, k, v: flash_attention(q, k, v, block_q=16, block_k=16).sum(), argnums=(0, 1, 2))(q, k, v))


@pytest.mark.parametrize("heads,kw", [("unequal_widths", {}), ("grouped_kv_heads", {}), ("both", {}), ((4, 4, 16, 16), {"true_len": 64})],
                         ids=["unequal_widths", "grouped_kv_heads", "both", "true_len"])
def test_the_forward_only_shapes_say_so_when_differentiated(heads, kw):
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(HEADS.get(heads, heads))
    kw = {name: jnp.int32(val) for name, val in kw.items()}
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(lambda q: flash_attention(q, k, v, block_q=16, block_k=16, **kw).sum())(q)


def test_shapes_that_fit_no_head_layout_are_refused_by_what_is_wrong():
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv((6, 4, 16, 16))
    with pytest.raises(ValueError, match="the KV heads divide q's"):
        flash_attention(q, k, v)


# -- the choice ------------------------------------------------------------------------------------
def _choice_cfg(**over):
    return GPTConfig(**dict(WIDE["mixed"], **over))


@pytest.mark.parametrize("kind,backend,rows,over,want", [
    ("full", "tpu", 4096, {}, True),
    ("latent", "tpu", 6144, dict(WIDE["latent"]), True),
    ("full", "tpu", 4096 - 512, {}, False),  # under the crossing: a program does not gain there
    ("full", "tpu", 2048, {}, False),
    ("full", "cpu", 4096, {}, False),
    ("full", None, 4096, {}, False),  # the backend the tests run on
    ("window", "tpu", 4096, {}, False),  # rows x 2W already, and a sink logit
    ("full", "tpu", 4096, dict(attn_sink_logit=["window", "full"]), False),
    ("full", "tpu", 4096, dict(attn_impl="reference"), False),
    ("full", "tpu", 4096, dict(qk_head_dim=72), False),  # not a width Mosaic is known to take
    ("full", "tpu", 4096, dict(v_head_dim=8), False),
    ("full", "tpu", 4096, dict(qk_head_dim=320, rope_dim=64), False),
    ("full", "tpu", 4096 + 64, {}, False),  # no tile divides it
    ("full", "tpu", 4096 + 128, {}, True),  # tiles of 128
    ("ssm", "tpu", 4096, {}, False),
], ids=lambda x: None if isinstance(x, dict) else str(x))
def test_the_choice_of_the_prefill_read_by_what_it_can_observe(kind, backend, rows, over, want):
    from ray_lightning_tpu.models.mixed import _KERNEL_ROWS, prefill_kernel

    assert _KERNEL_ROWS == 4096  # the crossing itself is the first case
    assert prefill_kernel(_choice_cfg(**over), kind, rows, backend) is want


# -- the layers over a prompt under both reads ---------------------------------------------------
@pytest.mark.parametrize("true_len", [None, 130, 384], ids=["gpt_prefill", "a_padded_prompt", "a_full_bucket"])
@pytest.mark.parametrize("name", sorted(WIDE))
def test_the_rows_of_a_prompt_come_out_the_same_under_both_reads(name, true_len, monkeypatch):
    """``mixed_rows`` over 384 rows (three tiles of 128; at 130 real rows
    the third is skipped), float32: logits and what the rows leave behind,
    kernel against XLA on the real rows at the tolerance of the decode
    kernel's twin (tests/test_latent_layers.py, tests/test_mixed_layers.py)."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import mixed
    from ray_lightning_tpu.models.layers import _rmsnorm
    from tests.utils import force_prefill_kernel

    cfg = GPTConfig(**dict(WIDE[name], compute_dtype="float32"))
    params = init_gpt_params(jax.random.PRNGKey(1), cfg)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 96, (1, 384)), jnp.int32)
    n = None if true_len is None else jnp.int32(true_len)
    reads = []

    def spy(*a, **kw):
        reads.append(kw.get("true_len"))
        return flash(*a, **kw)

    import importlib

    F = importlib.import_module("ray_lightning_tpu.ops.flash_attention")  # the package's attribute of that name is the function
    flash = F.flash_attention
    monkeypatch.setattr(F, "flash_attention", spy)
    want = mixed.mixed_rows(params, cfg, tokens, true_len=n, prefill=True)
    assert not reads  # off a TPU the XLA read, forced or not
    force_prefill_kernel(monkeypatch, rows=256)
    got = mixed.mixed_rows(params, cfg, tokens, true_len=n, prefill=True)
    square = sum(mixed.count_kind(cfg, kind) for kind in ("full", "latent"))
    assert len(reads) == square and all(int(r) == (true_len or 384) for r in reads)
    real = true_len or 384
    logits = [np.asarray(mixed.mixed_logits(_rmsnorm(h[0][:, :real], params["lnf_g"], cfg.norm_eps), params, cfg)) for h in (got, want)]
    np.testing.assert_allclose(logits[0], logits[1], atol=2e-4, rtol=0)
    for half in (1, 2):
        for kind in want[half]:
            if kind != "ssm":
                np.testing.assert_allclose(np.asarray(got[half][kind])[:, :, :real], np.asarray(want[half][kind])[:, :, :real], atol=2e-4, rtol=0)
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


def test_the_forward_pass_that_may_be_differentiated_keeps_the_xla_read(monkeypatch):
    """``gpt_forward`` of mixed layer kinds has a gradient on a TPU too: it
    does not ask for the forward-only kernel, whatever the choice says."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import mixed
    from ray_lightning_tpu.models.gpt import gpt_forward
    from tests.utils import force_prefill_kernel

    force_prefill_kernel(monkeypatch, rows=0)
    cfg = GPTConfig(**dict(WIDE["mixed"], compute_dtype="float32"))
    assert mixed.prefill_kernel(cfg, "full", 128)
    params = init_gpt_params(jax.random.PRNGKey(1), cfg)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 96, (1, 128)), jnp.int32)
    grads = jax.grad(lambda p: gpt_forward(p, tokens, cfg).sum())(params)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(lambda p: mixed.mixed_rows(p, cfg, tokens, prefill=True)[0].sum())(params)


# -- the counters ----------------------------------------------------------------------------------
@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_the_four_prefill_counters_of_a_hand_made_sequence_of_admissions(read, monkeypatch):
    """Three attention layers, two of them full (the causal square); buckets
    of 128 (under the forced crossing of 256: the XLA read) and 384 (three
    tiles of 128). Prompts of 100, 130 and 300 tokens."""
    import jax

    from ray_lightning_tpu.serve.engine import DecodeEngine
    from tests.utils import force_prefill_kernel

    if read == "kernel":
        force_prefill_kernel(monkeypatch, rows=256)
    cfg = GPTConfig(**WIDE["mixed"])
    eng = DecodeEngine(init_gpt_params(jax.random.PRNGKey(0), cfg), cfg, num_slots=3, max_seq=512,
                       prefill_buckets=[128, 384], decode_fold=2)
    assert eng.attn_stats()["prefill_rows"] == 0
    rng = np.random.default_rng(5)
    eng.admit_many([dict(prompt=rng.integers(0, 96, n).tolist(), request_id=f"r{n}", max_new_tokens=2) for n in (100, 130, 300)])
    attn = eng.attn_stats()
    assert attn["prefill_rows"] == 3 * (128 + 384 + 384)
    if read == "kernel":
        # 130 tokens hold a row in two of the three query blocks: 3 of the 6 tiles; 300 in all three
        assert attn["prefill_rows_kernel"] == 2 * (384 + 384)
        assert (attn["prefill_tiles"], attn["prefill_tiles_visited"]) == (2 * (1 + 6 + 6), 2 * (1 + 3 + 6))
    else:
        # the XLA read's query blocks are 512 rows: a bucket is one tile, and every tile is computed
        assert attn["prefill_rows_kernel"] == 0
        assert (attn["prefill_tiles"], attn["prefill_tiles_visited"]) == (2 * 3, 2 * 3)
    eng.step()
    assert {k: v for k, v in eng.attn_stats().items() if k.startswith("prefill_")} == {k: v for k, v in attn.items() if k.startswith("prefill_")}
