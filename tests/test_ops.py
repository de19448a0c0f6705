"""Kernel correctness: flash attention and ring attention vs the XLA
reference, values and gradients, on the 8-virtual-device CPU mesh."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.ops import (
    attention_reference,
    flash_attention,
    ring_self_attention,
)


def _make_qkv(batch=2, seq=64, heads=2, head_dim=8, seed=0, dtype=jnp.float32):
    g = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(g, 3)
    shape = (batch, seq, heads, head_dim)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _make_qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match(causal):
    q, k, v = _make_qkv(seq=32)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=16, block_k=16) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(gf, gr, atol=5e-5, rtol=5e-5)


def test_flash_unaligned_falls_back():
    # Sequence not divisible by block: must still produce correct values
    # (reference fallback path).
    q, k, v = _make_qkv(seq=24)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def _seq_mesh(n=8):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("seq",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    q, k, v = _make_qkv(seq=64)
    mesh = _seq_mesh()
    out = ring_self_attention(q, k, v, mesh, axis_name="seq", causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_attention_gradients_match():
    q, k, v = _make_qkv(seq=32, batch=1)
    mesh = _seq_mesh()

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_self_attention(q, k, v, mesh, axis_name="seq", causal=True) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr_, gref in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr_), gref, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize(
    "window,sinks",
    [(1, 0), (5, 0), (8, 0), (17, 0), (64, 0), (8, 2), (17, 4), (9, 8)],
)
def test_ring_attention_window_matches_reference(window, sinks):
    """Band-limited ring (+ sink block) == dense sliding-window mask for
    windows smaller than, equal to, and spanning multiple 8-wide shards."""
    q, k, v = _make_qkv(seq=64)
    mesh = _seq_mesh()
    out = ring_self_attention(
        q, k, v, mesh, axis_name="seq", causal=True,
        window=window, sinks=sinks,
    )
    ref = attention_reference(
        q, k, v, causal=True, window=window, sinks=sinks
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_attention_window_gradients_match():
    q, k, v = _make_qkv(seq=32, batch=1)
    mesh = _seq_mesh()

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_self_attention(
                q, k, v, mesh, axis_name="seq", causal=True, window=7,
                sinks=2,
            )
            ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            attention_reference(q, k, v, causal=True, window=7, sinks=2) ** 2
        )

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr_, gref in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr_), gref, atol=5e-5, rtol=5e-5)


def test_ring_attention_window_band_limits_rotations():
    """The window must CAP the scan: ceil((W-1)/S_local)+1 rotations, not
    the full ring (the communication saving is the point)."""
    q, k, v = _make_qkv(seq=64)
    mesh = _seq_mesh()  # 8 ranks, S_local=8
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: ring_self_attention(
            q, k, v, mesh, axis_name="seq", causal=True, window=8
        )
    )(q, k, v)

    def scan_lengths(jxp):
        out = []
        for e in jxp.eqns:
            if e.primitive.name == "scan":
                out.append(e.params["length"])
            for p in e.params.values():
                inner = getattr(p, "jaxpr", p)  # ClosedJaxpr -> Jaxpr
                if hasattr(inner, "eqns"):
                    out.extend(scan_lengths(inner))
        return out

    assert scan_lengths(jaxpr.jaxpr) == [2]  # W=8, S_local=8 -> 2 rotations


def test_ring_attention_long_context_sharded_memory():
    # The point of the ring: each device only ever holds S/n of K/V. Check
    # output correctness at a longer sequence under jit with sharded inputs.
    mesh = _seq_mesh()
    q, k, v = _make_qkv(batch=1, seq=256, heads=1, head_dim=8)
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    fn = jax.jit(
        functools.partial(ring_self_attention, mesh=mesh, causal=True)
    )
    out = fn(qs, ks, vs)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)
    # Output keeps the sequence sharding (no implicit all-gather).
    assert out.sharding.spec == P(None, "seq", None, None)


def test_zigzag_permutation_roundtrip():
    from ray_lightning_tpu.ops.zigzag_attention import (
        inverse_permutation,
        zigzag_permutation,
    )

    perm = zigzag_permutation(32, 4)
    # Shard p holds global chunks (p, 2P-1-p): p=0 -> chunks 0 and 7.
    assert perm[:4].tolist() == [0, 1, 2, 3]
    assert perm[4:8].tolist() == [28, 29, 30, 31]
    inv = inverse_permutation(perm)
    np.testing.assert_array_equal(perm[inv], np.arange(32))


def test_zigzag_ring_matches_reference():
    from ray_lightning_tpu.ops.zigzag_attention import zigzag_ring_self_attention

    q, k, v = _make_qkv(seq=64)
    mesh = _seq_mesh()
    out = zigzag_ring_self_attention(q, k, v, mesh, axis_name="seq")
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_zigzag_ring_gradients_match():
    from ray_lightning_tpu.ops.zigzag_attention import zigzag_ring_self_attention

    q, k, v = _make_qkv(seq=32, batch=1)
    mesh = _seq_mesh()

    def loss_zig(q, k, v):
        return jnp.sum(
            zigzag_ring_self_attention(q, k, v, mesh, axis_name="seq") ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_zig = jax.grad(loss_zig, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gz, gr in zip(g_zig, g_ref):
        np.testing.assert_allclose(np.asarray(gz), gr, atol=5e-5, rtol=5e-5)


def test_zigzag_ring_sharded_jit():
    """Under jit with seq-sharded inputs the op runs and keeps sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_lightning_tpu.ops.zigzag_attention import zigzag_ring_self_attention

    mesh = _seq_mesh()
    q, k, v = _make_qkv(batch=1, seq=128, heads=2, head_dim=8)
    shard = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    fn = jax.jit(functools.partial(zigzag_ring_self_attention, mesh=mesh))
    out = fn(qs, ks, vs)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)
    # Output keeps the sequence sharding (no implicit all-gather escapes).
    assert out.sharding.spec == P(None, "seq", None, None)


def test_flash_backward_matches_reference_grads():
    """The Pallas dq/dk/dv kernels must match the dense reference VJP
    (block recompute never materializes (Sq, Sk))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.ops.attention import attention_reference
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(3)
    B, S, H, D = 2, 256, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    do = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    for causal in (True, False):
        _, vjp_ref = jax.vjp(
            lambda q, k, v: attention_reference(q, k, v, causal=causal), q, k, v
        )
        _, vjp_fl = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, block_q=128, block_k=128,
                interpret=True,
            ),
            q, k, v,
        )
        for name, a, b in zip(("dq", "dk", "dv"), vjp_fl(do), vjp_ref(do)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, rtol=1e-3,
                err_msg=f"causal={causal} {name}",
            )


def test_sliding_window_attention_matches_masked_reference():
    """flash window kernels == dense masked reference, forward and grads,
    including windows narrower than the block size (fully-masked blocks
    must not NaN the online softmax)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.ops.attention import (
        attention_reference, causal_mask_allowed,
    )
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(7)
    B, S, H, D = 2, 256, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    do = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    # W=64 < block 128 forces fully-masked visited blocks for late rows.
    for W in (64, 128, 300):
        ref_out, ref_vjp = jax.vjp(
            lambda q, k, v: attention_reference(q, k, v, window=W), q, k, v
        )
        fl_out, fl_vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, window=W, block_q=128, block_k=128, interpret=True
            ),
            q, k, v,
        )
        np.testing.assert_allclose(
            np.asarray(fl_out), np.asarray(ref_out), atol=2e-5,
            err_msg=f"W={W} forward",
        )
        assert np.isfinite(np.asarray(fl_out)).all()
        for name, a, b in zip(("dq", "dk", "dv"), fl_vjp(do), ref_vjp(do)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, rtol=1e-3,
                err_msg=f"W={W} {name}",
            )

    # W >= S is exactly full causal attention.
    full = attention_reference(q, k, v, causal=True)
    wide = flash_attention(q, k, v, window=4096, interpret=True)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(full), atol=2e-5)

    # mask helper semantics: row attends to itself and W-1 predecessors
    m = np.asarray(causal_mask_allowed(8, 8, window=3))
    assert m[5].tolist() == [False, False, False, True, True, True, False, False]


def test_attention_sinks_match_masked_reference():
    """window + sinks == dense masked reference (fwd + grads); sinks keep
    the first tokens visible to every query."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.ops.attention import (
        attention_reference, causal_mask_allowed,
    )
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(9)
    B, S, H, D = 2, 256, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.5, jnp.float32)
    do = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    for W, N in ((64, 4), (48, 130)):  # sinks crossing a block boundary too
        ref_out, ref_vjp = jax.vjp(
            lambda q, k, v: attention_reference(q, k, v, window=W, sinks=N),
            q, k, v,
        )
        fl_out, fl_vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, window=W, sinks=N, block_q=128, block_k=128,
                interpret=True,
            ),
            q, k, v,
        )
        np.testing.assert_allclose(
            np.asarray(fl_out), np.asarray(ref_out), atol=2e-5,
            err_msg=f"W={W} N={N}",
        )
        for name, a, b in zip(("dq", "dk", "dv"), fl_vjp(do), ref_vjp(do)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, rtol=1e-3,
                err_msg=f"W={W} N={N} {name}",
            )

    # Mask semantics: row 100, window 8, sinks 2 -> cols {0,1} + (92..100].
    m = np.asarray(causal_mask_allowed(128, 128, window=8, sinks=2))
    cols = set(np.nonzero(m[100])[0].tolist())
    assert cols == {0, 1} | set(range(93, 101)), sorted(cols)

    import pytest

    with pytest.raises(ValueError, match="sinks"):
        flash_attention(q, k, v, sinks=4)  # sinks require a window
    with pytest.raises(ValueError, match="sinks"):
        attention_reference(q, k, v, sinks=4)  # same contract on every path


_MASK_CASES = {
    "causal": dict(causal=True),
    "non_causal": dict(causal=False),
    "window_sinks": dict(causal=True, window=24, sinks=3),
}


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("case", sorted(_MASK_CASES))
def test_flash_bf16_operands_match_reference_on_the_same_inputs(case, head_dim):
    """bf16 inputs are multiplied as bf16 (float32 accumulation; p and ds
    rounded once at their product, as ``attention_reference`` rounds p):
    forward and dq / dk / dv against the reference on the same bf16 inputs.

    Tolerance: both sides round their bf16 outputs (half an ulp = 2**-9
    relative) and the p / ds operands (the same); the kernels add blocks up
    in another order and round p before the row sum divides it. 2**-6 of the
    reference's largest magnitude is four bf16 ulps there; the readings over
    three seeds are at most 2**-7.15 (dq; dv 2**-10.4, rounded as the
    reference rounds), and float32 copies of the operands read 2**-7.26."""
    kw = _MASK_CASES[case]
    q, k, v = _make_qkv(batch=1, seq=64, heads=2, head_dim=head_dim, seed=5,
                        dtype=jnp.bfloat16)
    do = jax.random.normal(jax.random.PRNGKey(11), q.shape, jnp.bfloat16)

    def fwd_bwd(attn, **extra):
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, **kw, **extra), q, k, v)
        return (out,) + vjp(do)

    got = fwd_bwd(flash_attention, block_q=16, block_k=16, interpret=True)
    want = fwd_bwd(attention_reference)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16, name
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a32).all(), name
        err = np.abs(a32 - b32).max()
        assert err <= 2.0 ** -6 * np.abs(b32).max(), (name, err, np.abs(b32).max())


def _flash_kernels(x, **kw):
    """{kernel name: (grid, [(lhs dtype, rhs dtype, result dtype) of every
    dot_general inside it])} for the three ``pallas_call``s of
    flash_attention's forward and backward on inputs like ``x``."""

    def f(q, k, v):
        return flash_attention(q, k, v, interpret=True, **kw)

    jaxpr = jax.make_jaxpr(lambda q, k, v, do: jax.vjp(f, q, k, v)[1](do))(x, x, x, x)
    found = {}

    def walk(jp, kernel):
        for eqn in jp.eqns:
            inside = kernel
            if eqn.primitive.name == "pallas_call":
                inside = eqn.params["name"]
                found[inside] = (tuple(eqn.params["grid_mapping"].grid), [])
            elif eqn.primitive.name == "dot_general" and kernel:
                found[kernel][1].append(
                    tuple(str(var.aval.dtype) for var in (*eqn.invars, *eqn.outvars))
                )
            for val in eqn.params.values():
                for sub in val if isinstance(val, (list, tuple)) else [val]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, inside)

    walk(jaxpr.jaxpr, None)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(_MASK_CASES))
def test_flash_kernels_multiply_in_the_dtype_of_their_inputs(dtype, case):
    """Every product inside the three Pallas kernels takes both operands in
    the inputs' dtype and accumulates in float32: bf16 inputs are never
    widened for the MXU, float32 inputs still multiply float32."""
    x = jnp.zeros((1, 64, 2, 64), jnp.dtype(dtype))
    found = _flash_kernels(x, block_q=16, block_k=16, **_MASK_CASES[case])
    assert sorted(found) == ["flash_dkv", "flash_dq", "flash_fwd"], found
    # products a loop body: fwd q.kT, p.v; dkv q.kT, pT.do, do.vT, dsT.q;
    # dq q.kT, do.vT, ds.k (a body is traced once per loop that uses it)
    for name, per_body in (("flash_fwd", 2), ("flash_dkv", 4), ("flash_dq", 3)):
        dots = found[name][1]
        assert dots and len(dots) % per_body == 0, (name, dots)
        assert set(dots) == {(dtype, dtype, "float32")}, (name, dots)


@pytest.mark.parametrize(
    "seq,tile",
    [(64, 64), (128, 128), (256, 256), (384, 128), (512, 512), (768, 256),
     (1024, 512), (1536, 512), (8192, 512)],
)
def test_flash_default_tile_follows_the_sequence_length(seq, tile):
    """No block named: the largest of 512 / 256 / 128 that divides the
    sequence, clipped to it — read off the grids of the three kernels. A
    named block is taken as given."""
    x = jax.ShapeDtypeStruct((1, seq, 1, 8), jnp.float32)

    def grids(**blocks):
        return {name: grid for name, (grid, _) in _flash_kernels(x, **blocks).items()}

    n = seq // tile
    assert grids() == {"flash_fwd": (1, n), "flash_dkv": (1, n), "flash_dq": (1, n)}
    if seq >= 128:
        m = seq // 128
        assert grids(block_q=128, block_k=128) == {
            "flash_fwd": (1, m), "flash_dkv": (1, m), "flash_dq": (1, m)
        }
