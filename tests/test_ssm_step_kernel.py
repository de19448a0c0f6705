"""The state layers' decode update over the live slots (``ops/ssm_step.py``),
interpreted on the CPU, against the XLA lines it replaces on a TPU
(``models/ssm.py:_update_all``) at both served shapes cut in slots only:
the live slots' state and ``y`` to 1e-6 of their norm, a slot that is not
live bit for bit on BOTH paths, a mask that changes between two steps,
falcon's muP scalars through ``ssm_step`` itself, which shapes and backends
choose the kernel, and four planted faults each caught. Whether Mosaic
takes the kernel is ``tests/test_state_step_v5e.py``'s and
``tests/test_parallel_step_v5e.py``'s; what it costs is the chip's
(``tools/state_step_check.py``)."""
import functools

import numpy as np
import pytest

#: slots, heads, head width, state width, groups: the falcon and nemotron cells' state layers, six slots of each
SHAPES = {"falcon": (6, 32, 128, 256, 2), "nemotron": (6, 128, 64, 128, 8)}
LIVE = {
    "none": [False] * 6, "one": [False, False, False, True, False, False],
    "some": [True, False, True, True, False, False], "all": [True] * 6,
}


def _operands(shape, seed=0):
    import jax
    import jax.numpy as jnp

    B, H, P, N, G = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jax.random.normal(ks[0], (B, H, P, N), jnp.float32),
        jax.random.uniform(ks[1], (B, H), jnp.float32, 0.3, 1.0),
        jax.random.normal(ks[2], (B, H, P), jnp.float32),
        jax.random.normal(ks[3], (B, G, N), jnp.float32) * 0.3,
        jax.random.normal(ks[4], (B, G, N), jnp.float32) * 0.3,
    )


def _xla(state, decay, dtx, bm, cm, active):
    """The XLA lines on the kernel's operands."""
    from ray_lightning_tpu.models.ssm import _update_all

    B, H, P, N = state.shape
    G = bm.shape[1]
    s, y = _update_all(state.reshape(B, G, H // G, P, N), decay.reshape(B, G, H // G), dtx.reshape(B, G, H // G, P),
                       bm, cm, active)
    return s.reshape(state.shape), y.reshape(B, H, P)


def _off(got, want, live):
    """The live slots' largest distance, as a share of their norm, slot by slot."""
    got, want = np.asarray(got, np.float64)[live], np.asarray(want, np.float64)[live]
    if not len(want):
        return 0.0
    flat = (got - want).reshape(len(want), -1), want.reshape(len(want), -1)
    return float((np.linalg.norm(flat[0], axis=1) / np.linalg.norm(flat[1], axis=1)).max())


def _both(shape, live, seed=0):
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.ssm_step import ssm_step_update

    ops = _operands(shape, seed)
    active = jnp.asarray(live)
    return ops, ssm_step_update(*ops, active), _xla(*ops, active)


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernel_is_the_xla_lines_over_the_live_slots_and_touches_no_other(shape, live):
    mask = np.array(LIVE[live])
    ops, (got_s, got_y), (want_s, want_y) = _both(SHAPES[shape], LIVE[live])
    assert _off(got_s, want_s, mask) < 1e-6 and _off(got_y, want_y, mask) < 1e-6
    # a slot that is not live keeps its state bit for bit on both paths, and its y is zeros on both
    before = np.asarray(ops[0])
    for s, y in ((got_s, got_y), (want_s, want_y)):
        assert np.array_equal(np.asarray(s)[~mask], before[~mask])
        assert not np.asarray(y)[~mask].any()
    if mask.any():
        assert not np.array_equal(np.asarray(got_s)[mask], before[mask])


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_slot_frozen_between_two_steps_keeps_what_the_first_left(shape):
    """The fold's mask changes from one iteration to the next (a slot that
    met its budget freezes; another was never live): the second step
    advances what the first left in the slots still live, and the frozen
    slot's state is the first step's to the bit."""
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.ssm_step import ssm_step_update

    first, second = np.array(LIVE["some"]), np.array([True, False, False, True, False, True])
    state, *rest = _operands(SHAPES[shape])
    other = _operands(SHAPES[shape], seed=1)[1:]
    got1, _ = ssm_step_update(state, *rest, jnp.asarray(first))
    got2, y2 = ssm_step_update(got1, *other, jnp.asarray(second))
    want1, _ = _xla(state, *rest, jnp.asarray(first))
    want2, want_y2 = _xla(want1, *other, jnp.asarray(second))
    assert _off(got2, want2, second) < 1e-6 and _off(y2, want_y2, second) < 1e-6
    frozen = first & ~second  # slot 2
    assert frozen.sum() == 1 and np.array_equal(np.asarray(got2)[frozen], np.asarray(got1)[frozen])
    never = ~first & ~second
    assert np.array_equal(np.asarray(got2)[never], np.asarray(state)[never])
    late = ~first & second  # slot 5: its first step starts from the state as it was
    assert late.sum() == 1 and _off(got2, want2, late) < 1e-6


# -- planted faults ---------------------------------------------------------------------------
def _no_decay(K):
    real = K._advance
    return "_advance", lambda s_ref, out_ref, decay, d, bm, cm: real(s_ref, out_ref, [1.0] * len(decay), d, bm, cm)


def _b_and_c_swapped(K):
    real = K._advance
    return "_advance", lambda s_ref, out_ref, decay, d, bm, cm: real(s_ref, out_ref, decay, d, cm, bm)


def _written_to_the_next_slot(K):
    def home(ids_ref, t, blocks, heads):
        b, h0 = K._block(ids_ref, t, blocks, heads)
        return b + 1, h0  # the mask below leaves the slot after every live one in bounds

    return "_home", home


def _last_block_skipped(K):
    return "_steps", lambda n_live, blocks: n_live * blocks - 1


@pytest.mark.parametrize("plant", [_no_decay, _b_and_c_swapped, _written_to_the_next_slot, _last_block_skipped],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_planted_fault_is_caught(plant, monkeypatch):
    """The comparison above at nemotron's shape with one fault in the
    kernel: sound it reads under 1e-6 and keeps the other slots' bits; with
    the fault the live slots are off by a hundredth of their norm or more,
    or a slot that is not live has lost its bits."""
    from ray_lightning_tpu.ops import ssm_step as K

    live = [True, False, False, True, False, False]
    mask = np.array(live)

    def reading():
        ops, (got_s, got_y), (want_s, want_y) = _both(SHAPES["nemotron"], live)
        kept = np.array_equal(np.asarray(got_s)[~mask], np.asarray(ops[0])[~mask])
        return max(_off(got_s, want_s, mask), _off(got_y, want_y, mask)), kept

    off, kept = reading()
    assert off < 1e-6 and kept
    monkeypatch.setattr(K, *plant(K))
    off, kept = reading()
    assert off > 1e-2 or not kept, (off, kept)
    if plant is _written_to_the_next_slot:
        assert off > 1e-2 and not kept  # the live slot kept its old state AND its neighbour was overwritten


# -- which update a step takes ------------------------------------------------------------------
@pytest.mark.parametrize("H,P,N,G,want", [
    (32, 128, 256, 2, 8), (128, 64, 128, 8, 16), (32, 2, 8, 2, 0), (4, 8, 128, 1, 4), (4, 12, 128, 1, 0),
    (6, 8, 128, 4, 0), (24, 64, 128, 8, 1), (8, 256, 512, 1, 2), (8, 512, 1024, 1, 0),
], ids=["falcon", "nemotron", "the_toy_roots", "a_small_state", "rows_off_the_sublanes", "heads_off_the_groups",
        "three_heads_a_group", "wide_heads", "a_head_past_a_block"])
def test_the_block_follows_the_shape(H, P, N, G, want):
    from ray_lightning_tpu.ops.ssm_step import step_heads

    assert step_heads(H, P, N, G) == want


def test_off_a_tpu_the_step_keeps_the_xla_lines():
    import jax

    from ray_lightning_tpu.models import ssm

    state = jax.ShapeDtypeStruct((4, 32, 128, 256), "float32")
    assert ssm._step_heads(state, 2) == 0
    assert ssm._step_heads(state, 2, backend="tpu") == 8
    assert ssm._step_heads(jax.ShapeDtypeStruct((4, 32, 2, 8), "float32"), 2, backend="tpu") == 0


def test_a_shape_without_a_block_is_refused_by_name():
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.ssm_step import ssm_step_update

    ops = _operands((2, 4, 8, 128, 1))
    with pytest.raises(ValueError, match="no block of heads"):
        ssm_step_update(*ops, jnp.ones((2,), bool), heads=3)
    with pytest.raises(ValueError, match="no block of heads"):
        ssm_step_update(*_operands((2, 4, 8, 64, 1)), jnp.ones((2,), bool))


# -- through ssm_step, with the muP scalars ------------------------------------------------------
@pytest.mark.parametrize("scalars", ["plain", "falcon_mup"])
def test_ssm_step_takes_the_kernel_where_it_is_told_a_tpu_and_gives_the_xla_steps_result(scalars, monkeypatch):
    """``models/ssm.py:ssm_step`` at falcon's state widths (a narrow model
    around them) under both updates, two steps with a mask that changes:
    the layer's write, the live slots' state and every slot's conv tail
    agree, and a slot that is not live keeps its state's bits under both."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import ssm
    from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
    from ray_lightning_tpu.models.mixed import _layer_leaves, layer_specs

    mult = (3.0, 0.5, 1.25, 0.9, 0.5, 0.8, 0.2, 1.5, 2.0, 1.6, 2.4, 1.2, 1.7, 2.5) if scalars == "falcon_mup" else ()
    cfg = GPTConfig(
        vocab_size=64, n_layer=1, n_head=2, n_kv_head=1, d_model=32, qk_head_dim=8, v_head_dim=8, d_ff=32, max_seq=16,
        pos_embed="rope", norm_impl="rmsnorm", mlp_variant="swiglu", tie_word_embeddings=False,
        layer_types=[["ssm", "dense"]], ssm_heads=32, ssm_head_dim=128, ssm_groups=2, ssm_state=256, ssm_conv=4,
        ssm_chunk=8, multipliers=mult,
    )
    blocks = init_gpt_params(jax.random.PRNGKey(0), cfg)["blocks"]
    blocks.update(ssm_A_log=blocks["ssm_A_log"] + 0.3, ssm_dt_bias=blocks["ssm_dt_bias"] - 0.2,
                  ssm_D=blocks["ssm_D"] * 0.7, ssm_conv_w=blocks["ssm_conv_w"] * 30.0)
    lp = _layer_leaves(blocks, layer_specs(cfg)[0])
    B = 4
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    state = jax.random.normal(ks[0], (B, 32, 128, 256), jnp.float32)
    tail = jax.random.normal(ks[1], (3, B, ssm.conv_dim(cfg)), jnp.float32)
    us = [jax.random.normal(k, (B, 1, 32), jnp.float32) for k in ks[2:]]
    masks = [jnp.asarray([True, True, False, True]), jnp.asarray([True, False, False, True])]

    def two_steps():
        s, t, outs = state, tail, []
        for u, m in zip(us, masks):
            o, s, t = ssm.ssm_step(u, lp, cfg, s, t, m)
            outs.append(o)
        return outs, s, t

    want_o, want_s, want_t = two_steps()
    monkeypatch.setattr(ssm, "_step_heads", functools.partial(ssm._step_heads, backend="tpu"))
    got_o, got_s, got_t = two_steps()
    for m, g, w in zip(masks, got_o, want_o):
        assert _off(g, w, np.asarray(m)) < 1e-5
    assert _off(got_s, want_s, np.asarray(masks[1])) < 1e-6
    assert np.array_equal(np.asarray(got_t), np.asarray(want_t))  # the tail is XLA's on both
    for s in (got_s, want_s):
        assert np.array_equal(np.asarray(s)[2], np.asarray(state)[2])  # never live
    assert np.array_equal(np.asarray(got_s)[1], np.asarray(ssm.ssm_step(us[0], lp, cfg, state, tail, masks[0])[1])[1])
