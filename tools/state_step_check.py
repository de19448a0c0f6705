"""On-chip check of the state layers' decode update: does the kernel that
walks the live slots compile at a cell's shape, is it the XLA lines'
result, and what does a block of heads cost?

For each shape (the falcon cell's state layers: 64 slots of 32 heads x 128
x 256 in 2 groups, six of them; the nemotron cell's: 128 slots of 128 x 64
x 128 in 8 groups, five) and each occupancy (``idle``: no slot live, which
is the call's own cost; ``cell``: the cell's live share, 38% and 66%
(ledger, PR 47); ``half``; ``full``: every slot live, where kernel and
fusion move the same bytes) runs ``ops.ssm_step.ssm_step_update`` on every
layer's float32 state, updated where it lies, at each candidate block, and
the XLA lines on the same inputs (``models/ssm.py:_update_all``), and
records: compiled or refused with Mosaic's message, the max abs error of
the live slots' state and ``y`` against the XLA lines, whether the other
slots kept their bits, and the time of one layer's call. In-process on the
real chip; fails off-chip (interpret mode proves nothing about Mosaic, and
a CPU time is no device number). Prints one JSON line per row and writes
them all to ``--out``.
"""
import argparse
import json
import os
import sys
import time

SHAPES = {
    # name: layers, slots, heads, head width, state width, groups, the cell's live share
    "burstchat_6x64x32x128x256_g2": (6, 64, 32, 128, 256, 2, 0.38),
    "shortchat_5x128x128x64x128_g8": (5, 128, 128, 64, 128, 8, 0.66),
}
SHARES = {"idle": 0.0, "half": 0.5, "full": 1.0}
#: a program of few short calls is timed by the host's dispatch (about 0.23 ms a program): two passes over the
#: layers, ten or twelve calls a program
PASSES = 2


def main() -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = argparse.ArgumentParser()
    p.add_argument("--shapes", default=",".join(SHAPES))
    p.add_argument("--heads", default="2,4,8,16", help="candidate blocks, in heads")
    p.add_argument("--occupancies", default="idle,cell,half,full")
    p.add_argument("--calls", type=int, default=20)
    p.add_argument("--out", default=os.path.join(here, "chiprun_out", "state_step_check.json"))
    args = p.parse_args()

    sys.path.insert(0, here)  # run as `python tools/state_step_check.py`
    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"state_step_check: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    from ray_lightning_tpu.models.ssm import _update_all
    from ray_lightning_tpu.ops.ssm_step import ssm_step_update

    def timed(fn, grab, states, *a):
        """``fn`` donates the states, so each call takes the last one's; ``grab`` keeps what is compared of
        the first call's results before the second call takes them."""
        states, y = fn(states, *a)
        first = grab(states, y)
        states, y = fn(states, *a)
        jax.block_until_ready(y)
        t = time.perf_counter()
        for _ in range(args.calls):
            states, y = fn(states, *a)
        jax.block_until_ready((states, y))
        del states
        return first, (time.perf_counter() - t) / args.calls

    rows = []
    for shape_name in args.shapes.split(","):
        L, B, H, P, N, G, cell_share = SHAPES[shape_name]
        R = H // G

        def program(update, states, decay, dtx, bm, cm, active):
            """``PASSES`` passes over the layers; a call's ``dtx`` waits for the call before it (its ``y`` times
            zero), so that the compiler neither merges nor reorders the calls."""
            states = list(states)
            for _ in range(PASSES):
                for li in range(L):
                    states[li], y = update(states[li], decay, dtx, bm, cm, active)
                    dtx = dtx + y * 0.0
            return tuple(states), y

        def xla_update(state, decay, dtx, bm, cm, active):
            s, y = _update_all(state.reshape(B, G, R, P, N), decay.reshape(B, G, R), dtx.reshape(B, G, R, P), bm, cm, active)
            return s.reshape(state.shape), y.reshape(B, H, P)

        xla = jax.jit(lambda *a: program(xla_update, *a), donate_argnums=0)
        fresh = jax.jit(lambda k: tuple(jax.random.normal(jax.random.fold_in(k, li), (B, H, P, N), jnp.float32) for li in range(L)))
        ks = jax.random.split(jax.random.PRNGKey(H), 4)
        # a decay in (0, 1) and inputs of the state's own size: twelve steps neither grow nor empty the state
        decay = jax.random.uniform(ks[0], (B, H), jnp.float32, 0.5, 1.0)
        dtx = jax.random.normal(ks[1], (B, H, P), jnp.float32)
        bm, cm = (jax.random.normal(k, (B, G, N), jnp.float32) * 0.3 for k in ks[2:])
        for occ in args.occupancies.split(","):
            n_live = round(B * (cell_share if occ == "cell" else SHARES[occ]))
            live_np = np.zeros((B,), bool)
            live_np[np.random.default_rng(len(occ)).permutation(B)[:n_live]] = True
            live = jnp.asarray(live_np)
            operands = (decay, dtx, bm, cm, live)

            def grab(states, y):
                """The first layer's state after its two steps, of two live slots and two others, and the
                last call's y."""
                s = states[0]
                return np.asarray(s[live_np][:2]), np.asarray(s[~live_np][:2]), np.asarray(y)

            before = fresh(jax.random.PRNGKey(7))
            kept = np.asarray(before[0][~live_np][:2])
            want, t_xla = timed(xla, grab, before, *operands)
            base = {"shape": shape_name, "occupancy": occ, "live_slots": n_live, "device": dev.device_kind}
            rows.append(dict(base, form="xla", us_per_layer=t_xla / (PASSES * L) * 1e6))
            print(json.dumps(rows[-1]), flush=True)
            for hb in (int(h) for h in args.heads.split(",")):
                if R % hb:
                    continue
                row = dict(base, form="kernel", heads=hb, block_kib=hb * P * N * 4 // 1024)
                kern = jax.jit(
                    lambda *a, hb=hb: program(lambda *b: ssm_step_update(*b, heads=hb), *a), donate_argnums=0)
                try:
                    got, t = timed(kern, grab, fresh(jax.random.PRNGKey(7)), *operands)
                except Exception as exc:  # noqa: BLE001 - the refusal IS the record
                    row["status"] = "refused"
                    row["error"] = f"{type(exc).__name__}: {exc}"[:2000]
                else:
                    row["status"] = "compiled"
                    row["us_per_layer"] = t / (PASSES * L) * 1e6
                    if n_live:
                        row["state_max_abs_err"] = float(np.abs(got[0] - want[0]).max())
                        row["state_abs_max"] = float(np.abs(want[0]).max())
                        row["y_max_abs_err"] = float(np.abs(got[2] - want[2])[live_np].max())
                        row["y_abs_max"] = float(np.abs(want[2][live_np]).max())
                    if n_live < B:
                        row["others_kept_their_bits"] = bool((got[1] == kept).all() and not got[2][~live_np].any())
                    row["finite"] = bool(np.isfinite(got[2]).all())
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0 if all(r.get("status", "compiled") == "compiled" for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
