"""A new kind of cell, added as a file: score seeded rows with the
program's plain forward pass inside an actor (the parent never touches
JAX), for as long as the window lasts; the mean loss of the first batch
is held against the reference's."""
import time

from pb import traffic, weights
from pb.harness import check_line, run_reference, say, teardown


class Scorer:
    def __init__(self, bench):
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.models.gpt import GPTConfig, gpt_forward

        cfg = GPTConfig(**bench["program_config"])
        self.params = weights.make_params(bench["seed"], bench["dims"], cfg.max_seq, "float32")

        def loss(params, rows):
            lg = gpt_forward(params, rows[:, :-1], cfg)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            return jnp.mean(lse - jnp.take_along_axis(lg, rows[:, 1:, None], axis=-1)[..., 0])

        self.loss = jax.jit(loss)
        d = jax.devices()[0]
        self.device = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}

    def score(self, rows):
        return float(self.loss(self.params, rows))

    def info(self):
        return self.device


def run(ctx):
    import numpy as np

    from ray_lightning_tpu import fabric

    mix, dims = ctx["mix"], ctx["dims"]
    rows = traffic.fake_text(int(mix["rows"]), int(mix["seq"]), dims["vocab"], ctx["seed"])
    np.save(ctx["out_dir"] + "/rows.npy", rows)
    env = {"JAX_PLATFORMS": "cpu"} if ctx["rehearse"] else {}
    actor = fabric.remote(Scorer).options(num_cpus=1, env=env).remote(
        {"seed": ctx["seed"], "dims": dims, "program_config": ctx["config"]["program_config"]})
    first = fabric.get(actor.score.remote(rows))  # warms the one shape up
    t0, n = time.time(), 0
    while time.time() - t0 < ctx["seconds"]:
        fabric.get(actor.score.remote(rows))
        n += 1
    window = time.time() - t0
    device = fabric.get(actor.info.remote())
    leftovers = teardown()
    ref = run_reference(ctx, {"kind": "forward"})
    checks = []
    gap = abs(first - ref["reference"]["loss"])
    check_line(checks, "loss_abs", gap, ctx["limits"]["loss_abs"], gap <= ctx["limits"]["loss_abs"])
    say(f"window: {n} batches in {window:.2f} s; leftovers: {leftovers}")
    return {
        "numbers": {"loss_abs": gap}, "checks": checks, "reference": ref,
        "e2e": {"forward_tokens_per_s": n * rows.shape[0] * int(mix["seq"]) / window, "setup_s": t0 - ctx["t_start"]},
        "program": {"batches": n, "window_s": window}, "attempted": n, "failed": 0,
        "device": device, "memory_peak_bytes": 0, "trace": None, "between": "",
    }
