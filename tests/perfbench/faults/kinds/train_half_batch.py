"""A planted fault: the second half of every batch is left out (the first half is fed twice)."""
from kinds import train as base


class HalfBatch(base.Tap):
    def dispatch(self, step, params, opt_state, payload, rng, step_idx):
        import jax
        import jax.numpy as jnp

        fed = jax.tree_util.tree_map(lambda x: jnp.concatenate([x[:, : x.shape[1] // 2]] * 2, axis=1), payload)
        return step(params, opt_state, fed, rng, step_idx)


def run(ctx):
    return base.run(ctx, tap=HalfBatch)
