"""A planted fault: the train kind with a step that returns its state unchanged."""
from kinds import train as base


class Frozen(base.Tap):
    def dispatch(self, step, params, opt_state, payload, rng, step_idx):
        import jax
        import jax.numpy as jnp

        keep = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        out = step(params, opt_state, payload, rng, step_idx)
        return (keep[0], keep[1], out[2])


def run(ctx):
    return base.run(ctx, tap=Frozen)
