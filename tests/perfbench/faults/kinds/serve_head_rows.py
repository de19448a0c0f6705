"""A planted fault: the output head's rows are shifted by one, so every
token is produced under its neighbour's id."""
from kinds import serve as base


class Shifted(base.BenchReplica):
    def make_params(self, bench, max_seq):
        import jax.numpy as jnp

        params = super().make_params(bench, max_seq)
        head = "wte" if bench["dims"]["tied"] else "lm_head"
        params[head] = jnp.roll(params[head], 1, axis=0)
        return params


def run(ctx):
    return base.run(ctx, replica_cls=Shifted)
