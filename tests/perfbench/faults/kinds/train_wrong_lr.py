"""A planted fault: the optimizer runs at 0.7 of the learning rate the mix states."""
from kinds import train as base


class WrongLr(base.BenchGPTLM):
    def __init__(self, *args, lr, **kw):
        super().__init__(*args, lr=0.7 * lr, **kw)


def run(ctx):
    return base.run(ctx, module_cls=WrongLr)
