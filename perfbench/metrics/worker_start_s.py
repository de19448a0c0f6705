"""worker_start_s: Benchmark clock: Trainer.fit / replica spawn until the first callback inside the worker / the replica's engine is built."""


def read(ctx):
    p = ctx["program"]
    if "worker_ready_wall" in p:  # a fit: Trainer.fit called -> first callback inside the worker
        return p["worker_ready_wall"] - p["fit_call_wall"]
    return p["info1"]["ready_wall"] - p["spawn_wall"]  # a replica: spawn -> engine built
