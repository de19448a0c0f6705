"""compiles_in_window.serve: stats()['compiles_since_init'] at window end minus at window start; expected 0."""


def read(ctx):
    p = ctx["program"]
    return float(p["stats1"]["compiles_since_init"] - p["stats0"]["compiles_since_init"])
