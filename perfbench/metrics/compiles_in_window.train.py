"""compiles_in_window.train: Backend compiles the obs/jaxmon listener counted between window start and end; expected 0."""


def read(ctx):
    w = ctx["program"].get("window")
    return None if not w else float(w["compiles"])
