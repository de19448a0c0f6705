"""The reference's side of the ``forward`` kind: the family's loss over the same rows."""
import os

import numpy as np

from pb import reference, weights


def run(spec, out_dir, devs):
    rows = np.load(os.path.join(out_dir, "rows.npy"))
    params = weights.make_params(spec["seed"], spec["dims"], rows.shape[1] - 1, "float32")
    return {"reference": {"loss": float(reference.lm_loss(params, rows, spec["dims"]))}}
