"""The uniform layer's programs, mode by mode, against the record taken at
PR 49's tree (``tests/data/uniform_outputs_pr49.json``) before the layer's
parts were written once (``models/layers.py``, PR 50): the outputs of every
mode of ``models/gpt.py``, to the bit, on the CPU. A change to the record is
a change to what a uniform configuration computes, and says so in its PR."""
import json
import os

import pytest

from tests.utils import UNIFORM_PROGRAMS, uniform_program_hashes

RECORD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "uniform_outputs_pr49.json")


@pytest.mark.parametrize("case,mode", UNIFORM_PROGRAMS, ids=["-".join(p) for p in UNIFORM_PROGRAMS])
def test_a_uniform_program_puts_out_the_bytes_it_put_out_at_pr49(case, mode):
    with open(RECORD) as f:
        want = json.load(f)[case][mode]
    assert uniform_program_hashes(case, mode) == want
