"""The fp8 control of the serving cell fails its limit: the plain reference
with every matmul operand rounded to float8_e4m3 puts first tokens whose
float32 reference logits lie further below the best than the limit allows.

Run with the reference alone at the published widths, but 4 layers and a
16,384-row slice of the vocabulary, so that a CPU test run holds it.
"""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import numpy as np
import pytest

from chipbench.files import load_benchmark, load_module, resolve_cell

CELL = resolve_cell(load_benchmark(), "qwen05.serve")
REF = load_module(CELL.reference_path)
SMALL = dict(CELL.config, num_hidden_layers=4, vocab_size=16384)


@pytest.mark.parametrize("seed", [3501, 3502, 3503])
def test_fp8_control_fails_the_limit(seed):
    params = REF.make_params(SMALL, seed)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, SMALL["vocab_size"], (2, 128), dtype=np.int32)
    served = rng.integers(0, SMALL["vocab_size"], (2, 64), dtype=np.int32)
    logits = REF.logits_for(SMALL, params, prompts, served)
    low = REF.logits_for(SMALL, params, prompts, served, quant=True)
    gap = REF.served_gaps(logits, np.asarray(low).argmax(axis=-1)).max()
    assert gap > CELL.config["limits"]["served_logit_gap"]
    # the reference's own first tokens lie nowhere below its best
    assert REF.served_gaps(logits, np.asarray(logits).argmax(axis=-1)).max() == 0.0
