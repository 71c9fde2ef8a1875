"""End-to-end behaviour tests for the paper's system: characterize (real
execution) -> fit -> route -> serve, on reduced models."""

import numpy as np
import pytest

from repro.launch.serve import characterize, serve

pytestmark = pytest.mark.slow  # real-execution pipelines, minutes of compile


def test_end_to_end_serve_pipeline():
    out = serve(["llama2-7b-reduced", "llama2-70b-reduced"],
                n_queries=8, zeta=0.5, batch_size=4)
    totals = out["totals"]
    assert sum(t["queries"] for t in totals.values()) >= 8
    served_energy = sum(t["energy_j"] for t in totals.values())
    assert served_energy > 0
    # the routing plan objective is finite and the assignment covers all
    asg = out["plan"].assignment
    assert np.isfinite(asg.objective)
    assert asg.counts().sum() == 8


def test_characterization_produces_usable_fits():
    p, compiles = characterize("llama2-7b-reduced", max_tokens=32)
    assert compiles["compiles"] > 0 and compiles["compile_s"] > 0
    # real CPU wall-clock data is noisy at this scale; the fit must still
    # be strongly explanatory (the paper's full-scale fits are > 0.96)
    assert p.runtime.r_squared > 0.7
    assert p.energy.r_squared > 0.7
    # cost surfaces must increase with tokens
    assert p.runtime(64, 64) > p.runtime(8, 8)
