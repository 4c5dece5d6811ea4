"""The control of each cell's correctness check, at smoke size on the CPU:
the reference with int8 weight products (the precision below the bf16 the
configurations state), read at the same prompts and served tokens, fails
one of the cell's limits, while the program as configured passes them.
An open-loop mix is offered at a higher rate than on the chip, so that the
smoke model, which steps far faster, serves a full batch in the window."""
import json
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from benchmarks.chip import harness, readings  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]

# readings.gap_stats name -> the cell file's limit key
STATS = {"gap_sd": "max_logit_gap_sd", "gap_mean_sd": "mean_logit_gap_sd"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(monkeypatch, cell):
    load = harness.load_cell

    def busier(root, name):
        c = load(root, name)
        if c.mix["arrival"]["kind"] == "poisson":
            c.mix = dict(c.mix, arrival=dict(c.mix["arrival"],
                                             rate_per_s=40.0))
        return c

    monkeypatch.setattr(harness, "load_cell", busier)
    limits = harness.load_cell(ROOT, cell).check
    compared = {s: k for s, k in STATS.items() if k in limits}
    assert compared
    (r,) = readings.readings(cell, [13], [13], 3.0, root=ROOT,
                             require_tpu=False, smoke=True,
                             emit=lambda line: None)
    assert r["tokens_checked"] > 50
    for stat, key in compared.items():
        assert r[f"program_{stat}"] <= limits[key], stat
    assert any(r[f"control_{stat}"] > limits[key]
               for stat, key in compared.items())
    # The same verdict the harness gives a run (harness.compare).
    assert r["program_correct"] is True and r["control_correct"] is False
