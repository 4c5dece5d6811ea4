"""The harness end to end at smoke size on the CPU: a sound run is
correct, and each fault a serving cell can have, planted under the timed
path, makes ``correct`` false.  Also: the command refuses to measure
without a TPU, and BENCHMARK.json names only files that exist."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import harness  # noqa: E402
from repro.models.transformer import LM  # noqa: E402

CELL = "granite-moe.short-poisson"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def smoke_run(cell=CELL, seed=2**31 + 21, seconds=2.0):
    return harness.run(cell, seed, seconds, False, t_start=time.perf_counter(),
                       root=ROOT, require_tpu=False, smoke=True)


def test_sound_run_is_correct_and_well_formed():
    r = smoke_run()
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"] and list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    cell = harness.load_cell(ROOT, CELL)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert all(c["value"] <= c["limit"] for n, c in r["checks"].items()
               if n != "served_tokens_checked")
    assert r["checks"]["served_tokens_checked"]["value"] > 50


def _state_unchanged(orig):
    def step(self, params, tokens, caches, *a):
        logits, _ = orig(self, params, tokens, caches, *a)
        return logits, caches                  # the KV write is lost
    return step


def _half_batch(orig):
    def step(self, params, tokens, caches, bt, q_start, n_valid):
        half = tokens.shape[0] // 2
        logits, caches = orig(self, params, tokens, caches, bt, q_start,
                              n_valid.at[half:].set(0))
        # Rows of the left-out half answer with the rest's logits.
        return jnp.concatenate([logits[:half], logits[:half]])[
            :tokens.shape[0]], caches
    return step


def _token_altered(orig):
    def step(self, *a):
        logits, caches = orig(self, *a)
        return jnp.roll(logits, 1, axis=-1), caches
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_under_the_timed_path_is_caught(monkeypatch, fault, cell):
    monkeypatch.setattr(LM, "paged_step", fault(LM.paged_step))
    load = harness.load_cell

    def busier(root, name):
        # The smoke model steps far faster than the chip serves the real
        # one: offer more load so that every slot of the batch is used.
        c = load(root, name)
        if c.mix["arrival"]["kind"] == "poisson":
            c.mix = dict(c.mix, arrival=dict(c.mix["arrival"],
                                             rate_per_s=40.0))
        return c

    monkeypatch.setattr(harness, "load_cell", busier)
    r = smoke_run(cell)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for n, c in r["checks"].items()
               if n != "served_tokens_checked")


def _cmd(cwd, env_extra=None, workload=CELL):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _cmd(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no result" in p.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cmd(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_names_only_files_that_exist():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    here = os.path.join(ROOT, "benchmarks", "chip")
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(ROOT, c["file"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert os.path.isfile(os.path.join(here, "traffic",
                                           f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(here, "cells",
                                           f"{w['name']}.json"))
        cell = harness.load_cell(ROOT, w["name"])
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert hasattr(harness.load_reader(m["name"]), "read")
    for m in bench["per_layer"]:
        for w in m.get("workloads", []):
            cell = harness.load_cell(ROOT, w)
            assert m["moves"] in {x["name"] for x in cell.end_to_end}
        assert m["moves"] in e2e
