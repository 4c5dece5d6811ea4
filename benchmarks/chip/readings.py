"""The readings a cell's correctness limit is set from, on the chip.

    python3 benchmarks/chip/readings.py --workload <cell> --seconds <s> \\
        --seeds 1 2 ... 12 --control-seeds 1 2 3

For each seed, in one process: weights from the seed, the cell's window
at its own load, the same seeded sample of finished requests a run
compares, and the float32 reference over their prompts and served tokens.
It prints one JSON line per seed with the gaps of the served tokens (the
lower reading is the largest over the seeds) and, for control seeds, the
gaps of the tokens each control puts first at the same positions (the
upper reading is the smallest), and whether each would be ``correct``
under the cell file's limits (``harness.compare``).  The control is the reference with every
weight product rounded to int8 per tensor (``Reference(int8=True)``), the
step below the ``bf16_serve`` the configurations state: the program's own
``int8`` policy does not compile at these cells' shapes.  The
benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def gap_stats(who: str, gaps) -> dict:
    """The widest gap and, beside it, how the gaps are spread."""
    import numpy as np
    return {f"{who}_gap_sd": float(gaps.max()),
            f"{who}_gap_p99_sd": float(np.percentile(gaps, 99)),
            f"{who}_gap_mean_sd": float(gaps.mean()),
            f"{who}_off_best": int((gaps > 0).sum())}


def readings(workload: str, seeds, control_seeds, seconds: float, *,
             root: str = ROOT, require_tpu: bool = True, smoke: bool = False,
             emit=print):
    import jax.numpy as jnp
    from benchmarks.chip import harness
    from benchmarks.chip.reference import Reference
    from benchmarks.chip.traffic import Traffic
    cell = harness.load_cell(root, workload)
    _, compiles = harness.start(cell, require_tpu)
    program = harness.Program(cell, smoke=smoke)
    out = []
    for seed in list(dict.fromkeys(list(seeds) + list(control_seeds))):
        program.load(seed)
        traffic = Traffic(cell.mix, program.arch.vocab, seed)
        res = harness.serve_window(program, traffic, seconds, compiles)
        sample = harness.sample_requests(res.driver.served,
                                         cell.check["sample_requests"], seed)
        tokens, pos, chosen, mask = harness.teacher_batch(
            sample, traffic, cell.engine["max_len"])
        program.eng = res.driver = None
        rec = {"workload": workload, "seed": seed,
               "requests_checked": len(sample),
               "tokens_checked": int(mask.sum())}
        program.free()
        logits = harness.reference_logits(program.conf, seed, tokens, pos)
        gaps = harness.gaps_of(logits, chosen, mask)
        rec.update(gap_stats("program", gaps))
        rec["program_correct"] = harness.verdict(
            harness.compare(gaps, cell.check))
        if seed in control_seeds:
            low = Reference(program.conf, harness.seed_key(seed), int8=True)
            first = jnp.argmax(low.logits_at(tokens, pos), axis=-1)
            gaps = harness.gaps_of(logits, first.astype(jnp.int32), mask)
            rec.update(gap_stats("control", gaps))
            rec["control_correct"] = harness.verdict(
                harness.compare(gaps, cell.check))
        emit(json.dumps(rec))
        out.append(rec)
        del logits
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import harness
    try:
        readings(args.workload, args.seeds, args.control_seeds, args.seconds,
                 emit=lambda line: print(line, flush=True))
    except harness.NoChip as e:
        print(f"[chip-bench] {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
