"""Sweep the arrival rate of an open-loop cell to find its knee, on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \\
        --rates 1 1.5 2 2.5 3

Runs the cell's window once per rate (the cell's mix with only the rate
changed, one seed, fresh weights and engine each time) and prints one
JSON line per rate: requests due and finished, tokens/s, the time to
first token of the first and the second half of the due requests, and
the backlog left at the window's close.  The knee is the highest rate
whose backlog stays bounded (second-half TTFT no worse than the first's
by much, no growing queue); a cell is then set at about 0.8 of it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def sweep(workload: str, rates, seconds: float, seed: int, *,
          root: str = ROOT, require_tpu: bool = True, smoke: bool = False,
          emit=print):
    import numpy as np
    from benchmarks.chip import harness
    from benchmarks.chip.traffic import Traffic
    cell = harness.load_cell(root, workload)
    _, compiles = harness.start(cell, require_tpu)
    program = harness.Program(cell, smoke=smoke)
    for rate in rates:
        program.load(seed)
        mix = dict(cell.mix, arrival=dict(cell.mix["arrival"],
                                          rate_per_s=rate))
        traffic = Traffic(mix, program.arch.vocab, seed)
        drv = harness.Driver(program.eng, traffic, cell)
        win = drv.run(seconds, None)
        backlog = program.eng.pending
        due = sorted((r for r in drv.served.values()
                      if r.due < win.deadline), key=lambda r: r.due)
        drv.drain(lambda: all(r.done for r in due), harness.DRAIN_LIMIT_S)

        def ttft(rs):
            t = [drv.steps[r.first_step()].t_end - r.due for r in rs
                 if r.done]
            return float(np.percentile(t, 90)) * 1e3 if t else None

        half = len(due) // 2
        tokens = sum(s.tokens for s in drv.steps[:win.n_steps])
        emit(json.dumps({
            "workload": workload, "rate_per_s": rate, "due": len(due),
            "finished": sum(r.done for r in due),
            "tokens_per_s": tokens / (win.end - win.t0),
            "ttft_p90_ms_first_half": ttft(due[:half]),
            "ttft_p90_ms_second_half": ttft(due[half:]),
            "pending_at_close": backlog,
            "compiles": compiles["n"]}))
        program.free()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import harness
    try:
        sweep(args.workload, args.rates, args.seconds, args.seed,
              emit=lambda line: print(line, flush=True))
    except harness.NoChip as e:
        print(f"[chip-bench] {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
