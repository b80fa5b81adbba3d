#!/usr/bin/env python3
"""Readings for the limit of ``correct``: one ordinary run of a cell, then
the control on the same served sequences.

  python3 bench/calibrate.py --workload <cell> --seed <n> --seconds <s>

The control is the float32 reference's architecture computed in a lower
precision than the configuration states (``control_picks`` of
``bench/references/<model_type>.py``): its greedy pick at every position of
the sampled prompts and served tokens is judged by the float32 reference as
the served tokens are.  The last stdout line is JSON with both widest gaps;
a limit belongs above the program's readings over a dozen seeds and below
the control's.  The benchmark's own runs never run the control.
"""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def control_gap(cell, state) -> float:
    ref, w, tokens, spans, best = state
    picks = ref.control_picks(cell.conf, w, tokens)
    _, picked = ref.scores(cell.conf, w, tokens, picks)
    return run.widest_gap(best, picked, spans)


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.resolve(spec, args.workload)
    result = run.run(cell, args.seed, args.seconds, False,
                     keep_check_state=True)
    state = result.pop("_state")
    program = result["check"]["max_logit_gap"]["value"]
    control = control_gap(cell, state) if state is not None else None
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "program_gap": program, "control_gap": control,
                      "result": result}), flush=True)


if __name__ == "__main__":
    main()
