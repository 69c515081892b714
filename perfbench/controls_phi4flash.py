"""Readings that the Phi-4-mini-flash-reasoning session cell's limits
are set from, taken on the chip at the cell's own size. Not part of a
benchmark run.

    python3 perfbench/controls_phi4flash.py --workload <name> --seeds 1,2 --control-seeds 1

For each seed the program's turns against the reference (the lower
reading; every benchmark run prints the same numbers for its own seed).
For each control seed besides, the reference put in the program's place
with a lower precision (context and all) or a planted fault
(`reference_phi4flash.FAULTS`, in the scanned positions only, over the
sound context: the least it can read), judged by the same comparison:
the tokens it would have served at those positions against the sound
reference's logits. Each has to read over a limit. A line of JSON for
each; `PERF.md` has the table.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def run(cell, seeds, control_seeds, modes, devices, make_session=None,
        program=True, faults=None):
    """Without ``program`` the reference alone: the stand-ins are judged
    on tails of fresh ids (what a turn is fed, at every position), and
    no weights of the program are built."""
    import numpy as np

    from perfbench import compare, model_phi4flash as model
    from perfbench import reference_phi4flash as ref, weights
    from perfbench.controls_session import say, turns
    cfg, t = cell.config, cell.traffic
    n = t["n_new"]
    for seed in seeds:
        if program:
            session, picks = turns(cell, seed, devices, make_session)
            rows, served = model.rows_of(session, picks)
        else:
            context = weights.token_rows(seed, 0, 1, t["context_len"],
                                         cfg["vocab_size"])[0]
            tails = weights.token_rows(seed, 1, t["checked_requests"], n,
                                       cfg["vocab_size"])
            rows = np.stack([np.concatenate([context, tail])
                             for tail in tails])
        sound = np.stack([ref.forward(cfg, seed, row, n, quiet=i > 0)
                          for i, row in enumerate(rows)])
        if program:
            say(seed=seed, what="program", readings=compare.decode(
                ref.logit_gaps(sound, served)))
        if seed not in control_seeds:
            continue
        plants = [(f"fault {f}", {"fault": f})
                  for f in (ref.FAULTS if faults is None else faults)]
        plants += [(f"control {m}", {"mode": m}) for m in modes]
        for what, kw in plants:
            theirs = np.stack([ref.forward(cfg, seed, row, n, quiet=True,
                                           **kw).argmax(-1) for row in rows])
            say(seed=seed, what=what, readings=compare.decode(
                ref.logit_gaps(sound, theirs)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--modes", default="int8")
    ap.add_argument("--faults", default=None,
                    help="a comma-separated subset of the planted faults")
    ap.add_argument("--reference-only", action="store_true",
                    help="the controls on tails of fresh ids, without the "
                         "program")
    args = ap.parse_args()
    from perfbench import harness
    cell = harness.Cell(args.workload)
    harness.place_compile_cache()
    devices = harness.require_chips(cell.chips)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    run(cell, ints(args.seeds), set(ints(args.control_seeds)),
        [m for m in args.modes.split(",") if m], devices,
        program=not args.reference_only,
        faults=None if args.faults is None else args.faults.split(","))
    return 0


if __name__ == "__main__":
    sys.exit(main())
