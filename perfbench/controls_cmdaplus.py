"""Readings that the command-a-plus-05-2026 session cell's limits are set
from, taken on the chip at the cell's own size. Not part of a benchmark
run.

    python3 perfbench/controls_cmdaplus.py --workload <name> --seeds 1,2 --control-seeds 1

For each seed the program's turns against the reference (the lower
reading; every benchmark run prints the same numbers for its own seed).
For each control seed besides, the reference put in the program's place
with a lower precision (int8) or a planted fault
(`reference_cmdaplus.FAULTS`: RoPE turned on in the full layer, a
sequential block, the shared experts summed), judged by the same
comparison: the tokens it would have served and the
experts it would have routed to, against the sound reference forced to
those experts. A fault stands in the scanned positions only, over the
sound context (the least it can read); the lower precision runs context
and all. Each has to read over a limit. A line of JSON for each;
`PERF.md` has the table.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def run(cell, seeds, control_seeds, modes, devices, make_session=None,
        program=True):
    """Without ``program`` the reference alone: the stand-ins are judged
    on tails of fresh ids (what a turn is fed, at every position), and
    no weights of the program are built."""
    from perfbench import model_cmdaplus as model, reference_cmdaplus as ref
    from perfbench import weights
    from perfbench.controls_session import say, turns
    cfg, t = cell.config, cell.traffic
    for seed in seeds:
        if program:
            session, picks = turns(cell, seed, devices, make_session)
            row = picks[0][1]
            tails, served, experts = model.turns_of(session, picks, row)
            context = session.context()[row]
        else:
            context = weights.token_rows(seed, 0, 1, t["context_len"],
                                         cfg["vocab_size"])[0]
            tails = weights.token_rows(seed, 1, t["checked_requests"],
                                       t["n_new"], cfg["vocab_size"])
        state = ref.context_pass(cfg, seed, context)
        if program:
            free, forced = ref.tails_pass(cfg, seed, state, tails,
                                          [{}, {"forced": experts}])
            say(seed=seed, what="program",
                readings=model.readings_of(served, experts, forced),
                left_to_its_own=model.readings_of(served, experts, free))
        if seed not in control_seeds:
            continue
        plants = [(f"fault {f}", {"fault": f}) for f in ref.FAULTS]
        stood_in = ref.tails_pass(cfg, seed, state, tails,
                                  [kw for _, kw in plants])
        for m in modes:
            plants.append((f"control {m}", {"mode": m}))
            stood_in += ref.tails_pass(
                cfg, seed, ref.context_pass(cfg, seed, context, mode=m),
                tails, [{"mode": m}])
        theirs = [(out["logits"].argmax(-1), out["experts"])
                  for out in stood_in]
        judged = ref.tails_pass(cfg, seed, state, tails,
                                [{"forced": exp} for _, exp in theirs])
        for (what, _), mine, verdict in zip(plants, theirs, judged):
            say(seed=seed, what=what,
                readings=model.readings_of(*mine, verdict))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--modes", default="int8")
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
        program=not args.reference_only)
    return 0


if __name__ == "__main__":
    sys.exit(main())
