"""Readings that the `session` cells' limits are set from, taken on the
chip at a cell's own size. Not part of a benchmark run.

    python3 perfbench/controls_session.py --workload <name> --seeds 1,2 --control-seeds 1

For each seed the program's turns against the reference (the lower
reading). For each control seed besides, the reference put in the
program's place with a lower precision or a planted fault, judged by
the same comparison: the tokens it would have chosen at the served
positions and, for DeepSeek-V3.2-Exp, the cache positions it would
have read and the experts it would have routed to (the fault stands in
the scanned positions only, over the sound context: the least it can
read). Each has to read over a limit. A line of JSON for each;
`PERF.md` has the table.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_T0 = time.perf_counter()


def say(**row):
    print(json.dumps(dict(row, at_s=round(time.perf_counter() - _T0, 1))),
          flush=True)


def turns(cell, seed, devices, make_session=None):
    """A session after its checked turns, and the (request, row) pairs
    to compare."""
    from perfbench.drivers import session as drv
    session = (make_session or drv.Session)(cell, seed, devices)
    session.prepare()
    picks = session.checked(1)
    session.free()
    return session, picks


def dsv32(cell, seeds, control_seeds, modes, devices, make_session=None,
          program=True):
    """Without ``program`` the reference alone: the stand-ins are judged
    on tails of fresh ids (what a turn is fed, at every position), and
    no weights of the program are built."""
    from perfbench import model_dsv32 as model, reference_dsv32 as ref
    from perfbench import weights
    cfg, t, k = cell.config, cell.traffic, cell.config["index_topk"]
    for seed in seeds:
        if program:
            session, picks = turns(cell, seed, devices, make_session)
            row = picks[0][1]
            _, tails, served, selected, experts = model.turns_of(
                session, picks, row)
            context = session.context()[row]
        else:
            context = weights.token_rows(seed, 0, 1, t["context_len"],
                                         cfg["vocab_size"])[0]
            tails = weights.token_rows(seed, 1, t["checked_requests"],
                                       t["n_new"], cfg["vocab_size"])
        state = ref.context_pass(cfg, seed, context)
        if program:
            free, forced = ref.tails_pass(
                cfg, seed, state, tails,
                [{}, {"forced": (selected, experts)}])
            say(seed=seed, what="program",
                readings=model.readings_of(served, selected, experts, forced),
                left_to_its_own=model.readings_of(served, selected, experts,
                                                  free))
        if seed not in control_seeds:
            continue
        # the reference with a planted fault in the program's place, over
        # the sound context; then with a lower precision, context and all
        # (what such a program would have prefilled): what each serves,
        # reads and routes, judged as the program is
        plants = [(f"fault {f}", {"fault": f}) for f in ref.FAULTS]
        stood_in = ref.tails_pass(cfg, seed, state, tails,
                                  [kw for _, kw in plants])
        for m in modes:
            plants.append((f"control {m}", {"mode": m}))
            stood_in += ref.tails_pass(
                cfg, seed, ref.context_pass(cfg, seed, context, mode=m),
                tails, [{"mode": m}])
        theirs = [(out["logits"].argmax(-1),
                   ref.mask_positions(out["selected"], k), out["experts"])
                  for out in stood_in]
        judged = ref.tails_pass(
            cfg, seed, state, tails,
            [{"forced": (sel, exp)} for _, sel, exp in theirs])
        for (what, _), mine, verdict in zip(plants, theirs, judged):
            say(seed=seed, what=what,
                readings=model.readings_of(*mine, verdict))


def mistral(cell, seeds, control_seeds, modes, devices):
    import numpy as np

    from perfbench import compare, reference
    t = cell.traffic
    for seed in seeds:
        session, picks = turns(cell, seed, devices)
        context = session.context()
        rows = np.stack([np.concatenate(
            [context[row], session.fed(r)[row:row + 1],
             session.outputs[r][row]]) for r, row in picks])
        use = modes if seed in control_seeds else ()
        gaps = reference.decode_logit_gaps(cell.config, seed, rows,
                                           t["context_len"] + 1, modes=use)
        say(seed=seed, what="program",
            readings=compare.decode(gaps["served"]))
        for mode in use:
            say(seed=seed, what=f"control {mode}",
                readings=compare.decode(gaps[mode]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--modes", default="int8,fp8")
    ap.add_argument("--reference-only", action="store_true",
                    help="DeepSeek: the controls on tails of fresh ids, "
                         "without the program")
    args = ap.parse_args()
    from perfbench import harness
    cell = harness.Cell(args.workload)
    harness.place_compile_cache()
    devices = harness.require_chips(cell.chips)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    run, kw = mistral, {}
    if cell.config.get("session_model") == "model_dsv32":
        run, kw = dsv32, {"program": not args.reference_only}
    run(cell, ints(args.seeds), set(ints(args.control_seeds)),
        [m for m in args.modes.split(",") if m], devices, **kw)
    return 0


if __name__ == "__main__":
    sys.exit(main())
