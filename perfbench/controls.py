"""Readings that the limits are set from, taken on the chip at a cell's
own size, many seeds in one process. Not part of a benchmark run.

    python3 perfbench/controls.py --workload <name> --seeds 1,2,3 --control-seeds 1,2,3

For each seed: the program's numbers against the reference (the lower
reading). For each control seed besides: the reference computed in lower
precision and put in the program's place (the control, which has to fail),
and for a training cell the faults of a step that leaves half its rows out
(or never sums the gradients of a data-parallel pair) and, on a mesh, of a
ring that leaves its exchange of keys out.
A line of JSON for each; `PERF.md` has the table.
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


def train(cell, seeds, control_seeds, modes, devices):
    from perfbench import compare, reference
    from perfbench.drivers import train as drv
    followed = cell.traffic["followed_steps"]
    for seed in seeds:
        trainer = drv.Trainer(cell, seed, devices)
        prog = drv.first_steps(trainer, followed)
        batches = [trainer.batch(n) for n in range(followed)]
        adam = trainer.adam
        trainer.free()
        del trainer
        ref = reference.train_readings(cell.config, seed, batches, adam)
        readings, notes = compare.train(prog, ref)
        say(seed=seed, what="program", readings=readings, notes=notes,
            losses=[prog["loss"], ref["loss"]])
        if seed not in control_seeds:
            continue
        for mode in modes:
            low = reference.train_readings(cell.config, seed, batches, adam,
                                           mode=mode)
            say(seed=seed, what=f"control {mode}",
                readings=compare.train(low, ref)[0])
        half = reference.train_readings(cell.config, seed, batches, adam,
                                        half_batch=True)
        say(seed=seed, what="fault half_batch",
            readings=compare.train(half, ref)[0])
        if cell.traffic["sp"] > 1:
            alone = reference.train_readings(
                cell.config, seed, batches, adam,
                attn_blocks=cell.traffic["sp"])
            say(seed=seed, what="fault no_exchange",
                readings=compare.train(alone, ref)[0])


def decode(cell, seeds, control_seeds, modes, devices):
    import jax
    import numpy as np

    from perfbench import compare, reference
    from perfbench.drivers import decode as drv
    t = cell.traffic
    for seed in seeds:
        decoder = drv.Decoder(cell, seed, devices)
        n = max(2, t["checked_requests"])
        for i in range(n + 1):
            decoder.one(i)()
        picks = drv.sample(seed, list(range(1, n + 1)), t["checked_requests"],
                           t["checked_rows"], t["batch"])
        rows = np.stack([np.asarray(decoder.outputs[r])[row]
                         for r, row in picks])
        q8_rows = None
        if seed in control_seeds:
            # the program's own lower-precision path: int8 weights and cache
            from lua_mapreduce_tpu.models import transformer as tfm
            q8 = tfm.quantize_lm(decoder.params)
            decoder.params = None
            outs = {r: np.asarray(jax.block_until_ready(tfm.greedy_decode(
                q8, jax.numpy.asarray(decoder.prompt(r)), t["n_new"],
                cfg=decoder.program_cfg, use_prefill=True, kv_q8=True)))
                for r in sorted({r for r, _ in picks})}
            q8_rows = np.stack([outs[r][row] for r, row in picks])
            del q8
        decoder.free()
        del decoder
        use = modes if seed in control_seeds else ()
        gaps = reference.decode_logit_gaps(cell.config, seed, rows,
                                           t["prompt_len"], modes=use)
        served = gaps["served"]
        say(seed=seed, what="program", readings=compare.decode(served),
            tokens=int(served.size),
            quantiles=[float(np.quantile(served, q))
                       for q in (0.5, 0.9, 0.99, 0.999)],
            nonzero=int(np.sum(served > 0)))
        for mode in use:
            say(seed=seed, what=f"control {mode}",
                readings=compare.decode(gaps[mode]),
                nonzero=int(np.sum(gaps[mode] > 0)))
        if q8_rows is not None:
            g = reference.decode_logit_gaps(cell.config, seed, q8_rows,
                                            t["prompt_len"])["served"]
            say(seed=seed, what="control program q8 path",
                readings=compare.decode(g), nonzero=int(np.sum(g > 0)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--modes", default="int8,fp8")
    args = ap.parse_args()
    from perfbench import harness
    cell = harness.Cell(args.workload)
    harness.place_compile_cache()
    devices = harness.require_chips(cell.chips)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    {"train": train, "decode": decode}[cell.traffic["kind"]](
        cell, ints(args.seeds), set(ints(args.control_seeds)),
        [m for m in args.modes.split(",") if m], devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
