"""Traffic kind `decode`: one caller, back to back, each request a fresh
(batch, prompt_len) prompt from the seed through the program's
`greedy_decode(use_prefill=True)`, waiting for the returned tokens."""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import compare, harness, reference, weights
from perfbench.drivers.train import program_config


class Decoder:
    def __init__(self, cell, seed: int, devices: list):
        from lua_mapreduce_tpu.models import transformer as tfm
        self.cfg, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.params = weights.make_params(self.cfg, seed)
        self.program_cfg = program_config(self.cfg)
        self.decode = tfm.greedy_decode
        self.outputs = {}

    def prompt(self, index: int) -> np.ndarray:
        t = self.traffic
        return weights.token_rows(self.seed, index, t["batch"],
                                  t["prompt_len"], self.cfg["vocab_size"])

    def one(self, index: int):
        prompt = jax.device_put(jnp.asarray(self.prompt(index)))

        def call():
            out = self.decode(self.params, prompt, self.traffic["n_new"],
                              cfg=self.program_cfg, use_prefill=True)
            self.outputs[index] = jax.block_until_ready(out)
        return call

    def free(self):
        self.params = None


def sample(seed: int, finished: list, requests: int, rows: int,
           batch: int) -> list:
    """(request, row) pairs to compare, drawn from the seed among the
    requests that the window finished. All requests are of one length, so
    the longest is in any sample."""
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    chosen = rng.choice(finished, size=min(requests, len(finished)),
                        replace=False)
    return [(int(r), int(row)) for r in sorted(chosen)
            for row in sorted(rng.choice(batch, size=min(rows, batch),
                                         replace=False))]


def run(cell, seed: int, seconds: float, trace: bool, devices: list,
        clock, log, make_decoder=Decoder, work_dir=None) -> tuple:
    t = cell.traffic
    decoder = make_decoder(cell, seed, devices)
    jax.block_until_ready(decoder.params)
    print(f"set-up: weights by {clock():.2f} s", file=sys.stderr)
    decoder.one(0)()
    setup_s = clock()
    built_before = log.programs

    def one(i):
        return decoder.one(1 + i)

    if trace:
        loop, trace_file = harness.traced(
            lambda: harness.measured_loop(one, float("inf"),
                                          at_most=t["traced_calls"]),
            work_dir)
    else:
        loop, trace_file = harness.measured_loop(one, seconds), None
    built = log.programs - built_before
    requests = len(loop["times"])
    peak = harness.memory_peak_bytes(devices)
    decoder.free()
    print(f"requests {requests} (the 95th percentile is of {requests} "
          f"samples) window_s {loop['window_s']:.4f} of which feeding "
          f"{loop['window_s'] - sum(loop['times']):.4f} programs built in the "
          f"window {built}", file=sys.stderr)

    total = t["prompt_len"] + t["n_new"]
    picks = sample(seed, list(range(1, 1 + requests)), t["checked_requests"],
                   t["checked_rows"], t["batch"])
    outputs = {r: np.asarray(decoder.outputs[r]) for r, _ in picks}
    failed = sum(1 for r in range(1, 1 + requests)
                 if decoder.outputs[r].shape != (t["batch"], total))
    rows = np.stack([outputs[r][row] for r, row in picks])
    sent = np.stack([decoder.prompt(r)[row] for r, row in picks])
    t0 = time.perf_counter()
    gaps = reference.decode_logit_gaps(cell.config, seed, rows,
                                       t["prompt_len"])
    print(f"reference took {time.perf_counter() - t0:.2f} s", file=sys.stderr)
    readings = compare.decode(gaps["served"])
    readings["prompt_echo_mismatches"] = float(
        np.sum(rows[:, :t["prompt_len"]] != sent))
    print(f"compared {gaps['served'].size} served tokens of requests "
          f"{sorted({r for r, _ in picks})}", file=sys.stderr)
    measured = {
        "serve_tokens_per_s": requests * t["batch"] * t["n_new"]
        / loop["window_s"],
        "request_p95_ms": 1e3 * harness.percentile_nearest_rank(
            loop["times"], 95),
        "setup_s": setup_s,
    }
    context = {"cell": cell, "loop": loop, "calls": requests,
               "programs_built": built, "trace_file": trace_file,
               "chips": len(devices), "device": devices[0]}
    return measured, context, readings, {"attempted": requests,
                                         "failed": failed,
                                         "memory_peak_bytes": peak}
