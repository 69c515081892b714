"""Traffic kind `session`: further turns on a cache that is resident.

Set-up builds the weights, runs the program's `prefill` over `batch` x
`context_len` ids from the seed and keeps the caches on the device. A
request is one turn for all `batch` sessions: at position `context_len`
every row is fed a fresh id drawn from (seed, request), the program's
session entry (`decode_from`) scans `n_new` positions and returns a
greedy token for each; the caller waits for the tokens. One caller,
back to back. A turn writes positions >= `context_len` only, so the
prepared cache is the same for every turn.

The model family's files (weights, the program's configuration, the
comparison) are a module that the configuration names under
`session_model` (`perfbench/model_mistral.py` where it names none): a
new family is a new module. The program's entry is imported here, at
the top: a program without it fails at import, before it touches the
chip.

The window runs the program as its users call it. The turns that are
compared come after it: `checked_requests` further turns of the same
session, with `decode_from(stats=True)` where the family's comparison
reads the program's counters, which is another compiled program.
"""

from __future__ import annotations

import importlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from lua_mapreduce_tpu.models.transformer import (decode_caches,
                                                  decode_from, prefill)
from perfbench import harness, scope_reader, session_scopes, weights


def model_of(cfg: dict):
    return importlib.import_module(
        "perfbench." + cfg.get("session_model", "model_mistral"))


class Session:
    """Weights, the prepared caches and the turn."""

    def __init__(self, cell, seed: int, devices: list):
        self.cfg, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.model = model_of(self.cfg)
        self.params = self.model.make_params(self.cfg, seed)
        self.program_cfg = self.model.program_config(self.cfg)
        self.caches = None
        self.outputs, self.counters = {}, {}

    def context(self) -> np.ndarray:
        t = self.traffic
        return weights.token_rows(self.seed, 0, t["batch"], t["context_len"],
                                  self.cfg["vocab_size"])

    def fed(self, index: int) -> np.ndarray:
        """The fresh id of each row in request ``index`` (from 1)."""
        return weights.token_rows(self.seed, index, self.traffic["batch"], 1,
                                  self.cfg["vocab_size"])[:, 0]

    def prepare(self):
        """The program's prefill over the context, into the caches that
        every turn goes on from."""
        t, cfg = self.traffic, self.program_cfg
        total = t["context_len"] + t["n_new"]

        @jax.jit
        def session_prefill(params, ids):
            caches, _ = prefill(params, ids, cfg=cfg, total=total,
                                chunk=t.get("prefill_chunk"))
            return decode_caches(caches, cfg=cfg, p_len=t["context_len"],
                                 total=total)

        self.caches = jax.block_until_ready(session_prefill(
            self.params, jax.device_put(jnp.asarray(self.context()))))

    def one(self, index: int, counted: bool = False):
        """Request ``index``; ``counted`` asks the program for its
        counters too (the turns that are compared)."""
        t = self.traffic
        ids = jax.device_put(jnp.asarray(self.fed(index)))

        def call():
            out = decode_from(self.params, self.caches, ids,
                              t["context_len"], t["n_new"],
                              cfg=self.program_cfg, stats=counted)
            self.outputs[index], self.caches = jax.block_until_ready(out[:2])
            if counted:
                self.counters[index] = out[2]
        return call

    def checked(self, first: int) -> list:
        """The turns that are compared: requests ``first ...``, with the
        program's counters where the comparison reads them. Returns
        their (request, row) pairs, outputs and counters on the host."""
        t = self.traffic
        requests = range(first, first + t["checked_requests"])
        times = []
        for r in requests:
            t0 = time.perf_counter()
            self.one(r, counted=self.model.COUNTERS)()
            times.append(time.perf_counter() - t0)
        print("checked turns: " + ", ".join(f"{1e3 * s:.2f} ms"
                                            for s in times), file=sys.stderr)
        self.outputs = {r: np.asarray(self.outputs[r]) for r in requests}
        self.counters = {r: {k: np.asarray(v) for k, v in c.items()}
                         for r, c in self.counters.items()}
        return [(r, row) for r in requests
                for row in rows_checked(self.seed, t)]

    def free(self):
        self.params = self.caches = None


def rows_checked(seed: int, t: dict) -> list:
    """The `checked_rows` rows that are compared, the same in every
    checked request (the reference then passes over a row's context
    once)."""
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    return sorted(int(row) for row in rng.choice(
        t["batch"], replace=False, size=min(t["checked_rows"], t["batch"])))


def run(cell, seed: int, seconds: float, trace: bool, devices: list,
        clock, log, make_session=Session, work_dir=None) -> tuple:
    t = cell.traffic
    session = make_session(cell, seed, devices)
    jax.block_until_ready(session.params)
    print(f"set-up: weights by {clock():.2f} s", file=sys.stderr)
    session.prepare()
    print(f"set-up: context of {t['batch']} x {t['context_len']} in the "
          f"caches by {clock():.2f} s", file=sys.stderr)
    session.one(0)()
    if session.model.COUNTERS:
        session.one(0, counted=True)()
        session.counters.clear()
    setup_s = clock()
    built_before = log.programs

    def one(i):
        return session.one(1 + i)

    scopes = sub = None
    if trace:
        loop, trace_file = harness.traced(
            lambda: harness.measured_loop(one, float("inf"),
                                          at_most=t["traced_calls"]),
            work_dir)
        rows = scope_reader.load(trace_file)
        scopes = scope_reader.reduce(rows)
        sub = session_scopes.seconds_under(rows)
        if scopes:
            scopes["calls"] = len(loop["times"])
            print(scope_reader.table(scopes, scopes["calls"]),
                  file=sys.stderr)
            print(session_scopes.table(sub, scopes["calls"] * t["n_new"]),
                  file=sys.stderr)
    else:
        loop, trace_file = harness.measured_loop(one, seconds), None
    built = log.programs - built_before
    requests = len(loop["times"])
    peak = harness.memory_peak_bytes(devices)
    failed = sum(1 for r in range(1, 1 + requests)
                 if session.outputs[r].shape != (t["batch"], t["n_new"]))
    print(f"requests {requests} (the 95th percentile is of {requests} "
          f"samples) window_s {loop['window_s']:.4f} of which feeding "
          f"{loop['window_s'] - sum(loop['times']):.4f} programs built in the "
          f"window {built}", file=sys.stderr)
    slowest = sorted(range(requests), key=lambda i: -loop["times"][i])[:3]
    print(f"turns: median {1e3 * statistics.median(loop['times']):.2f} ms, "
          f"slowest " + ", ".join(f"{1e3 * loop['times'][i]:.2f} ms (request "
                                  f"{1 + i})" for i in slowest),
          file=sys.stderr)

    picks = session.checked(1 + requests)
    session.free()
    t0 = time.perf_counter()
    readings = session.model.judge(cell, seed, session, picks)
    print(f"reference took {time.perf_counter() - t0:.2f} s; compared "
          f"{len(picks) * t['n_new']} served tokens of (request, row) "
          f"{picks}", file=sys.stderr)
    measured = {
        "serve_tokens_per_s": requests * t["batch"] * t["n_new"]
        / loop["window_s"],
        "request_p95_ms": 1e3 * harness.percentile_nearest_rank(
            loop["times"], 95),
        "setup_s": setup_s,
    }
    context = {"cell": cell, "loop": loop, "calls": requests,
               "programs_built": built, "trace_file": trace_file,
               "chips": len(devices), "device": devices[0],
               "scopes": scopes, "sub_scopes": sub}
    return measured, context, readings, {"attempted": requests,
                                         "failed": failed,
                                         "memory_peak_bytes": peak}
