"""Performance defaults baked into the kernels must follow the
committed sweep that chose them (DESIGN §7: perf claims live in
artifacts). The per-op routing in ``ops._TPU_AUTO_POLICY`` has no
committed measurement on the current installation (ROADMAP A3-A5);
``chip_smoke.py`` checks on the chip that every op it routes to Pallas
compiles there and agrees with its XLA twin.
"""

import json
import os

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "results")


def test_flash_block_defaults_match_tuner_artifact():
    """ADVICE r4 (medium): ops/attention.py's default (block_q, block_k)
    schedule is a perf claim, so it must equal the committed sweep's
    winner for every swept shape (benchmarks/results/flash_tune.json) —
    a re-sweep that crowns different blocks turns the suite red until
    the defaults (and their rationale comment) follow the artifact."""
    from lua_mapreduce_tpu.ops import attention

    path = os.path.join(RESULTS, "flash_tune.json")
    with open(path) as f:
        tune = json.load(f)
    winners = {tag: tuple(v["best_blocks"]) for tag, v in tune.items()
               if isinstance(v, dict) and "best_blocks" in v}
    assert winners, "flash_tune.json carries no sweep winners"
    default = (attention._DEFAULT_BLOCK_Q, attention._DEFAULT_BLOCK_K)
    for tag, best in sorted(winners.items()):
        assert default == best, (
            f"flash default blocks {default} != flash_tune.json's "
            f"{tag} winner {best}; re-tune or update the defaults")
