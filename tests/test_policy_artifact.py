"""Performance defaults baked into the kernels must follow the
committed sweep that chose them (DESIGN §7: perf claims live in
artifacts). The per-op routing in ``ops._TPU_AUTO_POLICY`` has no
committed measurement on the current installation (ROADMAP A3-A5);
``chip_smoke.py`` checks on the chip that every op it routes to Pallas
compiles there and agrees with its XLA twin.
"""

import json
import os

import pytest

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "results")


@pytest.mark.parametrize("shape", ["train-1chip", "ring-hop0",
                                   "ring-hop1"])
def test_flash_block_defaults_match_tuner_artifact(shape):
    """ADVICE r4 (medium): the (block_q, block_k) that ops/attention.py
    chooses from a call's shape is a perf claim, so at every shape the
    committed sweep ran (benchmarks/results/flash_tune.json: the train
    cells' calls) it must be the sweep's forward + backward winner, or
    level with it (within 2%: the one-chip cell's two best stand 1.6%
    apart) — a re-sweep that crowns other blocks turns the suite red
    until _resolve_blocks (and its rationale) follows the artifact."""
    from benchmarks import flash_tune
    from lua_mapreduce_tpu.ops import attention

    with open(os.path.join(RESULTS, "flash_tune.json")) as f:
        swept = json.load(f)[shape]
    _, l, q_offset, _ = flash_tune.SHAPES[shape]
    default = list(attention._resolve_blocks(
        l, None, None, True, flash_tune.WINDOW, q_offset))
    best = swept["best_fwdbwd_us"]
    ours, = (r for r in swept["all"] if r["blocks"] == default)
    assert ours["fwdbwd_us"] <= 1.02 * best["fwdbwd_us"], (
        f"{shape}: the default blocks {default} take "
        f"{ours['fwdbwd_us']} us, flash_tune.json's winner "
        f"{best['blocks']} {best['fwdbwd_us']}; re-tune or update "
        "_resolve_blocks")
    # the schedule the timing ran is the one the kernels walk today
    assert ours["tiles"] == list(attention.tile_classes(
        l, l, *default, True, flash_tune.WINDOW, q_offset))
