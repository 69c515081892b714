"""Digits-sheet e2e: BOTH execution paths to a validation-ACCURACY
target (VERDICT r3 item 5 — the APRIL-ANN capability demonstrated end to
end with accuracy, not loss deltas; reference examples/APRIL-ANN/
init.lua:80-123 + common.lua:144-202).

Trains the digits MLP on the checked-in full-size digits sheet
(tests/fixtures/digits_sheet.png, 1600x160 — the reference's exact
16x16/800-200 contract via train/data.load_digits_image) through:

- the **TPU-native path**: train/harness.DataParallelTrainer, jitted
  SPMD steps over the dp mesh axis;
- the **MapReduce path**: examples/digits/mr_train's six functions
  looping under the LocalExecutor ("loop" protocol, grad shards
  shuffled by parameter name, finalfn optimizer step) — the faithful
  re-expression of the reference's common.lua.

Both must clear the accuracy bar and agree with each other; the paths
share the dataset but not batch schedules or optimizer plumbing, so
agreement is a genuine two-implementations check of the training
semantics, not a replay.

Usage: python benchmarks/digits_e2e.py  → results/digits_e2e.json
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESULTS = os.path.join(REPO, "benchmarks", "results", "digits_e2e.json")
SHEET = os.path.join(REPO, "tests", "fixtures", "digits_sheet.png")


def native_path(sheet: str = SHEET, steps: int = 300,
                batch: int = 512) -> dict:
    """DataParallelTrainer on the sheet → final validation accuracy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lua_mapreduce_tpu.models.mlp import (accuracy, init_mlp,
                                              nll_loss)
    from lua_mapreduce_tpu.parallel.mesh import make_mesh
    from lua_mapreduce_tpu.train.data import load_digits_image
    from lua_mapreduce_tpu.train.harness import (DataParallelTrainer,
                                                 TrainConfig)

    x_tr, y_tr, x_va, y_va = load_digits_image(sheet)
    mesh = make_mesh()
    params = init_mlp(jax.random.PRNGKey(0))
    tr = DataParallelTrainer(nll_loss, params, mesh,
                             TrainConfig(batch_size=batch,
                                         learning_rate=0.05,
                                         momentum=0.9))
    rng = np.random.RandomState(0)
    for _ in range(steps):
        idx = rng.randint(0, len(x_tr), batch)
        tr.run_steps(jnp.asarray(x_tr[idx]), jnp.asarray(y_tr[idx]), 1)
    acc = float(accuracy(jax.device_get(tr.params), jnp.asarray(x_va),
                         jnp.asarray(y_va)))
    return {"val_accuracy": round(acc, 4), "steps": steps,
            "batch": batch}


def mapreduce_path(sheet: str = SHEET, max_steps: int = 60,
                   model_store: str = "mem:digits-e2e") -> dict:
    """mr_train's six functions under the LocalExecutor to convergence
    (early stopping on validation loss), then accuracy of the final
    checkpointed params."""
    import jax.numpy as jnp

    from examples.digits import mr_train
    from lua_mapreduce_tpu.engine.contract import TaskSpec
    from lua_mapreduce_tpu.engine.local import LocalExecutor
    from lua_mapreduce_tpu.models.mlp import accuracy
    from lua_mapreduce_tpu.store.router import get_storage_from
    from lua_mapreduce_tpu.train.data import load_digits_image

    store = get_storage_from(model_store)
    for f in (mr_train.MODEL_FILE, mr_train.META_FILE):
        if store.exists(f):
            store.remove(f)
    mod = "examples.digits.mr_train"
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    finalfn=mod,
                    init_args={"image": sheet, "model_store": model_store,
                               "max_steps": max_steps, "patience": 10},
                    storage="mem:digits-e2e-spill")
    LocalExecutor(spec).run()
    meta = mr_train.read_meta(model_store)
    state = mr_train._load_state(store)
    _, _, x_va, y_va = load_digits_image(sheet)
    acc = float(accuracy(state["params"], jnp.asarray(x_va),
                         jnp.asarray(y_va)))
    return {"val_accuracy": round(acc, 4), "steps": meta["step"],
            "val_loss": round(meta["val_loss"], 4)}


def run(native_steps: int = 300, mr_steps: int = 60,
        target: float = 0.95) -> dict:
    import jax

    native = native_path(steps=native_steps)
    mr = mapreduce_path(max_steps=mr_steps)
    return {
        "sheet": os.path.relpath(SHEET, REPO),
        "split": "800 train / 200 val (init.lua:80-123 contract)",
        "target_accuracy": target,
        "tpu_native_path": native,
        "mapreduce_path": mr,
        "agree_within": round(abs(native["val_accuracy"]
                                  - mr["val_accuracy"]), 4),
        "both_reach_target": (native["val_accuracy"] >= target
                              and mr["val_accuracy"] >= target),
        "platform": jax.default_backend(),
    }


def main() -> None:
    from lua_mapreduce_tpu.utils.jax_env import place_compile_cache
    place_compile_cache()

    out = run()
    print(json.dumps(out, indent=1))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    main()
