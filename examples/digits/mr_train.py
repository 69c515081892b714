"""Digits-MLP data-parallel SGD, packaged as the six MapReduce functions.

Mirrors examples/APRIL-ANN/common.lua function by function:
    init        — build/restore model, checkpoint to storage (57-77)
    taskfn      — emit n_shards map jobs over the same dataset (init.lua:65-70)
    mapfn       — load model, grad on a random bunch of 128, emit
                  (param_name, {grad, count}) + ("TR_LOSS", …) (85-104)
    partitionfn — byte-sum hash of param name % 10 (106-109)
    reducefn    — elementwise grad sum + count/loss accumulation (112-137)
    finalfn     — 1/sqrt(count) smoothing (163-166), SGD+momentum+weight
                  decay step (175-185), validation loss + early stopping,
                  re-checkpoint, return "loop" or finish (144-202)

Model + optimizer state persist in a checkpoint file plus a small meta
record in the task's storage backend (the GridFS model file +
persistent_table 'conf' analogs), so the example runs identically on the
LocalExecutor and on an elastic multi-process pool.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

from lua_mapreduce_tpu.models.mlp import init_mlp, nll_loss
from lua_mapreduce_tpu.store.router import get_storage_from
from lua_mapreduce_tpu.train import checkpoint as ckpt
from lua_mapreduce_tpu.train.data import make_digits

NUM_REDUCERS = 10       # common.lua:106-109
MODEL_FILE = "model.ckpt"
META_FILE = "model.meta"

_cfg = {}
_data = None


def init(args):
    global _cfg, _data
    # host-path pool members must not die when a sibling holds the chip
    # (a second process gets RuntimeError from JAX; utils/jax_env.py)
    from lua_mapreduce_tpu.utils.jax_env import ensure_backend
    ensure_backend()
    _cfg = {
        "sizes": tuple(args.get("sizes", (256, 128, 10))),
        "model_store": args.get("model_store", "mem:digits-model"),
        "n_shards": int(args.get("n_shards", 4)),      # init.lua:65-70
        "bunch": int(args.get("bunch", 128)),          # init.lua:127-141
        "lr": float(args.get("lr", 0.05)),
        "momentum": float(args.get("momentum", 0.9)),
        "weight_decay": float(args.get("weight_decay", 1e-5)),
        "max_steps": int(args.get("max_steps", 40)),   # max epochs init.lua:20
        "patience": int(args.get("patience", 5)),
        "seed": int(args.get("seed", 0)),
        # real-data contract (init.lua:80-123): a digits sheet image
        # sliced into 16x16 patterns, 800/200 split; synthetic fallback
        "image": args.get("image"),
    }
    if _cfg["image"]:
        from lua_mapreduce_tpu.train.data import load_digits_image
        _data = load_digits_image(_cfg["image"])
        if _data[0].shape[1] != _cfg["sizes"][0]:
            raise ValueError(
                f"digits sheet patterns are {_data[0].shape[1]}-dim but "
                f"the model expects {_cfg['sizes'][0]} inputs")
    else:
        _data = make_digits(seed=_cfg["seed"], dim=_cfg["sizes"][0])
    store = get_storage_from(_cfg["model_store"])
    if not store.exists(MODEL_FILE):
        params = init_mlp(jax.random.PRNGKey(_cfg["seed"]), _cfg["sizes"])
        _save_state(store, params, jax.tree.map(jnp.zeros_like, params))
        _write_meta(store, {"step": 0, "best_val": None, "best_step": 0,
                            "finished": False})


# -- state helpers ----------------------------------------------------------

def _template():
    params = init_mlp(jax.random.PRNGKey(0), _cfg["sizes"])
    return {"params": params, "vel": jax.tree.map(jnp.zeros_like, params)}


def _save_state(store, params, vel):
    ckpt.save_pytree(store, MODEL_FILE, {"params": params, "vel": vel})


def _load_state(store):
    return ckpt.load_pytree(store, MODEL_FILE, _template())


def _write_meta(store, meta):
    b = store.builder()
    b.write(json.dumps(meta))
    b.build(META_FILE)


def read_meta(store_spec: str):
    store = get_storage_from(store_spec)
    return json.loads("".join(store.lines(META_FILE)))


# -- the six functions ------------------------------------------------------

def taskfn(emit):
    for i in range(_cfg["n_shards"]):
        emit(i, i)


def mapfn(key, shard, emit):
    store = get_storage_from(_cfg["model_store"])
    state = _load_state(store)
    meta = json.loads("".join(store.lines(META_FILE)))
    x_train, y_train, _, _ = _data
    rng = np.random.RandomState(1000 + 7919 * meta["step"] + int(shard))
    idx = rng.randint(0, len(x_train), _cfg["bunch"])
    loss, grads = jax.value_and_grad(nll_loss)(
        state["params"], jnp.asarray(x_train[idx]), jnp.asarray(y_train[idx]))
    for name, g in grads.items():
        emit(name, {"grad": np.asarray(g).tolist(), "count": 1})
    emit("TR_LOSS", {"loss": float(loss), "count": 1})


def partitionfn(key):
    return sum(str(key).encode()) % NUM_REDUCERS


def reducefn(key, values):
    if key == "TR_LOSS":
        return {"loss": sum(v["loss"] for v in values),
                "count": sum(v["count"] for v in values)}
    acc = np.asarray(values[0]["grad"], dtype=np.float32)
    count = values[0]["count"]
    for v in values[1:]:
        acc = acc + np.asarray(v["grad"], dtype=np.float32)
        count += v["count"]
    return {"grad": acc.tolist(), "count": count}


def finalfn(pairs):
    store = get_storage_from(_cfg["model_store"])
    state = _load_state(store)
    meta = json.loads("".join(store.lines(META_FILE)))
    params, vel = state["params"], state["vel"]

    grads = {}
    tr_loss = None
    for key, vs in pairs:
        v = vs[0]
        if key == "TR_LOSS":
            tr_loss = v["loss"] / v["count"]
        else:
            grads[key] = (np.asarray(v["grad"], np.float32) /
                          np.sqrt(v["count"]))        # common.lua:163-166

    new_params, new_vel = {}, {}
    for name, p in params.items():
        g = jnp.asarray(grads[name]) + _cfg["weight_decay"] * p
        v = _cfg["momentum"] * vel[name] - _cfg["lr"] * g
        new_vel[name] = v
        new_params[name] = p + v

    step = meta["step"] + 1
    _, _, x_val, y_val = _data
    val_loss = float(nll_loss(new_params, jnp.asarray(x_val),
                              jnp.asarray(y_val)))
    best_val, best_step = meta["best_val"], meta["best_step"]
    if best_val is None or val_loss < best_val:
        best_val, best_step = val_loss, step
    finished = (step >= _cfg["max_steps"] or
                step - best_step >= _cfg["patience"])

    _save_state(store, new_params, new_vel)
    _write_meta(store, {"step": step, "best_val": best_val,
                        "best_step": best_step, "finished": finished,
                        "val_loss": val_loss, "tr_loss": tr_loss})
    return False if finished else "loop"
