"""Operations and bytes of a session's decode steps for
Kimi-Linear-48B-A3B-Instruct, from shapes: Kimi Delta Attention's
recurrent state in the `kda_layers`, absorbed latent attention over the
whole cache in the `full_attn_layers`, the expert layer of a chip that
holds a share with one shared expert beside it. The benchmark's own
copy. A configuration is the dict read from
`perfbench/configs/<name>.json`; counted is what the work needs, not
what an implementation reads.
"""

from __future__ import annotations

from perfbench.weights_kimilinear import kda_dims, kind


def layers_of(cfg: dict, which: str) -> int:
    return sum(kind(cfg, i) == which for i in range(cfg["num_hidden_layers"]))


def n_moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def held_assignments_expected(cfg: dict) -> float:
    """Routed (token, expert) pairs a token leaves on the held experts,
    in expectation under uniform routing."""
    return (cfg["num_experts_per_token"] * cfg["num_experts"]
            / cfg["router_experts"])


def experts_touched_expected(cfg: dict, batch: int) -> float:
    """Held experts that at least one of ``batch`` tokens chose, a
    layer, in expectation under uniform routing."""
    p = cfg["num_experts_per_token"] / cfg["router_experts"]
    return cfg["num_experts"] * (1.0 - (1.0 - p) ** batch)


# --------------------------------------------------------------------------
# the delta rule of a decode step
# --------------------------------------------------------------------------

def kda_flops(cfg: dict, batch: int, n_new: int) -> float:
    """The rule's FLOPs in a turn: per (session, head, step) the decay of
    the K x V state (1 a value), the key's projection of it (2), the
    rank-one correction (2) and the query's read (2)."""
    h, dk, _ = kda_dims(cfg)
    return 7.0 * dk * dk * h * batch * n_new * layers_of(cfg, "kda")


def kda_bytes(cfg: dict, batch: int, n_new: int) -> float:
    """The rule's bytes in a turn: every (session, head) float32 state
    read once and written once per KDA layer per step, and its float32
    rows: q, k, the decay and v in, beta, o out."""
    h, dk, _ = kda_dims(cfg)
    state = 2 * h * dk * dk * 4
    rows = (4 * h * dk + 2 * h) * 4
    return float((state + rows) * batch * n_new * layers_of(cfg, "kda"))


# --------------------------------------------------------------------------
# the expert layer
# --------------------------------------------------------------------------

def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    """One expert's three matrices (shared or routed: the same width)."""
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def moe_bytes(cfg: dict, touched: float, n_new: int,
              itemsize: int = 2) -> float:
    """Weight bytes a turn's steps read in the expert layers: ``touched``
    held experts a layer and step (`experts_touched_expected` under
    uniform routing, or what the program counted) and the shared one,
    every expert layer, every step."""
    per_layer = (touched + cfg["num_shared_experts"]) * expert_bytes(
        cfg, itemsize)
    return per_layer * n_moe_layers(cfg) * n_new


# --------------------------------------------------------------------------
# the caches and the whole step
# --------------------------------------------------------------------------

def latent_row(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def cache_bytes(cfg: dict, batch: int, total: int) -> dict:
    """The caches a session scan carries, by kind: the KDA states
    (float32) and convolution tails (bfloat16), their one snapshot and
    its position, the latent rows of ``total`` positions (bfloat16)."""
    h, dk, taps = kda_dims(cfg)
    state = h * dk * dk * 4 + (taps - 1) * 3 * h * dk * 2
    n_kda = layers_of(cfg, "kda")
    return {"state_bytes": n_kda * batch * state,
            "snapshot_bytes": n_kda * (batch * state + 4),
            "growing_bytes": (layers_of(cfg, "latent") * batch * total
                              * latent_row(cfg) * 2)}


def kda_mixer_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    h, dk, taps = kda_dims(cfg)
    p = h * dk
    return (3 * d * p + taps * 3 * p + 2 * (d * dk + dk * p) + d * h
            + p * d + p + h + dk)


def latent_mixer_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvr, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                          cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (d * h * (nope + rope) + d * (kvr + rope) + kvr
            + kvr * h * (nope + v) + h * v * d)


def step_bytes(cfg: dict, batch: int, position: int) -> dict:
    """The bytes one decode step must read at 0-based ``position``, by
    part: the KDA states (read and written), the mixers' weights, the
    held experts expected touched, the shared experts and routers, the
    latent cache up to the position, the dense FFN and the head."""
    d = cfg["hidden_size"]
    n_moe = n_moe_layers(cfg)
    return {
        "kda_state": kda_bytes(cfg, batch, 1),
        "mixer_weights": 2.0 * (layers_of(cfg, "kda") * kda_mixer_params(cfg)
                                + layers_of(cfg, "latent")
                                * latent_mixer_params(cfg)),
        "held_experts": experts_touched_expected(cfg, batch)
        * expert_bytes(cfg) * n_moe,
        "shared_and_routers": n_moe * (
            cfg["num_shared_experts"] * expert_bytes(cfg)
            + 2.0 * d * cfg["router_experts"]),
        "latent_cache": 2.0 * layers_of(cfg, "latent") * batch
        * (position + 1) * latent_row(cfg),
        "dense_and_head": 2.0 * (cfg["first_k_dense_replace"] * 3 * d
                                 * cfg["intermediate_size"]
                                 + d * cfg["vocab_size"]),
    }


def token_flops(cfg: dict, position: int) -> float:
    """Model FLOPs of one token's forward at 0-based ``position``: each
    KDA layer's projections, convolution and rule, each latent layer's
    projections (the query's absorption into the latent's basis and the
    value half's way out among them) and its scores and values over all
    ``position + 1`` cached rows, the dense FFN or the router, the shared
    expert and the expected held assignments, and the sliced head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kh, dk, taps = kda_dims(cfg)
    kvr, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                          cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    p = kh * dk
    kda = (2.0 * (3 * d * p + 2 * (d * dk + dk * p) + d * kh + p * d)
           + 2.0 * taps * 3 * p + 7.0 * kh * dk * dk)
    latent = (2.0 * (d * h * (nope + rope) + d * (kvr + rope)
                     + kvr * h * (nope + v) + h * v * d)
              + 2.0 * h * (kvr + rope + kvr) * (position + 1))
    dense = 6.0 * d * cfg["intermediate_size"]
    moe = (2.0 * d * cfg["router_experts"]
           + 6.0 * d * cfg["moe_intermediate_size"]
           * (cfg["num_shared_experts"] + held_assignments_expected(cfg)))
    first = cfg["first_k_dense_replace"]
    return (layers_of(cfg, "kda") * kda + layers_of(cfg, "latent") * latent
            + first * dense + n_moe_layers(cfg) * moe
            + 2.0 * d * cfg["vocab_size"])


def turn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    """Model FLOPs of one turn: the ``n_new`` scanned positions of
    every row, and nothing of the caches that were there."""
    return batch * sum(token_flops(cfg, q)
                       for q in range(start, start + n_new))
