"""Decoder-only transformer LM — the long-context model family.

The reference's model zoo is its example workloads (SURVEY.md §2.3); this
family extends the zoo to sequences, the capability the sequence-parallel
layer (parallel/ring_attention.py) exists for. One model, three execution
forms that must agree (golden-diff discipline, SURVEY.md §4):

- :func:`transformer_apply` — single-device oracle (full attention).
- :func:`make_sharded_apply` — the same forward inside ``shard_map`` over
  a (dp, sp) mesh: batch sharded on ``dp``, sequence sharded on ``sp``,
  attention via the ring (KV shards rotating over ICI) or Ulysses
  (all_to_all head reshard). No device ever holds a full sequence —
  context length scales with the sp axis.
- :func:`make_train_step` — jitted SPMD LM training step over the mesh:
  per-device loss on its (batch, seq) tile, the gradients summed over
  BOTH axes behind the backward pass, in the same program (the
  reference's reducefn-sum shape, common.lua:112-137), each device
  updating its rows of every leaf, the weights gathered where a step
  takes them.

Params are a flat name→array dict (the grad-shuffle key space, like every
model in this zoo). Layout: activations (B, L, D); attention heads split
D as (H, D/H). Weights stay f32; matmul FLOPs ride the MXU.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from lua_mapreduce_tpu.models.attention_kinds import (
    Cross, GatedMemory, GroupedQuery, KimiDelta, Latent, Params, StateSpace,
    _dense, _mm, _norm, _rope, head_dim, kv_heads)
from lua_mapreduce_tpu.ops.attention import flash_attention
from lua_mapreduce_tpu.ops.q8 import quantize_q8
from lua_mapreduce_tpu.parallel import moe as _moe
from lua_mapreduce_tpu.parallel import zero1 as _z1
from lua_mapreduce_tpu.parallel.mesh import rows_layout, rows_spec
from lua_mapreduce_tpu.parallel.pipeline import pipeline_apply
from lua_mapreduce_tpu.parallel.ring_attention import (
    _NEG_INF, _ring_shard, _ring_shard_zigzag, _ulysses_shard,
    _zigzag_check, _zigzag_perm, attention_reference)
from lua_mapreduce_tpu.train.accum import accum_value_and_grad
from lua_mapreduce_tpu.utils.profiling import annotate, build_log, scope

# every launcher imports this module before it builds a program: the
# build log's listeners go in here, once a process
build_log()


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """Multi-head latent attention (DeepSeek-V2/V3 and what followed):
    keys and values go through a low-rank latent, and the cache holds
    one ``kv_rank + rope_dim`` row a token for ALL heads. ``n_heads`` of
    the enclosing config are the query heads; ``nope_dim + rope_dim`` is
    a head's score width and ``v_dim`` its value width. Two parts a
    model may lack. ``q_rank`` > 0: queries go through a normed latent
    of that rank; 0: ``q`` is projected straight from the stream.
    ``index_top_k`` > 0: V3.2's sparse attention indexer
    (``index_heads`` x ``index_dim``, its own cached key a token), a
    query attends the ``index_top_k`` cached rows it scores highest
    (the indexer's queries come from the query latent, so it needs
    ``q_rank``); 0: no indexer, a query reads every cached row up to
    its own. ``qk_norm``: an RMSNorm with a gain (``q_g``) of every
    head's query before the rope; the key side's norm is the latent's
    own (``kv_g``), which every such model has. The ``rope_dim`` part is
    rotated where the config has ``rope``, and carried unrotated where
    it has no positions (Kimi Linear's ``mla_use_nope``)."""
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    index_heads: int = 0
    index_dim: int = 0
    index_top_k: int = 0
    # YaRN on the rotary frequencies (factor 1 = plain rope) and its
    # softmax-scale correction (0.1 * mscale_all_dim * ln(factor) + 1)^2
    rope_factor: float = 1.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 0.0
    qk_norm: bool = False


@dataclasses.dataclass(frozen=True)
class LinearAttention:
    """The sizes of Kimi Delta Attention (``models/attention_kinds.
    KimiDelta``), the layers an ``attn_pattern`` names ``"kda"``:
    ``heads`` of ``head_dim`` (keys and values alike; the decay's and
    the output gate's low rank is ``head_dim`` too) and the short
    convolution's ``conv`` taps."""
    heads: int
    head_dim: int
    conv: int = 4


@dataclasses.dataclass(frozen=True)
class HybridStack:
    """A stack whose layers differ in their MIXER (SambaY, Ren et al.
    2025, arXiv:2507.06607, is the one written for): ``kinds[i]`` says
    what layer ``i`` has in attention's place.

    - ``"ssm"``: a Mamba-1 selective state space (``ssm_*``: the
      state's size, the convolution's taps, the inner width as a
      multiple of ``d_model``, the rank of the step's projection, 0 =
      ceil(d_model / 16)); it caches a state, not positions;
    - ``"swa"``: self-attention over the last ``window`` positions (a
      rolling cache); ``"full"``: over all of them (the one cache that
      grows);
    - ``"cross"``: attention with queries of its own over the keys and
      values that layer ``kv_from`` (a ``"full"`` one) cached;
    - ``"gmu"``: a gated memory unit over what layer ``memory_from``
      (an ``"ssm"`` one) made of the same positions.

    The two references point BACK: a layer reads what an earlier one
    left. Every attention layer is differential (pairs of heads,
    ``models/attention_kinds.GroupedQuery``). Layers from
    :attr:`cached_layers` on cache nothing, so a prefill needs them for
    its last position only."""
    kinds: Tuple[str, ...]
    window: int
    kv_from: int
    memory_from: int
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_rank: int = 0

    @property
    def cached_layers(self) -> int:
        """Layers up to the last that caches something."""
        return 1 + max(i for i, k in enumerate(self.kinds)
                       if k in ("ssm", "swa", "full"))

    @staticmethod
    def sambay(n_layers: int, window: int, **sizes) -> "HybridStack":
        """The SambaY pattern: a self-decoder of ``n_layers // 2``
        layers alternating state space and window attention, one
        state-space and one full-attention layer, then a cross-decoder
        alternating gated memory units (on the last state-space layer)
        and cross-attention (on the full-attention layer's cache)."""
        half = n_layers // 2
        kinds = tuple(
            ("ssm" if i % 2 == 0 else "swa") if i < half
            else "ssm" if i == half else "full" if i == half + 1
            else ("gmu" if i % 2 == 0 else "cross")
            for i in range(n_layers))
        return HybridStack(kinds, window, kv_from=half + 1,
                           memory_from=half, **sizes)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_seq: int = 512
    # grouped-query attention: 0 (default) = n_heads (plain MHA); a
    # divisor of n_heads shares each kv head across n_heads/n_kv_heads
    # query heads — the KV cache and the kv projection shrink by that
    # factor (the modern long-context serving lever; the flash kernels
    # regroup via index maps, ops/attention.py)
    n_kv_heads: int = 0
    # a head's width; 0 = d_model // n_heads
    head_dim: int = 0
    # rotary position embeddings: q/k rotated by their GLOBAL position
    # before attention (relative-position encoding, no pos_emb table —
    # the standard long-context scheme; composes with every sequence-
    # parallel form because _shard_pos already hands each device its
    # global positions). head_dim must be even.
    rope: bool = False
    rope_base: float = 10000.0
    # None = the scheme ``rope`` says: rotary, or else a learned table
    # added to the embeddings (``pos_emb``). "none" = no positional
    # encoding at all (a model whose state-space and window layers carry
    # the order)
    positions: Optional[str] = None
    # "ln" (pre-LN with bias), "rms" (RMSNorm, scale only) or "ln_gain"
    # (LayerNorm with a gain and no bias: Cohere's)
    norm: str = "ln"
    norm_eps: float = 1e-5
    # the output head is ``tok_emb.T`` (tied) or a matrix of its own
    tied_head: bool = True
    # None = grouped-query attention over (k, v) caches; else latent
    # attention over a latent cache, sparse where it has an indexer
    latent: Optional[LatentAttention] = None
    # the sizes of the "kda" layers of an attention pattern (None: none)
    linear: Optional[LinearAttention] = None
    # None = every layer has the one attention above; else the mixer is
    # chosen by layer
    hybrid: Optional[HybridStack] = None
    # "gelu" (2-matmul MLP with biases) or "swiglu" (gate/up/down,
    # no biases — the llama-style FFN)
    ffn: str = "gelu"
    # sliding-window attention: each position sees at most the last
    # ``window`` positions (0 = full causal). Oracle, KV-cached decode
    # (rolling O(window) cache), prefill, pipeline, and the BANDED
    # contiguous ring (attn="ring") speak it; zigzag/ulysses reject.
    window: int = 0
    # () = every layer's attention as ``window`` (or ``latent``) says.
    # Else the layers' mixers, ONE period of them (or every layer's),
    # layer i having ``attn_pattern[i % len]``: "swa" (grouped-query
    # over the last ``window`` positions, rope where ``rope``) or
    # "full_nope" (every position, no positional encoding): Cohere2's
    # three window layers to one global NoPE layer; "latent" (``latent``
    # attention) or "kda" (Kimi Delta Attention of ``linear``'s sizes):
    # Kimi Linear's three linear layers to one latent one. Served only
    # (prefill, decode_from, greedy_decode, transformer_apply)
    attn_pattern: Tuple[str, ...] = ()
    # the parallel block: x + attn(norm(x)) + ffn(norm(x)) from ONE norm
    # a layer (GPT-J, Cohere), where the default is sequential
    parallel_block: bool = False
    # mixture-of-experts: >0 replaces every block's dense FFN with a
    # switch-routed expert FFN (parallel/moe.py); 0 = dense. capacity is
    # REQUIRED with experts and is per routing group (the device tile in
    # sharded runs, the whole batch in the oracle) — an auto-derived
    # default would differ between the two and break their golden-diff.
    moe_experts: int = 0
    moe_capacity: int = 0
    moe_aux_weight: float = 0.01
    # experts each token is routed to: 1 = switch, >1 = Mixtral-style
    # top-k with combine weights renormalized over the selected k
    moe_top_k: int = 1
    # "switch": softmax gates, capacity-bounded, gelu experts (above).
    # "grouped": sigmoid scores with a selection bias, a group-limited
    # choice (``moe_groups`` groups, the best ``moe_topk_groups`` stay),
    # weights normalised over the selected k and scaled by
    # ``moe_scale``, SwiGLU experts of width ``moe_d_ff`` (0 = d_ff),
    # ``moe_shared`` shared experts (summed: a model that averages them
    # scales their down projections on load), and NO dropped token (no
    # capacity).
    # ``moe_router_bias`` False: no selection bias, the choice is on the
    # scores alone.
    # ``moe_held = (first, count)`` is the range of experts this chip
    # holds (None = all): the router keeps its ``moe_experts`` outputs,
    # the layer computes its own experts' part of the result.
    moe_router: str = "switch"
    moe_groups: int = 1
    moe_topk_groups: int = 1
    moe_scale: float = 1.0
    moe_d_ff: int = 0
    moe_shared: int = 0
    moe_router_bias: bool = True
    moe_held: Optional[Tuple[int, int]] = None
    # the first ``moe_first_dense`` layers keep the dense FFN
    moe_first_dense: int = 0
    # rematerialization: recompute each block in the backward pass
    # instead of saving its activations — trades ~1/3 more FLOPs for
    # O(n_layers) less activation HBM, the standard long-context lever
    # (activations dominate HBM at large L; the MXU has FLOPs to spare).
    # Applies to every execution form (oracle, sp, 3-D) since they share
    # _forward.
    remat: bool = False

    @staticmethod
    def tiny() -> "TransformerConfig":
        return TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                 n_layers=2, d_ff=64, max_seq=128)

    @staticmethod
    def llama_style(**kw) -> "TransformerConfig":
        """The modern decoder recipe: RoPE + RMSNorm + SwiGLU + GQA
        (pass ``n_kv_heads``); any field overridable via ``kw``."""
        base = dict(rope=True, norm="rms", ffn="swiglu")
        base.update(kw)
        return TransformerConfig(**base)


def flops_per_token(cfg: TransformerConfig, seq_len: int,
                    causal: bool = True) -> float:
    """Matmul FLOPs per token for one TRAIN step (fwd + bwd ≈ 3× fwd) —
    the MFU numerator (same accounting role as models/mlp.py
    ``flops_per_example``). Counted: qkv+out projections
    (2·d·(H+2H_kv)·hd + 2d² per token — 8d² at MHA, less under GQA),
    attention score+value contractions (4·L·d, halved when causal;
    unchanged by GQA — every QUERY head still contracts), dense FFN
    (4·d·d_ff), tied LM head (2·d·V). Uncounted (understates
    utilization): layernorms, softmax, embeddings, and the extra block
    forward under ``cfg.remat``. MoE FFN FLOPs follow the per-token
    routed expert (same as dense for top-1 switch routing)."""
    d, dff = cfg.d_model, cfg.d_ff
    hd = head_dim(cfg)
    qkv_proj = 2.0 * d * (cfg.n_heads + 2 * kv_heads(cfg)) * hd
    if cfg.window and causal:
        # sliding window: mean visible keys per token is
        # (Σ_{i=1..L} min(i, w)) / L — the kernel prunes the rest,
        # so counting full-causal work would inflate MFU
        we = min(cfg.window, seq_len)
        visible = (we * (we + 1) / 2 + (seq_len - we) * we) / seq_len
        attn = 4.0 * d * visible
    else:
        attn = 4.0 * seq_len * d * (0.5 if causal else 1.0)
    ffn = (6.0 if cfg.ffn == "swiglu" else 4.0) * d * dff
    per_layer = qkv_proj + 2.0 * d * cfg.n_heads * hd + attn + ffn
    fwd = cfg.n_layers * per_layer + 2.0 * d * cfg.vocab
    return 3.0 * fwd


def moe_layer(cfg: TransformerConfig, i: int) -> bool:
    """Whether layer ``i`` has the expert FFN (else the dense one)."""
    return bool(cfg.moe_experts) and i >= cfg.moe_first_dense


def attention_kind(cfg: TransformerConfig, i: int):
    """Layer ``i``'s attention (or what it has in attention's place):
    its weights, what a position caches, the cache's format and the
    attention over it (models/attention_kinds.py). The one place that
    reads the configuration for it."""
    if cfg.attn_pattern:
        kind = cfg.attn_pattern[i % len(cfg.attn_pattern)]
        if kind == "kda":
            return KimiDelta(cfg)
        if kind == "latent":
            return Latent(cfg)
        full = kind == "full_nope"
        return GroupedQuery(cfg, window=0 if full else cfg.window,
                            nope=full)
    if cfg.latent is not None:
        return Latent(cfg)
    hy = cfg.hybrid
    if hy is None:
        return GroupedQuery(cfg, window=cfg.window)
    kind = hy.kinds[i]
    if kind == "ssm":
        return StateSpace(cfg, hands=i == hy.memory_from)
    if kind == "gmu":
        return GatedMemory(cfg)
    if kind == "cross":
        return Cross(cfg, source=hy.kv_from, depth=i)
    return GroupedQuery(cfg, window=hy.window if kind == "swa" else 0,
                        differential=True, depth=i, shares=i == hy.kv_from)


def _has_pos_table(cfg: TransformerConfig) -> bool:
    """Whether positions are a learned table added to the embeddings
    (rope turns q and k inside the layers; "none" has neither)."""
    return not cfg.rope and cfg.positions is None


def _check_arch(cfg: TransformerConfig) -> None:
    """Architecture-knob validation shared by init and every factory."""
    if cfg.norm not in ("ln", "rms", "ln_gain"):
        raise ValueError(f"unknown norm {cfg.norm!r} "
                         f"(want 'ln'|'rms'|'ln_gain')")
    if cfg.ffn not in ("gelu", "swiglu"):
        raise ValueError(f"unknown ffn {cfg.ffn!r} "
                         f"(want 'gelu'|'swiglu')")
    if cfg.rope and head_dim(cfg) % 2:
        raise ValueError(f"rope needs an even head_dim; got {head_dim(cfg)}")
    if cfg.moe_router not in ("switch", "grouped"):
        raise ValueError(f"unknown moe_router {cfg.moe_router!r} "
                         f"(want 'switch'|'grouped')")
    if cfg.moe_experts and cfg.moe_router == "switch" and cfg.ffn != "gelu":
        raise ValueError("the switch router's experts are gelu FFNs; "
                         "SwiGLU experts come with moe_router='grouped'")
    if cfg.moe_experts and cfg.moe_router == "grouped":
        if cfg.ffn != "swiglu":
            raise ValueError("moe_router='grouped' has SwiGLU experts: "
                             "set ffn='swiglu'")
        if (cfg.moe_experts % cfg.moe_groups
                or cfg.moe_topk_groups > cfg.moe_groups
                or cfg.moe_top_k > cfg.moe_topk_groups
                * (cfg.moe_experts // cfg.moe_groups)):
            raise ValueError("moe_groups must divide moe_experts and the "
                             "kept groups must hold moe_top_k experts")
        _moe._check_held(cfg.moe_held or (0, cfg.moe_experts),
                         cfg.moe_experts)
    if not cfg.moe_router_bias and not (cfg.moe_experts
                                        and cfg.moe_router == "grouped"):
        raise ValueError("moe_router_bias is the grouped expert layer's "
                         "(moe_router='grouped')")
    if cfg.attn_pattern:
        _check_pattern(cfg)
    elif cfg.linear is not None:
        raise ValueError("linear sizes without an attn_pattern that names "
                         "'kda' layers: no layer has them")
    if cfg.latent is not None:
        if cfg.norm != "rms":
            raise ValueError("latent attention is written for norm='rms'")
        if cfg.latent.rope_dim % 2 or cfg.window:
            raise ValueError("latent attention needs an even rope_dim and "
                             "takes no window")
        la = cfg.latent
        if la.q_rank < 0 or la.index_top_k < 0:
            raise ValueError(f"q_rank={la.q_rank} and index_top_k="
                             f"{la.index_top_k} are sizes or 0 (the part "
                             f"is absent)")
        if la.index_top_k and not (la.index_heads > 0 and la.index_dim > 0):
            raise ValueError(f"index_top_k={la.index_top_k} needs an "
                             f"indexer: index_heads={la.index_heads}, "
                             f"index_dim={la.index_dim}")
        if la.index_top_k and not la.q_rank:
            raise ValueError(f"index_top_k={la.index_top_k} with q_rank=0: "
                             f"the indexer's queries come from the query "
                             f"latent")
        if not la.index_top_k and (la.index_heads or la.index_dim):
            raise ValueError(f"index_heads={la.index_heads} and index_dim="
                             f"{la.index_dim} without index_top_k: an "
                             f"indexer that selects nothing")
    if cfg.window < 0:
        raise ValueError(f"window must be >= 0, got {cfg.window}")
    if cfg.positions not in (None, "none") or (cfg.rope and cfg.positions):
        raise ValueError(f"positions={cfg.positions!r}: None (rope, or the "
                         f"learned table) or 'none', which takes no rope")
    if cfg.hybrid is not None:
        _check_hybrid(cfg)


def _check_pattern(cfg: TransformerConfig) -> None:
    """What an attention pattern is written for: grouped-query layers of
    the two kinds, a window for the "swa" ones; or latent layers; either
    beside "kda" layers with ``linear``'s sizes; no other stack."""
    known = ("swa", "full_nope", "latent", "kda")
    kinds = set(cfg.attn_pattern)
    if kinds - set(known):
        raise ValueError(f"attn_pattern names each layer of a period as one "
                         f"of {known}; got {cfg.attn_pattern}")
    if cfg.hybrid is not None:
        raise ValueError("attn_pattern is a period of grouped-query, latent "
                         "or 'kda' layers; a hybrid stack takes none")
    if ("latent" in kinds) != (cfg.latent is not None) or (
            cfg.latent is not None and kinds & {"swa", "full_nope"}):
        raise ValueError("attn_pattern's 'latent' layers are latent's, and "
                         "a stack with latent attention has no grouped-query "
                         f"layers; got {cfg.attn_pattern}, latent "
                         f"{cfg.latent is not None}")
    if ("kda" in kinds) != (cfg.linear is not None):
        raise ValueError("attn_pattern's 'kda' layers take linear's sizes, "
                         f"and linear has no other use; got "
                         f"{cfg.attn_pattern}, linear {cfg.linear}")
    if "swa" in cfg.attn_pattern and cfg.window < 1:
        raise ValueError("attn_pattern's 'swa' layers need window >= 1")


def _check_hybrid(cfg: TransformerConfig) -> None:
    """What a hybrid stack is written for: pre-LN layers with a dense
    SwiGLU FFN, no rope (its attention layers turn nothing), pairs of
    heads, and references that point back to a layer of the right
    kind."""
    hy = cfg.hybrid
    known = ("ssm", "swa", "full", "cross", "gmu")
    if len(hy.kinds) != cfg.n_layers or set(hy.kinds) - set(known):
        raise ValueError(f"hybrid.kinds names each of the {cfg.n_layers} "
                         f"layers' mixer, one of {known}; got {hy.kinds}")
    if (cfg.latent is not None or cfg.window or cfg.rope or cfg.moe_experts
            or cfg.remat or cfg.norm != "ln" or cfg.ffn != "swiglu"):
        raise ValueError(
            "a hybrid stack is written for norm='ln', ffn='swiglu' and no "
            "rope; its windows are the stack's (window=0), and it takes "
            "no latent attention, experts or remat")
    if cfg.n_heads % 2 or kv_heads(cfg) % 2 or hy.window < 1:
        raise ValueError("a hybrid stack's attention is differential: "
                         "n_heads and n_kv_heads are even; its 'swa' "
                         "layers need hybrid.window >= 1")
    for what, kind, of, readers in (("kv_from", "full", hy.kv_from, "cross"),
                                    ("memory_from", "ssm", hy.memory_from,
                                     "gmu")):
        first = min((i for i, k in enumerate(hy.kinds) if k == readers),
                    default=cfg.n_layers)
        if not (0 <= of < first and hy.kinds[of] == kind):
            raise ValueError(
                f"hybrid.{what}={of} must be a {kind!r} layer before the "
                f"first {readers!r} layer ({first}); kinds {hy.kinds}")


def _check_sharded(cfg: TransformerConfig) -> None:
    """What the sharded forward and the train steps cannot run yet, by
    name: their attention is the caller's one window for every layer,
    and their blocks (the tensor-parallel and the pipeline ones among
    them) are sequential."""
    served = [what for what, has in (
        ("latent attention", cfg.latent is not None),
        ("the KDA kind (Kimi Delta Attention)", "kda" in cfg.attn_pattern),
        ("a hybrid stack", cfg.hybrid is not None),
        ("the grouped expert layer",
         cfg.moe_experts and cfg.moe_router == "grouped"),
        (f"an attention pattern {cfg.attn_pattern}", cfg.attn_pattern),
        ("a parallel block", cfg.parallel_block))
        if has]
    if served:
        raise ValueError(
            f"{', '.join(served)}: served only "
            "(prefill, decode_from, greedy_decode, transformer_apply); "
            "the sharded forward and the train steps run grouped-query "
            "attention with one window, sequential blocks and the switch "
            "MoE")


def _check_moe(cfg: TransformerConfig, n_ep: Optional[int] = None) -> None:
    if (cfg.moe_experts and cfg.moe_capacity <= 0
            and cfg.moe_router == "switch"):
        raise ValueError(
            "moe_experts > 0 requires an explicit moe_capacity (it is "
            "per routing group; see TransformerConfig)")
    if cfg.moe_top_k < 1:
        raise ValueError(f"moe_top_k must be >= 1, got {cfg.moe_top_k}")
    if cfg.moe_experts and cfg.moe_top_k > cfg.moe_experts:
        raise ValueError(f"moe_top_k={cfg.moe_top_k} exceeds "
                         f"moe_experts={cfg.moe_experts}")
    if n_ep is not None and cfg.moe_experts % n_ep:
        raise ValueError(f"moe_experts={cfg.moe_experts} not divisible "
                         f"by the expert-parallel axis size {n_ep}")


def init_transformer(key, cfg: TransformerConfig = TransformerConfig(),
                     dtype=jnp.float32) -> Params:
    """Flat params: tok/pos embeddings, per layer the attention's
    projections (fused qkv + out, or the latent attention's and its
    indexer's), the FFN (dense, or router and experts) and 2 norms, the
    final norm; the LM head is tied to the token embedding unless
    ``cfg.tied_head`` is off (``head_W``)."""
    _check_moe(cfg)
    _check_arch(cfg)
    d, ff = cfg.d_model, cfg.d_ff
    params: Params = {}
    # (a hybrid stack's attention layers draw a sixth key)
    keys = iter(jax.random.split(
        key, 2 + (6 if cfg.hybrid else 5) * cfg.n_layers))

    def dense(shape):
        return _dense(next(keys), shape, dtype)

    params["tok_emb"] = 0.02 * jax.random.normal(
        next(keys), (cfg.vocab, d), dtype)
    if _has_pos_table(cfg):     # rope needs no position table
        params["pos_emb"] = 0.02 * jax.random.normal(
            next(keys), (cfg.max_seq, d), dtype)
    for i in range(cfg.n_layers):
        p = f"L{i}"
        params.update(attention_kind(cfg, i).init(keys, dtype, p))
        if moe_layer(cfg, i) and cfg.moe_router == "grouped":
            params.update(_moe.init_moe_held(
                next(keys), d, cfg.moe_d_ff or ff, cfg.moe_experts,
                cfg.moe_held or (0, cfg.moe_experts), cfg.moe_shared,
                dtype, prefix=f"{p}_moe", router_bias=cfg.moe_router_bias))
        elif moe_layer(cfg, i):
            params.update(_moe.init_moe(
                next(keys), d, ff, cfg.moe_experts, dtype,
                prefix=f"{p}_moe"))
        elif cfg.ffn == "swiglu":
            params[f"{p}_ff1_W"] = dense((d, ff))       # gate
            params[f"{p}_ff3_W"] = dense((d, ff))       # up
            params[f"{p}_ff2_W"] = dense((ff, d))       # down
        else:
            params[f"{p}_ff1_W"] = dense((d, ff))
            params[f"{p}_ff1_b"] = jnp.zeros((ff,), dtype)
            params[f"{p}_ff2_W"] = dense((ff, d))
            params[f"{p}_ff2_b"] = jnp.zeros((d,), dtype)
        for ln in ("ln1",) if cfg.parallel_block else ("ln1", "ln2"):
            params[f"{p}_{ln}_g"] = jnp.ones((d,), dtype)
            if cfg.norm == "ln":
                params[f"{p}_{ln}_b"] = jnp.zeros((d,), dtype)
    params["lnf_g"] = jnp.ones((d,), dtype)
    if cfg.norm == "ln":
        params["lnf_b"] = jnp.zeros((d,), dtype)
    if not cfg.tied_head:
        params["head_W"] = jax.random.normal(
            jax.random.fold_in(key, 1), (d, cfg.vocab), dtype) / np.sqrt(d)
    return params


def _head(params: Params, x):
    """The LM head: ``x @ head_W`` where the dict has one (an untied
    head), else the tied ``x @ tok_emb.T`` — through the int8 kernel when
    the serving dict carries ``head::q8`` (quantize_lm). At production
    vocab sizes this is THE decode-bandwidth matmul; the embedding
    GATHER keeps the full-precision tok_emb (it reads only B rows per
    step, negligible traffic)."""
    if "head::q8" in params:
        return _mm(params, "head", x)       # one q8 dispatch path only
    if "head_W" in params:
        return x @ params["head_W"]
    return x @ params["tok_emb"].T


def quantize_lm(params: Params) -> Params:
    """Weight-only int8 SERVING copy of an LM's DENSE projection
    weights: every per-block 2-D projection (qkv / out / ff*) is
    replaced by
    ``name::q8`` (int8) + ``name::scale`` (f32 per output channel),
    and the tied head gets an int8 copy (``head::q8``) while tok_emb
    stays full precision for the embedding gather; biases and norms
    are untouched.
    Use with the single-device inference paths (``greedy_decode``,
    ``prefill``) — training and the sharded forward reject quantized
    dicts loudly (the original keys are gone). Dense PROJECTIONS are
    4× smaller than f32; the embedding table itself grows 1.25×
    (f32 gather copy + int8 head copy) — the head quantization buys
    decode BANDWIDTH (int8 streamed per step), not footprint, so at
    embedding-dominated sizes the dict shrinks less than 4× overall.
    MoE expert stacks (3-D, einsum-dispatched) and embeddings stay full
    precision — for dense models the quantized projections are the
    decode-bandwidth bulk."""
    out = {}
    for k, v in params.items():
        if (k.endswith("_W") and v.ndim == 2
                and ("_qkv_" in k or "_out_" in k or "_ff" in k)):
            q, s = quantize_q8(v)
            out[k + "::q8"] = q
            out[k + "::scale"] = s.reshape(-1)
        else:
            out[k] = v
    # the tied head gets an int8 COPY (tok_emb stays for the gather):
    # at production vocab the head is the decode-bandwidth matmul
    qh, sh = quantize_q8(jnp.transpose(params["tok_emb"]))
    out["head::q8"] = qh
    out["head::scale"] = sh.reshape(-1)
    return out


def _ffn(params: Params, i: int, y, cfg: TransformerConfig,
         moe_axis: Optional[str], stats_sink: Optional[list] = None):
    """Layer ``i``'s FFN: dense, or in an expert layer the switch MoE
    (expert-parallel over ``moe_axis`` inside shard_map, single-device
    reference routing when ``moe_axis`` is None) or the dropless grouped
    MoE over the experts this chip holds (its counters appended to
    ``stats_sink``). Returns (out, aux)."""
    p = f"L{i}"
    if not moe_layer(cfg, i):
        if cfg.ffn == "swiglu":
            gate = jax.nn.silu(_mm(params, f"{p}_ff1_W", y))
            up = _mm(params, f"{p}_ff3_W", y)
            return _mm(params, f"{p}_ff2_W", gate * up), 0.0
        h = jax.nn.gelu(_mm(params, f"{p}_ff1_W", y)
                        + params[f"{p}_ff1_b"])
        return _mm(params, f"{p}_ff2_W", h) + params[f"{p}_ff2_b"], 0.0
    b, l, d = y.shape
    t = b * l
    flat = y.reshape(t, d)
    if cfg.moe_router == "grouped":
        out, stats = _moe.moe_ffn_held(
            params, flat, held=cfg.moe_held or (0, cfg.moe_experts),
            top_k=cfg.moe_top_k, n_groups=cfg.moe_groups,
            topk_groups=cfg.moe_topk_groups, scale=cfg.moe_scale,
            prefix=f"{p}_moe")
        if stats_sink is not None:
            stats_sink.append(stats)
        return out.reshape(b, l, d), 0.0
    cap = cfg.moe_capacity
    if moe_axis is None:
        out, aux = _moe.moe_ffn_reference(params, flat, capacity=cap,
                                          prefix=f"{p}_moe",
                                          top_k=cfg.moe_top_k)
    else:
        out, aux = _moe.moe_ffn_shard(params, flat, capacity=cap,
                                      ep_axis=moe_axis,
                                      prefix=f"{p}_moe",
                                      top_k=cfg.moe_top_k)
    return out.reshape(b, l, d), aux


def _layer(params: Params, i: int, x, cfg: TransformerConfig, attend,
           moe_axis: Optional[str] = None,
           stats_sink: Optional[list] = None):
    """One pre-norm decoder layer, written once: norm, attention,
    residual; norm, FFN, residual; or with ``cfg.parallel_block`` one
    norm whose output both take, ``x + attn + ffn``. ``attend(kind, p,
    y) -> (out, kept)`` is the form of layer ``i``'s attention kind the
    caller runs on the normed input (a full sequence, a chunk over a
    growing cache, one position) with what that form hands back; it is
    called here, once, so a caller's closure sees its loop's current
    caches. Returns (x, moe aux, kept)."""
    p = f"L{i}"
    if cfg.parallel_block:
        with scope("lm.attn"):
            y = _norm(params, f"{p}_ln1", x, cfg)
            a, kept = attend(attention_kind(cfg, i), p, y)
        with scope("lm.ffn"):
            out, aux = _ffn(params, i, y, cfg, moe_axis, stats_sink)
            return x + a + out, aux, kept
    with scope("lm.attn"):
        out, kept = attend(attention_kind(cfg, i), p,
                           _norm(params, f"{p}_ln1", x, cfg))
        x = x + out
    with scope("lm.ffn"):
        y = _norm(params, f"{p}_ln2", x, cfg)
        out, aux = _ffn(params, i, y, cfg, moe_axis, stats_sink)
        return x + out, aux, kept


def _given(kind, handed: Optional[dict]) -> dict:
    """The keyword under which a kind that takes part in a hybrid
    stack's hand-over (``kind.shares``) is given ``handed``: the dict in
    which the layers of ONE forward (a sequence, a chunk, a decode
    step) leave what later layers of that forward read, made where the
    forward's layer loop is. Nothing for any other kind."""
    return {"handed": handed} if kind.shares else {}


def _block(params: Params, i: int, x, cfg: TransformerConfig, attn_fn,
           pos, moe_axis: Optional[str] = None,
           handed: Optional[dict] = None):
    """The layer over a full sequence; ``attn_fn(q, k, v) -> out``
    supplies the (possibly sequence-parallel) attention where the kind
    takes one; ``pos`` are the GLOBAL positions of the L rows (rope
    consumes them; ignored otherwise). Returns (x, moe_aux, what the
    layer cached: the prefill path hands it out as the decode cache)."""
    return _layer(params, i, x, cfg,
                  lambda kind, p, y: kind.full(params, p, y, pos, attn_fn,
                                               **_given(kind, handed)),
                  moe_axis)


def _check_seq(global_len: int, cfg: TransformerConfig) -> None:
    """Static-shape guard: out-of-range position gathers would silently
    CLAMP to pos_emb's last row under jit, not raise."""
    if global_len > cfg.max_seq:
        raise ValueError(
            f"sequence length {global_len} exceeds max_seq={cfg.max_seq}")


def _forward(params: Params, tokens, pos, cfg: TransformerConfig,
             attn_fn, block=None, last_only: bool = False):
    """Shared body: tokens (B, L) int32, pos (L,) global positions;
    ``block`` swaps the decoder-block implementation (the 3-D form
    passes its tensor-parallel block) — one forward for every path.
    Returns (logits, summed moe aux loss; 0.0 for dense blocks, what
    each layer cached, where the block hands that back). With
    ``last_only`` the logits are wanted for the last position alone: a
    hybrid stack's layers that cache nothing then run on that position
    only (the others' outputs would feed no cache and no logit)."""
    # what a hybrid stack's layers hand on, for this one forward
    block = block or functools.partial(_block, handed={})
    with scope("lm.embed"):
        x = params["tok_emb"][tokens]
        if _has_pos_table(cfg):
            x = x + params["pos_emb"][pos]   # rope positions live in-block
    aux_total, cached = 0.0, []
    for i in range(cfg.n_layers):
        if last_only and cfg.hybrid and i == cfg.hybrid.cached_layers:
            x = x[:, -1:]
        def run_block(p, xx, _i=i):
            return block(p, _i, xx, cfg, attn_fn, pos)
        if cfg.remat:
            # checkpoint boundary = one decoder block (collectives inside
            # sp/tp blocks are re-executed in the backward — the usual
            # ring-attention remat shape)
            run_block = jax.checkpoint(run_block)
        x, aux, *rows = run_block(params, x)
        cached.extend(rows)
        aux_total = aux_total + aux
    with scope("lm.head"):
        x = _norm(params, "lnf", x, cfg)
        return _head(params, x), aux_total, cached      # tied head


def prefill(params: Params, prompt, *,
            cfg: TransformerConfig = TransformerConfig(),
            total: Optional[int] = None, mesh=None, attn: str = "ring",
            dp_axis: str = "dp", sp_axis: str = "sp",
            chunk: Optional[int] = None):
    """Parallel prompt ingestion: ONE causal forward over the (B, P)
    prompt yields every layer's (k, v) projections — the decode KV
    cache — plus the last position's logits, instead of the O(P)
    sequential scan the from-scratch decode pays. With ``mesh``, the
    forward runs SEQUENCE-PARALLEL (ring/zigzag/ulysses over
    ``sp_axis``), so prompts longer than one device's memory prefill
    across the mesh — the long-context inference counterpart of the
    sharded train step.

    Returns ``(caches, last_logits)``: caches is the
    ``L{i}_{k,v} -> (B, total, H_kv, Dh)`` dict :func:`greedy_decode`
    uses (H_kv = ``kv_heads(cfg)``, which is where GQA's group-factor
    cache shrink shows up; zero-padded to ``total``, default P), or for
    latent attention ``L{i}_ckv -> (B, total, kv_rank + rope_dim)`` and,
    where it has an indexer, ``L{i}_ik -> (B, total, index_dim)``;
    last_logits is (B, vocab). :func:`decode_caches` turns them into
    what :func:`decode_from` scans over. A hybrid stack's layers hand
    out what the scan carries (``models/attention_kinds.py``): a
    state-space layer ``L{i}_conv`` and ``L{i}_ssm`` with no axis of
    positions, an attention layer ``L{i}_{k,v}`` as (B, H_kv / 2, S,
    2 Dh) pairs with S the window's slots where the layer has one, a
    cross layer and a memory unit nothing; the layers that cache
    nothing run for the last position only. A stack with an
    ``attn_pattern`` hands its (k, v) out as the scan carries them too,
    (B, H_kv, S, Dh), rolling in its window layers. With ``chunk``
    (single-device, kinds whose caches have that one form: latent
    attention, a hybrid stack, an attention pattern; it must divide P)
    the prompt goes through
    the layers that many positions at a time over the growing cache, so
    a long prompt's activations exist for one chunk only.
    Dense and MoE configs single-device; the
    sharded path is dense-only (expert sharding composes with
    training's dp, not with replicated-param prefill)."""
    b, p_len = prompt.shape
    if p_len < 1:
        raise ValueError("prompt must contain at least one token")
    _check_arch(cfg)
    total = p_len if total is None else total
    if total < p_len:
        raise ValueError(f"total={total} shorter than the prompt {p_len}")
    _check_seq(total, cfg)
    cfg_fwd = dataclasses.replace(cfg, remat=False)  # capture ≠ remat
    tokens = prompt.astype(jnp.int32)

    if chunk:
        if (mesh is not None or p_len % chunk
                or not all(attention_kind(cfg, i).one_form
                           for i in range(cfg.n_layers))):
            raise ValueError(
                "chunk is for a single device and kinds whose caches "
                "have one form, prefill's and the scan's (latent "
                "attention, a hybrid stack's layers, an attention "
                f"pattern's), and must divide the prompt ({p_len})")
        return _prefill_chunked(params, tokens, cfg_fwd, total, chunk)
    if mesh is None:
        # backend="auto": the fused flash kernel on TPU — prefilling a
        # long prompt is exactly the workload whose (P, P) score matrix
        # must not land in HBM; off-TPU this resolves to the XLA oracle
        logits, _, kvs = _forward(
            params, tokens, jnp.arange(p_len), cfg_fwd,
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            backend="auto",
                                            window=cfg.window),
            last_only=True)
    else:
        _check_sharded(cfg)
        if cfg.moe_experts:
            raise ValueError("sequence-parallel prefill supports dense "
                             "configs; MoE prefills single-device")
        if cfg.window and attn != "ring":
            raise ValueError("sequence-parallel sliding-window prefill "
                             "runs the banded ring (attn='ring')")
        n_sp = mesh.shape[sp_axis]
        attn_shard = _attn_shard_fn(attn, sp_axis, n_sp, cfg)

        def shard_fwd(params, toks):
            l_loc = toks.shape[1]
            pos = _shard_pos(attn, sp_axis, n_sp, l_loc)
            logits, _, rows = _forward(params, toks, pos, cfg_fwd,
                                       attn_shard)
            # leaf by leaf over the layers: (nl, B, Lloc, Hkv, hd)
            return logits, tuple(jnp.stack(leaf) for leaf in zip(*rows))

        tokens_z, perm = _maybe_zigzag(attn, n_sp, tokens)
        # inference batches are often smaller than the training dp
        # size: when B doesn't divide it, replicate the batch axis and
        # keep only the sequence sharded (the memory that matters at
        # long context is the L axis anyway)
        bspec = dp_axis if b % mesh.shape[dp_axis] == 0 else None
        fn = shard_map(
            shard_fwd, mesh=mesh,
            in_specs=(P(), P(bspec, sp_axis)),
            out_specs=(P(bspec, sp_axis), P(None, bspec, sp_axis)))
        logits, stacked = fn(params, tokens_z)
        if perm is not None:                 # back to standard order
            inv = perm.argsort()
            logits = logits[:, inv]
            stacked = tuple(leaf[:, :, inv] for leaf in stacked)
        kvs = [tuple(leaf[i] for leaf in stacked)
               for i in range(cfg.n_layers)]

    caches = {}
    for i, rows in enumerate(kvs):
        caches.update(attention_kind(cfg, i).padded(
            f"L{i}", rows, total, params["tok_emb"].dtype))
    return caches, logits[:, -1].astype(jnp.float32)


def _prefill_chunked(params: Params, tokens, cfg: TransformerConfig,
                     total: int, chunk: int):
    """:func:`prefill`, ``chunk`` positions of every row at a time: a
    chunk writes its rows into the caches and attends what they hold by
    then (its own positions and all before). A hybrid stack's layers
    that cache nothing (its cross-decoder) run once, on the last
    position of the last chunk, which is all the last logits need."""
    b, p_len = tokens.shape
    caches = _layer_caches(cfg, lambda kind, p: kind.empty(
        p, b, total, params["tok_emb"].dtype))
    n_cached = cfg.hybrid.cached_layers if cfg.hybrid else cfg.n_layers

    def layers(x, pos, caches, start, handed, which):
        for i in which:
            x, _, caches = _layer(
                params, i, x, cfg, lambda kind, p, y: kind.chunk(
                    params, p, y, pos, caches, start,
                    **_given(kind, handed)))
        return x, caches

    def one_chunk(caches, toks_start):
        toks, start = toks_start
        pos = start + jnp.arange(chunk)
        with scope("lm.embed"):
            x = params["tok_emb"][toks]
        handed = {}
        x, caches = layers(x, pos, caches, start, handed, range(n_cached))
        return caches, (x[:, -1], {k: v[:, -1] for k, v in handed.items()})

    caches, (last, handed) = lax.scan(
        one_chunk, caches,
        (tokens.reshape(b, p_len // chunk, chunk).transpose(1, 0, 2),
         jnp.arange(0, p_len, chunk)))
    x, _ = layers(last[-1][:, None], jnp.arange(p_len - 1, p_len), caches,
                  p_len - 1, {k: v[-1][:, None] for k, v in handed.items()},
                  range(n_cached, cfg.n_layers))
    with scope("lm.head"):
        logits = _head(params, _norm(params, "lnf", x, cfg))
    return caches, logits[:, 0].astype(jnp.float32)


def _layer_caches(cfg: TransformerConfig, form) -> Params:
    """Every layer's caches: the union of ``form(kind, p)`` over them."""
    caches = {}
    for i in range(cfg.n_layers):
        caches.update(form(attention_kind(cfg, i), f"L{i}"))
    return caches


def _selector(cfg: TransformerConfig, temperature: float,
              top_k: Optional[int], key):
    """``select(logits (B, vocab), t) -> (B,) int32``: the argmax when
    ``temperature`` is 0, else a categorical sample of
    logits/temperature from ``fold_in(key, t)``, optionally of the
    ``top_k`` highest logits only."""
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")

    def select(logits, t):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits.astype(jnp.float32) / temperature
        if top_k is not None and top_k < cfg.vocab:
            kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
            lg = jnp.where(lg >= kth, lg, _NEG_INF)
        return jax.random.categorical(
            jax.random.fold_in(key, t), lg, axis=-1).astype(jnp.int32)

    return select


def decode_caches(caches: Params, *, cfg: TransformerConfig, p_len: int,
                  total: int, kv_q8: bool = False) -> Params:
    """:func:`prefill`'s caches (of a ``p_len`` prompt, padded to
    ``total``) as the decode scan carries them. Grouped-query caches go
    from prefill's public (B, S, H_kv, D) to (B, H_kv, S, D) — one
    transpose at the boundary, not one per step —, are quantized under
    ``kv_q8`` (int8 rows, ``L{i}_{k,v}s`` scales) and folded into the
    rolling layout where the window is shorter than ``total``. Latent
    caches and a hybrid stack's are scanned as prefill lays them out; a
    state and a differential layer's rolling buffer get room for the one
    snapshot that lets :func:`decode_from` take a turn back."""
    return _layer_caches(cfg, lambda kind, p: kind.scanned(
        p, caches, p_len, total, kv_q8))


def _decode_step(params: Params, cfg: TransformerConfig, b: int,
                 kv_q8: bool, select, feed, stats: bool):
    """The scan body of every decode: ``step((caches, cur), t)`` feeds
    ``feed(t, cur)`` at position ``t``, writes the position's cache
    rows, attends the cache, and selects the next token. Caches are the
    layout of :func:`decode_caches`. With ``stats`` a step also yields
    its counters: the positions an indexer selected in each layer
    (``selected``, -1 where the query saw fewer; absent where attention
    reads its cache whole), and from each grouped
    expert layer ``held_assignments``, ``experts_touched`` and the
    experts every token was routed to (``experts``)."""
    # the switch router's per-step routing group = B tokens; clamp
    # dispatch capacity to it
    step_cfg = (dataclasses.replace(cfg, moe_capacity=min(cfg.moe_capacity,
                                                          b))
                if cfg.moe_experts and cfg.moe_router == "switch" else cfg)

    def step(carry, t):
        caches, cur = carry
        with scope("lm.embed"):
            tok = feed(t, cur)                              # (B,)
            x = params["tok_emb"][tok]                      # (B, D)
            if _has_pos_table(cfg):
                x = x + params["pos_emb"][t]
            x = x[:, None, :]                               # (B, 1, D)
        selected, moe_stats = [], []
        handed = {}     # what a hybrid stack's layers hand on, this step
        for i in range(cfg.n_layers):
            x, _, (caches, idx) = _layer(
                params, i, x, step_cfg, lambda kind, p, y: kind.step(
                    params, p, y, t, caches, kv_q8, **_given(kind, handed)),
                None, moe_stats)
            if idx is not None:
                selected.append(idx[:, 0])
        with scope("lm.head"):
            x = _norm(params, "lnf", x, cfg)
            logits = _head(params, x)[:, 0]             # (B, vocab)
            nxt = select(logits, t)
        if not stats:
            return (caches, nxt), nxt
        out = {k: jnp.stack([m[k] for m in moe_stats])
               for k in (moe_stats[0] if moe_stats else ())}
        if selected:
            out["selected"] = jnp.stack(selected)
        return (caches, nxt), (nxt, out)

    return step


def _scan_from(params: Params, caches: Params, first_ids, start, n_new: int,
               *, cfg: TransformerConfig, kv_q8: bool, select,
               stats: bool = False, feed=lambda t, cur: cur):
    """``n_new`` positions from ``start`` over caches in the decode
    layout: ``first_ids`` (B,) is fed at ``start``, each later position
    the token selected before it (or what ``feed(t, selected before)``
    says). Returns (tokens (n_new, B), caches, per-step counters or
    None)."""
    step = _decode_step(params, cfg, first_ids.shape[0], kv_q8, select,
                        feed, stats)
    with scope("lm.decode"):
        (caches, _), ys = lax.scan(step, (caches, first_ids.astype(jnp.int32)),
                                   start + jnp.arange(n_new))
    tokens, counters = ys if stats else (ys, None)
    return tokens, caches, counters


@functools.partial(
    jax.jit,
    static_argnames=("n_new", "cfg", "temperature", "top_k", "kv_q8",
                     "stats"),
    donate_argnames=("caches",))
def decode_from(params: Params, caches: Params, first_ids, start,
                n_new: int, *, cfg: TransformerConfig = TransformerConfig(),
                temperature: float = 0.0, top_k: Optional[int] = None,
                key=None, kv_q8: bool = False, stats: bool = False):
    """The session entry: decode ``n_new`` positions from position
    ``start`` over caches that exist already, built by
    ``decode_caches(prefill(..., total=T)[0], p_len=start, total=T)``
    with ``T >= start + n_new`` or handed back by an earlier call.

    ``first_ids`` (B,) is the token at position ``start`` (after a
    prefill, the one selected from its last logits; in a later turn,
    whatever the session feeds next); positions ``start + 1 ...`` are
    fed the token selected before them. One jitted program, cached on
    the shapes and every keyword but ``key``; ``start`` is an operand,
    so turns at other positions of one cache shape share it.

    Returns ``(tokens (B, n_new), caches)``: the token selected after
    each scanned position (greedy, or sampled as :func:`greedy_decode`
    samples), and the caches with those positions written. ``caches``
    is DONATED: go on with the returned one. A turn writes positions
    ``>= start`` only, and reads none it has not written, so a cache
    taken back to ``start`` needs no clearing (a ROLLING cache, where
    the window is shorter than the whole, reuses its slots: to take
    that one back, keep a copy). A hybrid stack's recurrent states and
    rolling buffers keep that copy themselves, ONE, of the last turn's
    ``start``: a turn from the same ``start`` as the turn before finds
    them as that turn found them (``turn_start`` of
    models/attention_kinds.py), a turn from anywhere else takes them to
    stand at its ``start``. With ``stats`` a third
    value holds each step's counters, stacked over the steps:
    ``selected`` (n_new, layers, B, K) the cache positions latent
    attention's indexer chose, -1 where fewer than K existed (no such
    entry where attention reads its cache whole), and from the grouped
    expert layers ``held_assignments`` / ``experts_touched`` (n_new,
    expert layers) and ``experts`` (n_new, expert layers, B, top_k),
    the experts each token was routed to."""
    _check_arch(cfg)
    if cfg.moe_experts:
        _check_moe(cfg)
    start = jnp.asarray(start, jnp.int32)
    caches = {**caches, **_layer_caches(
        cfg, lambda kind, p: kind.turn_start(p, caches, start))}
    tokens, caches, counters = _scan_from(
        params, caches, first_ids, start, n_new,
        cfg=cfg, kv_q8=kv_q8,
        select=_selector(cfg, temperature, top_k, key), stats=stats)
    tokens = jnp.transpose(tokens, (1, 0))
    return (tokens, caches, counters) if stats else (tokens, caches)


@functools.partial(
    jax.jit,
    static_argnames=("n_new", "cfg", "temperature", "top_k",
                     "use_prefill", "mesh", "attn", "dp_axis", "sp_axis",
                     "kv_q8"))
def greedy_decode(params: Params, prompt, n_new: int, *,
                  cfg: TransformerConfig = TransformerConfig(),
                  temperature: float = 0.0,
                  top_k: Optional[int] = None,
                  key=None, use_prefill: bool = False, mesh=None,
                  attn: str = "ring", dp_axis: str = "dp",
                  sp_axis: str = "sp",
                  kv_q8: bool = False) -> jnp.ndarray:
    """KV-cached decoding: (B, P) int32 prompt → (B, P+n_new).

    The inference half of the LM family (training: make_train_step).
    The whole call is ONE jitted program, cached on the prompt shape,
    the param structure and every keyword but ``key`` — called eagerly,
    the position scan's body was a fresh closure per call and so was
    recompiled on every request.
    One ``lax.scan`` over positions with per-layer (B, L, H_kv, Dh)
    caches in the carry (H_kv < H under GQA — the cache shrinks by the
    group factor) — static shapes throughout, so the whole decode is one
    compiled program; each step attends its single query against the
    cache under an iota≤t mask. Inside the prompt the next input is the
    given token (prefill and generation share one code path); after it,
    the selected token: argmax when ``temperature`` is 0 (greedy — the
    default, pinned token-exact against re-running the FULL forward at
    every prefix), otherwise a categorical sample of logits/temperature
    (requires ``key``), optionally truncated to the ``top_k`` highest
    logits. Sampling is deterministic per (key, position).

    MoE configs decode with capacity-bounded switch routing per STEP:
    the routing group at position t is that step's B tokens (one per
    batch row), with the effective capacity ``min(moe_capacity, B)`` —
    a bucket can never hold more than B tokens, so the clamp changes
    no drop decision, only the dispatch shapes. When no bucket
    overflows anywhere (capacity ≥ its worst-case load), decode is
    token-exact against the full-forward oracle; under overflow the
    drop ORDER differs (the oracle's cumulative token order runs over
    the whole (B, L) tile, a step's over its B tokens), matching the
    train-time rule that capacity semantics follow the routing group.

    ``kv_q8=True`` stores the KV caches int8 with per-row f32 scales
    (ops/decode.quantize_kv): the cache is the dominant decode byte
    stream, so its HBM traffic halves. Rows quantize as they are
    written (prefill caches quantize once at the boundary); the fused
    decode kernel folds the scales into its contractions without ever
    materializing a dequantized cache. A serving knob, orthogonal to
    ``quantize_lm`` (int8 weights) — the two compose into the full
    int8 serving story.

    ``use_prefill=True`` ingests the prompt with :func:`prefill` — one
    parallel causal forward instead of P sequential steps — then scans
    only the ``n_new`` generation positions: it is :func:`prefill`, the
    first token, and :func:`decode_from`'s scan in one program. With ``mesh`` the prefill
    runs sequence-parallel (``attn`` selects ring/zigzag/ulysses over
    ``dp_axis``/``sp_axis``), so prompts at training-scale context
    lengths decode without ever holding full attention on one device.
    Dense configs produce the same tokens either way (the prompt caches
    are the same projections computed batched); MoE configs match as
    long as no routing bucket overflows — prefill routes the whole
    (B, P) prompt as one group (the oracle grouping) while the scan
    routes B tokens per step, so under overflow the two drop DIFFERENT
    tokens and may diverge, the same caveat as decode-vs-oracle."""
    _check_arch(cfg)
    if cfg.moe_experts:
        _check_moe(cfg)
    select = _selector(cfg, temperature, top_k, key)
    b, p_len = prompt.shape
    if p_len < 1:
        raise ValueError("prompt must contain at least one token "
                         "(an empty prompt would silently return an "
                         "empty continuation)")
    total = p_len + n_new
    _check_seq(total, cfg)

    if use_prefill and n_new == 0:
        return prompt.astype(jnp.int32)
    if use_prefill:
        with scope("lm.prefill"):
            caches, last_logits = prefill(
                params, prompt, cfg=cfg, total=total, mesh=mesh, attn=attn,
                dp_axis=dp_axis, sp_axis=sp_axis)
            caches = decode_caches(caches, cfg=cfg, p_len=p_len,
                                   total=total, kv_q8=kv_q8)
        with scope("lm.first_token"):
            tok1 = select(last_logits, p_len - 1)
        # remaining n_new - 1 positions ride the session entry's scan
        emitted, _, _ = _scan_from(params, caches, tok1, p_len, n_new - 1,
                                   cfg=cfg, kv_q8=kv_q8, select=select)
        gen = jnp.concatenate(
            [tok1[:, None], jnp.transpose(emitted, (1, 0))], axis=1)
    else:
        # from scratch: empty caches, each row written (and under
        # ``kv_q8`` quantized) as its position is scanned
        caches = _layer_caches(cfg, lambda kind, p: kind.empty(
            p, b, total, params["tok_emb"].dtype, kv_q8))
        # position t reads its input from `prompt` while t < p_len, else
        # the previously generated token riding the carry
        pad = jnp.zeros((b, total - p_len), jnp.int32)
        given = jnp.concatenate([prompt.astype(jnp.int32), pad], axis=1)
        emitted, _, _ = _scan_from(
            params, caches, given[:, 0], 0, total, cfg=cfg, kv_q8=kv_q8,
            select=select,
            feed=lambda t, cur: jnp.where(t < p_len, given[:, t], cur))
        # emitted[t] is the model's prediction AFTER seeing position t
        gen = jnp.transpose(emitted, (1, 0))[:, p_len - 1:total - 1]
    # output = prompt ‖ generated continuation
    return jnp.concatenate([prompt.astype(jnp.int32), gen], axis=1)


def transformer_apply(params: Params, tokens, *,
                      cfg: TransformerConfig = TransformerConfig()
                      ) -> jnp.ndarray:
    """Single-device oracle: (B, L) tokens → (B, L, vocab) logits."""
    _check_seq(tokens.shape[1], cfg)
    pos = jnp.arange(tokens.shape[1])
    return _forward(params, tokens, pos, cfg,
                    functools.partial(attention_reference, causal=True,
                                      window=cfg.window))[0]


def _attn_shard_fn(attn: str, sp_axis: str, n_sp: int,
                   cfg: TransformerConfig, n_heads: Optional[int] = None):
    """Resolve the sequence-parallel attention body; strict — a typo'd
    name or an infeasible head split must fail at factory time, never as
    a shape error deep inside a collective. ``n_heads`` overrides the
    head count the divisibility check sees (the 3-D form passes its
    per-tp-slice count)."""
    n_heads = cfg.n_heads if n_heads is None else n_heads
    if cfg.window and attn != "ring":
        raise ValueError(
            "sliding-window attention (cfg.window > 0) runs "
            "sequence-parallel as the BANDED contiguous ring "
            "(attn='ring'); zigzag balances full-causal work a window "
            "already bounds, and ulysses materializes full-sequence "
            "heads per device")
    if attn == "ring":
        return functools.partial(_ring_shard, axis=sp_axis,
                                 n_shards=n_sp, causal=True,
                                 window=cfg.window)
    if attn == "zigzag":
        return functools.partial(_ring_shard_zigzag, axis=sp_axis,
                                 n_shards=n_sp, causal=True)
    if attn == "ulysses":
        if n_heads % n_sp:
            raise ValueError(
                f"ulysses needs n_heads divisible by the {sp_axis} axis: "
                f"{n_heads} heads over {n_sp} devices")
        if kv_heads(cfg) % n_sp:
            raise ValueError(
                f"ulysses needs n_kv_heads divisible by the {sp_axis} "
                f"axis: {kv_heads(cfg)} kv heads over {n_sp} devices "
                f"(ring/zigzag have no such constraint)")
        return functools.partial(_ulysses_shard, axis=sp_axis,
                                 n_shards=n_sp, causal=True)
    raise ValueError(f"unknown attn {attn!r} "
                     f"(want 'ring', 'zigzag' or 'ulysses')")


def _shard_pos(attn: str, sp_axis: str, n_sp: int, l_loc: int):
    """This device's global positions: contiguous for ring/ulysses, the
    two-stripe layout for zigzag (parallel/ring_attention._zigzag_perm)
    — shared by every shard_step/shard_fwd body."""
    if attn == "zigzag":
        h = l_loc // 2
        my = lax.axis_index(sp_axis)
        return jnp.concatenate([my * h + jnp.arange(h),
                                (2 * n_sp - 1 - my) * h + jnp.arange(h)])
    return lax.axis_index(sp_axis) * l_loc + jnp.arange(l_loc)


def _maybe_zigzag(attn: str, n_sp: int, *seqs, pre_permuted: bool = False):
    """Apply the internal zigzag permutation to (B, L) sequence arrays
    at a step/apply boundary; identity for other schedules. Returns the
    permuted arrays plus the permutation (None when not zigzag) so a
    forward can un-permute its outputs.

    ``pre_permuted=True`` (zigzag only) declares the arrays already in
    zigzag layout — validated, not re-permuted (the caller permuted
    host-side via ``shard_batch(..., schedule="zigzag")``, avoiding the
    per-step cross-shard gather of sharded arrays)."""
    if attn != "zigzag":
        return (*seqs, None)
    _zigzag_check(seqs[0].shape[1], n_sp)
    perm = _zigzag_perm(seqs[0].shape[1], n_sp)
    if pre_permuted:
        return (*seqs, perm)
    return (*(s[:, perm] for s in seqs), perm)


def make_sharded_apply(cfg: TransformerConfig, mesh, *,
                       attn: str = "ring", dp_axis: str = "dp",
                       sp_axis: str = "sp"):
    """Jitted forward over the mesh: tokens P(dp, sp), attention
    sequence-parallel over ``sp``. Dense params are replicated; with
    ``cfg.moe_experts`` > 0 the expert stacks shard over dp and params
    must come from :func:`shard_params_moe`."""
    _check_arch(cfg)
    _check_sharded(cfg)
    n_sp = mesh.shape[sp_axis]
    attn_shard = _attn_shard_fn(attn, sp_axis, n_sp, cfg)
    moe_axis = dp_axis if cfg.moe_experts else None
    if cfg.moe_experts:
        _check_moe(cfg, mesh.shape[dp_axis])
    block = functools.partial(_block, moe_axis=moe_axis)
    suffix = param_specs_moe(dp_axis)

    def shard_fwd(params, tokens):
        l_loc = tokens.shape[1]
        _check_seq(l_loc * n_sp, cfg)
        pos = _shard_pos(attn, sp_axis, n_sp, l_loc)
        return _forward(params, tokens, pos, cfg, attn_shard,
                        block=block)[0]

    def apply(params, tokens):
        # specs derive from the ACTUAL param keys so the tree can never
        # drift from init_transformer's key set
        specs = {k: _spec_for(k, suffix) for k in params} \
            if cfg.moe_experts else P()
        # zigzag: permute in, un-permute out — callers see
        # standard order (perm is None otherwise)
        tokens, perm = _maybe_zigzag(attn, n_sp, tokens)
        fn = shard_map(shard_fwd, mesh=mesh,
                           in_specs=(specs, P(dp_axis, sp_axis)),
                           out_specs=P(dp_axis, sp_axis))
        out = fn(params, tokens)
        return out if perm is None else out[:, perm.argsort()]

    return jax.jit(apply)


def _mean_nll(logits, targets):
    """Mean next-token NLL — the ONE loss tail every execution form
    shares (a loss change here reaches dp/sp/tp/ep/pp alike)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def lm_loss_local(params, tokens, targets, cfg, attn_fn, pos, block=None):
    """Mean next-token NLL (+ weighted MoE aux loss) on this device's
    tile (targets pre-shifted by the caller — with a sharded sequence
    the shift crosses shard edges, so it happens host-side before
    sharding)."""
    with scope("lm.loss"):
        logits, aux, _ = _forward(params, tokens, pos, cfg, attn_fn,
                                  block=block)
        with scope("lm.head"):
            nll = _mean_nll(logits, targets)
        return nll + cfg.moe_aux_weight * aux


def param_specs_moe(ep_axis: str = "dp") -> Dict[str, object]:
    """Suffix→PartitionSpec for expert-parallel params: expert FFN
    stacks shard on their leading experts axis; the router replicates."""
    return {
        "_moe_w1": P(ep_axis), "_moe_b1": P(ep_axis),
        "_moe_w2": P(ep_axis), "_moe_b2": P(ep_axis),
    }


def shard_params_moe(params: Params, mesh, *, ep_axis: str = "dp"
                     ) -> Params:
    """device_put params where :func:`make_train_step` keeps them between
    steps: in an expert model the expert stacks sharded over ``ep_axis``
    and every other leaf replicated; in a dense model on more than one
    device every leaf split by rows (:func:`parallel.mesh.rows_layout`),
    as the optimizer state is, and gathered inside the step."""
    if _splits_rows(mesh, _has_experts(params)):
        return jax.device_put(params, rows_layout(mesh, params))
    specs = param_specs_moe(ep_axis)
    return {k: jax.device_put(v, NamedSharding(mesh, _spec_for(k, specs)))
            for k, v in params.items()}


def _has_experts(params: Params) -> bool:
    return any(_spec_for(k, param_specs_moe()) != P() for k in params)


def _splits_rows(mesh, moe: bool) -> bool:
    """Whether :func:`make_train_step` reduce-scatters the gradients and
    keeps weights and optimizer state split by rows: on a mesh of more
    than one device, in a dense model (an expert model spends dp on
    experts)."""
    return mesh.size > 1 and not moe


def init_opt_state(optimizer, params: Params, mesh):
    """``optimizer.init(params)`` laid out the way :func:`make_train_step`'s
    step returns it; ``params`` must already live on ``mesh``
    (:func:`shard_params_moe`). Where the step splits the state (a dense
    model on more than one device) every leaf is made in the layout of
    :func:`parallel.mesh.rows_layout` by one program, so no device holds
    more than its rows. Otherwise the moments inherit their parameter's
    layout, but what ``init`` makes from nothing (Adam's step count) is
    left uncommitted; the first step returns it committed to the mesh,
    and that one changed input type makes the SECOND step trace and
    compile the whole program again."""
    if _splits_rows(mesh, _has_experts(params)):
        layout = rows_layout(mesh, jax.eval_shape(optimizer.init, params))
        return jax.jit(optimizer.init, out_shardings=layout)(params)
    replicated = NamedSharding(mesh, P())

    def place(x):
        s = x.sharding
        on_mesh = isinstance(s, NamedSharding) and s.mesh == mesh
        return x if on_mesh else jax.device_put(x, replicated)

    return jax.tree.map(place, optimizer.init(params))


def make_train_step(cfg: TransformerConfig, mesh, optimizer, *,
                    attn: str = "ring", dp_axis: str = "dp",
                    sp_axis: str = "sp", grad_accum: int = 1,
                    zigzag_layout: bool = False, zero1: bool = False):
    """Jitted SPMD LM train step: ``step(params, opt_state, tokens,
    targets) -> (params, opt_state, loss)`` with tokens/targets sharded
    P(dp, sp). Every gradient crosses the wire once, summed over dp AND
    sp, and XLA runs that in line behind the leaf's weight-gradient
    matmul, hidden by nothing (PERF.md section 5). How, by what the mesh
    and the configuration are:

    - a dense model on more than one device: each device differentiates
      its share of the mean (its tile's loss over the device count)
      with respect to its own copy of the weights, and reduce-scatters
      each leaf along its rows over every device (``rows_spec`` in
      `parallel/mesh.py`), where the optimizer state lives
      (:func:`init_opt_state` puts it there). The update runs outside
      the shard_map, on each device's rows, so any optax transformation
      stays right: one that reads across a leaf (``clip_by_global_norm``)
      gets its sum over the devices from the partitioner. The weights
      stay split by rows between steps too (:func:`shard_params_moe`
      puts them there), and are all-gathered in their own dtype where
      the next step's shard_map takes them. A leaf whose rows do not
      divide is all-reduced, updated and kept whole;
    - one device, or an expert model: the sum is the transpose of the
      loss's pmean, no call of this function's, and the update is
      replicated (an expert leaf is summed over sp alone).

    shard_map's vma check stays ON. Every gradient leaf is written out
    in its own type before the optimizer reads it.

    ``grad_accum`` > 1 folds that many microbatches (split along each
    device's batch rows) in a lax.scan before the single optimizer
    update — activation memory ÷ grad_accum, numbers identical to the
    whole tile (the long-context lever that composes with cfg.remat:
    remat bounds per-layer activations, accumulation bounds the batch).

    With ``cfg.moe_experts`` > 0 the block FFNs are switch-MoE with
    experts sharded over the dp axis (the standard ep ≡ dp grouping:
    expert buckets ride all_to_all between data-parallel peers); params
    must then come from :func:`shard_params_moe`. Params and optimizer
    state laid out on the mesh beforehand (:func:`shard_params_moe`,
    :func:`init_opt_state`) keep the second step from recompiling.

    ``zigzag_layout=True`` (``attn="zigzag"`` only) declares tokens and
    targets ALREADY in zigzag order — feed batches through
    ``shard_batch(..., schedule="zigzag")``, which permutes host-side
    before device_put. The default path permutes inside the jitted step,
    which on P(dp, sp)-sharded arrays is a per-step cross-shard gather
    (ADVICE r2); the pre-permuted path removes it from steady state.

    ``zero1=True`` shards the OPTIMIZER STATE over the dp axis
    (parallel/zero1.py): gradients reduce-scatter instead of
    all-reducing, each dp rank updates only its 1/n_dp chunk of every
    parameter (Adam's m/v shrink by n_dp), and the updated chunks
    all-gather back — same wire traffic as the all-reduce, optimizer
    memory ÷ n_dp. The opt_state must come from
    :func:`parallel.zero1.init_state`. Elementwise optimizers only;
    dense configs (MoE already spends the dp axis on experts).
    Composes with ``grad_accum`` (the microbatch fold feeds the same
    reduce-scatter) and every ``attn`` schedule."""
    if zigzag_layout and attn != "zigzag":
        raise ValueError("zigzag_layout=True requires attn='zigzag'")
    if zero1 and cfg.moe_experts:
        raise ValueError("zero1 shards optimizer state over dp, "
                         "which MoE already spends on experts")
    _check_arch(cfg)
    _check_sharded(cfg)
    n_sp = mesh.shape[sp_axis]
    attn_shard = _attn_shard_fn(attn, sp_axis, n_sp, cfg)
    moe_axis = None
    if cfg.moe_experts:
        if grad_accum > 1:
            raise ValueError(
                "grad_accum > 1 with moe_experts > 0 would silently "
                "change the numbers: MoE capacity and the aux loss are "
                "defined per device tile, so quarter-size microbatches "
                "drop/route tokens differently than the whole tile")
        _check_moe(cfg, mesh.shape[dp_axis])
        moe_axis = dp_axis
    block = functools.partial(_block, moe_axis=moe_axis)
    suffix = param_specs_moe(dp_axis)
    scatter = _splits_rows(mesh, bool(cfg.moe_experts))
    devices, every_axis = mesh.size, tuple(mesh.axis_names)

    def exchange(g):
        if rows_spec(mesh, g.shape) == P():
            return lax.psum(g, every_axis)
        return lax.psum_scatter(g, every_axis, scatter_dimension=0,
                                tiled=True)

    def shard_step(params, tokens, targets):
        l_loc = tokens.shape[1]
        _check_seq(l_loc * n_sp, cfg)
        pos = _shard_pos(attn, sp_axis, n_sp, l_loc)

        def global_loss(p, tok, tgt):
            local = lm_loss_local(p, tok, tgt, cfg, attn_shard,
                                  pos, block=block)
            if scatter:
                # this device's share of the mean (1/n is exact for a
                # power of two, and scales a scalar, not a gradient);
                # the transpose of the sum sums nothing
                return lax.psum(local / devices, every_axis)
            return lax.pmean(lax.pmean(local, sp_axis), dp_axis)

        if scatter:
            # this device's own copy: the transpose of a replicated
            # weight's use would be an all-reduce of its gradient
            params = lax.pcast(params, every_axis, to="varying")
        if grad_accum == 1:
            loss, grads = jax.value_and_grad(global_loss)(
                params, tokens, targets)
        else:
            loss, grads = accum_value_and_grad(
                global_loss, params, (tokens, targets), grad_accum)
        if scatter:
            grads = {k: exchange(g) for k, g in grads.items()}
        # each leaf is written out in its own type before anything
        # reads it. Left alone on one device, XLA fuses optimizer.update
        # into the weight-gradient matmuls' output and they run at half
        # their speed: `subtract_add_fusion (bf16[4096,14336],
        # f32[4096,14336], ...)` 0.1982 s and `(bf16[14336,4096],
        # f32[14336,4096], ...)` 0.0821 s over 3 steps, 16.5 and 13.7 ms
        # an instance for a 7.3 ms matmul (ledger, PR 26; ISSUE 27). On
        # a mesh the exchange already stands between the two: there the
        # barrier moves a few small values to another memory space and
        # costs nothing (PERF.md section 6), and its only other effect is
        # the placement `tests/test_tpu_compile.py` pins.
        # (An expert leaf was summed over sp alone and stays varying
        # over dp, as its spec says.)
        return loss, {k: lax.optimization_barrier(g)
                      for k, g in grads.items()}

    def shard_step_zero1(params, opt_state, tokens, targets):
        """The ZeRO-1 body: loss/grad per rank, dp-mean via
        reduce-scatter, chunk update, all-gather (parallel/zero1.py).
        Lives INSIDE shard_map so the optimizer runs on per-rank
        chunks; the replicated path keeps its update outside."""
        l_loc = tokens.shape[1]
        _check_seq(l_loc * n_sp, cfg)
        pos = _shard_pos(attn, sp_axis, n_sp, l_loc)
        n_dp = mesh.shape[dp_axis]

        def local_loss(p, tok, tgt):
            return lm_loss_local(p, tok, tgt, cfg, attn_shard,
                                 pos, block=block)

        if grad_accum == 1:
            loss, grads = jax.value_and_grad(local_loss)(
                params, tokens, targets)
        else:
            # the microbatch fold composes: it returns the tile-mean
            # LOCAL loss/grads, which then ride the same sp-pmean +
            # dp reduce-scatter as the unaccumulated path
            loss, grads = accum_value_and_grad(
                local_loss, params, (tokens, targets), grad_accum)
        # sp first: grads must be identical along every non-dp axis
        # before the dp reduce-scatter
        grads = jax.tree.map(lambda g: lax.pmean(g, sp_axis), grads)
        with scope("lm.opt"):
            params, opt_state = _z1.update_chunks(
                optimizer, params, grads, opt_state, dp_axis, n_dp)
        return params, opt_state, lax.pmean(
            lax.pmean(loss, sp_axis), dp_axis)

    def lm_train_step(params, opt_state, tokens, targets):
        # specs derive from the ACTUAL param keys (cannot drift from
        # init_transformer; same pattern as the 3-D step)
        specs = {k: _spec_for(k, suffix) for k in params} \
            if cfg.moe_experts else P()
        # zigzag: tokens AND targets ride the same internal
        # permutation; the loss is a token mean, so no
        # un-permutation is needed — drop-in for the ring
        tokens, targets, _ = _maybe_zigzag(attn, n_sp, tokens, targets,
                                           pre_permuted=zigzag_layout)
        if zero1:
            st_specs = _z1.state_specs(opt_state, dp_axis)
            # check_vma off: the all_gather'd params ARE replicated
            # (chunks updated from dp-invariant inputs), but the static
            # varying-axes checker cannot prove it through all_gather
            mapped = shard_map(
                shard_step_zero1, mesh=mesh,
                in_specs=(P(), st_specs, P(dp_axis, sp_axis),
                          P(dp_axis, sp_axis)),
                out_specs=(P(), st_specs, P()), check_vma=False)
            return mapped(params, opt_state, tokens, targets)
        grad_specs = {k: rows_spec(mesh, v.shape)
                      for k, v in params.items()} if scatter else specs
        mapped = shard_map(
            shard_step, mesh=mesh,
            in_specs=(specs, P(dp_axis, sp_axis), P(dp_axis, sp_axis)),
            out_specs=(P(), grad_specs))
        loss, grads = mapped(params, tokens, targets)
        with scope("lm.opt"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if scatter:
                # each device makes and keeps its rows of the new
                # weights, beside those of the state: the next step
                # gathers them where the shard_map needs them whole (as
                # an output, a gathered weight costs a copy more)
                params = lax.with_sharding_constraint(
                    params, rows_layout(mesh, params))
                opt_state = lax.with_sharding_constraint(
                    opt_state, rows_layout(mesh, opt_state))
        return params, opt_state, loss

    return jax.jit(lm_train_step, donate_argnums=(0, 1))


def shard_batch(mesh, tokens, targets, dp_axis="dp", sp_axis="sp",
                schedule="contiguous"):
    """Place a (B, L) batch with batch over dp, sequence over sp.

    ``schedule="zigzag"`` permutes both arrays into zigzag sequence
    order HOST-SIDE before device_put (cheap numpy indexing, not a
    cross-shard collective) — the data path for train steps built with
    ``zigzag_layout=True``. Tokens and targets ride the same
    permutation, so the next-token pairing is preserved row-wise."""
    if schedule == "zigzag":
        n_sp = mesh.shape[sp_axis]
        _zigzag_check(np.shape(tokens)[1], n_sp)
        perm = _zigzag_perm(np.shape(tokens)[1], n_sp)
        tokens = np.asarray(tokens)[:, perm]
        targets = np.asarray(targets)[:, perm]
    elif schedule != "contiguous":
        raise ValueError(f"unknown schedule {schedule!r}")
    sharding = NamedSharding(mesh, P(dp_axis, sp_axis))
    with annotate("lm.shard_batch"):
        return (jax.device_put(tokens, sharding),
                jax.device_put(targets, sharding))


# ---------------------------------------------------------------------------
# 3-D parallel form: data x sequence x tensor (Megatron-style tp)
# ---------------------------------------------------------------------------
#
# Attention heads and MLP hidden units shard over ``mp``; activations stay
# replicated across mp at block boundaries via one psum after the
# attention out-projection and one after the second MLP matmul (the
# Megatron pattern). Composes with the sp ring: each mp slice runs the
# ring over ITS heads. Gradient flow needs no hand-written collectives —
# the loss is pmean'd over the data axes (dp, sp) ONLY; shard_map's
# transpose machinery then psums replicated-param cotangents over every
# axis they were broadcast to (including mp), while mp-sharded params
# keep their local slice gradients.
#
# tp weights use head-structured layouts so a PartitionSpec can split
# them per head rather than per raw column: qkv (d, 3, H, hd) sharded on
# H; out-proj (H, hd, d) sharded on H; MLP (d, ff)/(ff, d) sharded on ff.

def param_specs_3d(mp_axis: str = "mp") -> Dict[str, object]:
    """PartitionSpec per parameter-name PATTERN (suffix match)."""
    return {
        "_qkv_W": P(None, None, mp_axis, None),
        "_out_W": P(mp_axis, None, None),
        "_ff1_W": P(None, mp_axis),
        "_ff1_b": P(mp_axis),
        "_ff3_W": P(None, mp_axis),     # swiglu up (columns, like gate)
        "_ff2_W": P(mp_axis, None),
    }


def _spec_for(name: str, specs: Dict[str, object]):
    for suffix, spec in specs.items():
        if name.endswith(suffix):
            return spec
    return P()


def shard_params_3d(params: Params, mesh, cfg: TransformerConfig, *,
                    mp_axis: str = "mp") -> Params:
    """Reshape tp weights to head-structured layouts and device_put every
    param with its 3-D sharding (inverse: :func:`unshard_params_3d`)."""
    d, h = cfg.d_model, cfg.n_heads
    if kv_heads(cfg) != h:
        raise ValueError("the 3-D tp path shards the fused qkv by head "
                         "and supports MHA only; GQA composes with "
                         "dp/sp (make_train_step) in the current build")
    hd = d // h
    specs = param_specs_3d(mp_axis)
    out: Params = {}
    for name, w in params.items():
        if name.endswith("_qkv_W"):
            w = w.reshape(d, 3, h, hd)
        elif name.endswith("_out_W"):
            w = w.reshape(h, hd, d)
        out[name] = jax.device_put(
            w, NamedSharding(mesh, _spec_for(name, specs)))
    return out


def unshard_params_3d(params: Params, cfg: TransformerConfig) -> Params:
    """Back to the canonical 2-D layouts (for checkpoints / the oracle)."""
    d = cfg.d_model
    out: Params = {}
    for name, w in params.items():
        if name.endswith("_qkv_W"):
            w = jnp.asarray(w).reshape(d, 3 * d)
        elif name.endswith("_out_W"):
            w = jnp.asarray(w).reshape(d, d)
        out[name] = w
    return out


def _block_tp(params: Params, i: int, x, cfg: TransformerConfig, attn_fn,
              pos, mp_axis: str):
    """One decoder block on LOCAL tp slices; x enters and leaves
    replicated across mp. Rope rotates this slice's heads by the same
    global ``pos`` (per-head independent, so head sharding is free);
    swiglu shards gate/up columns and down rows like gelu's ff1/ff2."""
    p = f"L{i}"
    with scope("lm.attn"):
        y = _norm(params, f"{p}_ln1", x, cfg)
        w_qkv = params[f"{p}_qkv_W"]            # (d, 3, H/mp, hd) local
        q, k, v = (jnp.einsum("bld,dhk->blhk", y, w_qkv[:, t])
                   for t in range(3))           # (B, L, H/mp, hd)
        if cfg.rope:
            q = _rope(q, pos, cfg.rope_base)
            k = _rope(k, pos, cfg.rope_base)
        a = attn_fn(q, k, v)                    # this mp slice's heads
        partial = jnp.einsum("blhk,hkd->bld", a, params[f"{p}_out_W"])
        x = x + lax.psum(partial, mp_axis)      # Megatron sync point 1
    with scope("lm.ffn"):
        y = _norm(params, f"{p}_ln2", x, cfg)
        if cfg.ffn == "swiglu":
            h = (jax.nn.silu(y @ params[f"{p}_ff1_W"])
                 * (y @ params[f"{p}_ff3_W"]))
            return x + lax.psum(h @ params[f"{p}_ff2_W"], mp_axis), 0.0
        y = jax.nn.gelu(y @ params[f"{p}_ff1_W"] + params[f"{p}_ff1_b"])
        partial = y @ params[f"{p}_ff2_W"]
        return x + lax.psum(partial, mp_axis) + params[f"{p}_ff2_b"], 0.0


def make_train_step_3d(cfg: TransformerConfig, mesh, optimizer, *,
                       attn: str = "ring", dp_axis: str = "dp",
                       sp_axis: str = "sp", mp_axis: str = "mp",
                       grad_accum: int = 1, zigzag_layout: bool = False):
    """Jitted LM train step over a (dp, sp, mp) mesh. ``params`` must
    come from :func:`shard_params_3d`; tokens/targets are P(dp, sp).
    ``grad_accum`` and ``zigzag_layout`` as in :func:`make_train_step` —
    microbatch fold before the single optimizer update; host-side
    pre-permuted zigzag batches via ``shard_batch(schedule="zigzag")``."""
    if zigzag_layout and attn != "zigzag":
        raise ValueError("zigzag_layout=True requires attn='zigzag'")
    _check_arch(cfg)
    _check_sharded(cfg)
    n_sp = mesh.shape[sp_axis]
    n_mp = mesh.shape[mp_axis]
    if cfg.n_heads % n_mp:
        raise ValueError(f"n_heads={cfg.n_heads} not divisible by "
                         f"{mp_axis}={n_mp}")
    if kv_heads(cfg) != cfg.n_heads:
        raise ValueError("the 3-D tp path shards the fused qkv by head "
                         "and supports MHA only; GQA composes with "
                         "dp/sp (make_train_step) in the current build")
    if cfg.moe_experts:
        raise ValueError("MoE blocks are not supported on the 3-D tp "
                         "path; use make_train_step (experts over dp)")
    # the ulysses divisibility check sees the PER-TP-SLICE head count
    attn_shard = _attn_shard_fn(attn, sp_axis, n_sp, cfg,
                                n_heads=cfg.n_heads // n_mp)
    tp_block = functools.partial(_block_tp, mp_axis=mp_axis)
    specs = param_specs_3d(mp_axis)

    def shard_step(params, tokens, targets):
        l_loc = tokens.shape[1]
        _check_seq(l_loc * n_sp, cfg)
        pos = _shard_pos(attn, sp_axis, n_sp, l_loc)

        def global_loss(p, tok, tgt):
            local = lm_loss_local(p, tok, tgt, cfg, attn_shard,
                                  pos, block=tp_block)
            # pmean over the DATA axes only: the mp axis carries the
            # same loss replicated, and omitting it keeps the
            # backward-pass psum of replicated-param cotangents at the
            # right scale (sum of per-slice contributions, unscaled)
            return lax.pmean(lax.pmean(local, sp_axis), dp_axis)

        if grad_accum == 1:
            return jax.value_and_grad(global_loss)(params, tokens,
                                                   targets)
        return accum_value_and_grad(global_loss, params,
                                    (tokens, targets), grad_accum)

    def specs_tree(params_like):
        return {k: _spec_for(k, specs) for k in params_like}

    def lm_train_step(params, opt_state, tokens, targets):
        # same internal zigzag permutation as the 2-D step
        tokens, targets, _ = _maybe_zigzag(attn, n_sp, tokens, targets,
                                           pre_permuted=zigzag_layout)
        mapped = shard_map(
            shard_step, mesh=mesh,
            in_specs=(specs_tree(params), P(dp_axis, sp_axis),
                      P(dp_axis, sp_axis)),
            out_specs=(P(), specs_tree(params)))
        loss, grads = mapped(params, tokens, targets)
        with scope("lm.opt"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(lm_train_step, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Pipeline-parallel form: layer groups over a ``pp`` axis (GPipe schedule)
# ---------------------------------------------------------------------------
#
# The decoder blocks are homogeneous, so their weights stack on a leading
# layer axis and shard over ``pp`` — each stage owns n_layers/pp
# consecutive layers and scans over its local slice. Embedding + LM head
# (the tied tok_emb) and the final layernorm are replicated: every device
# embeds the microbatches identically and scores the (psum-broadcast)
# last-stage outputs identically, so only the block stack actually rides
# the pipeline (parallel/pipeline.py). Dense FFN blocks only — tp/MoE
# compose with dp/sp, not with this axis, in the current build.

def _layer_weight_names(params: Params) -> list:
    """Per-layer weight suffixes, derived from the ACTUAL keys (the set
    varies with cfg.ffn / cfg.norm — a fixed list would silently drop
    swiglu's ff3 or crash on rms's missing biases)."""
    return sorted(k[len("L0_"):] for k in params if k.startswith("L0_"))


def stack_params_pp(params: Params, cfg: TransformerConfig) -> Params:
    """Per-layer weights → one leading-layer-axis stack per weight name
    (``layers_<name>``); embeddings/final-ln keys pass through."""
    if cfg.moe_experts:
        raise ValueError("pipeline form supports dense blocks only")
    out: Params = {k: v for k, v in params.items()
                   if not k.startswith("L")}
    for name in _layer_weight_names(params):
        out[f"layers_{name}"] = jnp.stack(
            [params[f"L{i}_{name}"] for i in range(cfg.n_layers)])
    return out


def unstack_params_pp(stacked: Params, cfg: TransformerConfig) -> Params:
    """Inverse of :func:`stack_params_pp` (canonical per-layer names)."""
    out: Params = {k: jnp.asarray(v) for k, v in stacked.items()
                   if not k.startswith("layers_")}
    for k, v in stacked.items():
        if k.startswith("layers_"):
            name = k[len("layers_"):]
            w = jnp.asarray(v)
            for i in range(cfg.n_layers):
                out[f"L{i}_{name}"] = w[i]
    return out


def shard_params_pp(params: Params, mesh, cfg: TransformerConfig, *,
                    pp_axis: str = "pp") -> Params:
    """Stack and device_put: layer stacks split over ``pp``, rest
    replicated."""
    stacked = stack_params_pp(params, cfg)
    return {k: jax.device_put(
        v, NamedSharding(mesh, P(pp_axis) if k.startswith("layers_")
                         else P()))
        for k, v in stacked.items()}


def _block_stacked(w: Params, x, cfg: TransformerConfig, pos):
    """One dense decoder block from a single layer's weight dict (no
    name prefixes) with full local attention — the pipeline stage body.
    Delegates to _block so the pipeline computes EXACTLY the model the
    oracle it is golden-diffed against computes."""
    prefixed = {f"L0_{k}": v for k, v in w.items()}
    return _block(prefixed, 0, x, cfg,
                  functools.partial(attention_reference, causal=True,
                                    window=cfg.window), pos)[0]


def make_train_step_pp(cfg: TransformerConfig, mesh, optimizer, *,
                       n_micro: int, pp_axis: str = "pp"):
    """Jitted pipeline-parallel LM train step over a 1-D (pp,) mesh:
    ``step(params, opt_state, tokens, targets)`` with params from
    :func:`shard_params_pp` and tokens/targets replicated (B must divide
    by ``n_micro``). Reverse-mode AD transposes the GPipe scan into the
    backward pipeline — no hand-written schedule."""
    _check_arch(cfg)
    _check_sharded(cfg)
    n_pp = mesh.shape[pp_axis]
    if cfg.n_layers % n_pp:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"{pp_axis}={n_pp}")
    if cfg.moe_experts:
        raise ValueError("pipeline form supports dense blocks only")

    def shard_step(params, tokens, targets):
        _check_seq(tokens.shape[1], cfg)
        b, l = tokens.shape
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by "
                             f"n_micro={n_micro}")
        mb = b // n_micro
        tok_m = tokens.reshape(n_micro, mb, l)
        tgt_m = targets.reshape(n_micro, mb, l)

        @scope("lm.loss")
        def global_loss(p):
            local_layers = {k[len("layers_"):]: v for k, v in p.items()
                            if k.startswith("layers_")}
            pos = jnp.arange(l)
            with scope("lm.embed"):
                x_micro = p["tok_emb"][tok_m]
                if _has_pos_table(cfg):
                    x_micro = x_micro + p["pos_emb"][pos]

            def stage(x):
                def body(x, w):
                    return _block_stacked(w, x, cfg, pos), None
                x, _ = lax.scan(body, x, local_layers)
                return x

            outs = pipeline_apply(stage, x_micro, pp_axis=pp_axis,
                                  n_stages=n_pp)       # (M, mb, l, d)
            with scope("lm.head"):
                x = _norm(p, "lnf", outs, cfg)
                logits = x @ p["tok_emb"].T
                return _mean_nll(logits, tgt_m)

        return jax.value_and_grad(global_loss)(params)

    def specs_for(params):
        return {k: (P(pp_axis) if k.startswith("layers_") else P())
                for k in params}

    def lm_train_step(params, opt_state, tokens, targets):
        specs = specs_for(params)
        mapped = shard_map(
            shard_step, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), specs))
        loss, grads = mapped(params, tokens, targets)
        with scope("lm.opt"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(lm_train_step, donate_argnums=(0, 1))
