"""Compiles for the chip, without the chip: the TPU's compiler is
installed here and compiles for a described `v5e:2x2`. Nothing runs, so
nothing here is a time or a result; what it guards is the SHAPE of the
compiled train step at the train cell's real widths (one layer).

The topology is described inside a fixture and never at import: one
process at a time may load the TPU's library, and every xdist worker
imports every test file. Keep every such compile in this one file.
"""

import collections
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from lua_mapreduce_tpu import ops
from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.parallel.mesh import rows_layout
from lua_mapreduce_tpu.train.precision import with_f32_master

CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "configs",
    "mistral-7b-v0.1.train.json")
SEQ = 4096
ROWS = {(1, 1): 3, (2, 2): 8}       # the two train cells' batches


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        c = json.load(f)
    return tfm.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=1,
        d_ff=c["intermediate_size"], max_seq=c["max_position_embeddings"],
        n_kv_heads=c["num_key_value_heads"], rope=True,
        rope_base=float(c["rope_theta"]), norm="rms", ffn="swiglu",
        window=c["sliding_window"])


@pytest.fixture
def chip_policy(monkeypatch):
    """What the program would decide on the chip: `ops.default_backend`
    asks `jax.default_backend()`, which is the CPU here. And no compile
    cache: an entry written for a described chip cannot be read back."""
    monkeypatch.setattr(
        ops, "default_backend",
        lambda op=None: ops._TPU_AUTO_POLICY.get(op, "pallas"))
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_step(topo, cfg, shape) -> str:
    """The train step as the cells build it (bfloat16 weights, float32
    masters under Adam, weights and state where `shard_params_moe` and
    `init_opt_state` put them: split by rows over the chips,
    `parallel.mesh.rows_layout`), compiled for `shape` chips."""
    dp, sp = shape
    mesh = Mesh(np.array(topo.devices[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))
    placed = lambda tree, spec: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)
    by_rows = lambda tree: jax.tree.map(  # noqa: E731
        lambda x, at: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=at),
        tree, rows_layout(mesh, tree))
    opt = with_f32_master(optax.adam(3e-4))
    params = jax.eval_shape(
        lambda: {k: v.astype(jnp.bfloat16) for k, v in tfm.init_transformer(
            jax.random.PRNGKey(0), cfg).items()})
    state = by_rows(jax.eval_shape(opt.init, params))
    tokens = placed(jax.ShapeDtypeStruct((ROWS[shape], SEQ), jnp.int32),
                    P("dp", "sp"))
    step = tfm.make_train_step(cfg, mesh, opt, attn="ring")
    return step.lower(by_rows(params), state, tokens,
                      tokens).compile().as_text()


@pytest.fixture(scope="module")
def as_built(topo, cfg):
    """`compiled_step` of the package as it stands, compiled once a
    shape for the whole file (a test that patches the package first
    calls `compiled_step` itself). Call it under `chip_policy`."""
    texts = {}

    def text_of(shape) -> str:
        if shape not in texts:
            texts[shape] = compiled_step(topo, cfg, shape)
        return texts[shape]

    return text_of


def fusions(text: str):
    """(output shapes, kind, body) of every fusion instruction."""
    bodies = {name: body for name, body in re.findall(
        r"^%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S)}
    for out, rest in re.findall(r" = (.*?) fusion\((.*)", text):
        called = re.search(r"calls=%([\w.\-]+)", rest).group(1)
        yield out, re.search(r"kind=(\w+)", rest).group(1), bodies[called]


def instruction_multiset(text: str) -> collections.Counter:
    """Every instruction of every computation, names and metadata off."""
    lines = collections.Counter()
    for line in text.splitlines():
        if " = " in line:
            line = re.sub(r", metadata=\{[^}]*\}", "", line.strip())
            lines[re.sub(r"%[\w.\-]+", "%", line)] += 1
    return lines


def test_one_chip_weight_gradient_matmuls_are_fusions_of_their_own(
        cfg, chip_policy, as_built):
    """ISSUE 27: with the masters' Adam update fused into its output the
    SwiGLU weight-gradient matmul ran at 47% of peak. No fusion may hold
    a convolution AND write the float32 streams of the update."""
    d, ff = cfg.d_model, cfg.d_ff
    found = list(fusions(as_built((1, 1))))
    held = lambda body: " convolution(" in body  # noqa: E731
    for wide in (f"f32[{d},{ff}]", f"f32[{ff},{d}]"):
        assert not [out for out, _, body in found
                    if wide in out and held(body)], wide
        # the update is there, elementwise, once a leaf
        n = sum(wide in out and kind == "kLoop" for out, kind, _ in found)
        assert n == (2 if wide.startswith(f"f32[{d},") else 1), (wide, n)
    # and the matmuls write bfloat16 gradients: ff1 and ff3, then ff2
    alone = [out for out, _, body in found if held(body)]
    assert sum(out.startswith(f"bf16[{d},{ff}]") for out in alone) == 2
    assert sum(out.startswith(f"bf16[{ff},{d}]") for out in alone) == 1


def written(line: str) -> str:
    """Opcode and arrays written by a line of `instruction_multiset`,
    layout, tiling and memory space off."""
    out, opcode = re.match(r"(?:ROOT )?% = (.*?) ([\w\-]+)\(", line).groups()
    return opcode + " " + re.sub(r"\{[^{}]*\}", "", out)


# What PR 27's barrier changes in the one-layer 2x2 step, and all it
# changes, since each chip updates and keeps its own rows of weights and
# state. With the barrier the compiler hands the updates of the
# [1024, 4096] and [8000, 4096] rows and of the three norm gains' [1024]
# their slice of the bfloat16 weight as an operand, cut by a fusion of
# its own (the [8000, 4096] one fetched in four slices, joined and copied
# back); without it each update fusion takes the whole weight and an
# offset and slices inside. The instructions that read or write one of
# these are the same on both sides but for that: five update fusions
# take other operands. What the barrier costs on the chip: PERF.md
# section 6.
PLACED_OTHERWISE = {
    "add f32[8000,4096]": 1, "convert bf16[8000,4096]": 1,
    "convert f32[8000,4096]": 1, "dynamic-slice bf16[1024,4096]": 1,
    "dynamic-slice bf16[1024]": 3, "dynamic-slice bf16[8000,4096]": 1,
    "fusion (bf16[1024,4096], f32[1024,4096], f32[1024,4096], "
    "f32[1024,4096])": 1,
    "fusion (bf16[1024], f32[1024], f32[1024], f32[1024])": 3,
    "fusion (bf16[8000,4096], f32[8000,4096], f32[8000,4096], "
    "f32[8000,4096])": 1,
    "get-tuple-element bf16[8000,4096]": 1, "parameter bf16[1408,4096]": 1,
    "parameter bf16[8000,4096]": 1, "parameter bf16[8256,4096]": 1,
    "parameter f32[1024,4096]": 1, "parameter f32[1024]": 3,
    "parameter f32[8000,4096]": 1, "parameter u32[]": 2,
    "tuple (bf16[8000,4096], f32[8000,4096], f32[8000,4096], "
    "f32[8000,4096])": 1,
}
MOVED_WITH_THE_BARRIER = {
    "fusion bf16[1024,4096]": 1, "fusion bf16[8000,4096]": 1,
    "parameter bf16[1024,4096]": 1, "parameter bf16[8000,4096]": 1,
    "parameter bf16[1024]": 3,
    "slice-start ((bf16[8000,4096]), bf16[2000,4096], s32[])": 4,
    "slice-done bf16[2000,4096]": 4,
    "custom-call bf16[8000,4096]": 1,       # ConcatBitcast of the slices
    "copy-start (bf16[8000,4096], bf16[8000,4096], u32[])": 1,
    "copy-done bf16[8000,4096]": 1,
}
MOVED_WITHOUT_IT = {
    "parameter bf16[4096]": 3,
    "parameter u32[]": 3,                   # the offsets of the slices
}


def test_2x2_program_is_the_one_without_the_barrier(topo, cfg, chip_policy,
                                                    as_built, monkeypatch):
    """Behind the reduce-scatter the barrier changes no instruction: the
    2x2 step's are those of the same step built without it, line for
    line, but for the placement pinned above."""
    with_barrier = as_built((2, 2))
    assert "reduce-scatter" in with_barrier
    with monkeypatch.context() as m:
        m.setattr(jax.lax, "optimization_barrier", lambda x: x)
        without = compiled_step(topo, cfg, (2, 2))
    with_barrier, without = map(instruction_multiset, (with_barrier, without))
    differs = lambda a, b: collections.Counter(  # noqa: E731
        written(line) for line in (a - b).elements())
    assert differs(with_barrier, without) == collections.Counter(
        PLACED_OTHERWISE) + collections.Counter(MOVED_WITH_THE_BARRIER)
    assert differs(without, with_barrier) == collections.Counter(
        PLACED_OTHERWISE) + collections.Counter(MOVED_WITHOUT_IT)
    assert sum(with_barrier.values()) > 3100        # of which 43 and 31


def entry_instructions(text: str) -> dict:
    """name -> (output type, opcode, operand names, the whole line) of
    the entry computation's instructions."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.M | re.S).group(1)
    found = {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)", line)
        if m:
            name, out, opcode, rest = m.groups()
            found[name] = (out, opcode, re.findall(
                r"%([\w.\-]+)", re.sub(r", \w+=.*", "", rest)), line)
    return found


def array_bytes(out: str) -> int:
    """Bytes of the arrays in an output type (scalars count nothing)."""
    width = {"bf16": 2, "f32": 4}
    return sum(width[kind] * int(np.prod([int(d) for d in dims.split(",")]))
               for kind, dims in re.findall(r"(\w+)\[([\d,]+)\]", out))


def collectives(text: str) -> dict:
    """(kind, bytes carried, output type, replica groups) of every
    collective of a compiled program, by a key of its own. A
    reduce-scatter carries its operand, an all-gather its result, an
    all-reduce both. The chip runs some reduce-scatters as a fusion
    whose computation, `all-reduce-scatter`, pads the operand, sums it
    whole and slices: one reduce-scatter of the fusion's operand. An
    asynchronous collective stands in the computations of its start,
    its body and its end, on one channel: one collective. (Channels are
    no key elsewhere: the synchronous reduce-scatters share one.)"""
    found = {}
    for entry, computation, operands, body in re.findall(
            r"^(ENTRY )?%([\w.\-]+) \(([^\n]*)\) -> [^\n]*\{\n(.*?)^\}",
            text, re.M | re.S):
        for name, out, opcode, rest in re.findall(
                r"%([\w.\-]+) = (.*?) (all-reduce|all-gather|reduce-scatter|"
                r"collective-permute|all-to-all)(?:-start)?\((.*)", body):
            groups = re.search(r"replica_groups=(\{\{[\d,]*\}\}|\S+<=\[\d+\])",
                               rest)
            groups = groups and groups.group(1).replace(
                "[1,4]<=[4]", "{{0,1,2,3}}")
            carried = array_bytes(out)
            key = name
            if opcode == "reduce-scatter":
                carried *= len(groups.strip("{}").split(","))
            elif computation.startswith("all-reduce-scatter"):
                opcode, carried = "reduce-scatter", array_bytes(operands)
            elif not entry:
                key = re.search(r"channel_id=(\d+)", rest).group(1)
            found.setdefault(key, (opcode, carried, out, groups))
    return found


def test_2x2_gradients_cross_the_wire_once(cfg, chip_policy, as_built):
    """ISSUE 29: until then every gradient went through three all-reduces
    back to back. Since weights and optimizer state are split by rows, a
    gradient crosses the wire once as a reduce-scatter over all four
    chips, and a bfloat16 weight once as an all-gather where the step
    takes it: together the
    reduce-scatters carry every matrix's bytes once and the all-gathers
    every parameter's. The norms' gains, 8 KB a gradient, the compiler
    sums whole, in one all-reduce with the loss, and each chip slices
    its rows; no other all-reduce carries an array, and no collective
    a float32 one (no master leaves its chip). Each chip updates a
    quarter of the rows: the update of a [4096, 14336] leaf writes its
    float32 streams at [1024, 14336]."""
    text = as_built((2, 2))
    found = collectives(text)
    params = jax.eval_shape(
        lambda: tfm.init_transformer(jax.random.PRNGKey(0), cfg))
    held = lambda rank: sum(  # noqa: E731
        2 * math.prod(v.shape) for v in params.values() if rank(v.ndim))
    carried = lambda kind: [  # noqa: E731
        (b, groups) for k, b, _, groups in found.values() if k == kind and b]
    scatters = carried("reduce-scatter")
    assert scatters and {groups for _, groups in scatters} == {"{{0,1,2,3}}"}
    assert sum(b for b, _ in scatters) == held(lambda n: n > 1)
    gathers = carried("all-gather")
    assert {groups for _, groups in gathers} == {"{{0,1,2,3}}"}
    assert sum(b for b, _ in gathers) == held(lambda n: n > 0)
    assert sum(b for b, _ in carried("all-reduce")) == held(lambda n: n == 1)
    for kind, _, out, _ in found.values():
        assert not re.search(r"f32\[\d", out), (kind, out)
    d, ff = cfg.d_model, cfg.d_ff
    written = [out for out, _, _ in fusions(text)]
    assert any(f"f32[{d // 4},{ff}]" in out for out in written)
    assert not any(f"f32[{d},{ff}]" in out for out in written)
    # and nothing scales a gradient on its way (the old x 0.5 of a mean)
    assert "broadcast_multiply_fusion" not in "".join(entry_instructions(text))


def test_one_chip_step_holds_no_all_reduce(chip_policy, as_built):
    """On a (1, 1) mesh the sums of the loss's mean compile to nothing,
    and no other collective stands in the step: the state stays whole,
    so nothing is reduce-scattered or gathered either."""
    text = as_built((1, 1))
    assert text.startswith("HloModule jit_lm_train_step")
    for collective in ("all-reduce", "reduce-scatter", "all-gather"):
        assert collective not in text


# --------------------------------------------------------------------------
# the session entry (PR 28), at the serving cells' real widths, one layer
# --------------------------------------------------------------------------

def serve_config(name: str) -> dict:
    path = os.path.join(os.path.dirname(CONFIG), name + ".json")
    with open(path) as f:
        return json.load(f)


def compiled_turn(topo, program_cfg, batch, context, n_new, stats=False):
    """`decode_from` over the caches of a prefill, compiled for one chip."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)
    params = jax.eval_shape(
        lambda: {k: v.astype(jnp.bfloat16) for k, v in tfm.init_transformer(
            jax.random.PRNGKey(0), program_cfg).items()})
    total = context + n_new
    caches = jax.eval_shape(
        lambda p, ids: tfm.decode_caches(
            tfm.prefill(p, ids, cfg=program_cfg, total=total)[0],
            cfg=program_cfg, p_len=context, total=total),
        params, jax.ShapeDtypeStruct((batch, context), jnp.int32))
    return tfm.decode_from.lower(
        placed(params), placed(caches),
        placed(jax.ShapeDtypeStruct((batch,), jnp.int32)),
        placed(jax.ShapeDtypeStruct((), jnp.int32)), n_new, cfg=program_cfg,
        stats=stats).compile()


def test_the_dense_session_entry_keeps_kernel_and_caches_in_place(
        topo, chip_policy):
    from perfbench.drivers.train import program_config
    import dataclasses
    cfg = dataclasses.replace(
        program_config(serve_config("mistral-7b-v0.1.serve")), n_layers=1)
    compiled = compiled_turn(topo, cfg, 8, 3968, 128)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_decode_from")
    assert "_decode_pallas" in text
    # the donated caches are written in place: 2 x (8, 8, 4096, 128) bf16
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * 8 * 8 * 4096 * 128 * 2
    assert memory.temp_size_in_bytes < 64 * 2 ** 20


@pytest.fixture(scope="module")
def latent_turn(topo):
    """One expert layer of DeepSeek-V3.2-Exp's share at its real widths,
    the cell's turn (8 sessions, 32,768 cached positions, 64 scanned),
    compiled once for the file. Call it under `chip_policy`."""
    built = []

    def turn():
        if not built:
            from perfbench.model_dsv32 import program_config
            import dataclasses
            cfg = dataclasses.replace(program_config(serve_config(
                "deepseek-v3.2-exp.serve-ep16")), n_layers=1,
                moe_first_dense=0)
            built.append(compiled_turn(topo, cfg, 8, 32768, 64))
        return built[0]

    return turn


def held_expert_calls(text: str, count: int, d: int, f: int) -> list:
    """The operands of every `_moe_held_pallas` call in a compiled
    program, after the assertion that each takes the three expert stacks
    in the layout the parameters have (row-major, the chip's own tiling:
    no transposed or re-laid copy is asked for)."""
    calls = re.findall(
        r"%_moe_held_pallas[.\d]* = f32\[\d+,\d+\][^ ]* custom-call\(([^)]*)\)"
        r", custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{([^}]*(?:\}[^}]*)*?)\}, frontend", text)
    up, down = f"bf16[{count},{d},{f}]{{2,1,0}}", f"bf16[{count},{f},{d}]{{2,1,0}}"
    for _, layouts in calls:
        assert layouts.endswith(f"{up}, {up}, {down}"), layouts
    return [operands for operands, _ in calls]


def no_copy_of_an_expert_stack(text: str, count: int, d: int, f: int):
    assert not re.findall(
        rf"= bf16\[{count},(?:{d},{f}|{f},{d})\][^ ]* "
        r"(?:copy|transpose|fusion|copy-start)\(", text)


def latent_row_puts(text: str, b: int, s_len: int) -> int:
    """How often the decode step's in-place row put engages: the
    `_latent_row_put` calls on a (b, 576, s_len) latent cache that
    write their result over the cache they were given, after the
    assertion that no dynamic-update-slice, copy or transpose makes a
    (b, s_len, 576) cache (XLA's dynamic-update-slice writes the row a
    tile at a time, 2.5 us a session on a v5e)."""
    assert not re.findall(
        rf"= bf16\[{b},(?:{s_len},576|576,{s_len})\][^ ]* "
        r"(?:dynamic-update-slice|copy|transpose)\(", text)
    return len(re.findall(
        rf"%_latent_row_put[.\d]* = bf16\[{b},576,{s_len}\][^ ]* "
        r"custom-call\([^)]*\), custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{.*?\}, "
        r"output_to_operand_aliasing=\{\{\}: \(2, \{\}\)\}", text))


def test_the_latent_session_entry_reads_an_expert_only_where_touched(
        chip_policy, latent_turn):
    """The 16 held experts are ONE grouped call in the decode program
    (`ops/moe_held.py`: it walks the list of touched experts, so an
    untouched one costs no byte) and no conditional an expert; the
    stacked weights go in as the parameters hold them, and the
    compiler's temporaries are no larger than with the conditionals
    (435,530,240 bytes at PR 36: no copy of a stack); nothing sorts 32k
    scores, and the latent caches stay in place."""
    compiled = latent_turn()
    text = compiled.as_text()
    assert len(held_expert_calls(text, 16, 7168, 2048)) == 1
    assert not re.findall(r" conditional\(", text)
    no_copy_of_an_expert_stack(text, 16, 7168, 2048)
    assert not re.findall(r"sort\([^)]*\[8,1,32832\]", text)
    assert not re.findall(r"= [^=]*\[8,1,32832\][^=]* sort\(", text)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= 435530240
    # (the chip's tiling pads the rows a little)
    assert memory.alias_size_in_bytes >= 8 * 32832 * (576 + 128) * 2


def test_the_latent_session_entry_gathers_the_selected_rows_and_no_more(
        chip_policy, latent_turn):
    """The selection of a decode step (8 rows x 2048 slots) looks nothing
    up index by index: the one gather over the selected slots is the row
    gather from the (8, 32832, 576) cache, and none gives an `s32` or
    `f32` scalar a slot, whatever axes of one it keeps (the lane lookup
    and the re-read of the scores, 7.9 ns an index each on the chip;
    ledger, PR 32)."""
    text = latent_turn().as_text()
    gathers = re.findall(r"= (\w+)\[([\d,]*)\]\S* gather\(", text)
    slots = [(dtype, dims) for dtype, dims in gathers
             if math.prod(map(int, re.findall(r"\d+", dims)))
             in (8 * 2048, 8 * 2048 * 576)]          # a scalar, a row a slot
    assert slots == [("bf16", "8,2048,576")]


def test_the_indexed_latent_cache_keeps_its_dynamic_update_slice(
        chip_policy, latent_turn):
    """DeepSeek's indexed cache is laid out as XLA picks it for the row
    gather, which the row put's transposed view could force into a
    re-layout: its decode step writes the row with the one
    dynamic-update-slice a layer, as before, and no put."""
    text = latent_turn().as_text()
    assert "_latent_row_put" not in text
    assert len(re.findall(
        r"= bf16\[8,32832,576\][^ ]* dynamic-update-slice\(", text)) == 1


# --------------------------------------------------------------------------
# the flash-decode kernel alone (PR 31): Mosaic compiles the tiling that
# `ops/decode._tiles` chose inside the VMEM limit the call hands it, which
# neither interpret mode nor `jax.export`'s lowering can see
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape,q8,roll", [
    ((32, 8, 4, 128, 512), False, False),       # the chat cell
    ((8, 8, 4, 128, 4096), False, True),        # long context, rolling
    ((32, 8, 4, 128, 512), True, False),        # int8 caches, 16 rows a step
    ((8, 8, 4, 128, 4096), True, True),
    ((4, 16, 1, 64, 4096), True, False),        # head width 64 pads to 128
    ((1, 17, 4, 128, 32768), False, False),     # one row, chunks of 4096
    ((2, 2, 8, 64, 300), True, False),          # a ragged only chunk
])
def test_the_decode_kernel_compiles_inside_its_vmem_limit(
        topo, chip_policy, shape, q8, roll):
    from jax.sharding import SingleDeviceSharding
    from lua_mapreduce_tpu.ops.decode import _decode_pallas
    one = SingleDeviceSharding(topo.devices[0])
    b, hkv, g, d, s_len = shape
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one)
    cache = arg((b, hkv, s_len, d), jnp.int8 if q8 else jnp.bfloat16)
    scales = {"k_scale": arg((b, hkv, s_len), jnp.float32),
              "v_scale": arg((b, hkv, s_len), jnp.float32)} if q8 else {}
    text = _decode_pallas.lower(
        arg((b, hkv, g, d), jnp.bfloat16), cache, cache,
        arg((), jnp.int32), roll=roll, **scales).compile().as_text()
    assert "_decode_pallas" in text and "tpu_custom_call" in text


# --------------------------------------------------------------------------
# the training flash kernels alone (PR 35), at what the two train cells hand
# them: Mosaic compiles the tile table's walk (a scalar-prefetched int32 a
# step, read by every index map) and both bodies of a step inside VMEM
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", ["train-1chip", "ring-hop0", "ring-hop1"])
def test_the_flash_kernels_compile_at_the_train_cells_shapes(topo, shape):
    from jax.sharding import SingleDeviceSharding
    from benchmarks import flash_tune as T
    from lua_mapreduce_tpu.ops.attention import flash_attention
    one = SingleDeviceSharding(topo.devices[0])
    b, l, q_offset, lse = T.SHAPES[shape]
    q, kv = (jax.ShapeDtypeStruct((b, l, h, T.HEAD_DIM), jnp.bfloat16,
                                  sharding=one)
             for h in (T.HEADS, T.KV_HEADS))

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, backend="pallas",
                              window=T.WINDOW, q_offset=q_offset,
                              return_lse=lse)
        return sum(x.astype(jnp.float32).sum()
                   for x in (out if lse else (out,)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    for kernel in T.KERNELS:
        assert f"%{kernel}" in text, kernel


# --------------------------------------------------------------------------
# latent attention over a whole cache (PR 32): the latent flash-decode
# kernel at the sarvam-105b cell's shape, and the session entry around it
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,heads,s_len,tiles", [
    (16, 64, 32832, (2, 1024)),     # the sarvam-105b cell
    (1, 64, 32832, (1, 2048)),      # one session: a longer chunk
    (8, 128, 32832, (2, 1024)),     # 128 heads over the same row
    (4, 16, 300, (4, 384)),         # a ragged only chunk
])
def test_the_latent_decode_kernel_compiles_inside_its_vmem_limit(
        topo, chip_policy, b, heads, s_len, tiles):
    from jax.sharding import SingleDeviceSharding
    from lua_mapreduce_tpu.ops import mla_decode
    one = SingleDeviceSharding(topo.devices[0])
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one)
    assert mla_decode._tiles(b, s_len, 576, 512, heads, 2) == tiles
    assert (mla_decode._vmem_bytes(*tiles, 576, 512, heads, 2)
            <= mla_decode._VMEM_BUDGET)
    text = mla_decode._mla_decode_pallas.lower(
        arg((b, heads, 576), jnp.bfloat16),
        arg((b, s_len, 576), jnp.bfloat16), arg((), jnp.int32),
        v_rank=512).compile().as_text()
    assert "_mla_decode_pallas" in text and "tpu_custom_call" in text


def test_the_whole_cache_session_entry_keeps_kernel_and_caches_in_place(
        topo, chip_policy):
    """One expert layer of sarvam-105b's share at its real widths: the
    attention kernel is there, the 32 held experts are one grouped call
    and no conditional, their stacks go in as they are (temporaries no
    larger than the conditionals' 122,104,320 bytes at PR 36), and
    the latent cache stays in place in the layout the chip keeps it in:
    the scan re-lays no (16, 32832, 576) array (a kernel over (B, S, R)
    blocks made XLA copy the whole cache into the scan and out of it,
    and hold a second one), and a step's row goes in through the one
    in-place put."""
    from perfbench.model_sarvam import program_config as sarvam_config
    import dataclasses
    cfg = dataclasses.replace(
        sarvam_config(serve_config("sarvam-105b.serve-ep4")),
        n_layers=1, moe_first_dense=0)
    compiled = compiled_turn(topo, cfg, 16, 32768, 64)
    text = compiled.as_text()
    assert "_mla_decode_pallas" in text
    assert len(held_expert_calls(text, 32, 4096, 2048)) == 1
    assert not re.findall(r" conditional\(", text)
    no_copy_of_an_expert_stack(text, 32, 4096, 2048)
    assert not re.findall(
        r"= bf16\[16,(?:32832,576|576,32832)\][^ ]* (?:copy|transpose)\(",
        text)
    # the decode step's row goes in through the in-place put
    assert latent_row_puts(text, 16, 32832) == 1
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 16 * 32832 * 576 * 2
    # (122,104,320 with a conditional an expert; 21,382,144 with the
    # row's dynamic-update-slice)
    assert memory.temp_size_in_bytes <= 21382144


# --------------------------------------------------------------------------
# the held experts of a decode step as one call (PR 38): Mosaic compiles
# the walk over the touched experts' tiles (copies from the stacks as they
# are, two sets of three tiles in VMEM) at the two latent cells' shapes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("t,count,d,f", [
    (16, 32, 4096, 2048),           # the sarvam-105b cell
    (8, 16, 7168, 2048),            # the DeepSeek-V3.2-Exp cell
    (64, 32, 4096, 2048),           # the most tokens a call takes
])
def test_the_held_expert_kernel_compiles_inside_its_vmem_limit(
        topo, chip_policy, t, count, d, f):
    from jax.sharding import SingleDeviceSharding
    from lua_mapreduce_tpu.ops import moe_held
    one = SingleDeviceSharding(topo.devices[0])
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one)
    tile_f = moe_held._tiles(t, d, f, count, 2)
    assert (moe_held._vmem_bytes(t, d, tile_f, count, 2)
            <= moe_held._VMEM_BUDGET)
    compiled = moe_held._moe_held_pallas.lower(
        arg((t, d), jnp.bfloat16), arg((t, count), jnp.float32),
        arg((count,), jnp.int32), arg((count, d, f), jnp.bfloat16),
        arg((count, d, f), jnp.bfloat16),
        arg((count, f, d), jnp.bfloat16)).compile()
    assert len(held_expert_calls(compiled.as_text(), count, d, f)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


# --------------------------------------------------------------------------
# a hybrid stack (PR 34): the Phi-4-mini-flash-reasoning cell's turn, whole
# --------------------------------------------------------------------------

def test_the_hybrid_session_entry_reads_one_shared_cache_in_place(
        topo, chip_policy):
    """All 32 layers at the cell's size (32 sessions, 16,384 cached
    positions, 32 scanned): sixteen calls of the decode kernel a step,
    eight over rolling windows of 512 and eight over the ONE whole cache
    (layer 17's: its own read and the seven cross layers' are the same
    two operands); the scan re-lays no cache (no copy or transpose of a
    (32, 10, 16416, 128) or (32, 10, 512, 128) array: a kernel over
    another layout made XLA copy 3.96 GB into the scan, PR 32), the
    donated caches and their snapshots are written in place, and the
    snapshots cost the scan nothing (they are not in its temporaries)."""
    from perfbench.model_phi4flash import program_config
    cfg = program_config(serve_config("phi-4-mini-flash-reasoning.serve"))
    compiled = compiled_turn(topo, cfg, 32, 16384, 32)
    text = compiled.as_text()
    calls = re.findall(
        r"= f32\[320,4,128\][^ ]* custom-call\(([^)]*)\), "
        r"custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
        r"\{s32\[1\]\{0\}, bf16\[320,4,128\]\{[^}]*\}, bf16\[320,(\d+),128\]",
        text)
    whole = [ops_.split(", ")[2:] for ops_, s_len in calls
             if s_len == "16416"]
    assert len(calls) == 16 and len(whole) == 8
    assert len({tuple(kv) for kv in whole}) == 1
    assert len({tuple(ops_.split(", ")[2:]) for ops_, s_len in calls
                if s_len == "512"}) == 8
    relaid = [line for line in text.splitlines() if re.search(
        r"= bf16\[(?:32,10|320),(?:16416|512),128\][^ ]* (?:copy|transpose)\(",
        line)]
    # a turn's start keeps a snapshot of the sixteen rolling buffers: one
    # copy each, before the scan and not in it; the whole cache has none
    assert len(relaid) == 16 and not any(
        "16416" in line.split(" copy(")[0] or "while/body" in line
        for line in relaid), relaid
    memory = compiled.memory_analysis()
    held = (2 * 32 * 10 * 16416 * 128 * 2           # the one whole cache
            + 2 * 8 * 2 * 32 * 10 * 512 * 128 * 2   # windows and snapshots
            + 2 * 9 * 32 * 5120 * (16 * 4 + 3 * 2))  # states and snapshots
    # (and seventeen scalars: the positions the snapshots are of)
    assert held <= memory.alias_size_in_bytes < held + 2 ** 16
    assert memory.temp_size_in_bytes < 128 * 2 ** 20


# --------------------------------------------------------------------------
# an attention pattern with a parallel block (PR 39): the
# command-a-plus-05-2026 cell's turn, its four layers whole
# --------------------------------------------------------------------------

def test_the_pattern_session_entry_keeps_its_caches_in_place(topo,
                                                             chip_policy):
    """One period at the cell's size (16 sessions, 32,768 cached
    positions, 64 scanned): four calls of the decode kernel a step,
    three over rolling windows of 4096 and one over the whole cache of
    32,832; one grouped expert call a layer and no conditional; the
    donated caches and the windows' snapshots written in place, and a
    scan whose temporaries are a few megabytes."""
    from perfbench.model_cmdaplus import program_config
    cfg = program_config(serve_config("command-a-plus-05-2026.serve-ep8"))
    compiled = compiled_turn(topo, cfg, 16, 32768, 64)
    text = compiled.as_text()
    lengths = re.findall(
        r"= f32\[128,16,128\][^ ]* custom-call\([^)]*\), "
        r"custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
        r"\{s32\[1\]\{0\}, bf16\[128,16,128\]\{[^}]*\}, bf16\[128,(\d+),128\]",
        text)
    assert sorted(lengths) == ["32832", "4096", "4096", "4096"]
    assert len(held_expert_calls(text, 16, 4096, 4096)) == 4
    assert not re.findall(r" conditional\(", text)
    no_copy_of_an_expert_stack(text, 16, 4096, 4096)
    memory = compiled.memory_analysis()
    held = (2 * 16 * 8 * 32832 * 128 * 2             # the full layer's
            + 2 * 3 * 2 * 16 * 8 * 4096 * 128 * 2)   # windows, snapshots
    assert held <= memory.alias_size_in_bytes < held + 2 ** 16
    assert memory.temp_size_in_bytes < 16 * 2 ** 20


# --------------------------------------------------------------------------
# Kimi Delta Attention beside latent layers: the
# Kimi-Linear-48B-A3B-Instruct cell's kernel and the first four layers of
# its turn
# --------------------------------------------------------------------------

def test_the_kda_step_kernel_writes_the_state_in_place(topo, chip_policy):
    """At the cell's 32 sessions of 32 heads of 128: Mosaic compiles the
    kernel, and a donated state is its output (no copy of 64 MiB)."""
    from jax.sharding import SingleDeviceSharding
    from lua_mapreduce_tpu.ops import kda
    one = SingleDeviceSharding(topo.devices[0])
    arg = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one)
    step = jax.jit(lambda s, q, k, v, g, b: kda._kda_step_pallas(
        s, q, k, v, g, b), donate_argnums=0)
    compiled = step.lower(arg((32, 32, 128, 128)), arg((32, 32, 128)),
                          arg((32, 32, 128)), arg((32, 32, 128)),
                          arg((32, 32, 128)), arg((32, 32))).compile()
    text = compiled.as_text()
    assert "_kda_step_pallas" in text and "tpu_custom_call" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 32 * 32 * 128 * 128 * 4
    assert memory.temp_size_in_bytes < 2 ** 20


def test_the_kda_session_entry_keeps_its_states_in_place(topo, chip_policy):
    """Layers 1-4 at the cell's size (32 sessions, 4,096 cached positions,
    64 scanned): three KDA layers and the latent one. A call of the KDA
    kernel a KDA layer and step, the latent kernel and the grouped
    expert call; the scan copies no state (the snapshots are copied
    once, before it), the latent layer's row goes in through the
    in-place put, and the donated states, tails, snapshots and latent
    rows are written in place."""
    from perfbench.model_kimilinear import program_config
    import dataclasses
    cfg = program_config(serve_config(
        "kimi-linear-48b-a3b-instruct.serve-ep16"))
    cfg = dataclasses.replace(cfg, n_layers=4,
                              attn_pattern=cfg.attn_pattern[:4])
    compiled = compiled_turn(topo, cfg, 32, 4096, 64)
    text = compiled.as_text()
    assert len(re.findall(r"%_kda_step_pallas[.\d]* = \(f32\[32,32,128,128\]",
                          text)) == 3
    assert "_mla_decode_pallas" in text
    assert len(held_expert_calls(text, 16, 2304, 1024)) == 3
    body = re.search(r"^%wide\.[^\n]*\{\n(.*?)^\}", text, re.M | re.S)
    assert body and "_kda_step_pallas" in body.group(1)
    assert not re.findall(r"= f32\[32,32,128,128\][^ ]* copy\(",
                          body.group(1))
    # the latent layer's row goes in through the in-place put, a step
    assert latent_row_puts(body.group(1), 32, 4160) == 1
    assert latent_row_puts(text, 32, 4160) == 1
    memory = compiled.memory_analysis()
    states = 3 * 2 * 32 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    latent = 32 * 4160 * 576 * 2
    assert states + latent <= memory.alias_size_in_bytes
    # (the tails' 3 rows a session lie in the chip's tiles of more)
    assert memory.alias_size_in_bytes < states + latent + 2 ** 23
    # (49,540,096 with the row's dynamic-update-slice)
    assert memory.temp_size_in_bytes <= 49540096
