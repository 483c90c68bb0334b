"""The main path's kernels compile for a TPU v5e at Llama-3-8B shapes.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  What it refuses here (a tile
it cannot lay out, more scoped VMEM than a kernel may use) it refuses on
the chip, where the same failure costs chip time — `rms_norm` at hidden
4096 was refused for exactly that and no interpret-mode test saw it.

Everything chip-shaped happens inside fixtures and tests of THIS file:
only one process may hold the TPU library, pytest-xdist imports every
test file in every worker, and a second file could land on a worker
that cannot load it.  Nothing runs; a compile that passes is not a chip
run.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import decode_attention as DA
from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import flash_mask as FM
from paddle_tpu.ops.pallas import grouped_ffn as GF
from paddle_tpu.ops.pallas import mla_paged_attention as MLA
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.ops.pallas import rms_norm as RN

# Llama-3-8B attention: 32 query heads on 8 KV heads, head dim 128, bf16
NH, KVH, HD = 32, 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no /tmp/tpu_logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory pinned to one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but can never be read back without a chip; keep these compiles
    out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_text(fn, *args) -> str:
    """Compile ``fn`` for the described chip; the compiled program's
    text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def event_names(compiled) -> list[str]:
    """The compiled program's instructions as the v5e's trace names
    their events: the whole line from its ``%``, operands with their
    shapes (``as_text()`` leaves those out)."""
    from jax._src.lib import _jax
    opts = _jax.HloPrintOptions()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
            if ln.lstrip().startswith(("%", "ROOT %"))]


def compile_kernels(fn, *args) -> int:
    """The number of Pallas kernels in ``fn`` compiled for the described
    chip."""
    return compiled_text(fn, *args).count(
        'custom_call_target="tpu_custom_call"')


# what a Mosaic call that sets no ``vmem_limit_bytes`` may use on a v5e
V5E_SCOPED_VMEM = 16 << 20


def _pallas_call(fn, *args):
    """The one ``pallas_call`` equation ``fn`` traces to, inside
    whatever ``jit`` of its own the kernel's wrapper holds it in."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            elif e.primitive.name in ("pjit", "jit"):
                yield from walk(e.params["jaxpr"].jaxpr)
    found = list(walk(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(found) == 1, found
    return found[0]


def _scratch_vmem(eqn) -> int:
    """Bytes of VMEM scratch the ``pallas_call`` equation declares."""
    mapping = eqn.params["grid_mapping"]
    scratch = [v.aval for v in
               eqn.params["jaxpr"].invars[-mapping.num_scratch_operands:]]
    return sum(a.size * a.dtype.itemsize for a in scratch
               if str(a.memory_space) == "vmem")


@pytest.mark.parametrize("slots,kvh,rep,ps,width", [
    (8, KVH, 4, 16, 128),   # a decode step of 8 slots, 2048 tokens each
    (32, KVH, 4, 16, 64),   # the Mistral cell: 32 slots, 1024 tokens
    (32, 2, 4, 16, 64),     # the same inside tp=4: a shard's 2 KV heads
    (4, KVH, 4, 128, 5),    # the one-shot generate's pool: page 128
    (64, 4, 8, 16, 256),    # the Granite cell: two heads of 64 a row
    (64, 2, 16, 16, 256),   # the Nemotron cell: 2 KV heads of 128
], ids=["slots8", "cell", "tp-local", "one-shot", "granite-cell",
        "nemotron-cell"])
def test_paged_attention(sds, slots, kvh, rep, ps, width):
    pool = sds((2, slots * width + 1, kvh, ps, HD))     # two layers

    def call(q, k, v, t, n):
        return PA.paged_attention(q, k, v, 1, t, n)
    args = (sds((slots, rep * kvh, HD)), pool, pool,
            sds((slots, width), jnp.int32), sds((slots,), jnp.int32))
    compiled = jax.jit(call).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(_paged_events(event_names(compiled))) == 1
    # the benchmark's roofline finds the kernel's events by the table
    # [slots, width] and the lengths [slots] as the call's first two
    # operands, and its breakdown names them by the stem
    assert re.search(
        r'%paged_attention[.\d]* = \S+ custom-call\(.*'
        r'custom_call_target="tpu_custom_call", '
        rf'operand_layout_constraints=\{{s32\[{slots},{width}\]\S* '
        rf's32\[{slots}\]', text), text[-3000:]
    # the grid the block rule gives, and its buffers (two a pool, of
    # whole rounds) beside the rounds' own values inside the VMEM a
    # call may use unasked: the compile above is what holds it to that
    eqn = _pallas_call(call, *args)
    page_bytes = kvh * ps * HD * 2
    blk = PA.pages_per_block(width, page_bytes)
    assert eqn.params["grid_mapping"].grid == (slots, -(-width // blk))
    chunk = PA.round_tokens(blk * ps)
    rows = -(-blk * ps // chunk) * chunk
    assert _scratch_vmem(eqn) == (
        4 * rows * (page_bytes // ps) + kvh * rep * (HD + 2 * 128) * 4)
    assert dict(eqn.params["compiler_params"]).get("mosaic_tpu") is None
    assert _scratch_vmem(eqn) <= V5E_SCOPED_VMEM // 2 + (1 << 20)


def _metric_events(name):
    """The patterns by which a roofline metric finds its kernel's
    events in the trace."""
    import json
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)["args"]["events"]


def _paged_events(names):
    """The events the paged kernel's roofline would time."""
    patterns = _metric_events("paged_attention_roofline.serve")
    return [n for n in names if any(re.search(p, n) for p in patterns)]


def _hlo_lines(text, stem):
    """The compiled program's instructions called ``stem``, as the v5e's
    trace names their events: the line from its ``%``."""
    lines = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()]
    return [ln for ln in lines if ln.startswith("%" + stem)]


def _paged_body_and_sites(lowered_text):
    """(paged kernels the lowered program holds, calls of the function
    that holds one): the kernel's wrapper is one ``jit`` with the layer
    as an operand, so a step program traces and lowers the body once
    and calls it a layer."""
    return (lowered_text.count('kernel_name = "paged_attention"'),
            len(re.findall(r"\bcall @_paged_attention\(", lowered_text)))


def _cell_runner(monkeypatch, *, spec_k=0, kv_quant=False, layers=2):
    """A ModelRunner at the Mistral cell's widths and engine sizes, cut
    to ``layers`` layers (None: the cell's 16), that holds what ``_build_step`` / ``_build_verify``
    read and nothing on any device; and the shapes of its state."""
    import json
    import sys
    from paddle_tpu.models.llama import LlamaConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib.state import decoder_shapes
    with open(os.path.join(root, "benchmarks", "configs",
                           "mistral-7b-v0.3-l16.json")) as f:
        cell = json.load(f)
    m, eng = dict(cell["model"]), cell["engine"]
    m["num_hidden_layers"] = layers or m["num_hidden_layers"]
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"]) == (NH, KVH, HD)
    run = _bare_runner(monkeypatch, LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=NH, num_key_value_heads=KVH,
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        dtype=m["torch_dtype"]), eng, spec_k=spec_k, kv_quant=kv_quant)
    return run, {k: shape for k, (shape, _) in decoder_shapes(m).items()}


def _bare_runner(monkeypatch, config, eng, *, latent=False,
                 recurrent=False, spec_k=0, kv_quant=False):
    """A ``ModelRunner`` that holds what its program builders read and
    nothing on a device: ``config`` with a cell's ``engine`` sizes."""
    from paddle_tpu.serving.parallel.runner import ModelRunner
    # the kernel gate asks for the backend; the described chip is a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    run = object.__new__(ModelRunner)
    run.config = config
    run.tp, run.mesh, run.latent, run.emit_logits = 1, None, latent, False
    run.recurrent = recurrent
    run.spec_k, run.kv_quant = spec_k, kv_quant
    run.max_slots, run.page_size = eng["max_slots"], eng["page_size"]
    run.table_width = eng["max_model_len"] // run.page_size
    run.num_pages = run.max_slots * run.table_width     # + the dump page
    run._rope_len = eng["max_model_len"]
    run.decode_traces = run.verify_traces = run.push_traces = 0
    return run


def _latent_cell_runner(monkeypatch):
    """The GigaChat cell's runner, as ``_cell_runner`` makes the Mistral
    cell's: its configuration as the benchmark's driver reads it, every
    layer of the cut (1 dense + 4 expert layers), shapes only."""
    import json
    import sys
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import mla_moe_state
    with open(os.path.join(root, "benchmarks", "configs",
                           "gigachat3.1-702b-a36b-ep16-l5.json")) as f:
        conf = json.load(f)
    m, eng = conf["model"], conf["engine"]
    run = _bare_runner(monkeypatch, DeepseekV3Config(
        n_routed_experts=mla_moe_state.router_width(conf),
        local_experts=mla_moe_state.local_experts(conf),
        dtype=conf["assumed"]["torch_dtype"],
        **{k: m[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_shared_experts", "num_experts_per_tok",
            "n_group", "topk_group", "routed_scaling_factor",
            "norm_topk_prob", "max_position_embeddings", "rms_norm_eps",
            "rope_theta", "rope_scaling")}), eng, latent=True)
    return run, {k: shape for k, (shape, _)
                 in mla_moe_state.shapes(conf).items()}


def _compile_decode_program(sds, run, shapes):
    return _lower_decode_program(sds, run, shapes).compile()


def _lower_decode_program(sds, run, shapes):
    """The runner's own jitted decode step (``spec_k`` 0) or verify
    program, donated as the runner donates, lowered from shapes."""
    slots, k = run.max_slots, run.spec_k
    state = {name: sds(shape) for name, shape in shapes.items()}
    pool_shape = _pool_shape(run)
    pool = sds(pool_shape, jnp.int8 if run.kv_quant else jnp.bfloat16)
    scale = sds(pool_shape[:-1], jnp.float32) if run.kv_quant else ()

    def i32(*shape):
        return sds(shape, jnp.int32)
    rope = sds((run._rope_len, HD), jnp.float32)
    head = (state, pool, pool, scale, scale, i32(slots, run.table_width),
            i32(slots), i32(slots), i32(slots))
    if k == 0:
        return run._make_step_fn().lower(
            *head, i32(1, slots), i32(), rope, rope, (), (), (), ())
    return run._make_verify_fn().lower(
        *head, i32(1, slots, k + 1), i32(), i32(slots, k), i32(slots),
        rope, rope, (), ())


def _pool_shape(run):
    return (run.config.num_hidden_layers, run.num_pages + 1, KVH,
            run.page_size, HD)


def _pool_sized(names, run):
    """(stem, opcode) of every instruction whose result is a whole pool
    or one layer's slice of it."""
    layers, *layer = _pool_shape(run)
    layer = ",".join(map(str, layer))
    found = []
    for n in names:
        m = re.match(rf"%(\S+) = \w+\[(?:{layers},)?{layer}\]\S* "
                     r"([\w-]+)\(", n)
        if m:
            found.append((m.group(1), m.group(2)))
    return found


@pytest.mark.parametrize("spec_k", [0, 3], ids=["decode_step", "verify_step"])
def test_decode_programs_update_the_pools_in_place(sds, monkeypatch, spec_k):
    """The pools go through the step whole: with both donated, the
    compiled program declares no pool-sized temporary (two layers' pools
    are 134 MB each), copies no pool and no layer of one, and holds one
    paged kernel a layer where the roofline's pattern finds it."""
    run, shapes = _cell_runner(monkeypatch, spec_k=spec_k)
    compiled = _compile_decode_program(sds, run, shapes)
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)
    names = event_names(compiled)
    assert len(_paged_events(names)) == run.config.num_hidden_layers
    pool_sized = _pool_sized(names, run)
    assert pool_sized                       # the in-place row scatters
    for stem, opcode in pool_sized:
        for word in ("copy", "dynamic-update-slice", "slice"):
            assert word not in opcode and word not in stem, (stem, opcode)


def _scope_order(lowered, scopes):
    """The named scopes of ``scopes`` in the order the lowered program's
    main function enters them (a run of one scope counts once)."""
    text = lowered.as_text(debug_info=True)
    defs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def stack(ref, depth=0):
        body = defs.get(ref, "")
        name = re.match(r'"([^"]*)"', body)
        if name and "/" in name.group(1):
            return name.group(1)
        inner = re.search(r"#loc\d+", body)
        return stack(inner.group(0), depth + 1) if inner and depth < 8 else ""

    main = text[text.index("func.func public @main"):]
    main = main[:main.index("\n  }")]
    order = []
    for ref in re.findall(r"loc\((#loc\d+)\)\s*$", main, re.M):
        hit = [part for part in stack(ref).split("/") if part in scopes]
        if hit and order[-1:] != hit[:1]:
            order.append(hit[0])
    return order


@pytest.mark.parametrize("program,layer_scopes", [
    ("decode_step", ["attn.qkv", "kv.write", "attn.decode", "attn.out",
                     "mlp"]),
    ("prefill", ["attn.qkv", "attn.prefill", "attn.out", "mlp",
                 "kv.write"]),
])
def test_cell_programs_keep_their_kernels_and_scope_order(
        sds, monkeypatch, program, layer_scopes):
    """The dense tp=1 programs the Mistral cell runs: one attention
    kernel a layer and no other, and the scopes in the order the
    benchmark's breakdown names device time by — an edit of a layer
    body that reorders or renames them shows here, not in a trace."""
    run, shapes = _cell_runner(monkeypatch)
    layers = run.config.num_hidden_layers
    if program == "decode_step":
        lowered = _lower_decode_program(sds, run, shapes)
    else:
        bucket = 256
        pool = sds(_pool_shape(run))
        rope = sds((run._rope_len, HD), jnp.float32)
        run._prefill_fns = {}
        lowered = run._prefill_fn(bucket).lower(
            {name: sds(shape) for name, shape in shapes.items()},
            sds((1, bucket), jnp.int32), sds((1,), jnp.int32),
            sds((bucket // run.page_size,), jnp.int32), pool, pool, (), (),
            rope, rope, (), (), (), ())
    text = lowered.as_text()
    if program == "decode_step":    # one body, a call of it a layer
        assert text.count("@tpu_custom_call") == 1
        assert _paged_body_and_sites(text) == (1, layers)
    else:
        assert text.count("@tpu_custom_call") == layers
    assert _scope_order(lowered, {"embed", "head", *layer_scopes}) == (
        ["embed"] + layer_scopes * layers + ["head"])


# sha256 (first 16 hex digits) of the two existing serving cells' lowered
# programs as the PARENT of PR 32 (commit ec95bdd) lowers them, with every
# Pallas call's ``backend_config`` blanked (it holds the kernel as
# bytecode WITH source paths and lines, which differ between checkouts
# of one program: PERF.md, PR 30).  Computed from a ``git archive`` of
# that commit with the arguments it took then; here the programs take
# ``rstate`` (and the prefill ``slot``) as empty tuples: no leaf, no op.
# A jax upgrade moves these; so does any edit that reaches the Mistral
# or the GigaChat program, which is what they are here to show.
# The GigaChat ``decode_step`` is PR 33's own (its parent's was
# 82aae69a205bf43f): the block table rides flat into
# ``mla_paged_attention``, so each layer reshapes it ([64, 256] ->
# [16384]) and the call's first operand is that; with SSA numbers
# stripped nothing else differs from the parent's text.
# The Mistral ``decode_step`` is PR 36's own (its parent's was
# 8fadd2647ac63de8): each layer's ``@tpu_custom_call`` of the paged
# kernel became a ``call @_paged_attention`` of one private function
# that holds the one custom call (and the reshapes of q and the output
# around it), the layer riding in as ``tensor<1xi32>``.  The Mistral
# ``prefill`` and both GigaChat programs are untouched.
# Since PR 37 a prefill program is named for its bucket (its key in the
# compile ledger): the module is ``@jit_prefill_256``.  With the name put
# back to ``@jit_prefill`` the two prefills read their PR 36 digests
# (b60cf16e4026bb63, ea3b1ffe856fee5e): nothing else moved.
PARENT_HLO = {
    ("mistral", "decode_step"): "92543bcc7227c45d",
    ("mistral", "prefill"): "0afba31f2329d1d0",
    ("gigachat", "decode_step"): "b32ad1ec7af330f5",
    ("gigachat", "prefill"): "1143fa72fecea234"}


@pytest.mark.parametrize("cell,program", sorted(PARENT_HLO))
def test_existing_cells_programs_are_the_parents_hlo(sds, monkeypatch, cell,
                                                     program):
    """The recurrent family rides the shared programs as one more
    argument that is empty for the others, and the shared layer pieces
    read multipliers the others do not have: the Mistral cell's and the
    GigaChat cell's ``decode_step`` and ``prefill[256]``, at the depth
    they run, still lower to the parent's StableHLO."""
    import hashlib
    from paddle_tpu.serving.parallel import latent

    def i32(*shape):
        return sds(shape, jnp.int32)
    if cell == "mistral":
        run, shapes = _cell_runner(monkeypatch, layers=None)
        pools = (sds(_pool_shape(run)),) * 2
        rope = sds((run._rope_len, HD), jnp.float32)
        counters = ()
    else:
        run, shapes = _latent_cell_runner(monkeypatch)
        pools = (sds(latent.pool_shape(run.config, run.num_pages,
                                       run.page_size)), ())
        rope = sds((run._rope_len, run.config.qk_rope_head_dim),
                   jnp.float32)
        counters = sds(latent.counters0().shape, latent.counters0().dtype)
    state = {name: sds(shape) for name, shape in shapes.items()}
    slots = run.max_slots
    if program == "decode_step":
        lowered = run._make_step_fn().lower(
            state, *pools, (), (), i32(slots, run.table_width), i32(slots),
            i32(slots), i32(slots), i32(1, slots), i32(), rope, rope, (),
            (), counters, ())
    else:
        run._prefill_fns = {}
        lowered = run._prefill_fn(256).lower(
            state, i32(1, 256), i32(1), i32(256 // run.page_size), *pools,
            (), (), rope, rope, (), (), (), ())
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""',
                  lowered.as_text())
    assert "@tpu_custom_call" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_HLO[
        cell, program]


def _hybrid_cell_runner(monkeypatch):
    """The Granite cell's runner, as ``_cell_runner`` makes the Mistral
    cell's: the configuration as the benchmark's driver reads it, all 40
    layers, shapes only."""
    import json
    import sys
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import hybrid_state
    with open(os.path.join(root, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        conf = json.load(f)
    m, eng = conf["model"], conf["engine"]
    run = _bare_runner(monkeypatch, GraniteHybridConfig(
        intermediate_size=m["shared_intermediate_size"],
        layer_types=tuple(m["layer_types"]),
        dtype=conf["assumed"]["torch_dtype"],
        **{k: m[k] for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups",
            "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
            "mamba_conv_bias", "mamba_proj_bias", "embedding_multiplier",
            "attention_multiplier", "residual_multiplier", "logits_scaling",
            "position_embedding_type", "rms_norm_eps",
            "max_position_embeddings", "tie_word_embeddings")}), eng,
        recurrent=True)
    return run, {k: shape for k, (shape, _)
                 in hybrid_state.shapes(conf).items()}


def _lower_hybrid_program(sds, run, shapes, program):
    """The Granite cell's ``decode_step`` or ``prefill[256]``, donated as
    the runner donates, lowered from shapes."""
    from paddle_tpu.serving.parallel import recurrent
    slots = run.max_slots
    state = {name: sds(shape) for name, shape in shapes.items()}
    pool = sds(recurrent.kv_pool_shape(run.config, run.num_pages,
                                       run.page_size))
    rstate = recurrent.state_pools(run.config, slots, zeros=sds)
    none = sds((0,), jnp.float32)

    def i32(*shape):
        return sds(shape, jnp.int32)
    if program == "decode_step":
        return run._make_step_fn().lower(
            state, pool, pool, (), (), i32(slots, run.table_width),
            i32(slots), i32(slots), i32(slots), i32(1, slots), i32(), none,
            none, (), (), i32(1), rstate)
    bucket = 256
    run._prefill_fns = {}
    return run._prefill_fn(bucket).lower(
        state, i32(1, bucket), i32(1), i32(bucket // run.page_size), pool,
        pool, (), (), none, none, (), (), rstate, i32())


MAMBA_DECODE = ["ssm.in_proj", "ssm.conv", "ssm.update", "ssm.gate",
                "ssm.out", "mlp"]
MAMBA_PREFILL = ["ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate",
                 "ssm.out", "mlp", "ssm.write"]
ATTN_DECODE = ["attn.qkv", "kv.write", "attn.decode", "attn.out", "mlp"]
ATTN_PREFILL = ["attn.qkv", "attn.prefill", "attn.out", "mlp", "kv.write"]


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_hybrid_cell_programs_keep_their_kernels_and_scope_order(
        sds, monkeypatch, record_property, program):
    """The Granite cell's programs at the published widths, all 40
    layers: one ``ssm_state_update`` call a Mamba layer (decode), one
    ``paged_attention`` call an attention layer at two KV heads of 64 a
    128-lane row (decode), the flash kernel at head dim 64 (prefill),
    and the scopes in layer order.  The compiled ``decode_step`` updates
    the recurrent state where it lies: no second copy of the 2.42 GB
    pool (temporaries well under 1 GB)."""
    run, shapes = _hybrid_cell_runner(monkeypatch)
    cfg = run.config
    lowered = _lower_hybrid_program(sds, run, shapes, program)
    mamba, attn = ((MAMBA_DECODE, ATTN_DECODE) if program == "decode_step"
                   else (MAMBA_PREFILL, ATTN_PREFILL))
    want = ["embed"]
    for kind in cfg.layer_types:
        want += mamba if kind == "mamba" else attn
    assert _scope_order(lowered, set(want) | {"head"}) == want + ["head"]
    text = lowered.as_text()
    calls = text.count("@tpu_custom_call")
    if program == "prefill":
        assert calls == len(cfg.attention_layers)       # flash, D = 64
        return
    # a kernel a Mamba layer, and the attention layers' ONE
    assert calls == len(cfg.mamba_layers) + 1
    assert _paged_body_and_sites(text) == (1, len(cfg.attention_layers))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    updates = _hlo_lines(hlo, "ssm_state_update")
    assert len(updates) == len(cfg.mamba_layers) == 36
    paged = _hlo_lines(hlo, "paged_attention")
    assert len(paged) == 4
    # each roofline's pattern finds its own kernel's events and no other
    for lines, metric in ((updates, "ssm_update_roofline.serve"),
                          (paged, "paged_attention_roofline.serve.hybrid")):
        found = [ln for ln in updates + paged if any(
            re.search(p, ln) for p in _metric_events(metric))]
        assert found == lines, metric
    mem = compiled.memory_analysis()
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)
    print(f"granite decode_step: temp {mem.temp_size_in_bytes}, "
          f"arguments {mem.argument_size_in_bytes}, "
          f"aliased {mem.alias_size_in_bytes}")
    assert mem.temp_size_in_bytes < 400e6
    # weights 6.38 + ssm 2.42 (bfloat16, the served dtype) + conv 0.06
    # + K/V 2.15 GB, nothing padded
    assert 10.9e9 < mem.argument_size_in_bytes < 11.2e9
    # the state pools and the K/V pools are all updated in place
    assert mem.alias_size_in_bytes > 2.41e9 + 2.1e9


# The Granite cell's programs as PR 34 lowers them (same blanking as
# ``PARENT_HLO``).  They are not PR 33's (269a7fb9bb44d958,
# 29af4c4d42b1eda0): B and C ride into ``ssm_state_update`` as
# ``[slots, 1, N]``, the chunked scan carries its state as ``[1, N,
# H * P]`` and batches its products over the one group, the gated norm
# reshapes to one group: reshapes and unit batch dimensions around the
# parent's arithmetic (PERF.md, PR 34, has the cell's numbers beside the
# parent's).  The ``decode_step`` is PR 36's (PR 34's was
# 25da47839de8b841): the four attention layers call ONE private
# ``@_paged_attention`` function, as the Mistral program does; the
# ``prefill`` is PR 34's still, named ``@jit_prefill_256`` since PR 37
# (2bac911596edc873 under its old name).  An edit that reaches the
# Granite program moves these.
GRANITE_HLO = {"decode_step": "a4eec2bffdb90ced",
               "prefill": "441027826ee25a1a"}


@pytest.mark.parametrize("program", sorted(GRANITE_HLO))
def test_granite_cells_programs_are_this_prs_hlo(sds, monkeypatch, program):
    import hashlib
    run, shapes = _hybrid_cell_runner(monkeypatch)
    lowered = _lower_hybrid_program(sds, run, shapes, program)
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""',
                  lowered.as_text())
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == GRANITE_HLO[
        program]


def _hybrid_moe_cell_runner(monkeypatch):
    """The Nemotron cell's runner: the configuration as the benchmark's
    driver reads it, all 52 blocks, shapes only."""
    import json
    import sys
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers.engine_closed_loop_hybrid_moe import routed_model
    from benchmarks.lib import hybrid_moe_state
    with open(os.path.join(root, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b-ep8.json")) as f:
        conf = json.load(f)
    m = routed_model(conf)
    run = _bare_runner(monkeypatch, NemotronHConfig.from_published(
        {k: v for k, v in m.items() if k != "local_experts"},
        local_experts=tuple(m["local_experts"]),
        dtype=conf["assumed"]["torch_dtype"]), conf["engine"],
        recurrent=True)
    return run, {k: shape for k, (shape, _)
                 in hybrid_moe_state.shapes(conf).items()}


MOE_PART = ["moe.route", "moe.experts", "moe.shared"]


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_hybrid_moe_cell_programs_keep_their_kernels_and_scope_order(
        sds, monkeypatch, record_property, program):
    """The Nemotron cell's programs at the published widths, all 52
    blocks, each ONE part: one ``ssm_state_update`` call a Mamba block
    (8 groups of B and C), two ``grouped_matmul`` calls an expert block
    (up and down, the held experts stored 1,920 wide), one
    ``paged_attention`` call an attention block at 2 KV heads of 128
    (decode), the flash kernel (prefill), the scopes in block order and
    no ``mlp`` scope.  The compiled ``decode_step`` updates both kinds
    of state where they lie and fits the chip."""
    run, shapes = _hybrid_moe_cell_runner(monkeypatch)
    cfg = run.config
    lowered = _lower_hybrid_program(sds, run, shapes, program)
    parts = {"mamba": (MAMBA_DECODE if program == "decode_step"
                       else MAMBA_PREFILL),
             "attention": (ATTN_DECODE if program == "decode_step"
                           else ATTN_PREFILL), "moe": MOE_PART + ["mlp"]}
    want = ["embed"]
    for kind in cfg.layer_types:
        want += [s for s in parts[kind] if s != "mlp"]
    assert _scope_order(lowered, set(want) | {"head", "mlp"}) == (
        want + ["head"])
    text = lowered.as_text()
    calls = text.count("@tpu_custom_call")
    n_m, n_a, n_e = (len(cfg.layers_of(k))
                     for k in ("mamba", "attention", "moe"))
    assert (n_m, n_a, n_e) == (23, 6, 23)
    if program == "prefill":
        assert calls == n_a + 2 * n_e       # flash; up and down products
        return
    # the attention blocks' calls share one body
    assert calls == n_m + 1 + 2 * n_e
    assert _paged_body_and_sites(text) == (1, n_a)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    by_stem = {stem: _hlo_lines(hlo, stem) for stem in (
        "ssm_state_update", "grouped_matmul", "paged_attention")}
    assert [len(by_stem[k]) for k in (
        "ssm_state_update", "grouped_matmul", "paged_attention")] == [
        n_m, 2 * n_e, n_a]
    # each roofline's pattern finds its own kernel's events and no other
    # (the experts' by the decode step's 640 sorted rows)
    every = sum(by_stem.values(), [])
    for stem, metric in (
            ("ssm_state_update", "ssm_update_roofline.serve.hybrid_moe"),
            ("grouped_matmul", "moe_experts_roofline.serve.hybrid_moe"),
            ("paged_attention",
             "paged_attention_roofline.serve.hybrid_moe")):
        names = [n for n in event_names(compiled)
                 if n.startswith(tuple("%" + s for s in by_stem))]
        found = [n for n in names if any(
            re.search(p, n) for p in _metric_events(metric))]
        assert len(found) == len(by_stem[stem]), metric
        assert all(n.startswith("%" + stem) for n in found), metric
    assert len(every) == n_m + n_a + 2 * n_e
    mem = compiled.memory_analysis()
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)
    print(f"nemotron decode_step: temp {mem.temp_size_in_bytes}, "
          f"arguments {mem.argument_size_in_bytes}, "
          f"aliased {mem.alias_size_in_bytes}")
    assert mem.temp_size_in_bytes < 400e6
    # weights 10.52 + the held experts' lane padding 0.25 + ssm 1.54
    # (bfloat16) + conv 0.05 + K/V 1.61 GB
    assert 13.9e9 < mem.argument_size_in_bytes < 14.1e9
    # the state pools and the K/V pools are all updated in place
    assert mem.alias_size_in_bytes > 1.54e9 + 1.6e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 0.9 * 2**34


def _granite_moe_cell_runner(monkeypatch):
    """The Granite 4.0-H Small cell's runner: the configuration as the
    benchmark's driver reads it, its 10 layers, shapes only."""
    import json
    import sys
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers.engine_closed_loop_granite_moe import routed_model
    from benchmarks.lib import granite_moe_state
    with open(os.path.join(root, "benchmarks", "configs",
                           "granite-4.0-h-small-ep2-l10.json")) as f:
        conf = json.load(f)
    m = routed_model(conf)
    run = _bare_runner(monkeypatch, GraniteHybridConfig.from_published(
        {k: v for k, v in m.items() if k != "local_experts"},
        local_experts=tuple(m["local_experts"]),
        dtype=conf["assumed"]["torch_dtype"]), conf["engine"],
        recurrent=True)
    return run, {k: shape for k, (shape, _)
                 in granite_moe_state.shapes(conf).items()}


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_granite_moe_cell_programs_keep_their_kernels_and_scope_order(
        sds, monkeypatch, record_property, program):
    """The Granite 4.0-H Small cell's programs at the published widths,
    its 10 layers, each a mixer and then the expert part: one
    ``ssm_state_update`` call a Mamba layer at 128 heads (H x P = 8,192),
    one ``paged_attention`` call at 8 KV heads of 128 (decode), the
    flash kernel (prefill), two ``grouped_matmul`` calls a layer (gate |
    up 1,536 wide as one, down), the scopes in layer order and no
    ``mlp`` scope (the shared MLP is the expert part's).  The compiled
    ``decode_step`` updates both kinds of state where they lie and fits
    the chip."""
    from paddle_tpu.serving.parallel import recurrent
    run, shapes = _granite_moe_cell_runner(monkeypatch)
    cfg = run.config
    decode = program == "decode_step"
    mixers = {"mamba": MAMBA_DECODE if decode else MAMBA_PREFILL,
              "attention": ATTN_DECODE if decode else ATTN_PREFILL}
    want = ["embed"]
    for kind in cfg.layer_types:
        mixer = [s for s in mixers[kind] if s != "mlp"]
        # the layer's state is written after its last part
        write = [s for s in mixer if s in ("ssm.write", "kv.write")
                 and not decode]
        want += [s for s in mixer if s not in write] + MOE_PART + write
    slots = run.max_slots
    if decode:
        lowered = run._make_step_fn().lower(
            {name: sds(shape) for name, shape in shapes.items()},
            *(sds(recurrent.kv_pool_shape(cfg, run.num_pages,
                                          run.page_size)),) * 2, (), (),
            sds((slots, run.table_width), jnp.int32),
            *(sds((slots,), jnp.int32),) * 3, sds((1, slots), jnp.int32),
            sds((), jnp.int32), sds((0,), jnp.float32),
            sds((0,), jnp.float32), (), (), sds((5,), jnp.int32),
            recurrent.state_pools(cfg, slots, zeros=sds))
    else:
        lowered = _lower_hybrid_program(sds, run, shapes, program)
    assert _scope_order(lowered, set(want) | {"head", "mlp"}) == (
        want + ["head"])
    text = lowered.as_text()
    calls = text.count("@tpu_custom_call")
    n_m, n_a = len(cfg.mamba_layers), len(cfg.attention_layers)
    assert (n_m, n_a, cfg.num_hidden_layers) == (9, 1, 10)
    if not decode:
        assert calls == n_a + 2 * 10        # flash; gate|up and down
        return
    assert calls == n_m + 1 + 2 * 10
    compiled = lowered.compile()
    hlo = compiled.as_text()
    by_stem = {stem: _hlo_lines(hlo, stem) for stem in (
        "ssm_state_update", "grouped_matmul", "paged_attention")}
    assert [len(by_stem[k]) for k in (
        "ssm_state_update", "grouped_matmul", "paged_attention")] == [
        n_m, 2 * 10, n_a]
    # each roofline's pattern finds its own kernel's events and no other
    # (the experts' by the decode step's 1,216 sorted rows)
    names = [n for n in event_names(compiled)
             if n.startswith(tuple("%" + s for s in by_stem))]
    for stem, metric in (
            ("ssm_state_update", "ssm_update_roofline.serve.granite_moe"),
            ("grouped_matmul", "moe_experts_roofline.serve.granite_moe"),
            ("paged_attention",
             "paged_attention_roofline.serve.granite_moe")):
        found = [n for n in names if any(
            re.search(p, n) for p in _metric_events(metric))]
        assert len(found) == len(by_stem[stem]), metric
        assert all(n.startswith("%" + stem) for n in found), metric
    mem = compiled.memory_analysis()
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)
    print(f"granite-moe decode_step: temp {mem.temp_size_in_bytes}, "
          f"arguments {mem.argument_size_in_bytes}, "
          f"aliased {mem.alias_size_in_bytes}")
    assert mem.temp_size_in_bytes < 400e6
    # weights 9.51 + ssm 1.21 (bfloat16) + conv 0.03 + K/V 1.07 GB
    assert 11.8e9 < mem.argument_size_in_bytes < 11.9e9
    # the state pools and the K/V pools are all updated in place
    assert mem.alias_size_in_bytes > 1.20e9 + 1.07e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 0.9 * 2**34


@pytest.mark.parametrize("rows,k,n,tile,timed", [
    (1216, 4096, 1536, 16, True),   # a decode step's gate | up: 640 + 36 x 16
    (1216, 768, 4096, 16, True),    # ... and its down
    (7168, 4096, 1536, 128, False),  # a prefill of 256 tokens
], ids=["decode-gateup", "decode-down", "prefill-gateup"])
def test_grouped_matmul_at_the_granite_moe_cells_shapes(sds, rows, k, n,
                                                        tile, timed):
    """36 held experts 768 wide, gate | up as one call 1,536 wide: whole
    lane tiles, no padding; the experts' roofline times the decode
    step's calls alone."""
    text = compiled_text(
        lambda x, w, e, live: GF.grouped_matmul(x, w, e, live, tile_m=tile),
        sds((rows, k)), sds((36, k, n)), sds((rows // tile,), jnp.int32),
        sds((), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    lines = _hlo_lines(text, "grouped_matmul")
    assert len(lines) == 1
    assert timed == any(re.search(p, lines[0]) for p in _metric_events(
        "moe_experts_roofline.serve.granite_moe"))


def test_ssm_state_update_at_128_heads(sds):
    """The state-update kernel at the Granite 4.0-H Small cell's pool,
    ``[9, 64, 128, 8192]`` float32 (4 MiB a slot a layer), one group:
    one call, the pool updated where it lies."""
    from paddle_tpu.ops.pallas import ssm_update as U
    compiled = jax.jit(
        lambda pool, d, x, b, c, a: U.ssm_state_update(pool, 3, d, x, b, c,
                                                       a),
        donate_argnums=0).lower(
        sds((9, 64, 128, 8192), jnp.float32), sds((64, 8192), jnp.float32),
        sds((64, 8192), jnp.float32), sds((64, 1, 128), jnp.float32),
        sds((64, 1, 128), jnp.float32), sds((64,), jnp.int32)).compile()
    assert len(_hlo_lines(compiled.as_text(), "ssm_state_update")) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < (8 << 20)


@pytest.mark.parametrize("cell,slots,width,calls", [
    ("mistral", 32, 64, 16), ("granite", 64, 256, 4),
    ("nemotron", 64, 256, 6)])
def test_decode_steps_trace_and_lower_the_paged_kernel_once(
        sds, monkeypatch, cell, slots, width, calls):
    """What keeps set-up flat whatever the kernel's body holds, tested
    without a clock: each paged cell's ``decode_step`` at the depth it
    runs lowers to ONE paged-kernel body and a call of it an attention
    layer (every process pays tracing and lowering, whether or not the
    compile cache then serves the executable), and compiles to that many
    ``%paged_attention`` custom calls whose first operands are still
    the table ``[slots, width]`` and the lengths ``[slots]``, where the
    Mistral cell's roofline looks for them."""
    if cell == "mistral":
        run, shapes = _cell_runner(monkeypatch, layers=None)
        lowered = _lower_decode_program(sds, run, shapes)
    else:
        run, shapes = (_hybrid_cell_runner if cell == "granite"
                       else _hybrid_moe_cell_runner)(monkeypatch)
        lowered = _lower_hybrid_program(sds, run, shapes, "decode_step")
    assert (run.max_slots, run.table_width) == (slots, width)
    assert _paged_body_and_sites(lowered.as_text()) == (1, calls)
    lines = _hlo_lines(lowered.compile().as_text(), "paged_attention")
    assert len(lines) == calls
    for ln in lines:
        assert re.search(
            r'custom_call_target="tpu_custom_call", '
            rf'operand_layout_constraints=\{{s32\[{slots},{width}\]\S* '
            rf's32\[{slots}\]\S* s32\[1\]', ln), ln[:600]


def _donated(lowered) -> list[bool]:
    """Whether each argument of the lowered program's main function is
    donated (``jax.buffer_donor`` or an aliased output), in order."""
    text = lowered.as_text()
    sig = text[text.index("func.func public @main("):]
    sig = sig[:sig.index(") -> ")]
    args = re.split(r",?\s*(?=%arg\d+: )", sig[sig.index("%arg0"):])
    return [("jax.buffer_donor" in a or "tf.aliasing_output" in a)
            for a in args if a]


@pytest.mark.parametrize("cell,low_mb,high_mb", [
    ("mistral", 48, 60), ("gigachat", 10, 18)])
def test_cell_decode_steps_lend_the_ring_and_keep_their_temporaries(
        sds, monkeypatch, record_property, cell, low_mb, high_mb):
    """Both cells' ``decode_step`` at the depth they run (16 layers;
    1 dense + 4 expert layers): the ring is the one piece of decode
    state that is NOT donated (the host fetches step n's after step n+1
    is out), which costs no temporary worth the name: 53.9 MB and 14 MB
    declared before it (PERF.md), one Pallas call a layer still."""
    if cell == "mistral":
        run, shapes = _cell_runner(monkeypatch, layers=None)
        lowered = _lower_decode_program(sds, run, shapes)
        kernels = run.config.num_hidden_layers
    else:
        run, shapes = _latent_cell_runner(monkeypatch)
        from paddle_tpu.serving.parallel import latent
        slots = run.max_slots
        pool = sds(latent.pool_shape(run.config, run.num_pages,
                                     run.page_size))
        rope = sds((run._rope_len, run.config.qk_rope_head_dim),
                   jnp.float32)

        def i32(*shape):
            return sds(shape, jnp.int32)
        lowered = run._make_step_fn().lower(
            {name: sds(shape) for name, shape in shapes.items()}, pool,
            (), (), (), i32(slots, run.table_width), i32(slots),
            i32(slots), i32(slots), i32(1, slots), i32(), rope, rope, (),
            (), sds(latent.counters0().shape, latent.counters0().dtype), ())
        kernels = None
    donated = _donated(lowered)
    n_state = len(shapes)
    # after the weights: pool(s), table, pos, tok, active, ring, ridx
    tail = donated[n_state:]
    pools = 2 if cell == "mistral" else 1
    assert tail[:pools] == [True] * pools               # the pools
    table, pos, tok, active, ring, ridx = tail[pools:pools + 6]
    assert (pos, tok, ridx) == (True, True, True)
    assert (table, active) == (False, False)            # read, not written
    assert ring is False                                # lent, not given
    compiled = lowered.compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    record_property("temp_size_in_bytes", temp)
    print(f"{cell} decode_step: temp_size_in_bytes {temp}")
    assert low_mb * 1e6 < temp < high_mb * 1e6
    if kernels is not None:         # one body, a call of it a layer
        assert _paged_body_and_sites(lowered.as_text()) == (1, kernels)
        assert len(_hlo_lines(compiled.as_text(),
                              "paged_attention")) == kernels


def test_slot_patch_is_one_program_at_the_cells_shapes(sds, monkeypatch):
    """``push_slot`` for the Mistral cell's 32 slots of 64 pages: one
    program whose slot is data, every state array donated and updated
    where it lies."""
    run, _ = _cell_runner(monkeypatch)
    slots, width = run.max_slots, run.table_width

    def i32(*shape):
        return sds(shape, jnp.int32)
    lowered = run._make_push_fn().lower(
        i32(slots, width), i32(slots), i32(slots), i32(slots), (),
        i32(5 + width))
    assert run.push_traces == 1
    assert _donated(lowered) == [True, True, True, True, False]
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)
    text = compiled.as_text()
    assert "custom_call_target=\"tpu_custom_call\"" not in text
    assert text.count("input_output_alias") == 1
    for out in range(4):                # each output aliases its input
        assert re.search(rf"\{{{out}\}}: \({out}, \{{\}}", text), out


def test_int8_pages_decode_step_reports_its_temporaries(sds, monkeypatch,
                                                        record_property):
    """No Pallas call pins the pool's layout where the pages are int8
    (the step attends through the dequantizing gather): what the
    compiler declares there is reported, and held to nothing but that
    the int8 pools themselves are not copied."""
    run, shapes = _cell_runner(monkeypatch, kv_quant=True)
    compiled = _compile_decode_program(sds, run, shapes)
    names = event_names(compiled)
    assert not _paged_events(names)
    temp = compiled.memory_analysis().temp_size_in_bytes
    record_property("temp_size_in_bytes", temp)
    print(f"int8 pages, 2 layers: temp_size_in_bytes {temp}")
    pool_sized = _pool_sized(names, run)
    assert pool_sized                       # the in-place row scatters
    for stem, opcode in pool_sized:
        assert "copy" not in opcode and "copy" not in stem, (stem, opcode)


def test_mla_paged_attention_at_the_cells_shapes(sds):
    """64 slots, 256 pages a slot, 5 layers in one pool, rows of 576
    values (declared at the 640 lanes they occupy), 64 heads over a
    latent of 512 + 64: the grid the kernel's own block rule gives, its
    buffers inside the VMEM the call asks for."""
    slots, width, heads, page = 64, 256, 64, 16
    assert MLA.row_width(576) == 640

    def call(ql, qr, pool, t, n):
        return MLA.mla_paged_attention(ql, qr, pool, 3, t, n,
                                       sm_scale=0.1447)
    args = (sds((slots, heads, 512)), sds((slots, heads, 64)),
            sds((5, slots * width + 1, page, 640)),
            sds((slots, width), jnp.int32), sds((slots,), jnp.int32))
    text = compiled_text(call, *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    lines = _hlo_lines(text, "mla_paged_attention")
    assert len(lines) == 1
    assert any(re.search(p, lines[0])
               for p in _metric_events("mla_decode_roofline.serve"))
    eqn = _pallas_call(call, *args)
    blk = MLA.pages_per_block(page, width)
    assert blk * page == MLA.BLOCK_TOKENS == 4096
    mapping = eqn.params["grid_mapping"]
    assert mapping.grid == (slots, -(-width // blk)) == (64, 1)
    vmem = _scratch_vmem(eqn)
    # two buffers of a block's rows, the accumulator, two lane-wide sides
    assert vmem == (2 * blk * page * 640 * 2 + heads * 512 * 4
                    + 2 * heads * 128 * 4)
    asked = dict(eqn.params["compiler_params"]).get("mosaic_tpu")
    limit = getattr(asked, "vmem_limit_bytes", None) or V5E_SCOPED_VMEM
    # beside them the pipeline holds each query and output block twice
    assert vmem + 2 * heads * (512 + 64 + 512) * 2 <= limit


def test_cache_write_rewrites_pages_in_place(sds):
    """The decode step's 64 new rows: whole pages through VMEM, the pool
    aliased to the output, so the compiled call declares no copy."""
    slots = 64
    compiled = jax.jit(
        lambda pool, page, off, rows: MLA.write_rows(pool, 2, page, off,
                                                     rows),
        donate_argnums=0).lower(
        sds((5, slots * 256 + 1, 16, 640)), sds((slots,), jnp.int32),
        sds((slots,), jnp.int32), sds((slots, 576))).compile()
    assert len(_hlo_lines(compiled.as_text(), "mla_cache_write")) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("rows,k,n,tile", [
    (768, 7168, 2048, 16),      # a decode step's gate / up: 64 x 8 + 16 x 16
    (768, 2048, 7168, 16),      # ... and its down
    (10240, 7168, 2048, 128),   # a prefill of 1024 tokens
], ids=["decode-up", "decode-down", "prefill-up"])
def test_grouped_matmul_at_the_cells_shapes(sds, rows, k, n, tile):
    """16 held experts of 7168 x 2048: a block fits VMEM only tiled."""
    text = compiled_text(
        lambda x, w, e, live: GF.grouped_matmul(x, w, e, live, tile_m=tile),
        sds((rows, k)), sds((16, k, n)), sds((rows // tile,), jnp.int32),
        sds((), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    lines = _hlo_lines(text, "grouped_matmul")
    assert len(lines) == 1
    # the experts' roofline times the decode step's calls only
    timed = any(re.search(p, lines[0])
                for p in _metric_events("moe_experts_roofline.serve"))
    assert timed == (rows == 768)


def test_flash_masked_at_the_latent_familys_head_dim(sds):
    # MLA's prefill: 64 heads of 128 + 64 = 192, values 192, no padding
    s, d = 1024, 192

    def prefill_attention(q, k, v, mask):
        vecs = FM.padding_mask_to_intervals(mask[:, :, 0, :], s)
        return FA._pallas_sdpa_masked(q, k, v, vecs, True)

    n = compile_kernels(prefill_attention, *[sds((1, s, 64, d))] * 3,
                        sds((1, 1, 1, s), jnp.bool_))
    assert n == 1


def test_pallas_decode(sds):
    cache = sds((8, KVH, 2048, HD))
    n = compile_kernels(
        lambda q, k, v, pos: DA._pallas_decode(q, k, v, pos, 256),
        sds((8, NH, HD)), cache, cache, sds((8,), jnp.int32))
    assert n == 1


def _qkv(sds, s, batch=1):
    return (sds((batch, s, NH, HD)), sds((batch, s, KVH, HD)),
            sds((batch, s, KVH, HD)))


def test_flash_forward(sds):
    n = compile_kernels(lambda q, k, v: FA._pallas_sdpa(q, k, v, True),
                        *_qkv(sds, 2048))
    assert n == 1


def test_flash_grad(sds):
    def loss(q, k, v):
        return jnp.sum(FA._pallas_sdpa(q, k, v, True).astype(jnp.float32))

    n = compile_kernels(jax.grad(loss, argnums=(0, 1, 2)),
                        *_qkv(sds, 2048))
    assert n == 3       # forward with lse, dq, dk/dv


def test_flash_masked_from_key_padding(sds):
    # the serving prefill: causal + a key-padding mask over the bucket
    s = 1024

    def prefill_attention(q, k, v, mask):
        vecs = FM.padding_mask_to_intervals(mask[:, :, 0, :], s)
        return FA._pallas_sdpa_masked(q, k, v, vecs, True)

    n = compile_kernels(prefill_attention, *_qkv(sds, s),
                        sds((1, 1, 1, s), jnp.bool_))
    assert n == 1


@pytest.mark.parametrize("rows,width", [(4096, 4096), (16384, 2048)])
def test_rms_norm(sds, rows, width):
    # 512 rows at width 4096 asked for 24.21 MB of the 16 MB scoped VMEM
    n = compile_kernels(lambda x, w: RN._pallas_rms(x, w, eps=1e-6),
                        sds((rows, width)), sds((width,)))
    assert n == 1


def test_rms_row_block_shrinks_with_width():
    assert RN._row_block(16384, 2048, 2) == 512     # as before the fix
    assert RN._row_block(4096, 4096, 2) == 256      # was 512: refused
    assert RN._row_block(4096, 4096, 4) == 128
    assert RN._row_block(24, 4096, 2) == 8          # divides the rows
    assert RN._row_block(7, 4096, 2) == 1
