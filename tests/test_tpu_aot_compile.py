"""Ask the chip's compiler, without the chip (ISSUE 21).

The TPU compiler is installed here and compiles for a device that is
described, not attached. Every case lowers a kernel or a compiled step
of the main path at the widths chip_smoke.py runs on the chip — 16
heads of 128, bf16, max_len 2048, a pool the size one chip holds — and
compiles it for one described v5e: what Mosaic refuses (a tile that
does not fit VMEM, prefetch operands that do not fit SMEM, a slice it
cannot lay out) is refused HERE, where interpret-mode tests cannot see
it. Depth is cut to two layers; a kernel's compile does not depend on
how many layers call it. Nothing runs, so nothing here says anything
about results or times.

The topology is described inside a fixture of this file — never while
a module is imported — because only one process may load the TPU's
library: under pytest-xdist only the worker that is handed this file
may touch it, and every worker must collect the same tests.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.models import transformer as tlm
from paddle_tpu.parallel import paged_attention as pa
from paddle_tpu.parallel.flash_attention import flash_attention

# chip_smoke.py's serving widths
H, DH, L, BT, S = 16, 128, 2048, 16, 8
MAXB = L // BT
# pools the size the smoke's engines allocate on a 16 GB chip: ~12 GiB
# of 2 MiB bf16 blocks, twice as many half-size int8/fp8 blocks
NB, NB_QUANT = 6000, 12000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Steer code that asks the backend (the engine's kernel default,
    resolve_interpret) down its accelerator branch: the host here is a
    CPU, the compile target is not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled(fn, *args, **kwargs):
    """Compile `fn` (a function, or one of the engine's jitted steps)
    for the devices its argument shapes are placed on, at the chip's
    own matmul precision (conftest pins float32 for the numeric
    tests)."""
    lower = fn.lower if hasattr(fn, "lower") else jax.jit(fn).lower
    with jax.default_matmul_precision(None):
        return lower(*args, **kwargs).compile()


def _compile(fn, *args, **kwargs):
    """-> the compiled module's text."""
    return _compiled(fn, *args, **kwargs).as_text()


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), tree)


def _sds(one_chip):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(one_chip, backward):
    q = _sds(one_chip)((2, L, H, DH), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    assert "tpu_custom_call" in _compile(fn, q, q, q)


def _pool(sds, quant):
    """Pool (and scale) shapes of the smoke's bf16 / quantized engine."""
    if quant == "none":
        return sds((NB, BT, H, DH), jnp.bfloat16), {}
    scale = sds((NB_QUANT, H), jnp.float32)
    return (sds((NB_QUANT, BT, H, DH), tlm.kv_storage_dtype(quant)),
            {"k_scale": scale, "v_scale": scale})


@pytest.mark.parametrize("quant", ["none", "int8", "fp8"])
def test_paged_decode_attention_compiles_at_the_chips_pool(one_chip,
                                                           quant):
    """The quantized cases are the refusal this PR repaired: the
    pool's [NB, H] scales as a prefetch operand need NB x 128 words of
    scalar memory; the scales of the blocks the tables name do not
    grow with the pool."""
    sds = _sds(one_chip)
    pool, scales = _pool(sds, quant)

    def fn(q, k, v, tables, pos, **sc):
        return pa.paged_decode_attention(q, k, v, tables, pos,
                                         interpret=False, **sc)

    assert "tpu_custom_call" in _compile(
        fn, sds((S, H, DH), jnp.bfloat16), pool, pool,
        sds((S, MAXB), jnp.int32), sds((S,), jnp.int32), **scales)


def test_paged_verify_attention_compiles(one_chip):
    sds = _sds(one_chip)
    pool, _ = _pool(sds, "none")
    assert "tpu_custom_call" in _compile(
        lambda q, k, v, t, p: pa.paged_verify_attention(
            q, k, v, t, p, interpret=False),
        sds((S, 4, H, DH), jnp.bfloat16), pool, pool,
        sds((S, MAXB), jnp.int32), sds((S,), jnp.int32))


@pytest.mark.parametrize("chunk,quant", [
    (8, "none"), (512, "none"), (L, "none"), (L, "int8")])
def test_paged_prefill_attention_compiles_at_every_bucket_size(
        one_chip, chunk, quant):
    """512 rows and up are the other refusal this PR repaired: a whole
    chunk as one q block overflows VMEM; row tiles do not."""
    sds = _sds(one_chip)
    pool, scales = _pool(sds, quant)

    def fn(q, k, v, table, start, **sc):
        return pa.paged_prefill_attention(q, k, v, table, start,
                                          interpret=False, **sc)

    assert "tpu_custom_call" in _compile(
        fn, sds((chunk, H, DH), jnp.bfloat16), pool, pool,
        sds((MAXB,), jnp.int32), sds((), jnp.int32), **scales)


@pytest.mark.parametrize("kernel", ["decode", "verify", "prefill"])
def test_paged_kernels_carry_their_names(one_chip, kernel):
    """The custom call is named after its kernel and carries the name
    in `kernel_metadata` (ISSUE 25): what a device trace's event text
    holds, so a reduction can find the kernel by name and not by the
    shape of what it returns."""
    sds = _sds(one_chip)
    pool, _ = _pool(sds, "none")
    name = "paged_%s_attention" % kernel
    fn = getattr(pa, name)
    if kernel == "prefill":
        args = (sds((8, H, DH), jnp.bfloat16), pool, pool,
                sds((MAXB,), jnp.int32), sds((), jnp.int32))
    else:
        rows = () if kernel == "decode" else (4,)
        args = (sds((S,) + rows + (H, DH), jnp.bfloat16), pool, pool,
                sds((S, MAXB), jnp.int32), sds((S,), jnp.int32))
    text = _compile(lambda *a: fn(*a, interpret=False), *args)
    call = [line for line in text.split("\n")
            if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(call) == 1 and call[0].lstrip().startswith("%" + name)
    assert re.search(r'kernel_metadata=\{\s*"kernel":"%s"\s*\}' % name, text)


def test_decode_kernel_at_the_cells_geometry_is_what_the_benchmark_reads(
        one_chip):
    """The benchmark's `paged_attn_roofline` finds the decode kernel in
    a device trace by the text of its instruction (`op_match` of
    benchmarks/chip/layer_metrics/paged_attn_roofline.json: today the
    SHAPE of the custom call's result). Compile the decode call at the
    serving cells' geometry (32 slots, 3,500 blocks of 16 tokens) and
    hold the compiled text to that pattern, read from the file — a
    kernel change that moves the result's shape fails here, not as
    `output_malformed` on the chip. The file is read, never edited."""
    rx = _metric_pattern("paged_attn_roofline")
    sds = _sds(one_chip)
    slots, blocks = 32, 3500
    pool = sds((blocks, BT, H, DH), jnp.bfloat16)
    text = _compile(
        lambda q, k, v, t, p: pa.paged_decode_attention(
            q, k, v, t, p, interpret=False),
        sds((slots, H, DH), jnp.bfloat16), pool, pool,
        sds((slots, MAXB), jnp.int32), sds((slots,), jnp.int32))
    found = list(rx.finditer(text))
    assert len(found) == 1  # the kernel, and nothing else of the call
    # the matched instruction is the kernel's own: named after it,
    # a TPU custom call, the name in its metadata
    line = text[text.rindex("\n", 0, found[0].start()) + 1:]
    line = line[:line.index("metadata={op_name=")]
    assert line.lstrip().startswith("%paged_decode_attention")
    assert 'custom_call_target="tpu_custom_call"' in line
    assert re.search(r'kernel_metadata=\{\s*"kernel":'
                     r'"paged_decode_attention"\s*\}', line)


def _engine(one_chip, **kw):
    """A default-options engine at the smoke's widths, depth cut to 2,
    built on shapes alone, with the argument shapes of its compiled
    steps placed on the described chip."""
    from paddle_tpu.serving import ServingEngine

    cfg = tlm.TransformerConfig(vocab=32000, dim=H * DH, heads=H,
                                layers=2, max_len=L, dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: tlm.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, kv_pool_blocks=4, **kw)
    assert eng.paged_kernel == "fused"
    assert (eng.max_slots, eng.kv_block_tokens) == (S, BT)
    cache = jax.eval_shape(lambda: tlm.init_paged_kv_cache(
        cfg, NB, BT, kv_quant=eng.kv_quant))
    sds = _sds(one_chip)
    bands = (sds((S, MAXB), jnp.int32), sds((S,), jnp.int32),
             sds((S,), jnp.int32), sds((S,), jnp.bool_),
             sds((S,), jnp.float32), sds((S,), jnp.int32),
             sds((S, 2), jnp.uint32))
    return (eng, _placed(params, one_chip), _placed(cache, one_chip),
            bands, sds)


def _decode_text(eng, params, cache, bands, sds):
    """The engine's one decode program, compiled for the chip."""
    limits_eos = (sds((S,), jnp.int32), sds((S,), jnp.int32))
    return _compile(eng._decode_fn, params, cache, *bands, *limits_eos)


def _text_digest(text):
    """sha256 of a compiled program's text less locations and less the
    kernels' embedded bodies (serialized with their files' line
    numbers) -> (digest, kernels)."""
    text, kernels = re.subn(r'"body":"[^"]*"', '"body":""',
                            _without_locations(text))
    return hashlib.sha256(text.encode()).hexdigest(), kernels


def _without_locations(text):
    """Compiled text less what names the source: the stack-frame
    tables at its head, the ids into them, and op metadata."""
    head = text.find("\nFileNames")
    if head >= 0:
        text = text[:head] + text[text.index("\n\n",
                                             text.index("StackFrames")):]
    text = re.sub(r",? ?stack_frame_id=\d+", "", text)
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("kw", [{}, {"async_dispatch": False}],
                         ids=["default", "lockstep"])
def test_engine_decode_step_compiles(one_chip, as_on_tpu, kw):
    eng, params, cache, bands, sds = _engine(one_chip, **kw)
    # one kernel call per layer
    assert _decode_text(eng, params, cache, bands,
                        sds).count("tpu_custom_call") >= 2


def _family_decode_text(family, one_chip, **kw):
    """-> (engine, its decode program compiled for the chip), the GPT
    block's or the hybrid family's."""
    if family == "hybrid":
        eng, params, cache, sds = _hybrid_engine(one_chip, **kw)
        return eng, _hybrid_decode_text(eng, params, cache, sds)
    eng, params, cache, bands, sds = _engine(one_chip, **kw)
    return eng, _decode_text(eng, params, cache, bands, sds)


@pytest.mark.parametrize("family", ["gpt", "hybrid"])
def test_both_depths_compile_the_same_decode_program(one_chip, as_on_tpu,
                                                     family):
    """One program, two depths (ISSUE 29), for every family (ISSUE 30:
    the hybrid family's default resolves to ahead too): the lock-step
    engine's decode program is the default engine's, instruction for
    instruction — `async_dispatch` chooses when the host reads a step,
    never what the chip runs — and its name still matches the pattern
    `decode_step_ms` reads it by."""
    texts = []
    for kw in ({}, {"async_dispatch": False}):
        eng, text = _family_decode_text(family, one_chip, **kw)
        texts.append(_without_locations(text))
        assert eng.async_dispatch == (not kw)
        assert (eng._win is not None) == (family == "hybrid")
    assert texts[0] == texts[1]
    assert texts[0].count("tpu_custom_call") >= 2
    module = re.match(r"HloModule (\S+?),", texts[0]).group(1)
    assert re.search(_metric_spec("decode_step_ms")["args"]["program_match"],
                     module + "(1)")


# the GPT decode program as the tree compiles it, with the GPT block's
# decode call on the ring body (its pools handed over as their merged
# view, no work list beside the call):
# sha256 of `_decode_text(*_engine(...))` less locations and less the
# kernels' embedded bodies (serialized with the line numbers of
# paged_attention.py), and of the decode call's jaxpr at the cells'
# geometry, which holds the kernel's body itself. A PR that MEANS to
# change the GPT decode program replaces both (the failing assertion
# prints the new one) and says so in CHANGES.md.
_GPT_DECODE_TEXT_SHA = (
    "1cece85e628d27469fb53240acbb5de2d2cc3786187c4e5b447d81437295d0aa")
_GPT_DECODE_CALL_JAXPR_SHA = (
    "63637e344ace670dfc39d6bb22114361751af2afc5ae2555f52be5ef8a48083b")


def test_gpt_decode_program_is_the_text_the_parent_compiled(programs):
    # two kernels: the two layers' decode calls
    text = programs("gpt").text
    assert _text_digest(text) == (_GPT_DECODE_TEXT_SHA, 2)
    sh = jax.ShapeDtypeStruct
    pool = sh((3500, BT, H, DH), jnp.bfloat16)
    with jax.default_matmul_precision(None):  # the chip's own, as `_compile`
        jaxpr = str(jax.make_jaxpr(
            lambda q, k, v, t, p: pa.paged_decode_attention(
                q, k, v, t, p, interpret=False))(
            sh((32, H, DH), jnp.bfloat16), pool, pool,
            sh((32, MAXB), jnp.int32), sh((32,), jnp.int32)))
    assert hashlib.sha256(jaxpr.encode()).hexdigest() \
        == _GPT_DECODE_CALL_JAXPR_SHA
    # the merged view of a pool is the memory the pool already is: each
    # call takes its K and V as bitcasts of the cache, no instruction
    # copies a pool, the temporaries stay under one pool, and the cache
    # (two layers' K and V) is still aliased whole
    one_pool = NB * BT * H * DH * 2
    calls = re.findall(
        r"%paged_decode_attention\S* = \S+ custom-call\(([^)]*)\)", text)
    assert len(calls) == 2
    for operands in calls:
        k, v = operands.split(", ")[-2:]
        assert k.startswith("%bitcast") and v.startswith("%bitcast")
    assert not re.search(r"= bf16\[%d,[^=]* copy\(" % NB, text)
    mem = programs("gpt").compiled.memory_analysis()
    assert mem.temp_size_in_bytes < one_pool
    assert mem.alias_size_in_bytes >= 4 * one_pool


def test_default_decode_program_is_what_the_benchmark_reads(programs):
    """The yardstick of the serving cells (ISSUE 28): a default engine
    runs one step ahead, and its decode program is still the one
    `decode_step_ms` and `paged_attn_roofline` find — named so that
    their `program_match` (read from
    benchmarks/chip/layer_metrics/decode_step_ms.json, never edited)
    matches it as a device trace names it, `jit_<function>(<id>)`, and
    FLAT: the kernel's custom call sits in the entry computation, once
    a layer, where `op_match` finds it — not inside a loop's body."""
    eng, text = programs("gpt").eng, programs("gpt").text
    assert eng.async_dispatch
    module = re.match(r"HloModule (\S+?),", text).group(1)
    program = _metric_spec("decode_step_ms")["args"]["program_match"]
    assert _metric_spec("paged_attn_roofline")["args"][
        "program_match"] == program
    assert re.search(program, module + "(1)")
    assert " while(" not in text
    entry = text[text.index("\nENTRY "):]
    found = _metric_pattern("paged_attn_roofline").findall(entry)
    assert len(found) == 2  # the two layers' calls
    assert eng.metrics.decode_trace_count() == 1


def test_engine_prefill_step_compiles_at_the_largest_bucket(programs):
    assert programs("gpt").eng._bucket(L - 100) == L
    assert programs("gpt", "chunk").text.count("tpu_custom_call") >= 2


def test_data_parallel_step_has_an_all_reduce(topo):
    """A fluid training step under the executor's own sharding rules
    on a four-device mesh: the partitioner must put the gradient
    all-reduce in."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import parallel
    from paddle_tpu.fluid.core.lowering import build_step_fn
    from paddle_tpu.fluid.executor import _mesh_jit_kwargs

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[784], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=512, act="relu")
        p = fluid.layers.fc(input=h, size=10, act="softmax")
        loss = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=p, label=y))
        fluid.optimizer.Momentum(learning_rate=0.01,
                                 momentum=0.9).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    names = sorted(v.name for v in main.list_vars() if v.persistable)
    persist = {n: scope.get(n) for n in names if n in scope}
    feed = {"x": np.zeros((128, 784), np.float32),
            "y": np.zeros((128, 1), np.int32)}
    fn, persist_out = build_step_fn(
        main, feed_names=list(feed), fetch_names=[loss.name],
        persist_names=names, persist_in=list(persist))
    mesh = parallel.make_mesh({"data": 4}, devices=list(topo.devices))
    kw = _mesh_jit_kwargs(mesh, main, feed, list(persist), persist_out,
                          [loss.name])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (persist, feed))
    compiled = jax.jit(fn, donate_argnums=(0,), **kw).lower(
        *shapes, jax.random.PRNGKey(0)).compile()
    assert "all-reduce" in compiled.as_text()
    assert len(compiled.input_shardings[0][1]["x"].device_set) == 4


def test_smem_bound_agrees_with_the_compiler(one_chip):
    """`check_paged_smem` is arithmetic about what the compiler will
    accept; hold it to the compiler on both sides of the bound."""
    sds = _sds(one_chip)
    pool, scales = _pool(sds, "int8")

    def compiles(slots):
        try:
            _compile(
                lambda q, k, v, t, p, **sc: pa.paged_decode_attention(
                    q, k, v, t, p, interpret=False, **sc),
                sds((slots, H, DH), jnp.bfloat16), pool, pool,
                sds((slots, MAXB), jnp.int32), sds((slots,), jnp.int32),
                **scales)
            return True
        except Exception as e:  # whatever type the compiler raises
            assert "smem" in str(e).lower(), e
            return False

    def accepted(slots):
        try:
            pa.check_paged_smem(slots, MAXB, BT, H, True)
            return True
        except ValueError:
            return False

    assert accepted(56) and compiles(56)
    assert not accepted(64) and not compiles(64)


# ---------------------------------------------------------------------
# the hybrid family (ISSUE 27) at the geometry of its cell,
# `phi4flash_reason_closed`: 64 slots, 8,192 positions, 14,336 blocks
# of 32 tokens in the full layer's pool, published widths; depth cut
# to 8 layers, which still holds every kind of layer
# ---------------------------------------------------------------------

HY_S, HY_L, HY_NB, HY_BT = 64, 8192, 14336, 32
HY_MAXB = HY_L // HY_BT


def _hybrid_engine(one_chip, **kw):
    from paddle_tpu.models import sambay as sb
    from paddle_tpu.serving import ServingEngine

    cfg = sb.SambaYConfig(vocab=200064, dim=2560, heads=40, kv_heads=20,
                          layers=8, window=512, max_len=HY_L,
                          dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: sb.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, max_slots=HY_S, kv_pool_blocks=4,
                        kv_block_tokens=HY_BT, prefill_chunk_tokens=4096,
                        **kw)
    assert eng.paged_kernel == "fused"
    cache = jax.eval_shape(
        lambda: sb.SERVING.init_cache(cfg, HY_NB, HY_BT, HY_S))
    return eng, _placed(params, one_chip), _placed(cache, one_chip), \
        _sds(one_chip)


def _metric_spec(name):
    import json
    import pathlib

    return json.loads((pathlib.Path(__file__).resolve().parent.parent
                       / "benchmarks" / "chip" / "layer_metrics"
                       / (name + ".json")).read_text())


def _metric_pattern(name):
    return re.compile(_metric_spec(name)["args"]["op_match"])


def _bands(sds, slots, *table):
    """A decode step's arguments behind the cache: the block tables
    [*table], then token, position, alive, temperature, count, base
    key, limit and EOS a slot."""
    S_ = (slots,)
    return (sds(table, jnp.int32), sds(S_, jnp.int32), sds(S_, jnp.int32),
            sds(S_, jnp.bool_), sds(S_, jnp.float32), sds(S_, jnp.int32),
            sds((slots, 2), jnp.uint32), sds(S_, jnp.int32),
            sds(S_, jnp.int32))


def _chunk_args(sds, rows, *table):
    """A chunk step's arguments behind the cache: the padded tokens,
    the start, the slot's table rows [*table], the true length, the
    temperature and the key."""
    return (sds((rows,), jnp.int32), sds((), jnp.int32),
            sds(table, jnp.int32), sds((), jnp.int32),
            sds((), jnp.float32), sds((2,), jnp.uint32))


def _hybrid_decode_text(eng, params, cache, sds):
    return _compile(eng._decode_fn, params, cache,
                    *_bands(sds, HY_S, 2, HY_S, HY_MAXB))


def test_hybrid_decode_program_is_the_one_the_benchmark_finds(programs):
    """The hybrid family rides the shared loop (ISSUE 29), one step
    ahead of the host by default like the GPT block (ISSUE 30): its
    decode program is built by the one `_make_decode`, so at the
    cell's geometry (64 slots, 8,192 positions in blocks of 32) it is
    still the program `decode_step_ms` and `hybrid_attn_roofline` look
    for — their `program_match` (read from the metric files, never
    edited) matches the module as a device trace names it — and FLAT:
    no loop, both kernels' calls in the entry computation where
    `op_match` finds them, one packed result beside the cache and the
    four advanced bands."""
    eng, text = programs("hybrid").eng, programs("hybrid").text
    assert eng.async_dispatch and eng._win is not None
    module = re.match(r"HloModule (\S+?),", text).group(1)
    for metric in ("decode_step_ms", "hybrid_attn_roofline",
                   "ssm_decode_roofline"):
        program = _metric_spec(metric)["args"]["program_match"]
        assert re.search(program, module + "(1)"), (metric, module)
    assert " while(" not in text
    entry = text[text.index("\nENTRY "):]
    lines = [ln.strip() for ln in entry.split("\n")]
    for metric, calls in (("hybrid_attn_roofline", 4),
                          ("ssm_decode_roofline", 3)):
        rx = _metric_pattern(metric)
        assert len([ln for ln in lines if rx.search(ln)]) == calls, metric
    # the host's one read: tokens, trap flags, magnitude, four bands
    assert re.search(r"s32\[%d\]" % (6 * HY_S + 1), entry)
    assert eng.metrics.decode_trace_count() == 1


def test_hybrid_decode_step_compiles_and_is_what_the_benchmark_reads(
        programs):
    """The decode program of the hybrid cell compiles for the chip with
    both new kernels in it, and the two roofline metrics' patterns
    (`op_match` of `hybrid_attn_roofline.json` and
    `ssm_decode_roofline.json`, matched against an instruction's text
    as a device trace names its events) find exactly the kernels'
    calls: 4 attention calls (2 window, 1 full, 1 cross at this depth)
    and 3 state updates, each named after its kernel in
    `kernel_metadata`. The files are read, never edited."""
    text = programs("hybrid").text
    lines = [ln.strip() for ln in text.split("\n")]
    for metric, kernel, calls in (
            ("hybrid_attn_roofline", "hybrid_decode_attention", 4),
            ("ssm_decode_roofline", "ssm_state_update", 3)):
        rx = _metric_pattern(metric)
        found = [ln for ln in lines if rx.search(ln)]
        assert len(found) == calls, (metric, len(found))
        assert all(ln.startswith("%" + kernel) and " custom-call(" in ln
                   for ln in found)
        assert len(re.findall(r'kernel_metadata=\{\s*"kernel":"%s"\s*\}'
                              % kernel, text)) >= calls
    # besides them, the three K/V writes (2 window layers, the full one)
    assert len([ln for ln in lines if ln.startswith("%paged_kv_write")
                and " custom-call(" in ln]) == 3
    assert text.count("tpu_custom_call") == 10


def test_hybrid_prefill_chunk_compiles_at_the_largest_bucket(programs):
    assert programs("hybrid").eng._bucket(4096 - 100) == 4096
    mem = programs("hybrid", "chunk").compiled.memory_analysis()
    # the tiled attention and the stepwise scan keep the temporaries
    # bounded: about 1 GB at 32 layers, the same here (they do not
    # add up over layers)
    assert mem.temp_size_in_bytes < 1.5e9


# ---------------------------------------------------------------------
# the Mamba-2 / grouped-query family (ISSUE 31) at the geometry of its
# cell, `granite4hmicro_reason_closed`: 64 slots, 8,192 positions,
# 11,264 blocks of 32 tokens, published widths; depth cut to one
# 10-layer period, which holds both kinds of layer (9 Mamba-2, 1
# attention)
# ---------------------------------------------------------------------

GR_S, GR_L, GR_NB, GR_BT, GR_CHUNK = 64, 8192, 11264, 32, 2048
GR_MAXB = GR_L // GR_BT


def _granite_engine(one_chip, **kw):
    from paddle_tpu.models import granite_hybrid as gh
    from paddle_tpu.serving import ServingEngine

    cfg = gh.GraniteHybridConfig(
        vocab=100352, dim=2048, heads=32, kv_heads=8, head_dim=64,
        layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
        mlp_mult=4, mamba_heads=64, mamba_head_dim=64, d_state=128,
        d_conv=4, chunk=256, max_len=GR_L, dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: gh.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, max_slots=GR_S, kv_pool_blocks=4,
                        kv_block_tokens=GR_BT, min_bucket=GR_CHUNK,
                        prefill_chunk_tokens=GR_CHUNK, **kw)
    assert eng.paged_kernel == "fused" and eng._win is None
    cache = jax.eval_shape(
        lambda: gh.SERVING.init_cache(cfg, GR_NB, GR_BT, GR_S))
    return eng, _placed(params, one_chip), _placed(cache, one_chip), \
        _sds(one_chip)


def test_ssd_state_update_kernel_carries_its_name(one_chip):
    """The one-token Mamba-2 state update at the cell's size (64 slots
    of [128, 4096] float32) compiles for the chip, in place, as ONE
    custom call named after the kernel, the name in `kernel_metadata`:
    what `ssd_decode_roofline`'s `op_match` finds in a device trace.
    The batch rule gives the cell four slots a batch (ISSUE 34), and
    Mosaic accepts the two batches and the call's rows in the VMEM the
    call asks for, twice what it holds (beyond the 16 MiB a call is
    scoped by default)."""
    from paddle_tpu.parallel import ssd_update

    assert ssd_update._step_slots(GR_S, 128 * 4096 * 4) == 4
    sds = _sds(one_chip)
    f32 = jnp.float32
    text = _compile(
        lambda *a: ssd_update.ssd_state_update(*a, interpret=False),
        sds((GR_S, 128, 4096), f32), sds((GR_S, 4096), f32),
        sds((GR_S, 4096), f32), sds((GR_S, 128), f32),
        sds((GR_S, 128), f32), sds((GR_S,), jnp.bool_))
    lines = [ln.strip() for ln in text.split("\n")]
    found = [ln for ln in lines
             if _metric_pattern("ssd_decode_roofline").search(ln)]
    assert len(found) == 1 and " custom-call(" in found[0]
    assert 'custom_call_target="tpu_custom_call"' in found[0]
    # the state operand (the call's fourth) is the state result
    assert "output_to_operand_aliasing={{0}: (3, {})}" in found[0]
    assert re.search(r'kernel_metadata=\{\s*"kernel":"ssd_state_update"\s*\}',
                     text)
    assert text.count("tpu_custom_call") == 1
    call = text[text.index(found[0][:40]):]
    scoped = int(re.search(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', call).group(1))
    # twice what the call holds: the two batches and its rows, the
    # rows double-buffered (da/dtx, y, and B/C padded to 128 lanes)
    held = 2 * 4 * 128 * 4096 * 4 + 2 * GR_S * (3 * 4096 + 2 * 128) * 4
    assert scoped == 2 * held <= ssd_update._VMEM_BYTES


def test_granite_decode_program_is_the_one_the_benchmark_finds(programs):
    """This family rides the shared loop too, one step ahead by
    default: at the cell's geometry its decode program is the one
    `decode_step_ms`, `ssd_decode_roofline` and `gqa_attn_roofline` look
    for (their `program_match`, read from the metric files), FLAT, with
    exactly the kernels' calls where `op_match` finds them: 9 state
    updates and 1 grouped-query attention call at this depth, each
    named after its kernel, beside the one K/V write; one packed
    result for the host."""
    eng, text = programs("granite").eng, programs("granite").text
    assert eng.async_dispatch and eng._has_state
    module = re.match(r"HloModule (\S+?),", text).group(1)
    for metric in ("decode_step_ms", "ssd_decode_roofline",
                   "gqa_attn_roofline"):
        program = _metric_spec(metric)["args"]["program_match"]
        assert re.search(program, module + "(1)"), (metric, module)
    assert " while(" not in text
    entry = text[text.index("\nENTRY "):]
    lines = [ln.strip() for ln in entry.split("\n")]
    for metric, kernel, calls in (
            ("ssd_decode_roofline", "ssd_state_update", 9),
            ("gqa_attn_roofline", "hybrid_decode_attention", 1)):
        rx = _metric_pattern(metric)
        found = [ln for ln in lines if rx.search(ln)]
        assert len(found) == calls, (metric, len(found))
        assert all(ln.startswith("%" + kernel) and " custom-call(" in ln
                   for ln in found)
        assert len(re.findall(r'kernel_metadata=\{\s*"kernel":"%s"\s*\}'
                              % kernel, text)) >= calls
    assert len([ln for ln in lines if ln.startswith("%paged_kv_write")
                and " custom-call(" in ln]) == 1
    assert text.count("tpu_custom_call") == 11
    # the mixers' and the MLP's device scopes, under the names every
    # family shares (ISSUE 35); the family-prefixed ones are gone
    for scope in ("lm_state", "lm_attention", "lm_mlp"):
        assert "/%s/" % scope in text
    assert not re.search(r'op_name="[^"]*granite_', text)
    assert re.search(r"s32\[%d\]" % (6 * GR_S + 1), entry)
    assert eng.metrics.decode_trace_count() == 1


def test_granite_prefill_chunk_compiles_at_its_one_bucket(programs):
    """The cell's one chunk program (2,048 rows; `min_bucket` makes it
    the only one) compiles for the chip under its own name, the blocked
    scan as a loop of matrix products, with bounded temporaries."""
    eng = programs("granite").eng
    assert eng._bucket(1) == eng._bucket(GR_CHUNK - 100) == GR_CHUNK
    compiled = programs("granite", "chunk").compiled
    text = programs("granite", "chunk").text
    assert re.match(r"HloModule jit__chunk[,.]", text)
    assert "/lm_state/" in text and " while(" in text
    assert "ssd_state_update" not in text  # the decode step's kernel
    # ~0.6 GB at 10 layers, 1.15 GB at 40 (weights' copies in flight)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


# ---------------------------------------------------------------------
# the same family with routed experts at the geometry of its
# cell, `granitehsmall_reason_closed`: granite-4.0-h-small's widths, one
# 10-layer period, 36 of 72 experts and half the vocabulary held, 64
# slots, 8,192 positions, 11,264 blocks of 32 tokens
# ---------------------------------------------------------------------

GS_NB = 11264


def _granite_moe_config():
    import json
    import pathlib

    from paddle_tpu.models import granite_hybrid as gh

    conf = json.loads((pathlib.Path(__file__).resolve().parent.parent
                       / "benchmarks" / "chip" / "configs"
                       / "granite_4_0_h_small.json").read_text())
    return gh.GraniteHybridConfig(max_len=GR_L, dtype=jnp.bfloat16,
                                  **conf["shape"]), conf


def _granite_moe_engine(one_chip, **kw):
    from paddle_tpu.models import granite_hybrid as gh
    from paddle_tpu.serving import ServingEngine

    cfg, conf = _granite_moe_config()
    eng_kw = dict(conf["engine"], kv_pool_blocks=4, **kw)
    params = jax.eval_shape(
        lambda: gh.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, **eng_kw)
    assert eng.paged_kernel == "fused" and eng._win is None
    assert (eng.max_slots, eng.kv_block_tokens) == (GR_S, GR_BT)
    cache = jax.eval_shape(lambda: gh.SERVING_EXPERTS.init_cache(
        cfg, conf["engine"]["kv_pool_blocks"], GR_BT, GR_S))
    return eng, _placed(params, one_chip), _placed(cache, one_chip), \
        _sds(one_chip)


def test_ssd_state_update_at_the_wider_state(one_chip):
    """h-small's state, 64 slots of [128, 8192] float32 (4 MiB a
    slot), under the call's VMEM rule as it stands: the batch rule
    gives two slots a batch (8 MiB), the call holds 16 MiB of batches
    and 12.1 MiB of rows and scopes twice that, 56.3 of the 128 MiB;
    Mosaic compiles it in place as ONE custom call under the kernel's
    name."""
    from paddle_tpu.parallel import ssd_update

    assert ssd_update._step_slots(GR_S, 128 * 8192 * 4) == 2
    sds = _sds(one_chip)
    f32 = jnp.float32
    text = _compile(
        lambda *a: ssd_update.ssd_state_update(*a, interpret=False),
        sds((GR_S, 128, 8192), f32), sds((GR_S, 8192), f32),
        sds((GR_S, 8192), f32), sds((GR_S, 128), f32),
        sds((GR_S, 128), f32), sds((GR_S,), jnp.bool_))
    found = [ln.strip() for ln in text.split("\n")
             if _metric_pattern("ssd_decode_roofline").search(ln.strip())]
    assert len(found) == 1 and " custom-call(" in found[0]
    assert "output_to_operand_aliasing={{0}: (3, {})}" in found[0]
    call = text[text.index(found[0][:40]):]
    scoped = int(re.search(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', call).group(1))
    held = 2 * 2 * 128 * 8192 * 4 + 2 * GR_S * (3 * 8192 + 2 * 128) * 4
    assert scoped == 2 * held <= ssd_update._VMEM_BYTES


def test_granite_moe_decode_program_is_the_one_the_benchmark_finds(
        programs):
    """At the cell's geometry the decode program is the one
    `decode_step_ms`, `ssd_decode_roofline`, `gqa_attn_roofline` and
    `moe_expert_roofline` look for, FLAT: 9 state updates, 1
    grouped-query call over one-head rows beside its one K/V write, and
    two grouped expert products a layer, each named after its kernel;
    the expert branch under `lm_experts` (no `lm_mlp`); one packed
    result that carries the router's two counters. Its arguments (9.5
    GB of weights, 2.45 GB of state, 1.48 GB of pool) and temporaries
    fit under the bytes the configuration allows."""
    eng, prog = programs("granite_moe").eng, programs("granite_moe")
    text = prog.text
    assert eng.async_dispatch and eng._has_state
    assert eng._step_counters == ("moe_experts_hit", "moe_rows_max")
    module = re.match(r"HloModule (\S+?),", text).group(1)
    for metric in ("decode_step_ms", "ssd_decode_roofline",
                   "gqa_attn_roofline", "moe_expert_roofline"):
        program = _metric_spec(metric)["args"]["program_match"]
        assert re.search(program, module + "(1)"), (metric, module)
    assert " while(" not in text
    entry = text[text.index("\nENTRY "):]
    lines = [ln.strip() for ln in entry.split("\n")]
    for metric, kernel, calls in (
            ("ssd_decode_roofline", "ssd_state_update", 9),
            ("gqa_attn_roofline", "hybrid_decode_attention", 1),
            ("moe_expert_roofline", "moe_grouped_matmul", 20)):
        found = [ln for ln in lines if _metric_pattern(metric).search(ln)]
        assert len(found) == calls, (metric, len(found))
        assert all(ln.startswith("%" + kernel) and " custom-call(" in ln
                   for ln in found)
    # one K/V head of 128 a pool row: a block is 32 tokens x 8 rows,
    # and the call returns the 32 query heads' 128-wide reads
    attn = [ln for ln in lines
            if _metric_pattern("gqa_attn_roofline").search(ln)][0]
    assert "bf16[%d,256,128]" % (GS_NB + 1) in attn
    assert "bf16[%d,32,1,128]" % GR_S in attn
    assert len([ln for ln in lines if ln.startswith("%paged_kv_write")
                and " custom-call(" in ln]) == 1
    assert text.count("tpu_custom_call") == 31
    assert "/lm_experts/" in text and "/lm_mlp/" not in text
    assert re.search(r"s32\[%d\]" % (6 * GR_S + 3), entry)
    _, conf = _granite_moe_config()
    mem = prog.compiled.memory_analysis()
    assert 13.3e9 < mem.argument_size_in_bytes < 13.6e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < conf["aot_bytes_limit"])
    assert eng.metrics.decode_trace_count() == 1


def test_granite_moe_prefill_chunk_compiles_at_its_one_bucket(programs):
    """The cell's one chunk program compiles for the chip under its own
    name: the blocked scan as a loop, the routed rows through the
    grouped product; beside the arguments its temporaries fit under
    the bytes the configuration allows."""
    eng = programs("granite_moe").eng
    _, conf = _granite_moe_config()
    rows = conf["engine"]["prefill_chunk_tokens"]
    assert eng._bucket(1) == eng._bucket(rows - 100) == rows
    compiled = programs("granite_moe", "chunk").compiled
    text = programs("granite_moe", "chunk").text
    assert re.match(r"HloModule jit__chunk[,.]", text)
    assert "/lm_state/" in text and " while(" in text
    assert "moe_grouped_matmul" in text and "/lm_experts/" in text
    assert "ssd_state_update" not in text  # the decode step's kernel
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < conf["aot_bytes_limit"])


# ---------------------------------------------------------------------
# the merged-pool decode call alone, at the three hybrid cells'
# geometry, with the group the byte rule gives it (ISSUEs 32 and 36)
# ---------------------------------------------------------------------


def _jaxpr_digest(fn, *args):
    """sha256 of `fn`'s jaxpr at the chip's matmul precision: a kernel
    call's holds the kernel's body, and no file or line of it."""
    with jax.default_matmul_precision(None):
        jaxpr = str(jax.make_jaxpr(fn)(*args))
    return hashlib.sha256(jaxpr.encode()).hexdigest()


# the merged-pool call's jaxpr at the four cell geometries below and the
# latent call's at its cell's (as the kernel now folds them: a slot's
# last group folds the first of its rungs, `_rungs`, behind one switch
# with the whole group first — the latent call's a ladder of 8 blocks
# in groups of 64, not 32; the others still a quarter group or the
# whole). A PR that MEANS to change the ring kernel replaces these (the
# failing assertion prints the new one) and says so in CHANGES.md
_RING_CALL_JAXPR_SHA = {
    "granite":
        "99d75c8100e06aa56bc7ffc4939063a4116c1fcdc782d7e5c10dd301c5e83989",
    "sambay_full":
        "6351a9961b29872d100b9a5a91d41fe7175a83d35e1165ab28f38bb64b98f951",
    "sambay_window":
        "82fa8eccec8a33ede15cb27da321ca0c585c4437b60763f0ed05f02d681afc76",
    "trinity_window":
        "b31ec0049e9f378f0b3477bc33fbbd9ad172b38c073b1ca4df44847011477da4",
    # the latent call's changed where its folds became two overlapped
    # halves (`_fold_halves`, a call bound by its fold); the four above
    # are bound by their copies and keep the serial fold, digit for digit
    "latent":
        "869199147e9f3c48749512cfa111ced409d1b1144c9251e41c655749f038fb6a",
}


@pytest.mark.parametrize("cell,metric,rows,rep,blocks,window,group,rung", [
    ("granite", "gqa_attn_roofline", 4, 8, GR_NB + 1, None, 32, 8),
    ("sambay_full", "hybrid_attn_roofline", 10, 4, HY_NB + 1, None, 8, 2),
    ("sambay_window", "hybrid_attn_roofline", 10, 4, HY_S * 17 + 1, 512, 8,
     2),
    ("trinity_window", "swa_attn_roofline", 4, 8, HY_S * 65 + 1, 2048, 32,
     8),
], ids=["granite", "sambay_full", "sambay_window", "trinity_window"])
def test_merged_pool_decode_call_compiles_with_its_own_copies(
        one_chip, cell, metric, rows, rep, blocks, window, group, rung):
    """`hybrid_decode_attention` at the geometry of
    `granite4hmicro_reason_closed` (4 pair-rows a token, 8 queries a
    row), of `phi4flash_reason_closed` (10 and 4; the shared pool, and
    a window pool with `first`) and of `trinitymini_reason_closed` (4
    K/V heads, 8 queries a head; a 2,048-token window pool): 64 slots,
    8,192 positions in 32-token blocks, bf16. The byte rule gives
    granite's and Trinity's pools 32 blocks a group and SambaY's 8;
    the call takes the tables, `pos` (and `first`), q and the two pools — ONE operand a pool, left
    in HBM, no work list beside it — Mosaic accepts the kernel's own
    copies, its loops of data-dependent length and the two-deep ring
    inside the memory a program scopes, and the compiled call is still
    the ONE instruction the cell's roofline metric finds (`op_match`,
    read from its file), named after the kernel, its result
    `[slots, rows x rep, 1, 128]`. Its body is the jaxpr pinned
    above: a slot's last group folds one `rung` or the whole group
    (`_rungs`: 8 of granite's and Trinity's 32, 2 of SambaY's 8)."""
    sds = _sds(one_chip)
    pool = sds((blocks, HY_BT * rows, 128), jnp.bfloat16)
    assert pa._bytes_group(HY_BT, HY_MAXB,
                           2 * HY_BT * rows * 128 * 2) == group
    assert pa._rungs(group, HY_BT * rows, False) == (rung, group)
    args = [sds((HY_S, rows, rep, 128), jnp.bfloat16), pool, pool,
            sds((HY_S, HY_MAXB), jnp.int32), sds((HY_S,), jnp.int32)]
    if window:
        args.append(sds((HY_S,), jnp.int32))

    def call(q, k, v, t, p, *first):
        return pa.paged_decode_attention(
            q, k, v, t, p, interpret=False, first=first[0] if first else None,
            scale=0.125)

    assert _jaxpr_digest(call, *args) == _RING_CALL_JAXPR_SHA[cell]
    text = _compile(call, *args)
    lines = [ln.strip() for ln in text.split("\n")]
    found = [ln for ln in lines if _metric_pattern(metric).search(ln)]
    assert len(found) == 1 and " custom-call(" in found[0]
    assert "bf16[%d,%d,1,128]" % (HY_S, rows * rep) in found[0]
    call = found[0][found[0].index(" custom-call("):
                    found[0].index("custom_call_target")]
    # the tables, the positions (and `first`), q, K, V — and nothing
    # computed from the tables rides beside them
    assert call.count("%") == (6 if window else 5)
    assert call.count("%k.1") == call.count("%v.1") == 1
    assert not re.search(r"s32\[%d,\d+\]" % group, text)
    assert re.search(r'kernel_metadata=\{\s*"kernel":'
                     r'"hybrid_decode_attention"\s*\}', text)
    assert text.count("tpu_custom_call") == 1



# ---------------------------------------------------------------------
# the sparse-expert family (ISSUE 33): window tables without state,
# rotated window keys, the grouped expert product — at the cell's
# geometry (trinitymini_reason_closed: 64 slots, 8,192 positions in
# blocks of 32, 128 experts top-8 of 2048 x 1024), depth 1 dense + 2
# expert layers (window, window, full)
# ---------------------------------------------------------------------

AF_S, AF_BT, AF_L, AF_NB = 64, 32, 8192, 14336
AF_MAXB = AF_L // AF_BT


def _afmoe_engine(one_chip, **kw):
    from paddle_tpu.models import afmoe as af
    from paddle_tpu.serving import ServingEngine

    cfg = af.AfmoeConfig(
        vocab=200192, dim=2048, heads=32, kv_heads=4, head_dim=128,
        layer_types=["sliding_attention", "sliding_attention",
                     "full_attention"],
        num_dense_layers=1, dense_width=6144, expert_width=1024,
        n_experts=128, top_k=8, route_scale=2.826, route_norm=True,
        window=2048, max_len=AF_L, dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: af.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, max_slots=AF_S, kv_pool_blocks=4,
                        kv_block_tokens=AF_BT, prefill_chunk_tokens=4096,
                        **kw)
    assert eng.paged_kernel == "fused"
    cache = jax.eval_shape(
        lambda: af.SERVING.init_cache(cfg, AF_NB, AF_BT, AF_S))
    return eng, _placed(params, one_chip), _placed(cache, one_chip), \
        _sds(one_chip)


@pytest.mark.parametrize("rows,tm,tiles", [(512, 16, 152), (32768, 128, 383)])
def test_grouped_expert_product_carries_its_name(one_chip, rows, tm, tiles):
    """The routed experts' layer at the cell's two geometries (a
    decode step's 512 (token, choice) pairs in 16-row tiles, a
    4,096-token chunk's 32,768 in 128-row tiles, over 128 experts of
    2048 x 1024, bf16) compiles for the chip as TWO custom calls named
    after the kernel, the name in `kernel_metadata`: what
    `moe_expert_roofline`'s `op_match` finds in a device trace."""
    from paddle_tpu.parallel import routed_experts as rx

    sds = _sds(one_chip)
    N, k, E, d, m = rows // 8, 8, 128, 2048, 1024
    assert rx.row_tile(rows, E) == tm
    bf = jnp.bfloat16
    text = _compile(
        lambda u, idx, w, gu, dn, valid: rx.expert_ffn(
            u, idx, w, {"w_gu": gu, "w_down": dn}, valid, kernel="fused",
            interpret=False),
        sds((N, d), bf), sds((N, k), jnp.int32), sds((N, k), jnp.float32),
        sds((E, d, 2 * m), bf), sds((E, m, d), bf), sds((N,), jnp.bool_))
    lines = [ln.strip() for ln in text.split("\n")]
    found = [ln for ln in lines
             if _metric_pattern("moe_expert_roofline").search(ln)]
    assert len(found) == 2 and all(" custom-call(" in ln for ln in found)
    # the tile-aligned layout: the pairs + 15 (127) rows an expert
    assert "[%d,2048]" % (tiles * tm) in found[0]
    assert len(re.findall(
        r'kernel_metadata=\{\s*"kernel":"moe_grouped_matmul"\s*\}',
        text)) >= 2
    assert text.count("tpu_custom_call") == 2


def test_afmoe_decode_program_is_the_one_the_benchmark_finds(programs):
    """The fourth family rides the shared loop, one step ahead by
    default, with window tables and NO state handling: at the cell's
    geometry its decode program is the one `decode_step_ms`,
    `moe_expert_roofline` and `swa_attn_roofline` look for (their
    `program_match`, read from the metric files), FLAT, with exactly
    the kernels' calls where `op_match` finds them — at this depth 3
    attention calls (two over the window pools, one over the full
    layer's) and 4 grouped products (two an expert layer), each named
    after its kernel, beside the three K/V writes — and ONE packed
    result for the host that carries the router's two counters behind
    the bands."""
    eng, text = programs("afmoe").eng, programs("afmoe").text
    assert eng.async_dispatch and eng._win is not None
    assert not eng._has_state and eng._step_counters == (
        "moe_experts_hit", "moe_rows_max")
    module = re.match(r"HloModule (\S+?),", text).group(1)
    for metric in ("decode_step_ms", "moe_expert_roofline",
                   "swa_attn_roofline"):
        program = _metric_spec(metric)["args"]["program_match"]
        assert re.search(program, module + "(1)"), (metric, module)
    assert " while(" not in text
    entry = text[text.index("\nENTRY "):]
    lines = [ln.strip() for ln in entry.split("\n")]
    for metric, kernel, calls in (
            ("moe_expert_roofline", "moe_grouped_matmul", 4),
            ("swa_attn_roofline", "hybrid_decode_attention", 3)):
        rx = _metric_pattern(metric)
        found = [ln for ln in lines if rx.search(ln)]
        assert len(found) == calls, (metric, len(found))
        assert all(ln.startswith("%" + kernel) and " custom-call(" in ln
                   for ln in found)
        assert len(re.findall(r'kernel_metadata=\{\s*"kernel":"%s"\s*\}'
                              % kernel, text)) >= calls
    assert len([ln for ln in lines if ln.startswith("%paged_kv_write")
                and " custom-call(" in ln]) == 3
    assert text.count("tpu_custom_call") == 10
    # the layer parts' device scopes, under the names every family
    # shares (ISSUE 35: router, experts and the shared expert are one
    # part, `lm_experts`; the leading dense layer's MLP is `lm_mlp`)
    for scope in ("lm_attention", "lm_experts", "lm_mlp"):
        assert "/%s/" % scope in text
    assert not re.search(r'op_name="[^"]*afmoe_', text)
    # tokens, trap flags, magnitude, four bands, then the two counters
    assert re.search(r"s32\[%d\]" % (6 * AF_S + 3), entry)
    assert eng.metrics.decode_trace_count() == 1


def test_afmoe_prefill_chunk_compiles_at_the_largest_bucket(programs):
    """The cell's largest chunk program (4,096 rows: 32,768 routed
    pairs through the same grouped product, the band of a window layer
    tile by tile) compiles for the chip under its own name, with
    bounded temporaries."""
    compiled = programs("afmoe", "chunk").compiled
    text = programs("afmoe", "chunk").text
    assert re.match(r"HloModule jit__chunk[,.]", text)
    assert "moe_grouped_matmul" in text and "/lm_experts/" in text
    assert "hybrid_decode_attention" not in text  # the decode step's
    # 1.17 GB at the cell's five layers (the routed rows' float32
    # products and the combine's gather)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# ---------------------------------------------------------------------
# the latent-attention family (ISSUE 37): one latent pool a layer on
# the one table, the absorbed decode call, 16 of 128 experts held — at
# the cell's geometry and depth (kanana2_reason128_closed: 128 slots,
# 8,192 positions in blocks of 32, a pool of 28,672 blocks, 1 dense + 7
# expert layers), so that memory_analysis is the chip's whole bill
# ---------------------------------------------------------------------

ML_S, ML_BT, ML_L, ML_NB, ML_LAYERS = 128, 32, 8192, 28672, 8
ML_MAXB = ML_L // ML_BT


def _mla_config():
    from paddle_tpu.models import mla_moe as ml

    return ml.MlaMoeConfig(
        vocab=128256, dim=2048, heads=32, nope_dim=128, rope_dim=64,
        v_dim=128, kv_rank=512, layers=ML_LAYERS, num_dense_layers=1,
        dense_width=6144, expert_width=768, n_shared_experts=2,
        n_experts=128, top_k=6, route_scale=2.448, rope_theta=1e6,
        experts_held=(0, 16), max_len=ML_L, dtype=jnp.bfloat16)


def _mla_engine(one_chip, **kw):
    from paddle_tpu.models import mla_moe as ml
    from paddle_tpu.serving import ServingEngine

    cfg = _mla_config()
    params = jax.eval_shape(
        lambda: ml.init_params(cfg, jax.random.PRNGKey(0)))
    eng = ServingEngine(params, cfg, max_slots=ML_S, kv_pool_blocks=4,
                        kv_block_tokens=ML_BT, prefill_chunk_tokens=4096,
                        **kw)
    assert eng.paged_kernel == "fused"
    cache = jax.eval_shape(
        lambda: ml.SERVING.init_cache(cfg, ML_NB, ML_BT, ML_S))
    return eng, _placed(params, one_chip), _placed(cache, one_chip), \
        _sds(one_chip)


def test_latent_decode_call_compiles_reading_one_pool(one_chip):
    """`mla_decode_attention` at the cell's geometry: 128 slots x 256
    table entries pass the scalar-memory bound, the ring holds groups
    of 64 blocks of 32 x 640 bf16 (the byte rule's 2.5 MiB for a call
    bound by its fold, one pool), and the compiled call is the ONE
    instruction `mla_decode_roofline`'s `op_match` finds, named after
    the kernel, taking the tables, the positions, q and the ONE latent
    pool — no second pool, no work list — and returning [slots, heads,
    1, kv_rank]; its body is the jaxpr pinned above, a slot's last
    group folding rungs of 8 of the 64 blocks (256 latent rows), every
    fold cut in two halves of whole 128-row tiles."""
    pa.check_paged_smem(ML_S, ML_MAXB, ML_BT, 32, False,
                        block_bytes=ML_BT * 640 * 2)
    assert pa._bytes_group(ML_BT, ML_MAXB, ML_BT * 640 * 2, True) == 64
    assert pa._rungs(64, ML_BT, True) == tuple(range(8, 65, 8))  # 256 rows
    sds = _sds(one_chip)
    args = (sds((ML_S, 32, 640), jnp.bfloat16),
            sds((ML_NB + 1, ML_BT, 640), jnp.bfloat16),
            sds((ML_S, ML_MAXB), jnp.int32), sds((ML_S,), jnp.int32))

    def call(q, pool, t, p):
        return pa.mla_decode_attention(q, pool, t, p, 512, 192 ** -0.5,
                                       interpret=False)

    assert _jaxpr_digest(call, *args) == _RING_CALL_JAXPR_SHA["latent"]
    text = _compile(call, *args)
    found = [ln.strip() for ln in text.split("\n")
             if _metric_pattern("mla_decode_roofline").search(ln.strip())]
    assert len(found) == 1 and " custom-call(" in found[0]
    assert "bf16[%d,32,1,512]" % ML_S in found[0]
    call = found[0][found[0].index(" custom-call("):
                    found[0].index("custom_call_target")]
    assert call.count("%") == 4 and call.count("%pool") == 1
    assert re.search(r'kernel_metadata=\{\s*"kernel":'
                     r'"mla_decode_attention"\s*\}', text)
    assert text.count("tpu_custom_call") == 1


def test_mla_decode_program_is_the_one_the_benchmark_finds(programs):
    """The fifth family rides the shared loop, one step ahead, with ONE
    table and no window or state: at the cell's geometry and depth its
    decode program is the one `decode_step_ms`, `mla_decode_roofline`
    and `moe_expert_roofline` look for, FLAT, with one latent call and
    one latent write a layer and two grouped products an expert layer,
    each named after its kernel, and ONE packed result that carries
    the router's two counters; its arguments — 2.74 GB of weights and
    9.40 GB of latent pools — and temporaries fit the chip."""
    eng, prog = programs("mla").eng, programs("mla")
    text = prog.text
    assert eng.async_dispatch and eng._win is None and not eng._has_state
    assert eng._step_counters == ("moe_experts_hit", "moe_rows_max")
    module = re.match(r"HloModule (\S+?),", text).group(1)
    for metric in ("decode_step_ms", "mla_decode_roofline",
                   "moe_expert_roofline"):
        program = _metric_spec(metric)["args"]["program_match"]
        assert re.search(program, module + "(1)"), (metric, module)
    assert " while(" not in text
    entry = text[text.index("\nENTRY "):]
    lines = [ln.strip() for ln in entry.split("\n")]
    for metric, kernel, calls in (
            ("moe_expert_roofline", "moe_grouped_matmul",
             2 * (ML_LAYERS - 1)),
            ("mla_decode_roofline", "mla_decode_attention", ML_LAYERS)):
        found = [ln for ln in lines if _metric_pattern(metric).search(ln)]
        assert len(found) == calls, (metric, len(found))
        assert all(ln.startswith("%" + kernel) and " custom-call(" in ln
                   for ln in found)
    assert len([ln for ln in lines if ln.startswith("%paged_kv_write")
                and " custom-call(" in ln]) == ML_LAYERS
    assert text.count("tpu_custom_call") == 4 * ML_LAYERS - 2
    assert "hybrid_decode_attention" not in text
    assert re.search(r"s32\[%d\]" % (6 * ML_S + 3), entry)
    mem = prog.compiled.memory_analysis()
    assert 12.0e9 < mem.argument_size_in_bytes < 12.3e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    assert eng.metrics.decode_trace_count() == 1


def test_mla_prefill_chunk_compiles_at_the_largest_bucket(programs):
    """The cell's one-chunk prompt program (4,096 rows: expanded keys and
    values, key-tiled; 24,576 routed pairs through the grouped product)
    compiles for the chip under its own name; beside the arguments its
    temporaries leave room on the 16 GB chip."""
    compiled = programs("mla", "chunk").compiled
    text = programs("mla", "chunk").text
    assert re.match(r"HloModule jit__chunk[,.]", text)
    assert "moe_grouped_matmul" in text and "/lm_experts/" in text
    assert "mla_decode_attention" not in text  # the decode step's
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


# the SambaY, the Mamba-2 / grouped-query (with and without routed
# experts), the sparse-expert and the latent-attention decode programs
# at their cells' geometry, as the tree compiles them:
# sha256 of the compiled text less locations and less the kernels'
# embedded bodies, as `_GPT_DECODE_TEXT_SHA` above. Their attention
# call takes its own operands and scratch, with no work list beside
# it, and is lowered through one jitted function a geometry
# (`_ring_call`), which moved only the numbers XLA gives instructions:
# with those taken out the texts are those of the form before it,
# instruction for instruction. A PR that MEANS to change one of them replaces its digest
# (the failing assertion prints the new one) and says so in CHANGES.md.
_DECODE_TEXT_SHA = {
    "hybrid": "a7fa22c9f38781e54e745f06198235060546b985208b61b7221c3573d80c46d9",
    "granite": "da13db230832f12fb7294deb98eb585e557be6228d0dfa1fe1d6ecde6963befb",
    "granite_moe": "8cb9ebd6779443b5c5bc4b823579f7b52b9c6f2a6727406794005f7204208023",
    "afmoe": "ff2173aa4c2e87810af7f92cdb0557b1cb44feb9e0647150c0b2843bd91784cc",
    # the latent call cuts each fold in two overlapped halves
    # (`_fold_halves`): its 8 calls' only change in this text is the
    # VMEM they use, 8,421,376 B where the serial fold used 8,536,064
    "mla": "d28ddbe6748faf6bf304ffa5d44eb938fc3c1f024a5b8444e351a12631efb465",
}


@pytest.mark.parametrize("family", sorted(_DECODE_TEXT_SHA))
def test_other_families_decode_programs_are_the_text_last_meant(
        programs, family):
    assert _text_digest(programs(family).text)[0] == _DECODE_TEXT_SHA[family]


# every family's prefill chunk program at the largest chunk `_FAMILIES`
# names, as the tree compiles it: sha256 of the compiled text less
# locations and less the kernels' embedded bodies, as
# `_DECODE_TEXT_SHA` above. A PR that MEANS to change one of them
# replaces its digest (the failing assertion prints the new one) and
# says so in CHANGES.md.
_CHUNK_TEXT_SHA = {
    "gpt": (
        "550909a765d977b897293158f210a8331359cdbb45c30505c53a04920926fea2"),
    "hybrid": (
        "43abb7817f254d8c75b2712b69c764b7bea886cc2d99e5796013d4ab68ea194d"),
    "granite": (
        "2fcdd9b2ff8b2557ce995d81538f4c3679fa12cb02694acfe4474ec05a020f7f"),
    "granite_moe": (
        "31af3046efc46c209f7aef340bcd0d29862c988484838587d35505a0e7d92c85"),
    "afmoe": (
        "f5ee9ac814d074bd6c3e106882706f74104c2215a1d796da358c9880a81ab3f5"),
    "mla": (
        "886d49057700cd329fe5acf57695f07255c506ac12507800d1eab794bbc56371"),
}


@pytest.mark.parametrize("family", sorted(_CHUNK_TEXT_SHA))
def test_chunk_programs_are_the_text_last_meant(programs, family):
    assert (_text_digest(programs(family, "chunk").text)[0]
            == _CHUNK_TEXT_SHA[family])


# ---------------------------------------------------------------------
# the device scopes (ISSUE 35): every family's compiled steps name
# their parts from the one vocabulary of `models/scopes.py`, which a
# device trace carries as each operation's framework op name
# (benchmarks/chip/lib/scopes.py reads it). Each family's decode
# program and largest chunk program, at its cell's geometry (the GPT
# block's at the smoke's), are compiled ONCE a module and shared with
# the tests above that read them.
# ---------------------------------------------------------------------

_FAMILIES = {  # family -> (engine, its largest chunk, its table rows)
    "gpt": (_engine, L, (MAXB,)),
    "hybrid": (_hybrid_engine, 4096, (4, HY_MAXB)),
    "granite": (_granite_engine, GR_CHUNK, (2, GR_MAXB)),
    "granite_moe": (_granite_moe_engine, GR_CHUNK, (2, GR_MAXB)),
    "afmoe": (_afmoe_engine, 4096, (4, AF_MAXB)),
    "mla": (_mla_engine, 4096, (ML_MAXB,)),
}
# the decode step's block tables: [(kinds of table,) slots, entries]
_DECODE_TABLES = {"gpt": (S, MAXB), "hybrid": (2, HY_S, HY_MAXB),
                  "granite": (GR_S, GR_MAXB),
                  "granite_moe": (GR_S, GR_MAXB),
                  "afmoe": (2, AF_S, AF_MAXB),
                  "mla": (ML_S, ML_MAXB)}


class _Program(object):
    def __init__(self, eng, compiled):
        self.eng, self.compiled = eng, compiled
        self.text = compiled.as_text()


@pytest.fixture(scope="module")
def programs(one_chip):
    """`programs(family, which="decode")` -> the family's decode or
    chunk program compiled for the chip (`.compiled`, `.text`) and
    the engine it was lowered from (`.eng`); a whole step compiles in
    5-60 s, so each is made once and kept for the module."""
    made = {}

    def get(family, which="decode"):
        if (family, which) not in made:
            engine, rows, table = _FAMILIES[family]
            with pytest.MonkeyPatch.context() as mp:  # as `as_on_tpu`
                mp.setattr(jax, "default_backend", lambda: "tpu")
                eng, params, cache, *_, sds = engine(one_chip)
                if which == "decode":
                    tables = _DECODE_TABLES[family]
                    fn = eng._decode_fn
                    args = _bands(sds, tables[-2], *tables)
                else:
                    fn = eng._chunk_fn(rows)
                    args = _chunk_args(sds, rows, *table)
                made[family, which] = _Program(
                    eng, _compiled(fn, params, cache, *args))
        return made[family, which]

    return get


_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%[\w.\-]+ = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")


def _stepwise_instructions(text):
    """The instructions the device runs one after another: those of
    the computations no fusion calls (the entry, a loop's body and
    condition, a conditional's branches), each with its continuation
    lines (a kernel's metadata spans three)."""
    found, comp, cur = [], None, None
    for ln in text.split("\n"):
        head = _COMPUTATION.match(ln)
        if head:
            comp, cur = head.group(1), None
        elif _INSTRUCTION.match(ln):
            cur = [comp, ln]
            found.append(cur)
        elif ln.rstrip() == "}":
            comp, cur = None, None
        elif cur is not None:
            cur[1] += "\n" + ln
    fused = set()
    for _, ins in found:
        if " fusion(" in ins:
            fused.update(re.findall(r"calls=%([\w.\-]+)", ins))
    return [ins for comp, ins in found if comp not in fused]


def _scope_of(instruction):
    """The outermost vocabulary scope in an instruction's `op_name`,
    or None."""
    from paddle_tpu.models.scopes import SCOPES

    op = re.search(r'op_name="([^"]*)"', instruction)
    for part in (op.group(1).split("/") if op else ()):
        if part in SCOPES:
            return part
    return None


# the layer parts a family's programs hold; every program also holds
# the embedding, the head and the engine's tail of the step
_FAMILY_SCOPES = {
    "gpt": {"lm_attention", "lm_mlp"},
    "hybrid": {"lm_attention", "lm_state", "lm_mlp"},
    "granite": {"lm_attention", "lm_state", "lm_mlp"},
    "granite_moe": {"lm_attention", "lm_state", "lm_experts"},
    "afmoe": {"lm_attention", "lm_mlp", "lm_experts"},
    "mla": {"lm_attention", "lm_mlp", "lm_experts"},
}


@pytest.mark.parametrize("which", ["decode", "chunk"])
@pytest.mark.parametrize("family", sorted(_FAMILY_SCOPES))
def test_compiled_steps_name_their_parts_from_the_one_vocabulary(
        programs, family, which):
    """Every kernel call and at least 95 % of the fusions, products
    and convolutions a decode step runs carry an `op_name` whose path
    holds a scope of the vocabulary (the few that do not are the
    step's prologue: the parked-slot mask, the tables' split, a work
    list's plan). A chunk program also runs what the compiler made
    itself and gave no `op_name` at all — the loop a scatter is
    lowered to, layout copies: 8-13 % of its instructions, which no
    scope can reach — so there the 95 % is of the instructions that
    carry a name, and of all of them 85 %. The scopes that appear are
    the family's own: no recurrent state in the GPT block and the
    sparse-expert family, no routed experts outside it; a decode step
    ends in the engine's sampling, traps and retirement, a chunk in
    sampling and traps."""
    from paddle_tpu.models.scopes import SCOPES

    kernels, work, seen = [], [], set()
    for ins in _stepwise_instructions(programs(family, which).text):
        opcode = re.search(r" ([a-z][a-z\-]*)\(", ins.split(" = ", 1)[1])
        scope = _scope_of(ins)
        if scope:
            seen.add(scope)
        if opcode is None:
            continue
        if opcode.group(1) == "custom-call" and "tpu_custom_call" in ins:
            kernels.append(scope)
        elif opcode.group(1) in ("fusion", "dot", "convolution"):
            work.append((scope, "op_name=" in ins))
    # (the granite family's chunk is XLA's alone: no kernel in it)
    assert all(kernels) and (kernels or (family, which) == ("granite",
                                                            "chunk"))
    assert len(work) > 50
    scoped = sum(1 for s, _ in work if s)
    if which == "decode":
        assert scoped >= 0.95 * len(work), (scoped, len(work))
    else:
        named = sum(1 for _, has_name in work if has_name)
        assert scoped >= 0.95 * named, (scoped, named)
        assert scoped >= 0.85 * len(work), (scoped, len(work))
    tail = {"step_sample", "step_traps"} | (
        {"step_retire"} if which == "decode" else set())
    assert seen == {"lm_embed", "lm_head"} | _FAMILY_SCOPES[family] | tail
    assert seen <= set(SCOPES)


def test_a_scope_outside_the_vocabulary_is_refused():
    from paddle_tpu.models import scopes

    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES) == 9
    with scopes.scope("lm_mlp"):
        pass
    with pytest.raises(ValueError, match="not a device scope"):
        scopes.scope("granite_mlp")
