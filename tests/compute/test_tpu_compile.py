"""The main path's Pallas kernels, compiled for a DESCRIBED v5e at real widths.

No chip is attached and nothing runs: libtpu's compiler lowers each kernel
for a ``v5e:2x2`` topology description and raises what the chip's compiler
would raise (block shapes the TPU lowering refuses, VMEM overflows).
Interpret-mode tests cannot see those — ``paged_decode_attention`` passed
every one of them while being refused at every real shape.

Kept in ONE file, with the topology described inside a module-scoped
fixture: only one process may hold libtpu, so the call must not happen at
import/collection time (every xdist worker imports every test file) and
must not be spread over files that can land on different workers.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dstack_tpu.ops import flash_attention as fa

#: (q heads, kv heads, head_dim, seq, batch): Llama-3.2-1B and Llama-3-8B
GEOMETRIES = {
    "1b": (32, 8, 64, 1024, 8),
    "8b": (32, 8, 128, 2048, 4),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def real_lowering():
    """Compile the kernels for real (the CPU backend would interpret them)
    and keep the persistent compilation cache out of it: an executable for
    a described device is written there but can never be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    saved_interpret = fa._interpret
    saved_cache = jax.config.jax_enable_compilation_cache
    fa._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    fa._interpret = saved_interpret
    jax.config.update("jax_enable_compilation_cache", saved_cache)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _qkv(geometry, sharding):
    hq, hkv, d, seq, b = GEOMETRIES[geometry]
    q = jax.ShapeDtypeStruct((b, seq, hq, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, seq, hkv, d), jnp.bfloat16,
                              sharding=sharding)
    return q, kv, kv


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_flash_forward_compiles(one_chip, real_lowering, geometry):
    text = _compiled_text(fa.flash_attention, *_qkv(geometry, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_flash_gradient_compiles(one_chip, real_lowering, geometry):
    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_qkv(geometry, one_chip))
    assert "tpu_custom_call" in text


#: (q heads, kv heads, head_dim) the paged kernel is compiled at: the
#: smoke's two (GQA 32/8 at d 128, 1024 lanes a page row, is also the
#: benchmark's batch cell), the chat cell's MHA 32/32 at d 64 (2048
#: lanes); then one and three kv heads of d 64, the 64 and 192 lanes a
#: shard holds at a tensor degree that leaves it that: not a multiple of
#: the 128-lane tile
PAGED_HEADS = {
    "1b": (32, 8, 64),
    "8b": (32, 8, 128),
    "mha-d64": (32, 32, 64),
    "one-head-d64": (4, 1, 64),
    "three-heads-d64": (12, 3, 64),
}


#: blocks of the pool the kernel alone is compiled over: a deployment's,
#: not the 257 these 8 slots could fill (a pool of some tens of MB is a
#: buffer the compiler copies into fast memory whole, which no real pool
#: is: seen at 64 lanes x 2,049 blocks)
PAGED_BLOCKS = 16385


#: the pages' element type as compiled HLO names it: the f32 scales of
#: int8 pages, padded to whole lane tiles around the call, can be as many
#: ELEMENTS as a layer of the pages (two layers x 128 lanes against one x
#: 256) and are no copy of it
_PAGES_HLO_TYPE = {"bf16": "bf16", "int8": "s8"}


def _pool(sds, pages, lead, hkv, d):
    """The stacked paged pool as the engine stores it: kv heads folded
    into the lane dim."""
    if pages == "bf16":
        return sds(lead + (hkv * d,), jnp.bfloat16)
    return {"q": sds(lead + (hkv * d,), jnp.int8),
            "s": sds(lead + (hkv,), jnp.float32)}


def _paged_call(fn, *args):
    """(grid, VMEM scratch bytes) of the one Pallas call ``fn`` makes on
    ``args``, read from the call as traced."""
    calls = [e for e in jax.make_jaxpr(fn)(*args).eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1, "one kernel call a layer-step, not a pair"
    mapping = calls[0].params["grid_mapping"]
    scratch = sum(
        math.prod(a.shape) * a.dtype.itemsize for a in mapping.scratch_avals
        if str(getattr(a, "memory_space", "")) == "vmem")
    return mapping.grid, scratch


@pytest.mark.parametrize("pages", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(PAGED_HEADS))
def test_paged_decode_compiles(one_chip, real_lowering, geometry, pages):
    """Page size 32 (the server default), an 8-slot batch over a 1024-token
    span — the decode-window shapes `python -m dstack_tpu.serving.server
    --paged` dispatches — out of a STACKED pool with a run-time layer
    index: the kernel's operand is the pool as stored, so the program
    holds nothing of a layer's pool size but the pool."""
    hq, hkv, d = PAGED_HEADS[geometry]
    layers, b, bs, span = 2, 8, 32, 1024
    num_blocks = PAGED_BLOCKS

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kv = _pool(sds, pages, (layers, num_blocks, bs), hkv, d)
    compiled = jax.jit(fa.paged_decode_attention).lower(
        sds((b, hkv, hq // hkv, d), jnp.bfloat16), kv, kv,
        sds((), jnp.int32), sds((b, span // bs), jnp.int32),
        sds((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if (hkv * d) % 128:
        # any lane width compiles, but only whole tiles are stored
        # row-major: the compiler keeps a narrower or ragged pool with the
        # BLOCKS minor-most and converts all of it into the operand's form
        # around the call, and the kernel's own copies take whole 128-lane
        # tiles, so the call pads it besides.  The engine warns at start
        # (test_serving.py); nothing to hold here but that the lowering
        # takes it.
        return
    assert not _pool_sized_ops(text, num_blocks * bs * hkv * d, layers,
                               _PAGES_HLO_TYPE[pages])
    if pages == "bf16":
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    # int8: the f32 scales [.., BS, Hkv] are kept with the blocks minor-most
    # (Hkv lanes of 128 would pad them 16x) and converted to the operand's
    # row-major, lane-padded form, whole, around the call: small beside the
    # pages, and once a decode window in the engine's program (PERF.md,
    # open questions)


#: the decode-window calls of the benchmark's two dense cells: (heads, kv
#: heads, head_dim, layers, blocks, slots, widest table bucket)
CELL_CALLS = {
    "smollm2-1.7b.chat": (32, 32, 64, 24, 768, 32, 64),
    "mistral-7b-v0.3-16l.batch": (32, 8, 128, 16, 2048, 16, 128),
}


@pytest.mark.parametrize("pages", ["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_paged_decode_grid_follows_the_page_bytes(one_chip, real_lowering,
                                                  cell, pages):
    """The two cells' own calls compile, and their grid is the one the
    page-bytes rule gives: P pages a step from the bytes of a page against
    the scratch budget (K and V, two buffers each), ``slots x ceil(columns
    / P)`` steps, the block buffers inside the budget and the whole
    scratch far inside v5e's 16 MiB of scoped VMEM."""
    hq, hkv, d, layers, blocks, slots, columns = CELL_CALLS[cell]
    bs = 32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kv = _pool(sds, pages, (layers, blocks, bs), hkv, d)
    args = (sds((slots, hkv, hq // hkv, d), jnp.bfloat16), kv, kv,
            sds((), jnp.int32), sds((slots, columns), jnp.int32),
            sds((slots,), jnp.int32))
    itemsize = 2 if pages == "bf16" else 1
    page_bytes = bs * hkv * d * itemsize
    per_step = fa._pages_per_step(page_bytes, columns)
    # the rule, worked out: 4 MiB over 4 block buffers of P pages each
    assert per_step == {("smollm2-1.7b.chat", "bf16"): 8,
                        ("smollm2-1.7b.chat", "int8"): 16,
                        ("mistral-7b-v0.3-16l.batch", "bf16"): 16,
                        ("mistral-7b-v0.3-16l.batch", "int8"): 32}[cell, pages]
    assert 4 * per_step * page_bytes <= fa._PAGED_SCRATCH_BYTES
    grid, scratch = _paged_call(fa.paged_decode_attention, *args)
    assert grid == (slots, -(-columns // per_step))
    assert scratch < 8 << 20
    compiled = jax.jit(fa.paged_decode_attention).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_decode_attention" in text
    assert not _pool_sized_ops(text, blocks * bs * hkv * d, layers,
                               _PAGES_HLO_TYPE[pages])
    if pages == "bf16":
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_pages_per_step_rule():
    """P from what the call can see: a power of two, bounded by the scratch
    budget and by the table's width, 1 at the least."""
    budget = fa._PAGED_SCRATCH_BYTES
    assert fa._pages_per_step(128 << 10, 64) == 8       # chat, bf16
    assert fa._pages_per_step(64 << 10, 128) == 16      # batch, bf16
    assert fa._pages_per_step(64 << 10, 8) == 8         # a narrow bucket
    assert fa._pages_per_step(64 << 10, 7) == 4         # ... an odd one
    assert fa._pages_per_step(64 << 10, 1) == 1
    assert fa._pages_per_step(budget, 64) == 1          # a page too large
    for page_bytes in (1 << 10, 48 << 10, 128 << 10, 1 << 20):
        for width in (1, 2, 3, 13, 64, 128):
            p = fa._pages_per_step(page_bytes, width)
            assert p >= 1 and p & (p - 1) == 0 and p <= max(width, 1)
            assert p == 1 or 4 * p * page_bytes <= budget


@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_paged_decode_compiles_under_tensor_mesh(topo, real_lowering, pages):
    """Llama-3-8B at TP = 4: the pool's lane dim sharded over the tensor
    axis, two whole kv heads (256 lanes) a shard, the kernel per device
    under ``shard_map`` with the specs the engine gives it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    hq, hkv, d = PAGED_HEADS["8b"]
    layers, b, bs, span = 2, 8, 32, 1024
    num_blocks = PAGED_BLOCKS
    mesh = Mesh(np.asarray(topo.devices[:4]), ("tensor",))

    def sds(spec):
        def make(shape, dtype):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec))
        return make

    heads, pages_spec = P(None, "tensor", None, None), P(None, None, None,
                                                         "tensor")
    kv = _pool(sds(pages_spec), pages, (layers, num_blocks, bs), hkv, d)
    kv_spec = jax.tree.map(lambda _: pages_spec, kv)
    fn = jax.shard_map(
        fa.paged_decode_attention, mesh=mesh,
        in_specs=(heads, kv_spec, kv_spec, P(), P(), P()),
        out_specs=(heads, P(None, "tensor", None)), check_vma=False)
    compiled = jax.jit(fn).lower(
        sds(heads)((b, hkv, hq // hkv, d), jnp.bfloat16), kv, kv,
        sds(P())((), jnp.int32), sds(P())((b, span // bs), jnp.int32),
        sds(P())((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _pool_sized_ops(text, num_blocks * bs * hkv * d // 4, layers,
                               _PAGES_HLO_TYPE[pages])


# ---------------------------------------------------------------------------
# The engine's own paged programs: nothing but the pool is pool-sized
# ---------------------------------------------------------------------------
#
# On the chip the decode window used to copy every layer's whole K and V
# pool twice per layer-step (the layer scan's slice of a stacked pool, then
# a change of layout into the kernel's operand form), and every prefill
# program converted the WHOLE pool's layout on the way in and out.  None of
# that shows on the CPU.  These tests compile the programs the engine
# dispatches, at the benchmark cells' widths and slot geometry, for the
# described chip and hold two things: no instruction outside a fusion's
# body produces a buffer of k whole layers of the pool unless it is the
# scatter that writes the pool in place, and the program's temporaries do
# not grow with the pool.

#: model widths, slots, max_len, decode table bucket: the two serving cells
#: of BENCHMARK.json (MHA 32/32 at d 64, tied head; GQA 32/8 at d 128).
#: ``blocks``: two pool sizes, near the cells' own (768 and 2048) and half
#: of that: temporaries are compared between them.  They are NOT small: a
#: layer of a small pool (tens of MB) is a temporary the compiler may keep
#: out of HBM, and the parent's copies then do not show in
#: ``temp_size_in_bytes``.  Nor are they the cells' own: at 768 and 2048
#: blocks two layers of the pool are exactly as large as the embedding, and
#: size alone has to tell the pool (checked in the fixture).
ENGINES = {
    "mha-d64": dict(
        cfg=dict(vocab_size=49152, hidden_size=2048, intermediate_size=8192,
                 num_heads=32, num_kv_heads=32, head_dim=64,
                 tie_embeddings=True),
        batch=32, max_len=2048, kb=64, blocks=(352, 704)),
    "gqa-d128": dict(
        cfg=dict(vocab_size=32768, hidden_size=4096, intermediate_size=14336,
                 num_heads=32, num_kv_heads=8, head_dim=128,
                 tie_embeddings=False),
        batch=16, max_len=4096, kb=128, blocks=(960, 1920)),
}
ENGINE_LAYERS = 3
PROGRAMS = ["decode_w64", "prefill_paged_b32", "prefill_paged_b512",
            "prefill_prefix_b512"]

_HLO_LINE = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\](?:\{[^}]*\})? "
    r"([\w\-]+)\(")
#: instructions that hold no buffer of their own
_NO_BUFFER = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
              "constant"}


def _pool_sized_ops(text: str, layer_elems: int, layers: int,
                    dtype: str = None, loops_only: bool = False,
                    skip_dims=()):
    """Instructions of compiled HLO ``text`` whose result is 1..``layers``
    whole layers of a pool leaf (``layer_elems`` elements a layer) and
    (of element type ``dtype``, an HLO name such as ``f32``, where given)
    that materialise it: everything outside fused computations except the
    plumbing of _NO_BUFFER and the scatters (and the fusions around them;
    a one-block scatter compiles to a dynamic-update-slice) that update
    the pool in place.  ``loops_only``: look inside the bodies of the
    program's loops alone; ``skip_dims``: shapes of something else of the
    same size (a layer's weight matrix), told in any order of their dims
    and with or without dims of 1."""
    bodies = dict(re.findall(
        r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\) -> [^\n]* \{\n(.*?)^\}", text,
        re.M | re.S))
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M)
    assert entry and entry.group(1) in bodies, "entry computation not parsed"
    fused = set(re.findall(r" fusion\(.*calls=%?([\w.\-]+)", text))
    applied = set(re.findall(r"to_apply=%?([\w.\-]+)", text))
    sizes = {layer_elems * k for k in range(1, layers + 1)}
    loops = set(re.findall(r" while\(.*body=%?([\w.\-]+)", text))
    plain = lambda dims: tuple(sorted(d for d in dims if d > 1))
    skip_dims = {plain(dims) for dims in skip_dims}

    def updates_in_place(computation: str) -> bool:
        """The computation, or a fusion nested in it, is the scatter."""
        body = bodies.get(computation, "")
        return bool(re.search(r" (scatter|dynamic-update-slice)\(", body)
                    ) or any(updates_in_place(inner) for inner in
                             re.findall(r"calls=%?([\w.\-]+)", body))

    found = []
    for name, body in bodies.items():
        if name in fused or name in applied:
            continue
        if loops_only and name not in loops:
            continue
        for line in body.splitlines():
            m = _HLO_LINE.match(line)
            if not m or m.group(4) in _NO_BUFFER:
                continue
            if dtype is not None and m.group(2) != dtype:
                continue
            dims = [int(x) for x in m.group(3).split(",") if x]
            if math.prod(dims) not in sizes or plain(dims) in skip_dims:
                continue
            if m.group(4) == "scatter":
                continue
            called = re.search(r"calls=%?([\w.\-]+)", line)
            if m.group(4) == "fusion" and called and \
                    updates_in_place(called.group(1)):
                continue
            found.append(line.strip()[:160])
    return found


@pytest.fixture(scope="module")
def engine_programs(one_chip, real_lowering):
    """``compile_program(geometry, program, blocks)`` -> (compiled, pool
    bytes, elements of one layer of a pool leaf): the engine's own program
    builders on a real-width, three-layer engine whose weights are never
    made (programs take them as arguments: shapes from ``eval_shape``)."""
    from dstack_tpu.models.llama import LlamaConfig, init_params
    from dstack_tpu.serving.engine import InferenceEngine

    env = pytest.MonkeyPatch()
    env.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", "1")  # read at engine init

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    @functools.lru_cache(maxsize=None)
    def compile_program(geometry: str, program: str, blocks: int,
                        layers: int = ENGINE_LAYERS):
        g = ENGINES[geometry]
        cfg = LlamaConfig(num_layers=layers, max_seq_len=g["max_len"],
                          **g["cfg"])
        b, bs = g["batch"], 32
        engine = InferenceEngine(
            cfg, params={"layers": {}}, batch_size=b, max_len=g["max_len"],
            paged=True, kv_block_size=bs, total_kv_blocks=blocks,
            prefill_chunk=512)
        params = sds(jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg)))
        pool = sds(tuple(engine._state))
        leaf = jax.tree.leaves(pool)[0]
        layer_elems = leaf.size // layers
        for weight in jax.tree.leaves(params):
            assert weight.size % layer_elems or \
                weight.size // layer_elems > layers, (
                    "a weight is as large as k layers of the pool: pick "
                    "another number of blocks", weight.shape)
        i32, f32 = jnp.int32, jnp.float32
        if program.startswith("decode_w"):
            fn = engine._decode_window_program(
                int(program[len("decode_w"):]), False, g["kb"])
            args = (params, arg(i32, b), arg(i32, b), arg(jnp.bool_, b),
                    *pool, arg(f32, b), arg(f32, b), arg(i32, b),
                    arg(i32, b, g["kb"]), arg(jnp.uint32, 2))
        elif program.startswith("prefill_paged_b"):
            bucket = int(program.rsplit("b", 1)[1])
            fn = engine._prefill_program(bucket)
            args = (params, arg(i32, bucket), arg(i32), *pool,
                    arg(i32, bucket // bs))
        else:
            assert program == "prefill_prefix_b512", program
            fn = engine._chunk_program(512)
            args = (params, arg(i32, 512), arg(i32), arg(i32), *pool,
                    arg(i32, g["max_len"] // bs))
        pool_bytes = sum(a.size * a.dtype.itemsize
                         for a in jax.tree.leaves(pool))
        return fn.lower(*args).compile(), pool_bytes, layer_elems

    yield compile_program
    env.undo()


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("geometry", sorted(ENGINES))
def test_engine_program_copies_no_pool(engine_programs, geometry, program):
    """No ``copy``, ``fusion``, ``reshape``, ``transpose``, slice or any
    other materialising instruction yields k whole layers of the pool,
    the in-place scatters excepted; the decode window holds the kernel
    once per layer (the benchmark counts decode steps by it)."""
    compiled, _, layer_elems = engine_programs(
        geometry, program, ENGINES[geometry]["blocks"][1])
    text = compiled.as_text()
    assert _pool_sized_ops(text, layer_elems, ENGINE_LAYERS) == []
    kernels = text.count('custom_call_target="tpu_custom_call"')
    assert kernels == (1 if program.startswith("decode") else 0)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("geometry", sorted(ENGINES))
def test_engine_program_temporaries_ignore_pool(engine_programs, geometry,
                                                program):
    """A pool twice as large adds under 5% of the added pool's bytes to
    the program's temporaries: what a program holds beside its arguments
    is its activations, never a copy of a layer's pool or of the whole."""
    blocks = ENGINES[geometry]["blocks"]
    small, small_pool, _ = engine_programs(geometry, program, blocks[0])
    large, large_pool, _ = engine_programs(geometry, program, blocks[1])
    grown = (large.memory_analysis().temp_size_in_bytes
             - small.memory_analysis().temp_size_in_bytes)
    assert grown < 0.05 * (large_pool - small_pool), (
        grown, large_pool - small_pool)


@pytest.mark.parametrize("program", ["slot_update", "first_token_sample"])
def test_scheduler_program_compiles_and_updates_in_place(one_chip,
                                                          real_lowering,
                                                          program):
    """The scheduler's own two programs at the widest cell's shape (256
    slots, a vocabulary of 65,536): the slot-update program writes the
    slots' three vectors and the sampler the first-token vector INTO their
    donated arguments (every donated buffer aliased to an output), and
    neither holds a copy."""
    from dstack_tpu.models.llama import LlamaConfig
    from dstack_tpu.serving.engine import InferenceEngine

    slots, vocab = 256, 65536
    cfg = LlamaConfig(num_layers=1, max_seq_len=256, vocab_size=vocab,
                      hidden_size=128, intermediate_size=256, num_heads=2,
                      num_kv_heads=2, head_dim=64)
    engine = InferenceEngine(cfg, params={"layers": {}}, batch_size=slots,
                             max_len=256, paged=True, kv_block_size=32,
                             total_kv_blocks=16)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    if program == "slot_update":
        fn, donated = engine._slot_update_program(), 3
        args = (arg(i32, slots), arg(i32, slots), arg(jnp.bool_, slots),
                arg(i32, slots), arg(i32, 4, slots))
    else:
        fn, donated = engine._first_token_program(), 1
        args = (arg(jnp.float32, vocab), arg(jnp.float32, 4),
                arg(jnp.uint32, 2), arg(i32, slots))
    assert fn.__name__ == program
    text = fn.lower(*args).compile().as_text()
    assert f"HloModule jit_{program}" in text
    aliases = re.search(r"input_output_alias=\{([^\n]*?)\}, entry", text)
    assert aliases and aliases.group(1).count("may-alias") + \
        aliases.group(1).count("must-alias") == donated, text[:400]
    assert " copy(" not in text


def _experts_stream_in_place(text: str, expert_layers: int, experts: int,
                             hidden: int, width: int) -> None:
    """The compiled program runs the experts through the repo's grouped
    product alone (``ops/grouped_matmul.py``: gate and up in one call, down
    in a second; the decode window's sit in its step loop, once), XLA's
    ``ragged-dot`` is gone, and nothing copies or re-lays a layer's stack of
    expert matrices in front of the kernel (ROADMAP S6 is what that would
    look like)."""
    assert not re.findall(r"%ragged-dot-(?!metadata)[\w.\-]* = ", text)
    assert len(re.findall(r"%grouped_matmul[\w.\-]* = ",
                          text)) == 2 * expert_layers
    assert _pool_sized_ops(text, experts * hidden * width, 1, "bf16") == []


#: the hybrid cell of BENCHMARK.json as its files size it: every width, all
#: seven layers, 128 slots, 8,192 blocks.  ``decode_w64`` at the widest table
#: bucket (128 columns): its gathered view is twice a layer of the pool, so
#: size tells them apart (at 64 columns, 128 slots x 64 blocks, they match).
HYBRID_PROGRAMS = ["decode_w64", "prefill_paged_b512", "prefill_prefix_b512"]
V5E_USABLE_BYTES = 15.75e9      # "Used 15.94G of 15.75G hbm" (PERF.md, PR 26)


@pytest.fixture(scope="module")
def hybrid_programs(one_chip, real_lowering):
    """``compile_program(program)`` -> (compiled, state tree shapes): the
    engine's own builders for the hybrid decoder at the cell's real sizes,
    weights never made."""
    import json
    from pathlib import Path

    from benchmarks.harness.sizes import load_config, program_config
    from dstack_tpu.models.ling_hybrid import init_params
    from dstack_tpu.serving.engine import InferenceEngine

    root = Path(__file__).resolve().parents[2] / "benchmarks"
    cfg = program_config(load_config(
        root / "configs" / "ling-3.0-flash-vl-7l-ep4.json"))
    load = json.loads((root / "workloads"
                       / "ling-3.0-flash-vl-7l-ep4.reason.json").read_text())
    args = dict(load["engine"], prefill_chunk=512)
    engine = InferenceEngine(cfg, params={"layers": {}}, **args)
    b, bs, kb = args["batch_size"], args["kv_block_size"], 128

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = sds(tuple(engine._state))
    i32, f32 = jnp.int32, jnp.float32

    @functools.lru_cache(maxsize=None)
    def compile_program(program: str):
        if program == "decode_w64":
            fn = engine._decode_window_program(64, False, kb)
            args = (params, arg(i32, b), arg(i32, b), arg(jnp.bool_, b),
                    *state, arg(f32, b), arg(f32, b), arg(i32, b),
                    arg(i32, b, kb), arg(jnp.uint32, 2))
        elif program == "prefill_paged_b512":
            fn = engine._prefill_program(512)
            args = (params, arg(i32, 512), arg(i32), *state,
                    (arg(i32, 512 // bs), arg(i32)))
        else:
            assert program == "prefill_prefix_b512", program
            fn = engine._chunk_program(512)
            args = (params, arg(i32, 512), arg(i32), arg(i32), *state,
                    (arg(i32, engine.max_len // bs), arg(i32)))
        return fn.lower(*args).compile(), state

    return compile_program


@pytest.mark.parametrize("program", HYBRID_PROGRAMS)
def test_hybrid_program_fits_and_copies_no_state(hybrid_programs, program):
    """Each program of the hybrid cell fits the chip beside its weights, and
    nothing but the in-place updates yields a layer (or k layers) of the
    recurrent state or the latent pool; its temporaries are far under the
    state's size (the decode window's are its gathered view of the pages)."""
    compiled, (pool, rec) = hybrid_programs(program)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < V5E_USABLE_BYTES, held
    # the state and the pool are donated: outputs alias them
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, rec)))
    assert mem.alias_size_in_bytes >= donated
    state = rec["state"]
    assert mem.temp_size_in_bytes < 0.6 * state.size * 4
    text = compiled.as_text()
    layers = state.shape[0]
    assert _pool_sized_ops(text, state.size // layers, layers, "f32") == []
    assert _pool_sized_ops(text, pool.size // pool.shape[0], pool.shape[0],
                           "bf16") == []
    _experts_stream_in_place(text, expert_layers=6, experts=128,
                             hidden=2560, width=768)
    if program == "decode_w64":
        _kda_state_in_place(text, state.shape)


def _kda_state_in_place(text: str, stack: tuple) -> None:
    """The decode window's step updates the KDA state through the repo's
    step kernel alone (``ops/kda.py`` ``kda_step_into``: one call a KDA
    layer in the step loop, the stack aliased in and out), and no fusion
    yields a whole layer of the state or the whole stack: XLA's form read a
    layer three times and wrote it back by a dynamic-update-slice fusion of
    the stack."""
    assert len(re.findall(r"%kda_step[\w.\-]* = ", text)) == stack[0]
    whole = [f"f32[{','.join(map(str, dims))}]" for dims in (stack,
                                                            stack[1:])]
    for result in re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) fusion\(",
                             text, re.M):
        assert not any(shape in result for shape in whole), result[:160]


#: the looped decoder's cell of BENCHMARK.json as its files size it: every
#: width, 48 layers run 4 times, 8 slots, the cell's own pool; ``decode_w64``
#: at the widest table bucket (64 columns)
LOOPED_PROGRAMS = ["decode_w64", "prefill_paged_b512", "prefill_prefix_b512"]


@pytest.fixture(scope="module")
def looped_programs(one_chip, real_lowering):
    """``compile_program(program)`` -> (compiled, pool shapes): the engine's
    own builders for the looped decoder at the cell's real sizes, weights
    never made."""
    import json
    from pathlib import Path

    from benchmarks.harness.sizes import load_config, program_config
    from dstack_tpu.models.ouro import init_params
    from dstack_tpu.serving.engine import InferenceEngine

    env = pytest.MonkeyPatch()
    env.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", "1")  # read at engine init
    root = Path(__file__).resolve().parents[2] / "benchmarks"
    cfg = program_config(load_config(root / "configs" / "ouro-2.6b.json"))
    load = json.loads((root / "workloads" / "ouro-2.6b.chat.json").read_text())
    args = dict(load["engine"], prefill_chunk=512)
    engine = InferenceEngine(cfg, params={"layers": {}}, **args)
    env.undo()
    b, bs = args["batch_size"], args["kv_block_size"]
    kb = args["max_len"] // bs

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pool = sds(tuple(engine._state))
    i32, f32 = jnp.int32, jnp.float32

    @functools.lru_cache(maxsize=None)
    def compile_program(program: str):
        if program == "decode_w64":
            fn = engine._decode_window_program(64, False, kb)
            args = (params, arg(i32, b), arg(i32, b), arg(jnp.bool_, b),
                    *pool, arg(f32, b), arg(f32, b), arg(i32, b),
                    arg(i32, b, kb), arg(jnp.uint32, 2))
        elif program == "prefill_paged_b512":
            fn = engine._prefill_program(512)
            args = (params, arg(i32, 512), arg(i32), *pool,
                    arg(i32, 512 // bs))
        else:
            assert program == "prefill_prefix_b512", program
            fn = engine._chunk_program(512)
            args = (params, arg(i32, 512), arg(i32), arg(i32), *pool,
                    arg(i32, kb))
        return fn.lower(*args).compile(), pool

    return compile_program


@pytest.mark.parametrize("program", LOOPED_PROGRAMS)
def test_looped_program_fits_and_stays_a_loop(looped_programs, program):
    """Each program of the looped decoder's cell fits the chip beside its
    weights at the cell's own pool size; the pool is [192, blocks, 32, 2048],
    donated and addressed in place (nothing yields k of its 192 cache
    layers); the pass loop and the layer scan stay loops in the compiled
    program: ONE kernel call site for 192 calls a step, and a program text
    that is no multiple of the plain decoder's."""
    compiled, pool = looped_programs(program)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < V5E_USABLE_BYTES, held
    k_pool = pool[0]
    assert k_pool.shape[0] == 192 and k_pool.shape[2:] == (32, 2048)
    assert mem.alias_size_in_bytes >= 2 * k_pool.size * 2
    text = compiled.as_text()
    assert _pool_sized_ops(text, k_pool.size // 192, 192, "bf16") == []
    kernels = text.count('custom_call_target="tpu_custom_call"')
    assert kernels == (1 if program.startswith("decode") else 0)
    # two nested loops around the layer body (and the window's step loop
    # around them in the decode program), not 192 copies of it
    assert len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = .* while\(", text,
                          re.M)) <= 6


def _held(compiled) -> int:
    """Bytes a compiled program holds on the device while it runs."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def _prompt_group(engine, bucket: int, params, state, arg):
    """The batched whole-prompt program of ``bucket`` (a provider with
    ``prefill_batch_fn``) and its arguments' shapes: a group of
    ``engine._prompts_per_program(bucket)`` prompts."""
    p, i32 = engine._prompts_per_program(bucket), jnp.int32
    return engine._prefill_program(bucket), (
        params, arg(i32, p, bucket), arg(i32, p), *state,
        arg(i32, p, bucket // engine._block_size), arg(i32, p),
        arg(jnp.float32, p, 3), (arg(jnp.uint32, 2),) * p,
        arg(i32, engine.batch_size))


def _chunk_group(engine, cbucket: int, params, state, arg):
    """The chunk program of ``cbucket`` of a provider with
    ``chunk_batch_fn`` and its arguments' shapes: a group of
    ``engine._prompts_per_program(cbucket)`` chunks of different slots."""
    g, i32 = engine._prompts_per_program(cbucket), jnp.int32
    return engine._chunk_program(cbucket), (
        params, arg(i32, g, cbucket), arg(i32, g), arg(i32, g), *state,
        (arg(i32, g, engine._blocks_per_slot), arg(i32, g)),
        arg(jnp.float32, g, 3), (arg(jnp.uint32, 2),) * g, arg(i32, g),
        arg(i32, engine.batch_size))


#: the LFM2 cell of BENCHMARK.json as its files size it: every width, 9
#: layers, all 64 experts, 256 slots, the cell's own pool; ``decode_w64`` at
#: the widest table bucket (128 columns)
LFM2_PROGRAMS = ["decode_w64", "prefill_paged_b512", "prefill_paged_b64",
                 "prefill_prefix_b512", "prefill_prefix_b64"]


@pytest.fixture(scope="module")
def lfm2_programs(one_chip, real_lowering):
    """``compile_program(program)`` -> (compiled, state tree shapes): the
    engine's own builders for the LFM2-MoE decoder at the cell's real
    sizes, with the block-table kernel, weights never made."""
    import json
    from pathlib import Path

    from benchmarks.harness.sizes import load_config, program_config
    from dstack_tpu.models.lfm2 import init_params
    from dstack_tpu.serving.engine import InferenceEngine

    env = pytest.MonkeyPatch()
    env.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", "1")  # read at engine init
    root = Path(__file__).resolve().parents[2] / "benchmarks"
    cfg = program_config(load_config(
        root / "configs" / "lfm2-24b-a2b-9l.json"))
    load = json.loads((root / "workloads"
                       / "lfm2-24b-a2b-9l.reason.json").read_text())
    args = dict(load["engine"], prefill_chunk=512)
    engine = InferenceEngine(cfg, params={"layers": {}}, **args)
    env.undo()
    b, bs = args["batch_size"], args["kv_block_size"]
    kb = args["max_len"] // bs

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = sds(tuple(engine._state))
    i32, f32 = jnp.int32, jnp.float32

    @functools.lru_cache(maxsize=None)
    def compile_program(program: str):
        if program == "decode_w64":
            fn = engine._decode_window_program(64, False, kb)
            args = (params, arg(i32, b), arg(i32, b), arg(jnp.bool_, b),
                    *state, arg(f32, b), arg(f32, b), arg(i32, b),
                    arg(i32, b, kb), arg(jnp.uint32, 2))
        elif program.startswith("prefill_paged_b"):
            fn, args = _prompt_group(engine, int(program[15:]), params, state,
                                     arg)
        else:
            assert program.startswith("prefill_prefix_b"), program
            fn, args = _chunk_group(engine, int(program[16:]), params, state,
                                    arg)
        return fn.lower(*args).compile(), state

    return compile_program


@pytest.mark.parametrize("program", LFM2_PROGRAMS)
def test_lfm2_program_fits_and_copies_no_pool(lfm2_programs, program):
    """Each program of the LFM2 cell fits the chip beside its 10.36 GB of
    weights at the cell's own pool size (2 x [2, 16384, 32, 512]); the pool
    and the tails are donated and addressed in place (nothing yields a whole
    layer of the K or V pool); the decode window calls the block-table
    kernel once an attention layer and the grouped product's kernel twice
    an expert layer, on the experts' matrices where they lie: so does a
    group of whole prompts (4 prompts at 512, 32 at 64), twice an expert
    layer for the group, not twice a prompt, within the bytes of the
    widest decode window, and a group of chunks of different slots (4 at
    512: 12.70 GB, where one chunk's program held 12.67; 32 at 64), each
    attending its slot's span one entry at a time."""
    compiled, (pool, rec) = lfm2_programs(program)
    held = _held(compiled)
    print(program, "held", held, "temp",
          compiled.memory_analysis().temp_size_in_bytes)
    assert held < V5E_USABLE_BYTES, held
    if program.startswith("prefill_paged"):
        assert held < _held(lfm2_programs("decode_w64")[0])
    mem = compiled.memory_analysis()
    assert pool["k"].shape == (2, 16384, 32, 512)
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, rec)))
    assert mem.alias_size_in_bytes >= donated
    text = compiled.as_text()
    assert _pool_sized_ops(text, pool["k"].size // 2, 2, "bf16") == []
    # (the grouped product is a tpu_custom_call too: tell the kernels by
    # their names, as benchmarks/layer_metrics/paged_attn_roofline.lfm2.py
    # does)
    kernels = len(re.findall(r"%paged_decode_attention[\w.\-]* = ", text))
    assert kernels == (2 if program.startswith("decode") else 0)
    _experts_stream_in_place(text, expert_layers=8, experts=64, hidden=2048,
                             width=1536)


#: the Nemotron-H cell of BENCHMARK.json as its files size it: every width,
#: all nine blocks, 384 slots, 24,576 blocks of 32 rows.  ``decode_w64`` at
#: the widest table bucket (128 columns)
NEMOTRON_PROGRAMS = ["decode_w64", "prefill_paged_b512", "prefill_paged_b64",
                     "prefill_prefix_b512", "prefill_prefix_b64"]


@pytest.fixture(scope="module")
def nemotron_programs(one_chip, real_lowering):
    """``compile_program(program)`` -> (compiled, state tree shapes): the
    engine's own builders for the Nemotron-H decoder at the cell's real
    sizes, with the block-table kernel, weights never made."""
    import json
    from pathlib import Path

    from benchmarks.harness.sizes import load_config, program_config
    from dstack_tpu.models.nemotron_h import init_params
    from dstack_tpu.serving.engine import InferenceEngine

    env = pytest.MonkeyPatch()
    env.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", "1")  # read at engine init
    root = Path(__file__).resolve().parents[2] / "benchmarks"
    cell = "nemotron-3-nano-30b-a3b-9l-ep2"
    cfg = program_config(load_config(root / "configs" / f"{cell}.json"))
    load = json.loads((root / "workloads"
                       / f"{cell}.reason.json").read_text())
    args = dict(load["engine"], prefill_chunk=512)
    b, bs = args["batch_size"], args["kv_block_size"]
    kb = args["max_len"] // bs
    # an engine of one slot, its provider then told the cell's slots and
    # pool: the 4.1 GB of states and pages are shapes only
    engine = InferenceEngine(cfg, params={"layers": {}}, **dict(
        args, batch_size=1, total_kv_blocks=kb + 1))
    env.undo()
    engine.batch_size = engine._programs.batch_size = b
    engine._programs.num_blocks = args["total_kv_blocks"]

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = sds(jax.eval_shape(engine._programs.init_state))
    i32, f32 = jnp.int32, jnp.float32

    @functools.lru_cache(maxsize=None)
    def compile_program(program: str):
        if program == "decode_w64":
            fn = engine._decode_window_program(64, False, kb)
            args = (params, arg(i32, b), arg(i32, b), arg(jnp.bool_, b),
                    *state, arg(f32, b), arg(f32, b), arg(i32, b),
                    arg(i32, b, kb), arg(jnp.uint32, 2))
        elif program.startswith("prefill_paged_b"):
            fn, args = _prompt_group(engine, int(program[15:]), params, state,
                                     arg)
        else:
            assert program.startswith("prefill_prefix_b"), program
            fn, args = _chunk_group(engine, int(program[16:]), params, state,
                                    arg)
        return fn.lower(*args).compile(), state

    return compile_program


@pytest.mark.parametrize("program", NEMOTRON_PROGRAMS)
def test_nemotron_program_fits_and_holds_the_state_once(nemotron_programs,
                                                        program):
    """Each program of the Nemotron-H cell fits the chip beside its 6.33 GB
    of weights at the cell's own sizes (384 slots: 3.28 GB of state-space
    states and tails; a pool of 2 x [1, 24576, 32, 256]); the pool, the
    states and the tails are donated and the program holds them once (its
    temporaries are far below ONE layer's states); the decode window calls
    the block-table kernel once (one attention layer), the grouped
    product's kernel twice an expert layer (up, down: no gate) on the
    experts' matrices where they lie (no copy or re-lay of a stack), and reads
    and writes a Mamba layer's state in ONE fusion a step.  A group of
    whole prompts (4 at 512, 32 at 64), and a group of chunks of different
    slots (4 at 512: 10.82 GB, where one chunk's program held 10.57; 32 at 64:
    10.99), calls the kernel twice an expert layer for the group, not twice
    a prompt or a chunk.  A group of whole prompts' bytes pass the widest
    decode window's (10.49 GB: the window holds the states once and keeps
    0.07 GB of temporaries, less than any other program of the cell; the
    512-token chunk program held 10.57 at the parent) by 0.30 GB at 512 and
    0.35 at 64 (PR 40: a group's float32 states, [prompts, 64, 64, 128] a
    Mamba layer, and its causal scores), and are held to half a GB over."""
    compiled, (pool, rec) = nemotron_programs(program)
    mem = compiled.memory_analysis()
    held = _held(compiled)
    print(program, "held", held, "temp", mem.temp_size_in_bytes,
          "args", mem.argument_size_in_bytes)
    assert held < V5E_USABLE_BYTES, held
    if program.startswith("prefill_paged"):
        assert held < _held(nemotron_programs("decode_w64")[0]) + 0.5e9
    assert pool["k"].shape == (1, 24576, 32, 256)
    assert [a.shape for a in rec["ssm"]] == [(384, 64, 64, 128)] * 4
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves((pool, rec)))
    assert mem.alias_size_in_bytes >= donated
    one_layer = rec["ssm"][0].size * 4
    assert mem.temp_size_in_bytes < one_layer, mem.temp_size_in_bytes
    text = compiled.as_text()
    kernels = len(re.findall(r"%paged_decode_attention[\w.\-]* = ", text))
    assert kernels == (1 if program.startswith("decode") else 0)
    assert not re.findall(r"%ragged-dot-(?!metadata)[\w.\-]* = ", text)
    assert len(re.findall(r"%grouped_matmul[\w.\-]* = ", text)) == 2 * 4
    assert _pool_sized_ops(text, 64 * 2688 * 1856, 1, "bf16") == []
    if program.startswith("decode"):
        # every operation that yields a whole layer's states is the one
        # fusion of ``ops/ssd.py`` ``ssm_step`` (the update and the read by
        # C in one pass), four a step
        whole = [line for line in text.splitlines()
                 if re.search(r" = \(?[^=]*f32\[384,64,64,128\]", line)
                 and " fusion(" in line]
        assert len(whole) == 4, [line[:200] for line in whole]
        assert all("jit(ssm_step)" in line for line in whole)


# -- the decode window buffer -------------------------------------------------
#
# The W rows a decode window produces are a scan CARRY of ``serving/dense.py``
# (PR 34): the compiled step loop, layer loop and pass loop may write a row of
# the buffer in place and read a layer's slab inside the fusion that consumes
# it, and must not slice, stack or re-lay k whole cache layers of it.  What
# the window's END does with the buffer (fold the heads into the pool's lanes,
# order the rows for the scatter) runs once in 64 steps and is left to the
# bound on the temporaries.

#: the dense cells at their own depth (the fixture's three layers make a
#: buffer small enough to live in fast memory, where nothing shows)
WINDOW_LAYERS = {"mha-d64": 24, "gqa-d128": 16}


@pytest.mark.parametrize("geometry", ["mha-d64", "gqa-d128", "looped"])
def test_decode_window_buffer_is_updated_in_place(request, geometry):
    """``decode_w64`` of both dense geometries and of the looped cell: no
    instruction of a loop's body yields k whole cache layers of a window
    buffer but the row update (a ``dynamic-update-slice`` in place), and
    the program's temporaries hold two window buffers, K's and V's, beside
    what the same program holds at a window of 8 (the chat geometry, 0.4 GB
    a buffer as stored; the looped cell: under 3 GB, where a scanned buffer
    compiled to 4.2-4.4; the batch geometry's 34 MB buffers are too small
    to tell from the rest).  One exception, by
    what the products are: with grouped queries they are matrix products,
    and the compiler hands a matrix product its sliced operand as a buffer
    (as it does a layer's weights): ONE layer's slab each for K and V, by
    a loop fusion, never a ``copy`` and never more than a layer."""
    if geometry == "looped":
        compiled, _ = request.getfixturevalue("looped_programs")(
            "decode_w64")
        layers, slots, hkv, d, group = 192, 8, 16, 128, 1
        weights = [(2048, 2048), (2048, 5632)]
        assert compiled.memory_analysis().temp_size_in_bytes < 3e9
    else:
        programs = request.getfixturevalue("engine_programs")
        g, layers = ENGINES[geometry], WINDOW_LAYERS[geometry]
        blocks = g["blocks"][1]
        compiled, _, _ = programs(geometry, "decode_w64", blocks, layers)
        slots, hkv, d = g["batch"], g["cfg"]["num_kv_heads"], \
            g["cfg"]["head_dim"]
        group = g["cfg"]["num_heads"] // hkv
        h, f = g["cfg"]["hidden_size"], g["cfg"]["intermediate_size"]
        weights = [(h, h), (h, hkv * d), (h, f)]
        # a buffer as the chip stores it: bf16, a head's values in lanes
        # of their own, so a 64-wide head takes a 128-lane tile
        stored = layers * 64 * slots * hkv * max(d, 128) * 2
        if stored > 100e6:  # the other's buffers are too small to tell
            short, _, _ = programs(geometry, "decode_w8", blocks, layers)
            grown = (compiled.memory_analysis().temp_size_in_bytes
                     - short.memory_analysis().temp_size_in_bytes)
            assert grown <= 2.1 * stored, grown
    # a layer's slab of the buffer can be exactly as large as a weight
    # matrix (64 x 32 slots x 2048 lanes = 2048 x 2048): tell them by shape
    found = _pool_sized_ops(compiled.as_text(), 64 * slots * hkv * d, layers,
                            "bf16", loops_only=True, skip_dims=weights)
    if group > 1:
        reads = [line for line in found
                 if "dynamic-slice" in line.split(" = ")[0]
                 and f" = bf16[1,64,{slots},{hkv},{d}]" in line
                 and " fusion(" in line]
        assert len(reads) <= 2, reads
        found = [line for line in found if line not in reads]
    assert found == [], found
