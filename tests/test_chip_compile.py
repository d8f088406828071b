"""Real-compiler guard: the main-path Pallas kernels at GPT-2 small widths.

Interpret mode cannot see what the chip's compiler refuses — a block that
breaks the (8, 128) rule, a DMA slice that is not tile-aligned, a kernel
that outgrows the 16 MB scoped VMEM. The TPU compiler is installed here
and compiles for a *described* v5e (``jax.experimental.topologies``), so
each kernel is lowered and compiled with ``interpret=False`` on shapes
placed on a described device. Nothing runs; a refusal raises.

Every case asserts ``tpu_custom_call`` in the compiled text: the ops pick
"kernel or lax" from the attached platform, which is ``cpu`` here, and a
case that compiled the lax composition would otherwise pass having
compiled no kernel. Where an op has no "force compiled" argument the test
steers that choice with ``monkeypatch`` — never through a product option.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mpit_tpu.ops import ring_collectives
from mpit_tpu.ops.decode_attention import (
    flash_paged_decode_attention,
)
from mpit_tpu.ops.flash_attention import flash_attention
from mpit_tpu.ops.kv_quant import QuantizedKV
from mpit_tpu.ops.quantized_matmul import QuantizedTensor, quantized_matmul
from mpit_tpu.train import GradSync
from mpit_tpu.utils.aot import abstractify, topology_world

# GPT-2 small: 12 heads of 64, d_model 768, d_ff 3072; the serve CLI's
# eight slots of 1024 positions over 16-token pages.
B, H, D, S, PAGE = 8, 12, 64, 1024, 16


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"v5e topology cannot be described: {e}")
    return topo


@pytest.fixture(autouse=True)
def _compile_cache_off():
    # A compile for a described device is written to the persistent
    # cache but cannot be read back without a chip (the next one warns
    # and recompiles), so keep the cache out of these compiles.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_on_chip(topo, fn, *shapes):
    """Compile ``fn`` for one described chip; the text must hold a kernel."""
    one = SingleDeviceSharding(topo.devices[0])
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes
    )
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_flash_attention_fwd_bwd(v5e):
    qkv = _sds((B, S, H, D), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    text = _compile_on_chip(v5e, jax.grad(loss, (0, 1, 2)), qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


# (slots, heads of 64, pages a slot, rows a page). The serve CLI's shape
# (GPT-2 small's 12 heads), and `gpt2l-serve-offline-decode`'s own: GPT-2
# large's 20 heads over 16 slots of 64 pages (a decode tick multiplies all
# heads at once over a tile of 16 pages, a chunk a head at a time): Mosaic's
# verdict on the page DMAs into a tile's rows and on the VMEM the tile
# takes, before the chip is asked. Then the other tilings `decode_tiling`
# can choose at GPT-2 large's row: four 64-row pages a step, and a page of
# 256 rows that is the step's whole tile.
_PAGED_SHAPES = {
    "small": (B, H, S // PAGE, PAGE), "large": (16, 20, 64, PAGE),
    "page64": (16, 20, 16, 64), "page256": (16, 20, 4, 256),
}


# T: one decode token, the CLI's prefill chunk, a spec_k=4 verify.
@pytest.mark.parametrize(
    "shape,t",
    [("small", 1), ("small", 64), ("small", 5), ("large", 1), ("large", 64),
     ("large", 5), ("page64", 1), ("page64", 64), ("page256", 1)],
)
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_flash_decode_paged(v5e, kv_dtype, shape, t):
    # One layer's pool as stored: rows packed [pages, page, H*D], an
    # int8 pool's scale plane [pages, page, H].
    b, h, pps, page = _PAGED_SHAPES[shape]
    pages = b * pps
    pool = _sds((pages, page, h * D), jnp.bfloat16)
    if kv_dtype == "int8":
        pool = QuantizedKV(
            q=_sds((pages, page, h * D), jnp.int8),
            scale=_sds((pages, page, h), jnp.float32),
        )
    _compile_on_chip(
        v5e,
        lambda q, k, v, n, bt: flash_paged_decode_attention(
            q, k, v, n, bt, interpret=False, return_visited=True
        ),
        _sds((b, t, h, D), jnp.bfloat16), pool, pool,
        _sds((b,), jnp.int32), _sds((b, pps), jnp.int32),
    )


# The pool's writer: a prefill chunk's rows over the slot batch, and a
# page's worth; bf16 rows and an int8 pool's payload.
@pytest.mark.parametrize("t", [64, PAGE])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_paged_write_pages(v5e, dtype, t):
    from mpit_tpu.ops.decode_attention import paged_write_pages

    _compile_on_chip(
        v5e,
        lambda pool, new, n, bt, ok: paged_write_pages(
            pool, new, n, bt, ok, interpret=False
        ),
        _sds((B * S // PAGE, PAGE, H * D), dtype), _sds((B, t, H * D), dtype),
        _sds((B,), jnp.int32), _sds((B, S // PAGE), jnp.int32),
        _sds((B, t), jnp.bool_),
    )


# The four GPT-2 small block matmuls; rows = one decode tick (8 slots)
# and one prefill chunk over the slot batch (8 x 64).
@pytest.mark.parametrize("rows", [(B, 1), (B, 64)])
@pytest.mark.parametrize(
    "d,f", [(768, 2304), (768, 768), (768, 3072), (3072, 768)]
)
def test_quantized_matmul(v5e, d, f, rows):
    w = QuantizedTensor(
        q=_sds((d, f), jnp.int8), scale=_sds((d, 1), jnp.float32)
    )
    _compile_on_chip(
        v5e,
        lambda x, w: quantized_matmul(x, w, interpret=False),
        _sds((*rows, d), jnp.bfloat16), w,
    )


@pytest.mark.parametrize("mode", ["ring", "ring_q8"])
def test_grad_sync_default_bucket_over_four_chips(v5e, mode, monkeypatch):
    """One default-sized gradient bucket through the ring reduce-scatter
    and all-gather kernels on the 2x2 mesh: the kernels are
    VMEM-resident, so the default bucket has to fit the scoped VMEM."""
    monkeypatch.setattr(ring_collectives, "_use_kernel", lambda _: True)
    world = topology_world({"data": 4}, "v5e:2x2")
    gs = GradSync("data", mode)

    def sync(v):
        flat = jnp.ravel(v)
        return gs.gather_updates(gs.scatter_grads(flat) / 4, flat.shape[0])

    f = jax.jit(
        world.shard_map(
            sync, in_specs=P("data"), out_specs=P(), check_vma=False
        )
    )
    # Two full default buckets (4 MB of the flat f32 vector each).
    x = abstractify(
        _sds((4, 2 * 2**20), jnp.float32), world.mesh, P("data")
    )
    text = f.lower(x).compile().as_text()
    assert text.count("tpu_custom_call") == 4  # 2 buckets x (RS + AG)


# ---------------------------------------------------------------------------
# A paged serving step is O(rows): it writes a tick's rows into the page
# pool and reads the tiles the kernel visits, and no operation in it
# grows with the pool (no copy of a layer's buffer, no stacking of the
# layers, no relayout before the kernel).
# ---------------------------------------------------------------------------

# GPT-2 large's widths: 20 heads of 64; 16-position pages, chunks of 64.
LARGE = dict(num_heads=20, d_model=1280, num_layers=3, max_seq_len=1024)
SLOTS, CHUNK = 4, 64
POOL_PAGES = 1024  # a buffer (42 MB in bf16) far above a step's activations


def _entry_parameters(text):
    """{parameter number: "dtype[dims]"} of a compiled module's ENTRY."""
    import re

    entry = text[text.index("\nENTRY "):]
    entry = entry[: entry.index("\n}")]
    return {
        int(n): shape
        for shape, n in re.findall(
            r"= (\w+\[[\d,]*\])\S* parameter\((\d+)\)", entry
        )
    }


def _aliased_parameters(text):
    import re

    header = text[: text.index("\n")]
    aliases = header[header.index("input_output_alias={"):]
    aliases = aliases[: aliases.index("entry_computation_layout")]
    return {int(n) for n in re.findall(r"\((\d+), \{\}", aliases)}


@pytest.fixture(scope="module")
def large_engines(v5e):
    """A paged engine a KV dtype at GPT-2 large's widths, its attention
    steered to the compiled kernel as on the chip (the platform here is
    the CPU), with what it takes to lower a step for the described chip."""
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.ops import decode_attention
    from mpit_tpu.serve import Engine

    cfg = GPT2Config(vocab_size=2048, dtype=jnp.bfloat16, **LARGE)
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        jax.jit(GPT2(cfg).init)(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"],
    )
    one = SingleDeviceSharding(v5e.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree
    )
    built = {}

    def build(kv_dtype):
        if kv_dtype not in built:
            eng = Engine(
                cfg, params, slots=SLOTS, max_len=1024,
                kv_pages=POOL_PAGES, kv_page_size=PAGE, prefill_chunk=CHUNK,
                kv_dtype=kv_dtype,
            )
            built[kv_dtype] = eng
        return built[kv_dtype]

    was = decode_attention._use_kernel
    decode_attention._use_kernel = lambda interpret: True
    yield build, on_chip
    decode_attention._use_kernel = was


def _paged_step(eng, step):
    s = eng.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    msk = jnp.zeros((s,), bool)
    bt = jnp.zeros((s, eng.pages_per_slot), jnp.int32)
    key = jax.random.key(0)
    if step == "decode":
        return eng._decode_paged_jit, (
            eng.params, eng.cache, eng.last_token, msk, bt, key, f32, i32)
    toks = jnp.zeros((s, eng.prefill_chunk), jnp.int32)
    return eng._prefill_paged_jit, (
        eng.params, eng.cache, eng.last_token, toks, i32, i32, i32, msk, bt,
        key, f32, i32)


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_step_is_o_rows(large_engines, kv_dtype, step):
    build, on_chip = large_engines
    eng = build(kv_dtype)
    jit, args = _paged_step(eng, step)
    compiled = jit.lower(*on_chip(args)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # (c) the kernel, not the lax fallback
    # (a) Every buffer of the pool is updated in place: each K and V
    # buffer of each layer (an int8 layer's scale plane too) is a
    # parameter that the executable aliases to an output.
    pool = jax.tree.leaves((eng.cache.k, eng.cache.v))
    want = {}
    for leaf in pool:
        shape = f"{leaf.dtype.name}[{','.join(map(str, leaf.shape))}]"
        shape = shape.replace("bfloat16", "bf16").replace("float32", "f32")
        shape = shape.replace("int8", "s8")
        want[shape] = want.get(shape, 0) + 1
    params, aliased = _entry_parameters(text), _aliased_parameters(text)
    got = {}
    for n in aliased:
        got[params[n]] = got.get(params[n], 0) + 1
    assert {s: got.get(s, 0) for s in want} == want
    # (b) What the step needs beside its arguments is less than ONE
    # buffer of the pool: no copy of a layer's K or V, no stack of the
    # layers and no relayout of a buffer for the kernel can hide in it.
    mem = compiled.memory_analysis()
    limit = min(leaf.nbytes for leaf in pool if leaf.dtype != jnp.float32)
    if kv_dtype == "int8":
        # Known and allowed (ROADMAP A3): the kernel's DMA wants whole
        # 128-lane tiles, so each call pads a layer's K and V scale
        # planes [pages, page, 20] to 128 lanes beside the pool.
        limit += 2 * eng.num_pages * PAGE * 128 * 4
    assert mem.temp_size_in_bytes < limit, (mem.temp_size_in_bytes, limit)
    assert mem.alias_size_in_bytes >= sum(leaf.nbytes for leaf in pool)


# GPT-2 large as the benchmark's serving cells run it: 36 layers, the whole
# vocabulary, and the slots, positions, page and chunk of the cells' own
# configuration file (48 slots of 1,024 positions in pages of 16, chunks
# of 64, since PR 42).
L_COUNTS = (2,)


def _gpt2_large_serve():
    import json

    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "configs", "gpt2-large.json")
    with open(path) as f:
        serve = json.load(f)["serve"]
    return (serve["slots"], serve["slot_positions"], serve["kv_page_size"],
            serve["prefill_chunk"])


@pytest.fixture(scope="module")
def gpt2_large_engine(v5e):
    """The cell's engine on shapes alone (parameters by ``eval_shape``, a
    two-slot pool) and the cache of the cell's whole pool, as shapes: the
    steps are lowered for that pool."""
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.ops import decode_attention
    from mpit_tpu.serve import Engine
    from mpit_tpu.serve.kvcache import PagedKVCache

    slots, positions, page, chunk = _gpt2_large_serve()
    cfg = GPT2Config(vocab_size=50257, d_ff=5120,
                     **{**LARGE, "num_layers": 36})
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        GPT2(cfg).init(jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))["params"]))
    was = decode_attention._use_kernel
    decode_attention._use_kernel = lambda interpret: True
    eng = Engine(cfg, params, slots=slots, max_len=positions,
                 kv_pages=2 * positions // page, kv_page_size=page,
                 prefill_chunk=chunk)
    pages = slots * eng.pages_per_slot
    full = lambda bufs: tuple(
        jax.ShapeDtypeStruct((pages, *b.shape[1:]), b.dtype) for b in bufs)
    cache = PagedKVCache(k=full(eng.cache.k), v=full(eng.cache.v),
                         lengths=eng.cache.lengths)
    one = SingleDeviceSharding(v5e.devices[0])
    yield eng, cache, lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    decode_attention._use_kernel = was


@pytest.mark.parametrize("n", [0, *L_COUNTS],
                         ids=lambda n: f"chunk{n}" if n else "decode")
def test_gpt2_large_step_fits_and_updates_in_place(gpt2_large_engine, n):
    """The decode step and the compacted chunk step of every count of
    participants the cell compiles (one: two seats' 128 rows), over the
    cell's whole pool: the kernels inside, every buffer of the pool
    aliased to an output, and what the step needs beside its arguments
    a small part of the 16 GB (every compiled count keeps its own
    temporaries) in which no copy of the head's table can hide."""
    eng, cache, on_chip = gpt2_large_engine
    assert eng._prefill_counts == L_COUNTS
    s, chunk = eng.slots, eng.prefill_chunk
    bt = jnp.zeros((s, eng.pages_per_slot), jnp.int32)
    temp, topk = jnp.zeros((s,), jnp.float32), jnp.zeros((s,), jnp.int32)
    if not n:
        jit, kernels = eng._decode_paged_jit, ("paged_decode_attn",)
        args = (eng.params, cache, eng.last_token, jnp.zeros((s,), bool),
                bt, jax.random.key(0), temp, topk)
    else:
        jit = eng._prefill_compact_jit
        kernels = ("paged_decode_attn", "paged_kv_write")
        z = jnp.zeros((n,), jnp.int32)
        args = (eng.params, cache, eng.last_token, z,
                jnp.zeros((n, chunk), jnp.int32), z, z, z,
                jnp.zeros((n,), bool), bt, jax.random.key(0), temp, topk)
    compiled = jit.lower(*on_chip(args)).compile()
    text = compiled.as_text()
    assert all(k in text for k in kernels)
    pool = jax.tree.leaves((cache.k, cache.v))
    shape = f"bf16[{','.join(map(str, pool[0].shape))}]"
    params, aliased = _entry_parameters(text), _aliased_parameters(text)
    assert sum(params[i] == shape for i in aliased) == len(pool) == 72
    mem = compiled.memory_analysis()
    pool_bytes = sum(l.size * l.dtype.itemsize for l in pool)
    assert mem.alias_size_in_bytes >= pool_bytes
    # Arguments 10.61 GB (weights 1.55, the pool of 48 slots 9.06) of the
    # 15.75 that load. Temporaries 0.06 GB (decode 59.8 MB, the chunk
    # step 58.5): the sampler reads the head's table where it lies. A
    # padded copy of the table (0.147 GB: 0.20 in all until PR 45), or a
    # prefix of it sliced out for the scan (0.126), fails the bound.
    assert mem.argument_size_in_bytes < 10.7e9, mem.argument_size_in_bytes
    assert mem.temp_size_in_bytes < 0.1e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("t", [1, CHUNK], ids=["decode", "prefill"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_tp_paged_forward_compiles(v5e, kv_dtype, t, monkeypatch):
    """The tensor-parallel paged forward, two ways over GPT-2 large's
    heads, as the engine composes it: each rank holds its 640 lanes of
    every pool buffer, and both kernels (the page writer for a chunk's
    rows, the paged decode attention) are traced on that slice under
    ``shard_map``. No engine can be built on a described mesh (it places
    arrays), so the forward is lowered from shapes alone."""
    import functools

    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.ops import decode_attention
    from mpit_tpu.parallel.megatron import repack_qkv
    from mpit_tpu.serve.engine import _tp_paged_forward, _tp_param_specs
    from mpit_tpu.serve.kvcache import alloc_paged_cache, paged_cache_specs

    monkeypatch.setattr(decode_attention, "_use_kernel", lambda _: True)
    cfg = GPT2Config(vocab_size=2048, dtype=jnp.bfloat16, **LARGE)
    params = jax.eval_shape(
        lambda: {
            k: repack_qkv(v, 2) if k.startswith("block_") else v
            for k, v in GPT2(cfg).init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
            )["params"].items()
        }
    )
    quantized = kv_dtype == "int8"
    cache = jax.eval_shape(
        lambda: alloc_paged_cache(
            cfg, SLOTS, POOL_PAGES, PAGE, dtype=jnp.bfloat16,
            quantized=quantized,
        )
    )
    world = topology_world({"data": 2, "model": 2}, "v5e:2x2")
    rep = P()
    specs = (
        _tp_param_specs(cfg, params, "model"), rep,
        paged_cache_specs(
            "model", num_layers=cfg.num_layers, quantized=quantized
        ),
        rep, rep,
    )
    fwd = world.shard_map(
        functools.partial(
            _tp_paged_forward, cfg=cfg, axis="model",
            attn_fn=functools.partial(
                flash_paged_decode_attention, interpret=None
            ),
        ),
        in_specs=specs, out_specs=(rep, specs[2]),
    )
    args = abstractify(
        (params, _sds((SLOTS, t), jnp.int32), cache,
         _sds((SLOTS, 1024 // PAGE), jnp.int32), _sds((SLOTS, t), bool)),
        world.mesh, specs,
    )
    compiled = jax.jit(fwd, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    # Attention a layer; a chunk's rows also take the writer, K and V.
    assert text.count("tpu_custom_call") == cfg.num_layers * (
        1 if t == 1 else 3
    )
    one = min(
        leaf.size * leaf.dtype.itemsize // 2
        for leaf in jax.tree.leaves((cache.k, cache.v))
        if leaf.dtype != jnp.float32
    )  # a rank's half of one buffer
    if quantized:
        one += 2 * POOL_PAGES * PAGE * 128 * 4  # as in the test above
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < one
    # Donated and written in place on every rank: its half of the pool.
    assert mem.alias_size_in_bytes >= sum(
        leaf.size * leaf.dtype.itemsize // 2
        for leaf in jax.tree.leaves((cache.k, cache.v))
    )


# ---------------------------------------------------------------------------
# The xing4 family at its published widths (benchmark/configs/
# xing4-29b-a4b-6of40.json): 32 heads against one shared latent row of
# 512 + 64 values (the rotary part stored in 128 lanes), 64 experts of
# width 1,024, 4 residual streams of 3,584, a vocabulary of 131,072; 32
# slots of 13,312 positions in pages of 256, prefill chunks of 2,048.
# ---------------------------------------------------------------------------

X4_SLOTS, X4_POSITIONS, X4_PAGE, X4_CHUNK = 32, 13312, 256, 2048
V5E_HBM = 15.75e9  # what loads on a v5e


def _x4_config():
    from mpit_tpu.models.xing4 import Xing4Config

    return Xing4Config(num_hidden_layers=6, first_k_dense_replace=1,
                       max_seq_len=X4_POSITIONS)


def test_mla_decode_kernel(v5e):
    from mpit_tpu.ops.mla_attention import mla_paged_decode_attention

    cfg = _x4_config()
    h, c, r = cfg.num_attention_heads, cfg.kv_lora_rank, 128
    pages, pps = 2 * X4_POSITIONS // X4_PAGE, X4_POSITIONS // X4_PAGE
    bf = jnp.bfloat16
    text = _compile_on_chip(
        v5e,
        lambda qa, qr, ckv, kr, lens, bt: mla_paged_decode_attention(
            qa, qr, ckv, kr, lens, bt, scale=cfg.softmax_scale,
            interpret=False),
        _sds((X4_SLOTS, h, c), bf), _sds((X4_SLOTS, h, cfg.qk_rope_head_dim), bf),
        _sds((pages, X4_PAGE, c), bf), _sds((pages, X4_PAGE, r), bf),
        _sds((X4_SLOTS,), jnp.int32), _sds((X4_SLOTS, pps), jnp.int32),
    )
    assert "mla_paged_decode_attn" in text


@pytest.mark.parametrize(
    "b,t,h,dn,dv,pps,masked",
    [(1, X4_CHUNK, 32, 128, 128, X4_POSITIONS // X4_PAGE, False),
     (4, 512, 64, 192, 256, 144, True)],
    ids=["xing4", "glm52"])
def test_mla_chunk_kernel(v5e, b, t, h, dn, dv, pps, masked):
    """A chunk's expanded latent attention at the two cells' shapes: 32
    heads of 128 + 64 / 128 over 2,048 rows, and four participants' 512
    rows under the choice's mask, 64 heads of 192 + 64 / 256 (the key's
    rotary part in the rest of the expanded key's second lane tile)."""
    from mpit_tpu.ops.mla_attention import mla_paged_prefill_attention

    bf, c, dr, ps = jnp.bfloat16, 512, 64, X4_PAGE
    args = [_sds((b, t, h, dn), bf), _sds((b, t, h, dr), bf),
            _sds((2 * pps, ps, c), bf), _sds((2 * pps, ps, 128), bf),
            _sds((b,), jnp.int32), _sds((b, pps), jnp.int32),
            _sds((c, h, dn + dv), bf)]
    if masked:
        args.append(_sds((b, t, pps * ps), bool))
    text = _compile_on_chip(
        v5e,
        lambda *a: mla_paged_prefill_attention(
            *a[:7], scale=(dn + dr) ** -0.5,
            select=a[7] if masked else None, interpret=False),
        *args)
    assert "mla_paged_chunk_attn" in text


@pytest.mark.parametrize("width", [512, 128], ids=["latent", "rope"])
def test_paged_write_pages_latent_layout(v5e, width):
    """A chunk's rows into a buffer of the latent pool, a page at a time."""
    from mpit_tpu.ops.decode_attention import paged_write_pages

    pages, pps = 2 * X4_POSITIONS // X4_PAGE, X4_POSITIONS // X4_PAGE
    text = _compile_on_chip(
        v5e,
        lambda pool, new, lens, bt, valid: paged_write_pages(
            pool, new, lens, bt, valid, interpret=False),
        _sds((pages, X4_PAGE, width), jnp.bfloat16),
        _sds((2, X4_CHUNK, width), jnp.bfloat16),
        _sds((2,), jnp.int32), _sds((2, pps), jnp.int32),
        _sds((2, X4_CHUNK), bool),
    )
    assert "paged_kv_write" in text


@pytest.fixture(scope="module")
def xing4_engine(v5e):
    """The configuration's engine on shapes alone: parameters by
    ``eval_shape`` (9.6 GB are never made) and a two-slot pool; the steps
    are then lowered for the pool of all 32 slots."""
    from mpit_tpu.models.xing4 import init_params
    from mpit_tpu.ops import decode_attention
    from mpit_tpu.serve import Engine

    cfg = _x4_config()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    pps = X4_POSITIONS // X4_PAGE
    was = decode_attention._use_kernel
    decode_attention._use_kernel = lambda interpret: True
    eng = Engine(cfg, params, slots=X4_SLOTS, max_len=X4_POSITIONS,
                 kv_pages=2 * pps, kv_page_size=X4_PAGE,
                 prefill_chunk=X4_CHUNK)
    one = SingleDeviceSharding(v5e.devices[0])
    yield eng, lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    decode_attention._use_kernel = was


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_xing4_paged_steps_fit_and_update_in_place(xing4_engine, step):
    """Both paged steps of the configuration, pool of 32 x 13,312
    positions: every buffer of the latent pool aliased to an output, and
    arguments + temporaries under what loads on the chip (this is how 32
    against 24 slots was decided before any chip time was spent)."""
    from mpit_tpu.serve.kvcache import PagedKVCache

    eng, on_chip = xing4_engine
    assert eng._prefill_counts == (1,)  # a chunk is the step's 2,048 rows
    pages = X4_SLOTS * eng.pages_per_slot
    full = lambda bufs: tuple(
        jax.ShapeDtypeStruct((pages, *b.shape[1:]), b.dtype) for b in bufs)
    cache = PagedKVCache(k=full(eng.cache.k), v=full(eng.cache.v),
                         lengths=eng.cache.lengths)
    s = eng.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt, key = jnp.zeros((s, eng.pages_per_slot), jnp.int32), jax.random.key(0)
    if step == "decode":
        jit, args = eng._decode_paged_jit, (
            eng.params, cache, eng.last_token, jnp.zeros((s,), bool), bt,
            key, f32, i32)
    else:
        n = eng._prefill_counts[-1]  # the largest step a tick can meet
        z = jnp.zeros((n,), jnp.int32)
        jit, args = eng._prefill_compact_jit, (
            eng.params, cache, eng.last_token, z,
            jnp.zeros((n, X4_CHUNK), jnp.int32), z, z, z,
            jnp.zeros((n,), bool), bt, key, f32, i32)
    compiled = jit.lower(*on_chip(args)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in (("mla_paged_decode_attn",) if step == "decode"
                 else ("mla_paged_chunk_attn", "paged_kv_write")):
        assert name in text, name
    if step == "prefill":
        # A chunk's scores stay in the kernel: no [participants, heads,
        # rows, tile] float32 array is left in the step.
        assert not re.search(rf"f32\[\d+,32,{X4_CHUNK},\d+\]", text)
    pool = jax.tree.leaves((cache.k, cache.v))
    want = {}
    for leaf in pool:
        shape = f"bf16[{','.join(map(str, leaf.shape))}]"
        want[shape] = want.get(shape, 0) + 1
    params, aliased = _entry_parameters(text), _aliased_parameters(text)
    got = {}
    for n_ in aliased:
        got[params[n_]] = got.get(params[n_], 0) + 1
    assert {s_: got.get(s_, 0) for s_ in want} == want
    mem = compiled.memory_analysis()
    pool_bytes = sum(l.size * l.dtype.itemsize for l in pool)
    assert mem.alias_size_in_bytes >= pool_bytes
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"xing4 {step}: arguments {mem.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, held "
          f"{held / 1e9:.2f} GB")
    assert held < V5E_HBM, held
    if step == "decode":
        # A tick's temporaries are O(rows): under the smallest buffer.
        assert mem.temp_size_in_bytes < min(
            l.size * l.dtype.itemsize for l in pool)


# Olmo-Hybrid-7B as benchmark/configs/olmo-hybrid-7b-8of32.json serves it:
# two periods (6 linear layers, 2 full), 64 slots of 4,096 positions over
# 128-position pages, chunks of 512, the sampler's tile 7,168 rows.
OH_SLOTS, OH_POSITIONS, OH_PAGE, OH_CHUNK, OH_SAMPLE = 64, 4096, 128, 512, 7168
OH_LIMIT = 15.0e9  # arguments + temporaries a step may hold (ISSUE 32)


def test_gated_delta_kernels(v5e):
    """The rule's chunk and step kernels at the published widths: 96 and
    192 wide heads on whole lane tiles inside the kernel, the step's state
    aliased to its result."""
    from mpit_tpu.ops import gated_delta as gd

    h, dk, dv = 30, 96, 192
    bf, f32 = jnp.bfloat16, jnp.float32
    text = _compile_on_chip(
        v5e, lambda *a: gd.gdn_chunk(*a, interpret=False),
        _sds((2, OH_CHUNK, h, dk), bf), _sds((2, OH_CHUNK, h, dk), bf),
        _sds((2, OH_CHUNK, h, dv), bf), _sds((2, OH_CHUNK, h), f32),
        _sds((2, OH_CHUNK, h), f32), _sds((2, h, dk, dv), f32))
    assert "gdn_chunk" in text
    text = _compile_on_chip(
        v5e, lambda *a: gd.gdn_step(*a, interpret=False),
        _sds((OH_SLOTS, h, dk), bf), _sds((OH_SLOTS, h, dk), bf),
        _sds((OH_SLOTS, h, dv), bf), _sds((OH_SLOTS, h), f32),
        _sds((OH_SLOTS, h), f32), _sds((OH_SLOTS, h, dk, dv), f32))
    assert "gdn_step" in text


@pytest.fixture(scope="module")
def olmo_hybrid_engine(v5e):
    """The configuration's engine on shapes alone (4.9 GB of parameters
    are never made) and a two-slot page pool; the steps are then lowered
    for the pool of all 64 slots."""
    from mpit_tpu.models.olmo_hybrid import OlmoHybridConfig, init_params
    from mpit_tpu.ops import decode_attention
    from mpit_tpu.serve import Engine

    cfg = OlmoHybridConfig(num_hidden_layers=8, max_seq_len=OH_POSITIONS)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    pps = OH_POSITIONS // OH_PAGE
    was = decode_attention._use_kernel
    decode_attention._use_kernel = lambda interpret: True
    eng = Engine(cfg, params, slots=OH_SLOTS, max_len=OH_POSITIONS,
                 kv_pages=2 * pps, kv_page_size=OH_PAGE,
                 prefill_chunk=OH_CHUNK, sample_block=OH_SAMPLE)
    one = SingleDeviceSharding(v5e.devices[0])
    yield eng, lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    decode_attention._use_kernel = was


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_olmo_hybrid_paged_steps_fit_and_update_in_place(
        olmo_hybrid_engine, step):
    """Both steps of the cell, pool of 64 x 4,096 positions and 64 seats
    of state: every buffer of both pools aliased to an output, the rule's
    and the attention's kernels in the text, and arguments + temporaries
    under 15.0 GB (how 64 slots were kept in PR 32: the sampler's tile
    of 8,192 rows then padded the head with a copy of 0.82 GB and passed
    it; since PR 45 no tile copies the table)."""
    import dataclasses

    eng, on_chip = olmo_hybrid_engine
    assert eng._prefill_counts == (1, 2, 4)
    pages = OH_SLOTS * eng.pages_per_slot
    full = lambda bufs: tuple(
        jax.ShapeDtypeStruct((pages, *b.shape[1:]), b.dtype) for b in bufs)
    cache = dataclasses.replace(
        eng.cache, k=full(eng.cache.k), v=full(eng.cache.v))
    s = eng.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt, key = jnp.zeros((s, eng.pages_per_slot), jnp.int32), jax.random.key(0)
    if step == "decode":
        jit, args = eng._decode_paged_jit, (
            eng.params, cache, eng.last_token, jnp.zeros((s,), bool), bt,
            key, f32, i32)
        kernels = ("gdn_step", "paged_decode_attn")
    else:
        n = eng._prefill_counts[-1]  # the largest step a tick can meet
        z = jnp.zeros((n,), jnp.int32)
        jit, args = eng._prefill_compact_jit, (
            eng.params, cache, eng.last_token, z,
            jnp.zeros((n, OH_CHUNK), jnp.int32), z, z, z,
            jnp.zeros((n,), bool), bt, key, f32, i32)
        kernels = ("gdn_chunk", "paged_decode_attn", "paged_kv_write")
    compiled = jit.lower(*on_chip(args)).compile()
    text = compiled.as_text()
    for name in kernels:
        assert name in text, name
    mem = compiled.memory_analysis()
    pools = jax.tree.leaves((cache.k, cache.v, cache.state))
    assert mem.alias_size_in_bytes >= sum(
        l.size * l.dtype.itemsize for l in pools)
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < OH_LIMIT, held


# GLM-5.2 as benchmark/configs/glm-5.2-5of78-ep16.json serves it: 1 dense
# + 4 expert layers, 16 of 256 experts and 19,360 rows of the vocabulary
# held, two layers with an indexer (a third seat of 128 values a
# position); 16 slots of 36,864 positions in pages of 256, chunks of 512
# rows (four slots' in the largest compacted step), the sampler's tile
# 4,840 rows.
GD_SLOTS, GD_POSITIONS, GD_PAGE, GD_CHUNK, GD_SAMPLE = 16, 36864, 256, 512, 4840
GD_LIMIT = 14.0e9  # arguments + temporaries a step may hold (ISSUE 34)


def _gd_config():
    from mpit_tpu.models.glm_dsa import GlmDsaConfig

    return GlmDsaConfig(
        num_hidden_layers=5, vocab_size=19360,
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        indexer_types=("full", "shared", "shared", "shared", "full"),
        experts_held=tuple(range(16)), max_seq_len=GD_POSITIONS)


@pytest.mark.parametrize("t", [1, GD_CHUNK], ids=["tick", "chunk"])
def test_dsa_index_scores_kernel(v5e, t):
    """The index scores' kernel at the cell's shapes: a tick's row a slot
    against 144 pages of keys, and a chunk's 512 rows of one slot."""
    from mpit_tpu.ops.dsa import dsa_index_scores

    cfg = _gd_config()
    b = GD_SLOTS if t == 1 else 1
    pps = GD_POSITIONS // GD_PAGE
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    text = _compile_on_chip(
        v5e,
        lambda q, w, pool, lens, bt: dsa_index_scores(
            q, w, pool, lens, bt, interpret=False),
        _sds((b, t, hi, di), jnp.bfloat16), _sds((b, t, hi), jnp.float32),
        _sds((2 * pps, GD_PAGE, di), jnp.bfloat16),
        _sds((b,), jnp.int32), _sds((b, pps), jnp.int32),
    )
    assert ("dsa_index_scores_tick" if t == 1
            else "dsa_index_scores_chunk") in text


@pytest.fixture(scope="module")
def glm_dsa_engine(v5e):
    """The configuration's engine on shapes alone (7.8 GB of parameters
    are never made) and a two-slot pool; the steps are then lowered for
    the pool of all 16 slots."""
    from mpit_tpu.models.glm_dsa import init_params
    from mpit_tpu.ops import decode_attention
    from mpit_tpu.serve import Engine

    cfg = _gd_config()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    pps = GD_POSITIONS // GD_PAGE
    was = decode_attention._use_kernel
    decode_attention._use_kernel = lambda interpret: True
    eng = Engine(cfg, params, slots=GD_SLOTS, max_len=GD_POSITIONS,
                 kv_pages=2 * pps, kv_page_size=GD_PAGE,
                 prefill_chunk=GD_CHUNK, sample_block=GD_SAMPLE)
    one = SingleDeviceSharding(v5e.devices[0])
    yield eng, lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    decode_attention._use_kernel = was


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_glm_dsa_paged_steps_fit_and_update_in_place(glm_dsa_engine, step):
    """Both steps of the cell, pool of 16 x 36,864 positions: every buffer
    of the pool, the third seats among them, aliased to an output; the
    kernels in the text; arguments + temporaries under 14.0 GB (printed:
    11.9 GB of them are the weights and the pool; the chunk step is the
    one of four slots' 512 rows)."""
    import dataclasses

    eng, on_chip = glm_dsa_engine
    assert eng._prefill_counts == (1, 2, 4)  # 2,048 rows a step at most
    pages = GD_SLOTS * eng.pages_per_slot
    full = lambda bufs: tuple(
        b if b is None else jax.ShapeDtypeStruct((pages, *b.shape[1:]),
                                                  b.dtype) for b in bufs)
    cache = dataclasses.replace(
        eng.cache, k=full(eng.cache.k), v=full(eng.cache.v),
        x=full(eng.cache.x))
    s = eng.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt, key = jnp.zeros((s, eng.pages_per_slot), jnp.int32), jax.random.key(0)
    if step == "decode":
        jit, args = eng._decode_paged_jit, (
            eng.params, cache, eng.last_token, jnp.zeros((s,), bool), bt,
            key, f32, i32)
        kernels = ("dsa_index_scores_tick", "mla_paged_decode_attn")
    else:
        n = eng._prefill_counts[-1]  # the largest step a tick can meet
        z = jnp.zeros((n,), jnp.int32)
        jit, args = eng._prefill_compact_jit, (
            eng.params, cache, eng.last_token, z,
            jnp.zeros((n, GD_CHUNK), jnp.int32), z, z, z,
            jnp.zeros((n,), bool), bt, key, f32, i32)
        kernels = ("dsa_index_scores_chunk", "mla_paged_chunk_attn",
                   "paged_kv_write")
    compiled = jit.lower(*on_chip(args)).compile()
    text = compiled.as_text()
    for name in kernels:
        assert name in text, name
    if step == "prefill":  # the scores stay in the kernel
        assert not re.search(rf"f32\[\d+,64,{GD_CHUNK},\d+\]", text)
    mem = compiled.memory_analysis()
    pool = jax.tree.leaves((cache.k, cache.v, cache.x))
    assert len(pool) == 5 + 5 + 2
    pool_bytes = sum(l.size * l.dtype.itemsize for l in pool)
    assert pool_bytes == GD_SLOTS * GD_POSITIONS * 6912
    assert mem.alias_size_in_bytes >= pool_bytes
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"glm_dsa {step}: arguments {mem.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, held "
          f"{held / 1e9:.2f} GB")
    assert held < GD_LIMIT, held
    if step == "decode":
        # A tick's temporaries are O(rows): far under the smallest buffer.
        assert mem.temp_size_in_bytes < min(
            l.size * l.dtype.itemsize for l in pool)


# Laguna-S-2.1 as benchmark/configs/laguna-s-2.1-5of48-ep4.json serves it:
# slots, positions, page, chunk and the sampler's tile are READ from that
# file, so a change there is compiled here. 1 dense + 4 expert layers, 64 of
# 256 experts and 25,088 rows of the vocabulary held; two full layers whose
# pages keep every position and three window layers whose pool is slots x 5
# pages whatever the positions.
LG_LIMIT = 15.75e9  # what loads on the chip: arguments + temporaries


def _lg_file():
    import json
    import os

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "laguna-s-2.1-5of48-ep4.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("heads,window,t", [
    (48, 0, 1), (72, 512, 1), (48, 0, 512), (72, 512, 512)],
    ids=["full-tick", "window-tick", "full-chunk", "window-chunk"])
def test_grouped_attention_kernel(v5e, heads, window, t):
    """The grouped kernel at the cell's shapes: groups of 6 and of 9 query
    heads over 8 cached heads of 128, a tick's row a slot and a chunk's 512
    rows, a window layer's first position."""
    from mpit_tpu.ops.decode_attention import grouped_paged_attention

    serve = _lg_file()["serve"]
    page = serve["kv_page_size"]
    pps = serve["slot_positions"] // page
    b = serve["slots"] if t == 1 else 4
    pages = b * (5 if window else pps)
    text = _compile_on_chip(
        v5e,
        lambda q, k, v, lens, bt: grouped_paged_attention(
            q, k, v, lens, bt, window=window, interpret=False),
        _sds((b, t, heads, 128), jnp.bfloat16),
        _sds((pages, page, 1024), jnp.bfloat16),
        _sds((pages, page, 1024), jnp.bfloat16),
        _sds((b,), jnp.int32), _sds((b, pps), jnp.int32),
    )
    assert ("gqa_paged_decode_attn" if t == 1
            else "gqa_paged_chunk_attn") in text


@pytest.fixture(scope="module")
def laguna_engine(v5e):
    """The configuration's engine on shapes alone (6.0 GB of parameters are
    never made) and a two-slot pool of the full layers; the steps are then
    lowered for the pool of all the slots."""
    from mpit_tpu.models.laguna import LagunaConfig, init_params
    from mpit_tpu.ops import decode_attention
    from mpit_tpu.serve import Engine

    model = _lg_file()
    serve = model["serve"]
    cfg = LagunaConfig.from_dict(model, max_seq_len=serve["slot_positions"])
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    pps = serve["slot_positions"] // serve["kv_page_size"]
    was = decode_attention._use_kernel
    decode_attention._use_kernel = lambda interpret: True
    eng = Engine(cfg, params, slots=serve["slots"],
                 max_len=serve["slot_positions"], kv_pages=2 * pps,
                 kv_page_size=serve["kv_page_size"],
                 prefill_chunk=serve["prefill_chunk"],
                 sample_block=serve["sample_block"])
    one = SingleDeviceSharding(v5e.devices[0])
    yield eng, lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    decode_attention._use_kernel = was


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_laguna_paged_steps_fit_and_update_in_place(laguna_engine, step):
    """Both steps of the cell at the file's slots and positions: every
    buffer of both pools aliased to an output; the grouped kernel in the
    text; the window layers' pool slots x (window + chunk + page) positions
    (one lifetime for all five layers would be 14.1 GB of pages); arguments
    + temporaries under the 15.75 GB that load (the chunk step is the one
    of four slots' 512 rows, 2,048 in all)."""
    import dataclasses

    eng, on_chip = laguna_engine
    serve = _lg_file()["serve"]
    slots, chunk = serve["slots"], serve["prefill_chunk"]
    assert eng._prefill_counts[-1] * chunk == 2048
    two = 2 * eng.pages_per_slot  # the fixture's pool of the full layers
    pages = slots * eng.pages_per_slot
    full = lambda bufs: tuple(
        jax.ShapeDtypeStruct(
            (pages if b.shape[0] == two else b.shape[0], *b.shape[1:]),
            b.dtype) for b in bufs)
    cache = dataclasses.replace(
        eng.cache, k=full(eng.cache.k), v=full(eng.cache.v))
    window_rows = 512 + chunk + serve["kv_page_size"]
    assert [b.shape[0] * b.shape[1] for b in cache.k] == [
        slots * serve["slot_positions"], *[slots * window_rows] * 3,
        slots * serve["slot_positions"]]
    i32, f32 = jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.float32)
    bt = jnp.zeros((slots, 2 * eng.pages_per_slot), jnp.int32)
    key = jax.random.key(0)
    if step == "decode":
        jit, args = eng._decode_paged_jit, (
            eng.params, cache, eng.last_token, jnp.zeros((slots,), bool), bt,
            key, f32, i32)
        kernels = ("gqa_paged_decode_attn",)
    else:
        n = eng._prefill_counts[-1]  # the largest step a tick can meet
        z = jnp.zeros((n,), jnp.int32)
        jit, args = eng._prefill_compact_jit, (
            eng.params, cache, eng.last_token, z,
            jnp.zeros((n, chunk), jnp.int32), z, z, z,
            jnp.zeros((n,), bool), bt, key, f32, i32)
        kernels = ("gqa_paged_chunk_attn", "paged_kv_write")
    compiled = jit.lower(*on_chip(args)).compile()
    text = compiled.as_text()
    for name in kernels:
        assert name in text, name
    mem = compiled.memory_analysis()
    pool = jax.tree.leaves((cache.k, cache.v))
    pool_bytes = sum(l.size * l.dtype.itemsize for l in pool)
    assert pool_bytes == slots * 4096 * (
        2 * serve["slot_positions"] + 3 * window_rows)
    assert mem.alias_size_in_bytes >= pool_bytes
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"laguna {step}: arguments {mem.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, held "
          f"{held / 1e9:.2f} GB")
    assert held < LG_LIMIT, held
