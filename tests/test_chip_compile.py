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

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mpit_tpu.ops import ring_collectives
from mpit_tpu.ops.decode_attention import (
    flash_decode_attention,
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


def _kv(shape, kv_dtype):
    """A K (or V) buffer spec: bf16 rows, or int8 rows + per-(row, head)
    f32 scales in the stored keepdims form."""
    if kv_dtype == "bf16":
        return _sds(shape, jnp.bfloat16)
    return QuantizedKV(
        q=_sds(shape, jnp.int8), scale=_sds((*shape[:-1], 1), jnp.float32)
    )


def test_flash_attention_fwd_bwd(v5e):
    qkv = _sds((B, S, H, D), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    text = _compile_on_chip(v5e, jax.grad(loss, (0, 1, 2)), qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


# T: one decode token, the CLI's prefill chunk, a spec_k=4 verify.
@pytest.mark.parametrize("t", [1, 64, 5])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_flash_decode_dense(v5e, kv_dtype, t):
    kv = _kv((B, S, H, D), kv_dtype)
    _compile_on_chip(
        v5e,
        lambda q, k, v, n: flash_decode_attention(
            q, k, v, n, interpret=False, return_visited=True
        ),
        _sds((B, t, H, D), jnp.bfloat16), kv, kv, _sds((B,), jnp.int32),
    )


@pytest.mark.parametrize("t", [1, 64, 5])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_flash_decode_paged(v5e, kv_dtype, t):
    pool = _kv((B * S // PAGE, PAGE, H, D), kv_dtype)
    _compile_on_chip(
        v5e,
        lambda q, k, v, n, bt: flash_paged_decode_attention(
            q, k, v, n, bt, interpret=False, return_visited=True
        ),
        _sds((B, t, H, D), jnp.bfloat16), pool, pool,
        _sds((B,), jnp.int32), _sds((B, S // PAGE), jnp.int32),
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
