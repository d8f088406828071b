"""sha256 of the lowered text of the steps the benchmark's cells run, AOT
for a described v5e (no chip needed): GPT-2 large's paged decode, chunk
(the full-batch step and the compacted one a count of participants) and
page-copy steps at the serving cells' shape, xing4's, glm_dsa's,
olmo_hybrid's and laguna's decode and compacted chunk steps at their cells' shapes, GPT-2 small's
train step over the 2x2. Two trees that print the same hashes run the same device
programs; a refactoring PR proves itself with

    python lowered_hashes.py            # from the root of each tree

The serialized Mosaic kernels carry the file path and line of every
traced operation, so the raw text differs between two checkouts of the
same code (and whenever a line above a kernel moves). By default the
kernels' debug locations are stripped before they are serialized;
``--raw`` hashes the text as ``.lower().as_text()`` gives it.
"""
import dataclasses
import hashlib
import importlib
import json
import os
import sys



def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    if "--raw" not in sys.argv[1:]:
        from jax._src import tpu_custom_call as _tcc
        from jaxlib.mlir.passmanager import PassManager as _PM

        _orig_asm = _tcc._lower_mosaic_module_to_asm

        def _stripped(module, **kw):
            with module.context, module.operation.location:
                clone = module.operation.clone()
                _PM.parse("builtin.module(strip-debuginfo)").run(clone)

            class _M:  # what _lower_mosaic_module_to_asm reads of a module
                context = module.context
                operation = clone

            return _orig_asm(_M, **kw)

        _tcc._lower_mosaic_module_to_asm = _stripped

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    sha = lambda t: hashlib.sha256(t.encode()).hexdigest()
    out = {}

    from mpit_tpu.ops import decode_attention
    decode_attention._use_kernel = lambda interpret: True
    from mpit_tpu.serve import Engine
    from mpit_tpu.serve.kvcache import PagedKVCache

    def full_cache(eng, pages):
        full = lambda bufs: tuple(
            jax.ShapeDtypeStruct((pages, *b.shape[1:]), b.dtype) for b in bufs)
        return PagedKVCache(k=full(eng.cache.k), v=full(eng.cache.v), lengths=eng.cache.lengths)

    # GPT-2 large, the cell's shape
    from mpit_tpu.models import GPT2, GPT2Config
    cfg = GPT2Config(vocab_size=50257, max_seq_len=1024, num_layers=36, num_heads=20, d_model=1280, d_ff=5120)
    params = jax.eval_shape(lambda: jax.tree.map(lambda a: a.astype(jnp.bfloat16),
        GPT2(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    S = 16
    eng = Engine(cfg, params, slots=S, max_len=1024, seed=1, kv_pages=2 * 64, kv_page_size=16, prefill_chunk=64)
    cache = full_cache(eng, S * 64)
    i32, f32, msk = jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.float32), jnp.zeros((S,), bool)
    bt, key = jnp.zeros((S, eng.pages_per_slot), jnp.int32), jax.random.key(0)
    args = (eng.params, cache, eng.last_token, msk, bt, key, f32, i32)
    out["gpt2l.jit_decode_paged"] = sha(eng._decode_paged_jit.lower(*on_chip(args)).as_text())
    toks = jnp.zeros((S, eng.prefill_chunk), jnp.int32)
    args = (eng.params, cache, eng.last_token, toks, i32, i32, i32, msk, bt, key, f32, i32)
    out["gpt2l.jit_prefill_paged"] = sha(eng._prefill_paged_jit.lower(*on_chip(args)).as_text())
    # The compacted chunk step, one a count of participants (a tree from before GPT-2 took it prints none)
    for n in eng._prefill_counts:
        z = jnp.zeros((n,), jnp.int32)
        args = (eng.params, cache, eng.last_token, z, jnp.zeros((n, eng.prefill_chunk), jnp.int32), z, z, z,
                jnp.zeros((n,), bool), bt, key, f32, i32)
        out[f"gpt2l.jit_prefill_paged.compact{n}"] = sha(eng._prefill_compact_jit.lower(*on_chip(args)).as_text())
    args = (cache, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    out["gpt2l.jit_copy_page"] = sha(eng._copy_page_jit.lower(*on_chip(args)).as_text())
    del eng

    # xing4, its cell's shape
    from mpit_tpu.models.xing4 import Xing4Config, init_params
    X_S, X_POS, X_PAGE, X_CHUNK = 32, 13312, 256, 2048
    xcfg = Xing4Config(num_hidden_layers=6, first_k_dense_replace=1, max_seq_len=X_POS)
    xparams = jax.eval_shape(lambda: init_params(xcfg, jax.random.key(0)))
    pps = X_POS // X_PAGE
    eng = Engine(xcfg, xparams, slots=X_S, max_len=X_POS, seed=1, kv_pages=2 * pps, kv_page_size=X_PAGE, prefill_chunk=X_CHUNK)
    cache = full_cache(eng, X_S * pps)
    s = X_S
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt = jnp.zeros((s, pps), jnp.int32)
    args = (eng.params, cache, eng.last_token, jnp.zeros((s,), bool), bt, key, f32, i32)
    out["xing4.jit_decode_paged"] = sha(eng._decode_paged_jit.lower(*on_chip(args)).as_text())
    for n in eng._prefill_counts:
        z = jnp.zeros((n,), jnp.int32)
        args = (eng.params, cache, eng.last_token, z, jnp.zeros((n, X_CHUNK), jnp.int32), z, z, z,
                jnp.zeros((n,), bool), bt, key, f32, i32)
        out[f"xing4.jit_prefill_paged.compact{n}"] = sha(eng._prefill_compact_jit.lower(*on_chip(args)).as_text())
    del eng

    # glm_dsa, its cell's shape (a tree from before the family prints nothing for it)
    try:
        from mpit_tpu.models import glm_dsa
    except ImportError:
        glm_dsa = None
    if glm_dsa is not None:
        G_S, G_POS, G_PAGE, G_CHUNK = 16, 36864, 256, 512
        gcfg = glm_dsa.GlmDsaConfig(
            num_hidden_layers=5, vocab_size=19360, mlp_layer_types=("dense",) + ("sparse",) * 4,
            indexer_types=("full", "shared", "shared", "shared", "full"), experts_held=tuple(range(16)),
            max_seq_len=G_POS)
        gparams = jax.eval_shape(lambda: glm_dsa.init_params(gcfg, jax.random.key(0)))
        pps = G_POS // G_PAGE
        eng = Engine(gcfg, gparams, slots=G_S, max_len=G_POS, seed=1, kv_pages=2 * pps, kv_page_size=G_PAGE,
                     prefill_chunk=G_CHUNK, sample_block=4840)
        seats = lambda bufs: tuple(
            b if b is None else jax.ShapeDtypeStruct((G_S * pps, *b.shape[1:]), b.dtype) for b in bufs)
        cache = dataclasses.replace(eng.cache, k=seats(eng.cache.k), v=seats(eng.cache.v), x=seats(eng.cache.x))
        i32, f32 = jnp.zeros((G_S,), jnp.int32), jnp.zeros((G_S,), jnp.float32)
        bt = jnp.zeros((G_S, pps), jnp.int32)
        args = (eng.params, cache, eng.last_token, jnp.zeros((G_S,), bool), bt, key, f32, i32)
        out["glm_dsa.jit_decode_paged"] = sha(eng._decode_paged_jit.lower(*on_chip(args)).as_text())
        for n in eng._prefill_counts:
            z = jnp.zeros((n,), jnp.int32)
            args = (eng.params, cache, eng.last_token, z, jnp.zeros((n, G_CHUNK), jnp.int32), z, z, z,
                    jnp.zeros((n,), bool), bt, key, f32, i32)
            out[f"glm_dsa.jit_prefill_paged.compact{n}"] = sha(
                eng._prefill_compact_jit.lower(*on_chip(args)).as_text())
        del eng

    # olmo_hybrid, its cell's shape (a tree from before the family prints nothing for it)
    try:
        from mpit_tpu.models import olmo_hybrid
    except ImportError:
        olmo_hybrid = None
    if olmo_hybrid is not None:
        O_S, O_POS, O_PAGE, O_CHUNK = 64, 4096, 128, 512
        ocfg = olmo_hybrid.OlmoHybridConfig(num_hidden_layers=8, max_seq_len=O_POS)
        oparams = jax.eval_shape(lambda: olmo_hybrid.init_params(ocfg, jax.random.key(0)))
        pps = O_POS // O_PAGE
        eng = Engine(ocfg, oparams, slots=O_S, max_len=O_POS, seed=1, kv_pages=2 * pps, kv_page_size=O_PAGE,
                     prefill_chunk=O_CHUNK, sample_block=7168)
        pool = lambda bufs: tuple(jax.ShapeDtypeStruct((O_S * pps, *b.shape[1:]), b.dtype) for b in bufs)
        cache = dataclasses.replace(eng.cache, k=pool(eng.cache.k), v=pool(eng.cache.v))
        i32, f32 = jnp.zeros((O_S,), jnp.int32), jnp.zeros((O_S,), jnp.float32)
        bt = jnp.zeros((O_S, pps), jnp.int32)
        args = (eng.params, cache, eng.last_token, jnp.zeros((O_S,), bool), bt, key, f32, i32)
        out["olmo_hybrid.jit_decode_paged"] = sha(eng._decode_paged_jit.lower(*on_chip(args)).as_text())
        for n in eng._prefill_counts:
            z = jnp.zeros((n,), jnp.int32)
            args = (eng.params, cache, eng.last_token, z, jnp.zeros((n, O_CHUNK), jnp.int32), z, z, z,
                    jnp.zeros((n,), bool), bt, key, f32, i32)
            out[f"olmo_hybrid.jit_prefill_paged.compact{n}"] = sha(
                eng._prefill_compact_jit.lower(*on_chip(args)).as_text())
        del eng

    # laguna, its cell's shape (a tree from before the family prints nothing for it)
    try:
        from mpit_tpu.models import laguna
    except ImportError:
        laguna = None
    if laguna is not None:
        L_S, L_POS, L_PAGE, L_CHUNK = 32, 21504, 256, 512
        lcfg = laguna.LagunaConfig(num_hidden_layers=5, vocab_size=25088, experts_held=tuple(range(64)),
                                   max_seq_len=L_POS)
        lparams = jax.eval_shape(lambda: laguna.init_params(lcfg, jax.random.key(0)))
        pps = L_POS // L_PAGE
        eng = Engine(lcfg, lparams, slots=L_S, max_len=L_POS, seed=1, kv_pages=2 * pps, kv_page_size=L_PAGE,
                     prefill_chunk=L_CHUNK, sample_block=6272)
        # The window layers' pool is sized by the slots, whatever kv_pages is: only the full layers' grows.
        pool = lambda bufs: tuple(
            jax.ShapeDtypeStruct((L_S * pps if b.shape[0] == 2 * pps else b.shape[0], *b.shape[1:]), b.dtype)
            for b in bufs)
        cache = dataclasses.replace(eng.cache, k=pool(eng.cache.k), v=pool(eng.cache.v))
        i32, f32 = jnp.zeros((L_S,), jnp.int32), jnp.zeros((L_S,), jnp.float32)
        bt = jnp.zeros((L_S, 2 * pps), jnp.int32)  # a table a lifetime, side by side
        args = (eng.params, cache, eng.last_token, jnp.zeros((L_S,), bool), bt, key, f32, i32)
        out["laguna.jit_decode_paged"] = sha(eng._decode_paged_jit.lower(*on_chip(args)).as_text())
        for n in eng._prefill_counts:
            z = jnp.zeros((n,), jnp.int32)
            args = (eng.params, cache, eng.last_token, z, jnp.zeros((n, L_CHUNK), jnp.int32), z, z, z,
                    jnp.zeros((n,), bool), bt, key, f32, i32)
            out[f"laguna.jit_prefill_paged.compact{n}"] = sha(
                eng._prefill_compact_jit.lower(*on_chip(args)).as_text())
        del eng

    # GPT-2 small train step, as benchmark/drivers/pretrain.py builds it, over the described 2x2 (the dp4 cell)
    from jax.sharding import PartitionSpec as P
    from mpit_tpu.asyncsgd.gpt2 import GPT2TrainConfig
    from mpit_tpu.opt import goo_adam, schedules
    from mpit_tpu.train import make_train_step
    from mpit_tpu.utils.aot import topology_world, abstractify
    _fa = importlib.import_module("mpit_tpu.ops.flash_attention")
    _fa._use_kernel = lambda interpret: True
    rows, seq = 64, 1024
    world = topology_world({"data": 4}, "v5e:2x2")
    tcfg = GPT2TrainConfig(vocab_size=50257, seq_len=seq, num_layers=12, num_heads=12, d_model=768,
                           flash=True, batch_size=rows, lr=3e-4, seed=1)
    mcfg = dataclasses.replace(tcfg.model_config(), max_seq_len=1024)
    gpt2 = GPT2(mcfg)
    def loss_fn(params, batch):
        return GPT2.fused_loss_fn(gpt2, params, batch["tokens"]), {}
    tx = goo_adam(schedules.from_config(tcfg), weight_decay=tcfg.weight_decay)
    init_fn, step_fn, state_specs = make_train_step(loss_fn, tx, world, zero1=True, grad_sync=tcfg.grad_sync,
                                          grad_bucket_mb=tcfg.grad_bucket_mb)
    p = jax.eval_shape(lambda: GPT2(mcfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    state = abstractify(jax.eval_shape(init_fn, p), world.mesh, state_specs(p))
    batch = abstractify({"tokens": jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32)}, world.mesh, P("data"))
    text = step_fn.build(p).lower(state, batch).as_text()
    out["gpt2s.jit_train_step.dp4"] = sha(text)
    out["gpt2s.jit_train_step.dp4.has_flash_kernel"] = "tpu_custom_call" in text
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
