"""Compile-only guards for one TPU v5e chip.

The kernels of the serving path, and the whole paged decode steps of
``gemma-2b`` and ``granite-4.0-h-micro`` on the Pallas backend, are
compiled here for a v5e chip that is
described, not attached (``jax.experimental.topologies``): the chip's
compiler refuses misaligned tiles, scoped-VMEM overruns and programs that
do not fit its memory, none of which interpret mode sees.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import runtime
from repro.configs.gemma_2b import CONFIG
from repro.core.lora import init_adapters
from repro.kernels import ops
from repro.models.api import get_model

V5E_HBM = 16 * 2**30
SLOTS, BLOCK, BLOCKS_PER_SLOT, CHUNK = 8, 16, 34, 64   # chip_smoke's stream


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described v5e chip, with the kernels set to compile (as on TPU)
    and the persistent compilation cache off: an entry written for a chip
    cannot be read back without one.  JAX's in-memory caches are cleared
    on both sides so no CPU test reuses a trace made for the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(runtime, "interpret_mode", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pools(sharding, quant: bool):
    nb = 1 + SLOTS * BLOCKS_PER_SLOT
    kv, hd = CONFIG.n_kv_heads, CONFIG.resolved_head_dim
    dtype = jnp.int8 if quant else jnp.bfloat16
    pools = [_spec((nb, BLOCK, kv, hd), dtype, sharding)] * 2
    scales = [_spec((nb, BLOCK, kv), jnp.float32, sharding)] * 2
    return pools, (scales if quant else [None, None])


def _tables(sharding):
    return (_spec((SLOTS, BLOCKS_PER_SLOT), jnp.int32, sharding),
            _spec((SLOTS,), jnp.int32, sharding))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, quant):
    (kp, vp), (ks, vs) = _pools(one_chip, quant)
    q = _spec((SLOTS, 1, CONFIG.n_heads, CONFIG.resolved_head_dim),
              jnp.bfloat16, one_chip)
    _compile(lambda q, kp, vp, bt, ln, ks, vs: ops.paged_gqa_attention(
        q, kp, vp, bt, ln, k_scale=ks, v_scale=vs),
        q, kp, vp, *_tables(one_chip), ks, vs)


# (B, H, Kv, NB, MB) of the chip benchmark's two cells, head_dim 128
CELL_SHAPES = {"olmo-1b": (20, 16, 16, 1801, 128),
               "yi-6b-l16": (32, 32, 4, 3073, 96)}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_paged_decode_kernel_reads_the_pool_in_place(one_chip, cell, quant):
    """At the cells' shapes no instruction but the decode kernel's own
    custom call takes a whole pool as an operand: no transpose, copy or
    staging of the pool on the way in."""
    B, H, Kv, NB, MB = CELL_SHAPES[cell]
    pool = (NB, BLOCK, Kv, 128)
    dtype = jnp.int8 if quant else jnp.bfloat16
    kp = _spec(pool, dtype, one_chip)
    ks = _spec(pool[:3], jnp.float32, one_chip) if quant else None
    compiled = _compile(
        lambda q, kp, vp, bt, ln, ks, vs: ops.paged_gqa_attention(
            q, kp, vp, bt, ln, k_scale=ks, v_scale=vs),
        _spec((B, 1, H, 128), jnp.bfloat16, one_chip), kp, kp,
        _spec((B, MB), jnp.int32, one_chip), _spec((B,), jnp.int32, one_chip),
        ks, ks)
    readers = _readers(compiled, pool)
    assert readers and all(r.startswith("paged_attention.")
                           for r in readers), readers


def _shapes_and_lines(compiled):
    lines = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$",
                       compiled.as_text(), re.M)
    dims = {name: tuple(int(d) for d in m.group(1).split(","))
            for name, rest in lines
            if (m := re.match(r"\(?\w+\[([\d,]+)\]", rest))}
    return dims, lines


def _readers(compiled, shape):
    """The instructions that take an array of ``shape`` as an operand."""
    dims, lines = _shapes_and_lines(compiled)
    return {name for name, rest in lines
            if any(dims.get(o) == shape
                   for o in re.findall(r"%([\w.\-]+)", rest))}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kv", [1, 2, 3, 6, 12])
def test_paged_decode_kernel_compiles_at_any_kv_head_count(one_chip, kv,
                                                            quant):
    """``dma_heads`` pads a KV axis that cannot DMA as it lies: every
    head count compiles, padded or not."""
    nb, mb = 1 + SLOTS * 16, 16
    dtype = jnp.int8 if quant else jnp.bfloat16
    kp = _spec((nb, BLOCK, kv, 128), dtype, one_chip)
    ks = _spec((nb, BLOCK, kv), jnp.float32, one_chip) if quant else None
    _compile(lambda q, kp, vp, bt, ln, ks, vs: ops.paged_gqa_attention(
        q, kp, vp, bt, ln, k_scale=ks, v_scale=vs),
        _spec((SLOTS, 1, 2 * kv, 128), jnp.bfloat16, one_chip), kp, kp,
        _spec((SLOTS, mb), jnp.int32, one_chip),
        _spec((SLOTS,), jnp.int32, one_chip), ks, ks)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_prefill_kernel_compiles_for_v5e(one_chip, quant):
    (kp, vp), (ks, vs) = _pools(one_chip, quant)
    hd = CONFIG.resolved_head_dim
    q = _spec((SLOTS, CHUNK, CONFIG.n_heads, hd), jnp.bfloat16, one_chip)
    kn = _spec((SLOTS, CHUNK, CONFIG.n_kv_heads, hd), jnp.bfloat16, one_chip)
    bt, ln = _tables(one_chip)
    _compile(lambda q, k, v, kp, vp, bt, ln, nn, ks, vs:
             ops.paged_prefill_gqa_attention(q, k, v, kp, vp, bt, ln, nn,
                                             k_scale=ks, v_scale=vs)[0],
             q, kn, kn, kp, vp, bt, ln, ln, ks, vs)


@pytest.mark.parametrize("kernel", ["paged_attention",
                                    "paged_prefill_attention"])
def test_kernel_instructions_keep_their_wrapper_names(one_chip, kernel):
    """The chip benchmark finds each paged kernel by its HLO instruction
    name (``%paged_attention.<n>``): the kernel's own ``name=`` must leave
    that name as it is."""
    (kp, vp), _ = _pools(one_chip, False)
    hd, H, Kv = (CONFIG.resolved_head_dim, CONFIG.n_heads,
                 CONFIG.n_kv_heads)
    bt, ln = _tables(one_chip)
    if kernel == "paged_attention":
        q = _spec((SLOTS, 1, H, hd), jnp.bfloat16, one_chip)
        compiled = _compile(lambda q, kp, vp, bt, ln: ops.paged_gqa_attention(
            q, kp, vp, bt, ln), q, kp, vp, bt, ln)
    else:
        q = _spec((SLOTS, CHUNK, H, hd), jnp.bfloat16, one_chip)
        kn = _spec((SLOTS, CHUNK, Kv, hd), jnp.bfloat16, one_chip)
        compiled = _compile(
            lambda q, k, v, kp, vp, bt, ln, nn:
            ops.paged_prefill_gqa_attention(q, k, v, kp, vp, bt, ln, nn)[0],
            q, kn, kn, kp, vp, bt, ln, ln)
    calls = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", compiled.as_text(), re.M)
    assert calls and all(c.startswith(kernel + ".") for c in calls), calls


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_batched_lora_dense_compiles_for_v5e(one_chip, quant):
    d, ff, C, r = CONFIG.d_model, CONFIG.d_ff, SLOTS, CONFIG.lora_rank
    bank_dtype = jnp.int8 if quant else jnp.float32
    bank = {"a": _spec((C, d, r), bank_dtype, one_chip),
            "b": _spec((C, r, ff), bank_dtype, one_chip)}
    if quant:
        bank["a_scale"] = bank["b_scale"] = _spec((C,), jnp.float32,
                                                  one_chip)
    _compile(lambda x, w, bank, ids: ops.batched_lora_dense(
        x, w, bank, ids, 2.0),
        _spec((SLOTS, 1, d), jnp.bfloat16, one_chip),
        _spec((d, ff), jnp.bfloat16, one_chip), bank,
        _spec((SLOTS,), jnp.int32, one_chip))


def test_gemma_2b_decode_step_compiles_for_v5e(one_chip):
    """The whole paged decode step at published widths: bf16 weights, an
    8-client adapter bank and the full-residency pool, Pallas backend."""
    model = get_model(CONFIG)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    # bank leaves grow a client axis after the period axis
    bank = jax.tree.map(
        lambda s: _spec((s.shape[0], SLOTS) + s.shape[1:], s.dtype,
                        one_chip),
        jax.eval_shape(lambda k: init_adapters(k, CONFIG),
                       jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_paged_decode_cache(
        SLOTS, 1 + SLOTS * BLOCKS_PER_SLOT, BLOCK)))
    bt, ln = _tables(one_chip)
    compiled = _compile(
        lambda p, c, tok, pos, bank, ids, bt: model.decode_step(
            p, c, tok, pos, adapters=bank, lora_scale=2.0, adapter_ids=ids,
            block_tables=bt, paged_backend="pallas"),
        params, cache, _spec((SLOTS, 1), jnp.int32, one_chip), ln, bank,
        _spec((SLOTS,), jnp.int32, one_chip), bt)
    assert compiled.memory_analysis().argument_size_in_bytes < V5E_HBM


# the granite-4.0-h-micro cell: 16 slots, 32 query and 8 KV heads of 64,
# a 4096-token context in blocks of 16, prefill chunks of 128; its bf16
# pools pack two heads to a 128-lane row (``layers.kv_pack``)
GRANITE = dict(B=16, H=32, Kv=8, hd=64, NB=4097, MB=256, T=128)


def test_granite_pools_are_read_in_place(one_chip):
    """At the granite cell's shapes the decode kernel alone takes the
    packed pool as an operand, and neither wrapper pads it to 128 lanes
    (which, at head size 64, would copy the pool on every call).  The
    prefill kernel's head-major transpose of the pool remains (ROADMAP
    queue 1)."""
    g = GRANITE
    from repro.models.layers import kv_pack
    f = kv_pack(g["Kv"], g["hd"])
    assert f == 2
    pool = (g["NB"], BLOCK, g["Kv"] // f, f * g["hd"])
    padded = (g["NB"], BLOCK, g["Kv"], 128)
    kp = _spec(pool, jnp.bfloat16, one_chip)
    bt = _spec((g["B"], g["MB"]), jnp.int32, one_chip)
    ln = _spec((g["B"],), jnp.int32, one_chip)
    dec = _compile(lambda q, kp, vp, bt, ln: ops.paged_gqa_attention(
        q, kp, vp, bt, ln, scale=1 / 64),
        _spec((g["B"], 1, g["H"], g["hd"]), jnp.bfloat16, one_chip),
        kp, kp, bt, ln)
    readers = _readers(dec, pool)
    assert readers and all(r.startswith("paged_attention.")
                           for r in readers), readers
    kn = _spec((g["B"], g["T"], g["Kv"], g["hd"]), jnp.bfloat16, one_chip)
    pre = _compile(
        lambda q, k, v, kp, vp, bt, ln, nn: ops.paged_prefill_gqa_attention(
            q, k, v, kp, vp, bt, ln, nn, scale=1 / 64)[0],
        _spec((g["B"], g["T"], g["H"], g["hd"]), jnp.bfloat16, one_chip),
        kn, kn, kp, kp, bt, ln, ln)
    for compiled in (dec, pre):
        dims, _ = _shapes_and_lines(compiled)
        assert padded not in dims.values()


def test_granite_decode_step_compiles_for_v5e(one_chip):
    """The whole paged decode step of granite-4.0-h-micro at published
    widths: 36 Mamba-2 layers' float32 state for 16 slots, the 4 NoPE GQA
    layers' packed pools, bf16 weights and a 16-client adapter bank,
    Pallas backend."""
    from repro.configs.granite_4p0_h_micro import CONFIG as GCFG

    g = GRANITE
    model = get_model(GCFG)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    bank = jax.tree.map(
        lambda s: _spec((s.shape[0], g["B"]) + s.shape[1:], s.dtype,
                        one_chip),
        jax.eval_shape(lambda k: init_adapters(k, GCFG),
                       jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_paged_decode_cache(
        g["B"], g["NB"], BLOCK)))
    assert cache["blocks"]["b0"]["h"].shape == (4, g["B"], 64, 64, 128)
    ln = _spec((g["B"],), jnp.int32, one_chip)
    compiled = _compile(
        lambda p, c, tok, pos, bank, ids, bt: model.decode_step(
            p, c, tok, pos, adapters=bank, lora_scale=2.0, adapter_ids=ids,
            block_tables=bt, paged_backend="pallas"),
        params, cache, _spec((g["B"], 1), jnp.int32, one_chip), ln, bank,
        ln, _spec((g["B"], g["MB"]), jnp.int32, one_chip))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM
