"""Compile checks for a TPU v5e that is described, not attached.

Every Pallas kernel on the main paths, at the widths of the models the
repo registers, goes through the TPU compiler here (Mosaic block tiling,
VMEM budget), plus one whole olmo-1b decode step whose device memory must
fit one 16 GB chip.  Nothing runs: a pass says the chip's compiler
accepts the program, not that it is right or fast.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler, and it skips where none can be
described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_arch
from repro.kernels import (decode_attention as dk, embedding_ops,
                           flash_attention, fused_adamw, grad_compress,
                           moe_router, ops, topk_sparsify, wkv6)
from repro.models import transformer as tf

HBM_BYTES = 16 * 10**9

# olmo-1b decode widths
B, H, D, S, BS = 8, 16, 128, 1024, 16
N_BLOCKS, NB = B * S // BS, S // BS
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _decode_args(chip, quant: bool, paged: bool):
    rows, lead = (BS, N_BLOCKS) if paged else (S, B)
    if quant:
        kv = [chip((lead, rows, H, D), I8), chip((lead, rows, H), F32),
              chip((lead, rows, H, D), I8), chip((lead, rows, H), F32)]
    else:
        kv = [chip((lead, rows, H, D), BF16), chip((lead, rows, H, D), BF16)]
    table = [chip((B, NB), I32)] if paged else []
    return [chip((B, 1, H, D), BF16)] + kv + table + [chip((B,), I32)]


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("variant", ["full", "window", "ring", "int8"])
def test_flash_decode_compiles(chip, variant, paged):
    mask = {"full": {}, "window": {"window": 256},
            "ring": {"window": 256, "ring": True}, "int8": {}}[variant]
    if variant == "int8":
        fn = (dk.flash_decode_attention_paged_quant if paged
              else dk.flash_decode_attention_quant)
    else:
        fn = (dk.flash_decode_attention_paged if paged
              else dk.flash_decode_attention)
    _compile(lambda *a: fn(*a, **mask),
             *_decode_args(chip, variant == "int8", paged))


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_flash_decode_op_carries_the_kernel_name(chip, paged, quant):
    """The kernel's device operation is named for the kernel, not for the
    jit that wraps it, so a profile finds it as ``flash_decode*``."""
    import re
    name = "flash_decode" + ("_paged" if paged else "") + \
        ("_int8" if quant else "")
    fn = {(False, False): dk.flash_decode_attention,
          (False, True): dk.flash_decode_attention_quant,
          (True, False): dk.flash_decode_attention_paged,
          (True, True): dk.flash_decode_attention_paged_quant}[paged, quant]

    def wrapper(*a):
        return fn(*a)

    text = _compile(wrapper, *_decode_args(chip, quant, paged)).as_text()
    ops_ = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                      text)
    assert [re.sub(r"(\.\d+)+$", "", o) for o in ops_] == [name], ops_


def test_flash_decode_spec_rows_compile(chip):
    """k-row speculative verification: q_lens prefetched per slot."""
    args = _decode_args(chip, False, True)
    args[0] = chip((B, 4, H, D), BF16)
    _compile(lambda *a: dk.flash_decode_attention_paged(*a[:-1],
                                                         q_lens=a[-1]),
             *args, chip((B,), I32))


def test_flash_attention_compiles(chip):
    qkv = chip((B, H, S, D), BF16)
    _compile(flash_attention.flash_attention, qkv, qkv, qkv)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_embedding_gather_compiles(chip, dtype):
    V = get_arch("recllm-base").padded_vocab           # 63232 rows
    _compile(embedding_ops.gather_rows, chip((V, 768), dtype),
             chip((256,), I32))


def test_embedding_scatter_add_compiles(chip):
    """The (V, D) output is tiled: VMEM holds one tile, not 194 MB."""
    V = get_arch("recllm-base").padded_vocab
    _compile(lambda x, i: embedding_ops.scatter_add_rows(x, i, V),
             chip((1000, 768), F32), chip((1000,), I32))


def test_onebit_compiles(chip):
    g = chip((8, 1 << 20), F32)
    packed, scales = jax.eval_shape(grad_compress.onebit_quantize, g)
    _compile(grad_compress.onebit_quantize, g)
    _compile(grad_compress.onebit_dequantize,
             chip(packed.shape, packed.dtype),
             chip(scales.shape, scales.dtype))


def test_topk_compiles(chip):
    _compile(lambda x: topk_sparsify.topk_sparsify(x, 32),
             chip((4099, 2048), F32))


def test_fused_adamw_compiles(chip):
    x = chip((2048 * 8192,), F32)
    _compile(lambda p, g, m, v: fused_adamw.adamw_update(
        p, g, m, v, lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1, bc1=0.1,
        bc2=0.05), x, x, x, x)


def test_wkv6_compiles(chip):
    r = chip((2, 32, 512, 64), BF16)                   # rwkv6-1.6b heads
    _compile(wkv6.wkv6_chunked, r, r, r, r, chip((32, 64), BF16))


def test_moe_router_compiles(chip):
    _compile(lambda x: moe_router.moe_router(x, 8),   # qwen3-moe: 128 x 8
             chip((4096, 128), F32))


def test_olmo_decode_step_fits_one_chip(chip, monkeypatch):
    """One whole olmo-1b decode step (dense cache, flash-decode kernel
    native) compiles and its program fits one chip's HBM."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_arch("olmo-1b")
    ctx = tf.ModelCtx(decode_impl="flash")
    on_chip = lambda t: jax.tree.map(                  # noqa: E731
        lambda x: chip(x.shape, x.dtype), t)
    params = on_chip(jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: tf.init_cache(cfg, B, S)))
    compiled = _compile(
        lambda p, c, t: tf.decode_step(cfg, p, c, t, ctx),
        params, cache, chip((B, 1), I32))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_paged_decode_step_updates_the_pool_in_place(chip, monkeypatch):
    """The serve deployment's paged olmo-1b decode step (64 slots x 512,
    2,048 blocks of 16, flash decode, slot state donated) writes its new
    K/V rows into the stacked pools in place: no temporary near a pool's
    2.15 GB, and no copy or (dynamic-)slice that materialises a pool, one
    layer of it, or the kernel's (L*N, ...) view of it."""
    import re
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_arch("olmo-1b")
    ctx = tf.ModelCtx(decode_impl="flash")
    on_chip = lambda t: jax.tree.map(                  # noqa: E731
        lambda x: chip(x.shape, x.dtype), t)
    params = on_chip(jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg)))
    state = on_chip(jax.eval_shape(lambda: tf.init_paged_slots(
        cfg, 64, 512, num_blocks=2048, block_size=16)))
    compiled = jax.jit(
        lambda p, c, t: tf.decode_step(cfg, p, c, t, ctx),
        donate_argnums=(1,)).lower(params, state,
                                   chip((64, 1), I32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    shape = r"= bf16\[(?:(?:16,)?2048|32768),16,16,128\]\{[^}]*\} "
    pool = re.compile(shape + r"(copy|dynamic-slice|dynamic-update-slice)\(")
    fusion = re.compile(shape + r"fusion\([^\n]*calls=%([\w.-]+)")
    moving = [m.group(0) for m in pool.finditer(text)]
    for m in fusion.finditer(text):
        body = text[text.index(f"%{m.group(1)} "):]
        body = body[:body.index("\n}")]
        if re.search(r"\b(copy|dynamic-slice|dynamic-update-slice)\(", body):
            moving.append(m.group(0))
    assert not moving, moving
