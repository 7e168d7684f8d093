"""The eight paged attention kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) never runs Mosaic, so a kernel
the chip's compiler refuses — a block shape that is neither tile-aligned
nor the whole array dim, a select between boolean vectors — passes there
and fails only on the chip.  These cases compile each kernel ahead of
time for a *described* v5e chip at the serving widths (bf16,
dialogpt-medium's 16 KV heads of 64 dims, 16-token pages; the decode
kernel also at dialogpt-large's 20 heads, its 1,601-page pool and 24
rows); nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under
pytest-xdist every worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H = HKV = 16          # dialogpt-medium: 16 heads, MHA
D = 64
BS = 16               # page size
NB = 577              # pool blocks: 8 rows x 64 table entries + trie + sentinel
NBT = 64              # table width: 1024-position rows
B = 8                 # decode / verify rows
C = 128               # prefill chunk
R = 2                 # fp ring-tail blocks of the int8 pool
HKV_L, NB_L, B_L = 20, 1601, 24   # dialogpt-large: heads, pool, rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 - any failure skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _cases(s):
    """kernel -> operand shapes, in each ops wrapper's argument order."""
    bf, i32, f32, i8 = jnp.bfloat16, jnp.int32, jnp.float32, jnp.int8
    pool, pool8 = s((NB, BS, HKV, D), bf), s((NB, BS, HKV, D), i8)
    scale = s((NB, BS, HKV), f32)
    tails = s((B, R * BS, HKV, D), bf)
    chunk = [s((1, C, H, D), bf), s((1, C, HKV, D), bf),
             s((1, C, HKV, D), bf)]
    packed = [s((1, B * C, H, D), bf), s((1, B * C, HKV, D), bf),
              s((1, B * C, HKV, D), bf)]
    bundle = [s((B, BS, H, D), bf), s((B, BS, HKV, D), bf),
              s((B, BS, HKV, D), bf)]
    tables, rows = s((B, NBT), i32), s((B,), i32)
    desc = s((4, B * C // BS), i32)
    row_tail = s((R * BS, HKV, D), bf)
    return {
        "decode": (ops.paged_decode_attention,
                   [s((B, 1, H, D), bf), pool, pool, tables, rows]),
        "decode_large": (ops.paged_decode_attention,
                         [s((B_L, 1, HKV_L, D), bf),
                          s((NB_L, BS, HKV_L, D), bf),
                          s((NB_L, BS, HKV_L, D), bf),
                          s((B_L, NBT), i32), s((B_L,), i32)]),
        "decode_int8": (ops.paged_decode_attention_quant,
                        [s((B, 1, H, D), bf), pool8, pool8, scale, scale,
                         tails, tails, tables, rows]),
        "prefill": (ops.paged_prefill_attention,
                    chunk + [pool, pool, s((NBT,), i32), s((), i32),
                             s((), i32)]),
        "prefill_int8": (ops.paged_prefill_attention_quant,
                         chunk + [pool8, pool8, scale, scale, row_tail,
                                  row_tail, s((NBT,), i32), s((), i32),
                                  s((), i32)]),
        "packed": (ops.paged_prefill_attention_packed,
                   packed + [pool, pool, tables, desc]),
        "packed_int8": (ops.paged_prefill_attention_packed_quant,
                        packed + [pool8, pool8, scale, scale, tails, tails,
                                  tables, desc]),
        "verify": (ops.paged_verify_attention,
                   bundle + [pool, pool, tables, rows]),
        "verify_int8": (ops.paged_verify_attention_quant,
                        bundle + [pool8, pool8, scale, scale, tails, tails,
                                  tables, rows]),
    }


KERNELS = ["decode", "decode_large", "decode_int8", "prefill", "prefill_int8", "packed",
           "packed_int8", "verify", "verify_int8"]


@pytest.mark.parametrize("kernel", KERNELS)
def test_paged_kernel_compiles_for_v5e(kernel, one_chip,
                                       no_persistent_cache):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _cases(s)[kernel]
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()    # the Mosaic kernel
    assert compiled.memory_analysis() is not None
