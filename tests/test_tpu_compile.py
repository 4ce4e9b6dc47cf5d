"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: misaligned blocks,
1-D gathers, unaligned dynamic slices, programs that do not fit HBM.  So
every registered kernel (plus the paged flash-decode kernel) is compiled
with ``interpret=False`` at a real width, and the qwen3-1.7b paged serve
steps at full width, for one chip of a described ``v5e:2x2`` topology.
Nothing here runs; ``chip_smoke.py`` runs the same path on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.kernels import registry as R
from repro.kernels.flash_decode import kernel as fdk
from repro.models import transformer
from repro.serve import engine as engine_mod

HBM_BYTES = 16 * 2**30  # one v5e chip

bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
B, KV, G, D, S, BS, C = 8, 8, 2, 128, 2048, 16, 64  # qwen3-1.7b attention
NB = S // BS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the topology, with the persistent compile cache off: a
    compile for a described chip is written to it but can never be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernel_case(name):
    """(call, operand shapes, static kwargs) at a real width."""
    pool = (1 + B * NB, BS, KV, D)
    table_shapes = [((B, NB), i32), ((B,), i32)]
    cases = {
        "gemm": (R.GEMM, [((4096, 4096), bf)] * 2, {}),
        "stream-copy": (R.STREAM_COPY, [((65536, 1024), f32)], {}),
        "stream-scale": (lambda a: R.STREAM_SCALE(a, 3.0, interpret=False),
                         [((65536, 1024), f32)], None),
        "stream-add": (R.STREAM_ADD, [((65536, 1024), f32)] * 2, {}),
        "stream-triad": (lambda a, b: R.STREAM_TRIAD(a, b, 3.0,
                                                     interpret=False),
                         [((65536, 1024), f32)] * 2, None),
        "spmv": (R.SPMV, [((16384, 8, 128), f32), ((16384, 8, 128), i32),
                          ((16384, 8), i32), ((131072,), f32)], {}),
        "spmv-fixed-width": (R.SPMV_FIXED,
                             [((16384, 8, 128), f32), ((16384, 8, 128), i32),
                              ((16384, 8), i32), ((131072,), f32)], {}),
        "jacobi2d": (R.JACOBI_STEP, [((4096, 4096), f32)], {}),
        "qc-gate": (R.RX_GATE, [((1 << 24,), f32)] * 2,
                    {"qubit": 10, "theta": 0.25}),
        "flash-decode": (R.FLASH_DECODE,
                         [((B, KV, G, D), bf), ((B, S, KV, D), bf),
                          ((B, S, KV, D), bf), ((B,), i32)], {}),
        "flash-prefill": (R.FLASH_PREFILL,
                          [((B, C, KV, G, D), bf), ((B, C, KV, D), bf),
                           ((B, C, KV, D), bf), (pool, bf), (pool, bf),
                           ((B, NB), i32), ((B,), i32)], {}),
        "flash-decode-paged": (
            lambda *a: fdk.flash_decode_paged(*a, interpret=False),
            [((B, KV, G, D), bf), (pool, bf), (pool, bf)] + table_shapes,
            None),
        "flash-decode-paged-int8": (
            lambda q, kp, vp, bt, vl, ks, vs: fdk.flash_decode_paged(
                q, kp, vp, bt, vl, k_scale=ks, v_scale=vs, interpret=False),
            [((B, KV, G, D), bf), (pool, jnp.int8), (pool, jnp.int8)]
            + table_shapes + [(pool[:2], f32)] * 2, None),
    }
    return cases[name]


KERNEL_CASES = sorted(R.KERNELS) + ["flash-decode-paged",
                                    "flash-decode-paged-int8"]


def test_every_registered_kernel_has_a_compile_case():
    for name in KERNEL_CASES:
        _kernel_case(name)  # raises KeyError for a kernel without a case
    assert set(R.KERNELS) <= set(KERNEL_CASES)


@pytest.mark.parametrize("name", KERNEL_CASES)
def test_kernel_compiles_for_v5e(name, one_chip):
    op, shapes, kw = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    if kw is None:  # a closure that already chose interpret=False
        lowered = jax.jit(op).lower(*args)
    else:
        lowered = op.lower(*args, interpret=False, **kw)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no Pallas kernel in the HLO"


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_qwen3_serve_step_fits_one_v5e(step, one_chip):
    cfg = configs.get_config("qwen3-1.7b")
    params = _on(jax.eval_shape(
        lambda k: transformer.init_lm(k, cfg), jax.random.PRNGKey(0)),
        one_chip)
    cache = _on(jax.eval_shape(
        lambda: transformer.init_paged_cache(cfg, B, S, BS, "bf16")), one_chip)
    vec = jax.ShapeDtypeStruct((B,), i32, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((B, NB), i32, sharding=one_chip)
    if step == "decode":
        fn = engine_mod._jit_decode_paged(cfg, BS, "bf16")
        tok = jax.ShapeDtypeStruct((B, 1), i32, sharding=one_chip)
        lowered = fn.lower(params, tok, cache, vec, tables)
    else:
        fn = engine_mod._jit_prefill_paged(cfg, BS, "bf16")
        tok = jax.ShapeDtypeStruct((B, C), i32, sharding=one_chip)
        lowered = fn.lower(params, tok, cache, vec, tables, vec)
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{step}: {total / 2**30:.2f} GiB"
