"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

Interpret mode (``tests/test_decode_kernel.py``) never checks TPU tiling
or memory, so these tests hand the served kernels and the param build to
the TPU compiler at ``onerec_v2.CONFIG`` widths.  Nothing runs: a pass
says the chip's compiler accepts the program, not what it computes.

The topology is described inside a module-scoped fixture — never while a
module is imported — because only one process at a time may load the TPU
library.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core.policy import PAPER_POLICY
from repro.core.ptq import quantized_leaf_programs
from repro.core.quant import (QuantizedTensor, fp8_grouped_matmul,
                              quantize_per_channel)
from repro.kernels.fp8_gemm.kernel import fp8_gemm_pallas
from repro.kernels.paged_decode.kernel import paged_decode_pallas
from repro.layers.moe import make_moe_spec
from repro.models import onerec as onerec_model

CFG = registry.get_arch("onerec-v2").CONFIG
PAGE = 32
SLOTS = 32
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("C", [1, 4], ids=["K1", "K4tree"])
@pytest.mark.parametrize("kv", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "fp8kv"])
def test_paged_decode_compiles(one_chip, kv, C):
    """The fused paged-decode kernel at head_dim 128, page 32, 32 slots,
    over the pool a CONFIG-width engine allocates."""
    t = CFG.transformer
    g = t.n_heads // t.n_kv_heads
    p_max = -(-(CFG.context_len + 1) // PAGE)
    n_pos = (SLOTS * p_max + 1) * PAGE
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    operands = [s((SLOTS, t.n_kv_heads, C * g, t.head_dim), jnp.bfloat16),
                s((n_pos, t.n_kv_heads, t.head_dim), kv),
                s((n_pos, t.n_kv_heads, t.head_dim), kv),
                s((n_pos // PAGE, PAGE), jnp.int32),
                s((SLOTS, p_max), jnp.int32),
                s((SLOTS,), jnp.int32), s((SLOTS,), jnp.int32)]
    if kv != jnp.bfloat16:               # per-(position, head) scales
        operands += [s((n_pos, t.n_kv_heads), jnp.float32)] * 2

    def step(q, k, v, pos, tables, lengths, starts, *scales):
        ks, vs = scales or (None, None)
        return paged_decode_pallas(q, k, v, pos, ks, vs, tables, lengths,
                                   starts, page_size=PAGE, group=g,
                                   branch_stride=CFG.decode_len - 1,
                                   scale=t.head_dim ** -0.5)

    compiled = jax.jit(step).lower(*operands).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fp8_gemm_compiles(one_chip):
    """The fp8 GEMM kernel at d_model x d_model (2048 x 2048)."""
    d = CFG.transformer.d_model
    wq = jax.eval_shape(lambda w: quantize_per_channel(w),
                        jax.ShapeDtypeStruct((d, d), jnp.bfloat16))
    x = _spec(one_chip, (SLOTS, d), jnp.bfloat16)
    w = _spec(one_chip, wq.data.shape, wq.data.dtype)
    sw = _spec(one_chip, (1, d), jnp.float32)
    compiled = jax.jit(lambda x, w, sw: fp8_gemm_pallas(
        x, w, sw, block_m=SLOTS, block_n=128,
        out_dtype=jnp.bfloat16)).lower(x, w, sw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("C", [8, 104], ids=["decode", "prefill"])
@pytest.mark.parametrize("proj", ["gate", "down"])
def test_moe_grouped_gemm_compiles(one_chip, proj, C):
    """The block-scaled expert GEMM at CONFIG widths, at the served
    decode capacity (32 slots) and a one-row prefill's: whichever form C
    picks, the compiled program holds no f32 array of the weight's size."""
    t = CFG.transformer
    E = make_moe_spec(t.n_experts, t.top_k, t.d_model, t.d_expert,
                      ep_degree=t.ep_degree).n_experts_padded
    K, N = ((t.d_model, t.d_expert) if proj == "gate"
            else (t.d_expert, t.d_model))
    x = _spec(one_chip, (E, C, K), jnp.bfloat16)
    data = _spec(one_chip, (E, K, N), jnp.float8_e4m3fn)
    scale = _spec(one_chip, (E, K // 128, N // 128), jnp.float32)
    compiled = jax.jit(lambda x, d, s: fp8_grouped_matmul(
        x, QuantizedTensor(d, s, "block"))).lower(x, data, scale).compile()
    # arrays the program writes to memory: the entry computation's results
    # (a fusion's f32 body stays in registers)
    entry = compiled.as_text().split("\nENTRY ")[1]
    sizes = [math.prod(int(n) for n in dims.split(",") if n)
             for dims in re.findall(r"= f32\[([\d,]*)\]", entry)]
    assert max(sizes) < E * K * N


def test_param_build_fits_one_chip(one_chip):
    """The served fp8 tree is built one leaf program at a time; while
    the last (largest) program runs, the leaves built before it, its
    output and its temporaries must fit one chip's 16 GiB."""
    key = _spec(one_chip, (2,), jnp.uint32)
    _, programs = quantized_leaf_programs(
        lambda k: onerec_model.init_onerec(k, CFG, jnp.bfloat16), key,
        PAPER_POLICY)
    sizes = [sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree_util.tree_leaves(
                     jax.eval_shape(program, key)))
             for _, program in programs]
    last = max(range(len(sizes)),
               key=lambda i: (sizes[i], i))  # the last of the largest
    mem = programs[last][1].lower(key).compile().memory_analysis()
    built_before = sum(sizes[:last])
    peak = (built_before + mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < HBM_BYTES, f"param build peaks at {peak / 2**30:.2f} GiB"
    # the quantized tree itself is well inside one chip
    assert sum(sizes) < HBM_BYTES // 2
