"""The four Pallas kernels compile for a described TPU v5e chip at real
widths, with no chip attached.

The only file that describes the chip: the topology is built inside a
module fixture (never at import, in ``skipif`` or in ``parametrize``), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.  The persistent compile cache is off
around these compiles: an entry compiled for a described chip cannot be
read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

DENSE = get_config("internlm2-1.8b")
MOE = get_config("qwen3-moe-30b-a3b")
REC = get_config("recurrentgemma-2b")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dt, sharding=one_chip)


def _kernel_cases(shape):
    H, K, D = DENSE.num_heads, DENSE.num_kv_heads, DENSE.head_dim
    W = REC.lru_width
    return {
        "flash_attention": (ops.flash_attention, (
            shape((1, 1024, H, D)), shape((1, 1024, K, D)),
            shape((1, 1024, K, D)))),
        "decode_attention": (ops.decode_attention, (
            shape((8, H, D)), shape((8, 2048, K, D)), shape((8, 2048, K, D)),
            shape((8,), jnp.int32))),
        # the calibration harness's geometry: 2 KV heads of width 64
        "decode_attention_2x64": (ops.decode_attention, (
            shape((3, 4, 64)), shape((3, 256, 2, 64)),
            shape((3, 256, 2, 64)), shape((3,), jnp.int32))),
        "moe_gemm": (ops.moe_gemm, (
            shape((8, 128, MOE.d_model)),
            shape((8, MOE.d_model, MOE.moe_d_ff)))),
        "rglru_scan": (ops.rglru_scan, (
            shape((4, 1024, W), jnp.float32), shape((4, 1024, W), jnp.float32),
            shape((4, W), jnp.float32))),
    }


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "decode_attention_2x64", "moe_gemm",
                                  "rglru_scan"])
def test_kernel_compiles_for_v5e(shape, name):
    kernel, args = _kernel_cases(shape)[name]
    compiled = kernel.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
