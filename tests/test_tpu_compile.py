"""Compile the Pallas kernels for a described TPU v5e, with no chip.

The TPU compiler is installed with jaxlib, so a program can be compiled
for a ``v5e:2x2`` topology that is described, not attached. These tests
compile each kernel at the real widths ``chip_smoke.py`` runs on the
chip, with ``interpret=False``, and check that the program holds the
Mosaic kernel (``tpu_custom_call``); interpret-mode tests cannot see
what Mosaic refuses. The public ``ops`` wrappers must pick the compiled
kernel when they are lowered for a TPU.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around these
compiles (an entry compiled for an absent chip cannot be read back).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import hier_agg as ha
from repro.kernels import ops
from repro.kernels import wkv6

# the widths chip_smoke.py runs: the paper-scale flat bank (devices,
# parameters, edges) and the paper's CNN bank; qwen3-1.7b's attention
# heads at seq 4096; rwkv6-1.6b's wkv heads at seq 4096
AGG_SHAPES = [(64, 500_000, 8), (10, 21_840, 2)]
FLASH_Q, FLASH_KV = (1, 16, 4096, 128), (1, 8, 4096, 128)
WKV_SHAPE = (1, 4096, 32, 64)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,p,e", AGG_SHAPES)
def test_segment_agg_compiles(one_chip, n, p, e, dtype):
    text = _compiled_text(
        lambda b, w, s: ha.segment_agg(b, w, s, e, interpret=False),
        one_chip, ((n, p), dtype), ((n,), jnp.float32), ((n,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,p,e", AGG_SHAPES)
def test_segment_broadcast_compiles(one_chip, n, p, e, dtype):
    text = _compiled_text(
        lambda m, s: ha.segment_broadcast(m, s, interpret=False),
        one_chip, ((e, p), dtype), ((n,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles(one_chip, dtype):
    text = _compiled_text(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
        one_chip, (FLASH_Q, dtype), (FLASH_KV, dtype), (FLASH_KV, dtype))
    assert "tpu_custom_call" in text


def test_wkv6_compiles(one_chip):
    f32 = jnp.float32
    text = _compiled_text(
        lambda r, k, v, w, u: wkv6.wkv6_chunked(r, k, v, w, u,
                                                interpret=False),
        one_chip, *[(WKV_SHAPE, f32)] * 4, (WKV_SHAPE[2:], f32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", ["segment_agg", "segment_broadcast",
                                  "flash_attention", "wkv6"])
def test_ops_compile_on_tpu(one_chip, name):
    """The platform, not a flag, picks the mode: lowered for a TPU, the
    public wrappers hold the Mosaic kernel and no interpreter branch."""
    n, p, e = AGG_SHAPES[1]
    f32, i32 = jnp.float32, jnp.int32
    calls = {
        "segment_agg": (lambda b, w, s: ops.segment_agg(b, w, s, e),
                        [((n, p), f32), ((n,), f32), ((n,), i32)]),
        "segment_broadcast": (ops.segment_broadcast,
                              [((e, p), f32), ((n,), i32)]),
        "flash_attention": (ops.flash_attention,
                            [(FLASH_Q, f32), (FLASH_KV, f32),
                             (FLASH_KV, f32)]),
        "wkv6": (ops.wkv6, [(WKV_SHAPE, f32)] * 4 + [(WKV_SHAPE[2:], f32)]),
    }
    fn, shapes = calls[name]
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text
    assert "conditional" not in text


@pytest.mark.parametrize("name", ["segment_agg", "segment_broadcast"])
def test_aggregation_kernels_keep_their_names(one_chip, name):
    """A TPU trace names an operation after its HLO instruction: the
    Mosaic call of each aggregation kernel is ``<name>.N``, whatever
    the code around it is called."""
    n, p, e = AGG_SHAPES[1]
    f32, i32 = jnp.float32, jnp.int32
    fn, shapes = {
        "segment_agg": (lambda b, w, s: ops.segment_agg(b, w, s, e),
                        [((n, p), f32), ((n,), f32), ((n,), i32)]),
        "segment_broadcast": (ops.segment_broadcast,
                              [((e, p), f32), ((n,), i32)]),
    }[name]
    text = _compiled_text(fn, one_chip, *shapes)
    assert re.search(rf"%{name}(\.\d+)? = \S+ custom-call\(", text), name
