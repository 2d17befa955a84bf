"""The main path's Pallas kernels, compiled for a DESCRIBED v5e at real widths.

No chip is attached and nothing runs: libtpu's compiler lowers each kernel
for a ``v5e:2x2`` topology description and raises what the chip's compiler
would raise (block shapes the TPU lowering refuses, VMEM overflows).
Interpret-mode tests cannot see those — ``paged_decode_attention`` passed
every one of them while being refused at every real shape.

Kept in ONE file, with the topology described inside a module-scoped
fixture: only one process may hold libtpu, so the call must not happen at
import/collection time (every xdist worker imports every test file) and
must not be spread over files that can land on different workers.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dstack_tpu.ops import flash_attention as fa

#: (q heads, kv heads, head_dim, seq, batch): Llama-3.2-1B and Llama-3-8B
GEOMETRIES = {
    "1b": (32, 8, 64, 1024, 8),
    "8b": (32, 8, 128, 2048, 4),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def real_lowering():
    """Compile the kernels for real (the CPU backend would interpret them)
    and keep the persistent compilation cache out of it: an executable for
    a described device is written there but can never be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    saved_interpret = fa._interpret
    saved_cache = jax.config.jax_enable_compilation_cache
    fa._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    fa._interpret = saved_interpret
    jax.config.update("jax_enable_compilation_cache", saved_cache)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _qkv(geometry, sharding):
    hq, hkv, d, seq, b = GEOMETRIES[geometry]
    q = jax.ShapeDtypeStruct((b, seq, hq, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, seq, hkv, d), jnp.bfloat16,
                              sharding=sharding)
    return q, kv, kv


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_flash_forward_compiles(one_chip, real_lowering, geometry):
    text = _compiled_text(fa.flash_attention, *_qkv(geometry, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_flash_gradient_compiles(one_chip, real_lowering, geometry):
    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *_qkv(geometry, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pages", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_paged_decode_compiles(one_chip, real_lowering, geometry, pages):
    """Page size 32 (the server default), an 8-slot batch over a 1024-token
    span — the decode-window shapes `python -m dstack_tpu.serving.server
    --paged` dispatches."""
    hq, hkv, d, _, _ = GEOMETRIES[geometry]
    b, bs, span = 8, 32, 1024
    num_blocks = b * span // bs + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (num_blocks, bs, hkv, d)
    kv = (sds(pool, jnp.bfloat16) if pages == "bf16" else
          {"q": sds(pool, jnp.int8), "s": sds(pool[:-1], jnp.float32)})
    text = _compiled_text(
        fa.paged_decode_attention,
        sds((b, hkv, hq // hkv, d), jnp.bfloat16), kv, kv,
        sds((b, span // bs), jnp.int32), sds((b,), jnp.int32))
    assert "tpu_custom_call" in text
