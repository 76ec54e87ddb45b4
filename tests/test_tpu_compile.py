"""Compile the summarizer's main-path Pallas kernels for a TPU v5e with no
chip attached: the compiler runs against a described v5e:2x2 topology and
refuses here what the chip would refuse (tiling, alignment, VMEM, dtypes),
which interpret mode cannot show.

The arenas pass the group size G through unpadded — the engine's size
buckets give 8…128, and the kernels accept any G up to 128, so odd and tiny
G are compiled too — and W, the packed-bitmap word count, is a power of
two. The serving kernel sees the fixed query-slot batch (or a short one)
and power-of-two interval/probe widths.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler's library, so a call at collection time
would break the other test workers.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitset_fold.kernel import jaccard_topj_kernel
from repro.kernels.bitset_fold.ops import fold_counts_fn
from repro.kernels.interval_expand.kernel import interval_count_kernel

GROUPS = (2, 37, 128)
WORDS = (2, 32, 512)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one — keep it out of the cache entirely."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("W", WORDS)
@pytest.mark.parametrize("G", GROUPS)
def test_jaccard_topj_compiles(one_chip, G, W):
    J = max(1, min(16, G - 1))
    compiled = jax.jit(
        lambda bits, alive: jaccard_topj_kernel(bits, alive, J,
                                                interpret=False)
    ).lower(_spec(one_chip, (G, W), jnp.uint32),
            _spec(one_chip, (G, 1), jnp.int8)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("W", WORDS)
@pytest.mark.parametrize("G", GROUPS)
def test_bitset_fold_compiles(one_chip, G, W):
    """Through the count-carrying fold op the resident arena dispatches."""
    B, R = 2, 32 * W            # W words of 32 columns each
    P = max(1, G // 2)          # the arena's fixed per-group pair slots
    fn = fold_counts_fn(B, G, R, W, P, use_kernel=True, interpret=False)
    i32 = jnp.int32
    per_g = [_spec(one_chip, (B, G), i32) for _ in range(6)]
    compiled = fn.compile(
        _spec(one_chip, (B, G, W), jnp.uint32),
        _spec(one_chip, (B, G), jnp.int8), _spec(one_chip, (B, G), jnp.int8),
        _spec(one_chip, (B, G, R), i32), _spec(one_chip, (B, R), i32),
        *per_g, _spec(one_chip, (B, P, 3), i32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("B,E,P", [
    (256, 128, 128),     # the fixed slot batch at the narrowest widths
    (256, 1024, 4096),   # wide probe rows (a hub's neighbours)
    (256, 8192, 256),    # long interval lists
    (37, 128, 512),      # a short final batch: B pads to the sublane tile
    (2, 512, 128),
])
def test_interval_count_compiles(one_chip, B, E, P):
    compiled = jax.jit(
        lambda lo, hi, sg, pos: interval_count_kernel(lo, hi, sg, pos,
                                                      interpret=False)
    ).lower(*[_spec(one_chip, (B, E), jnp.int32) for _ in range(3)],
            _spec(one_chip, (B, P), jnp.int32)).compile()
    _assert_kernel(compiled)
