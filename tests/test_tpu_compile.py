"""Compile the summarizer's main-path Pallas kernels for a TPU v5e with no
chip attached: the compiler runs against a described v5e:2x2 topology and
refuses here what the chip would refuse (tiling, alignment, VMEM, dtypes),
which interpret mode cannot show.

The arenas pass the group size G through unpadded — the engine's size
buckets give 8…128, and the kernels accept any G up to 128, so odd and tiny
G are compiled too — and W, the packed-bitmap word count, is a power of
two. The serving kernel sees the fixed query-slot batch (or a short one)
and power-of-two interval/probe widths. The queue sweep of a group over
128 members is a jitted loop with no Pallas kernel: it is compiled at the
two oversized group buckets and at a narrow and a hub-wide column width,
and on each chip of the host, where the mesh path places it.

The mesh path shards the arenas' group axis over all four chips of the
v5e:2x2 host: the ranking (`topj_fn`) and the count-carrying fold are
compiled sharded with the Pallas kernel inside each shard, and the
proposal round, whose every-row evaluation under a mesh is the jnp
`ref.round_all`, compiles to one program per shard with no collective
between the chips.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler's library, so a call at collection time
would break the other test workers.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.bitset_fold.kernel import jaccard_topj_kernel
from repro.kernels.bitset_fold.ops import (fold_counts_fn, round_fn,
                                           sweep_fn, topj_fn)
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


@pytest.fixture(scope="module")
def four_chips(topo):
    """The host's four chips as the engine's 1-D data mesh, with the
    arenas' sharding (group axis over the chips) and a replicated one."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    return (mesh, NamedSharding(mesh, P("data")), NamedSharding(mesh, P()))


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


MESH_SHAPES = [(8, 2), (37, 32), (128, 512)]   # (G, W)
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


def _arena_specs(sharded, B, G, R, W):
    """The resident state the round and fold ops take, sharded as the
    mesh arena's `device_put` places it."""
    i32 = jnp.int32
    return ([_spec(sharded, (B, G, W), jnp.uint32),
             _spec(sharded, (B, G), jnp.int8), _spec(sharded, (B, G), jnp.int8),
             _spec(sharded, (B, G, R), i32), _spec(sharded, (B, R), i32)]
            + [_spec(sharded, (B, G), i32) for _ in range(6)])


@pytest.mark.parametrize("G,W", MESH_SHAPES)
def test_sharded_topj_compiles(four_chips, G, W):
    mesh, sharded, replicated = four_chips
    B, J, n_pad = 8, max(1, min(16, G - 1)), 64
    fn = topj_fn(B, G, W, J, n_pad, use_kernel=True, interpret=False,
                 mesh=mesh)
    compiled = fn.compile(_spec(sharded, (B, G, W), jnp.uint32),
                          _spec(sharded, (B, G), jnp.int8),
                          _spec(replicated, (n_pad, 2), jnp.int32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("G,W", MESH_SHAPES)
def test_sharded_round_compiles_without_collectives(four_chips, G, W):
    mesh, sharded, replicated = four_chips
    B, R, J = 8, 32 * W, max(1, min(16, G - 1))
    fn = round_fn(B, G, R, W, 64, J, J, height_bound=None, use_kernel=True,
                  interpret=False, mesh=mesh)
    compiled = fn.compile(*_arena_specs(sharded, B, G, R, W),
                          _spec(replicated, (), jnp.uint32))
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    # one shard of the group axis on each chip
    assert f"u32[{B // 4},{G},{W}]" in text


@pytest.mark.parametrize("G,W", MESH_SHAPES)
def test_sharded_fold_counts_compiles(four_chips, G, W):
    mesh, sharded, _ = four_chips
    B, R, P_pairs = 8, 32 * W, max(1, G // 2)
    fn = fold_counts_fn(B, G, R, W, P_pairs, use_kernel=True,
                        interpret=False, mesh=mesh)
    compiled = fn.compile(*_arena_specs(sharded, B, G, R, W),
                          _spec(sharded, (B, P_pairs, 3), jnp.int32))
    _assert_kernel(compiled)
    assert not [c for c in COLLECTIVES if c in compiled.as_text()]


@pytest.mark.parametrize("Rp", (1024, 16384))
@pytest.mark.parametrize("G", (256, 512))
def test_queue_sweep_compiles(one_chip, G, Rp):
    """The one-group sweep program at the arena's extracted shapes:
    ``Wp`` u32 words of 32 columns each, the bank path's pow2 widths."""
    Wp = Rp // 32
    fn = sweep_fn(G, Rp, Wp, 16, height_bound=None)
    i32 = jnp.int32
    compiled = fn.jitted.lower(
        _spec(one_chip, (1, G, Wp), jnp.uint32),
        _spec(one_chip, (1, G), jnp.int8),
        _spec(one_chip, (1, G, Rp), i32), _spec(one_chip, (1, Rp), i32),
        *[_spec(one_chip, (1, G), i32) for _ in range(6)],
        _spec(one_chip, (G,), i32), _spec(one_chip, (), jnp.uint32)).compile()
    assert " while(" in compiled.as_text()


@pytest.mark.parametrize("chip", (1, 2, 3))
def test_queue_sweep_compiles_on_each_chip_of_the_mesh(topo, chip):
    """On a mesh each oversized group's arena is placed whole on one of the
    host's chips, and its sweep program is compiled for that chip."""
    G, Rp = 512, 16384
    Wp = Rp // 32
    on = SingleDeviceSharding(topo.devices[chip])
    i32 = jnp.int32
    compiled = sweep_fn(G, Rp, Wp, 16, height_bound=2).jitted.lower(
        _spec(on, (1, G, Wp), jnp.uint32), _spec(on, (1, G), jnp.int8),
        _spec(on, (1, G, Rp), i32), _spec(on, (1, Rp), i32),
        *[_spec(on, (1, G), i32) for _ in range(6)],
        _spec(on, (G,), i32), _spec(on, (), jnp.uint32)).compile()
    assert " while(" in compiled.as_text()
    assert {d for sh in compiled.input_shardings[0]
            for d in sh.device_set} == {topo.devices[chip]}


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
