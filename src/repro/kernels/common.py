"""Shared kernel-dispatch helpers: backend detection, jit-cache shaping, and
the bounded jit cache every ops module keys its compiled executables on.

Every kernel ops module (bitset_jaccard, bitset_fold, seghist, …) keys its
jit cache on power-of-two padded shapes and defaults to Pallas interpret
mode off-TPU — one copy of those rules lives here. `LruCache` bounds the
caches: before it, every new padded shape leaked a compiled executable for
the life of the process (ISSUE 5).
"""
from __future__ import annotations

import os
from collections import OrderedDict


def default_interpret() -> bool:
    """Pallas kernels run interpreted everywhere except real TPU backends."""
    import jax  # lazy: LruCache consumers must import without jax installed

    return jax.default_backend() != "tpu"


def default_use_kernel() -> bool:
    """Dispatch policy for ops that ship BOTH a Pallas kernel and a compiled
    jnp twin (`kernels/bitset_fold`): the kernel on real TPU backends, the
    jnp twin elsewhere — interpret-mode Pallas is a correctness emulation,
    not a fast path, and the twins are integer-exact equals (test-enforced).
    ``REPRO_FORCE_PALLAS=1`` forces the kernel (the CI resident smoke runs
    it in interpret mode); ``=0`` forces the jnp twin."""
    import jax

    env = os.environ.get("REPRO_FORCE_PALLAS")
    if env is not None:
        return env.strip() not in ("", "0", "false", "False")
    return jax.default_backend() == "tpu"


def pow2(x: int, floor: int = 8) -> int:
    """Round up to a power of two (≥ floor) so jit caches stay small."""
    return max(floor, 1 << (max(1, x) - 1).bit_length())


def mesh_content_key(mesh):
    """Cache key by mesh CONTENT, not object identity: the engine builds a
    fresh mesh per run, and equivalent meshes must reuse executables."""
    if mesh is None:
        return None
    import numpy as np

    return (tuple(int(d.id) for d in np.asarray(mesh.devices).ravel()),
            tuple(mesh.axis_names), tuple(mesh.shape.values()))


def shard_map_no_check(fn, mesh, in_specs, out_specs):
    """shard_map with the varying-axes check disabled (pallas_call has no
    replication rule)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class LruCache:
    """Tiny LRU map for compiled executables, dict-compatible on the ops
    modules' ``cache.get(key)`` / ``cache[key] = fn`` usage.

    Compiled shard_map/pallas executables hold device buffers; an unbounded
    dict keyed on padded shapes grows for the life of the process as batch
    shapes drift across iterations. A small LRU keeps the hot shapes
    compiled and lets cold ones be rebuilt on the rare revisit.
    """

    def __init__(self, maxsize: int = 16):
        if maxsize < 1:
            raise ValueError("maxsize must be >= 1")
        self.maxsize = int(maxsize)
        self._d: OrderedDict = OrderedDict()

    def get(self, key, default=None):
        try:
            self._d.move_to_end(key)
        except KeyError:
            return default
        return self._d[key]

    def __setitem__(self, key, value):
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)

    def __getitem__(self, key):
        self._d.move_to_end(key)
        return self._d[key]

    def __contains__(self, key):
        return key in self._d

    def __len__(self):
        return len(self._d)

    def keys(self):
        return self._d.keys()

    def clear(self):
        self._d.clear()
