"""Deterministic random context: global (seed, counter) state.

TPU-native analog of the reference's ``context_t`` (ref: base/context.hpp:19-194).
The reference hands out *counter ranges* of a virtual 2^64-long Threefry random
stream; any process can evaluate any element statelessly, which is what makes
sketches layout-independent and serializable.

Instead of a single flat 2^64 stream we hand out *allocation subkeys*:
allocation ``i`` of a context with seed ``s`` is the Threefry key
``fold_in(root(s), i)``, and each element of a nested ``path`` is folded in
after it. The definition is :mod:`libskylark_tpu.base.threefry`'s —
``seed_words`` and ``fold_in_words``, plain integer arithmetic on the host —
and not ``jax.random``'s: a JAX release cannot move a serialized sketch's
bits. ``jax.random`` is only the container of the typed key that
:attr:`Allocation.key` returns (bit-equal to ``jax.random.fold_in(
jax.random.key(s), i)`` on the installed JAX, tests/test_allocation_key.py).
Within an allocation, element access is again a pure function of
(allocation key, element index) — see :mod:`libskylark_tpu.base.randgen`.
The (seed, counter) pair round-trips through JSON exactly like the reference's
ptree serialization (ref: base/context.hpp:86-98), and an allocation can be
reconstructed from (seed, counter) alone without the context object.

An allocation's key is derived once a process and kept (:func:`_material`):
an apply of a long-lived transform issues no device dispatch for its key.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np

from libskylark_tpu.base import threefry as tf

# Key material by (seed, counter, path), outside the dataclass: equal
# allocations share it, and ==, hash, to_dict, replace and pickling see
# fields only. Values are idempotent, so a hit takes no lock and two
# threads that miss together both store the same thing. Emptied when full:
# a solver loop allocates a fresh slot a solve, and a long-lived transform
# then pays one derivation (tens of microseconds) every _KEY_CACHE_SIZE.
_KEY_CACHE_SIZE = 4096
_KEY_CACHE: dict = {}


class _KeyMaterial(NamedTuple):
    """One allocation's key: the host words and, from the first use on the
    device, the raw (2,) uint32 array and the typed key around it. Never
    mutated: the device use stores a new one."""

    words: np.ndarray
    data: Optional[jax.Array] = None
    key: Optional[jax.Array] = None


@functools.cache
def _telemetry():
    # lazy: telemetry sits above base in the import order
    from libskylark_tpu.telemetry import metrics, trace

    return trace.span, metrics.counter(
        "stream.key_cache",
        "accesses of an allocation's key material, by result (hit | miss)")


def _derive(ident: tuple, m: Optional[_KeyMaterial],
            device: bool) -> _KeyMaterial:
    """The miss: replay the cipher on the host (``m``: what the cache held,
    the words without the device arrays, or None) and, for ``device``, put
    the words on the device. Under a caller's trace the arrays are still
    built eagerly (``ensure_compile_time_eval``): no tracer is kept."""
    if m is None:
        seed, counter, path = ident
        kd = tf.fold_in_words(tf.seed_words(seed), counter)
        for p in path:
            kd = tf.fold_in_words(kd, p)
        words = np.array(kd, dtype=np.uint32)
        words.flags.writeable = False
        m = _KeyMaterial(words)
    if device:
        with jax.ensure_compile_time_eval():
            data = jnp.asarray(m.words)
            key = jr.wrap_key_data(data, impl="threefry2x32")
        m = _KeyMaterial(m.words, data, key)
        if isinstance(key, jax.core.Tracer):
            return m
    if len(_KEY_CACHE) >= _KEY_CACHE_SIZE:
        _KEY_CACHE.clear()
    _KEY_CACHE[ident] = m
    return m


def _material(alloc: "Allocation", device: bool) -> _KeyMaterial:
    """``alloc``'s key material, from the cache where it is there; one
    ``stream.key`` span (attribute ``cached``) and one count of
    ``stream.key_cache{result}`` an access."""
    span, accesses = _telemetry()
    with span("stream.key", {"what": "allocation",
                             "path_len": len(alloc.path)}) as sp:
        ident = (alloc.seed, alloc.counter, tuple(alloc.path))
        m = _KEY_CACHE.get(ident)
        cached = m is not None and (not device or m.key is not None)
        if not cached:
            m = _derive(ident, m, device)
        if sp is not None:
            sp.set_attr("cached", cached)
    accesses.inc_always(result="hit" if cached else "miss")
    return m


@dataclasses.dataclass(frozen=True)
class Allocation:
    """A reserved slot of the context's random space.

    Reconstructible from (seed, counter) alone — this pair is what sketch
    transforms serialize as their ``creation_context``
    (ref: sketch/sketch_transform_data.hpp:64-71). ``path`` supports nested
    sub-allocations for compound transforms (e.g. PPT's internal CWTs): each
    element is folded into the key in order.
    """

    seed: int
    counter: int
    path: tuple = ()

    @property
    def key(self) -> jax.Array:
        """The allocation's typed (threefry) key, built once a process."""
        return _material(self, device=True).key

    @property
    def key_data(self) -> jax.Array:
        """``jax.random.key_data(self.key)`` — the (2,) uint32 key words on
        the device, built once a process: what a compiled apply takes as its
        argument."""
        return _material(self, device=True).data

    @property
    def key_words(self) -> np.ndarray:
        """The same two words as a read-only host array; no device
        involved."""
        return _material(self, device=False).words

    def child(self, tag: int) -> "Allocation":
        return Allocation(self.seed, self.counter, self.path + (int(tag),))

    def to_dict(self) -> dict[str, Any]:
        d = {"seed": int(self.seed), "counter": int(self.counter)}
        if self.path:
            d["path"] = list(self.path)
        return d

    @staticmethod
    def from_dict(d: dict[str, Any]) -> "Allocation":
        return Allocation(
            int(d["seed"]), int(d["counter"]), tuple(d.get("path", ()))
        )


class Context:
    """Global deterministic RNG state = (seed, counter).

    ``allocate()`` reserves the next slot of the virtual random space and
    advances the counter (ref: base/context.hpp:130-137,
    ``allocate_random_samples_array``). Like the reference, allocation must be
    performed consistently across any cooperating processes to keep state
    synchronized — in JAX SPMD this is automatic because the context lives in
    the single Python program driving the mesh.
    """

    def __init__(self, seed: int = 0, counter: int = 0):
        self._seed = int(seed)
        self._counter = int(counter)

    @property
    def seed(self) -> int:
        return self._seed

    @property
    def counter(self) -> int:
        return self._counter

    def allocate(self) -> Allocation:
        """Reserve the next allocation slot; advances the counter."""
        alloc = Allocation(self._seed, self._counter)
        self._counter += 1
        return alloc

    def random_value(self, sampler, **kwargs):
        """Draw a single host-side sample (ref: base/context.hpp ``random_value``)."""
        alloc = self.allocate()
        return sampler(alloc.key, **kwargs)

    # -- serialization (ptree-compatible JSON; ref: base/context.hpp:86-98) --

    def to_dict(self) -> dict[str, Any]:
        return {
            "skylark_object_type": "context",
            "seed": self._seed,
            "counter": self._counter,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    @staticmethod
    def from_dict(d: dict[str, Any]) -> "Context":
        return Context(int(d["seed"]), int(d.get("counter", 0)))

    @staticmethod
    def from_json(s: str) -> "Context":
        return Context.from_dict(json.loads(s))

    def __repr__(self) -> str:
        return f"Context(seed={self._seed}, counter={self._counter})"
