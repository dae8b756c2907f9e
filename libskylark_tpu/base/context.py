"""Deterministic random context: global (seed, counter) state.

TPU-native analog of the reference's ``context_t`` (ref: base/context.hpp:19-194).
The reference hands out *counter ranges* of a virtual 2^64-long Threefry random
stream; any process can evaluate any element statelessly, which is what makes
sketches layout-independent and serializable.

``jax.random`` is itself a counter-based Threefry generator, so the mapping is
nearly 1:1 — but instead of a single flat 2^64 stream we hand out *allocation
subkeys*: allocation ``i`` of a context with seed ``s`` is the key
``fold_in(key(s), i)``. Within an allocation, element access is again a pure
function of (allocation key, element index) — see :mod:`libskylark_tpu.base.randgen`.
The (seed, counter) pair round-trips through JSON exactly like the reference's
ptree serialization (ref: base/context.hpp:86-98), and an allocation can be
reconstructed from (seed, counter) alone without the context object.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

import jax
import jax.random as jr


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
        # lazy: telemetry sits above base in the import order
        from libskylark_tpu.telemetry.trace import span

        with span("stream.key", {"what": "allocation",
                                 "path_len": len(self.path)}):
            k = jr.fold_in(jr.key(self.seed), self.counter)
            for p in self.path:
                k = jr.fold_in(k, p)
        return k

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
