"""Virtual-processor contexts: allocator, layout, store and views.

The thesis stores each virtual processor's memory (its *context*, size μ) in
external memory and swaps it into one of ``k`` partitions.  PEMS2 replaces the
bump allocator of PEMS1 with offset/size records and a free list so memory can
be freed and reused, and so swapping touches only *live* bytes (§6.6).

JAX arrays have static shapes, so allocation happens at trace time: a
:class:`Allocator` hands out word offsets inside the context, and a
:class:`ContextLayout` maps field names to ``(offset, shape, dtype)``.  The
whole population of contexts is a single ``[v, mu_words]`` array (the
:class:`ContextStore`) that can be sharded over a mesh axis — that array *is*
the external memory.  4-byte word granularity keeps bitcasts exact for
float32/int32/uint32 payloads (the BSP applications' element types).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

WORD = 4  # bytes per store word

_SUPPORTED = {
    jnp.dtype("float32"), jnp.dtype("int32"), jnp.dtype("uint32"),
}


# --------------------------------------------------------------------------- #
# Allocator (§6.6)                                                             #
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class _Chunk:
    offset: int
    size: int


class Allocator:
    """First-fit free-list allocator with merge-on-free (thesis §6.6).

    Offsets/sizes are in words.  ``live_words`` lets the swap engine move only
    allocated bytes, reproducing the PEMS2 "swap only allocated regions"
    optimisation.
    """

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._free: List[_Chunk] = [_Chunk(0, self.capacity)]
        self._allocated: Dict[int, int] = {}  # offset -> size

    def alloc(self, size: int) -> int:
        size = int(size)
        if size <= 0:
            raise ValueError(f"allocation size must be positive, got {size}")
        # First fit, scanning from the lowest address (§6.6).
        for i, chunk in enumerate(self._free):
            if chunk.size >= size:
                offset = chunk.offset
                if chunk.size == size:
                    self._free.pop(i)
                else:
                    chunk.offset += size
                    chunk.size -= size
                self._allocated[offset] = size
                return offset
        raise MemoryError(
            f"context exhausted: requested {size} words, "
            f"free={self.free_words} of {self.capacity}"
        )

    def free(self, offset: int) -> None:
        size = self._allocated.pop(offset, None)
        if size is None:
            raise ValueError(f"free of unallocated offset {offset}")
        # Insert sorted and merge with adjacent free chunks.
        new = _Chunk(offset, size)
        idx = 0
        while idx < len(self._free) and self._free[idx].offset < offset:
            idx += 1
        self._free.insert(idx, new)
        self._merge(idx)
        if idx > 0:
            self._merge(idx - 1)

    def _merge(self, i: int) -> None:
        while i + 1 < len(self._free):
            a, b = self._free[i], self._free[i + 1]
            if a.offset + a.size == b.offset:
                a.size += b.size
                self._free.pop(i + 1)
            else:
                break

    @property
    def live_words(self) -> int:
        return sum(self._allocated.values())

    @property
    def free_words(self) -> int:
        return self.capacity - self.live_words

    @property
    def n_free_chunks(self) -> int:
        """Fragmentation indicator."""
        return len(self._free)


# --------------------------------------------------------------------------- #
# Layout                                                                       #
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    shape: Tuple[int, ...]
    dtype: jnp.dtype

    @property
    def words(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1


class ContextLayout:
    """Named fields inside a context, placed by the allocator."""

    def __init__(self, capacity_words: Optional[int] = None):
        self._fields: Dict[str, Tuple[int, Field]] = {}
        self._capacity = capacity_words
        self._alloc: Optional[Allocator] = (
            Allocator(capacity_words) if capacity_words else None
        )
        self._next = 0  # bump fallback when capacity unknown

    def add(self, name: str, shape: Sequence[int], dtype=jnp.float32) -> "ContextLayout":
        dtype = jnp.dtype(dtype)
        if dtype not in _SUPPORTED:
            raise TypeError(f"context fields must be 4-byte dtypes, got {dtype}")
        if name in self._fields:
            raise ValueError(f"duplicate field {name!r}")
        f = Field(name, tuple(int(s) for s in shape), dtype)
        if f.words == 0:
            # A zero-dim shape would make field_words() == 0 while the
            # allocator hands out ≥ 1 word, desynchronising the ledger's byte
            # counts from Allocator.live_words.  Reject it outright.
            raise ValueError(
                f"field {name!r} has zero size (shape {f.shape}); "
                "context fields must occupy at least one word"
            )
        if self._alloc is not None:
            off = self._alloc.alloc(f.words)
        else:
            off = self._next
            self._next += f.words
        self._fields[name] = (off, f)
        return self

    def drop(self, name: str) -> "ContextLayout":
        """Free a field (its words become reusable — §6.6)."""
        off, _ = self._fields.pop(name)
        if self._alloc is not None:
            self._alloc.free(off)
        return self

    def offset(self, name: str) -> int:
        return self._fields[name][0]

    def field(self, name: str) -> Field:
        return self._fields[name][1]

    def field_words(self, name: str) -> int:
        return self._fields[name][1].words

    def field_bytes(self, name: str) -> int:
        return self.field_words(name) * WORD

    @property
    def names(self) -> List[str]:
        return list(self._fields)

    @property
    def words(self) -> int:
        """Context size in words (μ / 4).  With an allocator this is the fixed
        capacity; otherwise the high-water mark of the bump pointer."""
        if self._capacity is not None:
            return self._capacity
        return max(self._next, 1)

    @property
    def live_words(self) -> int:
        if self._alloc is not None:
            return self._alloc.live_words
        return sum(f.words for _, f in self._fields.values())

    @property
    def mu_bytes(self) -> int:
        """μ: the context size in bytes."""
        return self.words * WORD

    @property
    def live_bytes(self) -> int:
        return self.live_words * WORD

    def live_word_index(self) -> Optional[np.ndarray]:
        """Sorted word offsets of every *live* (field-allocated) word, or
        ``None`` when the whole context is live — the common bump-layout
        case, where callers can skip the gather/scatter entirely.

        This is what lets the backing-tier swap engine move only allocated
        bytes (PEMS2 §6.6): a layout with freed holes swaps ``live_words``
        words per context, not ``words``.
        """
        if self.live_words == self.words:
            return None
        return field_word_index(self, self.names)


def field_word_index(layout_: ContextLayout,
                     names: Sequence[str]) -> np.ndarray:
    """Union of the named fields' word ranges, sorted — the monotone
    gather/scatter index for sliced and live-word swaps."""
    ranges = [
        np.arange(layout_.offset(n), layout_.offset(n) + layout_.field_words(n))
        for n in names
    ]
    return np.unique(np.concatenate(ranges)) if ranges else np.arange(0)


def layout(fields: Iterable[Tuple[str, Sequence[int], object]],
           capacity_words: Optional[int] = None) -> ContextLayout:
    lo = ContextLayout(capacity_words)
    for name, shape, dtype in fields:
        lo.add(name, shape, dtype)
    return lo


def row_major(x):
    """Pin every array of the pytree ``x`` to the row-major layout.

    XLA picks the physical layout of every value in a jitted program.  On
    TPU it may give a ``[v, words]`` store, or a ``[k, words]`` round block,
    the transposed layout that a small consumer prefers (a few-word field
    slice, a batched sort): the short axis then lands in the 128-wide lane
    dimension and the whole array is copied 8-32x padded.  Pinning the
    store, the round blocks and the field slices keeps every such copy on
    the slice."""
    return jax.tree.map(
        lambda a: with_layout_constraint(a, Layout(tuple(range(a.ndim)))), x)


# --------------------------------------------------------------------------- #
# Context view                                                                 #
# --------------------------------------------------------------------------- #

def _to_words(x: jnp.ndarray) -> jnp.ndarray:
    if x.dtype == jnp.uint32:
        return x
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _from_words(w: jnp.ndarray, dtype) -> jnp.ndarray:
    dtype = jnp.dtype(dtype)
    if dtype == jnp.uint32:
        return w
    return jax.lax.bitcast_convert_type(w, dtype)


class Ctx:
    """A single swapped-in context: a ``[words]`` uint32 vector with typed
    field accessors.  Functional: ``set`` returns a new view."""

    def __init__(self, layout: ContextLayout, words: jnp.ndarray):
        self.layout = layout
        self.words = words

    def get(self, name: str) -> jnp.ndarray:
        off = self.layout.offset(name)
        f = self.layout.field(name)
        flat = jax.lax.slice_in_dim(self.words, off, off + f.words, axis=0)
        return _from_words(flat, f.dtype).reshape(f.shape)

    def set(self, name: str, value: jnp.ndarray) -> "Ctx":
        off = self.layout.offset(name)
        f = self.layout.field(name)
        value = jnp.asarray(value, f.dtype).reshape((f.words,))
        new = jax.lax.dynamic_update_slice_in_dim(
            self.words, _to_words(value), off, axis=0
        )
        return Ctx(self.layout, new)

    def update(self, **kv) -> "Ctx":
        c = self
        for k, v in kv.items():
            c = c.set(k, v)
        return c


# --------------------------------------------------------------------------- #
# Store                                                                        #
# --------------------------------------------------------------------------- #

@jax.tree_util.register_pytree_node_class
class ContextStore:
    """All ``v`` contexts: the external memory.  ``data`` is ``[v, words]``
    uint32, shardable on axis 0 over the mesh's virtual-processor axis."""

    def __init__(self, layout: ContextLayout, data: jnp.ndarray):
        self.layout = layout
        self.data = data

    # pytree plumbing -------------------------------------------------------
    def tree_flatten(self):
        return (self.data,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(layout, children[0])

    # convenience -----------------------------------------------------------
    @property
    def v(self) -> int:
        return self.data.shape[0]

    @property
    def mu_bytes(self) -> int:
        return self.layout.mu_bytes

    def field(self, name: str) -> jnp.ndarray:
        """Gather a field across all contexts → ``[v, *shape]`` (host debugging
        / result extraction; not part of the simulated I/O)."""
        off = self.layout.offset(name)
        f = self.layout.field(name)
        flat = row_major(self.data[:, off:off + f.words])
        return _from_words(flat, f.dtype).reshape((self.v,) + f.shape)

    def with_field(self, name: str, value: jnp.ndarray) -> "ContextStore":
        off = self.layout.offset(name)
        f = self.layout.field(name)
        value = jnp.asarray(value, f.dtype).reshape((self.v, f.words))
        data = jax.lax.dynamic_update_slice(
            self.data, _to_words(value), (0, off)
        )
        return ContextStore(self.layout, data)

    # word-level access --------------------------------------------------- #
    # The fused Alltoallv path moves raw context words (the on-disk byte
    # ranges), skipping the typed gather→bitcast→reshape round-trip: a field
    # is just a contiguous word range of every context row.

    def field_words_view(self, name: str) -> jnp.ndarray:
        """Raw ``[v, field_words]`` uint32 view of a field's word range
        across all contexts — no bitcast, no reshape to the field shape."""
        off = self.layout.offset(name)
        n = self.layout.field_words(name)
        return row_major(jax.lax.slice(self.data, (0, off), (self.v, off + n)))

    def with_field_words(self, name: str, words: jnp.ndarray) -> "ContextStore":
        """Write a field's raw word range from a ``[v, field_words]`` uint32
        array (inverse of :meth:`field_words_view`).

        The row is rebuilt with a concatenate rather than a
        dynamic-update-slice: XLA fuses the incoming value's producer (e.g.
        the delivery transpose) straight into the concatenate's output loop,
        where a dynamic-update-slice materialises the operand first — on CPU
        this is a consistent ~1.5× win for Alltoallv-sized writes.
        """
        off = self.layout.offset(name)
        n = self.layout.field_words(name)
        if words.dtype != jnp.uint32:
            raise TypeError(f"word-level writes must be uint32, got {words.dtype}")
        words = words.reshape((self.v, n))
        left = jax.lax.slice(self.data, (0, 0), (self.v, off))
        right = jax.lax.slice(
            self.data, (0, off + n), (self.v, self.data.shape[1])
        )
        data = jnp.concatenate([left, words, right], axis=1)
        return ContextStore(self.layout, data)


def init_store(layout_: ContextLayout, v: int,
               init_fn: Optional[Callable[[jnp.ndarray], Dict[str, jnp.ndarray]]] = None
               ) -> ContextStore:
    """Create a store; ``init_fn(rho) -> {field: value}`` runs vmapped over the
    virtual-processor IDs to populate initial contexts."""
    data = jnp.zeros((v, layout_.words), jnp.uint32)
    store = ContextStore(layout_, data)
    if init_fn is not None:
        def one(rho):
            ctx = Ctx(layout_, jnp.zeros((layout_.words,), jnp.uint32))
            vals = init_fn(rho)
            for name, val in vals.items():
                ctx = ctx.set(name, val)
            return ctx.words
        data = jax.vmap(one)(jnp.arange(v, dtype=jnp.int32))
        store = ContextStore(layout_, data)
    return store
