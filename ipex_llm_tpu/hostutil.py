"""Host<->device transfer helpers shared by every async-dispatch module.

The one rule this module exists to enforce (see PR 2's stream-corruption
race, fixed in ``serving/engine.py``, and rule JL001 in
``ipex_llm_tpu.analysis``):

    A MUTABLE host buffer must never be uploaded with zero-copy
    semantics while dispatch is asynchronous.

``jnp.asarray`` on the CPU backend zero-copy-aliases suitably-aligned
numpy buffers, and dispatch is async — a program still in flight reads
the live buffer AFTER host-side bookkeeping mutates it (the serving
engine's row_lens/temps/tables advance every tick; a generate() caller
may recycle its prompt buffer).  Whether a given array aliases depends
on where numpy's allocator placed it, so the corruption is alignment-
and history-dependent: the worst kind of intermittent.  ``jnp.array``
(copy semantics) pins a snapshot the device owns.

Use :func:`h2d` (or :func:`upload`, the transfer alone, for a numpy array
that already has its device dtype) at every host->device boundary whose
source is (or may be) a mutable numpy buffer.  Literal constants and values that are
already jax arrays may keep ``jnp.asarray``; ``ipex_llm_tpu.analysis``
rule JL001 machine-checks exactly that contract over the async-dispatch
modules.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


def h2d(x: Any, dtype: Any = None) -> jnp.ndarray:
    """Upload host data to the device, ALWAYS copying.

    Drop-in for ``jnp.asarray`` at mutable-buffer boundaries: same
    signature shape (value, optional dtype), but guaranteed copy
    semantics, so the caller may mutate or free ``x`` immediately after
    the call even while async dispatch is still reading the upload.
    """
    return jnp.array(x, dtype=dtype)


def upload(x: np.ndarray, sharding: Any = None) -> jax.Array:
    """Upload one host array as ONE runtime call: a transfer and nothing
    else.

    ``h2d`` is ``jnp.array``, whose transfer is the argument of an eagerly
    bound ``convert_element_type``: a second runtime call, a one-op
    program, for every upload.  On a path that runs between two ticks of a
    serving engine that program costs as much as the transfer.  This is
    the transfer alone, with ``h2d``'s copy semantics: the device is handed
    a PRIVATE copy of ``x`` that nobody writes again, so the caller may
    refill ``x`` at once (on the CPU backend the device array may alias
    that private copy, which is safe for the same reason).  ``x`` is a
    numpy array that already has the dtype wanted on the device;
    ``sharding`` places it (a replicated ``NamedSharding`` under a mesh).
    """
    return jax.device_put(np.array(x, copy=True), sharding)


def d2h(x: Any) -> np.ndarray:
    """Materialise a device value on the host (np.asarray; BLOCKING sync).

    Exists so hot-path code can name its designed sync points — rule
    JL002 flags ad-hoc ``np.asarray``/``int()``/``.item()`` syncs in the
    engine tick/decode paths; routing a *designed* sync through ``d2h``
    (with a JL002 suppression and reason at the call site) keeps the
    inventory of blocking points auditable.
    """
    return np.asarray(x)


# Host arrays of at least this many elements are worth splitting over a
# thread pool (numpy releases the GIL inside its copy and random-draw loops)
PARALLEL_MIN_ELEMS = 1 << 22
ROW_BLOCKS = 16


def for_row_blocks(n_rows: int, fn: "Callable[[int, int, int], Any]") -> None:
    """Run ``fn(block_index, lo, hi)`` over ``ROW_BLOCKS`` contiguous row
    ranges covering ``[0, n_rows)`` on a thread pool.  The block count is
    fixed, so what a block-indexed ``fn`` writes does not depend on the
    machine's core count."""
    from concurrent.futures import ThreadPoolExecutor

    bounds = np.linspace(0, n_rows, ROW_BLOCKS + 1).astype(int)
    with ThreadPoolExecutor() as pool:
        list(pool.map(fn, range(ROW_BLOCKS), bounds[:-1], bounds[1:]))


class HostLRU:
    """Byte-budgeted host-RAM LRU — ONE implementation of the
    evict-to-fit bookkeeping shared by every host-side cache tier
    (``offload.ExpertStore``'s HBM expert cache and the serving KV page
    store ``serving/pagestore.py``), so budget accounting and eviction
    order cannot drift between them.

    Semantics (the historical ExpertStore contract, preserved exactly):
    ``put`` evicts least-recently-used entries until the new entry fits
    (or the cache is empty — a single entry larger than the whole budget
    is admitted over-budget rather than refused, so a degenerate budget
    degrades to a 1-entry cache instead of a dead one); ``get`` is an
    LRU touch and counts hits/misses.  Values are treated as immutable —
    ``snapshot``/``restore`` copy only the bookkeeping (key order, byte
    sizes, counters), which is what makes a transactional caller's
    checkpoint/rollback of a tier O(entries), not O(bytes).
    """

    def __init__(self, budget_bytes: int,
                 on_evict: "Callable[[Any, Any], None] | None" = None):
        self.budget = int(budget_bytes)
        self.on_evict = on_evict     # called as on_evict(key, value)
        self._entries: "OrderedDict[Any, Any]" = OrderedDict()
        self._sizes: dict[Any, int] = {}
        self.used = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    def keys(self):
        return self._entries.keys()

    def get(self, key, touch: bool = True):
        """Value for ``key`` (None = miss); a hit is an LRU touch."""
        if key in self._entries:
            self.hits += 1
            if touch:
                self._entries.move_to_end(key)
            return self._entries[key]
        self.misses += 1
        return None

    def peek(self, key):
        """Value for ``key`` with NO side effects at all: no LRU touch,
        no hit/miss accounting — the read a pure observer (an export
        path, a stats probe) takes so it cannot perturb eviction order
        or the economics counters it is reporting on."""
        return self._entries.get(key)

    def put(self, key, value, nbytes: int):
        """Insert/replace ``key`` (becomes most-recent), evicting LRU
        entries until it fits under the byte budget."""
        if key in self._entries:
            self.used -= self._sizes.pop(key)
            del self._entries[key]
        while self.used + nbytes > self.budget and self._entries:
            old_key, old_val = self._entries.popitem(last=False)
            self.used -= self._sizes.pop(old_key)
            self.evictions += 1
            if self.on_evict is not None:
                self.on_evict(old_key, old_val)
        self._entries[key] = value
        self._sizes[key] = int(nbytes)
        self.used += int(nbytes)

    def pop(self, key):
        """Remove and return ``key``'s value (None when absent); does not
        count as a hit/miss — pairs with ``put`` for consume-and-restore
        callers."""
        if key not in self._entries:
            return None
        self.used -= self._sizes.pop(key)
        return self._entries.pop(key)

    def snapshot(self) -> dict:
        """Bookkeeping-only checkpoint (values held by reference)."""
        return {
            "entries": OrderedDict(self._entries),
            "sizes": dict(self._sizes),
            "used": self.used,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
        }

    def restore(self, snap: dict):
        self._entries = OrderedDict(snap["entries"])
        self._sizes = dict(snap["sizes"])
        self.used = snap["used"]
        self.hits = snap["hits"]
        self.misses = snap["misses"]
        self.evictions = snap["evictions"]
