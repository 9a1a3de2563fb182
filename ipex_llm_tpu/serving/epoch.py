"""The packed epoch: what the host publishes to the device for one epoch,
as ONE int32 buffer that crosses in ONE transfer.

An epoch is a tick before which the host's view of the rows changed
(admission, a prefill chunk, a completion, a finish, a page allocation).
Everything the device needs to know of it — the row state, the host's
whole block table, and (a mixed tick) the prefill block — is laid out in
one flat int32 buffer whose layout follows from shapes alone
(:class:`EpochLayout`).  The host fills it (:meth:`EpochLayout.pack`:
``float32`` fields as their bit patterns, bools as 0/1), uploads it with
``hostutil.upload`` and the device takes it apart again inside a jitted
program (:meth:`EpochLayout.unpack`: static slices, a
``bitcast_convert_type`` for the two float fields): inside
``engine._ragged_tick`` where the tick has a prefill block, in
:func:`unpack_rows` for every other epoch.  Integers are copied and
floats round-trip through their bits, so nothing a tick computes moves.

The way home is the mirror (:class:`ResultLayout`): everything a tick
sends back to the host (steps executed, the token and log-probability
blocks, a prefill block's first tokens, a speculating tick's accepted
counts and draft counters) is packed INSIDE the tick program into one
int32 buffer (:meth:`ResultLayout.pack`, traced), crosses in one transfer
and is taken apart on the host as views (:meth:`ResultLayout.unpack`).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# the per-row state, [R] each, in the order the buffer holds it
ROW_FIELDS = (("toks", np.int32), ("row_lens", np.int32),
              ("active", np.bool_), ("temps", np.float32),
              ("top_ps", np.float32), ("seeds", np.int32),
              ("top_ks", np.int32), ("steps", np.int32),
              ("remain", np.int32))
# what a tick never advances: the host re-passes these device arrays
# until the next epoch, so an epoch's program hands them back out
HELD_FIELDS = ("temps", "top_ps", "seeds", "top_ks", "eos")
# the prefill block's per-slot vectors, [p_b] each, after ``p_toks``
PREFILL_FIELDS = (("p_base", np.int32), ("p_nvalid", np.int32),
                  ("p_emit", np.bool_), ("p_canjoin", np.bool_),
                  ("p_rowmap", np.int32))


class EpochLayout(NamedTuple):
    """Where each field lies in the packed buffer.  ``rows`` x
    ``max_pages`` is the block table, ``e_w`` the width the per-row stop
    ids are padded to; ``p_b`` x ``width`` is the prefill block (0 = a
    steady tick's epoch: no block) and ``p_maxp`` the width the prefill
    stage's table view is cut to (no field of the buffer: the static
    slice of the rows gathered out of the table)."""

    rows: int
    max_pages: int
    e_w: int
    p_b: int = 0
    width: int = 0
    p_maxp: int = 0

    def slots(self) -> dict[str, tuple[int, int, tuple[int, ...], type]]:
        """name -> (first int32 word, one past the last, shape, dtype)."""
        return _slots(self)

    @property
    def size(self) -> int:
        return list(_slots(self).values())[-1][1]

    def pack(self, **fields) -> np.ndarray:
        """A fresh int32 buffer holding ``fields`` (one array a slot).
        Fresh on purpose: the caller hands it to the device and never
        writes it again, so an upload still in flight reads what was
        packed whatever the host does next."""
        buf = np.empty((self.size,), np.int32)
        for name, (lo, hi, shape, dt) in _slots(self).items():
            view = buf[lo:hi].reshape(shape)
            if dt is np.float32:
                view = view.view(np.float32)      # the same bits
            view[...] = fields[name]              # a bool lands as 0 / 1
        return buf

    def unpack(self, buf) -> dict[str, jnp.ndarray]:
        """The fields of a packed buffer, on the device (traced)."""
        out = {}
        for name, (lo, hi, shape, dt) in _slots(self).items():
            x = jax.lax.slice(buf, (lo,), (hi,)).reshape(shape)
            if dt is np.float32:
                x = jax.lax.bitcast_convert_type(x, jnp.float32)
            elif dt is np.bool_:
                x = x != 0
            out[name] = x
        return out


@lru_cache(maxsize=None)
def _slots(layout: EpochLayout) -> dict:
    r, p = layout.rows, layout.p_b
    fields = [(name, (r,), dt) for name, dt in ROW_FIELDS]
    fields += [("eos", (r, layout.e_w), np.int32),
               ("tables", (r, layout.max_pages), np.int32)]
    if p:
        fields.append(("p_toks", (p, layout.width), np.int32))
        fields += [(name, (p,), dt) for name, dt in PREFILL_FIELDS]
    return _lay_out(fields)


def _lay_out(fields) -> dict:
    """(name, shape, dtype) in order -> name -> (lo, hi, shape, dtype)."""
    out, off = {}, 0
    for name, shape, dt in fields:
        out[name] = (off, off + int(np.prod(shape)), shape, dt)
        off = out[name][1]
    return out


class ResultLayout(NamedTuple):
    """Where each host-bound result of a tick lies in the packed buffer it
    sends home.  ``rows`` x ``horizon`` are the token and log-probability
    blocks, ``k1`` their third axis on a speculating tick (``spec_k + 1``;
    0 = a plain tick, and no ``take`` / ``prop`` / ``acc``), ``p_b`` the
    prefill block's slots (0 = none, and no ``first_t`` / ``first_lp``),
    ``n_stats`` the recurrent state's counters behind ``n_exec`` (0 = a
    model without state)."""

    rows: int
    horizon: int
    k1: int = 0
    p_b: int = 0
    n_stats: int = 0

    @classmethod
    def of(cls, fields: dict) -> "ResultLayout":
        """The layout of a tick's results, from their shapes alone."""
        tok = fields["tok"]
        first = fields.get("first_t")
        return cls(rows=tok.shape[0], horizon=tok.shape[1],
                   k1=tok.shape[2] if tok.ndim == 3 else 0,
                   p_b=0 if first is None else first.shape[0],
                   n_stats=int(np.prod(fields["n_exec"].shape)) - 1)

    def slots(self) -> dict[str, tuple[int, int, tuple[int, ...], type]]:
        """name -> (first int32 word, one past the last, shape, dtype)."""
        return _result_slots(self)

    @property
    def size(self) -> int:
        return list(_result_slots(self).values())[-1][1]

    def pack(self, **fields) -> jnp.ndarray:
        """One int32 buffer holding ``fields`` (one array a slot), on the
        device (traced): integers copied, a ``float32`` as its bits."""
        parts = []
        for name, (lo, hi, shape, dt) in _result_slots(self).items():
            x = fields[name]
            if x.dtype != dt or int(np.prod(x.shape)) != hi - lo:
                raise TypeError(f"{name}: {x.dtype}{x.shape} does not fill "
                                f"a {np.dtype(dt).name}{shape} slot")
            if dt is np.float32:
                x = jax.lax.bitcast_convert_type(x, jnp.int32)
            parts.append(x.reshape(-1))
        return jnp.concatenate(parts)

    def unpack(self, buf: np.ndarray) -> dict[str, np.ndarray]:
        """The fields of a fetched buffer, on the host, as views of it."""
        if buf.dtype != np.int32 or buf.shape != (self.size,):
            raise ValueError(f"{buf.dtype}{buf.shape} is not a packed "
                             f"result of {self}")
        out = {}
        for name, (lo, hi, shape, dt) in _result_slots(self).items():
            view = buf[lo:hi].reshape(shape)
            out[name] = view.view(np.float32) if dt is np.float32 else view
        return out


@lru_cache(maxsize=None)
def _result_slots(layout: ResultLayout) -> dict:
    r, h = layout.rows, layout.horizon
    block = (r, h, layout.k1) if layout.k1 else (r, h)
    fields = [("n_exec", (1 + layout.n_stats,), np.int32),
              ("tok", block, np.int32), ("lp", block, np.float32)]
    if layout.p_b:
        fields += [("first_t", (layout.p_b,), np.int32),
                   ("first_lp", (layout.p_b,), np.float32)]
    if layout.k1:
        fields += [("take", (r, h), np.int32), ("prop", (), np.int32),
                   ("acc", (), np.int32)]
    return _lay_out(fields)


@partial(jax.jit, static_argnames=("layout",))
def unpack_rows(buf, layout: EpochLayout):
    """The epoch of a tick with no prefill block (a steady tick after a
    completion, a finish or a page boundary), and of any caller outside
    a tick: the row state and the block table out of one uploaded
    buffer, as one small program."""
    with jax.named_scope("tick/epoch"):
        return layout.unpack(buf)
