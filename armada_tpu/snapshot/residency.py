"""Device-resident round state: delta scatter updates instead of the
per-cycle snapshot re-upload.

The transfer ledger (observe/ledger.py) measured the warm flagship cycle
re-uploading ~163 MB of round tensors per cycle — the host↔device churn
ROADMAP item 1 names as the blocker for the 1M×50k sub-second round.
This module keeps the padded :class:`DeviceRound` resident on device
across warm cycles and applies each cycle's event-sourced delta stream
(submit / lease / terminal / requeue / cordon / fence / drain, already
folded into the columnar state by ``snapshot/incremental.py``) as
batched index/value scatter updates into the persistent buffers — the
way the hot-window ``scatter_back`` already writes in place.

Bit-exactness is by construction, not by re-derivation: every cycle the
host-side padded round the rebuild path would have uploaded is computed
anyway (it is O(delta)-maintained by ``IncrementalRound``), diffed
against an *owned host mirror* of the device state, and only the
changed rows travel. The mirror is updated with exactly the rows that
were scattered, so mirror == device bits at all times (modulo jax's
dtype canonicalization, which the fresh-upload path applies
identically). ``check_drift`` materializes the device buffers and
verifies that invariant — the live guard behind the ``resident_drift``
divergence kind.

Three update shapes, chosen per field per cycle by transfer cost:

- **row scatter** — changed rows along the field's diff axis (axis 1
  for ``alloc0``'s node axis, axis 0 elsewhere) uploaded as a
  pow4-bucketed (index, values) batch and applied with a donated
  ``buf.at[idx].set(vals)``. Bucket padding repeats a real index with
  its own row, so duplicate-index scatter stays deterministic.
- **slot permutation** — the slot table is resorted whenever a lease
  moves a gang between the running and queued segments, shifting most
  slot rows while changing almost no slot *content*. Each slot carries
  a stable leader (its first member's job row), so the new table is
  mostly a gather of the old one: one int32[S] source map uploads and
  every slot-axis field permutes on device, with only the residual
  rows (fresh gangs, segment flips) scattered after.
- **wholesale replace** — when the scatter batch would cost more bytes
  than the field itself (narrow fields under heavy churn, e.g.
  ``job_slot``), the whole field re-uploads via ``device_put``.

A structural change (padded-shape regrow past a pow2 boundary, config
meta change) resets the residency: one full upload, after which delta
cycles resume. Every upload — batches, source maps, resets — books
into the active transfer ledger, so ``bytes_up`` stays the honest
before/after axis.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ..observe import ledger as _tledger
from ..solver.kernel_prep import (
    _META_FIELDS,
    DeviceRound,
    pad_device_round,
)
from ..utils import tracing

_DATA_FIELDS = tuple(
    f.name for f in dataclasses.fields(DeviceRound) if f.name not in _META_FIELDS
)

# Slot-axis fields permuted together when the slot table reshuffles.
_SLOT_FIELDS = (
    "slot_members",
    "slot_count",
    "slot_queue",
    "slot_is_running",
    "slot_req",
    "slot_key_group",
    "slot_jobs_before",
    "slot_run_len",
    "slot_batchable",
    "slot_uni_start",
    "slot_uni_end",
    "slot_price",
    "slot_away",
)

# alloc0 is [P, N, R]: the mutable axis is the node axis.
_AXIS1_FIELDS = ("alloc0",)

# Scatter batches pad to pow4 buckets (64, 256, 1024, ...): coarse
# buckets keep the per-(field, batch-size) compiled-program population
# small and stable, so steady warm cycles never pay a scatter compile.
_BUCKET_FLOOR = 64


def _bucket(k: int) -> int:
    b = _BUCKET_FLOOR
    while b < k:
        b *= 4
    return b


def _changed_rows(old: np.ndarray, new: np.ndarray, axis: int) -> np.ndarray:
    """Indices along `axis` where any element differs. NaN compares
    unequal to itself, so NaN-carrying rows re-upload every cycle —
    conservative (extra bytes), never incorrect (same bits land)."""
    diff = old != new
    if diff.ndim > 1:
        reduce_axes = tuple(i for i in range(diff.ndim) if i != axis)
        mask = diff.any(axis=reduce_axes)
    else:
        mask = diff
    return np.flatnonzero(mask)


def _bits_equal(a, b) -> bool:
    a = np.asarray(a)
    b = np.asarray(b)
    return (
        a.dtype == b.dtype
        and a.shape == b.shape
        and np.ascontiguousarray(a).tobytes() == np.ascontiguousarray(b).tobytes()
    )


def _scatter0(buf, idx, vals):
    return buf.at[idx].set(vals)


def _scatter1(buf, idx, vals):
    return buf.at[:, idx].set(vals)


def _gather0(buf, perm):
    return buf[perm]


_JITS: dict = {}


def _jit_for(kind: str):
    """Jitted scatter/gather, donating the resident buffer where the
    backend supports donation (TPU/GPU update in place; CPU jax ignores
    donation, so requesting it there only emits warnings)."""
    import jax

    donate = jax.default_backend() != "cpu"
    key = (kind, donate)
    fn = _JITS.get(key)
    if fn is None:
        base = {"s0": _scatter0, "s1": _scatter1, "g0": _gather0}[kind]
        fn = jax.jit(base, donate_argnums=(0,) if donate else ())
        _JITS[key] = fn
    return fn


class ResidentRound:
    """The device-resident padded round for one pool, plus its owned
    host mirror.

    ``device_round(inc)`` is the per-cycle sync: idempotent per
    ``IncrementalRound`` generation (failover-ladder retries and shadow
    probes within a cycle reuse the committed tree without re-booking
    transfers), delta-applied between generations, fully reset on any
    structural change. The returned tree's array leaves are committed
    ``jax.Array``s — ``solve_round`` books zero upload for them — while
    scalar leaves stay host-side so the compiled programs and their
    dtype canonicalization match the rebuild path bit for bit.

    ``host_round()`` is the numpy twin of the device state for the
    consumers that must not touch (or risk donating) the live buffers:
    the admission firewall, the fairness ledger, the flight recorder,
    and postmortem capture. Callers must not mutate it.
    """

    def __init__(self):
        self._inc = None
        self._gen = None
        self._host: DeviceRound | None = None
        self._dev: DeviceRound | None = None
        # Last non-cached sync: {"mode": "reset"|"delta", "bytes_up": n,
        # "fields": [...], "permuted": bool}
        self.last_sync: dict = {}

    # ------------------------------------------------------------------

    def host_round(self) -> DeviceRound | None:
        return self._host

    def reset(self):
        """Drop all resident state; the next cycle pays one full upload."""
        self._inc = None
        self._gen = None
        self._host = None
        self._dev = None

    def device_round(self, inc) -> DeviceRound:
        """The device-resident padded round for `inc`'s current
        generation, synced via delta scatter (or full reset). Call
        inside the round's transfer ledger: every byte that actually
        travels host→device books here and nowhere else.

        A sync that does work is a `residency.sync` span (attrs `mode`,
        `bytes_up`, `fields` from `last_sync`) with children
        `residency.prep` (the padded host round), `residency.diff` and
        `residency.upload` (utils/tracing.span)."""
        gen = getattr(inc, "_gen", None)
        if self._dev is not None and self._inc is inc and gen == self._gen:
            return self._dev
        with tracing.span("residency.sync") as sync:
            with tracing.span("residency.prep"):
                new = pad_device_round(inc.device_round())
            if self._host is None or not self._compatible(new):
                self._full_reset(new)
            else:
                self._delta_sync(new)
            self._inc, self._gen = inc, gen
            sync.attrs.update(
                mode=self.last_sync["mode"],
                bytes_up=self.last_sync["bytes_up"],
                fields=self.last_sync["fields"],
            )
        return self._dev

    def check_drift(self) -> list[str]:
        """Materialize the device buffers and bit-compare against the
        host mirror (through the same dtype canonicalization the upload
        path applied). Returns the drifted field names — any entry
        means the resident state can no longer be trusted and the
        caller must demote to a rebuild."""
        if self._dev is None or self._host is None:
            return []
        drifted = []
        for name in _DATA_FIELDS:
            h = getattr(self._host, name)
            if not (isinstance(h, np.ndarray) and h.ndim >= 1):
                continue
            d = np.asarray(getattr(self._dev, name))
            expect = h if h.dtype == d.dtype else h.astype(d.dtype)
            if not _bits_equal(expect, d):
                drifted.append(name)
        return drifted

    # ------------------------------------------------------------------

    def _compatible(self, new: DeviceRound) -> bool:
        """Same static config and same padded shapes/dtypes — the
        precondition for delta updates into the existing buffers."""
        for m in _META_FIELDS:
            if getattr(new, m) != getattr(self._host, m):
                return False
        for name in _DATA_FIELDS:
            h = getattr(self._host, name)
            n = getattr(new, name)
            h_arr = isinstance(h, np.ndarray) and h.ndim >= 1
            n_arr = isinstance(n, np.ndarray) and np.ndim(n) >= 1
            if h_arr != n_arr:
                return False
            if h_arr and (h.shape != n.shape or h.dtype != n.dtype):
                return False
        return True

    def _full_reset(self, new: DeviceRound):
        import jax

        host: dict = {}
        dev: dict = {}
        bytes_up = 0
        with tracing.span("residency.upload"):
            for name in _DATA_FIELDS:
                v = getattr(new, name)
                if isinstance(v, np.ndarray) and v.ndim >= 1:
                    # Own the mirror: prep_device_round hands out views of
                    # the IncrementalRound's live columnar arrays, which
                    # the next delta mutates in place.
                    owned = np.ascontiguousarray(v)
                    if owned is v:
                        owned = v.copy()
                    _tledger.note_up(owned, site="residency.reset")
                    bytes_up += owned.nbytes
                    host[name] = owned
                    dev[name] = jax.device_put(owned)
                else:
                    # Scalar leaves (global_tokens, spot_price_cutoff,
                    # ...) stay host-side: jit canonicalizes them at
                    # dispatch exactly as on the rebuild path, keeping the
                    # compiled program and its dtype handling identical.
                    host[name] = v
                    dev[name] = v
        self._host = dataclasses.replace(new, **host)
        self._dev = dataclasses.replace(new, **dev)
        self.last_sync = {
            "mode": "reset",
            "bytes_up": int(bytes_up),
            "fields": list(_DATA_FIELDS),
            "permuted": False,
        }

    def _slot_source_map(self, new: DeviceRound) -> np.ndarray | None:
        """int32[S] map: new slot i's content lives at old slot
        source[i] (identity for fresh slots, fixed up by the residual
        scatter). None when the slot table did not reshuffle. Keyed on
        each slot's leader — its first member's job row, which is
        stable across cycles because IncrementalRound never renumbers
        live job rows."""
        old_lead = self._host.slot_members[:, 0]
        new_lead = np.asarray(new.slot_members)[:, 0]
        if np.array_equal(old_lead, new_lead):
            return None
        S = old_lead.shape[0]
        top = int(max(old_lead.max(initial=-1), new_lead.max(initial=-1))) + 1
        lut = np.full(max(top, 1), -1, dtype=np.int64)
        old_valid = old_lead >= 0
        lut[old_lead[old_valid]] = np.flatnonzero(old_valid)
        source = np.arange(S, dtype=np.int32)
        nv = np.flatnonzero(new_lead >= 0)
        src = lut[new_lead[nv]]
        source[nv] = np.where(src >= 0, src, nv).astype(np.int32)
        if np.array_equal(source, np.arange(S, dtype=np.int32)):
            return None
        return source

    def _delta_sync(self, new: DeviceRound):
        """Host diffing and device work overlap, each phase a span:
        `residency.diff` finds the slot source map; `residency.upload`
        dispatches the slot permutation, which the device runs (a quarter
        of a second at a million slots) while the host goes on;
        `residency.diff` permutes the host mirror the same way and finds
        each field's changed rows; `residency.upload` transfers and
        dispatches the row scatters and whole-field replacements."""
        import jax

        bytes_up = 0
        with tracing.span("residency.diff"):
            source = self._slot_source_map(new)
        if source is not None:
            with tracing.span("residency.upload"):
                # One uploaded source map permutes every slot-axis field
                # on device.
                _tledger.note_up(source, site="residency.slot_map")
                bytes_up += source.nbytes
                source_dev = jax.device_put(source)
                gather = _jit_for("g0")
                for name in _SLOT_FIELDS:
                    setattr(
                        self._dev, name,
                        gather(getattr(self._dev, name), source_dev),
                    )
        with tracing.span("residency.diff"):
            updates, touched = self._diff(new, source)
        with tracing.span("residency.upload"):
            for name, axis, payload in updates:
                if axis is None:
                    # The batch would outweigh the field: replace wholesale.
                    _tledger.note_up(payload, site="residency.full")
                    bytes_up += payload.nbytes
                    setattr(self._dev, name, jax.device_put(payload))
                    continue
                idx, vals = payload
                _tledger.note_up(payload, site="residency.delta")
                bytes_up += idx.nbytes + vals.nbytes
                fn = _jit_for("s1" if axis == 1 else "s0")
                setattr(self._dev, name, fn(getattr(self._dev, name), idx, vals))
        self.last_sync = {
            "mode": "delta",
            "bytes_up": int(bytes_up),
            "fields": touched,
            "permuted": source is not None,
        }

    def _diff(self, new: DeviceRound, source):
        """Bring the host mirror to `new` and say what the device needs
        beyond the slot permutation `source` (or None): ([(field, axis,
        (idx, vals))] row scatters and [(field, None, array)] whole-field
        replacements, touched field names). Scalar leaves are set on both
        sides here."""
        touched: list[str] = []
        updates: list[tuple] = []
        if source is not None:
            # The host mirror permutes as the device does, so the
            # residual diff below only sees true content changes.
            for name in _SLOT_FIELDS:
                h = getattr(self._host, name)
                setattr(self._host, name, np.ascontiguousarray(h[source]))
        for name in _DATA_FIELDS:
            cur = getattr(self._host, name)
            nxt = getattr(new, name)
            if not (isinstance(cur, np.ndarray) and cur.ndim >= 1):
                if not self._scalar_equal(cur, nxt):
                    setattr(self._host, name, nxt)
                    setattr(self._dev, name, nxt)
                    touched.append(name)
                continue
            nxt = np.asarray(nxt)
            axis = 1 if name in _AXIS1_FIELDS else 0
            rows = _changed_rows(cur, nxt, axis)
            if rows.size == 0:
                continue
            touched.append(name)
            row_bytes = max(1, cur.nbytes // cur.shape[axis])
            kb = _bucket(int(rows.size))
            if kb * (4 + row_bytes) >= cur.nbytes:
                owned = np.ascontiguousarray(nxt)
                if owned is nxt:
                    owned = nxt.copy()
                setattr(self._host, name, owned)
                updates.append((name, None, owned))
                continue
            # Bucket-pad by repeating a real index with its own row:
            # duplicate indices write duplicate values, so the scatter
            # result is deterministic and the pad rows are no-ops.
            idx = np.empty(kb, dtype=np.int32)
            idx[: rows.size] = rows
            idx[rows.size:] = rows[0]
            vals = np.ascontiguousarray(np.take(nxt, idx, axis=axis))
            updates.append((name, axis, (idx, vals)))
            if axis == 1:
                cur[:, rows] = nxt[:, rows]
            else:
                cur[rows] = nxt[rows]
        return updates, touched

    @staticmethod
    def _scalar_equal(a, b) -> bool:
        try:
            return _bits_equal(a, b)
        except (TypeError, ValueError):
            return a == b
