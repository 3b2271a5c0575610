"""Device-resident UJSON keyspace: hot documents live ON the TPU.

Round-3 shape (superseded): every drain re-encoded each hot key's pending
deltas host->device, folded them on device, pulled the folded delta back
and host-converged it into the authoritative host doc — O(new deltas)
encode per drain, but also a device->host pull and a host O(doc) converge
per drain, and a 32-replica fan-in additionally re-encoded the replica
documents themselves every round (the encode dominated).

This module keeps the hot keys' packed rows (ops/ujson_device.DocBatch:
sorted packed-dot planes + payload ids + vv + cloud) RESIDENT on the
device between drains. A drain then:

  1. encodes ONLY the new deltas into a (K, D, W) grid — O(new deltas),
  2. folds each key's D deltas and joins the result into that key's
     resident row in ONE fused dispatch (`fold_join_subset` /
     `fold_join_aligned`), entirely on device,
  3. decodes NOTHING — reads decode lazily (and cache host-side).

The reference's converge loop (repo_ujson.pony:96-110) walks the full
document once per delta; here the full document is never re-touched by
the host at all — steady-state host cost per drain is the delta encode.

Two properties keep a STREAM of drains fast on real hardware (a new
shape is a recompile, seconds each; a blocking read-back is a device
round trip the next drain waits behind):

* **No syncs, stable shapes.** A join's natural output width is the sum
  of its input widths, which would change the jitted shape EVERY drain.
  Instead the store tracks a host-side UPPER BOUND on the live row
  widths (admission widths + per-drain delta entry counts — removals
  only loosen the bound, never break it), and the fold kernels slice
  their output to the bucketed bound INSIDE the dispatch. Pads sort to
  the row tails, so slicing at >= the live width is lossless. Widths
  (and compiled shapes) then only change when the bound crosses a power
  of two, and no drain ever reads anything back from the device. Reads
  re-tighten the bound for free when they pull rows anyway.

* **Device causal-context compaction.** Host contexts absorb each
  contiguous dot into the version vector (ujson_host.CausalContext.
  compact); the round-3 device joins never did, so a resident row's
  cloud would grow by every dot ever seen. The fold kernels run a fused
  compaction epilogue (`_compact_ctx_row`): per replica column, the
  contiguous run of cloud dots above vv[col] absorbs into vv (a
  segmented-scan rank test on the sorted cloud row), and covered dots
  drop. Coverage (vv union cloud membership) is exactly preserved, so
  join semantics are untouched — it is the host compact, tensorised.

Layout migrations mirror the encode-side policy (ujson_device.plan_shift):
rows start in the narrow int32 dot layout and migrate IN PLACE on device
to the u64/32 layout the first time a seq or replica-column overflows the
narrow packing (`widen_rows`), or to a smaller narrow shift on replica
growth when every seq still fits (`repack_narrow` — provably safe because
a context covers its dot store, so the store's running max over delta
vv/cloud seqs bounds every seq on device). Seqs past u32 exceed every
device layout; `fold_in` raises OverflowError and the serving repo
demotes those keys to the host lattice.

Sharding: with a serving mesh, the row axis shards across devices and the
drain uses the row-ALIGNED fold (no gathers/scatters -> zero collectives,
SPMD like every plane-backed type); single-device serving uses the subset
fold (gather rows, join, scatter back) so a drain touching few of many
resident keys does not pay a full-batch join. Row 0 is a permanent
identity scratch row: subset-fold padding points spare slots at it, so
padded scatters write identical bytes and stay deterministic.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.batching import bucket
from . import ujson_device as dev
from .ujson_device import DocBatch, _join_inside, _pad_of

U32 = jnp.uint32
I32 = jnp.int32


# ---- fused device kernels --------------------------------------------------


def _fold_flat_one(g: DocBatch, shift: int) -> DocBatch:
    """Fold ONE key's (D, W) delta stack to a single row in closed form.

    The pairwise fold tree (ops/ujson_device.fold_segments) widens its
    intermediates to D*W — mostly pads for small deltas — and pays a
    sort per level. But for a FOLD (not a general join) there is a flat
    rule: an entry survives iff every delta either CONTAINS it or does
    not COVER it (containment implies coverage, so the delta that minted
    it never votes against it; associativity of the ORSWOT join makes
    the n-way statement exact). That is one (D, E) membership/coverage
    probe matrix and a reduce — no tree, no intermediate widening, and
    the single output sort. Contexts fold as an elementwise vv max plus
    one cloud sort+dedup.
    """
    dt = g.dots.dtype
    pad = _pad_of(dt)
    d, w = g.dots.shape
    dots = g.dots.reshape(d * w)
    pay = g.pay.reshape(d * w)
    valid = dots != pad
    rid = jnp.minimum((dots >> dt.type(shift)).astype(I32), g.vv.shape[-1] - 1)
    seq = (dots & dt.type((1 << shift) - 1)).astype(U32)
    # (D, E): does delta j cover entry e? (vv lookup or cloud membership)
    cover = (seq[None, :] <= g.vv[:, rid]) | jax.vmap(
        lambda row: dev._member(row, dots)
    )(g.cloud)
    # (D, E): does delta j contain entry e? (rows are sorted)
    present = jax.vmap(lambda row: dev._member(row, dots))(g.dots)
    survive = valid & jnp.all(present | ~cover, axis=0)
    out_dots = jnp.where(survive, dots, pad)
    out_pay = jnp.where(survive, pay, -1)
    order = jnp.argsort(out_dots)
    out_dots = out_dots[order]
    out_pay = out_pay[order]
    # dedup equal dots (several deltas carrying the same entry): keep one
    dup = jnp.concatenate(
        [out_dots[:-1] == out_dots[1:], jnp.zeros((1,), bool)]
    )
    d2 = jnp.where(dup, pad, out_dots)
    p2 = jnp.where(dup, -1, out_pay)
    order2 = jnp.argsort(d2)
    vv = jnp.max(g.vv, axis=0)
    cl = jnp.sort(g.cloud.reshape(d * g.cloud.shape[-1]))
    cdup = jnp.concatenate([jnp.zeros((1,), bool), cl[1:] == cl[:-1]])
    cloud = jnp.sort(jnp.where(cdup, pad, cl))
    return DocBatch(d2[order2], p2[order2], vv, cloud)


def _fold_grid(grid: DocBatch, shift: int) -> DocBatch:
    """(K, D, W) grid -> one folded row per key, all keys in the same
    dispatch (inlined into the callers' fused kernels).

    Two shapes: the log-depth pairwise tree (ops/ujson_device) probes
    O(E log E) per key but widens intermediates with pads; the flat
    closed-form rule (_fold_flat_one) never widens but probes O(E*D).
    Probes are gather-bound on this hardware, so the tree wins for deep
    stacks and flat wins for shallow ones; measured crossover ~64."""
    if grid.dots.shape[1] <= 64:
        return jax.vmap(partial(_fold_flat_one, shift=shift))(grid)
    return dev.fold_segments(grid, shift=shift)


def _compact_ctx_row(vv, cloud, shift: int):
    """The host CausalContext.compact, tensorised for one row: drop cloud
    dots covered by vv, absorb each column's contiguous run above vv[col]
    into vv. The cloud row is sorted and duplicate-free (joins dedup), so
    within a column's segment the kept seqs are strictly increasing —
    a dot absorbs iff seq == vv[col] + (its rank among kept) + 1, and a
    single pass is complete (any gap blocks everything after it)."""
    dt = cloud.dtype
    pad = _pad_of(dt)
    r = vv.shape[-1]
    valid = cloud != pad
    col = jnp.minimum((cloud >> dt.type(shift)).astype(I32), r - 1)
    seq = (cloud & dt.type((1 << shift) - 1)).astype(U32)
    # computed-index gathers/scatters are pathologically slow on this
    # chip; R is small and static, so per-column masks
    # do the vv lookup and the absorb counting as dense lane ops instead
    colmask = col[None, :] == jnp.arange(r, dtype=I32)[:, None]  # (R, C)
    vvc = jnp.sum(jnp.where(colmask, vv[:, None], U32(0)), axis=0, dtype=U32)
    drop = valid & (seq <= vvc)
    keep = valid & ~drop
    prev_col = jnp.concatenate([jnp.full((1,), -1, I32), col[:-1]])
    is_new = valid & (col != prev_col)
    kept_before = jnp.concatenate(
        [jnp.zeros((1,), I32), jnp.cumsum(keep.astype(I32))[:-1]]
    )
    # kept_before is non-decreasing, so the value at the latest segment
    # start is a running max over the marked positions (no gather)
    seg_base = jnp.maximum(
        jax.lax.cummax(jnp.where(is_new, kept_before, I32(-1))), 0
    )
    rank = kept_before - seg_base
    absorb = keep & (seq == vvc + rank.astype(U32) + 1)
    new_vv = vv + jnp.sum(
        (colmask & absorb[None, :]).astype(U32), axis=1, dtype=U32
    )
    new_cloud = jnp.sort(jnp.where(absorb | drop, pad, cloud))
    return new_vv, new_cloud


def _fit(plane, width: int, fill):
    """Slice or pad a (K, W) plane to the target width. Slicing is
    lossless whenever width covers the live row sizes (pads at tails)."""
    w = plane.shape[-1]
    if width == w:
        return plane
    if width < w:
        return plane[:, :width]
    k = plane.shape[0]
    return jnp.concatenate(
        [plane, jnp.full((k, width - w), fill, plane.dtype)], axis=-1
    )


def _finish(joined: DocBatch, shift: int, out_w: int, out_c: int) -> DocBatch:
    """Fold epilogue: compact contexts, then fit planes to the stable
    bucketed widths (all inside the same dispatch)."""
    vv, cloud = jax.vmap(partial(_compact_ctx_row, shift=shift))(
        joined.vv, joined.cloud
    )
    pad = _pad_of(joined.dots.dtype)
    return DocBatch(
        _fit(joined.dots, out_w, pad),
        _fit(joined.pay, out_w, -1),
        vv,
        _fit(cloud, out_c, pad),
    )


@partial(jax.jit, static_argnames=("shift", "out_w", "out_c"))
def fold_join_subset(
    resident: DocBatch, grid: DocBatch, idx, shift: int, out_w: int, out_c: int
) -> tuple[DocBatch, jax.Array]:
    """Fold each grid segment and join into resident rows idx, one
    dispatch. idx rows must be unique EXCEPT for padded slots pointing at
    scratch row 0 with identity segments: identity joins are no-ops, so
    duplicate scatters to row 0 all write the same bytes (deterministic).
    Output planes are fit to (out_w, out_c) — the caller's width bound —
    so shapes stay stable across a stream of drains."""
    folded = _fold_grid(grid, shift)
    sub = DocBatch(*(p[idx] for p in resident))
    joined = _finish(_join_inside(sub, folded, shift), shift, out_w, out_c)
    pad = _pad_of(resident.dots.dtype)
    base = DocBatch(
        _fit(resident.dots, out_w, pad),
        _fit(resident.pay, out_w, -1),
        resident.vv,
        _fit(resident.cloud, out_c, pad),
    )
    out = DocBatch(*(b.at[idx].set(j) for b, j in zip(base, joined)))
    # live widths of the FULL batch (untouched rows included): the
    # store's width bound must cover every row, not just the subset
    return out, live_widths(out)


@partial(jax.jit, static_argnames=("shift", "out_w", "out_c"))
def fold_join_aligned(
    resident: DocBatch, grid: DocBatch, shift: int, out_w: int, out_c: int
) -> tuple[DocBatch, jax.Array]:
    """Row-aligned variant: grid row i folds into resident row i. No
    gathers or scatters, so with both operands row-sharded over a mesh the
    whole drain is SPMD with zero collectives."""
    folded = _fold_grid(grid, shift)
    out = _finish(_join_inside(resident, folded, shift), shift, out_w, out_c)
    return out, live_widths(out)


@partial(jax.jit, static_argnames=("shift", "out_w", "out_c"))
def fold_broadcast_rows(
    resident: DocBatch,
    deltas: DocBatch,
    occupied,
    shift: int,
    out_w: int,
    out_c: int,
) -> tuple[DocBatch, jax.Array]:
    """Fold a (D, W) delta batch to ONE doc and join it into every
    OCCUPIED resident row — the N-replica anti-entropy fan-in with the
    replica documents already resident.
    Scratch row 0 and free rows re-clear in the same dispatch, so the
    row-0-is-identity invariant holds and the returned live widths
    measure occupied rows only (free-row garbage would inflate the
    store's width bound — ADVICE round 4)."""
    if deltas.dots.shape[0] <= 64:
        folded = _fold_flat_one(deltas, shift)
        folded = DocBatch(*(p[None] for p in folded))
    else:
        folded = dev._fold_body(deltas, shift)
    b = resident.dots.shape[0]
    tiled = DocBatch(
        *(jnp.broadcast_to(p, (b,) + p.shape[1:]) for p in folded)
    )
    out = _finish(_join_inside(resident, tiled, shift), shift, out_w, out_c)
    out = clear_rows(out, ~occupied)
    return out, live_widths(out)


@partial(jax.jit, static_argnames=("w", "c"))
def slice_widths(batch: DocBatch, w: int, c: int) -> DocBatch:
    """Re-bucket plane widths to (w, c) — safe whenever w/c cover the
    live widths, because joined rows keep pads sorted to the tail."""
    pad = _pad_of(batch.dots.dtype)
    return DocBatch(
        _fit(batch.dots, w, pad),
        _fit(batch.pay, w, -1),
        batch.vv,
        _fit(batch.cloud, c, pad),
    )


@jax.jit
def live_widths(batch: DocBatch):
    """(2,) int32: max live dot / cloud width over rows (pads at tails).
    Read at would-widen moments to re-tighten the host width bounds —
    redelivered deltas inflate the bounds but not the live state, and
    this one small pull is what keeps them from forcing spurious plane
    growth (and recompiles)."""
    pad = _pad_of(batch.dots.dtype)
    ld = jnp.max(jnp.sum((batch.dots != pad).astype(I32), axis=-1))
    lc = jnp.max(jnp.sum((batch.cloud != pad).astype(I32), axis=-1))
    return jnp.stack([ld, lc])


@jax.jit
def remap_pay(batch: DocBatch, table) -> DocBatch:
    """Rewrite payload ids through a compaction table (-1 stays -1)."""
    pay = jnp.where(batch.pay >= 0, table[jnp.maximum(batch.pay, 0)], -1)
    return DocBatch(batch.dots, pay, batch.vv, batch.cloud)


@partial(jax.jit, static_argnames=("old_shift",))
def widen_rows(batch: DocBatch, old_shift: int) -> DocBatch:
    """Migrate narrow int32 rows to the u64/32 layout in place on device.

    (col << old_shift | seq) -> (col << 32 | seq) is monotone in (col,
    seq), so row sort order survives; narrow pads map to the u64 pad."""
    mask = (1 << old_shift) - 1

    def w(plane):
        p64 = plane.astype(jnp.uint64)
        repacked = ((p64 >> old_shift) << jnp.uint64(32)) | (
            p64 & jnp.uint64(mask)
        )
        return jnp.where(plane == dev.PAD32, dev.PAD64, repacked)

    return DocBatch(w(batch.dots), batch.pay, batch.vv, w(batch.cloud))


@partial(jax.jit, static_argnames=("old_shift", "new_shift"))
def repack_narrow(batch: DocBatch, old_shift: int, new_shift: int) -> DocBatch:
    """Re-pack int32 rows at a smaller shift (replica-column growth that
    still fits a narrow layout). The caller must have verified every seq
    ever encoded is < 2**new_shift - 1 (strictly: the all-ones seq at the
    top column would collide with the pad). The map is monotone in
    (col, seq), so sorted rows stay sorted."""
    mask = (1 << old_shift) - 1

    def w(plane):
        repacked = ((plane >> old_shift) << new_shift) | (plane & mask)
        return jnp.where(plane == dev.PAD32, dev.PAD32, repacked)

    return DocBatch(w(batch.dots), batch.pay, batch.vv, w(batch.cloud))


@jax.jit
def gather_rows(batch: DocBatch, rows) -> DocBatch:
    """The rows a read pulls to the host, in ONE program (plane by plane
    an eager index is a handful of dispatches each)."""
    return DocBatch(*(p[rows] for p in batch))


@jax.jit
def clear_rows(batch: DocBatch, mask) -> DocBatch:
    """Reset masked rows to the identity document (eviction)."""
    pad = _pad_of(batch.dots.dtype)
    m = mask[:, None]
    return DocBatch(
        jnp.where(m, pad, batch.dots),
        jnp.where(m, -1, batch.pay),
        jnp.where(m, U32(0), batch.vv),
        jnp.where(m, pad, batch.cloud),
    )


@jax.jit
def place_rows(batch: DocBatch, rows: DocBatch, idx) -> DocBatch:
    """Write freshly-encoded rows into free slots (admission). Plane
    widths must already be harmonised by the caller."""
    return DocBatch(
        batch.dots.at[idx].set(rows.dots),
        batch.pay.at[idx].set(rows.pay),
        batch.vv.at[idx].set(rows.vv),
        batch.cloud.at[idx].set(rows.cloud),
    )


@partial(jax.jit, static_argnames=("rows",))
def grow_capacity(batch: DocBatch, rows: int) -> DocBatch:
    """Append identity rows (capacity growth, bucketed by the caller)."""
    pad = _pad_of(batch.dots.dtype)
    k = batch.dots.shape[0]

    def app(plane, fill):
        return jnp.concatenate(
            [plane, jnp.full((rows - k,) + plane.shape[1:], fill, plane.dtype)],
            axis=0,
        )

    return DocBatch(
        app(batch.dots, pad), app(batch.pay, -1), app(batch.vv, 0),
        app(batch.cloud, pad),
    )


@partial(jax.jit, static_argnames=("n_rep",))
def grow_reps(batch: DocBatch, n_rep: int) -> DocBatch:
    """Widen the vv plane for replica-column growth (interner append-only,
    so existing columns keep their meaning)."""
    k, r = batch.vv.shape
    vv = jnp.concatenate(
        [batch.vv, jnp.zeros((k, n_rep - r), U32)], axis=-1
    )
    return DocBatch(batch.dots, batch.pay, vv, batch.cloud)


def _ready(arr) -> bool:
    """True when a device array's host copy would not block."""
    try:
        return arr.is_ready()
    except AttributeError:
        return True  # no readiness API: reading is the only option


# ---- the store -------------------------------------------------------------


class ResidentStore:
    """Hot UJSON keys as device-resident DocBatch rows.

    Host-side bookkeeping: key->row map, free rows, the replica-id and
    payload interners (shared across every row, append-only), the current
    dot layout (shift), and the width upper bounds the fold kernels slice
    to. All device mutations go through the jitted kernels above.
    """

    ROW_BUCKET = 8  # capacity granularity (rows)
    # soft HBM budget for the resident planes: admission stops (keys fall
    # back to the host lattice) once the projected plane bytes cross it.
    # Width growth on already-resident keys is data the host would hold
    # in RAM anyway; admission count is the axis that must not run away
    BYTE_BUDGET = 256 << 20

    def __init__(self, n_rep: int = 8, mesh=None, shard_fn=None):
        self._mesh = mesh
        self._shard_fn = shard_fn  # parallel.shard_docbatch, mesh-bound
        self._nrep = bucket(n_rep, 4)
        self._shift = dev.narrow_shift(self._nrep)
        self._rid_cols: dict[int, int] = {}
        self._pay_ids: dict[tuple, int] = {}
        self._pay_rev: list[tuple] = []
        # canonical-wire-bytes -> pay id mirror (the native wire->planes
        # encoder interns payloads by their wire spans; identical
        # (path, token) pairs have identical canonical encodings)
        self._pay_wire: dict[bytes, int] = {}
        self._rows: dict[bytes, int] = {}
        self._free: list[int] = []
        self._batch: DocBatch | None = None
        # host-side width bounds as a BOUNDED PIPELINE: every fold
        # returns its live widths (async-copied to host at dispatch) and
        # joins the in-flight queue with its growth counts. The bound is
        # base (the newest CONSUMED fold's live, or admission widths) +
        # the growth of everything still in flight. Landed copies are
        # consumed for free; past PIPE_DEPTH the oldest is consumed
        # BLOCKING — which is exactly the backpressure that stops an
        # ever-wider fold backlog from snowballing device work
        self._base_w = 1
        self._base_c = 1
        self._floor_w = 1  # admission widths until the next exact read
        self._floor_c = 1
        self._inflight: list = []  # [(live_arr, grow_w, grow_c), ...]
        # lazily-batched broadcast deltas (fold_in_broadcast): joins
        # commute, so buffered rounds coalesce into ONE (R*D, W) fold at
        # the next read/drain/threshold — amortising the per-dispatch
        # latency that bounded the small-doc anti-entropy stream
        # (round-5 verdict item 5)
        self._bcast_pend: list = []
        # the largest seq ever encoded into the store: a causal context
        # covers its dot store, so the running max over delta vv/cloud
        # seqs bounds every seq on device — which is what makes the
        # narrow->narrow repack on replica growth provably safe
        self._max_seq = 0
        # `pin_shapes` (a deployment that keeps documents resident by
        # size): plane-width floors the fold's output never goes under,
        # and the fixed menu of grid shapes a fold pads to, so that a
        # stream of small drains meets only programs compiled at boot.
        # Unset (0 / None) every shape follows its data, as above
        self._min_w = 0
        self._min_c = 0
        self._pinned = False

    # -- interners ----------------------------------------------------------

    def pay(self, path, token) -> int:
        k = (path, token)
        pid = self._pay_ids.get(k)
        if pid is None:
            pid = self._pay_ids[k] = len(self._pay_rev)
            self._pay_rev.append(k)
        return pid

    def pay_lookup(self, pid: int):
        pt = self._pay_rev[pid]
        if type(pt) is bytes:
            # wire-interned payload: parse its canonical span on first
            # read (the drain never needs the parsed form — only decode
            # paths do, and only for payloads that survive to a read)
            from ..utils.wire import Reader

            r = Reader(pt)
            path = tuple(r.str_() for _ in range(r.varint()))
            pt = (path, r.str_())
            self._pay_rev[pid] = pt
            self._pay_ids.setdefault(pt, pid)
        return pt

    # -- introspection ------------------------------------------------------

    def __contains__(self, key: bytes) -> bool:
        return key in self._rows

    def __len__(self) -> int:
        return len(self._rows)

    def keys(self):
        return self._rows.keys()

    def block(self) -> None:
        """Wait for every queued device mutation (timing/shutdown)."""
        self._flush_broadcast()
        if self._batch is not None:
            jax.block_until_ready(self._batch.dots)

    def busy(self) -> bool:
        """True while a queued fold or placement has not finished on the
        device: a read now would wait for it."""
        return self._batch is not None and not _ready(self._batch.dots)

    def approx_bytes(self) -> int:
        """Projected resident plane footprint (current shapes)."""
        if self._batch is None:
            return 0
        return sum(p.size * p.dtype.itemsize for p in self._batch)

    def full(self) -> bool:
        """True when admission should stop (BYTE_BUDGET crossed): the
        serving repo keeps further keys on the host lattice."""
        return self.approx_bytes() >= self.BYTE_BUDGET

    # -- pinned shapes ------------------------------------------------------

    # (keys, deltas a key) of the fold programs a pinned store runs, by
    # rising key count; the last entry's keys are every row. A fold costs
    # the device keys x deltas^2 x slots membership probes (the flat fold
    # rule), so the program that spans every row is shallow and the deep
    # ones are narrow: a full drain folds every key's first MENU_ALL_D
    # deltas in one dispatch and the few keys that hold more in passes of
    # the smaller programs (my chip run, PR 39: every row x 32 deltas x 16
    # slots took ~1.2 s of device time a dispatch)
    MENU_PASSES = ((4, 64), (64, 32))
    MENU_ALL_D = 4
    MENU_W = 8  # entries / cloud dots a delta's grid row holds
    CLOUD_MIN = 256  # cloud slots a row keeps (a fold's bound adds its
    # group's cloud dots to the live width before compaction drops them)

    def pin_shapes(self) -> None:
        """Size the planes from what is resident NOW, with room, and fix
        the grid shapes folds pad to: rows x slots become powers of two
        with twice the widest row's slots, so a hot document's growth
        does not regrow (and recompile) inside a serving window; a fold
        then runs as passes of the few programs of `_passes`, each
        delta's entries and cloud dots padded to MENU_W. A delta wider
        than that does not fit the pinned grid (`fits`): the caller
        rewrites its row from the decoded view instead (`rewrite`). On a
        mesh the aligned grid spans every row whatever the drain, so
        only the depth and the widths are pinned there."""
        if self._batch is None:
            return
        self._min_w = 2 * bucket(self._base_w, 4)
        self._min_c = self.CLOUD_MIN
        bw, bc = self._batch.dots.shape[-1], self._batch.cloud.shape[-1]
        if self._min_w > bw or self._min_c > bc:
            self._batch = self._shard(slice_widths(
                self._batch, max(bw, self._min_w), max(bc, self._min_c)
            ))
        self._pinned = True

    def warm_pinned(self) -> None:
        """Compile what a serving window meets at the pinned shapes: one
        identity fold per program of `_passes` (rows stay as they are:
        the join's neutral element), a one-row rewrite of a row with its
        own document, and the one-row gather a read makes."""
        from .ujson_host import UJSON

        if not self._pinned or not self._rows:
            return
        keys = sorted(self._rows)
        for n, depth in self._passes():
            self.fold_in({k: [UJSON()] * depth for k in keys[:n]})
        self.rewrite([(keys[0], self.read(keys[0]))])
        self.block()

    def _passes(self) -> list[tuple[int, int]]:
        cap = self._row_axis()
        if self._mesh is not None:
            return [(cap, 2 * self.MENU_ALL_D)]
        return [p for p in self.MENU_PASSES if p[0] < cap] + [(cap, self.MENU_ALL_D)]

    def fits(self, delta) -> bool:
        """Does a delta fit a row of the pinned grid? (True while no
        shape is pinned: the grid then takes its widths from its data.)"""
        if not self._pinned:
            return True
        n = getattr(delta, "n_entries", None)
        if n is None:
            n, c = len(delta.entries), len(delta.ctx.cloud)
        else:
            c = delta.n_cloud
        return n <= self.MENU_W and c <= self.MENU_W

    def rewrite(self, items: list[tuple[bytes, object]]) -> None:
        """Replace resident rows by the encoding of the given documents
        (the caller's decoded views, current with everything pending):
        how a delta too wide for the pinned grid reaches its row without
        a fold in a new shape. One program a row count (a pinned store
        places them one at a time); rows wider than the planes widen
        them, as an admission does."""
        self._flush_broadcast()
        items = [(k, d) for k, d in items if k in self._rows]
        if not items:
            return
        if self._pinned and len(items) > 1:
            # one program a row count, and the boot compiled the one-row
            # placement only: a drain that rewrites two rows (my chip run,
            # PR 39: one of seven windows) must not compile a second
            for item in items:
                self.rewrite([item])
            return
        self._note_seqs([d for _, d in items])
        rows_np = self._encode_rows([d for _, d in items])
        # the width bound takes the rows' LENGTHS, not their padded
        # widths: a 1,300-leaf row encodes 2,048 wide, and a floor of
        # 2,048 would make the next fold ask for planes of 4,096
        pad = _pad_of(rows_np.dots.dtype)
        self._floor_w = max(self._floor_w, int((rows_np.dots != pad).sum(axis=1).max()))
        self._floor_c = max(self._floor_c, int((rows_np.cloud != pad).sum(axis=1).max()))
        self._base_w = max(self._base_w, self._floor_w)
        self._base_c = max(self._base_c, self._floor_c)
        self._place(rows_np, np.array([self._rows[k] for k, _ in items], np.int32))

    def plane_shape(self) -> tuple[int, int] | None:
        """(rows, slots) of the resident dot plane, None before the first
        admission."""
        return None if self._batch is None else tuple(self._batch.dots.shape)

    # -- layout plumbing ----------------------------------------------------

    def _row_axis(self) -> int:
        return self._batch.dots.shape[0] if self._batch is not None else 0

    def _capacity_for(self, rows: int) -> int:
        cap = bucket(max(rows, 2), self.ROW_BUCKET)
        if self._mesh is not None:
            m = self._mesh.devices.size
            cap += -cap % m
        return cap

    def _shard(self, batch: DocBatch) -> DocBatch:
        if self._shard_fn is None:
            return batch
        return self._shard_fn(batch)

    PIPE_DEPTH = 2  # folds allowed in flight before blocking on the oldest

    def _consume(self, block: bool) -> bool:
        """Consume the oldest in-flight fold's live widths into the
        base. The consumed fold's own growth is implicitly reflected in
        its measured live, so it leaves the in-flight sum."""
        if not self._inflight:
            return False
        arr, _gw, _gc = self._inflight[0]
        if not block and not _ready(arr):
            return False
        self._inflight.pop(0)
        lw, lc = (int(x) for x in jax.device_get(arr))
        # the floor covers rows admitted after the consumed fold
        # dispatched (their widths are invisible to its live output)
        self._base_w = max(lw, self._floor_w, 1)
        self._base_c = max(lc, self._floor_c, 1)
        return True

    def _budget_widths(self, grow_w: int, grow_c: int) -> tuple[int, int]:
        """Width targets for the next fold. The bound is the newest
        consumed fold's LIVE widths plus the growth counts of everything
        still in flight — an over-estimate whenever joins dedup
        (redelivery) or context compaction absorbs (contiguous dots),
        corrected as soon as a landed live-width copy is consumed. Past
        PIPE_DEPTH the consume BLOCKS: bounded pipelining, so a backlog
        of ever-wider folds can never snowball the device queue."""
        while self._consume(block=False):
            pass
        while len(self._inflight) >= self.PIPE_DEPTH:
            self._consume(block=True)
        ub_w = self._base_w + grow_w + sum(g for _, g, _c in self._inflight)
        ub_c = self._base_c + grow_c + sum(c for _, _g, c in self._inflight)
        if self._batch is None:
            return bucket(ub_w, 4), bucket(ub_c, 4)
        bw = self._batch.dots.shape[-1]
        bc = self._batch.cloud.shape[-1]
        out_w = max(bucket(ub_w, 4), self._min_w)
        out_c = max(bucket(ub_c, 4), self._min_c)
        # shape hysteresis: keep the current width unless it must grow
        # or can shrink 4x (no recompile thrash around a boundary)
        if out_w < bw and out_w * 4 > bw:
            out_w = bw
        if out_c < bc and out_c * 4 > bc:
            out_c = bc
        return out_w, out_c

    def _grid_to_device(self, grid: DocBatch) -> DocBatch:
        """Ship grid planes to the device, materialising all-identity
        planes on-device instead of transferring them (a sparse drain's
        vv plane is megabytes of zeros; anti-entropy deltas rarely carry
        vv entries at all — their dots ride in the cloud)."""
        pad = _pad_of(np.asarray(grid.dots).dtype)

        def put(p, fill):
            if isinstance(p, np.ndarray):
                uniform = (not p.any()) if fill == 0 else bool((p == fill).all())
                if uniform:
                    if fill == 0:
                        return jnp.zeros(p.shape, p.dtype)
                    return jnp.full(p.shape, fill, p.dtype)
            return jnp.asarray(p)

        return DocBatch(
            put(grid.dots, pad),
            put(grid.pay, -1),
            put(grid.vv, 0),
            put(grid.cloud, pad),
        )

    def _note_fold(self, batch: DocBatch, live, gw: int, gc: int) -> DocBatch:
        """Enqueue a fold in the bounded pipeline: keep its live-width
        scalars (host copy started in the background) and its growth
        counts for the in-flight bound."""
        self._inflight.append((live, gw, gc))
        try:
            live.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        return batch

    def _note_seqs(self, docs) -> None:
        """Track the max seq across delta contexts (context covers store,
        so vv+cloud bound the entries too). Wire deltas carry their
        measured max_seq — reading .ctx would defeat their laziness."""
        m = self._max_seq
        for d in docs:
            ms = getattr(d, "max_seq", None)
            if ms is not None:
                if ms > m:
                    m = ms
                continue
            for s in d.ctx.vv.values():
                if s > m:
                    m = s
            for _, s in d.ctx.cloud:
                if s > m:
                    m = s
        self._max_seq = m

    def _widen(self) -> None:
        if self._shift == 32:
            return
        if self._batch is not None:
            self._batch = self._shard(widen_rows(self._batch, self._shift))
        self._shift = 32

    def _ensure_reps(self) -> None:
        self._grow_reps_to(len(self._rid_cols))

    def _grow_reps_to(self, n: int) -> None:
        """Replica-column growth to at least n: widen vv columns, and
        re-pack the dot layout if the replica-column budget no longer
        fits — to a smaller narrow shift when every seq ever encoded
        still fits it, else to u64/32."""
        if self._shift != 32 and n > (1 << (31 - self._shift)):
            s2 = dev.narrow_shift(bucket(n, 4))
            if self._max_seq < (1 << s2) - 1:
                if self._batch is not None:
                    self._batch = self._shard(
                        repack_narrow(self._batch, self._shift, s2)
                    )
                self._shift = s2
            else:
                self._widen()
        if n > self._nrep:
            self._nrep = bucket(n, 4)
            if self._batch is not None:
                self._batch = self._shard(grow_reps(self._batch, self._nrep))

    def _encode_rows(self, docs) -> DocBatch:
        """Encode host docs at the store's current layout, migrating the
        store when the narrow layout can't hold them. OverflowError
        escapes only when even u64/32 can't (seq past u32)."""
        while True:
            try:
                b = dev._encode_docs_np(
                    docs, self._rid_cols, self.pay, self._nrep, shift=self._shift
                )
            except OverflowError:
                if self._shift == 32:
                    raise
                self._widen()
                continue
            except ValueError:  # rid interner outgrew the vv budget
                self._ensure_reps()
                continue
            # a successful encode at self._nrep proves the interner fits
            # it (the encoder checks); _ensure_reps only handles the
            # narrow-shift budget here
            self._ensure_reps()
            return b

    def _encode_grid(self, groups, depth: int = 0) -> DocBatch:
        """The (K, D, W) grid of a fold; ``depth`` is the pinned pass's
        (0 while no shape is pinned: the grid follows its data)."""
        wire = self._grid_from_wire(groups, depth)
        if wire is not None:
            return wire
        encode = dev.encode_doc_groups
        if depth:
            encode = partial(
                _encode_groups_menu, menu=(depth, self.MENU_W, self.MENU_W)
            )
        while True:
            try:
                g = encode(
                    groups, self._rid_cols, self.pay, self._nrep,
                    shift=self._shift,
                )
            except OverflowError:
                if self._shift == 32:
                    raise
                self._widen()
                continue
            except ValueError:
                self._ensure_reps()
                continue
            self._ensure_reps()
            return g

    def _grid_from_wire(self, groups, depth: int = 0) -> DocBatch | None:
        """The native wire->planes grid encoder: when every delta in the
        drain is a WireUJSON (the cluster receive path), the (K, D, W)
        grid fills straight from the raw payload bytes — per-delta host
        cost is native parsing + interning, no Python dict walks. Returns
        None (caller uses the object encoder) when the native library is
        missing or any delta is a plain document."""
        from ..native import lib
        from .ujson_wire import (
            GridOverflow,
            GridRepBudget,
            WireUJSON,
            grid_from_wire,
        )

        if lib() is None:
            return None
        flat = []
        for g in groups:
            for d in g:
                if type(d) is not WireUJSON:
                    return None
                flat.append(d)
        if not flat:
            return None
        d_dim = bucket(max(len(g) for g in groups), 1)
        w = bucket(max(max(d.n_entries for d in flat), 1), 4)
        c = bucket(max(max(d.n_cloud for d in flat), 1), 4)
        if depth:
            d_dim = max(d_dim, depth)
            w, c = max(w, self.MENU_W), max(c, self.MENU_W)
        rows = len(groups) * d_dim
        dest = np.fromiter(
            (
                k * d_dim + j
                for k, g in enumerate(groups)
                for j in range(len(g))
            ),
            np.int64,
            count=len(flat),
        )
        while True:
            known = [0] * len(self._rid_cols)
            for rid, col in self._rid_cols.items():
                known[col] = rid
            try:
                dots, pay, vv, cloud, new_rids, spans = grid_from_wire(
                    flat, dest, rows, w, c, self._shift, self._nrep, known
                )
            except GridOverflow:
                if self._shift == 32:
                    raise OverflowError("seq beyond the u64/32 layout")
                self._widen()
                continue
            except GridRepBudget as e:
                self._grow_reps_to(e.needed)
                continue
            break
        for rid in new_rids:
            self._rid_cols[rid] = len(self._rid_cols)
        self._ensure_reps()
        if self._nrep > vv.shape[-1]:
            # new columns crossed a vv bucket AFTER a successful fill:
            # widen the grid's vv plane to match the store
            vv = np.concatenate(
                [vv, np.zeros((rows, self._nrep - vv.shape[-1]), np.uint32)],
                axis=-1,
            )
        if spans:
            # new payloads intern by their canonical span; parsing to
            # (path, token) is deferred to pay_lookup (reads). A payload
            # that later ALSO arrives via the object path gets a second
            # id — harmless (ids just name payloads; dots dedup joins)
            lut = np.empty(len(spans), np.int32)
            pw = self._pay_wire
            rev = self._pay_rev
            for i, span in enumerate(spans):
                gid = pw.get(span)
                if gid is None:
                    gid = pw[span] = len(rev)
                    rev.append(span)
                lut[i] = gid
            pay = np.where(pay >= 0, lut[np.maximum(pay, 0)], -1)
        k = len(groups)
        return DocBatch(
            dots.reshape(k, d_dim, w),
            pay.reshape(k, d_dim, w),
            vv.reshape(k, d_dim, self._nrep),
            cloud.reshape(k, d_dim, c),
        )

    # -- admission / eviction ------------------------------------------------

    def admit(self, items: list[tuple[bytes, object]]) -> None:
        """Make keys resident with their current host docs (encoded ONCE;
        after this only reads ever decode them again)."""
        # buffered broadcasts target the rows present when they arrived
        self._flush_broadcast()
        items = [(k, d) for k, d in items if k not in self._rows]
        if not items:
            return
        self._note_seqs([d for _, d in items])
        # entries are not covered by _note_seqs' vv/cloud shortcut for
        # admitted FULL docs only in theory; the ORSWOT invariant (ctx
        # covers store) holds for every doc the host lattice builds, so
        # vv alone still bounds them
        rows_np = self._encode_rows([d for _, d in items])
        self._base_w = max(self._base_w, rows_np.dots.shape[-1])
        self._base_c = max(self._base_c, rows_np.cloud.shape[-1])
        # admitted rows can exceed any in-flight fold's live widths; the
        # floor survives consumes until the next exact full read
        self._floor_w = max(self._floor_w, rows_np.dots.shape[-1])
        self._floor_c = max(self._floor_c, rows_np.cloud.shape[-1])
        if self._batch is None:
            cap = self._capacity_for(len(items) + 1)
            pad = _pad_of(np.int32 if self._shift < 32 else np.uint64)
            dtype = np.int32 if self._shift < 32 else np.uint64
            w = rows_np.dots.shape[-1]
            c = rows_np.cloud.shape[-1]
            self._batch = self._shard(
                DocBatch(
                    jnp.asarray(np.full((cap, w), pad, dtype)),
                    jnp.asarray(np.full((cap, w), -1, np.int32)),
                    jnp.asarray(np.zeros((cap, self._nrep), np.uint32)),
                    jnp.asarray(np.full((cap, c), pad, dtype)),
                )
            )
            self._free = list(range(cap - 1, 0, -1))  # row 0 is scratch
        need = len(items)
        if len(self._free) < need:
            old = self._row_axis()
            cap = self._capacity_for(old + need - len(self._free))
            self._batch = self._shard(grow_capacity(self._batch, cap))
            self._free = list(range(cap - 1, old - 1, -1)) + self._free
        idx = np.empty(need, np.int32)
        for j, (key, _) in enumerate(items):
            row = self._free.pop()
            self._rows[key] = row
            idx[j] = row
        self._place(rows_np, idx)

    def _place(self, rows_np: DocBatch, idx: np.ndarray) -> None:
        """Write encoded rows into resident rows ``idx``, harmonising
        widths first: wider rows widen the planes, narrower ones pad."""
        bw, bc = self._batch.dots.shape[-1], self._batch.cloud.shape[-1]
        rw, rc = rows_np.dots.shape[-1], rows_np.cloud.shape[-1]
        if rw > bw or rc > bc:
            self._batch = self._shard(
                slice_widths(self._batch, max(rw, bw), max(rc, bc))
            )
            bw, bc = max(rw, bw), max(rc, bc)
        if rw < bw or rc < bc:
            rows_np = _pad_planes_np(rows_np, bw, bc)
        self._batch = self._shard(
            place_rows(self._batch, DocBatch(*(jnp.asarray(p) for p in rows_np)),
                       jnp.asarray(idx))
        )

    def evict(self, key: bytes):
        """Decode a key's current doc and drop its row (demotion to the
        host lattice, e.g. before a local write)."""
        doc = self.read(key)
        self.discard(key)
        return doc

    def discard(self, key: bytes) -> None:
        """Drop a key's row WITHOUT decoding (the caller already holds a
        current host view, e.g. the serving repo's read cache)."""
        self._flush_broadcast()  # the departing row must absorb its share
        row = self._rows.pop(key)
        mask = np.zeros(self._row_axis(), bool)
        mask[row] = True
        self._batch = self._shard(clear_rows(self._batch, jnp.asarray(mask)))
        self._free.append(row)

    # -- the drain ----------------------------------------------------------

    def fold_in(self, pending: dict[bytes, list], mark=None) -> None:
        """Fold each key's pending deltas into its resident row — ONE
        device dispatch for every key in the drain, no host read-backs.
        Raises OverflowError (rows unchanged) when a delta exceeds the
        u64/32 layout; the caller demotes those keys to the host
        lattice. ``mark`` (the repo's drain clock) is called where the
        host's encode ends and the dispatch begins."""
        self._flush_broadcast()
        pending = {k: v for k, v in pending.items() if v and k in self._rows}
        if not pending:
            return
        rows_n = depth = 0
        if self._pinned:
            # pinned shapes: the program that holds the drain's keys sets
            # how many deltas a key folds now; what is left folds in
            # further passes (the join is associative), never a new shape
            rows_n, depth = next(
                p for p in self._passes() if p[0] >= len(pending)
            )
            if any(len(v) > depth for v in pending.values()):
                self.fold_in({k: v[:depth] for k, v in pending.items()}, mark)
                self.fold_in(
                    {k: v[depth:] for k, v in pending.items() if len(v) > depth},
                    mark,
                )
                return
        self._note_seqs([d for lst in pending.values() for d in lst])
        # width bound: each row grows by at most its group's entry/cloud
        # counts (the join can only drop), so the batch max grows by at
        # most the largest group's counts. Wire deltas carry measured
        # counts; touching .entries would materialise them
        grow_w = grow_c = 0
        for lst in pending.values():
            ew = ec = 0
            for d in lst:
                n = getattr(d, "n_entries", None)
                if n is not None:
                    ew += n
                    ec += d.n_cloud
                else:
                    ew += len(d.entries)
                    ec += len(d.ctx.cloud)
            if ew > grow_w:
                grow_w = ew
            if ec > grow_c:
                grow_c = ec
        if self._mesh is None:
            # single device: the subset fold's grid covers exactly the
            # drained keys (the aligned grid spans every capacity row —
            # only worth it when sharding forbids gathers/scatters)
            self._fold_subset(pending, grow_w, grow_c, mark, rows_n, depth)
        else:
            self._fold_aligned(pending, grow_w, grow_c, mark, depth)

    # buffered broadcast deltas past this count force a flush, bounding
    # host memory and the single fold's delta axis. One large fold
    # shares its dispatches across more deltas than several smaller
    # ones, so the cap is a memory/width bound, not a performance knob
    BCAST_FLUSH_DELTAS = 16384

    def fold_in_broadcast(self, deltas: list) -> None:
        """Fold one delta list into EVERY resident row (the all-replicas
        anti-entropy shape). Same contracts as fold_in, but LAZY: the
        join is commutative and associative, so consecutive rounds buffer
        and coalesce into one (R*D, W) fold at the next read, per-key
        drain, admission/eviction, or threshold — one dispatch where the
        eager path paid one per round."""
        if not deltas or not self._rows:
            return
        self._note_seqs(deltas)
        self._bcast_pend.extend(deltas)
        if len(self._bcast_pend) >= self.BCAST_FLUSH_DELTAS:
            self._flush_broadcast()

    def _flush_broadcast(self) -> None:
        if not self._bcast_pend:
            return
        deltas, self._bcast_pend = self._bcast_pend, []
        if not self._rows:
            return
        from .ujson_host import UJSON
        # wire path: the whole list as ONE (1, D, W) grid segment
        grid = self._grid_from_wire([list(deltas)])
        if grid is not None:
            batch = self._grid_to_device(DocBatch(*(p[0] for p in grid)))
        else:
            d = bucket(len(deltas), 4)  # identity-pad: bound the jit cache
            rows_np = self._encode_rows(
                list(deltas) + [UJSON()] * (d - len(deltas))
            )
            batch = DocBatch(*(jnp.asarray(p) for p in rows_np))
        grow_w = grow_c = 0
        for x in deltas:
            n = getattr(x, "n_entries", None)
            if n is not None:
                grow_w += n
                grow_c += x.n_cloud
            else:
                grow_w += len(x.entries)
                grow_c += len(x.ctx.cloud)
        out_w, out_c = self._budget_widths(grow_w, grow_c)
        occ = np.zeros(self._row_axis(), bool)
        occ[list(self._rows.values())] = True
        # the delta batch's leading axis is deltas, not resident rows;
        # it stays replicated (only the resident planes are row-sharded)
        out, live = fold_broadcast_rows(
            self._batch, batch, jnp.asarray(occ),
            shift=self._shift, out_w=out_w, out_c=out_c,
        )
        self._batch = self._shard(self._note_fold(out, live, grow_w, grow_c))

    def _fold_subset(
        self, pending, grow_w: int, grow_c: int, mark, n: int, depth: int
    ) -> None:
        """``n`` x ``depth``: the pinned program's keys and deltas a key
        (0: no shape is pinned, the grid follows the drain)."""
        ks = sorted(pending)
        n = n or bucket(len(ks), 4)
        groups = [pending[k] for k in ks] + [[] for _ in range(n - len(ks))]
        grid = self._encode_grid(groups, depth)
        out_w, out_c = self._budget_widths(grow_w, grow_c)
        idx = np.zeros(n, np.int32)  # pad slots -> scratch row 0
        for j, k in enumerate(ks):
            idx[j] = self._rows[k]
        grid = self._grid_to_device(grid)
        if mark is not None:
            mark()
        out, live = fold_join_subset(
            self._batch, grid, jnp.asarray(idx), shift=self._shift,
            out_w=out_w, out_c=out_c,
        )
        self._batch = self._note_fold(out, live, grow_w, grow_c)

    def _fold_aligned(
        self, pending, grow_w: int, grow_c: int, mark, depth: int
    ) -> None:
        cap = self._row_axis()
        groups: list[list] = [[] for _ in range(cap)]
        for k, lst in pending.items():
            groups[self._rows[k]] = lst
        grid = self._encode_grid(groups, depth)
        out_w, out_c = self._budget_widths(grow_w, grow_c)
        grid = self._shard(self._grid_to_device(grid))
        if mark is not None:
            mark()
        out, live = fold_join_aligned(
            self._batch, grid, shift=self._shift, out_w=out_w, out_c=out_c
        )
        self._batch = self._shard(self._note_fold(out, live, grow_w, grow_c))

    # -- reads ---------------------------------------------------------------

    def read(self, key: bytes):
        """Decode ONE key's doc (device->host pull of its row slices)."""
        return self.read_many([key])[0]

    def read_many(self, keys: list[bytes]) -> list:
        self._flush_broadcast()
        rows = jnp.asarray(
            np.array([self._rows[k] for k in keys], np.int32)
        )
        sub = gather_rows(self._batch, rows)
        np_sub = DocBatch(*jax.device_get(tuple(sub)))  # one transfer
        # full-read detection must reject duplicate keys: a duplicated
        # subset could pass the length check and re-tighten (then slice)
        # below an unread row's live width
        if len(keys) == len(self._rows) and len(set(keys)) == len(keys):
            # a full read pulled every row anyway: re-tighten the width
            # bounds (and re-bucket the planes) for free
            pad = _pad_of(np_sub.dots.dtype)
            self._base_w = max(int((np_sub.dots != pad).sum(axis=1).max()), 1)
            self._base_c = max(int((np_sub.cloud != pad).sum(axis=1).max()), 1)
            self._inflight.clear()  # the pull reflects every queued fold
            self._floor_w = self._floor_c = 1
            w = max(bucket(self._base_w, 4), self._min_w)
            c = max(bucket(self._base_c, 4), self._min_c)
            if (
                w < self._batch.dots.shape[-1]
                or c < self._batch.cloud.shape[-1]
            ):
                self._batch = self._shard(slice_widths(self._batch, w, c))
        cols_rid = {c: r for r, c in self._rid_cols.items()}
        docs = dev.decode_batch(
            np_sub, cols_rid, self.pay_lookup, shift=self._shift
        )
        if len(keys) == len(self._rows) and len(set(keys)) == len(keys):
            self._compact_pay(np_sub)
        return docs

    def _compact_pay(self, np_sub: DocBatch) -> None:
        """Payload-interner epoch compaction (the ops/interner.py hazard:
        append-only tables leak under value churn). Runs on full reads —
        the pulled pay planes ARE the live-id census — when dead ids
        dominate: rebuild the interner from the live ids and remap the
        device plane through a table in one dispatch."""
        live = np.unique(np_sub.pay)
        live = live[live >= 0]
        if len(self._pay_rev) <= 2 * max(len(live), 16):
            return
        table = np.full(len(self._pay_rev), -1, np.int32)
        new_rev = []
        for pid in live:
            table[pid] = len(new_rev)
            new_rev.append(self._pay_rev[pid])
        self._pay_rev = new_rev
        self._pay_ids = {k: i for i, k in enumerate(new_rev)}
        self._pay_wire = {
            span: int(table[pid])
            for span, pid in self._pay_wire.items()
            if table[pid] >= 0
        }
        self._batch = self._shard(remap_pay(self._batch, jnp.asarray(table)))

    def dump(self) -> list[tuple[bytes, object]]:
        """Decode every resident key (snapshots / bootstrap sync)."""
        if not self._rows:
            return []
        keys = sorted(self._rows)
        return list(zip(keys, self.read_many(keys)))


def _encode_groups_menu(
    groups, rid_cols, pay_ids, n_rep: int, shift: int, menu
) -> DocBatch:
    """`ujson_device.encode_doc_groups` at pinned shapes: the (K, D, W)
    grid padded up to the menu's depth and widths, as host numpy planes
    (the pads are identity documents and pad slots, so the fold is the
    same fold)."""
    from .ujson_host import UJSON

    d_min, w_min, c_min = menu
    d = max(bucket(max((len(g) for g in groups), default=1), 1), d_min)
    # encode the deltas that are there and place them: the grid is
    # mostly identity slots (K rows x D deltas for a few deltas a key)
    flat = [x for g in groups for x in g] or [UJSON()]
    b = dev._encode_docs_np(flat, rid_cols, pay_ids, n_rep, shift=shift)
    w = max(w_min, b.dots.shape[-1])
    c = max(c_min, b.cloud.shape[-1])
    dest = np.fromiter(
        (k * d + j for k, g in enumerate(groups) for j in range(len(g))),
        np.int64,
    )
    pad = _pad_of(b.dots.dtype)
    rows = len(groups) * d
    dots = np.full((rows, w), pad, b.dots.dtype)
    pay = np.full((rows, w), -1, np.int32)
    vv = np.zeros((rows, n_rep), np.uint32)
    cloud = np.full((rows, c), pad, b.cloud.dtype)
    if len(dest):
        dots[dest, : b.dots.shape[-1]] = b.dots
        pay[dest, : b.pay.shape[-1]] = b.pay
        vv[dest] = b.vv
        cloud[dest, : b.cloud.shape[-1]] = b.cloud
    return DocBatch(
        *(p.reshape((len(groups), d) + p.shape[1:]) for p in (dots, pay, vv, cloud))
    )


def _pad_planes_np(batch: DocBatch, w: int, c: int) -> DocBatch:
    pad = _pad_of(batch.dots.dtype)
    k = batch.dots.shape[0]

    def padto(plane, width, fill):
        extra = width - plane.shape[-1]
        if extra <= 0:
            return plane
        return np.concatenate(
            [plane, np.full((k, extra), fill, plane.dtype)], axis=-1
        )

    return DocBatch(
        padto(batch.dots, w, pad), padto(batch.pay, w, -1), batch.vv,
        padto(batch.cloud, c, pad),
    )
