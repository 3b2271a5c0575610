"""UJSON ORSWOT join as batched device kernels.

The host lattice (`ops/ujson_host.py`) is authoritative for serving —
documents are small and pointer-heavy. What DOES tensorise is the
anti-entropy fan-in (docs/_docs/types/ujson.md:134-182 semantics,
reference loop repo_ujson.pony:96-110): joining many deltas into many
replica documents, where the per-entry set operations dominate. This
module represents a batch of documents as padded per-row tensors and
implements the ORSWOT join as sorted-set ops:

* ``dots (B, L)`` — each entry's causal dot packed as
  ``(replica_col << shift) | seq``, sorted ascending per row, pad-filled.
  Replica ids (64-bit hashes) are interned to columns on the host,
  exactly like the counter repos. The dtype is ADAPTIVE per batch:
  when every seq fits in ``31 - ceil(log2 R)`` bits the dots pack into
  native-sortable **int32** (TPUs have no 64-bit datapath; u64 sorts
  emulate compares and dominated the join's cost when this module used
  them unconditionally), otherwise uint64 with shift 32. The shift is a
  static jit parameter, so each layout compiles its own kernels.
* ``pay (B, L) int32`` — interned (path, value-token) payload id; -1 pad.
  Dots name payloads immutably (a dot's (path, value) never changes), so
  the join only moves ids and the host interner resolves them back.
* ``vv (B, R) uint32`` — per-replica-column contiguous causal max.
* ``cloud (B, C)`` — context dots beyond the vv, sorted, pad-filled (same
  dtype as ``dots``). Device joins never compact cloud→vv (that
  bookkeeping is sequential and host-cheap); coverage stays exact
  because ``contains`` checks the union vv ∪ cloud either way.

Join of rows a, b (the documented add-wins rule):
  keep an a-entry iff it is also in b, or b's context never observed it;
  add a b-entry iff a doesn't hold it and a's context never observed it.
Membership tests are ``searchsorted`` probes on the sorted dot rows;
coverage is a vv gather + compare plus a cloud probe; the surviving
entries merge by one concat + sort per side pair. Everything is static
shape: output widths are the (padded) sums of the input widths, and the
host re-buckets between rounds (`compact`).

``fold_deltas`` is where the TPU earns its keep: the join is associative
and commutative, so N deltas fold in ceil(log_8 N) batched device calls
(8 rows reduce per launch — dispatch cost is per launch) instead of N
sequential host merges, and the folded delta
then joins every replica in ONE batched call.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.batching import bucket

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

PAD64 = np.uint64(0xFFFFFFFFFFFFFFFF)
PAD32 = np.int32(0x7FFFFFFF)


def _pad_of(dtype) -> np.generic:
    return PAD32 if np.dtype(dtype) == np.int32 else PAD64


class DocBatch(NamedTuple):
    """B documents as padded device tensors (see module docstring)."""

    dots: jax.Array  # (B, L) int32 or uint64, sorted per row, pad-filled
    pay: jax.Array  # (B, L) int32, -1 pad
    vv: jax.Array  # (B, R) uint32
    cloud: jax.Array  # (B, C) same dtype as dots, sorted, pad-filled


def _member(sorted_row, queries):
    """For each query, is it present in the sorted (pad-filled) row?"""
    idx = jnp.searchsorted(sorted_row, queries)
    idx = jnp.minimum(idx, sorted_row.shape[-1] - 1)
    return sorted_row[idx] == queries


def _covered(vv, cloud, dots, shift):
    """ctx.contains for each dot: seq <= vv[rid] or dot in cloud.

    The vv lookup runs as a per-replica-column mask reduction instead of
    a computed-index gather (pathologically slow on this TPU); R is
    small and static."""
    dt = dots.dtype
    r = vv.shape[-1]
    rid = (dots >> dt.type(shift)).astype(I32)
    seq = (dots & dt.type((1 << shift) - 1)).astype(U32)
    rid = jnp.minimum(rid, r - 1)  # pads decode out of range; callers mask
    colmask = rid[None, :] == jnp.arange(r, dtype=I32)[:, None]  # (R, W)
    vvd = jnp.sum(jnp.where(colmask, vv[:, None], U32(0)), axis=0, dtype=U32)
    return (seq <= vvd) | _member(cloud, dots)


def _sortmerge(row_a, pay_a, row_b, pay_b):
    """Merge two masked rows into one sorted row (pays ride along)."""
    dots = jnp.concatenate([row_a, row_b], axis=-1)
    pays = jnp.concatenate([pay_a, pay_b], axis=-1)
    order = jnp.argsort(dots)
    return dots[order], pays[order]


def _join_row(
    a_dots, a_pay, a_vv, a_cloud, b_dots, b_pay, b_vv, b_cloud,
    shift, sort_output=True,
):
    pad = _pad_of(a_dots.dtype)
    valid_a = a_dots != pad
    valid_b = b_dots != pad
    keep_a = valid_a & (
        _member(b_dots, a_dots) | ~_covered(b_vv, b_cloud, a_dots, shift)
    )
    # no duplicate survivors: an added b-entry is by definition not in a
    add_b = valid_b & ~_member(a_dots, b_dots) & ~_covered(
        a_vv, a_cloud, b_dots, shift
    )
    ka_dots = jnp.where(keep_a, a_dots, pad)
    ka_pay = jnp.where(keep_a, a_pay, -1)
    ab_dots = jnp.where(add_b, b_dots, pad)
    ab_pay = jnp.where(add_b, b_pay, -1)
    if sort_output:
        dots, pay = _sortmerge(ka_dots, ka_pay, ab_dots, ab_pay)
    else:
        # the sort is the join's dominant cost; a FINAL join whose output
        # feeds no further searchsorted can skip it
        dots = jnp.concatenate([ka_dots, ab_dots], axis=-1)
        pay = jnp.concatenate([ka_pay, ab_pay], axis=-1)
    vv = jnp.maximum(a_vv, b_vv)
    # context union; duplicates are harmless for coverage but dedup keeps
    # growth linear: sort, blank repeats, resort
    cl = jnp.sort(jnp.concatenate([a_cloud, b_cloud], axis=-1))
    dup = jnp.concatenate([jnp.zeros((1,), bool), cl[1:] == cl[:-1]])
    cloud = jnp.sort(jnp.where(dup, pad, cl))
    return dots, pay, vv, cloud


@partial(jax.jit, static_argnames=("shift", "sort_output"))
def join_batch(
    a: DocBatch, b: DocBatch, shift: int = 32, sort_output: bool = True
) -> DocBatch:
    """Row-wise ORSWOT join of two document batches (row i joins row i).

    Output widths are the sums of the input widths (static shapes); use
    `compact` on the host to re-bucket when they grow past the live size.
    ``sort_output=False`` only when nothing will searchsorted-probe the
    result (e.g. the last join before a host read-back).
    """
    return DocBatch(
        *jax.vmap(partial(_join_row, shift=shift, sort_output=sort_output))(
            a.dots, a.pay, a.vv, a.cloud, b.dots, b.pay, b.vv, b.cloud
        )
    )


FOLD_ARITY = 8  # rows folded per unrolled fold level


def _join_inside(a: DocBatch, b: DocBatch, shift: int) -> DocBatch:
    return DocBatch(
        *jax.vmap(partial(_join_row, shift=shift))(
            a.dots, a.pay, a.vv, a.cloud, b.dots, b.pay, b.vv, b.cloud
        )
    )


def _empty_rows(batch: DocBatch, n: int) -> DocBatch:
    """n identity rows (no entries, empty context) at batch's widths."""
    pad = _pad_of(batch.dots.dtype)
    return DocBatch(
        jnp.full((n, batch.dots.shape[-1]), pad, batch.dots.dtype),
        jnp.full((n, batch.pay.shape[-1]), -1, I32),
        jnp.zeros((n, batch.vv.shape[-1]), U32),
        jnp.full((n, batch.cloud.shape[-1]), pad, batch.cloud.dtype),
    )


def _fold_body(batch: DocBatch, shift: int) -> DocBatch:
    """Traceable full fold: the level loop unrolls at trace time (shapes
    are static), so however many levels, the caller pays ONE dispatch."""
    while batch.dots.shape[0] > 1:
        n = batch.dots.shape[0]
        k = min(FOLD_ARITY, 1 << (n - 1).bit_length())
        if n % k:
            pad = _empty_rows(batch, k - n % k)
            batch = DocBatch(
                *(jnp.concatenate([p, q], axis=0) for p, q in zip(batch, pad))
            )
            n = batch.dots.shape[0]
        step = n // k
        items = [
            DocBatch(*(p[i * step : (i + 1) * step] for p in batch))
            for i in range(k)
        ]
        while len(items) > 1:
            items = [
                _join_inside(items[i], items[i + 1], shift)
                for i in range(0, len(items), 2)
            ]
        batch = items[0]
    return batch


@partial(jax.jit, static_argnames=("shift",))
def fold_deltas(batch: DocBatch, shift: int = 32) -> DocBatch:
    """Fold all B rows into ONE document in a single device dispatch (the
    join is associative and commutative, so any fold shape converges
    identically; FOLD_ARITY-wide levels keep the trace shallow)."""
    return _fold_body(batch, shift)


@partial(jax.jit, static_argnames=("shift",))
def fold_segments(batch: DocBatch, shift: int = 32) -> DocBatch:
    """Segmented multi-key fan-in: planes shaped (K, D, W); every key's D
    delta rows fold to ONE document, all keys in the SAME dispatch — K
    keys' anti-entropy fan-ins for a single launch's latency. The
    reference converges one delta at a time per key
    (repo_ujson.pony:96-110); here the whole drain is one device program.
    The key axis is a plain vmap over the single-key fold body, so the
    two paths can never diverge."""
    folded = jax.vmap(lambda b: _fold_body(b, shift))(batch)
    return DocBatch(*(p[:, 0] for p in folded))


def encode_doc_groups(
    groups, rid_cols: dict[int, int], pay_ids, n_rep: int, shift: int = 32
) -> DocBatch:
    """Pack K keys' delta lists into the (K, D, W) grid `fold_segments`
    takes; short groups pad with identity docs (the join's neutral
    element), so the fold result per key is exactly the fold of its own
    deltas."""
    from .ujson_host import UJSON

    d = bucket(max((len(g) for g in groups), default=1), 1)
    empty = UJSON()
    flat = []
    for g in groups:
        flat.extend(g)
        flat.extend([empty] * (d - len(g)))
    b = _encode_docs_np(flat, rid_cols, pay_ids, n_rep, shift=shift)
    return DocBatch(
        *(
            jnp.asarray(p.reshape((len(groups), d) + p.shape[1:]))
            for p in b
        )
    )


def _tile(delta_row: DocBatch, b: int) -> DocBatch:
    return DocBatch(
        *(jnp.broadcast_to(p, (b,) + p.shape[1:]) for p in delta_row)
    )


def broadcast_join(
    replicas: DocBatch,
    delta_row: DocBatch,
    shift: int = 32,
    sort_output: bool = True,
) -> DocBatch:
    """Join ONE folded delta into every replica row in one batched call."""
    return join_batch(
        replicas,
        _tile(delta_row, replicas.dots.shape[0]),
        shift=shift,
        sort_output=sort_output,
    )


@partial(jax.jit, static_argnames=("shift", "sort_output"))
def fold_and_broadcast(
    replicas: DocBatch,
    deltas: DocBatch,
    shift: int = 32,
    sort_output: bool = False,
) -> DocBatch:
    """The whole anti-entropy fan-in as ONE device program: fold all
    delta rows, then join the result into every replica row. The rows
    are small, so per-dispatch overhead would dominate separate launches:
    the fold levels and the broadcast stay one program."""
    folded = _fold_body(deltas, shift)
    b = replicas.dots.shape[0]
    return DocBatch(
        *jax.vmap(partial(_join_row, shift=shift, sort_output=sort_output))(
            replicas.dots,
            replicas.pay,
            replicas.vv,
            replicas.cloud,
            *_tile(folded, b),
        )
    )


# ---- host-side encode / decode / compaction --------------------------------


def plan_shift(docs, n_rep: int) -> int:
    """Pick the dot layout for a batch: int32 with the smallest workable
    shift when every seq fits (native TPU sorts), else the u64/32 layout.
    The all-ones seq is reserved in the narrow layout: the top replica
    column with an all-ones seq would pack to exactly PAD32 and vanish
    as padding.
    """
    seq_bits = narrow_shift(n_rep)
    wide = (1 << seq_bits) - 1
    # per-container max() builtins instead of per-item Python compares:
    # this scan runs on every drain, right next to the encode hot loop
    for doc in docs:
        if doc.entries and max(s for _, s in doc.entries) >= wide:
            return 32
        vv = doc.ctx.vv
        if vv and max(vv.values()) >= wide:
            return 32
        cl = doc.ctx.cloud
        if cl and max(s for _, s in cl) >= wide:
            return 32
    return seq_bits


def _slot_cols(lens: np.ndarray) -> np.ndarray:
    """Per-row slot columns for variable-length rows, vectorised:
    [0..lens[0]) ++ [0..lens[1]) ++ ... with no Python per-row loop."""
    total = int(lens.sum())
    starts = np.cumsum(lens) - lens
    return np.arange(total, dtype=np.int64) - np.repeat(starts, lens)


def narrow_shift(n_rep: int) -> int:
    """The int32 layout's shift for this replica-column budget."""
    return 31 - max(int(n_rep - 1).bit_length(), 1)


def _encode_docs_np(
    docs, rid_cols: dict[int, int], pay_ids, n_rep: int, shift: int = 32
) -> DocBatch:
    """`encode_docs` core, returning host numpy planes (callers that
    reshape or concatenate do it host-side, then transfer ONCE — a jnp
    reshape is a device dispatch of its own).

    This is the serving path's host bottleneck (the device fold is ~free
    next to it), so the loop accumulates flat lists only — no per-doc
    allocations, no sorting of singleton rows — and every plane fills
    with one fancy-index scatter built from vectorised row/column
    indices."""
    seq_cap = 1 << shift
    setd = rid_cols.setdefault
    b = len(docs)
    d_lens = np.zeros(b, np.int64)
    c_lens = np.zeros(b, np.int64)
    dv: list[int] = []
    pv: list[int] = []
    cv: list[int] = []
    vv_ri: list[int] = []
    vv_ci: list[int] = []
    vv_sv: list[int] = []
    for i, doc in enumerate(docs):
        n0 = len(dv)
        for (rid, seq), pt in doc.entries.items():
            col = setd(rid, len(rid_cols))
            if seq >= seq_cap:
                raise OverflowError(f"seq {seq} needs a wider layout than {shift}")
            dv.append((col << shift) | seq)
            pv.append(pay_ids(*pt))
        k = len(dv) - n0
        if k > 1:  # rows must be dot-sorted; singletons already are
            seg = sorted(zip(dv[n0:], pv[n0:]))
            dv[n0:] = [d for d, _ in seg]
            pv[n0:] = [p for _, p in seg]
        d_lens[i] = k
        for rid, s in doc.ctx.vv.items():
            col = setd(rid, len(rid_cols))
            if s >= seq_cap or s > 0xFFFFFFFF:
                # clamping would SHRINK coverage and resurrect removed
                # entries — refuse; callers fall back to the host lattice
                raise OverflowError(f"vv seq {s} needs a wider layout")
            vv_ri.append(i)
            vv_ci.append(col)
            vv_sv.append(s)
        n0c = len(cv)
        for rid, seq in doc.ctx.cloud:
            col = setd(rid, len(rid_cols))
            if seq >= seq_cap:
                raise OverflowError(f"seq {seq} needs a wider layout than {shift}")
            cv.append((col << shift) | seq)
        kc = len(cv) - n0c
        if kc > 1:
            cv[n0c:] = sorted(cv[n0c:])
        c_lens[i] = kc
    dtype = np.int32 if shift < 32 else np.uint64
    pad = _pad_of(dtype)
    if len(rid_cols) > n_rep:
        raise ValueError(f"n_rep {n_rep} too small for {len(rid_cols)} replicas")
    wl = bucket(max(int(d_lens.max()) if b else 0, 1), 4)
    wc = bucket(max(int(c_lens.max()) if b else 0, 1), 4)
    dots = np.full((b, wl), pad, dtype)
    pay = np.full((b, wl), -1, np.int32)
    vv = np.zeros((b, n_rep), np.uint32)
    cloud = np.full((b, wc), pad, dtype)
    if dv:
        dvals = np.asarray(dv, dtype)
        if bool((dvals == pad).any()):
            raise OverflowError("dot collides with the pad sentinel")
        rows_i = np.repeat(np.arange(b), d_lens)
        cols_i = _slot_cols(d_lens)
        dots[rows_i, cols_i] = dvals
        pay[rows_i, cols_i] = np.asarray(pv, np.int32)
    if vv_ri:
        vv[np.asarray(vv_ri, np.int64), np.asarray(vv_ci, np.int64)] = np.asarray(
            vv_sv, np.uint32
        )
    if cv:
        cvals = np.asarray(cv, dtype)
        if bool((cvals == pad).any()):
            raise OverflowError("dot collides with the pad sentinel")
        cloud[np.repeat(np.arange(b), c_lens), _slot_cols(c_lens)] = cvals
    return DocBatch(dots, pay, vv, cloud)


def encode_docs(
    docs, rid_cols: dict[int, int], pay_ids, n_rep: int, shift: int = 32
) -> DocBatch:
    """Pack host `UJSON` documents into one DocBatch at the given layout
    (see `plan_shift`).

    rid_cols: replica-id -> column (shared, grows on host like the
    counter repos' _rids). pay_ids: callable (path, token) -> int32 id.
    """
    return DocBatch(
        *(jnp.asarray(p) for p in _encode_docs_np(docs, rid_cols, pay_ids, n_rep, shift))
    )


def decode_batch(batch: DocBatch, cols_rid, pay_lookup, shift: int = 32) -> list:
    """Unpack every row back into host `UJSON` docs (reads/verification).

    cols_rid: column -> replica id; pay_lookup: id -> (path, token).
    Each plane transfers device->host exactly ONCE — per-row pulls would
    pay a dispatch and a sync B×4 times.
    """
    from .ujson_host import UJSON

    pad = _pad_of(np.asarray(batch.dots).dtype)
    mask = (1 << shift) - 1
    all_dots = np.asarray(batch.dots)
    all_pays = np.asarray(batch.pay)
    all_vv = np.asarray(batch.vv)
    all_cloud = np.asarray(batch.cloud)
    docs = []
    for row in range(all_dots.shape[0]):
        doc = UJSON()
        # the live slots as Python ints in one step each: a 1,000-leaf row
        # of 2,048 slots is not walked numpy scalar by numpy scalar
        live = all_dots[row] != pad
        entries = doc.entries
        for d, p in zip(all_dots[row][live].tolist(), all_pays[row][live].tolist()):
            entries[(cols_rid[d >> shift], d & mask)] = pay_lookup(p)
        for col in np.flatnonzero(all_vv[row]).tolist():
            doc.ctx.vv[cols_rid[col]] = int(all_vv[row][col])
        for c in all_cloud[row][all_cloud[row] != pad].tolist():
            doc.ctx.cloud.add((cols_rid[c >> shift], c & mask))
        doc.ctx.compact()
        docs.append(doc)
    return docs


def decode_doc(batch: DocBatch, row: int, cols_rid, pay_lookup, shift: int = 32):
    """Single-row convenience wrapper over `decode_batch`."""
    one = DocBatch(*(p[row : row + 1] for p in batch))
    return decode_batch(one, cols_rid, pay_lookup, shift=shift)[0]


def compact(batch: DocBatch) -> DocBatch:
    """Host-side re-bucket: drop all-pad columns the joins accumulated."""
    dots = np.asarray(batch.dots)
    cloud = np.asarray(batch.cloud)
    pad = _pad_of(dots.dtype)
    live_l = int((dots != pad).sum(axis=1).max()) if dots.size else 1
    live_c = int((cloud != pad).sum(axis=1).max()) if cloud.size else 1
    wl, wc = bucket(max(live_l, 1), 4), bucket(max(live_c, 1), 4)
    return DocBatch(
        jnp.asarray(dots[:, :wl]),
        jnp.asarray(np.asarray(batch.pay)[:, :wl]),
        batch.vv,
        jnp.asarray(cloud[:, :wc]),
    )
