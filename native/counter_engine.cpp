// Native counter engine — the GCOUNT/PNCOUNT host-state surface.
//
// The reference executes every command inside compiled Pony actors
// (repo_gcount.pony:25-60, repo_pncount.pony:26-67); the rebuild's
// Python engine seam tops out on interpreter dispatch. The counter
// tables (engine.h Table) own the counters' HOST state (key table, own
// contributions, serving value cache, dirty/pending/foreign bookkeeping
// — the exact fields jylis_tpu/models/repo_counters.py otherwise keeps
// in dicts); whole pipelined bursts apply through the all-types batch
// applier in serve_engine.cpp.
//
// Split of responsibilities (single source of truth):
//   * native: per-key own/value/dirty/pending-own + INC/DEC/GET, and the
//     foreign window: the columns peers converged into a row, held once
//     (cumulative, by column), folded in by one call a slice
//     (`jy_eng_fold_foreign`) and read by the sync digest
//     (`jy_eng_sync_cols`) and by the drain, which leaves as one ready
//     u64 matrix (`jy_eng_export_drain`)
//   * Python: the replica id -> column map, device drains,
//     flush/snapshot orchestration — all via the bulk calls below.
//
// All values are u64 bit patterns; PNCOUNT's serving value is the
// two's-complement wrapped i64 the reference's (p-n).i64() defines.

#include "engine.h"

using namespace jy;

extern "C" {

void* jy_eng_new() { return new Engine(); }
void jy_eng_free(void* e) { delete static_cast<Engine*>(e); }

int64_t jy_eng_rows(void* e, int32_t which) {
    return static_cast<Engine*>(e)->t[which].idx.rows();
}

int64_t jy_eng_upsert(void* e, int32_t which, const uint8_t* k, int64_t n) {
    return static_cast<Engine*>(e)->t[which].upsert(k, n);
}

int64_t jy_eng_find(void* e, int32_t which, const uint8_t* k, int64_t n) {
    return static_cast<Engine*>(e)->t[which].find(k, n);
}

void jy_eng_key(void* e, int32_t which, int64_t row, const uint8_t** ptr,
                int64_t* len) {
    Table& t = static_cast<Engine*>(e)->t[which];
    *ptr = t.idx.key_ptr(row);
    *len = t.idx.key_len[row];
}

void jy_eng_inc(void* e, int32_t which, int64_t row, int32_t polarity,
                uint64_t amount) {
    static_cast<Engine*>(e)->t[which].bump(row, polarity, amount);
}

int32_t jy_eng_is_foreign(void* e, int32_t which, int64_t row) {
    return (static_cast<Engine*>(e)->t[which].flags[row] & F_FOREIGN) ? 1 : 0;
}

uint64_t jy_eng_value(void* e, int32_t which, int64_t row) {
    return static_cast<Engine*>(e)->t[which].value[row];
}

uint64_t jy_eng_own(void* e, int32_t which, int64_t row, int32_t polarity) {
    Table& t = static_cast<Engine*>(e)->t[which];
    return polarity ? t.own_n[row] : t.own_p[row];
}

// every key of a slice in one call: `blob` is the keys end to end
void jy_eng_upsert_many(void* e, int32_t which, const uint8_t* blob,
                        const int64_t* lens, int64_t n, int64_t* rows) {
    Table& t = static_cast<Engine*>(e)->t[which];
    for (int64_t i = 0; i < n; i++) {
        rows[i] = t.upsert(blob, lens[i]);
        blob += lens[i];
    }
}

// a slice of foreign deltas joins the window: every key's row is marked
// foreign and sync-dirty, and its cells fold in by max. The cells lie
// key-major, then by polarity: `counts[k * npol + pol]` of them each.
// A cell at `adopt_col` (>= 0: a restore, whose batch carries this
// node's own column) is also adopted as the row's own contribution, or
// a later INC would vanish under it. Returns the cells folded.
int64_t jy_eng_fold_foreign(void* e, int32_t which, const int64_t* key_rows,
                            int64_t n_keys, int32_t npol,
                            const int32_t* counts, const int32_t* cols,
                            const uint64_t* vals, int32_t adopt_col) {
    Table& t = static_cast<Engine*>(e)->t[which];
    int64_t at = 0;
    for (int64_t k = 0; k < n_keys; k++) {
        int64_t row = key_rows[k];
        if (!(t.flags[row] & F_FOREIGN)) {
            t.flags[row] |= F_FOREIGN;
            t.foreign_rows.push_back(row);
        }
        t.mark_sync(row);
        for (int32_t pol = 0; pol < npol; pol++) {
            for (int32_t j = counts[k * npol + pol]; j > 0; j--, at++) {
                uint64_t v = vals[at];
                Table::FCell& c = t.fcell(row, static_cast<uint32_t>(cols[at]));
                uint64_t& cur = pol ? c.n : c.p;
                if (v > cur) cur = v;
                if (cols[at] == adopt_col) {
                    uint64_t& own = pol ? t.own_n[row] : t.own_p[row];
                    if (v > own) own = v;
                    t.flags[row] |= pol ? F_OWNSET_N : F_OWNSET_P;
                }
            }
        }
    }
    return at;
}

// rows the next drain carries: own-pending rows and foreign rows
int64_t jy_eng_drain_count(void* e, int32_t which) {
    Table& t = static_cast<Engine*>(e)->t[which];
    int64_t n = static_cast<int64_t>(t.pend_rows.size());
    for (int64_t r : t.foreign_rows)
        if (!(t.flags[r] & (F_PEND_P | F_PEND_N))) n++;
    return n;
}

// the drain batch, ready: `rows` and the zeroed (cap, npol * rep_cap) u64
// matrix [P | N] get the pending own values at `own_col` joined with a
// foreign row's columns; the i-th row of the batch is the matrix's i-th,
// or with `by_row` (a dense drain, cap = the plane's rows) the one at
// its own row number. Nothing clears: the window goes in
// `jy_eng_finish_drain`, so a device failure mid-drain leaves every
// contribution for the retry. Returns the rows written, or -1 when the
// batch does not fit `cap` rows or a column lies beyond `rep_cap`.
int64_t jy_eng_export_drain(void* e, int32_t which, int32_t own_col,
                            int32_t rep_cap, int32_t npol, int32_t by_row,
                            int64_t* rows, uint64_t* mat, int64_t cap) {
    Table& t = static_cast<Engine*>(e)->t[which];
    if (own_col >= rep_cap) return -1;
    const int64_t width = static_cast<int64_t>(npol) * rep_cap;
    int64_t n = 0;
    auto emit = [&](int64_t r) -> bool {
        if (n >= cap || (by_row && r >= cap)) return false;
        uint64_t* out = mat + (by_row ? r : n) * width;
        rows[n++] = r;
        if (t.flags[r] & F_PEND_P) out[own_col] = t.pend_p[r];
        if (npol > 1 && (t.flags[r] & F_PEND_N))
            out[rep_cap + own_col] = t.pend_n[r];
        if (!(t.flags[r] & F_FOREIGN)) return true;
        for (const Table::FCell& c : t.fcells[r]) {
            if (c.col >= static_cast<uint32_t>(rep_cap)) return false;
            if (c.p > out[c.col]) out[c.col] = c.p;
            if (npol > 1 && c.n > out[rep_cap + c.col])
                out[rep_cap + c.col] = c.n;
        }
        return true;
    };
    for (int64_t r : t.pend_rows)
        if (!emit(r)) return -1;
    for (int64_t r : t.foreign_rows)
        if (!(t.flags[r] & (F_PEND_P | F_PEND_N)) && !emit(r)) return -1;
    return n;
}

// drain writeback: authoritative post-join values for the drained rows;
// the window the batch was read from clears (pending own values, and
// the foreign marks: the columns themselves stay, they are the digest's)
void jy_eng_finish_drain(void* e, int32_t which, const int64_t* rows,
                         const uint64_t* values, int64_t n) {
    Table& t = static_cast<Engine*>(e)->t[which];
    for (int64_t i = 0; i < n; i++) t.value[rows[i]] = values[i];
    for (int64_t r : t.foreign_rows)
        t.flags[r] &= static_cast<uint8_t>(~F_FOREIGN);
    t.foreign_rows.clear();
    for (int64_t r : t.pend_rows) {
        t.flags[r] &= static_cast<uint8_t>(~(F_PEND_P | F_PEND_N));
        t.pend_p[r] = 0;
        t.pend_n[r] = 0;
    }
    t.pend_rows.clear();
}

// one row's canonical columns for the sync digest: the foreign columns
// joined with the own contribution at `own_col` — what the device
// converges to, with no device read. Returns the cells written, or -n
// when `cap` is too small for the row's n.
int64_t jy_eng_sync_cols(void* e, int32_t which, int64_t row, int32_t own_col,
                         int32_t* cols, uint64_t* vp, uint64_t* vn,
                         int64_t cap) {
    Table& t = static_cast<Engine*>(e)->t[which];
    const std::vector<Table::FCell>& cells = t.fcells[row];
    int64_t need = static_cast<int64_t>(cells.size()) + 1;
    if (need > cap) return -need;
    uint64_t own_p = (t.flags[row] & F_OWNSET_P) ? t.own_p[row] : 0;
    uint64_t own_n = (t.flags[row] & F_OWNSET_N) ? t.own_n[row] : 0;
    bool own_seen = false;
    int64_t n = 0;
    for (const Table::FCell& c : cells) {
        bool own = static_cast<int32_t>(c.col) == own_col;
        own_seen |= own;
        cols[n] = static_cast<int32_t>(c.col);
        vp[n] = own && own_p > c.p ? own_p : c.p;
        vn[n] = own && own_n > c.n ? own_n : c.n;
        n++;
    }
    if (!own_seen && (own_p || own_n)) {
        cols[n] = own_col;
        vp[n] = own_p;
        vn[n] = own_n;
        n++;
    }
    return n;
}

// rows changed since the last sync-digest pass (F_SYNCD); clears
int64_t jy_eng_export_sync_dirty(void* e, int32_t which, int64_t* rows,
                                 int64_t cap) {
    Table& t = static_cast<Engine*>(e)->t[which];
    int64_t n = static_cast<int64_t>(t.sync_dirty.size());
    if (n > cap) return -n;
    for (int64_t i = 0; i < n; i++) {
        rows[i] = t.sync_dirty[i];
        t.flags[t.sync_dirty[i]] &= static_cast<uint8_t>(~F_SYNCD);
    }
    t.sync_dirty.clear();
    return n;
}

int64_t jy_eng_dirty_count(void* e, int32_t which) {
    return static_cast<int64_t>(
        static_cast<Engine*>(e)->t[which].dirty_rows.size());
}

int64_t jy_eng_pend_count(void* e, int32_t which) {
    return static_cast<int64_t>(
        static_cast<Engine*>(e)->t[which].pend_rows.size());
}

// flush export: dirty rows + own contributions + own-set bits (bit0 = P
// was written, bit1 = N was written); clears the dirty set
int64_t jy_eng_export_dirty(void* e, int32_t which, int64_t* rows,
                            uint64_t* op, uint64_t* on, uint8_t* set_bits,
                            int64_t cap) {
    Table& t = static_cast<Engine*>(e)->t[which];
    int64_t n = static_cast<int64_t>(t.dirty_rows.size());
    if (n > cap) return -n;
    for (int64_t i = 0; i < n; i++) {
        int64_t r = t.dirty_rows[i];
        rows[i] = r;
        op[i] = t.own_p[r];
        on[i] = t.own_n[r];
        set_bits[i] =
            static_cast<uint8_t>(((t.flags[r] & F_OWNSET_P) ? 1 : 0) |
                                 ((t.flags[r] & F_OWNSET_N) ? 2 : 0));
        t.flags[r] &= static_cast<uint8_t>(~F_DIRTY);
    }
    t.dirty_rows.clear();
    return n;
}

}  // extern "C"
