// Shared host-state tables for the native serving engine.
//
// The reference executes every command inside compiled Pony actors
// scheduled across all cores (jylis/server_notify.pony:8-36,
// jylis/repo_manager.pony:18); the rebuild's Python serving seam tops out
// on interpreter dispatch. These tables own the per-type HOST state the
// Python repos otherwise keep in dicts, so whole pipelined bursts of ANY
// data type settle in one FFI call (native/serve_engine.cpp): parse (via
// resp_scan, same .so) + table update + reply bytes, all in C++.
//
// Split of responsibilities (single source of truth):
//   * native: key tables, serving winners/caches, pending windows, delta
//     accumulators — everything a command touches on the hot path
//   * Python: device drains, cluster converge orchestration, snapshots —
//     via the bulk export/apply calls in the .cpp files
// Any command the engine can't settle exactly like the Python oracle is
// returned to Python with its argument slices; the caller applies THAT
// command (after draining the UJSON write queue, which preserves
// per-connection ordering) and re-enters.

#pragma once

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <string>
#include <unordered_map>
#include <unordered_set>
#include <utility>
#include <vector>

extern "C" int32_t resp_scan(const uint8_t* buf, int64_t len,
                             int64_t* consumed, int64_t* offs, int64_t* lens,
                             int32_t max_args, int32_t* n_args);

namespace jy {

// ---- open-addressing key index (FNV-1a, power-of-two, linear probe) --------

struct KeyIndex {
    std::vector<int64_t> slot_row;  // -1 empty
    std::vector<uint8_t> arena;     // key bytes, append-only
    std::vector<int64_t> key_off;
    std::vector<int64_t> key_len;
    std::vector<uint64_t> key_hash;

    KeyIndex() : slot_row(64, -1) {}

    size_t mask() const { return slot_row.size() - 1; }
    int64_t rows() const { return static_cast<int64_t>(key_off.size()); }

    static uint64_t hash(const uint8_t* k, int64_t n) {
        uint64_t h = 1469598103934665603ull;
        for (int64_t i = 0; i < n; i++) h = (h ^ k[i]) * 1099511628211ull;
        return h;
    }

    bool key_eq(int64_t row, const uint8_t* k, int64_t n) const {
        return key_len[row] == n &&
               memcmp(arena.data() + key_off[row], k,
                      static_cast<size_t>(n)) == 0;
    }

    void rehash() {
        std::vector<int64_t> fresh(slot_row.size() * 2, -1);
        size_t m = fresh.size() - 1;
        for (size_t r = 0; r < key_off.size(); r++) {
            size_t i = key_hash[r] & m;
            while (fresh[i] >= 0) i = (i + 1) & m;
            fresh[i] = static_cast<int64_t>(r);
        }
        slot_row.swap(fresh);
    }

    int64_t find(const uint8_t* k, int64_t n) const {
        uint64_t h = hash(k, n);
        size_t i = h & mask();
        while (true) {
            int64_t row = slot_row[i];
            if (row < 0) return -1;
            if (key_hash[row] == h && key_eq(row, k, n)) return row;
            i = (i + 1) & mask();
        }
    }

    // returns (row, was_new): callers append their per-row columns on new
    std::pair<int64_t, bool> upsert(const uint8_t* k, int64_t n) {
        uint64_t h = hash(k, n);
        size_t i = h & mask();
        while (true) {
            int64_t row = slot_row[i];
            if (row < 0) break;
            if (key_hash[row] == h && key_eq(row, k, n)) return {row, false};
            i = (i + 1) & mask();
        }
        int64_t row = rows();
        key_off.push_back(static_cast<int64_t>(arena.size()));
        key_len.push_back(n);
        key_hash.push_back(h);
        arena.insert(arena.end(), k, k + n);
        slot_row[i] = row;
        if (key_off.size() * 10 >= slot_row.size() * 7) rehash();
        return {row, true};
    }

    const uint8_t* key_ptr(int64_t row) const {
        return arena.data() + key_off[row];
    }
};

// ---- counter table (GCOUNT / PNCOUNT) --------------------------------------

constexpr uint8_t F_FOREIGN = 1;
constexpr uint8_t F_DIRTY = 2;
constexpr uint8_t F_PEND_P = 4;
constexpr uint8_t F_PEND_N = 8;
// "own was ever written" per polarity: flush emits a polarity's entry
// only when set, matching the Python dicts' key-presence semantics
// (an INC of 0 still creates the entry)
constexpr uint8_t F_OWNSET_P = 16;
constexpr uint8_t F_OWNSET_N = 32;
// row changed since the last sync-digest pass (cluster/syncdigest)
constexpr uint8_t F_SYNCD = 64;

struct Table {
    KeyIndex idx;
    // per-row state
    std::vector<uint64_t> value;  // serving value (u64 bits)
    std::vector<uint64_t> own_p;
    std::vector<uint64_t> own_n;
    std::vector<uint64_t> pend_p;  // max own within the drain window
    std::vector<uint64_t> pend_n;
    std::vector<uint8_t> flags;
    std::vector<int64_t> dirty_rows;  // insertion order; F_DIRTY dedups
    std::vector<int64_t> pend_rows;   // rows with any F_PEND_*
    std::vector<int64_t> sync_dirty;  // rows changed since last digest
    // the foreign window: every column a peer ever converged into a row,
    // cumulative and by COLUMN (the repo maps replica id -> column). Held
    // once: the sync digest reads it, and a drain sends a foreign row's
    // columns whole (the device's join is idempotent)
    struct FCell {
        uint32_t col;
        uint64_t p, n;
    };
    std::vector<std::vector<FCell>> fcells;
    std::vector<int64_t> foreign_rows;  // rows with F_FOREIGN set

    int64_t find(const uint8_t* k, int64_t n) const { return idx.find(k, n); }

    int64_t upsert(const uint8_t* k, int64_t n) {
        auto [row, fresh] = idx.upsert(k, n);
        if (fresh) {
            value.push_back(0);
            own_p.push_back(0);
            own_n.push_back(0);
            pend_p.push_back(0);
            pend_n.push_back(0);
            flags.push_back(0);
            fcells.emplace_back();
        }
        return row;
    }

    FCell& fcell(int64_t row, uint32_t col) {
        for (FCell& c : fcells[row])
            if (c.col == col) return c;
        fcells[row].push_back(FCell{col, 0, 0});
        return fcells[row].back();
    }

    void mark_sync(int64_t row) {
        if (!(flags[row] & F_SYNCD)) {
            flags[row] |= F_SYNCD;
            sync_dirty.push_back(row);
        }
    }

    void mark_dirty(int64_t row) {
        if (!(flags[row] & F_DIRTY)) {
            flags[row] |= F_DIRTY;
            dirty_rows.push_back(row);
        }
    }

    // INC (polarity 0) / DEC (polarity 1): the exact sequence of
    // repo_counters.py _inc / PN apply
    void bump(int64_t row, int polarity, uint64_t amount) {
        uint64_t& own = polarity ? own_n[row] : own_p[row];
        uint64_t& pend = polarity ? pend_n[row] : pend_p[row];
        uint8_t bit = polarity ? F_PEND_N : F_PEND_P;
        flags[row] |= polarity ? F_OWNSET_N : F_OWNSET_P;
        own += amount;  // u64 wrap
        if (own > pend) pend = own;
        if (!(flags[row] & (F_PEND_P | F_PEND_N))) pend_rows.push_back(row);
        flags[row] |= bit;
        mark_dirty(row);
        mark_sync(row);
        value[row] += polarity ? static_cast<uint64_t>(-amount) : amount;
    }
};

// ---- TREG table ------------------------------------------------------------
//
// Last-writer-wins registers (jylis/repo_treg.pony:11-68). The winner rule
// is lexicographic (ts, value-bytes) — exactly models/repo_treg.py's host
// compare, so the native winner NEVER needs a device read-back: a drain
// just folds the pending window into the drained cache (the join of what
// both already hold), and the device converges to the same winner.
//
// The device mirror's vid plane holds a per-row GENERATION, not an id
// into a table of values: the kernel compares two ids only at one row
// (the state's and that row's delta's, ops/treg._b_wins), so it needs
// id >= 0 for a set register and id_a != id_b exactly when the two
// (ts, value) pairs differ. A pending write that differs from the
// drained winner exports generation + 1, an identical re-delivery
// exports the generation itself; the fold adopts what it exported.

struct TregTable {
    KeyIndex idx;
    // drained winner (the device mirror's exact host image)
    std::vector<uint64_t> cache_ts;
    std::vector<std::string> cache_val;
    std::vector<uint8_t> cache_set;
    std::vector<int32_t> cache_gen;  // the mirror's vid; -1 while unset
    // max (ts, value) written since the last drain
    std::vector<uint64_t> pend_ts;
    std::vector<std::string> pend_val;
    std::vector<uint8_t> pend_set;
    std::vector<int64_t> pend_rows;  // rows with pend_set, insertion order
    // max (ts, value) written locally since the last flush
    std::vector<uint64_t> delta_ts;
    std::vector<std::string> delta_val;
    std::vector<uint8_t> delta_set;
    std::vector<int64_t> delta_rows;
    // rows changed since the last sync-digest pass
    std::vector<uint8_t> sync_flag;
    std::vector<int64_t> sync_dirty;

    static bool wins(uint64_t ts, const uint8_t* v, int64_t n,
                     uint64_t cur_ts, const std::string& cur) {
        if (ts != cur_ts) return ts > cur_ts;
        size_t cn = cur.size();
        size_t m = static_cast<size_t>(n) < cn ? n : cn;
        int c = memcmp(v, cur.data(), m);
        if (c != 0) return c > 0;
        return static_cast<size_t>(n) > cn;
    }

    int64_t upsert(const uint8_t* k, int64_t n) {
        auto [row, fresh] = idx.upsert(k, n);
        if (fresh) {
            cache_ts.push_back(0);
            cache_val.emplace_back();
            cache_set.push_back(0);
            cache_gen.push_back(-1);
            pend_ts.push_back(0);
            pend_val.emplace_back();
            pend_set.push_back(0);
            delta_ts.push_back(0);
            delta_val.emplace_back();
            delta_set.push_back(0);
            sync_flag.push_back(0);
        }
        return row;
    }

    // local SET / cluster converge both funnel here (repo_treg.py _write)
    void write(int64_t row, uint64_t ts, const uint8_t* v, int64_t n) {
        if (!sync_flag[row]) {
            sync_flag[row] = 1;
            sync_dirty.push_back(row);
        }
        if (!pend_set[row]) {
            pend_set[row] = 1;
            pend_ts[row] = ts;
            pend_val[row].assign(reinterpret_cast<const char*>(v), n);
            pend_rows.push_back(row);
        } else if (wins(ts, v, n, pend_ts[row], pend_val[row])) {
            pend_ts[row] = ts;
            pend_val[row].assign(reinterpret_cast<const char*>(v), n);
        }
    }

    void note_delta(int64_t row, uint64_t ts, const uint8_t* v, int64_t n) {
        if (!delta_set[row]) {
            delta_set[row] = 1;
            delta_ts[row] = ts;
            delta_val[row].assign(reinterpret_cast<const char*>(v), n);
            delta_rows.push_back(row);
        } else if (wins(ts, v, n, delta_ts[row], delta_val[row])) {
            delta_ts[row] = ts;
            delta_val[row].assign(reinterpret_cast<const char*>(v), n);
        }
    }

    bool pend_wins(int64_t row) const {
        return !cache_set[row] ||
               wins(pend_ts[row],
                    reinterpret_cast<const uint8_t*>(pend_val[row].data()),
                    static_cast<int64_t>(pend_val[row].size()), cache_ts[row],
                    cache_val[row]);
    }

    // serving winner = join(cache, pend); returns false when the row has
    // never been written (GET -> null)
    bool winner(int64_t row, uint64_t* ts, const std::string** val) const {
        if (!cache_set[row] && !pend_set[row]) return false;
        if (!pend_set[row] || !pend_wins(row)) {
            *ts = cache_ts[row];
            *val = &cache_val[row];
        } else {
            *ts = pend_ts[row];
            *val = &pend_val[row];
        }
        return true;
    }

    // the id the pending write of `row` carries to the device (wraps
    // inside int32's non-negative half: neighbours still differ)
    int32_t pend_vid(int64_t row) const {
        if (cache_set[row] && pend_ts[row] == cache_ts[row] &&
            pend_val[row] == cache_val[row])
            return cache_gen[row];
        return static_cast<int32_t>(
            (static_cast<uint32_t>(cache_gen[row]) + 1u) & 0x7fffffffu);
    }

    // big-endian first 8 bytes, zero padded: ops/interner.prefix_rank
    static uint64_t prefix_rank(const std::string& v) {
        uint64_t r = 0;
        size_t m = v.size() < 8 ? v.size() : 8;
        for (size_t i = 0; i < m; i++)
            r |= static_cast<uint64_t>(static_cast<uint8_t>(v[i]))
                 << (56 - 8 * i);
        return r;
    }

    // drain prologue: the pending window as the drain kernel's batch
    // planes, in pend_rows order, written into the caller's padded
    // arrays (`cap` long; `ki` at least pend_rows long). Sparse: row i
    // of the window fills slot i; dense: slot = its row, and the caller
    // pre-filled the lattice identity everywhere else. Returns the rows
    // written, or -1 when a slot would fall outside `cap`.
    int64_t export_planes(int32_t* ki, uint32_t* ts_hi, uint32_t* ts_lo,
                          uint32_t* rank_hi, uint32_t* rank_lo, int32_t* vid,
                          int64_t cap, bool dense) const {
        int64_t n = static_cast<int64_t>(pend_rows.size());
        if (!dense && n > cap) return -1;
        for (int64_t i = 0; i < n; i++) {
            int64_t row = pend_rows[i];
            int64_t slot = dense ? row : i;
            if (slot >= cap) return -1;
            uint64_t rank = prefix_rank(pend_val[row]);
            ki[i] = static_cast<int32_t>(row);
            ts_hi[slot] = static_cast<uint32_t>(pend_ts[row] >> 32);
            ts_lo[slot] = static_cast<uint32_t>(pend_ts[row]);
            rank_hi[slot] = static_cast<uint32_t>(rank >> 32);
            rank_lo[slot] = static_cast<uint32_t>(rank);
            vid[slot] = pend_vid(row);
        }
        return n;
    }

    // rows the device flagged (ts and 8-byte rank equal, ids differ):
    // the full strings decide. Compacts `rows` in place to those whose
    // pending write wins, with the id the mirror must be patched to.
    int64_t settle_ties(int32_t* rows, int64_t n, int32_t* vids) const {
        int64_t m = 0;
        for (int64_t i = 0; i < n; i++) {
            int64_t row = rows[i];
            if (row < 0 || row >= idx.rows()) continue;
            if (!pend_set[row] || !pend_wins(row)) continue;
            rows[m] = static_cast<int32_t>(row);
            vids[m++] = pend_vid(row);
        }
        return m;
    }

    // drain epilogue: the pending window folds into the drained cache
    // (the join both sides already agree on) and clears; a winning
    // value MOVES (its buffer changes owner, no byte is copied)
    void fold_pending() {
        for (int64_t row : pend_rows) {
            if (pend_wins(row)) {
                cache_gen[row] = pend_vid(row);
                cache_ts[row] = pend_ts[row];
                cache_val[row] = std::move(pend_val[row]);
                cache_set[row] = 1;
            }
            pend_set[row] = 0;
            pend_val[row].clear();
        }
        pend_rows.clear();
    }
};

// ---- TLOG table ------------------------------------------------------------
//
// Timestamped logs with grow-only cutoff (jylis/repo_tlog.pony:16-111,
// docs tlog.md). Entries intern their value bytes once; the per-row
// merged view (drained ∪ pending, deduped on (ts, value), cutoff-
// filtered) is the SIZE serving surface — the exact mirror of
// models/repo_tlog.py's _merged_set memo, including its validity states.
// The drained "base" carries ACROSS drains: when the memo is current at
// drain time, the post-drain row content is exactly the memo filtered by
// the new cutoff, so SIZE keeps serving natively without ever reading
// the device back.

struct TlogEnt {
    uint64_t ts;
    int32_t vid;
    bool operator==(const TlogEnt& o) const {
        return ts == o.ts && vid == o.vid;
    }
};

struct TlogEntHash {
    size_t operator()(const TlogEnt& e) const {
        uint64_t h = e.ts * 0x9E3779B97F4A7C15ull;
        h ^= static_cast<uint64_t>(static_cast<uint32_t>(e.vid)) + (h >> 29);
        return static_cast<size_t>(h * 0xBF58476D1CE4E5B9ull);
    }
};

using TlogSet = std::unordered_set<TlogEnt, TlogEntHash>;

struct TlogRow {
    std::vector<TlogEnt> pend;  // un-drained entries, arrival order
    uint64_t pend_cutoff = 0;   // max incoming/trim cutoff not yet drained
    bool touched = false;       // in TlogTable::touched_list
    int64_t len_cache = 0;      // drained length (post-cutoff)
    uint64_t cut_cache = 0;     // drained cutoff
    // drained entries as a set-buildable list; valid when it exactly
    // mirrors the device row (maintained across drains via the memo)
    std::vector<TlogEnt> base;
    bool base_valid = true;  // new rows have an empty drained part
    // the merged-view memo: current when (memo_plen, memo_cut) matches
    // (pend.size(), cutoff_view) — repo_tlog.py _merged_set's state key
    TlogSet memo;
    bool memo_valid = false;
    int64_t memo_plen = 0;
    uint64_t memo_cut = 0;
    uint64_t gen = 0;  // bumped whenever the merged view may have changed
    // GET-order memo: the merged view sorted (ts, value-bytes) desc —
    // the native mirror of repo_tlog.py's _sorted cache, keyed by gen
    std::vector<TlogEnt> sorted_view;
    uint64_t sorted_gen = 0;
    bool sorted_valid = false;
    // delta accumulator (hostref.TLog): entry set + grow-only cutoff
    bool delta_present = false;
    TlogSet delta;
    uint64_t delta_cutoff = 0;
    bool sync_flag = false;  // in TlogTable::sync_dirty
};

struct TlogTable {
    KeyIndex idx;
    std::vector<TlogRow> rows;
    // value interner: vid -> bytes, bytes -> vid
    std::vector<std::string> vals;
    std::unordered_map<std::string, int32_t> vmap;
    int64_t pend_rows_count = 0;  // rows with non-empty pend
    bool row_overdue = false;     // some row's pend crossed ROW_DRAIN
    std::vector<int64_t> delta_rows;    // rows with delta_present
    std::vector<int64_t> touched_list;  // rows with pend or pend_cutoff
    std::vector<int64_t> sync_dirty;    // rows changed since last digest
    int64_t live_total = 0;  // sum of len_cache over all rows (O(1) reads)
    int64_t compact_floor;  // value-interner size below which no compact

    static constexpr int64_t ROW_DRAIN_THRESHOLD = 1024;   // tlog_table.py
    static constexpr int64_t PENDING_DRAIN_THRESHOLD = 4096;
    static constexpr int64_t VAL_COMPACT_SLACK = 8192;
    // a third bound, on the pending entries of ALL rows: off until the
    // repo sets it to what one batch of the drain program it compiled
    // ahead holds (tlog_table.py set_entries_bound)
    int64_t pend_total = 0;
    int64_t entries_bound = int64_t{1} << 62;

    bool overdue() const {
        return row_overdue || pend_rows_count >= PENDING_DRAIN_THRESHOLD ||
               pend_total >= entries_bound;
    }

    // would one more INS on a row with `in_row` pending entries make a
    // drain due (repo_tlog.py may_drain's predicate)
    bool ins_tips(int64_t in_row) const {
        return in_row + 1 >= ROW_DRAIN_THRESHOLD ||
               pend_rows_count + 1 >= PENDING_DRAIN_THRESHOLD ||
               pend_total + 1 >= entries_bound;
    }

    TlogTable() : compact_floor(VAL_COMPACT_SLACK) {}

    int32_t intern(const uint8_t* v, int64_t n) {
        std::string s(reinterpret_cast<const char*>(v), n);
        auto it = vmap.find(s);
        if (it != vmap.end()) return it->second;
        int32_t id = static_cast<int32_t>(vals.size());
        vals.push_back(std::move(s));
        vmap.emplace(vals.back(), id);
        return id;
    }

    int64_t upsert(const uint8_t* k, int64_t n) {
        auto [row, fresh] = idx.upsert(k, n);
        if (fresh) rows.emplace_back();
        return row;
    }

    uint64_t cutoff_view(const TlogRow& r) const {
        return r.pend_cutoff > r.cut_cache ? r.pend_cutoff : r.cut_cache;
    }

    bool quiescent(const TlogRow& r) const {
        return r.pend.empty() && r.pend_cutoff <= r.cut_cache;
    }

    bool memo_current(const TlogRow& r) const {
        return r.memo_valid &&
               r.memo_plen == static_cast<int64_t>(r.pend.size()) &&
               r.memo_cut == cutoff_view(r);
    }

    void touch(TlogRow& r, int64_t row_i) {
        if (!r.touched) {
            r.touched = true;
            touched_list.push_back(row_i);
        }
        mark_sync(r, row_i);
    }

    void mark_sync(TlogRow& r, int64_t row_i) {
        if (!r.sync_flag) {
            r.sync_flag = true;
            sync_dirty.push_back(row_i);
        }
    }

    void append_pend(TlogRow& r, int64_t row_i, TlogEnt e) {
        if (r.pend.empty()) pend_rows_count++;
        r.pend.push_back(e);
        pend_total++;
        touch(r, row_i);
        if (static_cast<int64_t>(r.pend.size()) >= ROW_DRAIN_THRESHOLD)
            row_overdue = true;
    }

    // local INS (repo_tlog.py apply INS): buffered as a peer's entry is,
    // plus the delta insert when ts clears the drained cutoff
    void ins(int64_t row_i, uint64_t ts, const uint8_t* v, int64_t n) {
        TlogEnt e = converge_entry(row_i, ts, v, n);
        TlogRow& r = rows[row_i];
        if (ts >= r.cut_cache) {
            if (!r.delta_present) {
                r.delta_present = true;
                delta_rows.push_back(row_i);
            }
            if (ts >= r.delta_cutoff) r.delta.insert(e);
        }
    }

    // buffer one entry, a peer's or a client's: pend append + memo
    // upkeep: a memo that was current stays current (one set insert), so
    // the next read of the row does not rebuild it from the whole base
    TlogEnt converge_entry(int64_t row_i, uint64_t ts, const uint8_t* v,
                           int64_t n) {
        TlogRow& r = rows[row_i];
        TlogEnt e{ts, intern(v, n)};
        append_pend(r, row_i, e);
        r.gen++;
        if (r.memo_valid) {
            uint64_t cut = cutoff_view(r);
            if (r.memo_plen != static_cast<int64_t>(r.pend.size()) - 1 ||
                r.memo_cut != cut) {
                r.memo_valid = false;
                TlogSet().swap(r.memo);  // free, don't retain dead sets
            } else {
                if (ts >= cut) r.memo.insert(e);
                r.memo_plen = static_cast<int64_t>(r.pend.size());
            }
        }
        return e;
    }

    void raise_pend_cutoff(int64_t row_i, uint64_t c) {
        TlogRow& r = rows[row_i];
        if (c > r.pend_cutoff) {
            r.pend_cutoff = c;
            touch(r, row_i);
            r.gen++;
        }
    }

    // merged-view size; -1 when the drained base is unknown (Python
    // rebuilds it from a device gather and calls set_base)
    int64_t size(int64_t row_i) {
        TlogRow& r = rows[row_i];
        if (quiescent(r)) return r.len_cache;
        if (memo_current(r)) return static_cast<int64_t>(r.memo.size());
        if (!r.base_valid) return -1;
        rebuild_memo(r);
        return static_cast<int64_t>(r.memo.size());
    }

    // the merged view from what the host holds: a valid base and the
    // pending window, each filtered by the cutoff view, deduplicated on
    // (ts, value id)
    void rebuild_memo(TlogRow& r) {
        uint64_t cut = cutoff_view(r);
        r.memo.clear();
        for (const TlogEnt& e : r.base)
            if (e.ts >= cut) r.memo.insert(e);
        for (const TlogEnt& e : r.pend)
            if (e.ts >= cut) r.memo.insert(e);
        r.memo_valid = true;
        r.memo_plen = static_cast<int64_t>(r.pend.size());
        r.memo_cut = cut;
        r.gen++;
    }

    // the merged view sorted (ts, value-bytes) desc — TLOG GET's serving
    // order (repo_tlog.py _merged_view). Returns nullptr when the drained
    // base is unknown (Python rebuilds it from a device gather) — the
    // caller defers the command. Cached per row, keyed by gen.
    const std::vector<TlogEnt>* sorted_view_of(int64_t row_i) {
        TlogRow& r = rows[row_i];
        if (size(row_i) < 0) return nullptr;  // base unknown: defer
        if (r.sorted_valid && r.sorted_gen == r.gen) return &r.sorted_view;
        r.sorted_view.clear();
        if (quiescent(r)) {
            if (!r.base_valid) return nullptr;  // device row render needed
            r.sorted_view = r.base;
        } else if (memo_current(r)) {
            r.sorted_view.assign(r.memo.begin(), r.memo.end());
        } else {
            return nullptr;  // unreachable after size() >= 0; stay safe
        }
        std::sort(r.sorted_view.begin(), r.sorted_view.end(),
                  [this](const TlogEnt& a, const TlogEnt& b) {
                      if (a.ts != b.ts) return a.ts > b.ts;
                      return vals[b.vid] < vals[a.vid];  // value desc
                  });
        r.sorted_valid = true;
        r.sorted_gen = r.gen;
        return &r.sorted_view;
    }

    static void drop_sorted(TlogRow& r) {
        r.sorted_valid = false;
        std::vector<TlogEnt>().swap(r.sorted_view);
    }

    // drain epilogue for one drained row: device reported (len, cut).
    // The pending window folds into the base the host holds (PyTlogTable.
    // finish_row states the same rule): the post-drain row is the merged
    // memo filtered by the returned cutoff, kept only when its size
    // equals the device's length. Returns whether the base is still held.
    bool finish_drain_row(int64_t row_i, int64_t len, uint64_t cut) {
        TlogRow& r = rows[row_i];
        drop_sorted(r);  // free rather than wait for the gen-key miss
        if (r.base_valid && !memo_current(r)) rebuild_memo(r);
        if (memo_current(r)) {
            r.base.clear();
            for (const TlogEnt& e : r.memo)
                if (e.ts >= cut) r.base.push_back(e);
            r.base_valid = static_cast<int64_t>(r.base.size()) == len;
        } else {
            r.base.clear();
            r.base_valid = (len == 0);
        }
        mark_sync(r, row_i);  // a fused trim can change the merged view
        live_total += len - r.len_cache;
        r.len_cache = len;
        r.cut_cache = cut;
        if (!r.pend.empty()) pend_rows_count--;
        pend_total -= static_cast<int64_t>(r.pend.size());
        r.pend.clear();
        r.pend_cutoff = 0;
        if (r.base_valid) {
            // the base was filtered OUT of the memo: equal sizes, equal
            // sets, and the memo stays as it is (no thousand re-inserts)
            if (!r.memo_valid || r.memo.size() != r.base.size()) {
                r.memo.clear();
                r.memo.insert(r.base.begin(), r.base.end());
            }
            r.memo_valid = true;
            r.memo_plen = 0;
            r.memo_cut = cutoff_view(r);
        } else {
            r.memo_valid = false;
            r.memo.clear();
        }
        r.gen++;
        return r.base_valid;
    }

    // global drain tail (repo_tlog.py drain(), after its last pass):
    // pend.clear() across every row + flag reset
    void finish_drain_end() {
        for (int64_t row_i : touched_list) {
            TlogRow& r = rows[row_i];
            r.touched = false;
            if (!r.pend.empty()) {  // touched but not in the drain set:
                r.pend.clear();     // cannot happen under the repo lock,
                r.memo_valid = false;  // but mirror the global clear
                r.gen++;
            }
            r.pend_cutoff = 0;
        }
        touched_list.clear();
        pend_rows_count = 0;
        pend_total = 0;
        row_overdue = false;
    }

    // value-interner epoch compaction (the host analog of the repo's
    // device-vid _maybe_compact_interner): once the table holds far more
    // strings than the live entry set references, remap every live vid
    // and drop the garbage. Returns true when a remap happened — callers
    // holding vid->bytes mirrors must reset them.
    bool compact_values() {
        if (static_cast<int64_t>(vals.size()) < compact_floor) return 0;
        std::vector<char> mark(vals.size(), 0);
        int64_t live = 0;
        auto see = [&](const TlogEnt& e) {
            if (e.vid >= 0 && !mark[e.vid]) {
                mark[e.vid] = 1;
                live++;
            }
        };
        for (TlogRow& r : rows) {
            for (const TlogEnt& e : r.pend) see(e);
            for (const TlogEnt& e : r.base) see(e);
            if (memo_current(r)) {
                for (const TlogEnt& e : r.memo) see(e);
            } else if (!r.memo.empty()) {
                // a state-stale memo (e.g. converge_entry appended past
                // it) is dead weight: free it rather than keeping its
                // vids alive through the compaction
                r.memo_valid = false;
                TlogSet().swap(r.memo);
            }
            for (const TlogEnt& e : r.delta) see(e);
        }
        if (static_cast<int64_t>(vals.size()) <= 2 * live + VAL_COMPACT_SLACK) {
            // genuinely live: raise the floor so the walk stays amortised
            compact_floor = static_cast<int64_t>(vals.size()) + VAL_COMPACT_SLACK;
            return 0;
        }
        std::vector<int32_t> remap(vals.size(), -1);
        std::vector<std::string> fresh;
        fresh.reserve(live);
        for (size_t i = 0; i < vals.size(); i++) {
            if (mark[i]) {
                remap[i] = static_cast<int32_t>(fresh.size());
                fresh.push_back(std::move(vals[i]));
            }
        }
        vals.swap(fresh);
        vmap.clear();
        for (size_t i = 0; i < vals.size(); i++)
            vmap.emplace(vals[i], static_cast<int32_t>(i));
        auto fix_vec = [&](std::vector<TlogEnt>& v) {
            for (TlogEnt& e : v)
                if (e.vid >= 0) e.vid = remap[e.vid];
        };
        auto fix_set = [&](TlogSet& s) {
            TlogSet out;
            out.reserve(s.size());
            for (TlogEnt e : s) {
                if (e.vid >= 0) e.vid = remap[e.vid];
                out.insert(e);
            }
            s.swap(out);
        };
        for (TlogRow& r : rows) {
            fix_vec(r.pend);
            fix_vec(r.base);
            fix_set(r.memo);
            fix_set(r.delta);
            // the GET-order cache holds vids too; a stale (old-gen) copy
            // may reference dead ids the remap never saw — drop it
            drop_sorted(r);
        }
        compact_floor =
            2 * static_cast<int64_t>(vals.size()) + VAL_COMPACT_SLACK;
        return 1;
    }
};

// ---- MAP field table -------------------------------------------------------
//
// `MAP TREG`: a record is a key whose fields are last-writer-wins
// registers (models/repo_map.py, ops/compose.py). One row a FIELD, holding
// the field's whole product state: per-replica edit counters `ver`, the
// removal tombstone `tomb` (both pointwise max, by replica column) and the
// inner register (ts, value) under TregTable's (ts, value-bytes) rule. The
// host row IS the state (every write and every foreign unit joins it at
// once, so a read never waits for a drain); the device table mirrors it:
// rows changed since the last drain (`pend_rows`) leave as the drain's
// batch planes, `ver`/`tomb` as the counters' cells and the register as
// TREG's five planes with a per-row generation in the vid plane (see
// "TREG table": the kernel compares ids only at one row).
//
// A record's fields are reachable from its key: `kfields[key row]` holds
// the key's field rows in ascending byte order of their names, so GETALL
// renders them with no scan and no sort, and a field is found by a binary
// search of ten names. Fields of any OTHER inner type stay in the Python
// oracle's table (models/map_table.py PyMapTable); a key that has one is
// `kmixed`, and every command on it is the oracle path's.

constexpr uint8_t M_DIRTY = 1;  // edited locally since the last flush
constexpr uint8_t M_PEND = 2;   // changed since the last device drain
constexpr uint8_t M_SYNC = 4;   // changed since the last digest pass

// LEB128 (cluster/codec.py, utils/wire.py): false on a truncated varint
// or one that does not fit 64 bits
inline bool rd_varint(const uint8_t* p, int64_t n, int64_t* pos,
                      uint64_t* out) {
    uint64_t v = 0;
    for (int shift = 0; shift < 64; shift += 7) {
        if (*pos >= n) return false;
        uint8_t b = p[(*pos)++];
        if (shift == 63 && (b & 0x7E)) return false;  // past 64 bits
        v |= static_cast<uint64_t>(b & 0x7F) << shift;
        if (!(b & 0x80)) {
            *out = v;
            return true;
        }
    }
    return false;
}

inline void wr_varint(std::vector<uint8_t>& out, uint64_t v) {
    while (v >= 0x80) {
        out.push_back(static_cast<uint8_t>(v) | 0x80);
        v >>= 7;
    }
    out.push_back(static_cast<uint8_t>(v));
}

struct MapTable {
    KeyIndex kidx;                              // record keys
    std::vector<std::vector<int32_t>> kfields;  // field rows, by name
    std::vector<uint8_t> kmixed;  // the oracle's table holds fields of it
    // per field row
    std::vector<int32_t> fkey;
    std::vector<uint8_t> fnames;  // field-name arena, append-only
    std::vector<int64_t> fname_off;
    std::vector<int32_t> fname_len;
    std::vector<uint64_t> ver, tomb;  // rows x hcap, by replica column
    std::vector<uint64_t> reg_ts;
    std::vector<std::string> reg_val;
    std::vector<int32_t> reg_gen;  // the mirror's vid; -1 while bottom
    std::vector<uint8_t> flags;
    std::vector<int64_t> dirty_rows, pend_rows, sync_rows;
    // replica id <-> column, in order of first sight
    std::vector<uint64_t> rids;
    std::unordered_map<uint64_t, int32_t> rid_col;
    int64_t hcap = 1;  // columns a row holds (doubles)
    // acknowledged SETs, GETALLs served, fields those rendered
    uint64_t n_sets = 0, n_getalls = 0, n_getall_fields = 0;
    std::vector<uint8_t> wire_buf;  // rows as wire units, built then taken
    std::vector<int64_t> wire_starts;  // where each unit begins in it

    int64_t rows() const { return static_cast<int64_t>(fkey.size()); }

    const uint8_t* fname(int64_t row) const {
        return fnames.data() + fname_off[row];
    }

    static int cmp_bytes(const uint8_t* a, int64_t an, const uint8_t* b,
                         int64_t bn) {
        int c = memcmp(a, b, static_cast<size_t>(an < bn ? an : bn));
        if (c != 0) return c;
        return an < bn ? -1 : (an > bn ? 1 : 0);
    }

    int32_t col_for(uint64_t rid) {
        auto it = rid_col.find(rid);
        if (it != rid_col.end()) return it->second;
        int32_t col = static_cast<int32_t>(rids.size());
        rids.push_back(rid);
        rid_col.emplace(rid, col);
        if (col >= hcap) {  // re-stride both planes to twice the columns
            int64_t wide = hcap * 2;
            for (std::vector<uint64_t>* plane : {&ver, &tomb}) {
                std::vector<uint64_t> fresh(
                    static_cast<size_t>(rows() * wide), 0);
                for (int64_t r = 0; r < rows(); r++)
                    memcpy(&fresh[r * wide], &(*plane)[r * hcap],
                           static_cast<size_t>(hcap) * 8);
                plane->swap(fresh);
            }
            hcap = wide;
        }
        return col;
    }

    int64_t upsert_key(const uint8_t* k, int64_t n) {
        auto [row, fresh] = kidx.upsert(k, n);
        if (fresh) {
            kfields.emplace_back();
            kmixed.push_back(0);
        }
        return row;
    }

    // position of `name` among the key's fields: (index, found)
    std::pair<size_t, bool> locate(int64_t krow, const uint8_t* f,
                                   int64_t fn) const {
        const std::vector<int32_t>& v = kfields[krow];
        size_t lo = 0, hi = v.size();
        while (lo < hi) {
            size_t mid = (lo + hi) / 2;
            int c = cmp_bytes(fname(v[mid]), fname_len[v[mid]], f, fn);
            if (c == 0) return {mid, true};
            if (c < 0)
                lo = mid + 1;
            else
                hi = mid;
        }
        return {lo, false};
    }

    int64_t find_field(const uint8_t* k, int64_t kn, const uint8_t* f,
                       int64_t fn) const {
        int64_t krow = kidx.find(k, kn);
        if (krow < 0) return -1;
        auto [at, found] = locate(krow, f, fn);
        return found ? kfields[krow][at] : -1;
    }

    int64_t upsert_field(int64_t krow, const uint8_t* f, int64_t fn) {
        auto [at, found] = locate(krow, f, fn);
        if (found) return kfields[krow][at];
        int64_t row = rows();
        fkey.push_back(static_cast<int32_t>(krow));
        fname_off.push_back(static_cast<int64_t>(fnames.size()));
        fname_len.push_back(static_cast<int32_t>(fn));
        fnames.insert(fnames.end(), f, f + fn);
        ver.resize(ver.size() + static_cast<size_t>(hcap), 0);
        tomb.resize(tomb.size() + static_cast<size_t>(hcap), 0);
        reg_ts.push_back(0);
        reg_val.emplace_back();
        reg_gen.push_back(-1);
        flags.push_back(0);
        std::vector<int32_t>& v = kfields[krow];
        v.insert(v.begin() + static_cast<int64_t>(at),
                 static_cast<int32_t>(row));
        return row;
    }

    // capacity a restore is about to need (no regrowth while it loads)
    void reserve(int64_t keys, int64_t fields) {
        fkey.reserve(fields);
        fname_off.reserve(fields);
        fname_len.reserve(fields);
        ver.reserve(static_cast<size_t>(fields * hcap));
        tomb.reserve(static_cast<size_t>(fields * hcap));
        reg_ts.reserve(fields);
        reg_val.reserve(fields);
        reg_gen.reserve(fields);
        flags.reserve(fields);
        kfields.reserve(keys);
        kmixed.reserve(keys);
    }

    bool live(int64_t row) const {
        const uint64_t* v = &ver[row * hcap];
        const uint64_t* t = &tomb[row * hcap];
        for (int64_t c = 0; c < hcap; c++)
            if (v[c] > t[c]) return true;
        return false;
    }

    void mark(int64_t row, uint8_t bits) {
        uint8_t fresh = bits & ~flags[row];
        flags[row] |= bits;
        if (fresh & M_DIRTY) dirty_rows.push_back(row);
        if (fresh & M_PEND) pend_rows.push_back(row);
        if (fresh & M_SYNC) sync_rows.push_back(row);
    }

    void reg_join(int64_t row, uint64_t ts, const uint8_t* v, int64_t n) {
        if (!TregTable::wins(ts, v, n, reg_ts[row], reg_val[row])) return;
        reg_ts[row] = ts;
        reg_val[row].assign(reinterpret_cast<const char*>(v), n);
        reg_gen[row] = static_cast<int32_t>(
            (static_cast<uint32_t>(reg_gen[row]) + 1u) & 0x7fffffffu);
    }

    // local SET (MapCRDT.set_field): the editor's counter advances by
    // one, the write joins the register
    int64_t set(const uint8_t* k, int64_t kn, const uint8_t* f, int64_t fn,
                uint64_t rid, uint64_t ts, const uint8_t* v, int64_t n) {
        int32_t col = col_for(rid);
        int64_t row = upsert_field(upsert_key(k, kn), f, fn);
        ver[row * hcap + col]++;
        reg_join(row, ts, v, n);
        mark(row, M_DIRTY | M_PEND | M_SYNC);
        n_sets++;
        return row;
    }

    // local DEL (MapCRDT.del_field): the tombstone covers every edit
    // this replica has seen; false when there is nothing live to remove
    bool del(int64_t row) {
        if (!live(row)) return false;
        uint64_t* v = &ver[row * hcap];
        uint64_t* t = &tomb[row * hcap];
        for (int64_t c = 0; c < hcap; c++)
            if (v[c] > t[c]) t[c] = v[c];
        mark(row, M_DIRTY | M_PEND | M_SYNC);
        return true;
    }

    // split a packed (key, field) wire key (ops/compose.pack_field)
    static bool unpack(const uint8_t* p, int64_t n, int64_t* koff,
                       int64_t* klen) {
        int64_t pos = 0;
        uint64_t kn = 0;
        if (!rd_varint(p, n, &pos, &kn)) return false;
        if (kn > static_cast<uint64_t>(n - pos)) return false;
        *koff = pos;
        *klen = static_cast<int64_t>(kn);
        return true;
    }

    // one unit of a MAP batch's wire bytes (cluster/codec.py delta/MAP
    // under its packed key), parsed in place. false: malformed, a varint
    // past u64, or an inner type other than TREG (the oracle's to read)
    struct WireUnit {
        const uint8_t *key, *field, *val;
        int64_t kn, fn, vn;
        uint64_t ts;
        int64_t ver_at, nver, tomb_at, ntomb;  // (rid, seq) varint pairs
    };

    static bool read_unit(const uint8_t* p, int64_t n, int64_t* pos,
                          WireUnit* u) {
        uint64_t len = 0;
        if (!rd_varint(p, n, pos, &len) ||
            len > static_cast<uint64_t>(n - *pos))
            return false;
        int64_t koff = 0, kn = 0;
        if (!unpack(p + *pos, static_cast<int64_t>(len), &koff, &kn))
            return false;
        u->key = p + *pos + koff;
        u->kn = kn;
        u->field = u->key + kn;
        u->fn = static_cast<int64_t>(len) - koff - kn;
        *pos += static_cast<int64_t>(len);
        if (!rd_varint(p, n, pos, &len) || len != 4 || n - *pos < 4 ||
            memcmp(p + *pos, "TREG", 4) != 0)
            return false;
        *pos += 4;
        uint64_t x = 0;
        for (int pass = 0; pass < 2; pass++) {
            uint64_t cnt = 0;
            if (!rd_varint(p, n, pos, &cnt) ||
                cnt > static_cast<uint64_t>(n - *pos))
                return false;
            (pass ? u->tomb_at : u->ver_at) = *pos;
            (pass ? u->ntomb : u->nver) = static_cast<int64_t>(cnt);
            for (uint64_t i = 0; i < 2 * cnt; i++)
                if (!rd_varint(p, n, pos, &x)) return false;
        }
        if (!rd_varint(p, n, pos, &len) ||
            len > static_cast<uint64_t>(n - *pos))
            return false;
        u->val = p + *pos;
        u->vn = static_cast<int64_t>(len);
        *pos += u->vn;
        return rd_varint(p, n, pos, &u->ts);
    }

    // a whole batch payload (`count` units): can `load_wire` take it
    static bool check_wire(const uint8_t* p, int64_t n, int64_t count) {
        int64_t pos = 0;
        WireUnit u;
        for (int64_t i = 0; i < count; i++)
            if (!read_unit(p, n, &pos, &u)) return false;
        return pos == n;
    }

    // join a checked batch payload in, unit by unit, with no object a
    // unit made on the way (boot recovery's 10^7 fields)
    void load_wire(const uint8_t* p, int64_t n, int64_t count) {
        int64_t pos = 0;
        WireUnit u;
        std::vector<uint64_t> pairs;
        for (int64_t i = 0; i < count; i++) {
            if (!read_unit(p, n, &pos, &u)) return;  // checked: cannot be
            pairs.clear();
            uint64_t x = 0;
            for (int64_t at : {u.ver_at, u.tomb_at}) {
                int64_t cnt = at == u.ver_at ? u.nver : u.ntomb;
                for (int64_t j = 0; j < 2 * cnt; j++) {
                    rd_varint(p, n, &at, &x);
                    pairs.push_back(x);
                }
            }
            join_unit(u, pairs.data(), pairs.data() + 2 * u.nver);
        }
    }

    // one foreign unit (a peer's, a restore's, a journal's): pointwise
    // max of the counters and the tombstone, given as interleaved
    // (rid, seq) pairs, and the register's join
    int64_t join_unit(const WireUnit& u, const uint64_t* vp,
                      const uint64_t* tp) {
        // columns first: a new replica id may re-stride the planes
        for (int64_t i = 0; i < u.nver; i++) col_for(vp[2 * i]);
        for (int64_t i = 0; i < u.ntomb; i++) col_for(tp[2 * i]);
        int64_t row = upsert_field(upsert_key(u.key, u.kn), u.field, u.fn);
        for (int64_t i = 0; i < u.nver; i++) {
            uint64_t& c = ver[row * hcap + rid_col[vp[2 * i]]];
            if (vp[2 * i + 1] > c) c = vp[2 * i + 1];
        }
        for (int64_t i = 0; i < u.ntomb; i++) {
            uint64_t& c = tomb[row * hcap + rid_col[tp[2 * i]]];
            if (tp[2 * i + 1] > c) c = tp[2 * i + 1];
        }
        reg_join(row, u.ts, u.val, u.vn);
        mark(row, M_PEND | M_SYNC);
        return row;
    }

    // one row as its wire unit under its packed key (delta/MAP): a
    // {rid: n} span is written in ascending rid order, zero cells left
    // out, as the oracle's encoder writes a normalised dict
    void write_unit(std::vector<uint8_t>& out, int64_t row,
                    std::vector<std::pair<uint64_t, uint64_t>>& tmp) const {
        int64_t krow = fkey[row];
        int64_t kn = kidx.key_len[krow], fn = fname_len[row];
        std::vector<uint8_t> head;
        wr_varint(head, static_cast<uint64_t>(kn));
        wr_varint(out, head.size() + static_cast<uint64_t>(kn + fn));
        out.insert(out.end(), head.begin(), head.end());
        out.insert(out.end(), kidx.key_ptr(krow), kidx.key_ptr(krow) + kn);
        out.insert(out.end(), fname(row), fname(row) + fn);
        out.push_back(4);
        out.insert(out.end(), {'T', 'R', 'E', 'G'});
        for (const std::vector<uint64_t>* plane : {&ver, &tomb}) {
            tmp.clear();
            for (int64_t c = 0; c < static_cast<int64_t>(rids.size()); c++)
                if ((*plane)[row * hcap + c])
                    tmp.emplace_back(rids[c], (*plane)[row * hcap + c]);
            std::sort(tmp.begin(), tmp.end());
            wr_varint(out, tmp.size());
            for (auto& [rid, v] : tmp) {
                wr_varint(out, rid);
                wr_varint(out, v);
            }
        }
        wr_varint(out, reg_val[row].size());
        out.insert(out.end(), reg_val[row].begin(), reg_val[row].end());
        wr_varint(out, reg_ts[row]);
    }

    // the packed wire keys of two rows, compared as bytes (dump order:
    // the oracle sorts its packed keys). Equal key lengths put the
    // varint heads equal; else the heads differ in their first bytes.
    bool packed_less(int64_t a, int64_t b) const {
        int64_t ka = fkey[a], kb = fkey[b];
        int64_t an = kidx.key_len[ka], bn = kidx.key_len[kb];
        if (an != bn) {
            std::vector<uint8_t> ha, hb;
            wr_varint(ha, static_cast<uint64_t>(an));
            wr_varint(hb, static_cast<uint64_t>(bn));
            ha.insert(ha.end(), kidx.key_ptr(ka), kidx.key_ptr(ka) + an);
            ha.insert(ha.end(), fname(a), fname(a) + fname_len[a]);
            hb.insert(hb.end(), kidx.key_ptr(kb), kidx.key_ptr(kb) + bn);
            hb.insert(hb.end(), fname(b), fname(b) + fname_len[b]);
            return cmp_bytes(ha.data(), static_cast<int64_t>(ha.size()),
                             hb.data(), static_cast<int64_t>(hb.size())) < 0;
        }
        int c = memcmp(kidx.key_ptr(ka), kidx.key_ptr(kb),
                       static_cast<size_t>(an));
        if (c != 0) return c < 0;
        return cmp_bytes(fname(a), fname_len[a], fname(b), fname_len[b]) < 0;
    }

    // drain prologue: the rows changed since the last drain as the
    // drain's batch planes, in pend_rows order (TregTable::export_planes'
    // contract for the register's five; `cells` is the counters' plane of
    // `rep` columns a polarity: ver hi | tomb hi | ver lo | tomb lo).
    // Returns the rows written, -1 when one would fall outside `cap` or
    // the table knows more replicas than `rep`.
    int64_t export_planes(int32_t* ki, uint32_t* cells, int64_t rep,
                          uint32_t* ts_hi, uint32_t* ts_lo,
                          uint32_t* rank_hi, uint32_t* rank_lo, int32_t* vid,
                          int64_t cap, bool dense) const {
        int64_t n = static_cast<int64_t>(pend_rows.size());
        int64_t known = static_cast<int64_t>(rids.size());
        if (known > rep || (!dense && n > cap)) return -1;
        for (int64_t i = 0; i < n; i++) {
            int64_t row = pend_rows[i];
            int64_t slot = dense ? row : i;
            if (slot >= cap) return -1;
            ki[i] = static_cast<int32_t>(row);
            uint32_t* c = cells + slot * 4 * rep;
            for (int64_t j = 0; j < known; j++) {
                uint64_t v = ver[row * hcap + j], t = tomb[row * hcap + j];
                c[j] = static_cast<uint32_t>(v >> 32);
                c[rep + j] = static_cast<uint32_t>(t >> 32);
                c[2 * rep + j] = static_cast<uint32_t>(v);
                c[3 * rep + j] = static_cast<uint32_t>(t);
            }
            uint64_t rank = TregTable::prefix_rank(reg_val[row]);
            ts_hi[slot] = static_cast<uint32_t>(reg_ts[row] >> 32);
            ts_lo[slot] = static_cast<uint32_t>(reg_ts[row]);
            rank_hi[slot] = static_cast<uint32_t>(rank >> 32);
            rank_lo[slot] = static_cast<uint32_t>(rank);
            vid[slot] = reg_gen[row];
        }
        return n;
    }

    // rows the device flagged (ts and 8-byte rank equal, ids differ): the
    // host row is the join of everything the mirror has seen, so the row
    // it sent is the winner; the mirror's id is patched to its generation
    int64_t settle_ties(int32_t* rws, int64_t n, int32_t* vids) const {
        int64_t m = 0;
        for (int64_t i = 0; i < n; i++) {
            int64_t row = rws[i];
            if (row < 0 || row >= rows()) continue;
            rws[m] = static_cast<int32_t>(row);
            vids[m++] = reg_gen[row];
        }
        return m;
    }

    void clear_pend() {
        for (int64_t row : pend_rows) flags[row] &= ~M_PEND;
        pend_rows.clear();
    }

    // rows of one list (M_DIRTY or M_SYNC), handed over and cleared
    int64_t take(uint8_t bit, int64_t* out, int64_t cap) {
        std::vector<int64_t>& list = bit == M_DIRTY ? dirty_rows : sync_rows;
        int64_t n = static_cast<int64_t>(list.size());
        if (n > cap) return -n;
        for (int64_t i = 0; i < n; i++) {
            out[i] = list[i];
            flags[list[i]] &= ~bit;
        }
        list.clear();
        return n;
    }
};

// ---- UJSON serving memo ----------------------------------------------------
//
// The ORSWOT document lattice stays in Python (host docs) or on the
// device (resident rows) — the engine never owns it. What it owns is the
// RENDER memo: per key, the exact reply bytes the Python oracle produced
// for `UJSON GET key [path...]`, keyed by the path argument vector. The
// Python GET path installs an entry after serving (repo_ujson.py), and
// every write invalidates the overlapping entries — natively at
// queue-bank time, from Python on converge/apply. This mirrors the TLOG
// merged-view memo contract: the memo is only ever a cache of what the
// oracle already rendered, a miss defers to Python (which repairs the
// memo while serving), and staleness is impossible because invalidation
// happens under the same repo-lock boundary as the write itself.
//
// Path keys are length-prefixed blobs (u32 len + bytes per component),
// which makes component-prefix exactly byte-prefix — so the precise
// invalidation rules are cheap:
//   * INS/RM at path p change only renders at paths q ⊆ p (q a prefix
//     of p): deeper disjoint subtrees keep serving natively;
//   * SET/CLR at p rewrite the subtree: q ⊆ p or p ⊆ q invalidates.

struct UjsonTable {
    KeyIndex idx;
    // row -> path-blob -> full reply payload ($len\r\nrender\r\n)
    std::vector<std::unordered_map<std::string, std::string>> memo;

    // renders cached per key; above this the row's map resets (GET paths
    // per key are few in practice — the cap only bounds pathology)
    static constexpr size_t MEMO_PER_KEY = 8;

    int64_t upsert(const uint8_t* k, int64_t n) {
        auto [row, fresh] = idx.upsert(k, n);
        if (fresh) memo.emplace_back();
        return row;
    }

    void put(int64_t row, std::string path, std::string reply) {
        auto& m = memo[row];
        if (m.size() >= MEMO_PER_KEY && m.find(path) == m.end()) m.clear();
        m[std::move(path)] = std::move(reply);
    }

    const std::string* get(int64_t row, const std::string& path) const {
        const auto& m = memo[row];
        auto it = m.find(path);
        return it == m.end() ? nullptr : &it->second;
    }

    static bool is_prefix(const std::string& a, const std::string& b) {
        return a.size() <= b.size() &&
               memcmp(a.data(), b.data(), a.size()) == 0;
    }

    // invalidate the renders a write at `path` can change; subtree=true
    // for SET/CLR (both prefix directions), false for INS/RM
    void invalidate(int64_t row, const std::string& path, bool subtree) {
        auto& m = memo[row];
        for (auto it = m.begin(); it != m.end();) {
            bool hit = is_prefix(it->first, path) ||
                       (subtree && is_prefix(path, it->first));
            it = hit ? m.erase(it) : std::next(it);
        }
    }
};

// ---- UJSON value validators ------------------------------------------------
//
// A natively banked write replies +OK immediately, so the one thing the
// engine must prove is that the oracle's later apply CANNOT raise — i.e.
// the value arg parses as Python's json.loads would parse it
// (ops/ujson_host.py parse_value/parse_doc; the token actually stored is
// the oracle's own canonical dumps, so no round-trip identity is needed
// for equivalence). These validators accept exactly Python's strict JSON
// grammar: escape-bearing and \uXXXX strings, raw UTF-8 (the oracle
// decodes argument bytes with errors="replace", so any byte >= 0x20 is
// parseable), full int/frac/exp numbers, and the NaN/Infinity literals
// json.loads allows by default. Raw control bytes inside strings, bad
// escapes, leading zeros, lone '-', and trailing garbage all bounce.

inline bool json_ws(uint8_t c) {
    return c == ' ' || c == '\t' || c == '\n' || c == '\r';
}

// returns index past the closing quote, or -1
inline int64_t scan_json_string(const uint8_t* p, int64_t n, int64_t i) {
    i++;  // opening quote
    while (i < n) {
        uint8_t c = p[i];
        if (c == '"') return i + 1;
        if (c < 0x20) return -1;  // strict mode rejects raw controls
        if (c == '\\') {
            if (i + 1 >= n) return -1;
            uint8_t e = p[i + 1];
            if (e == 'u') {
                if (i + 5 >= n) return -1;
                for (int64_t j = i + 2; j < i + 6; j++) {
                    uint8_t h = p[j];
                    if (!((h >= '0' && h <= '9') || (h >= 'a' && h <= 'f') ||
                          (h >= 'A' && h <= 'F')))
                        return -1;
                }
                i += 6;
                continue;
            }
            if (e != '"' && e != '\\' && e != '/' && e != 'b' && e != 'f' &&
                e != 'n' && e != 'r' && e != 't')
                return -1;
            i += 2;
            continue;
        }
        i++;  // any other byte incl. raw UTF-8 (replace-decoded oracle-side)
    }
    return -1;
}

// Python json's number regex: -?(0|[1-9]\d*)(\.\d+)?([eE][+-]?\d+)?
// int() refuses digit strings past sys.int_max_str_digits (4300 by
// default), so an INTEGER token that long makes json.loads raise — stay
// comfortably below so a banked +OK can never turn into a late crash at
// queue-flush time (floats parse via float(), which has no such limit)
constexpr int64_t JSON_INT_DIGITS_MAX = 4000;

inline int64_t scan_json_number(const uint8_t* p, int64_t n, int64_t i) {
    if (i < n && p[i] == '-') i++;
    if (i >= n) return -1;
    int64_t int_start = i;
    if (p[i] == '0') {
        i++;
    } else if (p[i] >= '1' && p[i] <= '9') {
        while (i < n && p[i] >= '0' && p[i] <= '9') i++;
    } else {
        return -1;
    }
    if ((i >= n || (p[i] != '.' && p[i] != 'e' && p[i] != 'E')) &&
        i - int_start > JSON_INT_DIGITS_MAX)
        return -1;  // integer token: Python's int() conversion would raise
    if (i < n && p[i] == '.') {
        i++;
        if (i >= n || p[i] < '0' || p[i] > '9') return -1;
        while (i < n && p[i] >= '0' && p[i] <= '9') i++;
    }
    if (i < n && (p[i] == 'e' || p[i] == 'E')) {
        i++;
        if (i < n && (p[i] == '+' || p[i] == '-')) i++;
        if (i >= n || p[i] < '0' || p[i] > '9') return -1;
        while (i < n && p[i] >= '0' && p[i] <= '9') i++;
    }
    return i;
}

inline bool word_at(const uint8_t* p, int64_t n, int64_t i, const char* w) {
    int64_t wn = static_cast<int64_t>(strlen(w));
    return i + wn <= n && memcmp(p + i, w, static_cast<size_t>(wn)) == 0;
}

// literal constants json.loads accepts (allow_nan default); returns end
// index or -1
inline int64_t scan_json_literal(const uint8_t* p, int64_t n, int64_t i) {
    for (const char* w : {"true", "false", "null", "NaN", "Infinity",
                          "-Infinity"})
        if (word_at(p, n, i, w)) return i + static_cast<int64_t>(strlen(w));
    return -1;
}

// full JSON value (objects/arrays too), depth-capped so a pathologically
// nested doc defers to Python instead of recursing here; returns end or -1
inline int64_t scan_json_value(const uint8_t* p, int64_t n, int64_t i,
                               int depth) {
    if (depth <= 0) return -1;
    while (i < n && json_ws(p[i])) i++;
    if (i >= n) return -1;
    uint8_t c = p[i];
    if (c == '"') return scan_json_string(p, n, i);
    if (c == '{') {
        i++;
        while (i < n && json_ws(p[i])) i++;
        if (i < n && p[i] == '}') return i + 1;
        while (true) {
            while (i < n && json_ws(p[i])) i++;
            if (i >= n || p[i] != '"') return -1;
            i = scan_json_string(p, n, i);
            if (i < 0) return -1;
            while (i < n && json_ws(p[i])) i++;
            if (i >= n || p[i] != ':') return -1;
            i = scan_json_value(p, n, i + 1, depth - 1);
            if (i < 0) return -1;
            while (i < n && json_ws(p[i])) i++;
            if (i < n && p[i] == ',') {
                i++;
                continue;
            }
            if (i < n && p[i] == '}') return i + 1;
            return -1;
        }
    }
    if (c == '[') {
        i++;
        while (i < n && json_ws(p[i])) i++;
        if (i < n && p[i] == ']') return i + 1;
        while (true) {
            i = scan_json_value(p, n, i, depth - 1);
            if (i < 0) return -1;
            while (i < n && json_ws(p[i])) i++;
            if (i < n && p[i] == ',') {
                i++;
                continue;
            }
            if (i < n && p[i] == ']') return i + 1;
            return -1;
        }
    }
    {
        int64_t e = scan_json_literal(p, n, i);
        if (e >= 0) return e;
    }
    if (c == '-' || (c >= '0' && c <= '9')) return scan_json_number(p, n, i);
    return -1;
}

// strict UTF-8 validity (no overlongs, no surrogates, max U+10FFFF).
// The render memo is keyed on CANONICAL path bytes — the UTF-8 encoding
// of the errors="replace" decode the oracle applies — and valid UTF-8
// is exactly the class where raw bytes == canonical bytes. Writes whose
// path components fail this defer to Python, whose invalidation
// canonicalises (native/engine.py uj_invalidate), so byte-distinct
// paths that decode identically can never leave a stale memo behind.
inline bool utf8_valid(const uint8_t* p, int64_t n) {
    int64_t i = 0;
    while (i < n) {
        uint8_t c = p[i];
        if (c < 0x80) {
            i++;
            continue;
        }
        int len;
        uint32_t cp;
        if ((c & 0xE0) == 0xC0) {
            len = 2;
            cp = c & 0x1F;
        } else if ((c & 0xF0) == 0xE0) {
            len = 3;
            cp = c & 0x0F;
        } else if ((c & 0xF8) == 0xF0) {
            len = 4;
            cp = c & 0x07;
        } else {
            return false;
        }
        if (i + len > n) return false;
        for (int j = 1; j < len; j++) {
            if ((p[i + j] & 0xC0) != 0x80) return false;
            cp = (cp << 6) | (p[i + j] & 0x3F);
        }
        if (len == 2 && cp < 0x80) return false;          // overlong
        if (len == 3 && cp < 0x800) return false;         // overlong
        if (len == 4 && cp < 0x10000) return false;       // overlong
        if (cp >= 0xD800 && cp <= 0xDFFF) return false;   // surrogate
        if (cp > 0x10FFFF) return false;
        i += len;
    }
    return true;
}

// INS/RM value: a JSON *primitive* (parse_value raises on containers)
inline bool ujson_prim_ok(const uint8_t* p, int64_t n) {
    int64_t i = 0;
    while (i < n && json_ws(p[i])) i++;
    if (i >= n) return false;
    int64_t e;
    if (p[i] == '"') {
        e = scan_json_string(p, n, i);
    } else if ((e = scan_json_literal(p, n, i)) < 0) {
        e = scan_json_number(p, n, i);
    }
    if (e < 0) return false;
    while (e < n && json_ws(p[e])) e++;
    return e == n;
}

// SET value: any JSON document (parse_doc takes containers too)
inline bool ujson_doc_ok(const uint8_t* p, int64_t n) {
    int64_t e = scan_json_value(p, n, 0, 64);
    if (e < 0) return false;
    while (e < n && json_ws(p[e])) e++;
    return e == n;
}

// ---- UJSON write queue -----------------------------------------------------
//
// UJSON INS/SET/RM/CLR are applied by the ORACLE at queue-flush time
// (repo_ujson.py _flush_queue, which runs before any other UJSON work in
// arrival order — per-connection ordering and the observe-first
// delta/lattice semantics are exactly the reference's). The engine's job
// is validate-and-bank: prove the later apply cannot raise (the value
// validators above), record the raw argument slices, invalidate the
// overlapping render memos, and reply +OK.

struct UjsonQueue {
    // blob layout per command: u32 argc, then per arg u32 len + bytes
    std::vector<uint8_t> blob;
    int64_t count = 0;

    static constexpr int64_t MAX_CMDS = 65536;
    static constexpr size_t MAX_BYTES = 16u << 20;

    bool full() const {
        return count >= MAX_CMDS || blob.size() >= MAX_BYTES;
    }

    void push(const uint8_t* buf, const int64_t* offs, const int64_t* lens,
              int32_t argc) {
        uint32_t n = static_cast<uint32_t>(argc);
        const uint8_t* np = reinterpret_cast<const uint8_t*>(&n);
        blob.insert(blob.end(), np, np + 4);
        for (int32_t i = 0; i < argc; i++) {
            uint32_t ln = static_cast<uint32_t>(lens[i]);
            const uint8_t* lp = reinterpret_cast<const uint8_t*>(&ln);
            blob.insert(blob.end(), lp, lp + 4);
            blob.insert(blob.end(), buf + offs[i], buf + offs[i] + lens[i]);
        }
        count++;
    }

    void clear() {
        blob.clear();
        count = 0;
    }
};

// ---- the engine ------------------------------------------------------------

// the reply sender (reply_sender.cpp): made at the first connection that
// is opened on it, its thread at the first hand-off
struct Sender;
void sender_destroy(Sender* s);

struct Engine {
    Table t[2];  // 0 = GCOUNT, 1 = PNCOUNT
    TregTable treg;
    TlogTable tlog;
    UjsonQueue uq;
    UjsonTable uj;
    MapTable map;
    uint64_t map_rid = 0;  // the node's own replica id (RepoMAP sets it)
    // commands settled natively, per type (G, PN, TREG, TLOG, UJSON, MAP) —
    // reads included; deferred commands count on the Python side instead
    // (models/manager.py _apply_core's per-Database tally). SYSTEM
    // METRICS reports the sum.
    uint64_t served[6] = {0, 0, 0, 0, 0, 0};
    // atomic: a counters' read from another thread may meet its making
    std::atomic<Sender*> sender{nullptr};

    ~Engine() {
        if (Sender* s = sender.load()) sender_destroy(s);
    }
};

// ---- shared formatting / parsing helpers -----------------------------------

inline int64_t digits10(uint64_t v) {
    int64_t n = 1;
    while (v >= 10) {
        v /= 10;
        n++;
    }
    return n;
}

inline int64_t fmt_u64(uint8_t* out, uint64_t v) {
    char tmp[24];
    int n = 0;
    do {
        tmp[n++] = static_cast<char>('0' + v % 10);
        v /= 10;
    } while (v);
    for (int i = 0; i < n; i++) out[i] = static_cast<uint8_t>(tmp[n - 1 - i]);
    return n;
}

inline int64_t fmt_int_reply(uint8_t* out, uint64_t bits, bool signed_i64) {
    int64_t n = 0;
    out[n++] = ':';
    if (signed_i64 && static_cast<int64_t>(bits) < 0) {
        out[n++] = '-';
        bits = ~bits + 1;  // unsigned-domain negate: defined for INT64_MIN
    }
    n += fmt_u64(out + n, bits);
    out[n++] = '\r';
    out[n++] = '\n';
    return n;
}

// strict u64 parse: ASCII digits only, must fit (models/base.py parse_u64)
inline bool parse_amount(const uint8_t* s, int64_t n, uint64_t* out) {
    if (n <= 0) return false;
    uint64_t v = 0;
    for (int64_t i = 0; i < n; i++) {
        if (s[i] < '0' || s[i] > '9') return false;
        uint64_t d = static_cast<uint64_t>(s[i] - '0');
        if (v > (UINT64_MAX - d) / 10) return false;
        v = v * 10 + d;
    }
    *out = v;
    return true;
}

inline bool word_is(const uint8_t* buf, int64_t off, int64_t len,
                    const char* w) {
    int64_t n = static_cast<int64_t>(strlen(w));
    return len == n && memcmp(buf + off, w, static_cast<size_t>(n)) == 0;
}

}  // namespace jy
