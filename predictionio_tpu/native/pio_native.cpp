// Native host-side data loader for predictionio_tpu.
//
// The reference delegates its hot host paths to the JVM/Spark (RDD
// shuffles, HBase scans — SURVEY.md §2.5: its only native code lives in
// dependencies like netlib/netty). The TPU rebuild's equivalents are two
// transforms of a train's COO, both in passes linear in the entry count,
// behind a C ABI bound via ctypes (predictionio_tpu/native/__init__.py).
// For each the numpy implementation is the fallback and the reference,
// and the output is bit-identical to it.
//
// 1. Ragged COO → padded dense buckets, what feeds the device
// (ops/als.py::bucket_ragged / bucket_ragged_split): one side of a
// train's ratings in, that side's buckets out. pio_bucket_plan counts the
// rows once and lays the buckets out, the caller allocates zeroed numpy
// buffers of the planned sizes, pio_bucket_fill places every entry,
// pio_bucket_free drops the plan:
//   - a row with more than split_cap entries becomes ceil(count /
//     split_cap) segment rows (pseudo-row ids n_rows, n_rows + 1, ... in
//     ascending order of the row, then of the segment); an entry's
//     segment is its rank among its row's entries in the caller's order,
//     divided by split_cap. rows_out carries the real row id, segmap_out
//     the row's slot in the split table
//   - buckets ordered by ascending capacity (the cap ladder: min_cap,
//     then ceil(prev * growth / 8) * 8, clamped to max_cap)
//   - rows within a bucket ordered by ascending (pseudo-)row id
//   - entries within a row sorted by column id, ties in the caller's
//     order; truncation to max_cap keeps the entries of rank < max_cap.
//     A padded row keeps numpy's layout of a stable sort over zero-padded
//     columns: real column-0 entries, then the padding, then the rest
//   - row count padded to a multiple of row_multiple with sentinel
//     row id == n_rows and zeroed cols/vals/mask
//
// The column order comes from one sort of the entries by column id,
// after which each entry is written straight to the next free cell of
// its row: a counting sort (histogram over the column range) unless the
// entries are few against a wide range (the online fold: a few hundred
// entries, a catalog of columns), which a comparison sort of (column,
// index) keys orders sooner. build_plan picks from the two sizes.
//
// 2. COO → CSR by row, the seen-items map a served model excludes by
// (models/als_model.py::SeenItems): pio_group_rows counts the entries a
// row, takes the prefix sum and writes each entry's column to its row's
// next free slot in the caller's order — the result of a stable sort by
// row, duplicates kept, without the sort.
//
// Build: g++ -O3 -shared -fPIC (see native/__init__.py; no deps).

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <climits>
#include <memory>
#include <new>
#include <vector>

namespace {

// Cap ladder: min_cap, then ceil(prev*growth/8)*8 — growth 2.0 reproduces
// the round-1 power-of-two caps exactly; smaller growth (e.g. 1.5) trades
// more bucket shapes (compile time) for less padding in the gather
// (measured 1.08x epoch at 2M rank-64, round 2). The arithmetic is
// IEEE double, identical to the numpy path's — bit-identical caps.
std::vector<int64_t> build_ladder(int64_t max_count, int64_t min_cap,
                                  double growth) {
    std::vector<int64_t> ladder{min_cap};
    while (ladder.back() < max_count) {
        int64_t next =
            static_cast<int64_t>(std::ceil(ladder.back() * growth / 8.0)) * 8;
        if (next <= ladder.back()) next = ladder.back() + 8;
        ladder.push_back(next);
    }
    return ladder;
}

// declined: the caller runs the numpy path, which defines the semantics
constexpr int64_t kBadIds = -1;       // a row id outside [0, n_rows), a column id < 0
constexpr int64_t kTooManyCaps = -2;  // more buckets than the caller's 63-slot arrays
constexpr int64_t kTooLarge = -3;     // entries or pseudo-rows beyond an int32 index
constexpr int64_t kNoMemory = -4;
constexpr size_t kMaxBuckets = 63;

struct Cursor {
    int64_t next;    // flat index of the row's next free cell
    int32_t pad;     // cap - count: the gap a non-zero column jumps over
    int32_t count;   // entries the row holds
};

struct Plan {
    int64_t n = 0;
    int32_t n_rows = 0;
    int64_t max_cap = 0, split_cap = 0;
    int64_t col_range = 0;             // largest column id + 1
    bool counting = false;             // how fill orders the columns
    std::vector<int32_t> seg_base;     // per row: its first pseudo-row id, -1 unsplit
    std::vector<int32_t> split_rows;   // rows that were split, ascending
    std::vector<int64_t> caps, rpads;  // per bucket, caps ascending
    std::vector<int64_t> has_seg;      // per bucket: 1 if it holds a segment row
    std::vector<Cursor> cursor;        // per pseudo-row
    std::vector<int32_t> rows_out, segmap_out;  // per padded bucket row
};

// Counts the rows once and lays out the buckets; < 0 declines.
int64_t build_plan(const int32_t* rows, const int32_t* cols, int64_t n,
                   int32_t n_rows, int64_t row_multiple, int64_t max_cap,
                   int64_t split_cap, int64_t min_cap, double growth,
                   Plan& plan) {
    if (n < 0 || n > INT32_MAX || n_rows < 0) return kTooLarge;
    plan.n = n;
    plan.n_rows = n_rows;
    plan.max_cap = max_cap;
    plan.split_cap = split_cap;
    std::vector<int32_t> counts(static_cast<size_t>(n_rows), 0);
    int32_t max_col = -1;
    for (int64_t k = 0; k < n; ++k) {
        const int32_t r = rows[k], c = cols[k];
        if (r < 0 || r >= n_rows || c < 0) return kBadIds;
        counts[r] += 1;
        if (c > max_col) max_col = c;
    }
    plan.col_range = static_cast<int64_t>(max_col) + 1;
    // a histogram over the column range costs about a nanosecond a column,
    // sorting the entries themselves about n log n times that: the two met
    // at 30 to 50 columns an entry (138 493 and 10^6 columns, 200 to 10^6
    // entries). A train has more entries than columns; a fold's few
    // histories against a catalog have hundreds of columns an entry.
    plan.counting = n > 0 && plan.col_range <= 32 * n;

    // pseudo-rows: the rows themselves, then every split row's segments
    plan.seg_base.assign(static_cast<size_t>(n_rows), -1);
    int64_t n_seg = 0;
    for (int32_t r = 0; r < n_rows; ++r) {
        if (split_cap > 0 && counts[r] > split_cap) {
            if (n_rows + n_seg > INT32_MAX) return kTooLarge;
            plan.seg_base[r] = static_cast<int32_t>(n_rows + n_seg);
            plan.split_rows.push_back(r);
            n_seg += (counts[r] + split_cap - 1) / split_cap;
        }
    }
    const int64_t n_prow = n_rows + n_seg;
    if (n_prow > INT32_MAX) return kTooLarge;
    plan.cursor.assign(static_cast<size_t>(n_prow), Cursor{-1, 0, 0});
    int64_t max_count = 1;
    for (int32_t r = 0; r < n_rows; ++r) {
        int64_t c = counts[r];
        if (plan.seg_base[r] < 0) {
            if (max_cap > 0 && c > max_cap) c = max_cap;
            plan.cursor[r].count = static_cast<int32_t>(c);
            if (c > max_count) max_count = c;
        } else {
            for (int64_t p = plan.seg_base[r]; c > 0; ++p, c -= split_cap)
                plan.cursor[p].count =
                    static_cast<int32_t>(c < split_cap ? c : split_cap);
            max_count = std::max(max_count, split_cap);
        }
    }

    // one bucket per ladder step that holds a row: only the ladder's last
    // step can exceed max_cap, so distinct steps are distinct caps
    const std::vector<int64_t> ladder = build_ladder(max_count, min_cap, growth);
    auto step_of = [&](int32_t count) {
        return std::lower_bound(ladder.begin(), ladder.end(),
                                static_cast<int64_t>(count)) - ladder.begin();
    };
    std::vector<int64_t> rows_in_step(ladder.size(), 0);
    for (const Cursor& cu : plan.cursor)
        if (cu.count > 0) rows_in_step[step_of(cu.count)] += 1;
    const int64_t rm = row_multiple > 0 ? row_multiple : 1;
    std::vector<int64_t> bucket_of_step(ladder.size(), -1);
    std::vector<int64_t> row_off, elem_off;
    int64_t ro = 0, eo = 0;
    for (size_t s = 0; s < ladder.size(); ++s) {
        if (rows_in_step[s] == 0) continue;
        bucket_of_step[s] = static_cast<int64_t>(plan.caps.size());
        const int64_t cap =
            max_cap > 0 && ladder[s] > max_cap ? max_cap : ladder[s];
        const int64_t rpad = (rows_in_step[s] + rm - 1) / rm * rm;
        plan.caps.push_back(cap);
        plan.rpads.push_back(rpad);
        row_off.push_back(ro);
        elem_off.push_back(eo);
        ro += rpad;
        eo += rpad * cap;
    }
    if (plan.caps.size() > kMaxBuckets) return kTooManyCaps;

    // each pseudo-row's slot in its bucket: ascending id
    plan.has_seg.assign(plan.caps.size(), 0);
    plan.rows_out.assign(static_cast<size_t>(ro), n_rows);
    plan.segmap_out.assign(static_cast<size_t>(ro),
                           static_cast<int32_t>(plan.split_rows.size()));
    std::vector<int64_t> next_slot(plan.caps.size(), 0);
    size_t split_slot = 0;  // of the split row whose segments p walks
    for (int64_t p = 0; p < n_prow; ++p) {
        Cursor& cu = plan.cursor[p];
        if (cu.count == 0) continue;
        const int64_t b = bucket_of_step[step_of(cu.count)];
        const int64_t slot = next_slot[b]++;
        cu.next = elem_off[b] + slot * plan.caps[b];
        cu.pad = static_cast<int32_t>(plan.caps[b] - cu.count);
        if (p < n_rows) {
            plan.rows_out[row_off[b] + slot] = static_cast<int32_t>(p);
            continue;
        }
        while (split_slot + 1 < plan.split_rows.size() &&
               plan.seg_base[plan.split_rows[split_slot + 1]] <= p)
            ++split_slot;
        plan.rows_out[row_off[b] + slot] = plan.split_rows[split_slot];
        plan.segmap_out[row_off[b] + slot] = static_cast<int32_t>(split_slot);
        plan.has_seg[b] = 1;
    }
    return static_cast<int64_t>(plan.caps.size());
}

// Places every entry. The caller's cols/vals/mask buffers arrive zeroed.
void fill(Plan& plan, const int32_t* rows, const int32_t* cols,
          const float* vals, int32_t* cols_out, float* vals_out,
          float* mask_out) {
    const int64_t n = plan.n;
    // an entry's pseudo-row, from its rank among its row's entries in the
    // caller's order; -1 past max_cap. Counts the row's column-0 entries
    // on the way: they say where its padding starts.
    std::vector<int32_t> seen(static_cast<size_t>(plan.n_rows), 0);
    std::vector<int32_t> zeros(plan.cursor.size(), 0);
    auto pseudo_row = [&](int32_t r, int32_t c) -> int32_t {
        const int32_t rank = seen[r]++;
        if (plan.max_cap > 0 && rank >= plan.max_cap) return -1;
        const int32_t base = plan.seg_base[r];
        const int32_t p =
            base < 0 ? r : base + static_cast<int32_t>(rank / plan.split_cap);
        if (c == 0) zeros[p] += 1;
        return p;
    };
    // entries arrive in column order, ties in the caller's: the next free
    // cell of the row is the entry's place in the column-sorted row
    auto place = [&](int32_t p, int32_t c, float v) {
        if (p < 0) return;
        Cursor& cu = plan.cursor[p];
        const int64_t q = cu.next++ + (c != 0 ? cu.pad : 0);
        cols_out[q] = c;
        vals_out[q] = v;
    };

    if (plan.counting) {
        struct Rec {
            int32_t p;
            float v;
        };
        std::vector<int32_t> at(static_cast<size_t>(plan.col_range) + 1, 0);
        for (int64_t k = 0; k < n; ++k) at[cols[k] + 1] += 1;
        for (int64_t c = 0; c < plan.col_range; ++c) at[c + 1] += at[c];
        std::unique_ptr<Rec[]> recs(new Rec[static_cast<size_t>(n)]);
        for (int64_t k = 0; k < n; ++k)
            recs[at[cols[k]]++] = Rec{pseudo_row(rows[k], cols[k]), vals[k]};
        // at[c] is now where column c ends
        int64_t j = 0;
        for (int64_t c = 0; c < plan.col_range; ++c)
            for (const int64_t end = at[c]; j < end; ++j)
                place(recs[j].p, static_cast<int32_t>(c), recs[j].v);
    } else {
        struct Key {
            int32_t c, k, p;
        };
        std::vector<Key> keys(static_cast<size_t>(n));
        for (int64_t k = 0; k < n; ++k)
            keys[k] = Key{cols[k], static_cast<int32_t>(k),
                          pseudo_row(rows[k], cols[k])};
        std::sort(keys.begin(), keys.end(), [](const Key& a, const Key& b) {
            return a.c != b.c ? a.c < b.c : a.k < b.k;
        });
        for (const Key& e : keys) place(e.p, e.c, vals[e.k]);
    }

    // the mask in one forward pass: ones, the padding's zeros, ones
    for (size_t p = 0; p < plan.cursor.size(); ++p) {
        const Cursor& cu = plan.cursor[p];
        if (cu.count == 0) continue;
        float* row = mask_out + (cu.next - cu.count);
        std::fill(row, row + zeros[p], 1.0f);
        std::fill(row + zeros[p] + cu.pad, row + cu.pad + cu.count, 1.0f);
    }
}

}  // namespace

extern "C" {

// Phase 1. Returns the number of buckets, or < 0 when it declines (see
// the k* codes). Writes per-bucket capacity, padded row count and whether
// the bucket holds segment rows into out_caps / out_rpads / out_has_seg
// (63 slots each), the split table's length and the column path (1
// counting, 0 comparison) into out_info[0..1], and the plan to hand to
// pio_bucket_fill and pio_bucket_free into out_plan.
int64_t pio_bucket_plan(const int32_t* rows, const int32_t* cols, int64_t n,
                        int32_t n_rows, int64_t row_multiple, int64_t max_cap,
                        int64_t split_cap, int64_t min_cap, double growth,
                        void** out_plan, int64_t* out_caps,
                        int64_t* out_rpads, int64_t* out_has_seg,
                        int64_t* out_info) {
    *out_plan = nullptr;
    try {
        std::unique_ptr<Plan> plan(new Plan);
        const int64_t nb = build_plan(rows, cols, n, n_rows, row_multiple,
                                      max_cap, split_cap, min_cap, growth,
                                      *plan);
        if (nb < 0) return nb;
        std::copy(plan->caps.begin(), plan->caps.end(), out_caps);
        std::copy(plan->rpads.begin(), plan->rpads.end(), out_rpads);
        std::copy(plan->has_seg.begin(), plan->has_seg.end(), out_has_seg);
        out_info[0] = static_cast<int64_t>(plan->split_rows.size());
        out_info[1] = plan->counting ? 1 : 0;
        *out_plan = plan.release();
        return nb;
    } catch (const std::bad_alloc&) {
        return kNoMemory;
    }
}

// Phase 2, once per plan: fill the caller's flat buffers, buckets in
// ascending-cap order, concatenated.
//   rows_out, segmap_out: [sum(rpads)] int32
//   cols_out/vals_out/mask_out: [sum(rpads[b] * caps[b])], ZEROED by the
//     caller (numpy's calloc hands out untouched zero pages)
//   split_out: [out_info[0]] int32, the split table
// Returns 0, or kNoMemory.
int64_t pio_bucket_fill(void* plan_ptr, const int32_t* rows,
                        const int32_t* cols, const float* vals,
                        int32_t* rows_out, int32_t* cols_out,
                        float* vals_out, float* mask_out,
                        int32_t* segmap_out, int32_t* split_out) {
    Plan& plan = *static_cast<Plan*>(plan_ptr);
    try {
        std::copy(plan.rows_out.begin(), plan.rows_out.end(), rows_out);
        std::copy(plan.segmap_out.begin(), plan.segmap_out.end(), segmap_out);
        std::copy(plan.split_rows.begin(), plan.split_rows.end(), split_out);
        fill(plan, rows, cols, vals, cols_out, vals_out, mask_out);
        return 0;
    } catch (const std::bad_alloc&) {
        return kNoMemory;
    }
}

void pio_bucket_free(void* plan_ptr) { delete static_cast<Plan*>(plan_ptr); }

// COO → CSR by row. indptr: [n_rows + 1] int64, items: [n] int32, both the
// caller's. Row r's columns end up in items[indptr[r] .. indptr[r + 1]) in
// the caller's order. Returns 0, or kBadIds (a row id outside [0, n_rows))
// with the outputs undefined.
int64_t pio_group_rows(const int32_t* rows, const int32_t* cols, int64_t n,
                       int64_t n_rows, int64_t* indptr, int32_t* items) {
    if (n_rows < 0) return kBadIds;
    std::fill(indptr, indptr + n_rows + 1, 0);
    for (int64_t k = 0; k < n; ++k) {
        const int32_t r = rows[k];
        if (r < 0 || r >= n_rows) return kBadIds;
        indptr[r + 1] += 1;
    }
    for (int64_t r = 0; r < n_rows; ++r) indptr[r + 1] += indptr[r];
    // indptr[r] is row r's next free slot, and its end once every entry is
    // placed: the starts, one slot down
    for (int64_t k = 0; k < n; ++k) items[indptr[rows[k]]++] = cols[k];
    std::copy_backward(indptr, indptr + n_rows, indptr + n_rows + 1);
    indptr[0] = 0;
    return 0;
}

}  // extern "C"
