// filodb_tpu native runtime: columnar codecs + block arena.
//
// Counterpart of the reference's off-heap "native tier"
// (memory/src/main/scala/filodb.memory: UnsafeUtils + jffi page allocation,
// NibblePack.scala, DeltaDeltaVector.scala, DoubleVector XOR encoding,
// BlockManager.scala) — here as real native code exposed through a C ABI
// consumed via ctypes. Byte-identical wire format with the numpy reference
// implementation in filodb_tpu/memory/nibblepack.py.
//
// Build: make -C native   (produces libfilodb_native.so)

#include <algorithm>
#include <charconv>
#include <cstdint>
#include <cstring>
#include <cstdlib>
#include <atomic>
#include <deque>
#include <initializer_list>
#include <limits>
#include <new>
#include <string>
#include <string_view>
#include <memory>
#include <unordered_map>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// zigzag

void zigzag_encode_i64(const int64_t* in, uint64_t* out, int64_t n) {
    for (int64_t i = 0; i < n; i++) {
        int64_t v = in[i];
        out[i] = (static_cast<uint64_t>(v) << 1) ^ static_cast<uint64_t>(v >> 63);
    }
}

void zigzag_decode_u64(const uint64_t* in, int64_t* out, int64_t n) {
    for (int64_t i = 0; i < n; i++) {
        uint64_t u = in[i];
        out[i] = static_cast<int64_t>(u >> 1) ^ -static_cast<int64_t>(u & 1);
    }
}

// ---------------------------------------------------------------------------
// NibblePack (see filodb_tpu/memory/nibblepack.py for the format spec)

static inline int nibble_width(uint64_t x) {
    if (x == 0) return 1;
    return (64 - __builtin_clzll(x) + 3) / 4;
}

static inline int trailing_zero_nibbles(uint64_t x) {
    if (x == 0) return 16;
    return __builtin_ctzll(x) / 4;
}

// out must have capacity >= 2 + 8*9 bytes per group of 8 (worst case);
// returns bytes written.
int64_t nibble_pack(const uint64_t* vals, int64_t n, uint8_t* out) {
    uint8_t* p = out;
    for (int64_t g = 0; g < n; g += 8) {
        uint64_t group[8] = {0, 0, 0, 0, 0, 0, 0, 0};
        int64_t cnt = (n - g) < 8 ? (n - g) : 8;
        std::memcpy(group, vals + g, cnt * sizeof(uint64_t));
        uint8_t bitmap = 0;
        for (int i = 0; i < 8; i++)
            if (group[i]) bitmap |= (1u << i);
        *p++ = bitmap;
        if (!bitmap) continue;
        int tz = 16, lead = 1;
        for (int i = 0; i < 8; i++) {
            if (!group[i]) continue;
            int t = trailing_zero_nibbles(group[i]);
            if (t < tz) tz = t;
            int w = nibble_width(group[i]);
            if (w > lead) lead = w;
        }
        int num_nibbles = lead - tz;
        *p++ = static_cast<uint8_t>(((num_nibbles - 1) << 4) | tz);
        // pack nibbles little-endian across nonzero values (128-bit
        // accumulator: up to 64 value bits on top of <8 residual bits)
        unsigned __int128 acc = 0;
        int acc_bits = 0;
        uint64_t mask = (num_nibbles >= 16) ? ~0ULL
                        : ((1ULL << (4 * num_nibbles)) - 1);
        for (int i = 0; i < 8; i++) {
            if (!group[i]) continue;
            uint64_t x = (group[i] >> (4 * tz)) & mask;
            acc |= static_cast<unsigned __int128>(x) << acc_bits;
            acc_bits += 4 * num_nibbles;
            while (acc_bits >= 8) {
                *p++ = static_cast<uint8_t>(acc & 0xFF);
                acc >>= 8;
                acc_bits -= 8;
            }
        }
        if (acc_bits > 0) *p++ = static_cast<uint8_t>(acc & 0xFF);
    }
    return p - out;
}

// returns bytes consumed, or -1 on truncated input.
int64_t nibble_unpack(const uint8_t* in, int64_t in_len, uint64_t* out,
                      int64_t count) {
    const uint8_t* p = in;
    const uint8_t* end = in + in_len;
    int64_t idx = 0;
    while (idx < count) {
        if (p >= end) return -1;
        uint8_t bitmap = *p++;
        if (!bitmap) {
            for (int i = 0; i < 8 && idx + i < count; i++) out[idx + i] = 0;
            idx += 8;
            continue;
        }
        if (p >= end) return -1;
        uint8_t desc = *p++;
        int num_nibbles = (desc >> 4) + 1;
        int tz = desc & 0xF;
        int nnz = __builtin_popcount(bitmap);
        int64_t nbytes = (static_cast<int64_t>(nnz) * num_nibbles + 1) / 2;
        if (p + nbytes > end) return -1;
        uint64_t mask = (num_nibbles >= 16) ? ~0ULL
                        : ((1ULL << (4 * num_nibbles)) - 1);
        // stream nibbles from the byte stream (128-bit accumulator)
        unsigned __int128 acc = 0;
        int acc_bits = 0;
        const uint8_t* q = p;
        for (int i = 0; i < 8; i++) {
            uint64_t v = 0;
            if (bitmap & (1u << i)) {
                while (acc_bits < 4 * num_nibbles && q < p + nbytes) {
                    acc |= static_cast<unsigned __int128>(*q++) << acc_bits;
                    acc_bits += 8;
                }
                v = (static_cast<uint64_t>(acc) & mask) << (4 * tz);
                acc >>= 4 * num_nibbles;
                acc_bits -= 4 * num_nibbles;
            }
            if (idx + i < count) out[idx + i] = v;
        }
        p += nbytes;
        idx += 8;
    }
    return p - in;
}

// ---------------------------------------------------------------------------
// murmur3-32 (x86 variant) — partition-key hashing (reference uses Murmur3
// for BinaryRecord partition hashes; python-side fallback matches bit-exact)

uint32_t murmur3_32(const uint8_t* data, int64_t n, uint32_t seed) {
    const uint32_t c1 = 0xCC9E2D51u, c2 = 0x1B873593u;
    uint32_t h = seed;
    int64_t rounded = n & ~3LL;
    for (int64_t i = 0; i < rounded; i += 4) {
        uint32_t k;
        std::memcpy(&k, data + i, 4);
        k *= c1;
        k = (k << 15) | (k >> 17);
        k *= c2;
        h ^= k;
        h = (h << 13) | (h >> 19);
        h = h * 5 + 0xE6546B64u;
    }
    uint32_t k = 0;
    int64_t tail = n - rounded;
    if (tail >= 3) k ^= static_cast<uint32_t>(data[rounded + 2]) << 16;
    if (tail >= 2) k ^= static_cast<uint32_t>(data[rounded + 1]) << 8;
    if (tail >= 1) {
        k ^= data[rounded];
        k *= c1;
        k = (k << 15) | (k >> 17);
        k *= c2;
        h ^= k;
    }
    h ^= static_cast<uint32_t>(n);
    h ^= h >> 16;
    h *= 0x85EBCA6Bu;
    h ^= h >> 13;
    h *= 0xC2B2AE35u;
    h ^= h >> 16;
    return h;
}

// ---------------------------------------------------------------------------
// XOR-double prep

void xor_encode_f64(const double* in, uint64_t* out, int64_t n) {
    uint64_t prev = 0;
    for (int64_t i = 0; i < n; i++) {
        uint64_t bits;
        std::memcpy(&bits, &in[i], 8);
        out[i] = bits ^ prev;
        prev = bits;
    }
}

void xor_decode_f64(const uint64_t* in, double* out, int64_t n) {
    uint64_t acc = 0;
    for (int64_t i = 0; i < n; i++) {
        acc ^= in[i];
        std::memcpy(&out[i], &acc, 8);
    }
}

// ---------------------------------------------------------------------------
// delta-delta helpers (sloped-line predictor residuals)

// residual[i] = v[i] - (base + slope*i); returns 1 if all residuals zero
int delta_delta_residuals(const int64_t* in, int64_t n, int64_t base,
                          int64_t slope, int64_t* out) {
    int all_zero = 1;
    for (int64_t i = 0; i < n; i++) {
        out[i] = in[i] - (base + slope * i);
        if (out[i] != 0) all_zero = 0;
    }
    return all_zero;
}

void delta_delta_reconstruct(const int64_t* resid, int64_t n, int64_t base,
                             int64_t slope, int64_t* out) {
    for (int64_t i = 0; i < n; i++) out[i] = base + slope * i + resid[i];
}

// ---------------------------------------------------------------------------
// Block arena (reference BlockManager/PageAlignedBlockManager semantics:
// fixed-size page-aligned blocks, owner-tagged, reclaimable lists, stats)

struct Block {
    uint8_t* data;
    int64_t size;
    int64_t used;
    int64_t owner;
    Block* next;
};

struct Arena {
    int64_t block_size;
    std::atomic<int64_t> allocated_blocks;
    std::atomic<int64_t> reclaimed_blocks;
    std::atomic<int64_t> bytes_in_use;
    Block* free_list;
    Block* used_list;
};

void* arena_create(int64_t block_size) {
    Arena* a = new (std::nothrow) Arena();
    if (!a) return nullptr;
    a->block_size = block_size;
    a->allocated_blocks = 0;
    a->reclaimed_blocks = 0;
    a->bytes_in_use = 0;
    a->free_list = nullptr;
    a->used_list = nullptr;
    return a;
}

// allocate one block for an owner; returns block handle (or null)
void* arena_alloc_block(void* arena, int64_t owner) {
    Arena* a = static_cast<Arena*>(arena);
    Block* b = a->free_list;
    if (b) {
        a->free_list = b->next;
    } else {
        b = new (std::nothrow) Block();
        if (!b) return nullptr;
        // page-aligned like the reference's PageAlignedBlockManager
        if (posix_memalign(reinterpret_cast<void**>(&b->data), 4096,
                           a->block_size) != 0) {
            delete b;
            return nullptr;
        }
        b->size = a->block_size;
        a->allocated_blocks++;
    }
    b->used = 0;
    b->owner = owner;
    b->next = a->used_list;
    a->used_list = b;
    a->bytes_in_use += a->block_size;
    return b;
}

// bump-allocate within a block; returns offset or -1 when full
int64_t block_alloc(void* block, int64_t nbytes) {
    Block* b = static_cast<Block*>(block);
    int64_t aligned = (nbytes + 7) & ~7LL;
    if (b->used + aligned > b->size) return -1;
    int64_t off = b->used;
    b->used += aligned;
    return off;
}

uint8_t* block_data(void* block) { return static_cast<Block*>(block)->data; }
int64_t block_remaining(void* block) {
    Block* b = static_cast<Block*>(block);
    return b->size - b->used;
}

// reclaim all blocks of an owner back to the free list; returns count
int64_t arena_reclaim_owner(void* arena, int64_t owner) {
    Arena* a = static_cast<Arena*>(arena);
    Block** prev = &a->used_list;
    int64_t n = 0;
    while (*prev) {
        Block* b = *prev;
        if (b->owner == owner) {
            *prev = b->next;
            b->next = a->free_list;
            a->free_list = b;
            a->bytes_in_use -= a->block_size;
            a->reclaimed_blocks++;
            n++;
        } else {
            prev = &b->next;
        }
    }
    return n;
}

int64_t arena_stats(void* arena, int64_t which) {
    Arena* a = static_cast<Arena*>(arena);
    switch (which) {
        case 0: return a->allocated_blocks.load();
        case 1: return a->reclaimed_blocks.load();
        case 2: return a->bytes_in_use.load();
        default: return -1;
    }
}

void arena_destroy(void* arena) {
    Arena* a = static_cast<Arena*>(arena);
    for (Block* l : {a->free_list, a->used_list}) {
        while (l) {
            Block* nxt = l->next;
            std::free(l->data);
            delete l;
            l = nxt;
        }
    }
    delete a;
}

// ---------------------------------------------------------------------------
// Shard ingest core — the native hot loop.
//
// Counterpart of the reference's per-shard ingest path
// (core/src/main/scala/filodb.core/memstore/TimeSeriesShard.scala:570 →
// TimeSeriesPartition.scala:137 appenders over off-heap buffers): parses
// binary RecordContainer v2 bytes directly (no per-record host-language
// objects), looks partitions up in a native hash map keyed by the canonical
// part-key bytes, appends to growable columnar buffers, and seals full
// buffers into encoded chunks (delta-delta timestamps + XOR-double values,
// byte-identical to the numpy codecs) — the Python layer only sees whole
// sealed chunks and partition-creation events.

namespace {

struct NSealed {
    int64_t id, start, end;
    int32_t nrows;
    // bit c: value column c holds a NaN (bit 31: some column >= 31 does);
    // lets the batched read count a NaN-free column without decoding it
    uint32_t nan_cols = 0;
    std::string ts_bytes;
    std::vector<std::string> col_bytes;
};

// first-class histogram state lives in a side table keyed by pid: only
// histogram partitions pay for it, keeping sizeof(NPart) lean for the
// 1M-series scalar case (the ZeroCopyUTF8String-era memory discipline)
struct HistState {
    int32_t nb = 0;
    std::vector<double> les;
    std::vector<int64_t> rows;  // ts.size() x nb, row-major
};

struct NPart {
    // canonical key bytes (schema_id + label blob) interned in the core's
    // append-only key arena — one copy total (NPart and the by_key map
    // both view it); the reference's zero-copy label tier analog
    std::string_view key;
    uint32_t hash = 0;
    bool alive = true;
    int64_t floor_ts = -1;   // dedup floor (recovery / eviction)
    int64_t first_ts = -1;
    int32_t seq = 0;
    int64_t flushed_id = -1;
    int64_t version = 0;     // bumped on seal/evict (python cache key)
    int64_t samples_sealed = 0;
    std::vector<int64_t> ts;
    std::vector<std::vector<double>> cols;
    std::vector<NSealed> sealed;
    // >=0: the schema column index of this partition's histogram column;
    // bucket state in ShardCore::hist. The cols[] slot carries NaN
    // placeholders so shape invariants (lockstep growth, buf copy) hold.
    int32_t hist_col = -1;

    int64_t latest() const {
        int64_t t = floor_ts;
        if (!ts.empty()) {
            if (ts.back() > t) t = ts.back();
        } else if (!sealed.empty()) {
            if (sealed.back().end > t) t = sealed.back().end;
        }
        return t;
    }
};

struct ShardCore {
    int32_t max_chunk;
    int32_t groups;
    std::vector<int64_t> watermarks;
    std::unordered_map<std::string_view, int32_t> by_key;
    std::deque<NPart> parts;  // stable references; index == pid
    std::unordered_map<int32_t, HistState> hist;  // pid -> hist state
    std::vector<int32_t> new_parts;
    int64_t rows_skipped = 0, rows_ooo = 0, rows_ingested = 0;
    int64_t rows_incompat = 0;  // value shape mismatched the partition
    // key arena: append-only stable storage for interned key bytes (block
    // pointers never move; views into blocks stay valid for the core's
    // lifetime — freed partitions leave small holes until destruction)
    std::vector<std::unique_ptr<char[]>> key_blocks;
    size_t key_block_used = 0;
    // encode scratch (single-writer per shard)
    std::vector<int64_t> resid;
    std::vector<uint64_t> words;
    std::vector<uint8_t> packed;

    static constexpr size_t KEY_BLOCK = 1 << 18;

    std::string_view intern_key(const char* d, size_t len) {
        if (key_blocks.empty()
            || key_block_used + len > KEY_BLOCK) {
            size_t cap = len > KEY_BLOCK ? len : KEY_BLOCK;
            key_blocks.emplace_back(new char[cap]);
            key_block_used = 0;
        }
        char* dst = key_blocks.back().get() + key_block_used;
        std::memcpy(dst, d, len);
        key_block_used += len;
        return std::string_view(dst, len);
    }
};

inline uint16_t rd_u16(const uint8_t* p) {
    uint16_t v; std::memcpy(&v, p, 2); return v;
}
inline uint32_t rd_u32(const uint8_t* p) {
    uint32_t v; std::memcpy(&v, p, 4); return v;
}
inline int64_t rd_i64(const uint8_t* p) {
    int64_t v; std::memcpy(&v, p, 8); return v;
}

inline int64_t floordiv_i64(int64_t a, int64_t b) {
    int64_t q = a / b, r = a % b;
    return (r != 0 && ((r < 0) != (b < 0))) ? q - 1 : q;
}

// delta-delta codec, byte-identical to codecs.encode_delta_delta:
// u8 codec | u32 n | i64 base | i64 slope [| nibble_pack(zigzag(resid))]
void encode_dd(ShardCore* c, const int64_t* v, int64_t n, std::string& out) {
    int64_t base = n ? v[0] : 0;
    int64_t slope = n > 1 ? floordiv_i64(v[n - 1] - base, n - 1) : 0;
    c->resid.resize(n);
    int all_zero = delta_delta_residuals(v, n, base, slope, c->resid.data());
    uint8_t head[21];
    head[0] = (n && !all_zero) ? 1 : 2;  // CODEC_DELTA_DELTA(_CONST)
    uint32_t n32 = (uint32_t)n;
    std::memcpy(head + 1, &n32, 4);
    std::memcpy(head + 5, &base, 8);
    std::memcpy(head + 13, &slope, 8);
    out.assign((char*)head, 21);
    if (n && !all_zero) {
        c->words.resize(n);
        zigzag_encode_i64(c->resid.data(), c->words.data(), n);
        c->packed.resize(16 + n * 9 + 64);
        int64_t m = nibble_pack(c->words.data(), n, c->packed.data());
        out.append((char*)c->packed.data(), m);
    }
}

// XOR-double codec, byte-identical to codecs.encode_xor_double:
// u8 codec=3 | u32 n | nibble_pack(xor-prep)
void encode_xor(ShardCore* c, const double* v, int64_t n, std::string& out) {
    uint8_t head[5];
    head[0] = 3;
    uint32_t n32 = (uint32_t)n;
    std::memcpy(head + 1, &n32, 4);
    out.assign((char*)head, 5);
    c->words.resize(n);
    xor_encode_f64(v, c->words.data(), n);
    c->packed.resize(16 + n * 9 + 64);
    int64_t m = nibble_pack(c->words.data(), n, c->packed.data());
    out.append((char*)c->packed.data(), m);
}

// Hist-2D-delta codec, byte-identical to codecs.encode_hist_2d_delta:
// u8 codec=4 | u32 n | u32 nb | f64*nb les | nibble_pack(zigzag(
//   delta-across-time(delta-across-buckets(rows))))
void encode_hist2d(ShardCore* c, const HistState& hs, int64_t n,
                   std::string& out) {
    uint32_t nb = (uint32_t)hs.nb;
    uint8_t head[9];
    head[0] = 4;
    uint32_t n32 = (uint32_t)n;
    std::memcpy(head + 1, &n32, 4);
    std::memcpy(head + 5, &nb, 4);
    out.assign((char*)head, 9);
    out.append((const char*)hs.les.data(), (size_t)nb * 8);
    int64_t total = n * (int64_t)nb;
    if (!total) return;
    c->resid.resize(total);
    const int64_t* r = hs.rows.data();
    for (int64_t i = 0; i < n; i++) {
        for (int64_t j = 0; j < (int64_t)nb; j++) {
            int64_t bd = r[i * nb + j] - (j ? r[i * nb + j - 1] : 0);
            int64_t pbd = i ? (r[(i - 1) * nb + j]
                               - (j ? r[(i - 1) * nb + j - 1] : 0)) : 0;
            c->resid[i * nb + j] = bd - pbd;
        }
    }
    c->words.resize(total);
    zigzag_encode_i64(c->resid.data(), c->words.data(), total);
    c->packed.resize(16 + total * 9 + 64);
    int64_t m = nibble_pack(c->words.data(), total, c->packed.data());
    out.append((char*)c->packed.data(), m);
}

void seal_part(ShardCore* c, int32_t pid, NPart& p) {
    int64_t n = (int64_t)p.ts.size();
    if (!n) return;
    HistState* hs = nullptr;
    if (p.hist_col >= 0) {
        auto hit = c->hist.find(pid);
        if (hit != c->hist.end()) hs = &hit->second;
    }
    NSealed s;
    s.nrows = (int32_t)n;
    s.start = p.ts[0];
    s.end = p.ts[n - 1];
    s.id = (s.start << 12) | (int64_t)(p.seq & 0xFFF);
    p.seq = (p.seq + 1) & 0xFFF;
    encode_dd(c, p.ts.data(), n, s.ts_bytes);
    s.col_bytes.resize(p.cols.size());
    for (size_t i = 0; i < p.cols.size(); i++) {
        if ((int32_t)i == p.hist_col && hs != nullptr)
            encode_hist2d(c, *hs, n, s.col_bytes[i]);
        else
            encode_xor(c, p.cols[i].data(), n, s.col_bytes[i]);
        for (double v : p.cols[i]) {
            if (v != v) {
                s.nan_cols |= 1u << (i < 31 ? i : 31);
                break;
            }
        }
    }
    p.samples_sealed += n;
    p.sealed.push_back(std::move(s));
    p.ts.clear();
    for (auto& col : p.cols) col.clear();
    if (hs != nullptr) hs->rows.clear();
    p.version++;
}

}  // namespace

void* shard_core_create(int32_t max_chunk_size, int32_t groups) {
    ShardCore* c = new ShardCore();
    c->max_chunk = max_chunk_size;
    c->groups = groups > 0 ? groups : 1;
    c->watermarks.assign(c->groups, -1);
    return c;
}

void shard_core_destroy(void* cp) { delete static_cast<ShardCore*>(cp); }

void shard_core_set_watermark(void* cp, int32_t group, int64_t off) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    if (group >= 0 && group < c->groups) c->watermarks[group] = off;
}

// Parse + ingest one binary RecordContainer (format: core/record.py v2).
// Value shapes covered: scalar f64 (tag 0) and first-class histogram
// les+counts (tag 1, at most one per record — reference multi-schema
// ingest, TimeSeriesShard.scala:570). Returns rows ingested, or -1 on a
// malformed/uncovered container: it is then NOT ingested at all and the
// caller takes the host fallback path. All-or-nothing via a validate pass.
int64_t shard_core_ingest(void* cp, const uint8_t* d, int64_t len,
                          int64_t offset) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    if (len < 5 || d[0] != 2) return -1;
    uint32_t nrec = rd_u32(d + 1);
    // pass 1: validate shapes and bounds
    int64_t off = 5;
    for (uint32_t i = 0; i < nrec; i++) {
        if (off + 4 > len) return -1;
        uint32_t rl = rd_u32(d + off);
        off += 4;
        int64_t end = off + rl;
        if (end > len || rl < 17) return -1;
        int64_t o = off + 14;
        uint16_t nl = rd_u16(d + o);
        o += 2;
        for (uint16_t j = 0; j < nl; j++) {
            if (o + 2 > end) return -1;
            o += 2 + rd_u16(d + o);
            if (o + 2 > end) return -1;
            o += 2 + rd_u16(d + o);
        }
        if (o + 1 > end) return -1;
        uint8_t nv = d[o];
        o += 1;
        if (nv == 0) return -1;
        int hists = 0;
        for (uint8_t j = 0; j < nv; j++) {
            if (o + 1 > end) return -1;
            uint8_t tag = d[o];
            if (tag == 0) {
                if (o + 9 > end) return -1;
                o += 9;
            } else if (tag == 1) {
                if (o + 3 > end) return -1;
                uint16_t nb = rd_u16(d + o + 1);
                if (nb == 0 || nb > 4096) return -1;
                if (o + 3 + (int64_t)nb * 16 > end) return -1;
                o += 3 + (int64_t)nb * 16;
                if (++hists > 1) return -1;  // one hist column per record
            } else {
                return -1;  // strings/other shapes take the host path
            }
        }
        if (o != end) return -1;
        off = end;
    }
    // pass 2: ingest
    int64_t ingested = 0;
    off = 5;
    for (uint32_t i = 0; i < nrec; i++) {
        uint32_t rl = rd_u32(d + off);
        off += 4;
        int64_t end = off + rl;
        uint32_t hash = rd_u32(d + off);
        int64_t ts = rd_i64(d + off + 4);
        int64_t key_off = off + 12;  // schema id + labels = canonical key
        int64_t o = key_off + 2;
        uint16_t nl = rd_u16(d + o);
        o += 2;
        for (uint16_t j = 0; j < nl; j++) {
            o += 2 + rd_u16(d + o);
            o += 2 + rd_u16(d + o);
        }
        int64_t key_len = o - key_off;
        uint8_t nv = d[o];
        o += 1;
        // per-value layout walk (validated in pass 1)
        int64_t voff[256];
        uint8_t vtag[256];
        uint16_t vnb[256];
        int32_t rec_hist = -1;
        {
            int64_t vo = o;
            for (uint16_t j = 0; j < nv; j++) {
                vtag[j] = d[vo];
                voff[j] = vo;
                if (d[vo] == 0) {
                    vnb[j] = 0;
                    vo += 9;
                } else {
                    vnb[j] = rd_u16(d + vo + 1);
                    rec_hist = (int32_t)j;
                    vo += 3 + (int64_t)vnb[j] * 16;
                }
            }
        }
        int32_t group = (int32_t)(hash % (uint32_t)c->groups);
        if (offset <= c->watermarks[group]) {
            c->rows_skipped++;
            off = end;
            continue;
        }
        std::string_view probe((const char*)d + key_off, key_len);
        auto it = c->by_key.find(probe);
        NPart* p;
        int32_t pid;
        if (it == c->by_key.end()) {
            pid = (int32_t)c->parts.size();
            c->parts.emplace_back();
            p = &c->parts.back();
            p->key = c->intern_key((const char*)d + key_off, key_len);
            p->hash = hash;
            p->cols.resize(nv);
            p->ts.reserve(8);
            for (auto& col : p->cols) col.reserve(8);
            if (rec_hist >= 0) {
                p->hist_col = rec_hist;
                HistState& hs = c->hist[pid];
                hs.nb = vnb[rec_hist];
                hs.les.resize(hs.nb);
                std::memcpy(hs.les.data(), d + voff[rec_hist] + 3,
                            (size_t)hs.nb * 8);
            }
            c->by_key.emplace(p->key, pid);
            c->new_parts.push_back(pid);
        } else {
            pid = it->second;
            p = &c->parts[pid];
        }
        // a record whose hist position disagrees with the partition's
        // shape cannot append without desyncing columns — drop it. An
        // EMPTY partition (pre-created via shard_core_create_part or a
        // snapshot bootstrap, which don't know value shapes) adopts the
        // first record's shape instead.
        if (rec_hist != p->hist_col) {
            if (rec_hist >= 0 && p->hist_col < 0 && p->ts.empty()
                    && p->sealed.empty()) {
                p->hist_col = rec_hist;
                HistState& hs = c->hist[pid];
                hs.nb = vnb[rec_hist];
                hs.les.resize(hs.nb);
                std::memcpy(hs.les.data(), d + voff[rec_hist] + 3,
                            (size_t)hs.nb * 8);
            } else {
                c->rows_incompat++;
                off = end;
                continue;
            }
        }
        if (ts <= p->latest()) {
            c->rows_ooo++;
            off = end;
            continue;
        }
        HistState* hsp = nullptr;
        if (p->hist_col >= 0) {
            hsp = &c->hist[pid];  // one lookup per record, reused below
            uint16_t nb = vnb[p->hist_col];
            if ((int32_t)nb != hsp->nb) {
                // bucket-scheme change forces a chunk switch (mirrors
                // TimeSeriesPartition.ingest host semantics)
                if (!p->ts.empty()) seal_part(c, pid, *p);
                hsp->nb = nb;
                hsp->les.resize(nb);
            }
            std::memcpy(hsp->les.data(), d + voff[p->hist_col] + 3,
                        (size_t)nb * 8);
        }
        if (p->first_ts < 0) p->first_ts = ts;
        p->ts.push_back(ts);
        // Every column must grow in lockstep with ts: a crafted container
        // whose later record carries fewer values than the partition's
        // column count would otherwise leave short columns, and seal-time
        // encoders read ts.size() elements (heap OOB). Missing values pad
        // with NaN; extra values are dropped.
        for (size_t j = 0; j < p->cols.size(); j++) {
            double x = std::numeric_limits<double>::quiet_NaN();
            if (j < (size_t)nv && vtag[j] == 0)
                std::memcpy(&x, d + voff[j] + 1, 8);
            p->cols[j].push_back(x);
        }
        if (hsp != nullptr) {
            const uint8_t* counts = d + voff[p->hist_col] + 3
                + (int64_t)hsp->nb * 8;
            size_t base = hsp->rows.size();
            hsp->rows.resize(base + hsp->nb);
            std::memcpy(hsp->rows.data() + base, counts,
                        (size_t)hsp->nb * 8);
        }
        if ((int32_t)p->ts.size() >= c->max_chunk) seal_part(c, pid, *p);
        ingested++;
        off = end;
    }
    c->rows_ingested += ingested;
    return ingested;
}

int64_t shard_core_stat(void* cp, int32_t which) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    switch (which) {
        case 0: return c->rows_ingested;
        case 1: return c->rows_skipped;
        case 2: return c->rows_ooo;
        case 3: return (int64_t)c->parts.size();
        case 4: return (int64_t)c->new_parts.size();
        case 5: return c->rows_incompat;
        default: return -1;
    }
}

int32_t shard_core_drain_new(void* cp, int32_t* out, int32_t cap) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    int32_t n = (int32_t)c->new_parts.size();
    if (n > cap) n = cap;
    for (int32_t i = 0; i < n; i++) out[i] = c->new_parts[i];
    c->new_parts.erase(c->new_parts.begin(), c->new_parts.begin() + n);
    return n;
}

// O(1) part lookup by canonical key bytes; -1 when absent. Restored shards
// need no host-language key dictionary — this map is authoritative.
int32_t shard_core_lookup(void* cp, const uint8_t* key, int32_t key_len) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    std::string_view probe((const char*)key, key_len);
    auto it = c->by_key.find(probe);
    return it == c->by_key.end() ? -1 : it->second;
}

// Bulk restore from an index snapshot: entries laid out as
//   u32 key_len | key bytes | u32 hash | i64 floor | u8 alive | u8 ncols
// pid == entry ordinal; key_len==0 marks a purged tombstone slot.
// Returns entries restored, or -1 on a malformed buffer.
int64_t shard_core_bootstrap(void* cp, const uint8_t* d, int64_t len) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    if (!c->parts.empty()) return -1;  // only into an empty core
    int64_t off = 0, n = 0;
    while (off < len) {
        if (off + 4 > len) return -1;
        uint32_t kl = rd_u32(d + off);
        off += 4;
        if (off + kl + 14 > len) return -1;
        c->parts.emplace_back();
        NPart& p = c->parts.back();
        int64_t key_off2 = off;
        off += kl;
        p.hash = rd_u32(d + off);
        p.floor_ts = rd_i64(d + off + 4);
        p.alive = kl != 0 && d[off + 12] != 0;
        uint8_t ncols = d[off + 13];
        off += 14;
        if (p.alive) {
            // intern only LIVE keys: tombstone bytes would otherwise leak
            // in the append-only arena on every snapshot restore
            p.key = c->intern_key((const char*)d + key_off2, kl);
            p.cols.resize(ncols ? ncols : 1);
            c->by_key.emplace(p.key, (int32_t)(c->parts.size() - 1));
        }
        n++;
    }
    return n;
}

int64_t part_floor(void* cp, int32_t pid) {
    return static_cast<ShardCore*>(cp)->parts[pid].floor_ts;
}

// bulk floor export for index snapshots (one call, not one per series)
void shard_core_floors(void* cp, int64_t* out, int64_t cap) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    int64_t n = (int64_t)c->parts.size();
    if (n > cap) n = cap;
    for (int64_t i = 0; i < n; i++) out[i] = c->parts[i].floor_ts;
}

// snapshot export: the exact bootstrap layout, built in one pass in C++
// (key_off/key_len let the host slice key blobs without re-parsing)
int64_t shard_core_export_size(void* cp) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    int64_t total = 0;
    for (auto& p : c->parts) total += 4 + (int64_t)p.key.size() + 14;
    return total;
}

void shard_core_export(void* cp, uint8_t* out, int64_t* key_off,
                       int32_t* key_len) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    int64_t off = 0;
    int64_t i = 0;
    for (auto& p : c->parts) {
        uint32_t kl = (uint32_t)p.key.size();
        std::memcpy(out + off, &kl, 4);
        off += 4;
        key_off[i] = off;
        key_len[i] = (int32_t)kl;
        if (kl) std::memcpy(out + off, p.key.data(), kl);
        off += kl;
        std::memcpy(out + off, &p.hash, 4);
        std::memcpy(out + off + 4, &p.floor_ts, 8);
        out[off + 12] = p.alive ? 1 : 0;
        out[off + 13] = (uint8_t)p.cols.size();
        off += 14;
        i++;
    }
}

// bulk floor seeding (post-bootstrap delta from the column store)
void shard_core_seed_floors(void* cp, const int32_t* pids,
                            const int64_t* floors, int64_t n) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    for (int64_t i = 0; i < n; i++) {
        NPart& p = c->parts[pids[i]];
        if (floors[i] > p.floor_ts) p.floor_ts = floors[i];
    }
}

int32_t shard_core_create_part(void* cp, const uint8_t* key, int32_t key_len,
                               uint32_t hash, int32_t ncols) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    std::string_view probe((const char*)key, key_len);
    auto it = c->by_key.find(probe);
    if (it != c->by_key.end()) return it->second;
    int32_t pid = (int32_t)c->parts.size();
    c->parts.emplace_back();
    NPart& p = c->parts.back();
    p.key = c->intern_key((const char*)key, key_len);
    p.hash = hash;
    p.cols.resize(ncols > 0 ? ncols : 1);
    c->by_key.emplace(p.key, pid);
    return pid;
}

int32_t shard_core_key_len(void* cp, int32_t pid) {
    return (int32_t)static_cast<ShardCore*>(cp)->parts[pid].key.size();
}
void shard_core_key_copy(void* cp, int32_t pid, uint8_t* out) {
    std::string_view k = static_cast<ShardCore*>(cp)->parts[pid].key;
    std::memcpy(out, k.data(), k.size());
}
uint32_t shard_core_part_hash(void* cp, int32_t pid) {
    return static_cast<ShardCore*>(cp)->parts[pid].hash;
}

int64_t part_append(void* cp, int32_t pid, int64_t ts, const double* vals,
                    int32_t nvals) {
    // host-fallback single append: the CALLER counts drops (returning 0
    // here already feeds stats.out_of_order_dropped; bumping rows_ooo too
    // would double-count when the delta sync runs)
    ShardCore* c = static_cast<ShardCore*>(cp);
    NPart& p = c->parts[pid];
    if (ts <= p.latest()) return 0;
    // a histogram partition must take part_append_hist: fabricating an
    // all-zero cumulative bucket row here would read as a counter reset
    // and corrupt every later rate()/increase() window
    if (p.hist_col >= 0) return 0;
    if (p.first_ts < 0) p.first_ts = ts;
    p.ts.push_back(ts);
    for (int32_t j = 0; j < nvals && j < (int32_t)p.cols.size(); j++)
        p.cols[j].push_back(vals[j]);
    if ((int32_t)p.ts.size() >= c->max_chunk) seal_part(c, pid, p);
    c->rows_ingested++;
    return 1;
}

// Host-fallback single append for histogram partitions: ``dvals`` carries
// every value column in schema order (the entry at the hist column is
// ignored); les+counts carry the bucket scheme and cumulative counts.
int64_t part_append_hist(void* cp, int32_t pid, int64_t ts,
                         const double* dvals, int32_t ndv,
                         const double* les, const int64_t* counts,
                         int32_t nb, int32_t hist_col) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    NPart& p = c->parts[pid];
    if (nb <= 0 || nb > 4096 || hist_col < 0) return 0;
    if (p.hist_col < 0 && p.ts.empty() && p.sealed.empty()) {
        p.hist_col = hist_col;  // first sample fixes the hist column
        HistState& hs0 = c->hist[pid];
        hs0.nb = nb;
        hs0.les.assign(les, les + nb);
    }
    if (hist_col != p.hist_col) return 0;
    if (ts <= p.latest()) return 0;
    HistState& hs = c->hist[pid];
    if (nb != hs.nb) {
        if (!p.ts.empty()) seal_part(c, pid, p);
        hs.nb = nb;
        hs.les.resize(nb);
    }
    hs.les.assign(les, les + nb);
    if (p.first_ts < 0) p.first_ts = ts;
    p.ts.push_back(ts);
    for (int32_t j = 0; j < (int32_t)p.cols.size(); j++)
        p.cols[j].push_back(
            j < ndv && j != hist_col
                ? dvals[j] : std::numeric_limits<double>::quiet_NaN());
    size_t base = hs.rows.size();
    hs.rows.resize(base + nb);
    std::memcpy(hs.rows.data() + base, counts, (size_t)nb * 8);
    if ((int32_t)p.ts.size() >= c->max_chunk) seal_part(c, pid, p);
    c->rows_ingested++;
    return 1;
}

int32_t part_hist_col(void* cp, int32_t pid) {
    return static_cast<ShardCore*>(cp)->parts[pid].hist_col;
}
int32_t part_hist_nb(void* cp, int32_t pid) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    auto it = c->hist.find(pid);
    return it == c->hist.end() ? 0 : it->second.nb;
}
void part_hist_les(void* cp, int32_t pid, double* out) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    auto it = c->hist.find(pid);
    if (it != c->hist.end())
        std::memcpy(out, it->second.les.data(), it->second.les.size() * 8);
}
// copies up to n buffer rows of bucket counts, row-major [n][nb]
int32_t part_buf_hist_copy(void* cp, int32_t pid, int32_t n, int64_t* out) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    auto it = c->hist.find(pid);
    if (it == c->hist.end() || it->second.nb <= 0) return 0;
    HistState& hs = it->second;
    int32_t have = (int32_t)(hs.rows.size() / hs.nb);
    if (n > have) n = have;
    std::memcpy(out, hs.rows.data(), (size_t)n * hs.nb * 8);
    return n;
}

int64_t part_latest_ts(void* cp, int32_t pid) {
    return static_cast<ShardCore*>(cp)->parts[pid].latest();
}
int64_t part_first_ts(void* cp, int32_t pid) {
    return static_cast<ShardCore*>(cp)->parts[pid].first_ts;
}
int64_t part_earliest_ts(void* cp, int32_t pid) {
    NPart& p = static_cast<ShardCore*>(cp)->parts[pid];
    if (!p.sealed.empty()) return p.sealed.front().start;
    if (!p.ts.empty()) return p.ts.front();
    return -1;
}
int64_t part_num_samples(void* cp, int32_t pid) {
    NPart& p = static_cast<ShardCore*>(cp)->parts[pid];
    return p.samples_sealed + (int64_t)p.ts.size();
}
int64_t part_version(void* cp, int32_t pid) {
    return static_cast<ShardCore*>(cp)->parts[pid].version;
}
int32_t part_buf_count(void* cp, int32_t pid) {
    return (int32_t)static_cast<ShardCore*>(cp)->parts[pid].ts.size();
}
int32_t part_ncols(void* cp, int32_t pid) {
    return (int32_t)static_cast<ShardCore*>(cp)->parts[pid].cols.size();
}
// copies up to n rows (snapshot prefix); cols_out laid out column-major
// [ncols][n]
int32_t part_buf_copy(void* cp, int32_t pid, int32_t n, int64_t* ts_out,
                      double* cols_out) {
    NPart& p = static_cast<ShardCore*>(cp)->parts[pid];
    int32_t have = (int32_t)p.ts.size();
    if (n > have) n = have;
    std::memcpy(ts_out, p.ts.data(), n * 8);
    for (size_t ci = 0; ci < p.cols.size(); ci++)
        std::memcpy(cols_out + ci * n, p.cols[ci].data(), n * 8);
    return n;
}

int32_t part_seal_buffer(void* cp, int32_t pid) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    NPart& p = c->parts[pid];
    if (p.ts.empty()) return 0;
    seal_part(c, pid, p);
    return 1;
}

int32_t part_num_sealed(void* cp, int32_t pid) {
    return (int32_t)static_cast<ShardCore*>(cp)->parts[pid].sealed.size();
}
void part_sealed_meta(void* cp, int32_t pid, int32_t idx, int64_t* out4) {
    NSealed& s = static_cast<ShardCore*>(cp)->parts[pid].sealed[idx];
    out4[0] = s.id;
    out4[1] = s.start;
    out4[2] = s.end;
    out4[3] = s.nrows;
}
int64_t part_sealed_veclen(void* cp, int32_t pid, int32_t idx, int32_t col) {
    NSealed& s = static_cast<ShardCore*>(cp)->parts[pid].sealed[idx];
    if (col == 0) return (int64_t)s.ts_bytes.size();
    return (int64_t)s.col_bytes[col - 1].size();
}
void part_sealed_veccopy(void* cp, int32_t pid, int32_t idx, int32_t col,
                         uint8_t* out) {
    NSealed& s = static_cast<ShardCore*>(cp)->parts[pid].sealed[idx];
    const std::string& b = col == 0 ? s.ts_bytes : s.col_bytes[col - 1];
    std::memcpy(out, b.data(), b.size());
}

void part_mark_flushed(void* cp, int32_t pid, int64_t up_to_id) {
    NPart& p = static_cast<ShardCore*>(cp)->parts[pid];
    if (up_to_id > p.flushed_id) p.flushed_id = up_to_id;
}
int64_t part_flushed_id(void* cp, int32_t pid) {
    return static_cast<ShardCore*>(cp)->parts[pid].flushed_id;
}

int32_t part_evict_flushed(void* cp, int32_t pid) {
    NPart& p = static_cast<ShardCore*>(cp)->parts[pid];
    int32_t dropped = 0;
    int64_t floor = p.floor_ts;
    std::vector<NSealed> keep;
    for (auto& s : p.sealed) {
        if (s.id <= p.flushed_id) {
            if (s.end > floor) floor = s.end;
            dropped++;
        } else {
            keep.push_back(std::move(s));
        }
    }
    if (dropped) {
        p.sealed = std::move(keep);
        p.floor_ts = floor;
        p.version++;
    }
    return dropped;
}

void part_seed_floor(void* cp, int32_t pid, int64_t ts) {
    NPart& p = static_cast<ShardCore*>(cp)->parts[pid];
    if (ts > p.floor_ts) p.floor_ts = ts;
}

// whole-shard encoded chunk footprint in one call (the flush scheduler
// checks the memory budget every tick; per-partition calls would be O(n)
// FFI round trips at high cardinality)
int64_t shard_core_chunk_bytes(void* cp) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    int64_t total = 0;
    for (auto& p : c->parts) {
        for (auto& s : p.sealed) {
            total += (int64_t)s.ts_bytes.size();
            for (auto& cb : s.col_bytes) total += (int64_t)cb.size();
        }
    }
    return total;
}

int64_t part_chunk_bytes(void* cp, int32_t pid) {
    NPart& p = static_cast<ShardCore*>(cp)->parts[pid];
    int64_t n = 0;
    for (auto& s : p.sealed) {
        n += (int64_t)s.ts_bytes.size();
        for (auto& cb : s.col_bytes) n += (int64_t)cb.size();
    }
    return n;
}

void part_free(void* cp, int32_t pid) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    NPart& p = c->parts[pid];
    if (!p.alive) return;
    c->by_key.erase(p.key);
    c->hist.erase(pid);
    p.alive = false;
    p.key = std::string_view();  // arena bytes leak until core teardown
    p.ts.clear();
    p.ts.shrink_to_fit();
    p.cols.clear();
    p.cols.shrink_to_fit();
    p.sealed.clear();
    p.sealed.shrink_to_fit();
}

// ---------------------------------------------------------------------------
// TagIndex: native part-key inverted index hot paths.
//
// Counterpart of the reference's PartKeyLuceneIndex postings + query ops
// (core/src/main/scala/filodb.core/memstore/PartKeyLuceneIndex.scala:455,494)
// and its JMH PartKeyIndexBenchmark. Two tiers, mirroring the Python
// structure in filodb_tpu/core/memstore/index.py:
//   - frozen: per label, a sorted value table (offset-indexed bytes) and a
//     flat pid array — bulk-loaded from index snapshots, binary-searched;
//   - tail: per label, value -> pid vector (pids ascend with creation order).
// Liveness/tombstones and [start,end] time bounds stay on the Python side
// (numpy masks); this structure is postings only.

namespace {

struct FrozenLab {
    std::vector<uint32_t> voff;  // [nv+1]
    std::string vblob;
    std::vector<int64_t> poff;   // [nv+1]
    std::vector<int32_t> pids;   // sorted within each value slice

    int64_t nv() const {
        return voff.empty() ? 0 : (int64_t)voff.size() - 1;
    }
    int64_t find(const char* v, int64_t len) const {
        int64_t lo = 0, hi = nv();
        while (lo < hi) {
            int64_t mid = (lo + hi) / 2;
            const char* mv = vblob.data() + voff[mid];
            int64_t ml = (int64_t)voff[mid + 1] - voff[mid];
            int cmp = std::memcmp(mv, v, ml < len ? ml : len);
            bool less = cmp < 0 || (cmp == 0 && ml < len);
            if (less) lo = mid + 1; else hi = mid;
        }
        if (lo < nv()) {
            const char* mv = vblob.data() + voff[lo];
            int64_t ml = (int64_t)voff[lo + 1] - voff[lo];
            if (ml == len && std::memcmp(mv, v, len) == 0) return lo;
        }
        return -1;
    }
};

struct TagLab {
    FrozenLab frozen;
    std::unordered_map<std::string, std::vector<int32_t>> tail;
};

struct TagIndex {
    std::unordered_map<std::string, int32_t> label_ids;
    std::vector<std::string> label_names;
    std::vector<TagLab> labs;
    // merged-export staging (sizes call builds; export call copies+clears)
    FrozenLab exp_tmp;
    std::vector<int32_t> scratch;

    TagLab* find_lab(const char* name, int64_t len) {
        auto it = label_ids.find(std::string(name, len));
        return it == label_ids.end() ? nullptr : &labs[it->second];
    }
    TagLab& get_lab(const std::string& name) {
        auto it = label_ids.find(name);
        if (it != label_ids.end()) return labs[it->second];
        label_ids.emplace(name, (int32_t)labs.size());
        label_names.push_back(name);
        labs.emplace_back();
        return labs.back();
    }
};

// merge two sorted unique ranges into out (unique)
static int64_t merge2(const int32_t* a, int64_t na, const int32_t* b,
                      int64_t nb, int32_t* out) {
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        int32_t x = a[i], y = b[j];
        if (x < y) { out[k++] = x; i++; }
        else if (y < x) { out[k++] = y; j++; }
        else { out[k++] = x; i++; j++; }
    }
    while (i < na) out[k++] = a[i++];
    while (j < nb) out[k++] = b[j++];
    return k;
}

// postings of (lab, value) merged across tiers into vec (sorted unique)
static void equals_into(TagLab* lab, const char* v, int64_t vl,
                        std::vector<int32_t>& vec) {
    vec.clear();
    const int32_t* fp = nullptr;
    int64_t fn = 0;
    int64_t vi = lab->frozen.find(v, vl);
    if (vi >= 0) {
        fp = lab->frozen.pids.data() + lab->frozen.poff[vi];
        fn = lab->frozen.poff[vi + 1] - lab->frozen.poff[vi];
    }
    auto it = lab->tail.find(std::string(v, vl));
    const int32_t* tp = nullptr;
    int64_t tn = 0;
    if (it != lab->tail.end()) {
        tp = it->second.data();
        tn = (int64_t)it->second.size();
    }
    vec.resize(fn + tn);
    vec.resize(merge2(fp, fn, tp, tn, vec.data()));
}

static int64_t copy_out(const std::vector<int32_t>& vec, int32_t* out,
                        int64_t cap) {
    int64_t n = (int64_t)vec.size();
    if (n > cap) return -n;  // caller re-calls with a bigger buffer
    std::memcpy(out, vec.data(), n * sizeof(int32_t));
    return n;
}

}  // namespace

void* tagindex_create() { return new TagIndex(); }
void tagindex_destroy(void* h) { delete static_cast<TagIndex*>(h); }

// key blob: [u16 schema][u16 nl][(u16 kl, k bytes)(u16 vl, v bytes)]*
// (canonical part-key layout shared with ShardCore records)
int32_t tagindex_add(void* h, int32_t pid, const uint8_t* key, int32_t len) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    if (len < 4) return -1;
    int64_t o = 2;
    uint16_t nl = rd_u16(key + o);
    o += 2;
    for (uint16_t i = 0; i < nl; i++) {
        if (o + 2 > len) return -1;
        uint16_t kl = rd_u16(key + o);
        o += 2;
        if (o + kl + 2 > len) return -1;
        std::string name((const char*)key + o, kl);
        o += kl;
        uint16_t vl = rd_u16(key + o);
        o += 2;
        if (o + vl > len) return -1;
        TagLab& lab = ix->get_lab(name);
        auto& vec = lab.tail[std::string((const char*)key + o, vl)];
        o += vl;
        if (vec.empty() || vec.back() < pid) {
            vec.push_back(pid);
        } else if (vec.back() != pid) {  // out-of-order (restore/readd)
            auto it = std::lower_bound(vec.begin(), vec.end(), pid);
            if (it == vec.end() || *it != pid) vec.insert(it, pid);
        }
    }
    return 0;
}

// remove pid from every posting list (rare: pid re-created after eviction
// with a different key; normal removals are Python-side tombstones).
// Frozen arrays are physically compacted to keep every slice sorted+unique.
void tagindex_purge_pid(void* h, int32_t pid) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    for (auto& lab : ix->labs) {
        for (auto& kv : lab.tail) {
            auto& vec = kv.second;
            auto it = std::lower_bound(vec.begin(), vec.end(), pid);
            if (it != vec.end() && *it == pid) vec.erase(it);
        }
        auto& fr = lab.frozen;
        bool hit = false;
        for (int64_t vi = 0; vi < fr.nv() && !hit; vi++) {
            const int32_t* b = fr.pids.data() + fr.poff[vi];
            const int32_t* e = fr.pids.data() + fr.poff[vi + 1];
            const int32_t* it = std::lower_bound(b, e, pid);
            hit = it != e && *it == pid;
        }
        if (!hit) continue;
        int64_t w = 0;
        std::vector<int64_t> npoff(1, 0);
        for (int64_t vi = 0; vi < fr.nv(); vi++) {
            for (int64_t k = fr.poff[vi]; k < fr.poff[vi + 1]; k++)
                if (fr.pids[k] != pid) fr.pids[w++] = fr.pids[k];
            npoff.push_back(w);
        }
        fr.pids.resize(w);
        fr.poff = std::move(npoff);
    }
}

int64_t tagindex_equals(void* h, const char* labn, int64_t ll,
                        const char* v, int64_t vl, int32_t* out,
                        int64_t cap) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    TagLab* lab = ix->find_lab(labn, ll);
    if (!lab) return 0;
    equals_into(lab, v, vl, ix->scratch);
    return copy_out(ix->scratch, out, cap);
}

// pairs: [(u16 kl, k)(u16 vl, v)]*; intersection of equals postings.
// Zero-materialization: each filter's postings stay as its (frozen, tail)
// sorted range pair; the smallest filter's merged enumeration is membership-
// checked against every other filter's two ranges with resumable cursors.
int64_t tagindex_intersect_equals(void* h, const uint8_t* pairs,
                                  int32_t npairs, int32_t* out, int64_t cap) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    struct Ranges {
        const int32_t* fp; int64_t fn;  // frozen slice
        const int32_t* tp; int64_t tn;  // tail vector
        int64_t fi = 0, ti = 0;         // resumable cursors
        int64_t total() const { return fn + tn; }
        bool contains(int32_t x) {
            // ascending probes: cursors only move forward
            int64_t step = 1;
            while (fi + step < fn && fp[fi + step] < x) step <<= 1;
            int64_t hi2 = fi + step < fn ? fi + step : fn;
            fi = std::lower_bound(fp + fi, fp + hi2, x) - fp;
            if (fi < fn && fp[fi] == x) return true;
            step = 1;
            while (ti + step < tn && tp[ti + step] < x) step <<= 1;
            hi2 = ti + step < tn ? ti + step : tn;
            ti = std::lower_bound(tp + ti, tp + hi2, x) - tp;
            return ti < tn && tp[ti] == x;
        }
    };
    std::vector<Ranges> rs(npairs);
    int64_t o = 0;
    for (int32_t i = 0; i < npairs; i++) {
        uint16_t kl = rd_u16(pairs + o);
        o += 2;
        const char* k = (const char*)pairs + o;
        o += kl;
        uint16_t vl = rd_u16(pairs + o);
        o += 2;
        const char* v = (const char*)pairs + o;
        o += vl;
        TagLab* lab = ix->find_lab(k, kl);
        if (!lab) return 0;
        Ranges& r = rs[i];
        r.fp = nullptr; r.fn = 0; r.tp = nullptr; r.tn = 0;
        int64_t vi = lab->frozen.find(v, vl);
        if (vi >= 0) {
            r.fp = lab->frozen.pids.data() + lab->frozen.poff[vi];
            r.fn = lab->frozen.poff[vi + 1] - lab->frozen.poff[vi];
        }
        auto it = lab->tail.find(std::string(v, vl));
        if (it != lab->tail.end()) {
            r.tp = it->second.data();
            r.tn = (int64_t)it->second.size();
        }
        if (r.total() == 0) return 0;
    }
    // smallest filter drives the enumeration
    int32_t si = 0;
    for (int32_t i = 1; i < npairs; i++)
        if (rs[i].total() < rs[si].total()) si = i;
    Ranges& s = rs[si];
    std::vector<int32_t>& res = ix->scratch;
    res.clear();
    int64_t fi = 0, ti = 0;
    while (fi < s.fn || ti < s.tn) {
        int32_t x;
        if (fi < s.fn && (ti >= s.tn || s.fp[fi] <= s.tp[ti])) {
            x = s.fp[fi];
            if (ti < s.tn && s.tp[ti] == x) ti++;
            fi++;
        } else {
            x = s.tp[ti++];
        }
        if (x == INT32_MIN) continue;  // purge sentinel
        bool all = true;
        for (int32_t i = 0; i < npairs && all; i++)
            if (i != si) all = rs[i].contains(x);
        if (all) res.push_back(x);
    }
    return copy_out(res, out, cap);
}

// batch add: pids[n], concatenated key blobs with offsets[n+1]
int32_t tagindex_add_batch(void* h, const int32_t* pids, int64_t n,
                           const uint8_t* blobs, const int64_t* offsets) {
    for (int64_t i = 0; i < n; i++) {
        int32_t rc = tagindex_add(h, pids[i], blobs + offsets[i],
                                  (int32_t)(offsets[i + 1] - offsets[i]));
        if (rc != 0) return rc;
    }
    return 0;
}

// one-shot: equals intersection + time-overlap predicate
// (starts[pid] <= end_t && ends[pid] >= start_t), the full
// partIdsFromFilters fast path in a single native call.
int64_t tagindex_query_equals(void* h, const uint8_t* pairs, int32_t npairs,
                              const int64_t* starts, const int64_t* ends,
                              int64_t bounds_len, int64_t start_t,
                              int64_t end_t, int32_t* out, int64_t cap) {
    int64_t n = tagindex_intersect_equals(h, pairs, npairs, out, cap);
    if (n < 0) return n;  // caller re-buffers; scratch still holds result
    int64_t w = 0;
    for (int64_t i = 0; i < n; i++) {
        int32_t pid = out[i];
        // pids beyond the caller's bounds snapshot (added concurrently)
        // are not visible to this query
        if (pid < bounds_len && starts[pid] <= end_t
            && ends[pid] >= start_t)
            out[w++] = pid;
    }
    return w;
}

// query_equals + an extra sorted allow-list intersected in the same pass —
// the regex fast path: equals postings ∩ cached regex postings ∩ time
// predicate, all in one call (no per-query numpy round trips host-side)
int64_t tagindex_query_equals_allow(void* h, const uint8_t* pairs,
                                    int32_t npairs, const int32_t* allow,
                                    int64_t allow_len, const int64_t* starts,
                                    const int64_t* ends, int64_t bounds_len,
                                    int64_t start_t, int64_t end_t,
                                    int32_t* out, int64_t cap) {
    int64_t n;
    if (npairs > 0) {
        n = tagindex_intersect_equals(h, pairs, npairs, out, cap);
        if (n < 0) return n;
    } else {
        // no equals filters: the allow list IS the candidate set
        n = allow_len < cap ? allow_len : cap;
        if (allow_len > cap) return -allow_len;
        std::memcpy(out, allow, n * 4);
    }
    int64_t w = 0, a = 0;
    for (int64_t i = 0; i < n; i++) {
        int32_t pid = out[i];
        if (npairs > 0) {  // gallop the sorted allow list alongside
            while (a < allow_len && allow[a] < pid) a++;
            if (a >= allow_len) break;
            if (allow[a] != pid) continue;
        }
        if (pid < bounds_len && starts[pid] <= end_t
            && ends[pid] >= start_t)
            out[w++] = pid;
    }
    return w;
}

// union of every posting of a label ("has this label at all")
int64_t tagindex_label_all(void* h, const char* labn, int64_t ll,
                           int32_t* out, int64_t cap) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    TagLab* lab = ix->find_lab(labn, ll);
    if (!lab) return 0;
    std::vector<int32_t>& res = ix->scratch;
    res.clear();
    res.insert(res.end(), lab->frozen.pids.begin(), lab->frozen.pids.end());
    for (auto& kv : lab->tail)
        res.insert(res.end(), kv.second.begin(), kv.second.end());
    std::sort(res.begin(), res.end());
    res.erase(std::unique(res.begin(), res.end()), res.end());
    if (!res.empty() && res.front() == INT32_MIN)
        res.erase(res.begin());
    return copy_out(res, out, cap);
}

// value enumeration: frozen values first (vid 0..nv-1), then tail values in
// map order (vid nv..). Stable between a values() call and a following
// union_values() call as long as no adds happen in between.
int64_t tagindex_values_size(void* h, const char* labn, int64_t ll) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    TagLab* lab = ix->find_lab(labn, ll);
    if (!lab) return 0;
    int64_t sz = 0;
    for (int64_t vi = 0; vi < lab->frozen.nv(); vi++)
        sz += 4 + (lab->frozen.voff[vi + 1] - lab->frozen.voff[vi]);
    for (auto& kv : lab->tail) sz += 4 + (int64_t)kv.first.size();
    return sz;
}

void tagindex_values(void* h, const char* labn, int64_t ll, uint8_t* out) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    TagLab* lab = ix->find_lab(labn, ll);
    if (!lab) return;
    uint8_t* p = out;
    for (int64_t vi = 0; vi < lab->frozen.nv(); vi++) {
        uint32_t n = lab->frozen.voff[vi + 1] - lab->frozen.voff[vi];
        std::memcpy(p, &n, 4);
        p += 4;
        std::memcpy(p, lab->frozen.vblob.data() + lab->frozen.voff[vi], n);
        p += n;
    }
    for (auto& kv : lab->tail) {
        uint32_t n = (uint32_t)kv.first.size();
        std::memcpy(p, &n, 4);
        p += 4;
        std::memcpy(p, kv.first.data(), n);
        p += n;
    }
}

// union postings of the vids listed (vid space as enumerated above)
int64_t tagindex_union_values(void* h, const char* labn, int64_t ll,
                              const int32_t* vids, int64_t n, int32_t* out,
                              int64_t cap) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    TagLab* lab = ix->find_lab(labn, ll);
    if (!lab) return 0;
    int64_t nfrozen = lab->frozen.nv();
    std::vector<int32_t>& res = ix->scratch;
    res.clear();
    std::vector<const std::vector<int32_t>*> tails;
    tails.reserve(lab->tail.size());
    for (auto& kv : lab->tail) tails.push_back(&kv.second);
    for (int64_t i = 0; i < n; i++) {
        int64_t vi = vids[i];
        if (vi < nfrozen) {
            res.insert(res.end(),
                       lab->frozen.pids.begin() + lab->frozen.poff[vi],
                       lab->frozen.pids.begin() + lab->frozen.poff[vi + 1]);
        } else if (vi - nfrozen < (int64_t)tails.size()) {
            const auto& t = *tails[vi - nfrozen];
            res.insert(res.end(), t.begin(), t.end());
        }
    }
    std::sort(res.begin(), res.end());
    res.erase(std::unique(res.begin(), res.end()), res.end());
    if (!res.empty() && res.front() == INT32_MIN)
        res.erase(res.begin());
    return copy_out(res, out, cap);
}

int64_t tagindex_num_labels(void* h) {
    return (int64_t)static_cast<TagIndex*>(h)->label_names.size();
}

int64_t tagindex_labels_size(void* h) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    int64_t sz = 0;
    for (auto& n : ix->label_names) sz += 4 + (int64_t)n.size();
    return sz;
}

void tagindex_labels(void* h, uint8_t* out) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    uint8_t* p = out;
    for (auto& nm : ix->label_names) {
        uint32_t n = (uint32_t)nm.size();
        std::memcpy(p, &n, 4);
        p += 4;
        std::memcpy(p, nm.data(), n);
        p += n;
    }
}

// ---- snapshot export/load -------------------------------------------------
// Export merges frozen + tail, drops `deleted` pids (sorted array) and the
// INT32_MIN purge sentinels, and produces the snapshot array layout.
// Two-phase: sizes() builds into exp_tmp, export() copies it out.

int64_t tagindex_export_sizes(void* h, const char* labn, int64_t ll,
                              const int32_t* deleted, int64_t ndel,
                              int64_t* out3) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    TagLab* lab = ix->find_lab(labn, ll);
    FrozenLab& t = ix->exp_tmp;
    t.voff.assign(1, 0);
    t.vblob.clear();
    t.poff.assign(1, 0);
    t.pids.clear();
    if (lab) {
        auto keep = [&](int32_t pid) {
            if (pid == INT32_MIN) return false;
            if (!ndel) return true;
            const int32_t* e = deleted + ndel;
            const int32_t* it = std::lower_bound(deleted, e, pid);
            return !(it != e && *it == pid);
        };
        // ordered value walk: frozen table is sorted; tail keys must be
        // sorted and merged with it
        std::vector<std::pair<std::string, const std::vector<int32_t>*>>
            tails;
        tails.reserve(lab->tail.size());
        for (auto& kv : lab->tail) tails.emplace_back(kv.first, &kv.second);
        std::sort(tails.begin(), tails.end(),
                  [](const auto& a, const auto& b) {
                      return a.first < b.first;
                  });
        int64_t fi = 0, ti = 0;
        int64_t nf = lab->frozen.nv();
        std::vector<int32_t> merged;
        while (fi < nf || ti < (int64_t)tails.size()) {
            std::string fv;
            bool use_f = false, use_t = false;
            if (fi < nf) {
                fv.assign(lab->frozen.vblob.data() + lab->frozen.voff[fi],
                          lab->frozen.voff[fi + 1] - lab->frozen.voff[fi]);
            }
            if (fi < nf && ti < (int64_t)tails.size()) {
                int c = fv.compare(tails[ti].first);
                use_f = c <= 0;
                use_t = c >= 0;
            } else if (fi < nf) {
                use_f = true;
            } else {
                use_t = true;
            }
            const std::string& vname = use_f ? fv : tails[ti].first;
            merged.clear();
            if (use_f) {
                for (int64_t k = lab->frozen.poff[fi];
                     k < lab->frozen.poff[fi + 1]; k++) {
                    int32_t pid = lab->frozen.pids[k];
                    if (keep(pid)) merged.push_back(pid);
                }
                fi++;
            }
            if (use_t) {
                size_t base = merged.size();
                for (int32_t pid : *tails[ti].second)
                    if (keep(pid)) merged.push_back(pid);
                if (base && merged.size() > base)
                    std::inplace_merge(merged.begin(),
                                       merged.begin() + base, merged.end());
                ti++;
            }
            merged.erase(std::unique(merged.begin(), merged.end()),
                         merged.end());
            if (merged.empty()) continue;
            t.vblob += vname;
            t.voff.push_back((uint32_t)t.vblob.size());
            t.pids.insert(t.pids.end(), merged.begin(), merged.end());
            t.poff.push_back((int64_t)t.pids.size());
        }
    }
    out3[0] = t.nv();
    out3[1] = (int64_t)t.vblob.size();
    out3[2] = (int64_t)t.pids.size();
    return 0;
}

void tagindex_export_label(void* h, uint32_t* voff, uint8_t* vblob,
                           int64_t* poff, int32_t* pids) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    FrozenLab& t = ix->exp_tmp;
    std::memcpy(voff, t.voff.data(), t.voff.size() * 4);
    std::memcpy(vblob, t.vblob.data(), t.vblob.size());
    std::memcpy(poff, t.poff.data(), t.poff.size() * 8);
    std::memcpy(pids, t.pids.data(), t.pids.size() * 4);
}

void tagindex_load_label(void* h, const char* labn, int64_t ll,
                         const uint32_t* voff, int64_t nv,
                         const uint8_t* vblob, int64_t vlen,
                         const int64_t* poff, const int32_t* pids,
                         int64_t npids) {
    TagIndex* ix = static_cast<TagIndex*>(h);
    TagLab& lab = ix->get_lab(std::string(labn, ll));
    lab.frozen.voff.assign(voff, voff + nv + 1);
    lab.frozen.vblob.assign((const char*)vblob, vlen);
    lab.frozen.poff.assign(poff, poff + nv + 1);
    lab.frozen.pids.assign(pids, pids + npids);
}

// ---------------------------------------------------------------------------
// batched write-buffer window fold (aggregate-sidecar query lane)
//
// For each pid and each window (t0[w], t1[w]], folds the buffer samples of
// value column `col` (index into NPart::cols) into a 12-double stats row:
//   [count, sum, sumsq, min, max, first_ts, first_val, last_ts, last_val,
//    resets, corr, changes]
// NaN samples are skipped; accumulation is strictly sequential, matching
// the numpy cumsum semantics of memory/chunk.summarize_values bit for bit.
// flags_out[i]: bit0 = buffer timestamps non-monotone (caller must bypass),
// bit1 = a sealed chunk overlaps (min t0, max t1] (buffer-only fold is
// incomplete for this partition).
int32_t shard_buf_fold(void* cp, const int32_t* pids, int32_t npids,
                       const int64_t* t0s, const int64_t* t1s, int32_t nwin,
                       int32_t col, double* out, int32_t* flags_out) {
    ShardCore* c = static_cast<ShardCore*>(cp);
    const double qnan = std::numeric_limits<double>::quiet_NaN();
    int64_t g0 = INT64_MAX, g1 = INT64_MIN;
    for (int32_t w = 0; w < nwin; w++) {
        if (t0s[w] < g0) g0 = t0s[w];
        if (t1s[w] > g1) g1 = t1s[w];
    }
    for (int32_t i = 0; i < npids; i++) {
        NPart& p = c->parts[pids[i]];
        int32_t flags = 0;
        for (auto& s : p.sealed)
            if (s.end > g0 && s.start <= g1) { flags |= 2; break; }
        size_t n = p.ts.size();
        if (col < 0 || (size_t)col >= p.cols.size()) flags |= 1;
        for (size_t k = 1; k < n; k++)
            if (p.ts[k] < p.ts[k - 1]) { flags |= 1; break; }
        flags_out[i] = flags;
        double* rows = out + (size_t)i * nwin * 12;
        if (flags & 1) continue;
        const int64_t* ts = p.ts.data();
        const double* vals = p.cols[col].data();
        for (int32_t w = 0; w < nwin; w++) {
            double* r = rows + (size_t)w * 12;
            size_t lo = std::upper_bound(ts, ts + n, t0s[w]) - ts;
            size_t hi = std::upper_bound(ts, ts + n, t1s[w]) - ts;
            double cnt = 0, sum = 0, sumsq = 0, mn = qnan, mx = qnan;
            double fts = qnan, fv = qnan, lts = qnan, lv = qnan;
            double resets = 0, corr = 0, changes = 0;
            bool have_prev = false;
            double prev = 0;
            for (size_t k = lo; k < hi; k++) {
                double v = vals[k];
                if (v != v) continue;
                cnt += 1;
                sum += v;
                sumsq += v * v;
                if (!have_prev) {
                    mn = mx = v;
                    fts = (double)ts[k];
                    fv = v;
                } else {
                    if (v < mn) mn = v;
                    if (v > mx) mx = v;
                    if (v < prev) { resets += 1; corr += prev; }
                    if (v != prev) changes += 1;
                }
                lts = (double)ts[k];
                lv = v;
                prev = v;
                have_prev = true;
            }
            r[0] = cnt; r[1] = sum; r[2] = sumsq; r[3] = mn; r[4] = mx;
            r[5] = fts; r[6] = fv; r[7] = lts; r[8] = lv;
            r[9] = resets; r[10] = corr; r[11] = changes;
        }
    }
    return 0;
}


// ---------------------------------------------------------------------------
// batched series read (the batch build of the mesh engine and the exec tree)
//
// For each pid: the sealed chunks that overlap [t0, t1] decoded, in order,
// then the write buffer, for value column `col` (index into NPart::cols); a
// sample is kept iff t0 <= ts <= t1 and its value is not NaN — read_samples'
// range mask and build_batch's staleness filter. shard_batch_count counts
// what is kept, so the caller can size the batch exactly; shard_batch_fill
// then writes `ts - t0` as int32 and the value as float or double straight
// into the caller's rows. Each call runs under the shard's lock and sees one
// state of every partition. Between the two a partition may have grown:
// fill keeps at most the counted samples of a row, which are the same
// samples, since a partition only grows at its end.
//
// A sealed chunk whose column holds no NaN (NSealed::nan_cols, noted when it
// was sealed) is counted from its timestamps alone; its values are decoded
// once, by the fill.

}  // extern "C"

namespace {

struct BatchScratch {
    std::vector<uint64_t> words;
    std::vector<int64_t> ts;
    std::vector<double> vals;
    void reserve(size_t n) {
        if (words.size() < n) {
            words.resize(n);
            ts.resize(n);
            vals.resize(n);
        }
    }
};

struct CountSink {
    static constexpr bool fills = false;
    int64_t n = 0;
    inline void put(int64_t, double) { n++; }
};

template <class F>
struct FillSink {
    static constexpr bool fills = true;
    int32_t* ts;
    F* vals;
    int64_t cap;
    int64_t n = 0;
    inline void put(int64_t dt, double v) {
        if (n < cap) {
            ts[n] = (int32_t)dt;
            vals[n] = (F)v;
            n++;
        }
    }
};

// flag bits a row may carry (nonzero: the caller reads the series the
// per-series way)
constexpr int32_t BR_DEAD = 1;      // unknown or freed pid
constexpr int32_t BR_COLUMN = 2;    // no such column, or one out of step
constexpr int32_t BR_HIST = 4;      // histogram column
constexpr int32_t BR_UNSORTED = 8;  // timestamps not strictly increasing
                                    // across chunks + buffer (read_samples
                                    // sorts)
constexpr int32_t BR_CODEC = 16;    // a vector this reader does not decode
                                    // (not delta-delta / XOR-double)

template <class Sink>
int32_t batch_read_series(const ShardCore* c, int32_t pid, int32_t col,
                          int64_t t0, int64_t t1, BatchScratch& sc,
                          Sink& out, int32_t* nchunks) {
    *nchunks = 0;
    if (pid < 0 || (size_t)pid >= c->parts.size() || !c->parts[pid].alive)
        return BR_DEAD;
    const NPart& p = c->parts[pid];
    if (col < 0 || (size_t)col >= p.cols.size()
            || p.cols[col].size() != p.ts.size())
        return BR_COLUMN;
    if (p.hist_col == col) return BR_HIST;
    int32_t flags = 0;
    int64_t prev = 0;
    bool have_prev = false;
    auto scan = [&](const int64_t* ts, const double* vals, int64_t n) {
        for (int64_t k = 0; k < n; k++) {
            int64_t t = ts[k];
            if (t < t0 || t > t1) continue;
            if (have_prev && t <= prev) flags |= BR_UNSORTED;
            prev = t;
            have_prev = true;
            double v = vals ? vals[k] : 0.0;
            if (v != v) continue;
            out.put(t - t0, v);
        }
    };
    for (const NSealed& s : p.sealed) {
        if (s.end < t0 || s.start > t1) continue;
        (*nchunks)++;
        if ((size_t)col >= s.col_bytes.size()) return BR_CODEC;
        const uint8_t* tb = (const uint8_t*)s.ts_bytes.data();
        const uint8_t* vb = (const uint8_t*)s.col_bytes[col].data();
        int64_t tlen = (int64_t)s.ts_bytes.size();
        int64_t vlen = (int64_t)s.col_bytes[col].size();
        int64_t n = s.nrows;
        // ts: u8 codec (1 | 2 const) | u32 n | i64 base | i64 slope
        //     [| nibble_pack(zigzag(residuals))]
        // values: u8 codec 3 | u32 n | nibble_pack(xor-prep)
        if (tlen < 21 || (tb[0] != 1 && tb[0] != 2)
                || (int64_t)rd_u32(tb + 1) != n || vlen < 5 || vb[0] != 3
                || (int64_t)rd_u32(vb + 1) != n)
            return BR_CODEC;
        sc.reserve((size_t)n);
        int64_t base = rd_i64(tb + 5), slope = rd_i64(tb + 13);
        int64_t* ts = sc.ts.data();
        if (tb[0] == 1) {
            if (nibble_unpack(tb + 21, tlen - 21, sc.words.data(), n) < 0)
                return BR_CODEC;
            zigzag_decode_u64(sc.words.data(), ts, n);
            delta_delta_reconstruct(ts, n, base, slope, ts);
        } else {
            for (int64_t k = 0; k < n; k++) ts[k] = base + slope * k;
        }
        const double* vals = nullptr;
        if (Sink::fills || ((s.nan_cols >> (col < 31 ? col : 31)) & 1)) {
            if (nibble_unpack(vb + 5, vlen - 5, sc.words.data(), n) < 0)
                return BR_CODEC;
            xor_decode_f64(sc.words.data(), sc.vals.data(), n);
            vals = sc.vals.data();
        }
        scan(ts, vals, n);
    }
    scan(p.ts.data(), p.cols[col].data(), (int64_t)p.ts.size());
    return flags;
}

// one row of a fill: at most `cap` samples of `pid` into ts / vals
template <class F>
int32_t batch_fill_row(const ShardCore* c, int32_t pid, int32_t col,
                       int64_t t0, int64_t t1, BatchScratch& sc, int32_t* ts,
                       F* vals, int32_t cap) {
    FillSink<F> sink{ts, vals, cap};
    int32_t nchunks;
    batch_read_series(c, pid, col, t0, t1, sc, sink, &nchunks);
    return (int32_t)sink.n;
}

}  // namespace

extern "C" {

// counts_out[i]: samples kept for pids[i]; chunks_out[i]: sealed chunks that
// overlap the range (what read_samples adds to memstore_chunks_queried);
// flags_out[i]: 0, or BR_* bits — the row is then not to be trusted (its
// count reads 0) and the caller reads that series the per-series way.
int32_t shard_batch_count(void* cp, const int32_t* pids, int32_t npids,
                          int32_t col, int64_t t0, int64_t t1,
                          int32_t* counts_out, int32_t* chunks_out,
                          int32_t* flags_out) {
    const ShardCore* c = static_cast<ShardCore*>(cp);
    BatchScratch sc;
    for (int32_t i = 0; i < npids; i++) {
        CountSink sink;
        flags_out[i] = batch_read_series(c, pids[i], col, t0, t1, sc, sink,
                                         &chunks_out[i]);
        counts_out[i] = flags_out[i] ? 0 : (int32_t)sink.n;
    }
    return 0;
}

// Writes the samples of pids[i] into row row_of[i] (< 0: skipped) of ts_out
// and vals_out, whose rows are ts_stride / vals_stride elements apart:
// at most caps[i] of them (the count shard_batch_count gave, which the row
// must hold), and how many into counts_out[row]. vals_out is float where
// vals_f32, else double. What lies beyond a row's count is the caller's
// padding, untouched.
int32_t shard_batch_fill(void* cp, const int32_t* pids, int32_t npids,
                         int32_t col, int64_t t0, int64_t t1,
                         const int32_t* row_of, const int32_t* caps,
                         int32_t* ts_out, int64_t ts_stride, void* vals_out,
                         int64_t vals_stride, int32_t vals_f32,
                         int32_t* counts_out) {
    const ShardCore* c = static_cast<ShardCore*>(cp);
    BatchScratch sc;
    for (int32_t i = 0; i < npids; i++) {
        int64_t row = row_of[i];
        if (row < 0) continue;
        int32_t* ts = ts_out + row * ts_stride;
        counts_out[row] = vals_f32
            ? batch_fill_row(c, pids[i], col, t0, t1, sc, ts,
                             static_cast<float*>(vals_out)
                             + row * vals_stride, caps[i])
            : batch_fill_row(c, pids[i], col, t0, t1, sc, ts,
                             static_cast<double*>(vals_out)
                             + row * vals_stride, caps[i]);
    }
    return 0;
}

}  // extern "C"


// ---------------------------------------------------------------------------
// Prom JSON matrix writer (filodb_tpu/http/promjson.py:matrix_json_bytes)
//
// The series of a query_range answer, byte for byte what the Python renderer
// writes: a NaN step is left out, a series with no finite step is left out
// whole, values are CPython's repr(float) (Prom's "+Inf" / "-Inf" for the
// infinities), labels are json.dumps(ensure_ascii=True) of the dict
// promjson._labels_json builds.

namespace {

const char JSON_HEX[] = "0123456789abcdef";

// CPython repr of a finite double: the shortest digits that round-trip
// (to_chars' scientific form gives them), in exponent form when the decimal
// exponent is < -4 or >= 16, else positional with ".0" on an integral value
inline char* put_float_repr(char* p, double v) {
    char sci[32];
    char* end = std::to_chars(sci, sci + sizeof sci, v,
                              std::chars_format::scientific).ptr;
    *end = '\0';
    const char* e = static_cast<const char*>(memchr(sci, 'e', end - sci));
    int exp = atoi(e + 1);
    if (exp < -4 || exp >= 16) {
        memcpy(p, sci, end - sci);
        return p + (end - sci);
    }
    const char* s = sci;
    if (*s == '-') *p++ = *s++;
    char dig[24];
    int nd = 0;
    dig[nd++] = s[0];
    if (s[1] == '.')
        for (const char* q = s + 2; q < e; q++) dig[nd++] = *q;
    if (exp < 0) {
        *p++ = '0';
        *p++ = '.';
        for (int i = 0; i < -exp - 1; i++) *p++ = '0';
        memcpy(p, dig, nd);
        return p + nd;
    }
    if (nd <= exp + 1) {
        memcpy(p, dig, nd);
        p += nd;
        for (int i = nd; i < exp + 1; i++) *p++ = '0';
        *p++ = '.';
        *p++ = '0';
        return p;
    }
    memcpy(p, dig, exp + 1);
    p += exp + 1;
    *p++ = '.';
    memcpy(p, dig + exp + 1, nd - exp - 1);
    return p + nd - exp - 1;
}

inline char* put_json_u(char* p, uint32_t c) {
    *p++ = '\\';
    *p++ = 'u';
    for (int sh = 12; sh >= 0; sh -= 4) *p++ = JSON_HEX[(c >> sh) & 15];
    return p;
}

// a JSON string of UTF-8 bytes (surrogates as Python's "surrogatepass"
// writes them), escaped as json.dumps(ensure_ascii=True) does; at most
// 2 + 6 * n bytes
char* put_json_str(char* p, const uint8_t* s, int64_t n) {
    *p++ = '"';
    for (int64_t i = 0; i < n;) {
        uint8_t b = s[i];
        if (b < 0x80) {
            i++;
            if (b >= 0x20 && b < 0x7f && b != '"' && b != '\\') {
                *p++ = static_cast<char>(b);
                continue;
            }
            char esc = 0;
            switch (b) {
                case '"': esc = '"'; break;
                case '\\': esc = '\\'; break;
                case '\n': esc = 'n'; break;
                case '\r': esc = 'r'; break;
                case '\t': esc = 't'; break;
                case '\b': esc = 'b'; break;
                case '\f': esc = 'f'; break;
            }
            if (esc) {
                *p++ = '\\';
                *p++ = esc;
            } else {
                p = put_json_u(p, b);
            }
            continue;
        }
        int len = b >= 0xf0 ? 4 : b >= 0xe0 ? 3 : 2;
        uint32_t c = b & (0x7f >> len);
        for (int k = 1; k < len && i + k < n; k++) c = (c << 6) | (s[i + k] & 63);
        i += len;
        if (c >= 0x10000) {
            c -= 0x10000;
            p = put_json_u(p, 0xd800 | (c >> 10));
            p = put_json_u(p, 0xdc00 | (c & 0x3ff));
        } else {
            p = put_json_u(p, c);
        }
    }
    *p++ = '"';
    return p;
}

struct LabelStrs {
    const uint8_t* buf;
    const int64_t* offs;

    // string j; the metric label's internal name reads as "__name__"
    std::string_view name(int64_t j) const {
        std::string_view s(reinterpret_cast<const char*>(buf) + offs[j],
                           offs[j + 1] - offs[j]);
        return s == "_metric_" ? std::string_view("__name__") : s;
    }
    std::string_view raw(int64_t j) const {
        return std::string_view(reinterpret_cast<const char*>(buf) + offs[j],
                                offs[j + 1] - offs[j]);
    }
};

// the dict promjson._labels_json builds from n (name, value) pairs starting
// at pair `first`: a name keeps the place of its first pair and the value
// of its last
char* put_labels(char* p, const LabelStrs& ls, int64_t first, int64_t n) {
    *p++ = '{';
    bool any = false;
    for (int64_t j = first; j < first + n; j++) {
        std::string_view nm = ls.name(2 * j);
        bool seen = false;
        for (int64_t q = first; q < j && !seen; q++) seen = ls.name(2 * q) == nm;
        if (seen) continue;
        int64_t last = j;
        for (int64_t q = j + 1; q < first + n; q++)
            if (ls.name(2 * q) == nm) last = q;
        if (any) *p++ = ',';
        any = true;
        p = put_json_str(p, reinterpret_cast<const uint8_t*>(nm.data()),
                         (int64_t)nm.size());
        *p++ = ':';
        std::string_view v = ls.raw(2 * last + 1);
        p = put_json_str(p, reinterpret_cast<const uint8_t*>(v.data()),
                         (int64_t)v.size());
    }
    *p++ = '}';
    return p;
}

}  // namespace

extern "C" {

// vals: [P, K] row-major; ts / ts_offs[K + 1]: the K step times, already
// formatted; labels / label_offs: every series' names and values in turn,
// label_counts[i] (name, value) pairs for series i. Writes the series,
// joined by ',', into out and the number written into *nseries. Returns the
// bytes written, or -1 if cap could not hold them.
int64_t render_matrix_json(const double* vals, int64_t P, int64_t K,
                           const char* ts, const int64_t* ts_offs,
                           const uint8_t* labels, const int64_t* label_offs,
                           const int64_t* label_counts, char* out,
                           int64_t cap, int64_t* nseries) {
    int64_t ts_max = 0;
    for (int64_t k = 0; k < K; k++)
        ts_max = std::max(ts_max, ts_offs[k + 1] - ts_offs[k]);
    LabelStrs ls{labels, label_offs};
    char* p = out;
    int64_t first = 0, written = 0;
    for (int64_t i = 0; i < P; i++) {
        const double* row = vals + i * K;
        int64_t n = label_counts[i];
        int64_t pair0 = first;
        first += n;
        int64_t k0 = 0;
        while (k0 < K && row[k0] != row[k0]) k0++;
        if (k0 == K) continue;
        // "{"metric":{...},"values":[...]}," with every piece at its widest
        int64_t need = 26 + 6 * n
            + 6 * (label_offs[2 * first] - label_offs[2 * pair0])
            + K * (ts_max + 30);
        if ((p - out) + need > cap) return -1;
        if (written++) *p++ = ',';
        memcpy(p, "{\"metric\":", 10);
        p = put_labels(p + 10, ls, pair0, n);
        memcpy(p, ",\"values\":[", 11);
        p += 11;
        for (int64_t k = k0; k < K; k++) {
            double v = row[k];
            if (v != v) continue;
            if (k != k0) *p++ = ',';
            *p++ = '[';
            memcpy(p, ts + ts_offs[k], ts_offs[k + 1] - ts_offs[k]);
            p += ts_offs[k + 1] - ts_offs[k];
            *p++ = ',';
            *p++ = '"';
            if (v == std::numeric_limits<double>::infinity()) {
                memcpy(p, "+Inf", 4);
                p += 4;
            } else if (v == -std::numeric_limits<double>::infinity()) {
                memcpy(p, "-Inf", 4);
                p += 4;
            } else {
                p = put_float_repr(p, v);
            }
            *p++ = '"';
            *p++ = ']';
        }
        *p++ = ']';
        *p++ = '}';
    }
    *nseries = written;
    return p - out;
}

}  // extern "C"
