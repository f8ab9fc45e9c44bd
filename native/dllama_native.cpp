// Native data-loader kernels for dllama-tpu.
//
// The TPU-native counterpart of the reference's C++ weight pipeline
// (mmap + per-node slicing + socket streaming, src/llm.cpp:614-669 and
// src/nn/nn-core.cpp:289-322): here the hot host-side work is unpacking
// Q40 blocks (nibble extraction) and transposing tensors into the device
// layout before jax.device_put ships shards over PCIe/ICI. numpy does this
// single-threaded with several materialized intermediates; these kernels do
// it in one multithreaded pass, which is what makes a 40 GB 70B checkpoint
// load in minutes instead of hours.
//
// Exposed via a plain C ABI consumed with ctypes (no pybind11 in the
// image). All functions are thread-parallel over the output's leading
// dimension with the same SPLIT_THREADS partitioning idea the reference
// uses (src/nn/nn-quants.hpp:82-86).

#include <cstdint>
#include <cstring>
#include <queue>
#include <string>
#include <string_view>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

constexpr int kBlock = 32;          // Q40/Q80 block size
constexpr int kBlockBytes = 18;     // fp16 scale + 16 packed nibble bytes

inline float f16_to_f32(uint16_t h) {
    // scalar IEEE half -> float (no F16C dependency)
    uint32_t sign = (uint32_t)(h >> 15) & 1u;
    uint32_t exp = (uint32_t)(h >> 10) & 0x1Fu;
    uint32_t mant = (uint32_t)h & 0x3FFu;
    uint32_t out;
    if (exp == 0) {
        if (mant == 0) {
            out = sign << 31;
        } else {
            // subnormal: normalize
            exp = 127 - 15 + 1;
            while ((mant & 0x400u) == 0) {
                mant <<= 1;
                exp--;
            }
            mant &= 0x3FFu;
            out = (sign << 31) | (exp << 23) | (mant << 13);
        }
    } else if (exp == 0x1F) {
        out = (sign << 31) | (0xFFu << 23) | (mant << 13);
    } else {
        out = (sign << 31) | ((exp - 15 + 127) << 23) | (mant << 13);
    }
    float f;
    std::memcpy(&f, &out, sizeof(f));
    return f;
}

template <typename Fn>
void parallel_for(int64_t n, int n_threads, Fn fn) {
    if (n_threads <= 1 || n < 2) {
        fn(0, n);
        return;
    }
    std::vector<std::thread> threads;
    int64_t chunk = n / n_threads;
    int64_t rest = n % n_threads;
    int64_t start = 0;
    for (int t = 0; t < n_threads; t++) {
        int64_t len = chunk + (t < rest ? 1 : 0);
        if (len == 0) continue;
        threads.emplace_back([=] { fn(start, start + len); });
        start += len;
    }
    for (auto &th : threads) th.join();
}

}  // namespace

extern "C" {

// Unpack packed Q40 rows ([rows, cols] logical, cols % 32 == 0) directly
// into the TRANSPOSED device layout:
//   q_out  int8  [cols, rows]   (contraction axis leading)
//   d_out  float [cols/32, rows]
// raw is rows * cols/32 blocks of 18 bytes, row-major.
void q40_unpack_transposed(const uint8_t *raw, int64_t rows, int64_t cols,
                           int8_t *q_out, float *d_out, int n_threads) {
    const int64_t blocks_per_row = cols / kBlock;
    // Tile over rows so transpose writes land in contiguous TILE-wide runs
    // (a naive per-element scatter is cache-hostile and no faster than
    // numpy). Each thread owns a range of row tiles.
    constexpr int64_t TILE = 128;
    const int64_t n_tiles = (rows + TILE - 1) / TILE;
    parallel_for(n_tiles, n_threads, [=](int64_t t0, int64_t t1) {
        int8_t tile[kBlock][TILE];
        for (int64_t tr = t0; tr < t1; tr++) {
            const int64_t r0 = tr * TILE;
            const int64_t r1 = r0 + TILE < rows ? r0 + TILE : rows;
            const int64_t width = r1 - r0;
            for (int64_t b = 0; b < blocks_per_row; b++) {
                const int64_t col0 = b * kBlock;
                for (int64_t r = r0; r < r1; r++) {
                    const uint8_t *blk =
                        raw + (r * blocks_per_row + b) * kBlockBytes;
                    uint16_t h;
                    std::memcpy(&h, blk, 2);
                    d_out[b * rows + r] = f16_to_f32(h);
                    const uint8_t *qs = blk + 2;
                    const int64_t rr = r - r0;
                    for (int j = 0; j < kBlock / 2; j++) {
                        tile[j][rr] = (int8_t)(qs[j] & 0x0F) - 8;
                        tile[j + kBlock / 2][rr] = (int8_t)(qs[j] >> 4) - 8;
                    }
                }
                for (int j = 0; j < kBlock; j++)
                    std::memcpy(q_out + (col0 + j) * rows + r0, tile[j],
                                (size_t)width);
            }
        }
    });
}

// Packed twin of q40_unpack_transposed: the wire's nibbles straight into the
// packed device form (ops/quant_matmul.PackedQuantWeight), no int8 plane
// between:
//   w_out  int32 [cols/8, rows]   eight two's-complement nibbles a word
//   d_out  float [cols/32, rows]
// Word row g*seg + t holds in nibble j (bits 4j..4j+3) weight row
// g*8*seg + j*seg + t, where seg = 32 (cols % 256 == 0: a nibble's rows
// are one quant block, eight blocks a group) or cols/8 (one group).
void q40_pack_transposed(const uint8_t *raw, int64_t rows, int64_t cols,
                         int32_t *w_out, float *d_out, int n_threads) {
    const int64_t blocks_per_row = cols / kBlock;
    const int64_t seg = cols % 256 == 0 ? 32 : cols / 8;
    const int64_t n_groups = cols / (8 * seg);
    constexpr int64_t TILE = 64;
    const int64_t n_tiles = (rows + TILE - 1) / TILE;
    parallel_for(n_tiles, n_threads, [=](int64_t t0, int64_t t1) {
        std::vector<uint32_t> tile((size_t)(seg * TILE));
        for (int64_t tr = t0; tr < t1; tr++) {
            const int64_t r0 = tr * TILE;
            const int64_t r1 = r0 + TILE < rows ? r0 + TILE : rows;
            const int64_t width = r1 - r0;
            for (int64_t g = 0; g < n_groups; g++) {
                for (int64_t r = r0; r < r1; r++) {
                    const uint8_t *row = raw + r * blocks_per_row * kBlockBytes;
                    const int64_t rr = r - r0;
                    const int64_t per_group = 8 * seg / kBlock;
                    for (int64_t b = g * per_group; b < (g + 1) * per_group; b++) {
                        uint16_t h;
                        std::memcpy(&h, row + b * kBlockBytes, 2);
                        d_out[b * rows + r] = f16_to_f32(h);
                    }
                    for (int64_t t = 0; t < seg; t++) {
                        uint32_t word = 0;
                        for (int j = 0; j < 8; j++) {
                            const int64_t k = (g * 8 + j) * seg + t;
                            const int e = (int)(k % kBlock);
                            const uint8_t byte =
                                row[(k / kBlock) * kBlockBytes + 2 + (e & 15)];
                            const uint32_t nib = (e < 16 ? byte : byte >> 4) & 0xFu;
                            word |= (nib ^ 8u) << (4 * j);  // nib - 8, two's complement
                        }
                        tile[(size_t)(t * TILE + rr)] = word;
                    }
                }
                for (int64_t t = 0; t < seg; t++)
                    std::memcpy(w_out + (g * seg + t) * rows + r0,
                                tile.data() + t * TILE, (size_t)width * 4);
            }
        }
    });
}

// Dequantize packed Q40 rows to dense f32 in the TRANSPOSED [cols, rows]
// layout the dense loader wants (file is [rows, cols] row-major).
void q40_dequant_transposed(const uint8_t *raw, int64_t rows, int64_t cols,
                            float *out, int n_threads) {
    const int64_t blocks_per_row = cols / kBlock;
    parallel_for(rows, n_threads, [=](int64_t r0, int64_t r1) {
        for (int64_t r = r0; r < r1; r++) {
            const uint8_t *row = raw + r * blocks_per_row * kBlockBytes;
            for (int64_t b = 0; b < blocks_per_row; b++) {
                const uint8_t *blk = row + b * kBlockBytes;
                uint16_t h;
                std::memcpy(&h, blk, 2);
                const float d = f16_to_f32(h);
                const uint8_t *qs = blk + 2;
                const int64_t col0 = b * kBlock;
                for (int j = 0; j < kBlock / 2; j++) {
                    out[(col0 + j) * rows + r] =
                        (float)((int)(qs[j] & 0x0F) - 8) * d;
                    out[(col0 + j + kBlock / 2) * rows + r] =
                        (float)((int)(qs[j] >> 4) - 8) * d;
                }
            }
        }
    });
}

// Dequantize packed Q40 rows to dense f32 in file order [rows, cols]
// (embedding tables and other non-transposed consumers).
void q40_dequant(const uint8_t *raw, int64_t rows, int64_t cols, float *out,
                 int n_threads) {
    const int64_t blocks_per_row = cols / kBlock;
    parallel_for(rows, n_threads, [=](int64_t r0, int64_t r1) {
        for (int64_t r = r0; r < r1; r++) {
            const uint8_t *row = raw + r * blocks_per_row * kBlockBytes;
            float *orow = out + r * cols;
            for (int64_t b = 0; b < blocks_per_row; b++) {
                const uint8_t *blk = row + b * kBlockBytes;
                uint16_t h;
                std::memcpy(&h, blk, 2);
                const float d = f16_to_f32(h);
                const uint8_t *qs = blk + 2;
                float *o = orow + b * kBlock;
                for (int j = 0; j < kBlock / 2; j++) {
                    o[j] = (float)((int)(qs[j] & 0x0F) - 8) * d;
                    o[j + kBlock / 2] = (float)((int)(qs[j] >> 4) - 8) * d;
                }
            }
        }
    });
}

// f32 [rows, cols] -> transposed [cols, rows] (norms stay small; this is
// for the dense path's big matmul weights).
void f32_transpose(const float *in, int64_t rows, int64_t cols, float *out,
                   int n_threads) {
    constexpr int64_t TILE = 64;
    parallel_for((rows + TILE - 1) / TILE, n_threads, [=](int64_t t0, int64_t t1) {
        for (int64_t tr = t0; tr < t1; tr++) {
            const int64_t r0 = tr * TILE;
            const int64_t r1 = r0 + TILE < rows ? r0 + TILE : rows;
            for (int64_t c0 = 0; c0 < cols; c0 += TILE) {
                const int64_t c1 = c0 + TILE < cols ? c0 + TILE : cols;
                for (int64_t r = r0; r < r1; r++)
                    for (int64_t c = c0; c < c1; c++)
                        out[c * rows + r] = in[r * cols + c];
            }
        }
    });
}

// Score-based BPE encode — the host-side hot loop of the tokenizer
// (reference semantics: src/tokenizer.cpp:311-390; Python twin:
// dllama_tpu/tokenizer/bpe.py Tokenizer.encode). The Python/reference
// merge loop rescans all adjacent pairs per round (O(n^2)); this
// implementation reproduces the EXACT same selection rule — highest
// merged-token score, leftmost pair on ties, strictly-greater than the
// -1e10 floor — with a lazy max-heap over a doubly-linked token list
// (O(n log n)): merges never reorder surviving tokens, so "leftmost" is
// a stable per-node order key (the original byte offset of the node's
// first constituent), and stale heap entries are dropped by stamp
// validation.
//
// The vocab index is built ONCE per tokenizer (bpe_index_new) — the
// caller keeps the blob/offsets/scores arrays alive for the handle's
// lifetime. A prepended BOS token participates in the merge phase
// exactly like Python's (its list includes the BOS before merging).

struct BpeIndex {
    const uint8_t *blob;
    const int64_t *offsets;
    const float *scores;
    int64_t vocab_size;
    int64_t regular_size;
    int64_t max_regular_len;
    std::unordered_map<std::string_view, int32_t> regular;

    std::string_view piece(int64_t id) const {
        return std::string_view(
            reinterpret_cast<const char *>(blob) + offsets[id],
            (size_t)(offsets[id + 1] - offsets[id]));
    }
};

void *bpe_index_new(const uint8_t *vocab_blob, const int64_t *offsets,
                    const float *scores, int64_t vocab_size,
                    int64_t regular_size) {
    // a malformed .t header can leave bos_id (= regular split) at -1;
    // returning null lets the Python side fall back to its own loop and
    // raise a catchable error instead of aborting through the C ABI
    if (regular_size < 0 || regular_size > vocab_size || vocab_size < 0)
        return nullptr;
    auto *ix = new BpeIndex{vocab_blob, offsets, scores,
                            vocab_size,  regular_size, 0,
                            {}};
    ix->regular.reserve((size_t)regular_size * 2);
    for (int64_t i = 0; i < regular_size; i++) {
        // first id wins on duplicates (bpe.py builds _regular with
        // setdefault in ascending id order)
        ix->regular.emplace(ix->piece(i), (int32_t)i);
        const int64_t len = offsets[i + 1] - offsets[i];
        if (len > ix->max_regular_len) ix->max_regular_len = len;
    }
    return ix;
}

void bpe_index_free(void *handle) { delete (BpeIndex *)handle; }

// Returns the token count, or -1 when out_cap is too small, or -2 for
// un-tokenizable trailing bytes (the caller falls back to Python, which
// raises the detailed error).
int64_t bpe_encode(void *handle, const uint8_t *text, int64_t text_len,
                   int64_t prepend_bos_id, int add_specials, int32_t *out,
                   int64_t out_cap) {
    const BpeIndex &ix = *(const BpeIndex *)handle;
    const auto piece = [&](int64_t id) { return ix.piece(id); };
    const auto &regular = ix.regular;
    const float *scores = ix.scores;
    const int64_t vocab_size = ix.vocab_size;
    const int64_t regular_size = ix.regular_size;
    const int64_t max_token_len = ix.max_regular_len;

    // 1. greedy byte accumulation with special-token prefix matching at
    //    every byte position (specials scanned in id order)
    std::vector<int32_t> toks;
    toks.reserve((size_t)text_len / 2 + 8);
    if (prepend_bos_id >= 0) toks.push_back((int32_t)prepend_bos_id);
    std::string acc;
    int64_t i = 0;
    const std::string_view text_sv(reinterpret_cast<const char *>(text),
                                   (size_t)text_len);
    while (i < text_len) {
        if (add_specials) {
            int64_t sid = -1;
            for (int64_t s = regular_size; s < vocab_size; s++) {
                std::string_view sp = piece(s);
                if (!sp.empty() &&
                    text_sv.compare((size_t)i, sp.size(), sp) == 0) {
                    sid = s;
                    break;
                }
            }
            if (sid >= 0) {
                toks.push_back((int32_t)sid);
                i += (int64_t)piece(sid).size();
                continue;
            }
        }
        acc.push_back((char)text[i]);
        i++;
        auto it = regular.find(std::string_view(acc));
        if (it != regular.end()) {
            toks.push_back(it->second);
            acc.clear();
        }
    }
    if (!acc.empty()) return -2;

    // 2. score-maximizing pair merge over a linked list + lazy heap
    const int64_t n = (int64_t)toks.size();
    if (n > 1) {
        struct Node {
            int32_t tok;
            int64_t order;  // stable left-to-right key (never reassigned)
            int64_t prev, next;
            uint32_t stamp;  // bumped whenever tok changes / node dies
            bool alive;
        };
        std::vector<Node> nodes((size_t)n);
        for (int64_t j = 0; j < n; j++)
            nodes[(size_t)j] = {toks[(size_t)j], j, j - 1,
                                j + 1 < n ? j + 1 : -1, 0, true};

        struct Cand {
            float score;
            int64_t order;
            int64_t left, right;
            uint32_t lstamp, rstamp;
            int32_t merged;
        };
        struct CandLess {
            bool operator()(const Cand &a, const Cand &b) const {
                if (a.score != b.score) return a.score < b.score;
                return a.order > b.order;  // leftmost wins ties
            }
        };
        std::priority_queue<Cand, std::vector<Cand>, CandLess> heap;
        std::string merged;
        const auto push_cand = [&](int64_t l, int64_t r) {
            const std::string_view a = piece(nodes[(size_t)l].tok);
            const std::string_view b = piece(nodes[(size_t)r].tok);
            if (max_token_len > 0 &&
                (int64_t)(a.size() + b.size()) > max_token_len)
                return;
            merged.assign(a);
            merged.append(b);
            auto it = regular.find(std::string_view(merged));
            if (it == regular.end()) return;
            const float sc = scores[it->second];
            if (!(sc > -1e10f)) return;  // the scan's best_score floor
            heap.push({sc, nodes[(size_t)l].order, l, r,
                       nodes[(size_t)l].stamp, nodes[(size_t)r].stamp,
                       it->second});
        };
        for (int64_t j = 0; j + 1 < n; j++) push_cand(j, j + 1);

        while (!heap.empty()) {
            const Cand c = heap.top();
            heap.pop();
            Node &l = nodes[(size_t)c.left];
            Node &r = nodes[(size_t)c.right];
            if (!l.alive || !r.alive || l.stamp != c.lstamp ||
                r.stamp != c.rstamp || l.next != c.right)
                continue;  // stale entry
            l.tok = c.merged;
            l.stamp++;
            r.alive = false;
            r.stamp++;
            l.next = r.next;
            if (r.next >= 0) nodes[(size_t)r.next].prev = c.left;
            if (l.prev >= 0) push_cand(l.prev, c.left);
            if (l.next >= 0) push_cand(c.left, l.next);
        }

        toks.clear();
        for (int64_t j = 0; j >= 0; j = nodes[(size_t)j].next)
            if (nodes[(size_t)j].alive) toks.push_back(nodes[(size_t)j].tok);
    }

    if ((int64_t)toks.size() > out_cap) return -1;
    std::memcpy(out, toks.data(), toks.size() * sizeof(int32_t));
    return (int64_t)toks.size();
}

int dllama_native_version() { return 4; }

}  // extern "C"
