// glz: gather-LZ — the down-link's result codec, host decoder.
//
// The device ENCODES result streams inside the chain's own program
// (smartengine/tpu/glz.py: encode_result) and this file inflates what
// crossed the link, validating it on the way. The format is restricted
// so that a decode is also expressible as a fixed number of vectorized
// gather rounds (the numpy mirror in glz.py):
//
//   * the stream is a list of SEQUENCES (LZ4-shaped): each copies
//     `lit_len` bytes from the literal stream, then `match_len` bytes
//     from out[src : src+match_len).
//   * matches NEVER overlap their own output: src + match_len <= dst.
//   * every output byte has a DEPTH: literal bytes are 0; a match
//     byte is 1 + max depth over its source range. The encoder bounds
//     depth, so a gather decode is exactly that many rounds.
//
// Long literal runs / matches are chains of sequences (lit-only /
// match-only); there are no escape codes, every sequence is
// self-describing: (lit_len u8, match_len u8, src i32) = 6 B across
// three struct-of-array link buffers.

#include <cstdint>
#include <cstring>

extern "C" {

// Sequential decoder: fails closed on anything the format forbids
// (return codes 1-5 below) instead of reading or writing out of range.
int32_t glz_decompress(const uint8_t* lit_lens, const uint8_t* match_lens,
                       const int32_t* srcs, int64_t n_seqs,
                       const uint8_t* lits, int64_t n_lits,
                       uint8_t* out, int64_t out_len) {
    int64_t dst = 0, lp = 0;
    for (int64_t t = 0; t < n_seqs; t++) {
        int64_t ll = lit_lens[t], ml = match_lens[t];
        // zero-total sequences are INVALID glz: the gather decode's
        // scatter+cumsum token labeling cannot represent them (bucketed
        // token slices carry zero-total padding only past the real
        // count, which the caller cuts off)
        if (ll + ml == 0) return 5;
        if (dst + ll + ml > out_len) return 1;
        if (ll) {
            if (lp + ll > n_lits) return 2;
            std::memcpy(out + dst, lits + lp, (size_t)ll);
            lp += ll; dst += ll;
        }
        if (ml) {
            int64_t s = srcs[t];
            if (s < 0 || s + ml > dst) return 3;  // overlap = invalid glz
            std::memcpy(out + dst, out + s, (size_t)ml);
            dst += ml;
        }
    }
    return (dst == out_len && lp == n_lits) ? 0 : 4;
}

}  // extern "C"
