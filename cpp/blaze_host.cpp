// blaze-tpu host runtime: the native (C++) tier of the engine.
//
// TPU-native equivalent of the reference's Rust host runtime
// (native-engine/datafusion-ext): everything that crunches bytes on the CPU
// around the device compute path lives here - Spark-compatible murmur3 over
// string buffers (reference spark_hash.rs:27-87), zstd framing for the
// segmented Arrow-IPC exchange format (reference util/ipc.rs:20-49), and
// shuffle .data/.index file assembly with spill merge (reference
// shuffle_writer_exec.rs:437-506).
//
// Exposed as a plain C ABI consumed via ctypes (no pybind11 in this image).
// All functions are GIL-free by construction; Python releases the GIL for
// the duration of each call automatically with ctypes.

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <vector>

#include <zstd.h>

extern "C" {

// ---------------------------------------------------------------------------
// zstd framing
// ---------------------------------------------------------------------------

int64_t blz_zstd_compress_bound(int64_t src_size) {
  return (int64_t)ZSTD_compressBound((size_t)src_size);
}

// Returns compressed size, or -1 on error.
int64_t blz_zstd_compress(const uint8_t* src, int64_t src_size, uint8_t* dst,
                          int64_t dst_cap, int level) {
  size_t n = ZSTD_compress(dst, (size_t)dst_cap, src, (size_t)src_size, level);
  if (ZSTD_isError(n)) return -1;
  return (int64_t)n;
}

// Returns decompressed size, or -1 on error.
int64_t blz_zstd_decompress(const uint8_t* src, int64_t src_size,
                            uint8_t* dst, int64_t dst_cap) {
  size_t n =
      ZSTD_decompress(dst, (size_t)dst_cap, src, (size_t)src_size);
  if (ZSTD_isError(n)) return -1;
  return (int64_t)n;
}

int64_t blz_zstd_frame_content_size(const uint8_t* src, int64_t src_size) {
  unsigned long long n = ZSTD_getFrameContentSize(src, (size_t)src_size);
  if (n == ZSTD_CONTENTSIZE_ERROR) return -1;
  if (n == ZSTD_CONTENTSIZE_UNKNOWN) return -2;
  return (int64_t)n;
}

// Streaming decompress for frames of unknown content size (arrow IPC zstd
// streams written by streaming encoders don't record it). Grows into a
// caller-provided buffer; returns bytes written or -1 (error) / -3 (buffer
// too small; call again with a bigger one).
int64_t blz_zstd_decompress_stream(const uint8_t* src, int64_t src_size,
                                   uint8_t* dst, int64_t dst_cap) {
  ZSTD_DStream* ds = ZSTD_createDStream();
  if (!ds) return -1;
  ZSTD_initDStream(ds);
  ZSTD_inBuffer in = {src, (size_t)src_size, 0};
  ZSTD_outBuffer out = {dst, (size_t)dst_cap, 0};
  while (in.pos < in.size) {
    size_t r = ZSTD_decompressStream(ds, &out, &in);
    if (ZSTD_isError(r)) {
      ZSTD_freeDStream(ds);
      return -1;
    }
    if (out.pos == out.size && in.pos < in.size) {
      ZSTD_freeDStream(ds);
      return -3;  // need a larger buffer
    }
    if (r == 0) break;  // frame complete
  }
  ZSTD_freeDStream(ds);
  return (int64_t)out.pos;
}

// ---------------------------------------------------------------------------
// Spark-compatible Murmur3 x86_32 (seed chains), bit-exact with
// org.apache.spark.unsafe.hash.Murmur3_x86_32 and the engine's device/host
// implementations (blaze_tpu/exprs/hashing.py).
// ---------------------------------------------------------------------------

static inline uint32_t rotl32(uint32_t x, int r) {
  return (x << r) | (x >> (32 - r));
}

static inline uint32_t mix_k1(uint32_t k1) {
  k1 *= 0xcc9e2d51u;
  k1 = rotl32(k1, 15);
  k1 *= 0x1b873593u;
  return k1;
}

static inline uint32_t mix_h1(uint32_t h1, uint32_t k1) {
  h1 ^= k1;
  h1 = rotl32(h1, 13);
  h1 = h1 * 5u + 0xe6546b64u;
  return h1;
}

static inline uint32_t fmix(uint32_t h1, uint32_t len) {
  h1 ^= len;
  h1 ^= h1 >> 16;
  h1 *= 0x85ebca6bu;
  h1 ^= h1 >> 13;
  h1 *= 0xc2b2ae35u;
  h1 ^= h1 >> 16;
  return h1;
}

static inline uint32_t hash_bytes(const uint8_t* data, int64_t len,
                                  uint32_t seed) {
  uint32_t h1 = seed;
  int64_t aligned = len - (len % 4);
  for (int64_t i = 0; i < aligned; i += 4) {
    uint32_t word;
    memcpy(&word, data + i, 4);  // little-endian hosts only
    h1 = mix_h1(h1, mix_k1(word));
  }
  for (int64_t i = aligned; i < len; i++) {
    // Spark quirk: each tail byte is sign-extended and sent through the
    // full mix pipeline (not the standard murmur3 tail)
    int32_t b = (int8_t)data[i];
    h1 = mix_h1(h1, mix_k1((uint32_t)b));
  }
  return fmix(h1, (uint32_t)len);
}

// Chain a string column into per-row running hashes.
// data/offsets follow the Arrow string layout (int32 offsets, n+1 entries);
// validity is a byte mask (1 = valid) or null; NULL rows keep their seed.
void blz_murmur3_strings_chain(const uint8_t* data, const int32_t* offsets,
                               const uint8_t* validity, int64_t n,
                               uint32_t* hashes) {
  for (int64_t i = 0; i < n; i++) {
    if (validity && !validity[i]) continue;
    int32_t start = offsets[i];
    int32_t end = offsets[i + 1];
    hashes[i] = hash_bytes(data + start, end - start, hashes[i]);
  }
}

// Same for dictionary-encoded strings: hash each dictionary value lazily
// per (code, seed) row. codes index into the dict arrays.
void blz_murmur3_dict_strings_chain(const uint8_t* dict_data,
                                    const int32_t* dict_offsets,
                                    const int32_t* codes,
                                    const uint8_t* validity, int64_t n,
                                    uint32_t* hashes) {
  for (int64_t i = 0; i < n; i++) {
    if (validity && !validity[i]) continue;
    int32_t c = codes[i];
    int32_t start = dict_offsets[c];
    int32_t end = dict_offsets[c + 1];
    hashes[i] = hash_bytes(dict_data + start, end - start, hashes[i]);
  }
}

void blz_murmur3_i32_chain(const int32_t* values, const uint8_t* validity,
                           int64_t n, uint32_t* hashes) {
  for (int64_t i = 0; i < n; i++) {
    if (validity && !validity[i]) continue;
    hashes[i] = fmix(mix_h1(hashes[i], mix_k1((uint32_t)values[i])), 4);
  }
}

void blz_murmur3_i64_chain(const int64_t* values, const uint8_t* validity,
                           int64_t n, uint32_t* hashes) {
  for (int64_t i = 0; i < n; i++) {
    if (validity && !validity[i]) continue;
    uint64_t v = (uint64_t)values[i];
    uint32_t h = mix_h1(hashes[i], mix_k1((uint32_t)(v & 0xffffffffu)));
    h = mix_h1(h, mix_k1((uint32_t)(v >> 32)));
    hashes[i] = fmix(h, 8);
  }
}

// Spark's non-negative mod for partition assignment (spark_hash.rs pmod).
void blz_pmod(const uint32_t* hashes, int64_t n, int32_t num_partitions,
              int32_t* out) {
  for (int64_t i = 0; i < n; i++) {
    int32_t h = (int32_t)hashes[i];
    int32_t r = h % num_partitions;
    out[i] = r < 0 ? r + num_partitions : r;
  }
}

// ---------------------------------------------------------------------------
// shuffle .data/.index assembly (reference shuffle_writer_exec.rs:437-506)
// ---------------------------------------------------------------------------

// Concatenate per-partition ranges of spill files (oldest first) and then
// the per-partition in-memory buffers into one data file, so a partition's
// parts keep the order they were written in; write (num_partitions+1) LE
// i64 offsets into the index file. Buffers are passed as one blob + offsets.
//
// spill_paths: array of C strings; spill_offsets: [n_spills][n_part+1].
// Returns 0 on success, negative errno-style code on failure.
int64_t blz_shuffle_assemble(const char* data_path, const char* index_path,
                             const uint8_t* buffers, const int64_t* buf_offsets,
                             int32_t num_partitions,
                             const char** spill_paths, int32_t n_spills,
                             const int64_t* spill_offsets) {
  FILE* out = fopen(data_path, "wb");
  if (!out) return -1;
  std::vector<int64_t> offsets(num_partitions + 1, 0);
  std::vector<uint8_t> copybuf(1 << 20);
  int64_t pos = 0;
  for (int32_t p = 0; p < num_partitions; p++) {
    offsets[p] = pos;
    for (int32_t s = 0; s < n_spills; s++) {
      const int64_t* so = spill_offsets + (int64_t)s * (num_partitions + 1);
      int64_t slen = so[p + 1] - so[p];
      if (slen <= 0) continue;
      FILE* in = fopen(spill_paths[s], "rb");
      if (!in) {
        fclose(out);
        return -3;
      }
      if (fseek(in, (long)so[p], SEEK_SET) != 0) {
        fclose(in);
        fclose(out);
        return -3;
      }
      int64_t remaining = slen;
      while (remaining > 0) {
        size_t chunk = (size_t)std::min<int64_t>(remaining,
                                                 (int64_t)copybuf.size());
        size_t got = fread(copybuf.data(), 1, chunk, in);
        if (got == 0) {
          fclose(in);
          fclose(out);
          return -4;
        }
        if (fwrite(copybuf.data(), 1, got, out) != got) {
          fclose(in);
          fclose(out);
          return -2;
        }
        remaining -= (int64_t)got;
        pos += (int64_t)got;
      }
      fclose(in);
    }
    int64_t len = buf_offsets[p + 1] - buf_offsets[p];
    if (len > 0) {
      if (fwrite(buffers + buf_offsets[p], 1, (size_t)len, out) !=
          (size_t)len) {
        fclose(out);
        return -2;
      }
      pos += len;
    }
  }
  offsets[num_partitions] = pos;
  if (fflush(out) != 0 || fclose(out) != 0) return -2;

  FILE* idx = fopen(index_path, "wb");
  if (!idx) return -1;
  for (int64_t off : offsets) {
    uint8_t le[8];
    for (int i = 0; i < 8; i++) le[i] = (uint8_t)((uint64_t)off >> (8 * i));
    if (fwrite(le, 1, 8, idx) != 8) {
      fclose(idx);
      return -2;
    }
  }
  if (fflush(idx) != 0 || fclose(idx) != 0) return -2;
  return 0;
}

}  // extern "C"
