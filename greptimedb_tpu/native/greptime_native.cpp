// Native IO hot paths for greptimedb_tpu.
//
// The reference implements its entire runtime in Rust; here the compute
// path is JAX/XLA and the IO-bound runtime pieces that profile hot in
// Python move to C++ (SURVEY.md §7.1: storage/WAL stay CPU-side, native):
//   - CRC32 (zlib polynomial) for WAL record integrity
//   - Snappy raw-format decompression (Prometheus remote write bodies)
//   - WAL segment scanning: frame validation + torn-tail detection
//   - the `rows` array of a /v1/sql reply, written from whole columns
//
// Build: make -C greptimedb_tpu/native      (produces libgreptime_native.so)
// Bound via ctypes (greptimedb_tpu/native/__init__.py); every entry point
// has a pure-python fallback so the library is an accelerator, not a
// dependency.

#if defined(__has_include)
#if __has_include(<charconv>)
#include <charconv>  // defines __cpp_lib_to_chars where doubles are covered
#endif
#endif
#include <cstddef>
#include <cstdint>
#include <cstring>

extern "C" {

// ---------------------------------------------------------------------------
// CRC32 (IEEE 802.3, zlib-compatible)
// ---------------------------------------------------------------------------

static uint32_t crc_table[8][256];
static bool crc_init_done = false;

static void crc_init() {
  if (crc_init_done) return;
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = i;
    for (int k = 0; k < 8; k++) c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
    crc_table[0][i] = c;
  }
  // slicing-by-8 tables
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = crc_table[0][i];
    for (int s = 1; s < 8; s++) {
      c = crc_table[0][c & 0xFF] ^ (c >> 8);
      crc_table[s][i] = c;
    }
  }
  crc_init_done = true;
}

uint32_t gt_crc32(const uint8_t* data, size_t len) {
  crc_init();
  uint32_t crc = 0xFFFFFFFFu;
  // slicing-by-8 main loop
  while (len >= 8) {
    uint32_t lo;
    uint32_t hi;
    memcpy(&lo, data, 4);
    memcpy(&hi, data + 4, 4);
    lo ^= crc;
    crc = crc_table[7][lo & 0xFF] ^ crc_table[6][(lo >> 8) & 0xFF] ^
          crc_table[5][(lo >> 16) & 0xFF] ^ crc_table[4][lo >> 24] ^
          crc_table[3][hi & 0xFF] ^ crc_table[2][(hi >> 8) & 0xFF] ^
          crc_table[1][(hi >> 16) & 0xFF] ^ crc_table[0][hi >> 24];
    data += 8;
    len -= 8;
  }
  while (len--) crc = crc_table[0][(crc ^ *data++) & 0xFF] ^ (crc >> 8);
  return crc ^ 0xFFFFFFFFu;
}

// ---------------------------------------------------------------------------
// Snappy raw format decompression
// ---------------------------------------------------------------------------

// Returns decompressed length from the header uvarint, or -1 on error.
int64_t gt_snappy_length(const uint8_t* in, size_t in_len) {
  uint64_t result = 0;
  int shift = 0;
  size_t pos = 0;
  while (pos < in_len && shift <= 63) {
    uint8_t b = in[pos++];
    result |= static_cast<uint64_t>(b & 0x7F) << shift;
    if (!(b & 0x80)) return static_cast<int64_t>(result);
    shift += 7;
  }
  return -1;
}

// 0 = ok; negative = error. out must hold gt_snappy_length() bytes.
int gt_snappy_decompress(const uint8_t* in, size_t in_len, uint8_t* out,
                         size_t out_cap, size_t* out_len) {
  size_t pos = 0;
  // skip the length varint
  while (pos < in_len && (in[pos] & 0x80)) pos++;
  if (pos >= in_len) return -1;
  pos++;
  size_t o = 0;
  while (pos < in_len) {
    uint8_t tag = in[pos++];
    uint32_t elem = tag & 0x03;
    if (elem == 0) {  // literal
      size_t len = (tag >> 2) + 1;
      if (len > 60) {
        size_t extra = len - 60;
        if (pos + extra > in_len) return -2;
        len = 0;
        for (size_t i = 0; i < extra; i++) len |= static_cast<size_t>(in[pos + i]) << (8 * i);
        len += 1;
        pos += extra;
      }
      if (pos + len > in_len || o + len > out_cap) return -3;
      memcpy(out + o, in + pos, len);
      pos += len;
      o += len;
      continue;
    }
    size_t len;
    size_t offset;
    if (elem == 1) {
      len = ((tag >> 2) & 0x07) + 4;
      if (pos >= in_len) return -4;
      offset = (static_cast<size_t>(tag >> 5) << 8) | in[pos++];
    } else if (elem == 2) {
      len = (tag >> 2) + 1;
      if (pos + 2 > in_len) return -5;
      offset = in[pos] | (static_cast<size_t>(in[pos + 1]) << 8);
      pos += 2;
    } else {
      len = (tag >> 2) + 1;
      if (pos + 4 > in_len) return -6;
      offset = 0;
      for (int i = 0; i < 4; i++) offset |= static_cast<size_t>(in[pos + i]) << (8 * i);
      pos += 4;
    }
    if (offset == 0 || offset > o || o + len > out_cap) return -7;
    if (offset >= len) {
      memcpy(out + o, out + o - offset, len);
      o += len;
    } else {
      // overlapping: byte-wise (run-length semantics)
      for (size_t i = 0; i < len; i++) {
        out[o] = out[o - offset];
        o++;
      }
    }
  }
  *out_len = o;
  return 0;
}

// ---------------------------------------------------------------------------
// WAL segment scan: [u32 len][u32 crc(payload)][u64 seq][u32 crc(hdr)]
// [payload] frames.  The header CRC covers the 16-byte prefix so a bit
// flip anywhere in a record (including the sequence field) is detected.
// ---------------------------------------------------------------------------

struct GtWalSpan {
  uint64_t seq;
  uint64_t payload_off;
  uint64_t payload_len;
};

// Scans v2 frames, validating header + payload CRCs. Returns the number of
// valid frames with seq >= min_seq written to spans (up to max_spans), and
// sets *good_end to the byte offset after the last valid frame (corruption
// triage resumes from there). A negative return means spans overflowed
// (call again with more room).
int64_t gt_wal_scan2(const uint8_t* buf, size_t len, uint64_t min_seq,
                     GtWalSpan* spans, size_t max_spans, size_t* good_end) {
  size_t off = 0;
  size_t n = 0;
  *good_end = 0;
  while (off + 20 <= len) {
    uint32_t rec_len;
    uint32_t crc;
    uint64_t seq;
    uint32_t hcrc;
    memcpy(&rec_len, buf + off, 4);
    memcpy(&crc, buf + off + 4, 4);
    memcpy(&seq, buf + off + 8, 8);
    memcpy(&hcrc, buf + off + 16, 4);
    if (gt_crc32(buf + off, 16) != hcrc) break;
    size_t end = off + 20 + rec_len;
    if (end > len) break;
    if (gt_crc32(buf + off + 20, rec_len) != crc) break;
    if (seq >= min_seq) {
      if (n >= max_spans) return -static_cast<int64_t>(n);
      spans[n].seq = seq;
      spans[n].payload_off = off + 20;
      spans[n].payload_len = rec_len;
      n++;
    }
    off = end;
    *good_end = end;
  }
  return static_cast<int64_t>(n);
}

// Byte-scan forward from `start` for the next offset holding a fully valid
// v2 frame — the interior-corruption resync point. Returns the offset, or
// -1 when no valid frame follows (damage reaches EOF).
int64_t gt_wal_find_boundary2(const uint8_t* buf, size_t len, size_t start) {
  if (len < 20) return -1;
  for (size_t off = start; off + 20 <= len; off++) {
    uint32_t hcrc;
    memcpy(&hcrc, buf + off + 16, 4);
    if (gt_crc32(buf + off, 16) != hcrc) continue;
    uint32_t rec_len;
    uint32_t crc;
    memcpy(&rec_len, buf + off, 4);
    memcpy(&crc, buf + off + 4, 4);
    size_t end = off + 20 + rec_len;
    if (end > len) continue;
    if (gt_crc32(buf + off + 20, rec_len) != crc) continue;
    return static_cast<int64_t>(off);
  }
  return -1;
}

// ---------------------------------------------------------------------------
// JSON rows: the `rows` array of GreptimeDB's HTTP format, row-major
// ([[v, v, ...], ...]), written from column pointers with the bytes that
// Python's json.dumps gives for the same values as a list of lists:
// float repr (shortest round-trip digits, ".0" on whole values, exponent
// form below 1e-4 and from 1e16 with two exponent digits at least),
// ensure_ascii string escapes, ", " between items.  Only where the
// library has floating-point to_chars (libstdc++ 11 and later); without
// it the symbol is absent and the caller keeps json.dumps.
// ---------------------------------------------------------------------------

#if defined(__cpp_lib_to_chars)

enum GtJsonKind : int32_t {
  GT_JSON_F64 = 0,   // data: double; NaN -> null
  GT_JSON_I64 = 1,   // data: int64_t
  GT_JSON_U64 = 2,   // data: uint64_t
  GT_JSON_BOOL = 3,  // data: uint8_t
  GT_JSON_STR = 4,   // data: UTF-8 bytes; offsets: int32_t[n + 1]
};

struct GtJsonCol {
  int32_t kind;
  const void* data;
  const int32_t* offsets;  // strings only
  const uint8_t* valid;    // bit i set = not null (Arrow bitmap) or NULL
};

static char* json_double(char* p, double v) {
  if (v != v) return static_cast<char*>(memcpy(p, "null", 4)) + 4;
  if (v - v != 0) {  // +-inf, as json.dumps(allow_nan=True) writes it
    if (v < 0) *p++ = '-';
    return static_cast<char*>(memcpy(p, "Infinity", 8)) + 8;
  }
  // shortest digits as d[.ddd]e[+-]XX, then laid out by repr()'s rule
  char sci[32];
  char* end = std::to_chars(sci, sci + sizeof sci, v,
                            std::chars_format::scientific).ptr;
  const char* s = sci;
  if (*s == '-') *p++ = *s++;
  char digits[20];
  int nd = 0;
  for (; *s != 'e'; s++)
    if (*s != '.') digits[nd++] = *s;
  int exp10 = 0;
  bool neg = s[1] == '-';
  for (s += 2; s < end; s++) exp10 = exp10 * 10 + (*s - '0');
  int decpt = (neg ? -exp10 : exp10) + 1;
  if (decpt <= -4 || decpt > 16) {
    *p++ = digits[0];
    if (nd > 1) {
      *p++ = '.';
      memcpy(p, digits + 1, nd - 1);
      p += nd - 1;
    }
    *p++ = 'e';
    int e = decpt - 1;
    *p++ = e < 0 ? '-' : '+';
    if (e < 0) e = -e;
    if (e < 10) *p++ = '0';
    return std::to_chars(p, p + 4, e).ptr;
  }
  if (decpt <= 0) {
    *p++ = '0';
    *p++ = '.';
    for (int i = decpt; i < 0; i++) *p++ = '0';
    memcpy(p, digits, nd);
    return p + nd;
  }
  if (decpt >= nd) {
    memcpy(p, digits, nd);
    p += nd;
    for (int i = nd; i < decpt; i++) *p++ = '0';
    *p++ = '.';
    *p++ = '0';
    return p;
  }
  memcpy(p, digits, decpt);
  p += decpt;
  *p++ = '.';
  memcpy(p, digits + decpt, nd - decpt);
  return p + (nd - decpt);
}

static char* json_u_escape(char* p, uint32_t c) {
  static const char hex[] = "0123456789abcdef";
  *p++ = '\\';
  *p++ = 'u';
  *p++ = hex[(c >> 12) & 15];
  *p++ = hex[(c >> 8) & 15];
  *p++ = hex[(c >> 4) & 15];
  *p++ = hex[c & 15];
  return p;
}

// valid UTF-8 in (Arrow checked it), json.dumps(ensure_ascii=True) out
static char* json_string(char* p, const uint8_t* s, const uint8_t* end) {
  *p++ = '"';
  while (s < end) {
    uint32_t c = *s++;
    if (c >= 0x20 && c < 0x7F && c != '"' && c != '\\') {
      *p++ = static_cast<char>(c);
      continue;
    }
    char short_esc = 0;
    switch (c) {
      case '"': short_esc = '"'; break;
      case '\\': short_esc = '\\'; break;
      case '\n': short_esc = 'n'; break;
      case '\r': short_esc = 'r'; break;
      case '\t': short_esc = 't'; break;
      case '\b': short_esc = 'b'; break;
      case '\f': short_esc = 'f'; break;
    }
    if (short_esc) {
      *p++ = '\\';
      *p++ = short_esc;
      continue;
    }
    int more = c < 0x80 ? 0 : c < 0xE0 ? 1 : c < 0xF0 ? 2 : 3;
    if (more) c &= 0x3F >> more;
    for (; more && s < end; more--) c = (c << 6) | (*s++ & 0x3F);
    if (c >= 0x10000) {
      c -= 0x10000;
      p = json_u_escape(p, 0xD800 | (c >> 10));
      c = 0xDC00 | (c & 0x3FF);
    }
    p = json_u_escape(p, c);
  }
  *p++ = '"';
  return p;
}

// An upper bound of what gt_json_rows writes for these columns.
size_t gt_json_rows_bound(const GtJsonCol* cols, int32_t ncols,
                          int64_t nrows) {
  size_t row = 2;  // "[" "]"; each value below counts its ", "
  size_t text = 0;
  for (int32_t c = 0; c < ncols; c++) {
    switch (cols[c].kind) {
      case GT_JSON_F64: row += 26; break;   // -1.7976931348623157e+308
      case GT_JSON_I64:
      case GT_JSON_U64: row += 22; break;   // -9223372036854775808
      case GT_JSON_BOOL: row += 7; break;   // false
      default:                              // "..." or null
        row += 6;
        text += 6 * static_cast<size_t>(cols[c].offsets[nrows]
                                        - cols[c].offsets[0]);
    }
  }
  return 2 + static_cast<size_t>(nrows) * (row + 2) + text;
}

// Writes the array into out (at least gt_json_rows_bound bytes) and
// returns its length.
size_t gt_json_rows(const GtJsonCol* cols, int32_t ncols, int64_t nrows,
                    char* out) {
  char* p = out;
  *p++ = '[';
  for (int64_t r = 0; r < nrows; r++) {
    if (r) {
      *p++ = ',';
      *p++ = ' ';
    }
    *p++ = '[';
    for (int32_t c = 0; c < ncols; c++) {
      const GtJsonCol& col = cols[c];
      if (c) {
        *p++ = ',';
        *p++ = ' ';
      }
      if (col.valid && !((col.valid[r >> 3] >> (r & 7)) & 1)) {
        memcpy(p, "null", 4);
        p += 4;
        continue;
      }
      switch (col.kind) {
        case GT_JSON_F64:
          p = json_double(p, static_cast<const double*>(col.data)[r]);
          break;
        case GT_JSON_I64:
          p = std::to_chars(p, p + 20,
                            static_cast<const int64_t*>(col.data)[r]).ptr;
          break;
        case GT_JSON_U64:
          p = std::to_chars(p, p + 20,
                            static_cast<const uint64_t*>(col.data)[r]).ptr;
          break;
        case GT_JSON_BOOL:
          if (static_cast<const uint8_t*>(col.data)[r]) {
            memcpy(p, "true", 4);
            p += 4;
          } else {
            memcpy(p, "false", 5);
            p += 5;
          }
          break;
        default: {
          const uint8_t* text = static_cast<const uint8_t*>(col.data);
          p = json_string(p, text + col.offsets[r],
                          text + col.offsets[r + 1]);
        }
      }
    }
    *p++ = ']';
  }
  *p++ = ']';
  return static_cast<size_t>(p - out);
}

// ---------------------------------------------------------------------------
// The "result" array of a Prometheus matrix (promql/format.py
// MatrixSeries), from the [nseries, nsteps] values: the bytes json.dumps
// gives for [{"metric": {...}, "values": [[t, "v"], ...]}, ...] with t a
// float and v the sample's repr in quotes ("+Inf" and "-Inf" for the
// infinities).  A NaN is no sample and a series of nothing but NaN no
// series.  metrics holds each series' "metric" object as JSON text, one
// after the other, split at metric_offsets[nseries + 1].
// ---------------------------------------------------------------------------

// what a point takes at most: , _ [ t , _ " (its head: 28 with a t of
// 24) and v " ] (26 with a v of 24)
static const size_t GT_MATRIX_HEAD = 28;
static const size_t GT_MATRIX_POINT = 2 + GT_MATRIX_HEAD + 26;

// An upper bound of what gt_json_matrix writes, and behind it the room
// where it lays out the steps' heads (nsteps * GT_MATRIX_POINT bytes).
size_t gt_json_matrix_bound(const int64_t* metric_offsets, int64_t nseries,
                            int64_t nsteps) {
  size_t steps = static_cast<size_t>(nsteps) * GT_MATRIX_POINT;
  // , _ {"metric": ..., "values": [...]} is 28 bytes around a series
  return 2 + static_cast<size_t>(nseries) * (28 + steps)
         + static_cast<size_t>(metric_offsets[nseries] - metric_offsets[0])
         + steps;
}

// values: row s starts at values + s * row_stride doubles; step_seconds
// are finite.  Writes into out (at least gt_json_matrix_bound bytes) and
// returns the array's length.
size_t gt_json_matrix(const double* values, int64_t row_stride,
                      const double* step_seconds, const char* metrics,
                      const int64_t* metric_offsets, int64_t nseries,
                      int64_t nsteps, char* out) {
  // every series opens its point at step t with the same text, [t, " :
  // printed once a step, behind what the bound leaves for the array
  char* heads = out + gt_json_matrix_bound(metric_offsets, nseries, nsteps)
                - static_cast<size_t>(nsteps) * GT_MATRIX_POINT;
  uint8_t* head_len = reinterpret_cast<uint8_t*>(heads)
                      + static_cast<size_t>(nsteps) * GT_MATRIX_HEAD;
  for (int64_t t = 0; t < nsteps; t++) {
    char* h = heads + t * GT_MATRIX_HEAD;
    char* q = h;
    *q++ = '[';
    q = json_double(q, step_seconds[t]);
    *q++ = ',';
    *q++ = ' ';
    *q++ = '"';
    head_len[t] = static_cast<uint8_t>(q - h);
  }
  char* p = out;
  *p++ = '[';
  bool first_series = true;
  for (int64_t s = 0; s < nseries; s++) {
    const double* row = values + s * row_stride;
    int64_t t = 0;
    while (t < nsteps && row[t] != row[t]) t++;
    if (t == nsteps) continue;
    if (!first_series) {
      *p++ = ',';
      *p++ = ' ';
    }
    first_series = false;
    memcpy(p, "{\"metric\": ", 11);
    p += 11;
    size_t mlen = static_cast<size_t>(metric_offsets[s + 1]
                                      - metric_offsets[s]);
    memcpy(p, metrics + metric_offsets[s], mlen);
    p += mlen;
    memcpy(p, ", \"values\": [", 13);
    p += 13;
    for (bool first = true; t < nsteps; t++) {
      double v = row[t];
      if (v != v) continue;
      if (!first) {
        *p++ = ',';
        *p++ = ' ';
      }
      first = false;
      memcpy(p, heads + t * GT_MATRIX_HEAD, head_len[t]);
      p += head_len[t];
      if (v - v != 0) {
        memcpy(p, v < 0 ? "-Inf" : "+Inf", 4);
        p += 4;
      } else {
        p = json_double(p, v);
      }
      *p++ = '"';
      *p++ = ']';
    }
    *p++ = ']';
    *p++ = '}';
  }
  *p++ = ']';
  return static_cast<size_t>(p - out);
}

#endif  // __cpp_lib_to_chars

}  // extern "C"
