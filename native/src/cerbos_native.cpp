// cerbos_native: CPython extension for host-side hot paths.
//
// Native runtime pieces around the JAX/XLA compute path (the reference has no
// native code to mirror — SURVEY.md notes the obligation attaches to the new
// evaluator; these are the host analogues of internal/util/globs_common.go and
// the index's per-dimension matchers):
//
//   glob_match(pattern, value)        gobwas-style glob with ':' separator
//   glob_match_many(patterns, value)  indices of matching patterns
//   encode_double_keys(float64 buf)   order-preserving (hi, lo) int32 pairs
//
// Built with plain g++ (no pybind11 in the image); loaded by
// cerbos_tpu/native.py with a pure-Python fallback.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <atomic>
#include <climits>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <ctime>
#include <string>
#include <vector>

#if defined(__linux__)
#include <linux/futex.h>
#include <sys/syscall.h>
#include <time.h>
#include <unistd.h>
#endif

namespace {

constexpr char kSeparator = ':';

// Recursive glob matcher. Pattern syntax (gobwas/glob with separators={':'}):
//   *      any run of non-separator chars
//   **     any run of any chars
//   ?      one non-separator char
//   [ab] / [!ab] / [a-z]   char class (single char, separator-agnostic)
//   {a,b}  alternates (may contain nested patterns)
//   \x     literal x
// A bare "*" pattern is promoted to "**" by the caller (fixGlob behavior).
bool MatchGlob(const char* p, size_t plen, const char* v, size_t vlen, int depth);

// Matches a brace alternate set starting at p (just past '{'). Returns the
// offset of the char after the closing '}' via out_end, and fills alts with
// (start, len) pairs of each alternative.
bool SplitAlternates(const char* p, size_t plen, size_t* out_end,
                     std::vector<std::pair<size_t, size_t>>* alts) {
  size_t depth = 1;
  bool in_class = false;  // commas inside [...] are not separators
  size_t start = 0;
  for (size_t i = 0; i < plen; i++) {
    char c = p[i];
    if (c == '\\' && i + 1 < plen) {
      i++;
      continue;
    }
    if (in_class) {
      if (c == ']') in_class = false;
      continue;
    }
    if (c == '[') {
      in_class = true;
    } else if (c == '{') {
      depth++;
    } else if (c == '}') {
      depth--;
      if (depth == 0) {
        alts->emplace_back(start, i - start);
        *out_end = i + 1;
        return true;
      }
    } else if (c == ',' && depth == 1) {
      alts->emplace_back(start, i - start);
      start = i + 1;
    }
  }
  return false;  // unterminated
}

bool MatchClass(const char* body, size_t blen, bool negate, char c) {
  bool hit = false;
  for (size_t i = 0; i < blen; i++) {
    if (i + 2 < blen && body[i + 1] == '-') {
      if (c >= body[i] && c <= body[i + 2]) hit = true;
      i += 2;
    } else if (body[i] == c) {
      hit = true;
    }
  }
  return negate ? !hit : hit;
}

bool MatchGlob(const char* p, size_t plen, const char* v, size_t vlen, int depth) {
  if (depth > 64) return false;  // pathological nesting guard
  size_t pi = 0, vi = 0;
  while (pi < plen) {
    char pc = p[pi];
    if (pc == '*') {
      bool super = (pi + 1 < plen && p[pi + 1] == '*');
      size_t rest = pi + (super ? 2 : 1);
      // try all split points (greedy backtracking)
      for (size_t skip = 0; vi + skip <= vlen; skip++) {
        if (!super && skip > 0 && v[vi + skip - 1] == kSeparator) break;
        if (MatchGlob(p + rest, plen - rest, v + vi + skip, vlen - vi - skip, depth + 1)) {
          return true;
        }
      }
      return false;
    }
    if (pc == '{') {
      std::vector<std::pair<size_t, size_t>> alts;
      size_t end = 0;
      if (!SplitAlternates(p + pi + 1, plen - pi - 1, &end, &alts)) {
        // unterminated: literal '{'
        if (vi >= vlen || v[vi] != '{') return false;
        pi++;
        vi++;
        continue;
      }
      size_t after = pi + 1 + end;
      for (const auto& alt : alts) {
        // splice alternative + rest of pattern
        std::string combined(p + pi + 1 + alt.first, alt.second);
        combined.append(p + after, plen - after);
        if (MatchGlob(combined.data(), combined.size(), v + vi, vlen - vi, depth + 1)) {
          return true;
        }
      }
      return false;
    }
    if (vi >= vlen) return false;
    if (pc == '?') {
      if (v[vi] == kSeparator) return false;
      pi++;
      vi++;
      continue;
    }
    if (pc == '[') {
      size_t j = pi + 1;
      bool negate = (j < plen && p[j] == '!');
      if (negate) j++;
      size_t k = j;
      if (k < plen && p[k] == ']') k++;  // literal ']' first member
      while (k < plen && p[k] != ']') k++;
      if (k >= plen) {  // unterminated: literal '['
        if (v[vi] != '[') return false;
        pi++;
        vi++;
        continue;
      }
      if (!MatchClass(p + j, k - j, negate, v[vi])) return false;
      pi = k + 1;
      vi++;
      continue;
    }
    if (pc == '\\' && pi + 1 < plen) {
      if (v[vi] != p[pi + 1]) return false;
      pi += 2;
      vi++;
      continue;
    }
    if (v[vi] != pc) return false;
    pi++;
    vi++;
  }
  return vi == vlen;
}

bool MatchTop(const char* p, Py_ssize_t plen, const char* v, Py_ssize_t vlen) {
  // fixGlob: bare "*" means "**"
  if (plen == 1 && p[0] == '*') return true;
  return MatchGlob(p, static_cast<size_t>(plen), v, static_cast<size_t>(vlen), 0);
}

PyObject* PyGlobMatch(PyObject*, PyObject* args) {
  const char* pattern;
  Py_ssize_t plen;
  const char* value;
  Py_ssize_t vlen;
  if (!PyArg_ParseTuple(args, "s#s#", &pattern, &plen, &value, &vlen)) return nullptr;
  if (MatchTop(pattern, plen, value, vlen)) Py_RETURN_TRUE;
  Py_RETURN_FALSE;
}

PyObject* PyGlobMatchMany(PyObject*, PyObject* args) {
  PyObject* patterns;
  const char* value;
  Py_ssize_t vlen;
  if (!PyArg_ParseTuple(args, "Os#", &patterns, &value, &vlen)) return nullptr;
  PyObject* seq = PySequence_Fast(patterns, "patterns must be a sequence");
  if (seq == nullptr) return nullptr;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  PyObject* out = PyList_New(0);
  if (out == nullptr) {
    Py_DECREF(seq);
    return nullptr;
  }
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject* item = PySequence_Fast_GET_ITEM(seq, i);
    Py_ssize_t plen;
    const char* pattern = PyUnicode_AsUTF8AndSize(item, &plen);
    if (pattern == nullptr) {
      Py_DECREF(seq);
      Py_DECREF(out);
      return nullptr;
    }
    if (MatchTop(pattern, plen, value, vlen)) {
      PyObject* idx = PyLong_FromSsize_t(i);
      PyList_Append(out, idx);
      Py_DECREF(idx);
    }
  }
  Py_DECREF(seq);
  return out;
}

// encode_double_keys(input_buffer_f64) -> (bytes_hi_i32, bytes_lo_i32, bytes_nan_u8)
PyObject* PyEncodeDoubleKeys(PyObject*, PyObject* args) {
  Py_buffer buf;
  if (!PyArg_ParseTuple(args, "y*", &buf)) return nullptr;
  if (buf.len % 8 != 0) {
    PyBuffer_Release(&buf);
    PyErr_SetString(PyExc_ValueError, "buffer length must be a multiple of 8");
    return nullptr;
  }
  Py_ssize_t n = buf.len / 8;
  PyObject* hi_b = PyBytes_FromStringAndSize(nullptr, n * 4);
  PyObject* lo_b = PyBytes_FromStringAndSize(nullptr, n * 4);
  PyObject* nan_b = PyBytes_FromStringAndSize(nullptr, n);
  if (!hi_b || !lo_b || !nan_b) {
    Py_XDECREF(hi_b);
    Py_XDECREF(lo_b);
    Py_XDECREF(nan_b);
    PyBuffer_Release(&buf);
    return nullptr;
  }
  const uint64_t* in = static_cast<const uint64_t*>(buf.buf);
  int32_t* hi = reinterpret_cast<int32_t*>(PyBytes_AS_STRING(hi_b));
  int32_t* lo = reinterpret_cast<int32_t*>(PyBytes_AS_STRING(lo_b));
  uint8_t* nan = reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(nan_b));
  for (Py_ssize_t i = 0; i < n; i++) {
    uint64_t bits = in[i];
    double d;
    std::memcpy(&d, &bits, 8);
    bool is_nan = d != d;
    if (d == 0.0) {  // -0.0 == 0.0 in CEL: same key
      d = 0.0;
      std::memcpy(&bits, &d, 8);
    }
    uint64_t key;
    if (bits & (1ULL << 63)) {
      key = ~bits;
    } else {
      key = bits | (1ULL << 63);
    }
    // sign-bias each word so signed int32 comparison preserves key order
    uint32_t h = static_cast<uint32_t>(key >> 32) ^ 0x80000000u;
    uint32_t l = static_cast<uint32_t>(key & 0xFFFFFFFFULL) ^ 0x80000000u;
    hi[i] = static_cast<int32_t>(h);
    lo[i] = static_cast<int32_t>(l);
    nan[i] = is_nan ? 1 : 0;
  }
  PyBuffer_Release(&buf);
  PyObject* result = PyTuple_Pack(3, hi_b, lo_b, nan_b);
  Py_DECREF(hi_b);
  Py_DECREF(lo_b);
  Py_DECREF(nan_b);
  return result;
}

// One value → (tag, hi, lo, sid, nan) at slot i. Returns -1 on allocation
// failure (Python error set), 0 otherwise. TAG codes (columns.py):
// MISSING=0 NULL=1 BOOL=2 NUM=3 STR=4 OTHER=5 ERR=6.
int EncodeOne(PyObject* v, PyObject* interner, PyObject* missing,
              PyObject* err, Py_ssize_t i, uint8_t* tags, int32_t* hi,
              int32_t* lo, int32_t* sid, uint8_t* nan) {
  tags[i] = 0;
  hi[i] = 0;
  lo[i] = 0;
  sid[i] = 0;
  nan[i] = 0;
  if (v == missing) {
    return 0;  // TAG_MISSING zeros
  }
  if (v == err) {
    tags[i] = 6;
    return 0;
  }
  if (v == Py_None) {
    tags[i] = 1;
    return 0;
  }
  if (PyBool_Check(v)) {
    tags[i] = 2;
    hi[i] = (v == Py_True) ? 1 : 0;
    return 0;
  }
  double d;
  // subtype-tolerant (np.float64, IntEnum...) to match encode_value's
  // isinstance checks; bool was already handled above
  if (PyFloat_Check(v)) {
    d = PyFloat_AS_DOUBLE(v);
  } else if (PyLong_Check(v)) {
    d = PyLong_AsDouble(v);
    if (d == -1.0 && PyErr_Occurred()) {
      PyErr_Clear();
      tags[i] = 5;  // magnitude beyond double: host/oracle territory
      return 0;
    }
  } else if (PyUnicode_Check(v)) {
    tags[i] = 4;
    PyObject* id_obj = PyDict_GetItem(interner, v);  // borrowed
    long id;
    if (id_obj != nullptr) {
      id = PyLong_AsLong(id_obj);
    } else {
      id = static_cast<long>(PyDict_Size(interner)) + 1;
      PyObject* new_id = PyLong_FromLong(id);
      if (!new_id || PyDict_SetItem(interner, v, new_id) < 0) {
        Py_XDECREF(new_id);
        return -1;
      }
      Py_DECREF(new_id);
    }
    sid[i] = static_cast<int32_t>(id);
    return 0;
  } else {
    tags[i] = 5;  // lists/dicts/other
    return 0;
  }
  // numeric path (float or in-range int)
  tags[i] = 3;
  if (d != d) {
    nan[i] = 1;
    return 0;
  }
  if (d == 0.0) d = 0.0;  // collapse -0.0
  uint64_t bits;
  std::memcpy(&bits, &d, 8);
  uint64_t key = (bits & (1ULL << 63)) ? ~bits : (bits | (1ULL << 63));
  hi[i] = static_cast<int32_t>(static_cast<uint32_t>(key >> 32) ^ 0x80000000u);
  lo[i] = static_cast<int32_t>(static_cast<uint32_t>(key) ^ 0x80000000u);
  return 0;
}

// encode_column(values, interner_dict, missing, err,
//               tags_u8, hi_i32, lo_i32, sid_i32, nan_u8) -> None
//
// One column's batch encoding (columns.py encode_value semantics over a
// whole [B] list): per element writes (tag, hi, lo, sid, nan) into the
// writable buffers. String ids come from / are added to interner_dict
// (str -> int, ids start at 1 — StringInterner). `missing` / `err` are the
// packer's sentinel objects compared by identity.
PyObject* PyEncodeColumn(PyObject*, PyObject* args) {
  PyObject* values;
  PyObject* interner;
  PyObject* missing;
  PyObject* err;
  Py_buffer tags_b, hi_b, lo_b, sid_b, nan_b;
  if (!PyArg_ParseTuple(args, "OO!OOw*w*w*w*w*", &values, &PyDict_Type,
                        &interner, &missing, &err, &tags_b, &hi_b, &lo_b,
                        &sid_b, &nan_b)) {
    return nullptr;
  }
  struct Bufs {
    Py_buffer *a, *b, *c, *d, *e;
    ~Bufs() {
      PyBuffer_Release(a);
      PyBuffer_Release(b);
      PyBuffer_Release(c);
      PyBuffer_Release(d);
      PyBuffer_Release(e);
    }
  } release{&tags_b, &hi_b, &lo_b, &sid_b, &nan_b};

  PyObject* seq = PySequence_Fast(values, "values must be a sequence");
  if (!seq) return nullptr;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  if (tags_b.len < n || nan_b.len < n ||
      hi_b.len < static_cast<Py_ssize_t>(n * 4) ||
      lo_b.len < static_cast<Py_ssize_t>(n * 4) ||
      sid_b.len < static_cast<Py_ssize_t>(n * 4)) {
    Py_DECREF(seq);
    PyErr_SetString(PyExc_ValueError, "output buffers too small");
    return nullptr;
  }
  uint8_t* tags = static_cast<uint8_t*>(tags_b.buf);
  int32_t* hi = static_cast<int32_t*>(hi_b.buf);
  int32_t* lo = static_cast<int32_t*>(lo_b.buf);
  int32_t* sid = static_cast<int32_t*>(sid_b.buf);
  uint8_t* nan = static_cast<uint8_t*>(nan_b.buf);

  // TAG codes (columns.py): MISSING=0 NULL=1 BOOL=2 NUM=3 STR=4 OTHER=5 ERR=6
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject* v = PySequence_Fast_GET_ITEM(seq, i);
    if (EncodeOne(v, interner, missing, err, i, tags, hi, lo, sid, nan) < 0) {
      Py_DECREF(seq);
      return nullptr;
    }
  }
  Py_DECREF(seq);
  Py_RETURN_NONE;
}

// Shared value gather for the packer's fused column modes (see
// PyEncodeAttrColumn). Returns a NEW reference (or `missing` borrowed with
// an extra ref) — caller decrefs.
PyObject* GatherValue(PyObject* inp, int mode, PyObject* root, PyObject* leaf,
                      PyObject* missing, PyObject* attr_name,
                      PyObject* aux_name, PyObject* jwt_name) {
  if (mode == 0) {
    PyObject* obj = PyObject_GetAttr(inp, root);
    if (!obj) {
      PyErr_Clear();
    } else {
      PyObject* attrs = PyObject_GetAttr(obj, attr_name);
      Py_DECREF(obj);
      if (!attrs) {
        PyErr_Clear();
      } else {
        if (PyDict_Check(attrs)) {
          PyObject* got = PyDict_GetItemWithError(attrs, leaf);  // borrowed
          if (got) {
            Py_INCREF(got);
            Py_DECREF(attrs);
            return got;
          }
          if (PyErr_Occurred()) PyErr_Clear();
        }
        Py_DECREF(attrs);
      }
    }
  } else if (mode == 1) {
    PyObject* aux = PyObject_GetAttr(inp, aux_name);
    if (!aux) {
      PyErr_Clear();
    } else {
      if (aux != Py_None) {
        PyObject* jwt = PyObject_GetAttr(aux, jwt_name);
        if (!jwt) {
          PyErr_Clear();
        } else {
          if (PyDict_Check(jwt)) {
            PyObject* got = PyDict_GetItemWithError(jwt, leaf);  // borrowed
            if (got) {
              Py_INCREF(got);
              Py_DECREF(jwt);
              Py_DECREF(aux);
              return got;
            }
            if (PyErr_Occurred()) PyErr_Clear();
          }
          Py_DECREF(jwt);
        }
      }
      Py_DECREF(aux);
    }
  } else {
    PyObject* obj = PyObject_GetAttr(inp, root);
    if (obj) {
      PyObject* got = PyObject_GetAttr(obj, leaf);
      Py_DECREF(obj);
      if (got) return got;
      PyErr_Clear();
    } else {
      PyErr_Clear();
    }
  }
  Py_INCREF(missing);
  return missing;
}

// encode_attr_column(inputs, mode, root, leaf, interner, missing, err,
//                    tags_u8, hi_i32, lo_i32, sid_i32, nan_u8
//                    [, subtype_u8]) -> None
//
// Fused gather + encode for the packer's common column shapes: the value
// resolution (Python attribute access per input) AND the type dispatch /
// key encoding run in one C loop, so no per-input Python frames and no
// intermediate values list. Modes mirror packer._path_accessor:
//   0: getattr(inp, root).attr.get(leaf)        — attr leaves
//   1: inp.aux_data → .jwt.get(leaf)            — JWT claims
//   2: getattr(getattr(inp, root), leaf)        — top-level fields
//
// The optional subtype buffer records information the (tag, hi, lo) key
// erases but CEL semantics keep: 0 = n/a, 1 = float, 2 = int exactly
// representable as double, 3 = int NOT exactly representable (key is
// lossy). Callers that group values by key need it to avoid collapsing
// CEL-distinct numerics (int 1 vs double 1.0, 2^53 vs 2^53+1).
PyObject* PyEncodeAttrColumn(PyObject*, PyObject* args) {
  PyObject* inputs;
  int mode;
  PyObject* root;
  PyObject* leaf;
  PyObject* interner;
  PyObject* missing;
  PyObject* err;
  Py_buffer tags_b, hi_b, lo_b, sid_b, nan_b;
  Py_buffer subtype_b;
  subtype_b.buf = nullptr;
  if (!PyArg_ParseTuple(args, "OiUUO!OOw*w*w*w*w*|w*", &inputs, &mode, &root,
                        &leaf, &PyDict_Type, &interner, &missing, &err,
                        &tags_b, &hi_b, &lo_b, &sid_b, &nan_b, &subtype_b)) {
    return nullptr;
  }
  struct Bufs {
    Py_buffer *a, *b, *c, *d, *e, *f;
    ~Bufs() {
      PyBuffer_Release(a);
      PyBuffer_Release(b);
      PyBuffer_Release(c);
      PyBuffer_Release(d);
      PyBuffer_Release(e);
      if (f->buf) PyBuffer_Release(f);
    }
  } release{&tags_b, &hi_b, &lo_b, &sid_b, &nan_b, &subtype_b};

  PyObject* seq = PySequence_Fast(inputs, "inputs must be a sequence");
  if (!seq) return nullptr;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  if (tags_b.len < n || nan_b.len < n ||
      hi_b.len < static_cast<Py_ssize_t>(n * 4) ||
      lo_b.len < static_cast<Py_ssize_t>(n * 4) ||
      sid_b.len < static_cast<Py_ssize_t>(n * 4)) {
    Py_DECREF(seq);
    PyErr_SetString(PyExc_ValueError, "output buffers too small");
    return nullptr;
  }
  uint8_t* tags = static_cast<uint8_t*>(tags_b.buf);
  int32_t* hi = static_cast<int32_t*>(hi_b.buf);
  int32_t* lo = static_cast<int32_t*>(lo_b.buf);
  int32_t* sid = static_cast<int32_t*>(sid_b.buf);
  uint8_t* nan = static_cast<uint8_t*>(nan_b.buf);
  uint8_t* subtype = static_cast<uint8_t*>(subtype_b.buf);  // may be null
  if (subtype && subtype_b.len < n) {
    Py_DECREF(seq);
    PyErr_SetString(PyExc_ValueError, "subtype buffer too small");
    return nullptr;
  }

  static PyObject* attr_name = nullptr;  // interned "attr"
  static PyObject* aux_name = nullptr;   // interned "aux_data"
  static PyObject* jwt_name = nullptr;   // interned "jwt"
  if (!attr_name) attr_name = PyUnicode_InternFromString("attr");
  if (!aux_name) aux_name = PyUnicode_InternFromString("aux_data");
  if (!jwt_name) jwt_name = PyUnicode_InternFromString("jwt");

  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject* inp = PySequence_Fast_GET_ITEM(seq, i);
    PyObject* v = GatherValue(inp, mode, root, leaf, missing, attr_name,
                              aux_name, jwt_name);  // owned
    int rc = EncodeOne(v, interner, missing, err, i, tags, hi, lo, sid, nan);
    if (subtype) {
      uint8_t st = 0;
      if (v != missing && v != err && !PyBool_Check(v)) {
        if (PyFloat_Check(v)) {
          st = 1;
        } else if (PyLong_Check(v)) {
          double d = PyLong_AsDouble(v);
          if (d == -1.0 && PyErr_Occurred()) {
            PyErr_Clear();
            st = 3;  // beyond double: key is lossy
          } else {
            PyObject* fl = PyFloat_FromDouble(d);
            if (fl) {
              // Python int==float comparison is exact (arbitrary precision)
              int eq = PyObject_RichCompareBool(v, fl, Py_EQ);
              Py_DECREF(fl);
              if (eq < 0) PyErr_Clear();
              st = (eq == 1) ? 2 : 3;
            } else {
              PyErr_Clear();
              st = 3;
            }
          }
        }
      }
      subtype[i] = st;
    }
    Py_DECREF(v);
    if (rc < 0) {
      Py_DECREF(seq);
      return nullptr;
    }
  }
  Py_DECREF(seq);
  Py_RETURN_NONE;
}

// encode_list_column(inputs, mode, root, leaf, interner, missing,
//                    state_u8_buf) -> (width, sids_bytes)
//
// Fused gather + intern for string-list membership columns
// (packer._encode_list_columns semantics): per input
//   missing attr        -> state 0
//   dict value          -> state 3 (caller routes the plan to the oracle)
//   non-list            -> state 2 (CEL error on device)
//   list                -> state 1; str elements interned, non-str -> sid 0
// The sid matrix is zero-padded to width = pow2(max_len, >=4) so jit traces
// reuse across batches; returned as raw little-endian int32 bytes [n, width].
PyObject* PyEncodeListColumn(PyObject*, PyObject* args) {
  PyObject* inputs;
  int mode;
  PyObject* root;
  PyObject* leaf;
  PyObject* interner;
  PyObject* missing;
  Py_buffer state_b;
  if (!PyArg_ParseTuple(args, "OiUUO!Ow*", &inputs, &mode, &root, &leaf,
                        &PyDict_Type, &interner, &missing, &state_b)) {
    return nullptr;
  }
  PyObject* seq = PySequence_Fast(inputs, "inputs must be a sequence");
  if (!seq) {
    PyBuffer_Release(&state_b);
    return nullptr;
  }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  if (state_b.len < n) {
    Py_DECREF(seq);
    PyBuffer_Release(&state_b);
    PyErr_SetString(PyExc_ValueError, "state buffer too small");
    return nullptr;
  }
  uint8_t* state = static_cast<uint8_t*>(state_b.buf);

  static PyObject* attr_name = nullptr;
  static PyObject* aux_name = nullptr;
  static PyObject* jwt_name = nullptr;
  if (!attr_name) attr_name = PyUnicode_InternFromString("attr");
  if (!aux_name) aux_name = PyUnicode_InternFromString("aux_data");
  if (!jwt_name) jwt_name = PyUnicode_InternFromString("jwt");

  std::vector<PyObject*> vals(static_cast<size_t>(n));
  Py_ssize_t max_len = 1;
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject* inp = PySequence_Fast_GET_ITEM(seq, i);
    PyObject* v = GatherValue(inp, mode, root, leaf, missing, attr_name,
                              aux_name, jwt_name);
    vals[static_cast<size_t>(i)] = v;
    if (PyList_Check(v)) {
      Py_ssize_t len = PyList_GET_SIZE(v);
      if (len > max_len) max_len = len;
    }
  }
  Py_ssize_t width = 4;
  while (width < max_len) width *= 2;

  PyObject* sids_b = PyBytes_FromStringAndSize(nullptr, n * width * 4);
  if (!sids_b) {
    for (PyObject* v : vals) Py_DECREF(v);
    Py_DECREF(seq);
    PyBuffer_Release(&state_b);
    return nullptr;
  }
  int32_t* sids = reinterpret_cast<int32_t*>(PyBytes_AS_STRING(sids_b));
  std::memset(sids, 0, static_cast<size_t>(n * width * 4));

  bool fail = false;
  for (Py_ssize_t i = 0; i < n && !fail; i++) {
    PyObject* v = vals[static_cast<size_t>(i)];
    if (v == missing) {
      state[i] = 0;
    } else if (PyDict_Check(v)) {
      state[i] = 3;  // map membership is key membership: oracle territory
    } else if (!PyList_Check(v)) {
      state[i] = 2;
    } else {
      state[i] = 1;
      Py_ssize_t len = PyList_GET_SIZE(v);
      int32_t* row = sids + i * width;
      for (Py_ssize_t j = 0; j < len; j++) {
        PyObject* el = PyList_GET_ITEM(v, j);
        if (!PyUnicode_Check(el)) {
          row[j] = 0;  // non-string never equals a string constant
          continue;
        }
        PyObject* id_obj = PyDict_GetItem(interner, el);  // borrowed
        long id;
        if (id_obj != nullptr) {
          id = PyLong_AsLong(id_obj);
        } else {
          id = static_cast<long>(PyDict_Size(interner)) + 1;
          PyObject* new_id = PyLong_FromLong(id);
          if (!new_id || PyDict_SetItem(interner, el, new_id) < 0) {
            Py_XDECREF(new_id);
            fail = true;
            break;
          }
          Py_DECREF(new_id);
        }
        row[j] = static_cast<int32_t>(id);
      }
    }
  }
  for (PyObject* v : vals) Py_DECREF(v);
  Py_DECREF(seq);
  PyBuffer_Release(&state_b);
  if (fail) {
    Py_DECREF(sids_b);
    return nullptr;
  }
  PyObject* width_obj = PyLong_FromSsize_t(width);
  PyObject* result = PyTuple_Pack(2, width_obj, sids_b);
  Py_DECREF(width_obj);
  Py_DECREF(sids_b);
  return result;
}

// resolve_effects(BA, K, J, D, C, ba_input_i32, cand_cond_i32, cand_drcond_i32,
//                 cand_effect_i8, cand_pt_i8, cand_depth_i8, cand_valid_u8,
//                 scope_sp_i8, sat_cond_u8, allow_code, deny_code, sp_override,
//                 final_i8[BA*4], role_results_i8[BA*K*2*2], win_j_i8[BA*K*2])
//
// The effect-resolution lattice (evaluator._compute's post-sat half) as one
// fused pass: per (input,action) cell walk roles × depths, first-DENY /
// first-ALLOW-with-OVERRIDE per depth, then the role/policy-type merge.
// Semantically identical to the numpy/jax lattice — the numpy fallback calls
// this to replace ~40 small-array kernel launches with one memory pass; the
// jax path keeps the XLA lattice for device execution.
PyObject* PyResolveEffects(PyObject*, PyObject* args) {
  int BA, K, J, D, C;
  Py_buffer ba_b, cc_b, cd_b, ce_b, cp_b, cdep_b, cv_b, sp_b, sat_b;
  int allow_code, deny_code, sp_override;
  Py_buffer fin_b, rr_b, wj_b;
  if (!PyArg_ParseTuple(args, "iiiiiy*y*y*y*y*y*y*y*y*iiiw*w*w*", &BA, &K, &J,
                        &D, &C, &ba_b, &cc_b, &cd_b, &ce_b, &cp_b, &cdep_b,
                        &cv_b, &sp_b, &sat_b, &allow_code, &deny_code,
                        &sp_override, &fin_b, &rr_b, &wj_b)) {
    return nullptr;
  }
  struct Bufs {
    std::vector<Py_buffer*> bufs;
    ~Bufs() {
      for (auto* b : bufs) PyBuffer_Release(b);
    }
  } release{{&ba_b, &cc_b, &cd_b, &ce_b, &cp_b, &cdep_b, &cv_b, &sp_b, &sat_b,
             &fin_b, &rr_b, &wj_b}};
  const Py_ssize_t cells = static_cast<Py_ssize_t>(BA) * K * J;
  if (ba_b.len < static_cast<Py_ssize_t>(BA * 4) ||
      cc_b.len < cells * 4 || cd_b.len < cells * 4 || ce_b.len < cells ||
      cp_b.len < cells || cdep_b.len < cells || cv_b.len < cells ||
      fin_b.len < static_cast<Py_ssize_t>(BA) * 4 ||
      rr_b.len < static_cast<Py_ssize_t>(BA) * K * 4 ||
      wj_b.len < static_cast<Py_ssize_t>(BA) * K * 2) {
    PyErr_SetString(PyExc_ValueError, "buffer sizes inconsistent");
    return nullptr;
  }
  const int32_t* ba_input = static_cast<const int32_t*>(ba_b.buf);
  const int32_t* cand_cond = static_cast<const int32_t*>(cc_b.buf);
  const int32_t* cand_drcond = static_cast<const int32_t*>(cd_b.buf);
  const int8_t* cand_effect = static_cast<const int8_t*>(ce_b.buf);
  const int8_t* cand_pt = static_cast<const int8_t*>(cp_b.buf);
  const int8_t* cand_depth = static_cast<const int8_t*>(cdep_b.buf);
  const uint8_t* cand_valid = static_cast<const uint8_t*>(cv_b.buf);
  const int8_t* scope_sp = static_cast<const int8_t*>(sp_b.buf);
  const uint8_t* sat_cond = static_cast<const uint8_t*>(sat_b.buf);
  int8_t* fin = static_cast<int8_t*>(fin_b.buf);
  int8_t* rr = static_cast<int8_t*>(rr_b.buf);
  int8_t* wj_out = static_cast<int8_t*>(wj_b.buf);

  constexpr int kNoMatch = 0, kAllow = 1, kDeny = 2;
  constexpr int kBig = 127;

  // scope_sp/sat_cond are indexed by input id b and (for sat) condition
  // column: validate against the largest b and cond id actually referenced
  // so a mis-sized array raises instead of reading out of bounds
  {
    int32_t max_b = -1;
    for (int ba = 0; ba < BA; ba++) {
      if (ba_input[ba] < 0) {
        PyErr_SetString(PyExc_ValueError, "negative ba_input entry");
        return nullptr;
      }
      if (ba_input[ba] > max_b) max_b = ba_input[ba];
    }
    if (sp_b.len < static_cast<Py_ssize_t>(max_b + 1) * 2 * D ||
        sat_b.len < static_cast<Py_ssize_t>(max_b + 1) * C) {
      PyErr_SetString(PyExc_ValueError,
                      "scope_sp/sat buffers too small for referenced inputs");
      return nullptr;
    }
    for (Py_ssize_t idx = 0; idx < cells; idx++) {
      if (cand_cond[idx] >= C || cand_drcond[idx] >= C) {
        PyErr_SetString(PyExc_ValueError, "cand cond id out of sat range");
        return nullptr;
      }
    }
  }

  Py_BEGIN_ALLOW_THREADS
  for (int ba = 0; ba < BA; ba++) {
    const int b = ba_input[ba];
    const uint8_t* sat_row = sat_cond + static_cast<Py_ssize_t>(b) * C;
    const int8_t* sp_row = scope_sp + static_cast<Py_ssize_t>(b) * 2 * D;
    // per (k, pt) results
    for (int pt = 0; pt < 2; pt++) {
      for (int k = 0; k < K; k++) {
        int code = kNoMatch, depth_out = D, wj = -1;
        bool decided = false;
        const Py_ssize_t cell = (static_cast<Py_ssize_t>(ba) * K + k) * J;
        for (int d = 0; d < D && !decided; d++) {
          bool deny_d = false, allow_d = false;
          int deny_j = kBig, allow_j = kBig;
          for (int j = 0; j < J; j++) {
            const Py_ssize_t idx = cell + j;
            if (!cand_valid[idx]) continue;
            if (cand_pt[idx] != pt || cand_depth[idx] != d) continue;
            const int32_t cond = cand_cond[idx];
            if (cond >= 0 && !sat_row[cond]) continue;
            const int32_t dr = cand_drcond[idx];
            if (dr >= 0 && !sat_row[dr]) continue;
            const int8_t eff = cand_effect[idx];
            if (eff == deny_code) {
              deny_d = true;
              if (j < deny_j) deny_j = j;
            } else if (eff == allow_code) {
              allow_d = true;
              if (j < allow_j) allow_j = j;
            }
          }
          const bool allow_ok = allow_d && sp_row[pt * D + d] == sp_override;
          if (deny_d) {
            code = kDeny;
            depth_out = d;
            wj = deny_j;
            decided = true;
          } else if (allow_ok) {
            // winning-rule column (ISSUE 20): ALLOW decisions record their
            // first satisfied j too, mirroring the numpy/jax lattice
            code = kAllow;
            depth_out = d;
            wj = allow_j;
            decided = true;
          }
        }
        const Py_ssize_t rr_idx = ((static_cast<Py_ssize_t>(ba) * K + k) * 2 + pt) * 2;
        rr[rr_idx] = static_cast<int8_t>(code);
        rr[rr_idx + 1] = static_cast<int8_t>(depth_out);
        wj_out[(static_cast<Py_ssize_t>(ba) * K + k) * 2 + pt] =
            static_cast<int8_t>(wj);
      }
    }
    // merge: principal pass uses role 0 only; resource pass picks the first
    // role with ALLOW, else the first role with any non-NO_MATCH, else 0
    const Py_ssize_t base = static_cast<Py_ssize_t>(ba) * K;
    const int p_code = rr[(base * 2 + 0) * 2];
    const int p_depth = rr[(base * 2 + 0) * 2 + 1];
    int r_pick = 0;
    {
      int allow_k = kBig, nonmatch_k = kBig;
      for (int k = 0; k < K; k++) {
        const int code = rr[((base + k) * 2 + 1) * 2];
        if (code == kAllow && allow_k == kBig) allow_k = k;
        if (code != kNoMatch && nonmatch_k == kBig) nonmatch_k = k;
      }
      r_pick = allow_k < kBig ? allow_k : (nonmatch_k < kBig ? nonmatch_k : 0);
    }
    const int r_code = rr[((base + r_pick) * 2 + 1) * 2];
    const int r_depth = rr[((base + r_pick) * 2 + 1) * 2 + 1];
    const bool use_p = p_code != kNoMatch;
    fin[static_cast<Py_ssize_t>(ba) * 4] =
        static_cast<int8_t>(use_p ? p_code : r_code);
    fin[static_cast<Py_ssize_t>(ba) * 4 + 1] = static_cast<int8_t>(use_p ? 0 : 1);
    fin[static_cast<Py_ssize_t>(ba) * 4 + 2] =
        static_cast<int8_t>(use_p ? p_depth : r_depth);
    fin[static_cast<Py_ssize_t>(ba) * 4 + 3] =
        static_cast<int8_t>(use_p ? 0 : r_pick);
  }
  Py_END_ALLOW_THREADS
  Py_RETURN_NONE;
}

// decode_node_pool(raw_nodes, class_map, dec_value) -> list
//
// Linear decode of the bundle codec's node pool (bundle_codec._Decoder
// semantics): one forward pass, children strictly before parents, instances
// created WITHOUT running __init__ (tp_new) and fields installed with
// PyObject_GenericSetAttr (bypasses the frozen-dataclass __setattr__ guard —
// these are freshly built objects we own). Scalars pass through; tagged
// value payloads ({"$B"/"$L"/"$S"/"$M"}) go through the Python `dec_value`
// callback. Malformed structure raises ValueError, which the Python wrapper
// maps to CodecError.
namespace nodepool {

struct Names {
  PyObject *value, *name, *operand, *field, *index, *fn, *args, *target;
  PyObject *items, *entries, *init, *body, *kind, *iter_range, *iter_var;
  PyObject *step, *iter_var2, *step2, *original, *node, *expr, *children;
  PyObject *rule_activated, *condition_not_met, *constants, *ordered_variables;
};

Names* GetNames() {
  static Names* names = nullptr;
  if (!names) {
    names = new Names{
        PyUnicode_InternFromString("value"),
        PyUnicode_InternFromString("name"),
        PyUnicode_InternFromString("operand"),
        PyUnicode_InternFromString("field"),
        PyUnicode_InternFromString("index"),
        PyUnicode_InternFromString("fn"),
        PyUnicode_InternFromString("args"),
        PyUnicode_InternFromString("target"),
        PyUnicode_InternFromString("items"),
        PyUnicode_InternFromString("entries"),
        PyUnicode_InternFromString("init"),
        PyUnicode_InternFromString("body"),
        PyUnicode_InternFromString("kind"),
        PyUnicode_InternFromString("iter_range"),
        PyUnicode_InternFromString("iter_var"),
        PyUnicode_InternFromString("step"),
        PyUnicode_InternFromString("iter_var2"),
        PyUnicode_InternFromString("step2"),
        PyUnicode_InternFromString("original"),
        PyUnicode_InternFromString("node"),
        PyUnicode_InternFromString("expr"),
        PyUnicode_InternFromString("children"),
        PyUnicode_InternFromString("rule_activated"),
        PyUnicode_InternFromString("condition_not_met"),
        PyUnicode_InternFromString("constants"),
        PyUnicode_InternFromString("ordered_variables"),
    };
  }
  return names;
}

bool BadRef(Py_ssize_t i) {
  PyErr_Format(PyExc_ValueError, "bad node ref in node %zd", i);
  return false;
}

// cache[j] for child ref j (must be int < i); None passes through.
// Returns BORROWED reference or nullptr with error set.
PyObject* Child(PyObject* cache, Py_ssize_t i, PyObject* j) {
  if (j == Py_None) return Py_None;
  if (!PyLong_Check(j)) {
    BadRef(i);
    return nullptr;
  }
  Py_ssize_t idx = PyLong_AsSsize_t(j);
  if (idx < 0 || idx >= i) {
    BadRef(i);
    return nullptr;
  }
  return PyList_GET_ITEM(cache, idx);
}

// decode a value payload: scalar passes through (new ref); dict -> callback
PyObject* Value(PyObject* dec_value, PyObject* v) {
  if (v == Py_None || PyBool_Check(v) || PyLong_Check(v) ||
      PyFloat_Check(v) || PyUnicode_Check(v)) {
    Py_INCREF(v);
    return v;
  }
  return PyObject_CallFunctionObjArgs(dec_value, v, nullptr);
}

// tuple of child refs from a list payload; new reference
PyObject* ChildTuple(PyObject* cache, Py_ssize_t i, PyObject* lst) {
  if (!PyList_Check(lst)) {
    BadRef(i);
    return nullptr;
  }
  Py_ssize_t n = PyList_GET_SIZE(lst);
  PyObject* out = PyTuple_New(n);
  if (!out) return nullptr;
  for (Py_ssize_t k = 0; k < n; k++) {
    PyObject* c = Child(cache, i, PyList_GET_ITEM(lst, k));
    if (!c) {
      Py_DECREF(out);
      return nullptr;
    }
    Py_INCREF(c);
    PyTuple_SET_ITEM(out, k, c);
  }
  return out;
}

PyObject* NewInstance(PyObject* cls) {
  PyTypeObject* tp = reinterpret_cast<PyTypeObject*>(cls);
  static PyObject* empty_args = nullptr;
  if (!empty_args) empty_args = PyTuple_New(0);
  return tp->tp_new(tp, empty_args, nullptr);
}

// set attr bypassing the class __setattr__ override (frozen dataclasses)
inline int Set(PyObject* obj, PyObject* name, PyObject* value) {
  return PyObject_GenericSetAttr(obj, name, value);
}

// steal-style helper: set then drop our reference
inline int SetSteal(PyObject* obj, PyObject* name, PyObject* value) {
  if (!value) return -1;
  int rc = PyObject_GenericSetAttr(obj, name, value);
  Py_DECREF(value);
  return rc;
}

}  // namespace nodepool

PyObject* PyDecodeNodePool(PyObject*, PyObject* args) {
  PyObject* raw;
  PyObject* class_map;
  PyObject* dec_value;
  if (!PyArg_ParseTuple(args, "O!O!O", &PyList_Type, &raw, &PyDict_Type,
                        &class_map, &dec_value)) {
    return nullptr;
  }
  using namespace nodepool;
  Names* N = GetNames();
  Py_ssize_t n = PyList_GET_SIZE(raw);
  PyObject* cache = PyList_New(n);
  if (!cache) return nullptr;
  for (Py_ssize_t k = 0; k < n; k++) {
    Py_INCREF(Py_None);
    PyList_SET_ITEM(cache, k, Py_None);
  }

  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject* e = PyList_GET_ITEM(raw, i);
    if (!PyList_Check(e) || PyList_GET_SIZE(e) < 2) {
      PyErr_Format(PyExc_ValueError, "malformed node %zd", i);
      Py_DECREF(cache);
      return nullptr;
    }
    PyObject* tag = PyList_GET_ITEM(e, 0);
    if (!PyUnicode_Check(tag)) {
      PyErr_Format(PyExc_ValueError, "malformed node tag at %zd", i);
      Py_DECREF(cache);
      return nullptr;
    }
    PyObject* cls = PyDict_GetItem(class_map, tag);  // borrowed
    if (!cls || !PyType_Check(cls)) {
      PyErr_Format(PyExc_ValueError, "unknown node tag at %zd", i);
      Py_DECREF(cache);
      return nullptr;
    }
    PyObject* obj = NewInstance(cls);
    if (!obj) {
      Py_DECREF(cache);
      return nullptr;
    }
    const char* t = PyUnicode_AsUTF8(tag);
    const Py_ssize_t sz = PyList_GET_SIZE(e);
    bool ok = true;
    auto item = [&](Py_ssize_t k) -> PyObject* {  // borrowed; None if short
      return k < sz ? PyList_GET_ITEM(e, k) : Py_None;
    };
    auto child_at = [&](Py_ssize_t k) -> PyObject* {
      return Child(cache, i, item(k));
    };
    if (std::strcmp(t, "sel") == 0 || std::strcmp(t, "has") == 0) {
      PyObject* op = child_at(1);
      ok = op && Set(obj, N->operand, op) == 0 &&
           Set(obj, N->field, item(2)) == 0;
    } else if (std::strcmp(t, "id") == 0) {
      ok = Set(obj, N->name, item(1)) == 0;
    } else if (std::strcmp(t, "lit") == 0) {
      ok = SetSteal(obj, N->value, Value(dec_value, item(1))) == 0;
    } else if (std::strcmp(t, "call") == 0) {
      PyObject* tgt = child_at(3);
      ok = Set(obj, N->fn, item(1)) == 0 &&
           SetSteal(obj, N->args, ChildTuple(cache, i, item(2))) == 0 &&
           tgt && Set(obj, N->target, tgt) == 0;
    } else if (std::strcmp(t, "ix") == 0) {
      PyObject* op = child_at(1);
      PyObject* ix = child_at(2);
      ok = op && ix && Set(obj, N->operand, op) == 0 &&
           Set(obj, N->index, ix) == 0;
    } else if (std::strcmp(t, "list") == 0) {
      ok = SetSteal(obj, N->items, ChildTuple(cache, i, item(1))) == 0;
    } else if (std::strcmp(t, "map") == 0) {
      PyObject* lst = item(1);
      ok = PyList_Check(lst);
      if (ok) {
        Py_ssize_t m = PyList_GET_SIZE(lst);
        PyObject* entries = PyTuple_New(m);
        ok = entries != nullptr;
        for (Py_ssize_t k = 0; ok && k < m; k++) {
          PyObject* pair = PyList_GET_ITEM(lst, k);
          if (!PyList_Check(pair) || PyList_GET_SIZE(pair) != 2) {
            ok = false;
            break;
          }
          PyObject* pk = Child(cache, i, PyList_GET_ITEM(pair, 0));
          PyObject* pv = Child(cache, i, PyList_GET_ITEM(pair, 1));
          if (!pk || !pv) {
            ok = false;
            break;
          }
          PyObject* tup = PyTuple_Pack(2, pk, pv);
          if (!tup) {
            ok = false;
            break;
          }
          PyTuple_SET_ITEM(entries, k, tup);
        }
        if (ok) {
          ok = Set(obj, N->entries, entries) == 0;
        }
        Py_XDECREF(entries);
      } else {
        BadRef(i);
      }
    } else if (std::strcmp(t, "bind") == 0) {
      PyObject* ini = child_at(2);
      PyObject* body = child_at(3);
      ok = ini && body && Set(obj, N->name, item(1)) == 0 &&
           Set(obj, N->init, ini) == 0 && Set(obj, N->body, body) == 0;
    } else if (std::strcmp(t, "comp") == 0) {
      PyObject* rng = child_at(2);
      PyObject* step = child_at(4);
      PyObject* step2 = child_at(6);
      ok = rng && step && step2 &&
           Set(obj, N->kind, item(1)) == 0 &&
           Set(obj, N->iter_range, rng) == 0 &&
           Set(obj, N->iter_var, item(3)) == 0 &&
           Set(obj, N->step, step) == 0 &&
           Set(obj, N->iter_var2, item(5)) == 0 &&
           Set(obj, N->step2, step2) == 0;
    } else if (std::strcmp(t, "E") == 0) {
      PyObject* nd = child_at(2);
      ok = nd && Set(obj, N->original, item(1)) == 0 &&
           Set(obj, N->node, nd) == 0;
    } else if (std::strcmp(t, "C") == 0) {
      PyObject* ex = child_at(2);
      ok = ex && Set(obj, N->kind, item(1)) == 0 &&
           Set(obj, N->expr, ex) == 0 &&
           SetSteal(obj, N->children, ChildTuple(cache, i, item(3))) == 0;
    } else if (std::strcmp(t, "V") == 0) {
      PyObject* ex = child_at(2);
      ok = ex && Set(obj, N->name, item(1)) == 0 &&
           Set(obj, N->expr, ex) == 0;
    } else if (std::strcmp(t, "O") == 0) {
      PyObject* ra = child_at(1);
      PyObject* cm = child_at(2);
      ok = ra && cm && Set(obj, N->rule_activated, ra) == 0 &&
           Set(obj, N->condition_not_met, cm) == 0;
    } else if (std::strcmp(t, "P") == 0) {
      ok = SetSteal(obj, N->constants, Value(dec_value, item(1))) == 0 &&
           SetSteal(obj, N->ordered_variables, ChildTuple(cache, i, item(2))) == 0;
    } else {
      PyErr_Format(PyExc_ValueError, "unknown node tag at %zd", i);
      ok = false;
    }
    if (!ok) {
      if (!PyErr_Occurred()) BadRef(i);
      Py_DECREF(obj);
      Py_DECREF(cache);
      return nullptr;
    }
    PyList_SetItem(cache, i, obj);  // steals obj, drops the None placeholder
  }
  return cache;
}

// encode_attr_columns_multi(inputs, specs, interner, missing, err,
//                           tags_u8, hi_i32, lo_i32, sid_i32, nan_u8) -> None
//
// One pass over the batch for EVERY fused column path at once. specs is a
// sequence of (mode, root, leaf) as in encode_attr_column; the output
// buffers are row-major [P, n] matrices (row p = spec p). Each input's
// principal / resource objects and their attr / jwt dicts are resolved
// ONCE and shared by all specs, so the per-input Python attribute-access
// overhead is paid once instead of P times (the packer's dominant
// memo-cold cost).
PyObject* PyEncodeAttrColumnsMulti(PyObject*, PyObject* args) {
  PyObject* inputs;
  PyObject* specs;
  PyObject* interner;
  PyObject* missing;
  PyObject* err;
  Py_buffer tags_b, hi_b, lo_b, sid_b, nan_b;
  if (!PyArg_ParseTuple(args, "OOO!OOw*w*w*w*w*", &inputs, &specs,
                        &PyDict_Type, &interner, &missing, &err, &tags_b,
                        &hi_b, &lo_b, &sid_b, &nan_b)) {
    return nullptr;
  }
  struct Bufs {
    Py_buffer *a, *b, *c, *d, *e;
    ~Bufs() {
      PyBuffer_Release(a);
      PyBuffer_Release(b);
      PyBuffer_Release(c);
      PyBuffer_Release(d);
      PyBuffer_Release(e);
    }
  } release{&tags_b, &hi_b, &lo_b, &sid_b, &nan_b};

  PyObject* seq = PySequence_Fast(inputs, "inputs must be a sequence");
  if (!seq) return nullptr;
  PyObject* spec_seq = PySequence_Fast(specs, "specs must be a sequence");
  if (!spec_seq) {
    Py_DECREF(seq);
    return nullptr;
  }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  Py_ssize_t P = PySequence_Fast_GET_SIZE(spec_seq);
  if (tags_b.len < P * n || nan_b.len < P * n ||
      hi_b.len < static_cast<Py_ssize_t>(P * n * 4) ||
      lo_b.len < static_cast<Py_ssize_t>(P * n * 4) ||
      sid_b.len < static_cast<Py_ssize_t>(P * n * 4)) {
    Py_DECREF(spec_seq);
    Py_DECREF(seq);
    PyErr_SetString(PyExc_ValueError, "output buffers too small");
    return nullptr;
  }
  uint8_t* tags = static_cast<uint8_t*>(tags_b.buf);
  int32_t* hi = static_cast<int32_t*>(hi_b.buf);
  int32_t* lo = static_cast<int32_t*>(lo_b.buf);
  int32_t* sid = static_cast<int32_t*>(sid_b.buf);
  uint8_t* nan = static_cast<uint8_t*>(nan_b.buf);

  // spec table: mode, principal-or-resource flag, leaf object
  struct Spec {
    int mode;
    bool principal;
    PyObject* leaf;  // borrowed from spec tuple (spec_seq held)
  };
  std::vector<Spec> sp(static_cast<size_t>(P));
  bool need_p = false, need_r = false, need_jwt = false;
  bool need_p_attr = false, need_r_attr = false;
  for (Py_ssize_t p = 0; p < P; p++) {
    PyObject* item = PySequence_Fast_GET_ITEM(spec_seq, p);
    PyObject* mode_o;
    PyObject* root_o;
    PyObject* leaf_o;
    if (!PyTuple_Check(item) || PyTuple_GET_SIZE(item) != 3) {
      Py_DECREF(spec_seq);
      Py_DECREF(seq);
      PyErr_SetString(PyExc_TypeError, "spec must be (mode, root, leaf)");
      return nullptr;
    }
    mode_o = PyTuple_GET_ITEM(item, 0);
    root_o = PyTuple_GET_ITEM(item, 1);
    leaf_o = PyTuple_GET_ITEM(item, 2);
    long mode = PyLong_AsLong(mode_o);
    if (mode < 0 || mode > 2 || !PyUnicode_Check(root_o) ||
        !PyUnicode_Check(leaf_o)) {
      Py_DECREF(spec_seq);
      Py_DECREF(seq);
      PyErr_SetString(PyExc_ValueError, "bad spec entry");
      return nullptr;
    }
    bool is_principal =
        PyUnicode_CompareWithASCIIString(root_o, "principal") == 0;
    sp[static_cast<size_t>(p)] = {static_cast<int>(mode), is_principal, leaf_o};
    if (mode == 1) {
      need_jwt = true;
    } else if (is_principal) {
      need_p = true;
      if (mode == 0) need_p_attr = true;
    } else {
      need_r = true;
      if (mode == 0) need_r_attr = true;
    }
  }

  static PyObject* attr_name = nullptr;
  static PyObject* aux_name = nullptr;
  static PyObject* jwt_name = nullptr;
  static PyObject* principal_name = nullptr;
  static PyObject* resource_name = nullptr;
  if (!attr_name) attr_name = PyUnicode_InternFromString("attr");
  if (!aux_name) aux_name = PyUnicode_InternFromString("aux_data");
  if (!jwt_name) jwt_name = PyUnicode_InternFromString("jwt");
  if (!principal_name) principal_name = PyUnicode_InternFromString("principal");
  if (!resource_name) resource_name = PyUnicode_InternFromString("resource");

  bool fail = false;
  for (Py_ssize_t i = 0; i < n && !fail; i++) {
    PyObject* inp = PySequence_Fast_GET_ITEM(seq, i);
    // resolve shared roots once per input (owned refs, may stay null)
    PyObject* p_obj = nullptr;
    PyObject* r_obj = nullptr;
    PyObject* p_attr = nullptr;
    PyObject* r_attr = nullptr;
    PyObject* jwt = nullptr;
    if (need_p) {
      p_obj = PyObject_GetAttr(inp, principal_name);
      if (!p_obj) PyErr_Clear();
      if (need_p_attr && p_obj) {
        p_attr = PyObject_GetAttr(p_obj, attr_name);
        if (!p_attr) PyErr_Clear();
        if (p_attr && !PyDict_Check(p_attr)) Py_CLEAR(p_attr);
      }
    }
    if (need_r) {
      r_obj = PyObject_GetAttr(inp, resource_name);
      if (!r_obj) PyErr_Clear();
      if (need_r_attr && r_obj) {
        r_attr = PyObject_GetAttr(r_obj, attr_name);
        if (!r_attr) PyErr_Clear();
        if (r_attr && !PyDict_Check(r_attr)) Py_CLEAR(r_attr);
      }
    }
    if (need_jwt) {
      PyObject* aux = PyObject_GetAttr(inp, aux_name);
      if (!aux) {
        PyErr_Clear();
      } else {
        if (aux != Py_None) {
          jwt = PyObject_GetAttr(aux, jwt_name);
          if (!jwt) PyErr_Clear();
          if (jwt && !PyDict_Check(jwt)) Py_CLEAR(jwt);
        }
        Py_DECREF(aux);
      }
    }

    for (Py_ssize_t p = 0; p < P && !fail; p++) {
      const Spec& s = sp[static_cast<size_t>(p)];
      PyObject* v = nullptr;  // owned
      if (s.mode == 0) {
        PyObject* d = s.principal ? p_attr : r_attr;
        if (d) {
          PyObject* got = PyDict_GetItemWithError(d, s.leaf);  // borrowed
          if (got) {
            Py_INCREF(got);
            v = got;
          } else if (PyErr_Occurred()) {
            PyErr_Clear();
          }
        }
      } else if (s.mode == 1) {
        if (jwt) {
          PyObject* got = PyDict_GetItemWithError(jwt, s.leaf);
          if (got) {
            Py_INCREF(got);
            v = got;
          } else if (PyErr_Occurred()) {
            PyErr_Clear();
          }
        }
      } else {
        PyObject* obj = s.principal ? p_obj : r_obj;
        if (obj) {
          v = PyObject_GetAttr(obj, s.leaf);
          if (!v) PyErr_Clear();
        }
      }
      if (!v) {
        Py_INCREF(missing);
        v = missing;
      }
      Py_ssize_t at = p * n + i;
      int rc = EncodeOne(v, interner, missing, err, at, tags, hi, lo, sid, nan);
      Py_DECREF(v);
      if (rc < 0) fail = true;
    }

    Py_XDECREF(p_obj);
    Py_XDECREF(r_obj);
    Py_XDECREF(p_attr);
    Py_XDECREF(r_attr);
    Py_XDECREF(jwt);
  }
  Py_DECREF(spec_seq);
  Py_DECREF(seq);
  if (fail) return nullptr;
  Py_RETURN_NONE;
}

// -- two-level packed bitmap sweep (ruletable/index.py bitmap backend) -------
//
// Each dimension arrives as a pair of uint64 numpy arrays: `words` (bit r of
// words[r>>6] set iff row r is in the posting list) and `summary` (bit w of
// summary[w>>6] set iff words[w] != 0). The sweep ANDs the summary level to
// find candidate 64-word blocks, ANDs only the live words, and decodes set
// bits into ascending row ids — the C twin of index._sweep_numpy.

struct BitmapDims {
  std::vector<Py_buffer> bufs;       // all acquired buffers (released in dtor)
  std::vector<const uint64_t*> words;
  std::vector<Py_ssize_t> words_len; // in uint64 words
  std::vector<const uint64_t*> sums;
  std::vector<Py_ssize_t> sums_len;
  bool ok = false;

  ~BitmapDims() {
    for (auto& b : bufs) PyBuffer_Release(&b);
  }

  // sums_seq may be Py_None: small tables skip the summary level entirely
  // (a linear word AND beats six extra buffer acquisitions).
  bool Acquire(PyObject* words_seq, PyObject* sums_seq) {
    PyObject* wfast = PySequence_Fast(words_seq, "words must be a sequence");
    if (!wfast) return false;
    PyObject* sfast = nullptr;
    if (sums_seq != Py_None) {
      sfast = PySequence_Fast(sums_seq, "summaries must be a sequence");
      if (!sfast) {
        Py_DECREF(wfast);
        return false;
      }
    }
    Py_ssize_t n = PySequence_Fast_GET_SIZE(wfast);
    bool good = n > 0 && (!sfast || PySequence_Fast_GET_SIZE(sfast) == n);
    if (!good) {
      PyErr_SetString(PyExc_ValueError, "words/summary dimension mismatch");
    }
    bufs.reserve((sfast ? 2 : 1) * (size_t)n);
    for (Py_ssize_t i = 0; good && i < n; i++) {
      Py_buffer wb, sb;
      if (PyObject_GetBuffer(PySequence_Fast_GET_ITEM(wfast, i), &wb,
                             PyBUF_SIMPLE) < 0) {
        good = false;
        break;
      }
      bufs.push_back(wb);
      words.push_back(static_cast<const uint64_t*>(wb.buf));
      words_len.push_back(wb.len / 8);
      if (sfast) {
        if (PyObject_GetBuffer(PySequence_Fast_GET_ITEM(sfast, i), &sb,
                               PyBUF_SIMPLE) < 0) {
          good = false;
          break;
        }
        bufs.push_back(sb);
        sums.push_back(static_cast<const uint64_t*>(sb.buf));
        sums_len.push_back(sb.len / 8);
      }
    }
    Py_DECREF(wfast);
    Py_XDECREF(sfast);
    ok = good;
    return good;
  }

  // shortest common word / summary extents (missing tails are all-zero)
  Py_ssize_t MinWords() const {
    Py_ssize_t m = words_len[0];
    for (size_t i = 1; i < words_len.size(); i++)
      if (words_len[i] < m) m = words_len[i];
    return m;
  }
  Py_ssize_t MinSums() const {
    Py_ssize_t m = sums_len[0];
    for (size_t i = 1; i < sums_len.size(); i++)
      if (sums_len[i] < m) m = sums_len[i];
    return m;
  }
};

// bitmap_sweep(words_seq, sums_seq, extra_words|None, rows|None)
//   -> (base_any, list)
// `extra` is the action dimension: legacy query semantics exclude it from the
// base-emptiness check (an empty base suppresses role-policy DENY synthesis;
// an empty action intersect does not), so it is applied after base_any is
// known. With `rows`, set bits gather rows[rid] (skipping None) instead of
// returning raw ids.
PyObject* PyBitmapSweep(PyObject*, PyObject* args) {
  PyObject *words_seq, *sums_seq, *extra_obj, *rows_obj;
  if (!PyArg_ParseTuple(args, "OOOO", &words_seq, &sums_seq, &extra_obj,
                        &rows_obj))
    return nullptr;

  if (rows_obj != Py_None && !PyList_Check(rows_obj)) {
    PyErr_SetString(PyExc_TypeError, "rows must be a list or None");
    return nullptr;
  }
  const Py_ssize_t nrows = rows_obj != Py_None ? PyList_GET_SIZE(rows_obj) : 0;

  BitmapDims dims;
  if (!dims.Acquire(words_seq, sums_seq)) return nullptr;

  Py_buffer extra_b;
  const uint64_t* extra = nullptr;
  Py_ssize_t extra_len = 0;
  if (extra_obj != Py_None) {
    if (PyObject_GetBuffer(extra_obj, &extra_b, PyBUF_SIMPLE) < 0)
      return nullptr;
    extra = static_cast<const uint64_t*>(extra_b.buf);
    extra_len = extra_b.len / 8;
  }

  PyObject* out = PyList_New(0);
  if (!out) {
    if (extra) PyBuffer_Release(&extra_b);
    return nullptr;
  }

  const Py_ssize_t L = dims.MinWords();
  const size_t nd = dims.words.size();
  bool base_any = false;
  bool fail = false;

  auto emit_word = [&](Py_ssize_t w) {
    uint64_t acc = dims.words[0][w];
    for (size_t i = 1; i < nd && acc; i++) acc &= dims.words[i][w];
    if (!acc) return;
    base_any = true;
    if (extra) acc &= (w < extra_len) ? extra[w] : 0;
    while (acc) {
      const int rbit = __builtin_ctzll(acc);
      acc &= acc - 1;
      const Py_ssize_t rid = (w << 6) + rbit;
      if (rows_obj != Py_None) {
        if (rid >= nrows) continue;  // capacity words past the row list
        PyObject* row = PyList_GET_ITEM(rows_obj, rid);  // borrowed
        if (row == Py_None) continue;
        if (PyList_Append(out, row) < 0) {
          fail = true;
          return;
        }
      } else {
        PyObject* rid_obj = PyLong_FromSsize_t(rid);
        if (!rid_obj || PyList_Append(out, rid_obj) < 0) {
          Py_XDECREF(rid_obj);
          fail = true;
          return;
        }
        Py_DECREF(rid_obj);
      }
    }
  };

  if (dims.sums.empty()) {
    for (Py_ssize_t w = 0; w < L && !fail; w++) emit_word(w);
  } else {
    const Py_ssize_t S = dims.MinSums();
    for (Py_ssize_t s = 0; s < S && !fail; s++) {
      uint64_t m = dims.sums[0][s];
      for (size_t i = 1; i < nd && m; i++) m &= dims.sums[i][s];
      while (m && !fail) {
        const int bit = __builtin_ctzll(m);
        m &= m - 1;
        const Py_ssize_t w = (s << 6) + bit;
        if (w >= L) break;  // ascending: later words in this block are past L
        emit_word(w);
      }
    }
  }

  if (extra) PyBuffer_Release(&extra_b);
  if (fail) {
    Py_DECREF(out);
    return nullptr;
  }
  PyObject* res = PyTuple_New(2);
  if (!res) {
    Py_DECREF(out);
    return nullptr;
  }
  PyTuple_SET_ITEM(res, 0, PyBool_FromLong(base_any));
  PyTuple_SET_ITEM(res, 1, out);
  return res;
}

// bitmap_any(words_seq, sums_seq) -> bool — sweep with first-hit early exit
// (exists checks).
PyObject* PyBitmapAny(PyObject*, PyObject* args) {
  PyObject *words_seq, *sums_seq;
  if (!PyArg_ParseTuple(args, "OO", &words_seq, &sums_seq)) return nullptr;

  BitmapDims dims;
  if (!dims.Acquire(words_seq, sums_seq)) return nullptr;

  const Py_ssize_t L = dims.MinWords();
  const size_t nd = dims.words.size();

  if (dims.sums.empty()) {
    for (Py_ssize_t w = 0; w < L; w++) {
      uint64_t acc = dims.words[0][w];
      for (size_t i = 1; i < nd && acc; i++) acc &= dims.words[i][w];
      if (acc) Py_RETURN_TRUE;
    }
    Py_RETURN_FALSE;
  }

  const Py_ssize_t S = dims.MinSums();
  for (Py_ssize_t s = 0; s < S; s++) {
    uint64_t m = dims.sums[0][s];
    for (size_t i = 1; i < nd && m; i++) m &= dims.sums[i][s];
    while (m) {
      const int bit = __builtin_ctzll(m);
      m &= m - 1;
      const Py_ssize_t w = (s << 6) + bit;
      if (w >= L) break;
      uint64_t acc = dims.words[0][w];
      for (size_t i = 1; i < nd && acc; i++) acc &= dims.words[i][w];
      if (acc) Py_RETURN_TRUE;
    }
  }
  Py_RETURN_FALSE;
}

// stack_pad_rows(dst, rows) — fill the 2-D+ transfer matrix `dst`
// (C-contiguous, len(rows) leading slots of row_bytes each) with the
// C-contiguous arrays in `rows`: memcpy each row's bytes into its slot and
// zero the padded tail. Replaces the per-row Python assignment loop in the
// evaluator's pad+stack pass (one call per column family per batch).
// Rows pad along their LEADING axis, so prefix-copy + zero-tail is exact.
PyObject* PyStackPadRows(PyObject*, PyObject* args) {
  PyObject *dst_obj, *rows_obj;
  if (!PyArg_ParseTuple(args, "OO", &dst_obj, &rows_obj)) return nullptr;

  Py_buffer dst_b;
  if (PyObject_GetBuffer(dst_obj, &dst_b, PyBUF_WRITABLE) < 0) return nullptr;

  PyObject* fast = PySequence_Fast(rows_obj, "rows must be a sequence");
  if (!fast) {
    PyBuffer_Release(&dst_b);
    return nullptr;
  }
  const Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
  bool ok = true;
  if (n == 0 || dst_b.len % n != 0) {
    PyErr_SetString(PyExc_ValueError, "dst length not divisible by row count");
    ok = false;
  }
  const Py_ssize_t row_bytes = ok ? dst_b.len / n : 0;
  char* out = static_cast<char*>(dst_b.buf);
  for (Py_ssize_t i = 0; ok && i < n; i++) {
    Py_buffer rb;
    if (PyObject_GetBuffer(PySequence_Fast_GET_ITEM(fast, i), &rb,
                           PyBUF_SIMPLE) < 0) {
      ok = false;
      break;
    }
    if (rb.len > row_bytes) {
      PyErr_SetString(PyExc_ValueError, "row larger than dst slot");
      PyBuffer_Release(&rb);
      ok = false;
      break;
    }
    char* slot = out + i * row_bytes;
    memcpy(slot, rb.buf, rb.len);
    if (rb.len < row_bytes) memset(slot + rb.len, 0, row_bytes - rb.len);
    PyBuffer_Release(&rb);
  }
  Py_DECREF(fast);
  PyBuffer_Release(&dst_b);
  if (!ok) return nullptr;
  Py_RETURN_NONE;
}

// ===========================================================================
// Front-door transport kernels (engine/ipc.py shm transport + server hot path)
//
// The multi-process front door's per-request path crosses these four pieces:
//
//   ticket_pack / ticket_unpack   CheckInput rows + relative deadline +
//                                 traceparent + waterfall carry <-> one
//                                 fixed-field-order binary frame
//   reply_pack / reply_unpack    CheckOutput effect rows + reply spec
//   ring_*                       lock-light SPSC byte ring over a shared
//                                mmap with futex wakeups (one ring per
//                                direction per front end)
//   json_loads / json_dumps      the CheckResources HTTP body parser and
//                                reply encoder (stdlib-compatible subset)
//
// Values inside frames use a small tagged binary codec (the marshal
// replacement): N/T/F, i (int64), g (bigint decimal), d (double), s (utf8
// string), b (bytes), l (list), m (dict). Field ORDER is fixed per frame
// type; values are self-describing so attr payloads stay schema-free.

PyObject* kEmptyTuple = nullptr;

struct InternTable {
  PyObject* request_id;
  PyObject* principal;
  PyObject* resource;
  PyObject* actions;
  PyObject* aux_data;
  PyObject* id;
  PyObject* roles;
  PyObject* attr;
  PyObject* policy_version;
  PyObject* scope;
  PyObject* kind;
  PyObject* jwt;
  PyObject* resource_id;
  PyObject* effective_derived_roles;
  PyObject* validation_errors;
  PyObject* outputs;
  PyObject* effective_policies;
  PyObject* effect;
  PyObject* policy;
  PyObject* src;
  PyObject* action;
  PyObject* val;
  PyObject* error;
  PyObject* path;
  PyObject* message;
  PyObject* source;
  PyObject* matched_rule;
  PyObject* rule_row_id;
};
InternTable I;

bool InitTransportStatics() {
  kEmptyTuple = PyTuple_New(0);
  if (!kEmptyTuple) return false;
#define CN_INTERN(f)                                      \
  if (!(I.f = PyUnicode_InternFromString(#f))) return false;
  CN_INTERN(request_id)
  CN_INTERN(principal)
  CN_INTERN(resource)
  CN_INTERN(actions)
  CN_INTERN(aux_data)
  CN_INTERN(id)
  CN_INTERN(roles)
  CN_INTERN(attr)
  CN_INTERN(policy_version)
  CN_INTERN(scope)
  CN_INTERN(kind)
  CN_INTERN(jwt)
  CN_INTERN(resource_id)
  CN_INTERN(effective_derived_roles)
  CN_INTERN(validation_errors)
  CN_INTERN(outputs)
  CN_INTERN(effective_policies)
  CN_INTERN(effect)
  CN_INTERN(policy)
  CN_INTERN(src)
  CN_INTERN(action)
  CN_INTERN(val)
  CN_INTERN(error)
  CN_INTERN(path)
  CN_INTERN(message)
  CN_INTERN(source)
  CN_INTERN(matched_rule)
  CN_INTERN(rule_row_id)
#undef CN_INTERN
  return true;
}

// -- tagged value codec ------------------------------------------------------

struct Buf {
  std::string s;
  void u8(uint8_t v) { s.push_back(static_cast<char>(v)); }
  void u32(uint32_t v) { s.append(reinterpret_cast<const char*>(&v), 4); }
  void u64(uint64_t v) { s.append(reinterpret_cast<const char*>(&v), 8); }
  void f64(double v) { s.append(reinterpret_cast<const char*>(&v), 8); }
  void raw(const char* p, size_t n) { s.append(p, n); }
};

struct Rd {
  const uint8_t* p;
  const uint8_t* end;
  bool need(size_t n) {
    if (static_cast<size_t>(end - p) < n) {
      PyErr_SetString(PyExc_ValueError, "truncated frame");
      return false;
    }
    return true;
  }
  bool u8(uint8_t* out) {
    if (!need(1)) return false;
    *out = *p++;
    return true;
  }
  bool u32(uint32_t* out) {
    if (!need(4)) return false;
    memcpy(out, p, 4);
    p += 4;
    return true;
  }
  bool u64(uint64_t* out) {
    if (!need(8)) return false;
    memcpy(out, p, 8);
    p += 8;
    return true;
  }
  bool f64(double* out) {
    if (!need(8)) return false;
    memcpy(out, p, 8);
    p += 8;
    return true;
  }
};

bool EncodeValue(Buf& b, PyObject* v, int depth) {
  if (depth > 64) {
    PyErr_SetString(PyExc_ValueError, "value nesting too deep for frame codec");
    return false;
  }
  if (v == Py_None) {
    b.u8('N');
    return true;
  }
  if (PyBool_Check(v)) {
    b.u8(v == Py_True ? 'T' : 'F');
    return true;
  }
  if (PyLong_Check(v)) {
    int overflow = 0;
    long long x = PyLong_AsLongLongAndOverflow(v, &overflow);
    if (!overflow) {
      if (x == -1 && PyErr_Occurred()) return false;
      b.u8('i');
      b.u64(static_cast<uint64_t>(x));
      return true;
    }
    PyObject* s = PyObject_Str(v);  // arbitrary-precision: decimal string
    if (!s) return false;
    Py_ssize_t n;
    const char* u = PyUnicode_AsUTF8AndSize(s, &n);
    if (!u) {
      Py_DECREF(s);
      return false;
    }
    b.u8('g');
    b.u32(static_cast<uint32_t>(n));
    b.raw(u, static_cast<size_t>(n));
    Py_DECREF(s);
    return true;
  }
  if (PyFloat_Check(v)) {
    b.u8('d');
    b.f64(PyFloat_AS_DOUBLE(v));
    return true;
  }
  if (PyUnicode_Check(v)) {
    Py_ssize_t n;
    const char* u = PyUnicode_AsUTF8AndSize(v, &n);
    if (!u) return false;
    b.u8('s');
    b.u32(static_cast<uint32_t>(n));
    b.raw(u, static_cast<size_t>(n));
    return true;
  }
  if (PyBytes_Check(v)) {
    b.u8('b');
    b.u32(static_cast<uint32_t>(PyBytes_GET_SIZE(v)));
    b.raw(PyBytes_AS_STRING(v), static_cast<size_t>(PyBytes_GET_SIZE(v)));
    return true;
  }
  if (PyList_Check(v) || PyTuple_Check(v)) {
    PyObject* fast = PySequence_Fast(v, "sequence");
    if (!fast) return false;
    const Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    b.u8('l');
    b.u32(static_cast<uint32_t>(n));
    for (Py_ssize_t i = 0; i < n; i++) {
      if (!EncodeValue(b, PySequence_Fast_GET_ITEM(fast, i), depth + 1)) {
        Py_DECREF(fast);
        return false;
      }
    }
    Py_DECREF(fast);
    return true;
  }
  if (PyDict_Check(v)) {
    b.u8('m');
    b.u32(static_cast<uint32_t>(PyDict_GET_SIZE(v)));
    PyObject *key, *value;
    Py_ssize_t pos = 0;
    while (PyDict_Next(v, &pos, &key, &value)) {
      if (!EncodeValue(b, key, depth + 1)) return false;
      if (!EncodeValue(b, value, depth + 1)) return false;
    }
    return true;
  }
  PyErr_Format(PyExc_TypeError, "frame codec cannot encode %s",
               Py_TYPE(v)->tp_name);
  return false;
}

PyObject* DecodeValue(Rd& rd, int depth) {
  if (depth > 64) {
    PyErr_SetString(PyExc_ValueError, "frame nesting too deep");
    return nullptr;
  }
  uint8_t tag;
  if (!rd.u8(&tag)) return nullptr;
  switch (tag) {
    case 'N':
      Py_RETURN_NONE;
    case 'T':
      Py_RETURN_TRUE;
    case 'F':
      Py_RETURN_FALSE;
    case 'i': {
      uint64_t v;
      if (!rd.u64(&v)) return nullptr;
      return PyLong_FromLongLong(static_cast<long long>(v));
    }
    case 'd': {
      double v;
      if (!rd.f64(&v)) return nullptr;
      return PyFloat_FromDouble(v);
    }
    case 'g': {
      uint32_t n;
      if (!rd.u32(&n) || !rd.need(n)) return nullptr;
      std::string s(reinterpret_cast<const char*>(rd.p), n);
      rd.p += n;
      return PyLong_FromString(s.c_str(), nullptr, 10);
    }
    case 's': {
      uint32_t n;
      if (!rd.u32(&n) || !rd.need(n)) return nullptr;
      const char* q = reinterpret_cast<const char*>(rd.p);
      rd.p += n;
      return PyUnicode_DecodeUTF8(q, n, "surrogatepass");
    }
    case 'b': {
      uint32_t n;
      if (!rd.u32(&n) || !rd.need(n)) return nullptr;
      const char* q = reinterpret_cast<const char*>(rd.p);
      rd.p += n;
      return PyBytes_FromStringAndSize(q, n);
    }
    case 'l': {
      uint32_t n;
      if (!rd.u32(&n)) return nullptr;
      if (n > static_cast<size_t>(rd.end - rd.p)) {  // >=1 byte per item
        PyErr_SetString(PyExc_ValueError, "truncated frame");
        return nullptr;
      }
      PyObject* lst = PyList_New(n);
      if (!lst) return nullptr;
      for (uint32_t i = 0; i < n; i++) {
        PyObject* item = DecodeValue(rd, depth + 1);
        if (!item) {
          Py_DECREF(lst);
          return nullptr;
        }
        PyList_SET_ITEM(lst, i, item);
      }
      return lst;
    }
    case 'm': {
      uint32_t n;
      if (!rd.u32(&n)) return nullptr;
      if (n > static_cast<size_t>(rd.end - rd.p)) {
        PyErr_SetString(PyExc_ValueError, "truncated frame");
        return nullptr;
      }
      PyObject* d = PyDict_New();
      if (!d) return nullptr;
      for (uint32_t i = 0; i < n; i++) {
        PyObject* key = DecodeValue(rd, depth + 1);
        if (!key) {
          Py_DECREF(d);
          return nullptr;
        }
        PyObject* value = DecodeValue(rd, depth + 1);
        if (!value) {
          Py_DECREF(key);
          Py_DECREF(d);
          return nullptr;
        }
        const int r = PyDict_SetItem(d, key, value);
        Py_DECREF(key);
        Py_DECREF(value);
        if (r < 0) {
          Py_DECREF(d);
          return nullptr;
        }
      }
      return d;
    }
    default:
      PyErr_Format(PyExc_ValueError, "bad frame tag 0x%02x", tag);
      return nullptr;
  }
}

// GetAttr + encode, dropping the temporary.
bool EncodeAttrOf(Buf& b, PyObject* obj, PyObject* name) {
  PyObject* v = PyObject_GetAttr(obj, name);
  if (!v) return false;
  const bool ok = EncodeValue(b, v, 0);
  Py_DECREF(v);
  return ok;
}

// cls.__new__(cls): construct without running __init__/__post_init__ — the
// attrs crossing the queue were normalized at ingestion (see engine/ipc.py).
PyObject* NewInstance(PyObject* cls) {
  if (!PyType_Check(cls)) {
    PyErr_SetString(PyExc_TypeError, "expected a class");
    return nullptr;
  }
  PyTypeObject* t = reinterpret_cast<PyTypeObject*>(cls);
  return t->tp_new(t, kEmptyTuple, nullptr);
}

bool DecodeInto(Rd& rd, PyObject* obj, PyObject* name) {
  PyObject* v = DecodeValue(rd, 0);
  if (!v) return false;
  const int r = PyObject_SetAttr(obj, name, v);
  Py_DECREF(v);
  return r == 0;
}

// -- check-ticket frames -----------------------------------------------------
//
// ticket_pack(inputs, deadline_rel, traceparent, carry) -> bytes
// Layout: u8 version; value(deadline_rel); value(traceparent); u32 n;
// n x [request_id, principal(id, roles, attr, policy_version, scope),
//      resource(kind, id, attr, policy_version, scope), actions, jwt|None];
// value(carry).

// v2: reply per-action rows grew decision-provenance fields
// (matched_rule, rule_row_id, source) — ISSUE 20
constexpr uint8_t kFrameVersion = 2;

PyObject* PyTicketPack(PyObject*, PyObject* args) {
  PyObject *inputs, *deadline, *traceparent, *carry;
  if (!PyArg_ParseTuple(args, "OOOO", &inputs, &deadline, &traceparent, &carry))
    return nullptr;
  Buf b;
  b.s.reserve(512);
  b.u8(kFrameVersion);
  if (!EncodeValue(b, deadline, 0) || !EncodeValue(b, traceparent, 0))
    return nullptr;
  PyObject* fast = PySequence_Fast(inputs, "inputs must be a sequence");
  if (!fast) return nullptr;
  const Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
  b.u32(static_cast<uint32_t>(n));
  bool ok = true;
  for (Py_ssize_t i = 0; ok && i < n; i++) {
    PyObject* inp = PySequence_Fast_GET_ITEM(fast, i);
    ok = EncodeAttrOf(b, inp, I.request_id);
    PyObject* p = ok ? PyObject_GetAttr(inp, I.principal) : nullptr;
    if (ok && !p) ok = false;
    if (ok) {
      ok = EncodeAttrOf(b, p, I.id) && EncodeAttrOf(b, p, I.roles) &&
           EncodeAttrOf(b, p, I.attr) && EncodeAttrOf(b, p, I.policy_version) &&
           EncodeAttrOf(b, p, I.scope);
    }
    Py_XDECREF(p);
    PyObject* r = ok ? PyObject_GetAttr(inp, I.resource) : nullptr;
    if (ok && !r) ok = false;
    if (ok) {
      ok = EncodeAttrOf(b, r, I.kind) && EncodeAttrOf(b, r, I.id) &&
           EncodeAttrOf(b, r, I.attr) && EncodeAttrOf(b, r, I.policy_version) &&
           EncodeAttrOf(b, r, I.scope);
    }
    Py_XDECREF(r);
    if (ok) ok = EncodeAttrOf(b, inp, I.actions);
    if (ok) {
      PyObject* aux = PyObject_GetAttr(inp, I.aux_data);
      if (!aux) {
        ok = false;
      } else {
        if (aux == Py_None) {
          b.u8('N');
        } else {
          ok = EncodeAttrOf(b, aux, I.jwt);
        }
        Py_DECREF(aux);
      }
    }
  }
  Py_DECREF(fast);
  if (!ok) return nullptr;
  if (!EncodeValue(b, carry, 0)) return nullptr;
  return PyBytes_FromStringAndSize(b.s.data(),
                                   static_cast<Py_ssize_t>(b.s.size()));
}

// ticket_unpack(data, Principal, Resource, AuxData, CheckInput)
//   -> (deadline_rel, traceparent, [CheckInput], carry)
PyObject* PyTicketUnpack(PyObject*, PyObject* args) {
  const char* data;
  Py_ssize_t len;
  PyObject *cls_p, *cls_r, *cls_aux, *cls_inp;
  if (!PyArg_ParseTuple(args, "y#OOOO", &data, &len, &cls_p, &cls_r, &cls_aux,
                        &cls_inp))
    return nullptr;
  Rd rd{reinterpret_cast<const uint8_t*>(data),
        reinterpret_cast<const uint8_t*>(data) + len};
  uint8_t ver;
  if (!rd.u8(&ver)) return nullptr;
  if (ver != kFrameVersion) {
    PyErr_Format(PyExc_ValueError, "unknown ticket frame version %d", ver);
    return nullptr;
  }
  PyObject* deadline = DecodeValue(rd, 0);
  if (!deadline) return nullptr;
  PyObject* traceparent = DecodeValue(rd, 0);
  if (!traceparent) {
    Py_DECREF(deadline);
    return nullptr;
  }
  uint32_t n = 0;
  PyObject* lst = nullptr;
  PyObject* carry = nullptr;
  bool ok = rd.u32(&n) && n <= static_cast<size_t>(rd.end - rd.p);
  if (ok) {
    lst = PyList_New(n);
    ok = lst != nullptr;
  } else if (!PyErr_Occurred()) {
    PyErr_SetString(PyExc_ValueError, "truncated frame");
  }
  for (uint32_t i = 0; ok && i < n; i++) {
    PyObject* rid = DecodeValue(rd, 0);
    PyObject* p = rid ? NewInstance(cls_p) : nullptr;
    ok = p && DecodeInto(rd, p, I.id) && DecodeInto(rd, p, I.roles) &&
         DecodeInto(rd, p, I.attr) && DecodeInto(rd, p, I.policy_version) &&
         DecodeInto(rd, p, I.scope);
    PyObject* r = ok ? NewInstance(cls_r) : nullptr;
    ok = ok && r && DecodeInto(rd, r, I.kind) && DecodeInto(rd, r, I.id) &&
         DecodeInto(rd, r, I.attr) && DecodeInto(rd, r, I.policy_version) &&
         DecodeInto(rd, r, I.scope);
    PyObject* actions = ok ? DecodeValue(rd, 0) : nullptr;
    ok = ok && actions;
    PyObject* aux = nullptr;
    if (ok) {
      PyObject* jwt = DecodeValue(rd, 0);
      if (!jwt) {
        ok = false;
      } else if (jwt == Py_None) {
        aux = Py_None;
        Py_INCREF(aux);
        Py_DECREF(jwt);
      } else {
        aux = NewInstance(cls_aux);
        ok = aux && PyObject_SetAttr(aux, I.jwt, jwt) == 0;
        Py_DECREF(jwt);
      }
    }
    PyObject* inp = ok ? NewInstance(cls_inp) : nullptr;
    ok = ok && inp && PyObject_SetAttr(inp, I.request_id, rid) == 0 &&
         PyObject_SetAttr(inp, I.principal, p) == 0 &&
         PyObject_SetAttr(inp, I.resource, r) == 0 &&
         PyObject_SetAttr(inp, I.actions, actions) == 0 &&
         PyObject_SetAttr(inp, I.aux_data, aux) == 0;
    Py_XDECREF(rid);
    Py_XDECREF(p);
    Py_XDECREF(r);
    Py_XDECREF(actions);
    Py_XDECREF(aux);
    if (ok) {
      PyList_SET_ITEM(lst, i, inp);  // steals
    } else {
      Py_XDECREF(inp);
    }
  }
  if (ok) {
    carry = DecodeValue(rd, 0);
    ok = carry != nullptr;
  }
  if (!ok) {
    Py_DECREF(deadline);
    Py_DECREF(traceparent);
    Py_XDECREF(lst);
    return nullptr;
  }
  PyObject* out = PyTuple_Pack(4, deadline, traceparent, lst, carry);
  Py_DECREF(deadline);
  Py_DECREF(traceparent);
  Py_DECREF(lst);
  Py_DECREF(carry);
  return out;
}

// -- reply frames ------------------------------------------------------------
//
// reply_pack(outputs, spec) -> bytes
// Layout: u8 version; u32 n; n x [request_id, resource_id,
//   u32 n_actions x (action, effect, policy, scope,
//                    matched_rule, rule_row_id, source),
//   effective_derived_roles,
//   u32 n_verrs x (path, message, source),
//   u32 n_outs x (src, action, val, error),
//   effective_policies]; value(spec).

PyObject* PyReplyPack(PyObject*, PyObject* args) {
  PyObject *outputs, *spec;
  if (!PyArg_ParseTuple(args, "OO", &outputs, &spec)) return nullptr;
  Buf b;
  b.s.reserve(512);
  b.u8(kFrameVersion);
  PyObject* fast = PySequence_Fast(outputs, "outputs must be a sequence");
  if (!fast) return nullptr;
  const Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
  b.u32(static_cast<uint32_t>(n));
  bool ok = true;
  for (Py_ssize_t i = 0; ok && i < n; i++) {
    PyObject* o = PySequence_Fast_GET_ITEM(fast, i);
    ok = EncodeAttrOf(b, o, I.request_id) && EncodeAttrOf(b, o, I.resource_id);
    if (ok) {
      PyObject* acts = PyObject_GetAttr(o, I.actions);
      ok = acts && PyDict_Check(acts);
      if (!ok && acts && !PyErr_Occurred())
        PyErr_SetString(PyExc_TypeError, "actions must be a dict");
      if (ok) {
        b.u32(static_cast<uint32_t>(PyDict_GET_SIZE(acts)));
        PyObject *key, *ae;
        Py_ssize_t pos = 0;
        while (ok && PyDict_Next(acts, &pos, &key, &ae)) {
          ok = EncodeValue(b, key, 0) && EncodeAttrOf(b, ae, I.effect) &&
               EncodeAttrOf(b, ae, I.policy) && EncodeAttrOf(b, ae, I.scope) &&
               EncodeAttrOf(b, ae, I.matched_rule) &&
               EncodeAttrOf(b, ae, I.rule_row_id) &&
               EncodeAttrOf(b, ae, I.source);
        }
      }
      Py_XDECREF(acts);
    }
    if (ok) ok = EncodeAttrOf(b, o, I.effective_derived_roles);
    if (ok) {
      PyObject* verrs = PyObject_GetAttr(o, I.validation_errors);
      PyObject* vfast =
          verrs ? PySequence_Fast(verrs, "validation_errors") : nullptr;
      ok = vfast != nullptr;
      if (ok) {
        const Py_ssize_t nv = PySequence_Fast_GET_SIZE(vfast);
        b.u32(static_cast<uint32_t>(nv));
        for (Py_ssize_t j = 0; ok && j < nv; j++) {
          PyObject* ve = PySequence_Fast_GET_ITEM(vfast, j);
          ok = EncodeAttrOf(b, ve, I.path) && EncodeAttrOf(b, ve, I.message) &&
               EncodeAttrOf(b, ve, I.source);
        }
      }
      Py_XDECREF(vfast);
      Py_XDECREF(verrs);
    }
    if (ok) {
      PyObject* oents = PyObject_GetAttr(o, I.outputs);
      PyObject* ofast = oents ? PySequence_Fast(oents, "outputs") : nullptr;
      ok = ofast != nullptr;
      if (ok) {
        const Py_ssize_t no = PySequence_Fast_GET_SIZE(ofast);
        b.u32(static_cast<uint32_t>(no));
        for (Py_ssize_t j = 0; ok && j < no; j++) {
          PyObject* oe = PySequence_Fast_GET_ITEM(ofast, j);
          ok = EncodeAttrOf(b, oe, I.src) && EncodeAttrOf(b, oe, I.action) &&
               EncodeAttrOf(b, oe, I.val) && EncodeAttrOf(b, oe, I.error);
        }
      }
      Py_XDECREF(ofast);
      Py_XDECREF(oents);
    }
    if (ok) ok = EncodeAttrOf(b, o, I.effective_policies);
  }
  Py_DECREF(fast);
  if (!ok) return nullptr;
  if (!EncodeValue(b, spec, 0)) return nullptr;
  return PyBytes_FromStringAndSize(b.s.data(),
                                   static_cast<Py_ssize_t>(b.s.size()));
}

// reply_unpack(data, CheckOutput, ActionEffect, ValidationError, OutputEntry)
//   -> ([CheckOutput], spec)
PyObject* PyReplyUnpack(PyObject*, PyObject* args) {
  const char* data;
  Py_ssize_t len;
  PyObject *cls_out, *cls_ae, *cls_ve, *cls_oe;
  if (!PyArg_ParseTuple(args, "y#OOOO", &data, &len, &cls_out, &cls_ae, &cls_ve,
                        &cls_oe))
    return nullptr;
  Rd rd{reinterpret_cast<const uint8_t*>(data),
        reinterpret_cast<const uint8_t*>(data) + len};
  uint8_t ver;
  if (!rd.u8(&ver)) return nullptr;
  if (ver != kFrameVersion) {
    PyErr_Format(PyExc_ValueError, "unknown reply frame version %d", ver);
    return nullptr;
  }
  uint32_t n = 0;
  if (!rd.u32(&n)) return nullptr;
  if (n > static_cast<size_t>(rd.end - rd.p)) {
    PyErr_SetString(PyExc_ValueError, "truncated frame");
    return nullptr;
  }
  PyObject* lst = PyList_New(n);
  if (!lst) return nullptr;
  bool ok = true;
  for (uint32_t i = 0; ok && i < n; i++) {
    PyObject* o = NewInstance(cls_out);
    ok = o && DecodeInto(rd, o, I.request_id) &&
         DecodeInto(rd, o, I.resource_id);
    if (ok) {
      uint32_t na = 0;
      ok = rd.u32(&na) && na <= static_cast<size_t>(rd.end - rd.p);
      PyObject* acts = ok ? PyDict_New() : nullptr;
      ok = ok && acts;
      for (uint32_t j = 0; ok && j < na; j++) {
        PyObject* action = DecodeValue(rd, 0);
        PyObject* ae = action ? NewInstance(cls_ae) : nullptr;
        ok = ae && DecodeInto(rd, ae, I.effect) &&
             DecodeInto(rd, ae, I.policy) && DecodeInto(rd, ae, I.scope) &&
             DecodeInto(rd, ae, I.matched_rule) &&
             DecodeInto(rd, ae, I.rule_row_id) &&
             DecodeInto(rd, ae, I.source);
        ok = ok && PyDict_SetItem(acts, action, ae) == 0;
        Py_XDECREF(action);
        Py_XDECREF(ae);
      }
      ok = ok && PyObject_SetAttr(o, I.actions, acts) == 0;
      Py_XDECREF(acts);
    }
    ok = ok && DecodeInto(rd, o, I.effective_derived_roles);
    if (ok) {
      uint32_t nv = 0;
      ok = rd.u32(&nv) && nv <= static_cast<size_t>(rd.end - rd.p);
      PyObject* verrs = ok ? PyList_New(nv) : nullptr;
      ok = ok && verrs;
      for (uint32_t j = 0; ok && j < nv; j++) {
        PyObject* ve = NewInstance(cls_ve);
        ok = ve && DecodeInto(rd, ve, I.path) &&
             DecodeInto(rd, ve, I.message) && DecodeInto(rd, ve, I.source);
        if (ok) {
          PyList_SET_ITEM(verrs, j, ve);  // steals
        } else {
          Py_XDECREF(ve);
        }
      }
      ok = ok && PyObject_SetAttr(o, I.validation_errors, verrs) == 0;
      Py_XDECREF(verrs);
    }
    if (ok) {
      uint32_t no = 0;
      ok = rd.u32(&no) && no <= static_cast<size_t>(rd.end - rd.p);
      PyObject* oents = ok ? PyList_New(no) : nullptr;
      ok = ok && oents;
      for (uint32_t j = 0; ok && j < no; j++) {
        PyObject* oe = NewInstance(cls_oe);
        ok = oe && DecodeInto(rd, oe, I.src) && DecodeInto(rd, oe, I.action) &&
             DecodeInto(rd, oe, I.val) && DecodeInto(rd, oe, I.error);
        if (ok) {
          PyList_SET_ITEM(oents, j, oe);  // steals
        } else {
          Py_XDECREF(oe);
        }
      }
      ok = ok && PyObject_SetAttr(o, I.outputs, oents) == 0;
      Py_XDECREF(oents);
    }
    ok = ok && DecodeInto(rd, o, I.effective_policies);
    if (ok) {
      PyList_SET_ITEM(lst, i, o);  // steals
    } else {
      Py_XDECREF(o);
    }
  }
  if (!ok && !PyErr_Occurred())
    PyErr_SetString(PyExc_ValueError, "truncated frame");
  PyObject* spec = ok ? DecodeValue(rd, 0) : nullptr;
  if (!spec) {
    Py_DECREF(lst);
    return nullptr;
  }
  PyObject* out = PyTuple_Pack(2, lst, spec);
  Py_DECREF(lst);
  Py_DECREF(spec);
  return out;
}

// -- the gRPC listener's codec -----------------------------------------------
//
// check_request_decode / check_reply_encode (server/server.py): a
// cerbos.request.v1.CheckResourcesRequest read from its wire bytes straight
// into validated CheckInputs, and a cerbos.response.v1.CheckResourcesResponse
// written straight from the CheckOutputs, so a request builds no protobuf
// message and walks none in Python. protobuf's own parse with
// server/convert.py and server/wire_validate.py stays the DEFINITION: both
// functions DECLINE (return None, raise nothing) whatever they are not sure
// to read or write exactly as those do, and the caller then takes that route,
// which also raises what is to be raised (malformed bytes: FromString's
// DecodeError). tests/test_wire_codec.py holds the two to each other.

namespace wire {

// Value-in-Value nesting the decoder follows and the encoder writes. A level
// is three messages to protobuf (Value, Struct or ListValue, the entry),
// whose own limit is 100: what is declined here for depth it still decides.
constexpr int kMaxValueDepth = 16;

struct Ref {
  PyObject* o;
  explicit Ref(PyObject* p = nullptr) : o(p) {}
  Ref(Ref&& other) noexcept : o(other.o) { other.o = nullptr; }
  Ref(const Ref&) = delete;
  Ref& operator=(const Ref&) = delete;
  ~Ref() { Py_XDECREF(o); }
  void reset(PyObject* p) {
    Py_XDECREF(o);
    o = p;
  }
  PyObject* release() {
    PyObject* p = o;
    o = nullptr;
    return p;
  }
  explicit operator bool() const { return o != nullptr; }
};

struct Cur {
  const uint8_t* p;
  const uint8_t* end;
  bool done() const { return p >= end; }
  size_t left() const { return static_cast<size_t>(end - p); }
};

// the bytes of a string field, kept beside its str for the validation rules
struct Slice {
  const char* p = nullptr;
  size_t n = 0;
  bool operator==(const Slice& other) const {
    return n == other.n && (n == 0 || memcmp(p, other.p, n) == 0);
  }
};

enum : uint32_t { kVarint = 0, kFixed64 = 1, kLen = 2, kFixed32 = 5 };

// A varint of at most ten bytes in its shortest spelling (a padded one reads
// the same everywhere, but no encoder writes it: declined, not studied).
inline bool ReadVarint(Cur& c, uint64_t* out) {
  uint64_t v = 0;
  for (int i = 0; i < 10; i++) {
    if (c.done()) return false;
    const uint8_t b = *c.p++;
    if (i == 9 && b > 1) return false;
    v |= static_cast<uint64_t>(b & 0x7f) << (7 * i);
    if (!(b & 0x80)) {
      if (i > 0 && b == 0) return false;
      *out = v;
      return true;
    }
  }
  return false;
}

inline bool ReadTag(Cur& c, uint32_t* field, uint32_t* wt) {
  uint64_t v;
  if (!ReadVarint(c, &v) || v > UINT32_MAX || (v >> 3) == 0) return false;
  *field = static_cast<uint32_t>(v >> 3);
  *wt = static_cast<uint32_t>(v & 7);
  return true;
}

inline bool ReadLen(Cur& c, Cur* sub) {
  uint64_t n;
  if (!ReadVarint(c, &n) || n >= INT32_MAX || n > c.left()) return false;
  sub->p = c.p;
  sub->end = c.p + n;
  c.p += n;
  return true;
}

// An unknown field is skipped as protobuf skips it; a group (wire types 3
// and 4) and the two wire types that do not exist are declined.
inline bool Skip(Cur& c, uint32_t wt) {
  uint64_t v;
  Cur sub;
  switch (wt) {
    case kVarint:
      return ReadVarint(c, &v);
    case kFixed64:
      if (c.left() < 8) return false;
      c.p += 8;
      return true;
    case kLen:
      return ReadLen(c, &sub);
    case kFixed32:
      if (c.left() < 4) return false;
      c.p += 4;
      return true;
    default:
      return false;
  }
}

// A proto3 string: strict UTF-8, as FromString refuses anything else.
inline PyObject* ReadStr(Cur& c, Slice* slice = nullptr) {
  Cur s;
  if (!ReadLen(c, &s)) return nullptr;
  const char* q = reinterpret_cast<const char*>(s.p);
  if (slice) {
    slice->p = q;
    slice->n = s.left();
  }
  return PyUnicode_DecodeUTF8(q, static_cast<Py_ssize_t>(s.left()), nullptr);
}

// A singular string field: the last one met stands, as in protobuf.
inline bool ReadStrInto(Cur& c, Ref* slot, Slice* slice = nullptr) {
  PyObject* s = ReadStr(c, slice);
  if (!s) return false;
  slot->reset(s);
  return true;
}

inline bool AppendStr(Cur& c, PyObject* lst, std::vector<Slice>* slices) {
  Slice slice;
  Ref s(ReadStr(c, &slice));
  if (!s || PyList_Append(lst, s.o) < 0) return false;
  slices->push_back(slice);
  return true;
}

PyObject* DecodeValueMsg(Cur c, int depth);

// One entry of map<string, google.protobuf.Value> (Principal.attr,
// Resource.attr, Struct.fields): key 1, value 2, in either order; an entry
// of a key the map has replaces it. An entry that holds anything else is
// declined: upb keeps such an entry out of the map, among the unknown fields.
bool DecodeAttrEntry(Cur c, PyObject* dict, int depth) {
  Ref key, val;
  while (!c.done()) {
    uint32_t f, wt;
    if (!ReadTag(c, &f, &wt) || wt != kLen) return false;
    if (f == 1) {
      if (!ReadStrInto(c, &key)) return false;
    } else if (f == 2) {
      Cur sub;
      if (val || !ReadLen(c, &sub)) return false;  // a second value would MERGE
      val.reset(DecodeValueMsg(sub, depth));
      if (!val) return false;
    } else {
      return false;
    }
  }
  if (!key) {
    key.reset(PyUnicode_New(0, 0));
    if (!key) return false;
  }
  return PyDict_SetItem(dict, key.o, val ? val.o : Py_None) == 0;
}

// Struct (fields 1, the map) when `entries`, else ListValue (values 1).
PyObject* DecodeContainer(Cur c, bool entries, int depth) {
  if (depth > kMaxValueDepth) return nullptr;
  Ref out(entries ? PyDict_New() : PyList_New(0));
  if (!out) return nullptr;
  while (!c.done()) {
    uint32_t f, wt;
    if (!ReadTag(c, &f, &wt)) return nullptr;
    if (f != 1) {
      if (!Skip(c, wt)) return nullptr;
      continue;
    }
    Cur sub;
    if (wt != kLen || !ReadLen(c, &sub)) return nullptr;
    if (entries) {
      if (!DecodeAttrEntry(sub, out.o, depth)) return nullptr;
    } else {
      Ref v(DecodeValueMsg(sub, depth));
      if (!v || PyList_Append(out.o, v.o) < 0) return nullptr;
    }
  }
  return out.release();
}

// google.protobuf.Value -> what convert.value_to_py gives: no field of the
// oneof is None, a number a float. A second field of the oneof (protobuf: the
// last one stands, two of one message merge) is declined.
PyObject* DecodeValueMsg(Cur c, int depth) {
  Ref out;
  while (!c.done()) {
    uint32_t f, wt;
    if (!ReadTag(c, &f, &wt)) return nullptr;
    if (f > 6) {
      if (!Skip(c, wt)) return nullptr;
      continue;
    }
    if (out) return nullptr;
    uint64_t v;
    Cur sub;
    switch (f) {
      case 1:  // null_value
        if (wt != kVarint || !ReadVarint(c, &v)) return nullptr;
        out.reset(Py_NewRef(Py_None));
        break;
      case 2: {  // number_value
        if (wt != kFixed64 || c.left() < 8) return nullptr;
        double d;
        memcpy(&d, c.p, 8);
        c.p += 8;
        out.reset(PyFloat_FromDouble(d));
        break;
      }
      case 3:  // string_value
        if (wt != kLen) return nullptr;
        out.reset(ReadStr(c));
        break;
      case 4:  // bool_value
        if (wt != kVarint || !ReadVarint(c, &v)) return nullptr;
        out.reset(PyBool_FromLong(v != 0));
        break;
      default:  // 5 struct_value, 6 list_value
        if (wt != kLen || !ReadLen(c, &sub)) return nullptr;
        out.reset(DecodeContainer(sub, f == 5, depth + 1));
    }
    if (!out) return nullptr;
  }
  if (!out) Py_RETURN_NONE;
  return out.release();
}

// engine.v1.Principal (id 1, policy_version 2, roles 3, attr 4, scope 5) or
// engine.v1.Resource (kind 1, policy_version 2, id 3, attr 4, scope 5) as it
// was read: the strs the instance gets, and their bytes for the rules.
struct Entity {
  Ref first, version, third, scope, attr;
  Slice s_first, s_version, s_third, s_scope;
  std::vector<Slice> roles;
};

bool ParseEntity(Cur c, bool principal, Entity* e) {
  e->attr.reset(PyDict_New());
  if (!e->attr) return false;
  if (principal) {
    e->third.reset(PyList_New(0));
    if (!e->third) return false;
  }
  while (!c.done()) {
    uint32_t f, wt;
    if (!ReadTag(c, &f, &wt)) return false;
    if (f > 5) {
      if (!Skip(c, wt)) return false;
      continue;
    }
    if (wt != kLen) return false;
    bool ok;
    switch (f) {
      case 1:
        ok = ReadStrInto(c, &e->first, &e->s_first);
        break;
      case 2:
        ok = ReadStrInto(c, &e->version, &e->s_version);
        break;
      case 3:
        ok = principal ? AppendStr(c, e->third.o, &e->roles)
                       : ReadStrInto(c, &e->third, &e->s_third);
        break;
      case 4: {
        Cur sub;
        ok = ReadLen(c, &sub) && DecodeAttrEntry(sub, e->attr.o, 0);
        break;
      }
      default:
        ok = ReadStrInto(c, &e->scope, &e->s_scope);
    }
    if (!ok) return false;
  }
  return true;
}

// Built as ticket_unpack builds them: no __init__, and no normalize_attr,
// which has nothing to do on what a Value holds.
PyObject* BuildEntity(const Entity& e, PyObject* cls, bool principal, PyObject* empty) {
  Ref obj(NewInstance(cls));
  if (!obj) return nullptr;
  auto set = [&](PyObject* name, const Ref& v) {
    return PyObject_SetAttr(obj.o, name, v ? v.o : empty) == 0;
  };
  if (!set(principal ? I.id : I.kind, e.first) || !set(principal ? I.roles : I.id, e.third) ||
      !set(I.attr, e.attr) || !set(I.policy_version, e.version) || !set(I.scope, e.scope))
    return nullptr;
  return obj.release();
}

// CheckResourcesRequest.ResourceEntry: actions 1, resource 2
struct Entry {
  Ref actions;
  std::vector<Slice> action_slices;
  bool has_resource = false;
  Entity resource;
};

bool ParseEntry(Cur c, Entry* e) {
  e->actions.reset(PyList_New(0));
  if (!e->actions) return false;
  while (!c.done()) {
    uint32_t f, wt;
    if (!ReadTag(c, &f, &wt)) return false;
    if (f > 2) {
      if (!Skip(c, wt)) return false;
      continue;
    }
    if (wt != kLen) return false;
    if (f == 1) {
      if (!AppendStr(c, e->actions.o, &e->action_slices)) return false;
    } else {
      Cur sub;
      if (e->has_resource || !ReadLen(c, &sub) || !ParseEntity(sub, false, &e->resource))
        return false;
      e->has_resource = true;
    }
  }
  return true;
}

// AuxData (jwt 1) -> JWT (token 1, key_set_id 2)
bool ParseAuxData(Cur c, Ref* token, Ref* key_set) {
  bool seen = false;
  while (!c.done()) {
    uint32_t f, wt;
    if (!ReadTag(c, &f, &wt)) return false;
    if (f != 1) {
      if (!Skip(c, wt)) return false;
      continue;
    }
    Cur jwt;
    if (wt != kLen || seen || !ReadLen(c, &jwt)) return false;
    seen = true;
    while (!jwt.done()) {
      if (!ReadTag(jwt, &f, &wt)) return false;
      if (f > 2) {
        if (!Skip(jwt, wt)) return false;
        continue;
      }
      if (wt != kLen || !ReadStrInto(jwt, f == 1 ? token : key_set)) return false;
    }
  }
  return true;
}

// -- the protovalidate rules, as server/wire_validate.py words them ----------
//
// The two patterns are Python's, matched on a str: \w is every Unicode word
// character, so a value with a byte over 0x7f is the regular expression's to
// judge (declined); and `$` also matches before ONE newline that ends the
// string, so such a newline is taken off before the look.

inline bool IsWord(char ch) {
  return (ch >= '0' && ch <= '9') || (ch >= 'a' && ch <= 'z') || (ch >= 'A' && ch <= 'Z') ||
         ch == '_';
}

inline bool Ascii(Slice s) {
  for (size_t i = 0; i < s.n; i++)
    if (static_cast<unsigned char>(s.p[i]) & 0x80) return false;
  return true;
}

inline Slice BeforeLastNewline(Slice s) {
  if (s.n && s.p[s.n - 1] == '\n') s.n--;
  return s;
}

// ^[\w]*$
bool VersionOk(Slice s) {
  s = BeforeLastNewline(s);
  for (size_t i = 0; i < s.n; i++)
    if (!IsWord(s.p[i])) return false;
  return true;
}

// ^(^$|\.|[0-9a-zA-Z][\w\-]*(\.\w[\w\-]*)*)$
bool ScopeOk(Slice s) {
  s = BeforeLastNewline(s);
  if (s.n == 0 || (s.n == 1 && s.p[0] == '.')) return true;
  if (!IsWord(s.p[0]) || s.p[0] == '_') return false;
  for (size_t i = 1; i < s.n; i++) {
    const char ch = s.p[i];
    if (ch == '.') {
      if (i + 1 >= s.n || !IsWord(s.p[i + 1])) return false;
    } else if (!IsWord(ch) && ch != '-') {
      return false;
    }
  }
  return true;
}

enum class Rule { kHolds, kViolated, kDeclined };

// CheckItems looks for a duplicate pair by pair: a list longer than any the
// service admits (50 actions a resource) is the Python path's, with its set
constexpr size_t kMaxItems = 64;

// wire_validate._check_actions over a repeated string that is there
Rule CheckItems(const std::vector<Slice>& items, const std::string& field, std::string* msg) {
  if (items.empty()) {
    *msg = field + ": value is required and must contain at least one item";
    return Rule::kViolated;
  }
  for (size_t i = 0; i < items.size(); i++) {
    if (items[i].n == 0) {
      *msg = field + ": items must be non-empty strings";
      return Rule::kViolated;
    }
    for (size_t j = 0; j < i; j++) {
      if (items[j] == items[i]) {
        *msg = field + ": items must be unique";
        return Rule::kViolated;
      }
    }
  }
  return Rule::kHolds;
}

// the policy version and the scope of a principal or a resource
Rule CheckVersionScope(const Entity& e, const std::string& field, std::string* msg) {
  if (!Ascii(e.s_version)) return Rule::kDeclined;
  if (!VersionOk(e.s_version)) {
    *msg = field + ".policyVersion: must match ^[\\w]*$";
    return Rule::kViolated;
  }
  if (!Ascii(e.s_scope)) return Rule::kDeclined;
  if (!ScopeOk(e.s_scope)) {
    *msg = field + ".scope: invalid scope";
    return Rule::kViolated;
  }
  return Rule::kHolds;
}

// wire_validate.check_resources_proto: the same rules in the same order,
// the first violation in the same words.
Rule Validate(bool has_principal, const Entity& principal, const std::vector<Entry>& entries,
              std::string* msg) {
  if (!has_principal) {
    *msg = "principal: value is required";
    return Rule::kViolated;
  }
  if (principal.s_first.n == 0) {
    *msg = "principal.id: value length must be at least 1";
    return Rule::kViolated;
  }
  Rule r = CheckItems(principal.roles, "principal.roles", msg);
  if (r != Rule::kHolds) return r;
  r = CheckVersionScope(principal, "principal", msg);
  if (r != Rule::kHolds) return r;
  if (entries.empty()) {
    *msg = "resources: value is required and must contain at least one item";
    return Rule::kViolated;
  }
  for (size_t i = 0; i < entries.size(); i++) {
    const Entry& e = entries[i];
    const std::string at = "resources[" + std::to_string(i) + "]";
    r = CheckItems(e.action_slices, at + ".actions", msg);
    if (r != Rule::kHolds) return r;
    if (!e.has_resource) {
      *msg = at + ".resource: value is required";
      return Rule::kViolated;
    }
    if (e.resource.s_first.n == 0) {
      *msg = at + ".resource.kind: value length must be at least 1";
      return Rule::kViolated;
    }
    if (e.resource.s_third.n == 0) {
      *msg = at + ".resource.id: value length must be at least 1";
      return Rule::kViolated;
    }
    r = CheckVersionScope(e.resource, at + ".resource", msg);
    if (r != Rule::kHolds) return r;
  }
  return Rule::kHolds;
}

// -> (inputs, request_id, include_meta, token, key_set_id, violation, data),
// or nullptr: declined where no exception is set.
PyObject* DecodeRequest(PyObject* data, PyObject* cls_p, PyObject* cls_r, PyObject* cls_inp) {
  const uint8_t* base = reinterpret_cast<const uint8_t*>(PyBytes_AS_STRING(data));
  Cur c{base, base + PyBytes_GET_SIZE(data)};
  Ref empty(PyUnicode_New(0, 0));
  Ref request_id, token, key_set;
  if (!empty) return nullptr;
  bool include_meta = false, has_principal = false, aux_seen = false;
  Entity principal;
  std::vector<Entry> entries;
  while (!c.done()) {
    uint32_t f, wt;
    if (!ReadTag(c, &f, &wt)) return nullptr;
    if (f > 5) {
      if (!Skip(c, wt)) return nullptr;
      continue;
    }
    if (f == 2) {
      uint64_t v;
      if (wt != kVarint || !ReadVarint(c, &v)) return nullptr;
      include_meta = v != 0;
      continue;
    }
    if (wt != kLen) return nullptr;
    if (f == 1) {
      if (!ReadStrInto(c, &request_id)) return nullptr;
      continue;
    }
    Cur sub;
    if (!ReadLen(c, &sub)) return nullptr;
    if (f == 3) {
      if (has_principal || !ParseEntity(sub, true, &principal)) return nullptr;
      has_principal = true;
    } else if (f == 4) {
      entries.emplace_back();
      Entry& e = entries.back();
      if (!ParseEntry(sub, &e) || e.action_slices.size() > kMaxItems) return nullptr;
    } else {
      if (aux_seen || !ParseAuxData(sub, &token, &key_set)) return nullptr;
      aux_seen = true;
    }
  }
  if (principal.roles.size() > kMaxItems) return nullptr;

  std::string msg;
  const Rule rule = Validate(has_principal, principal, entries, &msg);
  if (rule == Rule::kDeclined) return nullptr;
  Ref violation;
  Ref inputs(PyList_New(0));
  if (!inputs) return nullptr;
  PyObject* rid = request_id ? request_id.o : empty.o;
  if (rule == Rule::kViolated) {
    violation.reset(PyUnicode_FromStringAndSize(msg.data(), static_cast<Py_ssize_t>(msg.size())));
    if (!violation) return nullptr;
  } else {
    Ref p(BuildEntity(principal, cls_p, true, empty.o));
    if (!p) return nullptr;
    for (const Entry& e : entries) {
      Ref r(BuildEntity(e.resource, cls_r, false, empty.o));
      Ref inp(r ? NewInstance(cls_inp) : nullptr);
      if (!inp || PyObject_SetAttr(inp.o, I.request_id, rid) < 0 ||
          PyObject_SetAttr(inp.o, I.principal, p.o) < 0 ||
          PyObject_SetAttr(inp.o, I.resource, r.o) < 0 ||
          PyObject_SetAttr(inp.o, I.actions, e.actions.o) < 0 ||
          PyObject_SetAttr(inp.o, I.aux_data, Py_None) < 0 ||
          PyList_Append(inputs.o, inp.o) < 0)
        return nullptr;
    }
  }
  return PyTuple_Pack(7, inputs.o, rid, include_meta ? Py_True : Py_False, token ? token.o : empty.o,
                      key_set ? key_set.o : empty.o, violation ? violation.o : Py_None, data);
}

// -- the reply's writer --------------------------------------------------------

struct Out {
  std::string s;
  void varint(uint64_t v) {
    while (v >= 0x80) {
      s.push_back(static_cast<char>(v | 0x80));
      v >>= 7;
    }
    s.push_back(static_cast<char>(v));
  }
  void tag(uint32_t field, uint32_t wt) { varint(field << 3 | wt); }
  // a length-delimited field whose length is known when its content is
  // written: one byte is kept for it, and the few that are longer than 127
  // bytes move their content up by the rest
  size_t begin(uint32_t field) {
    tag(field, kLen);
    s.push_back(0);
    return s.size();
  }
  void end(size_t start) {
    size_t n = s.size() - start;
    if (n < 0x80) {
      s[start - 1] = static_cast<char>(n);
      return;
    }
    char more[10];
    size_t k = 0;
    s[start - 1] = static_cast<char>(n | 0x80);
    for (n >>= 7; n >= 0x80; n >>= 7) more[k++] = static_cast<char>(n | 0x80);
    more[k++] = static_cast<char>(n);
    s.insert(start, more, k);
  }
  // a string field; proto3 leaves an empty singular one out (`always`: an
  // item of a repeated field, a map's key, a member of a oneof)
  bool str(uint32_t field, PyObject* u, bool always = false) {
    if (!u || !PyUnicode_Check(u)) return false;
    Py_ssize_t n;
    const char* q = PyUnicode_AsUTF8AndSize(u, &n);
    if (!q) return false;
    if (n == 0 && !always) return true;
    tag(field, kLen);
    varint(static_cast<uint64_t>(n));
    s.append(q, static_cast<size_t>(n));
    return true;
  }
  // the attribute `name` of `obj` as a string field
  bool attr(uint32_t field, PyObject* obj, PyObject* name) {
    Ref v(PyObject_GetAttr(obj, name));
    return str(field, v.o);
  }
};

inline bool IsListOrTuple(PyObject* v) { return PyList_Check(v) || PyTuple_Check(v); }

// convert.py_to_value: the content of a google.protobuf.Value. What that
// function would stringify, and a key that is no str, is declined.
bool EncodeValueMsg(Out& o, PyObject* v, int depth) {
  if (v == Py_None) {
    o.tag(1, kVarint);
    o.varint(0);
    return true;
  }
  if (PyBool_Check(v)) {
    o.tag(4, kVarint);
    o.varint(v == Py_True);
    return true;
  }
  if (PyLong_Check(v) || PyFloat_Check(v)) {
    const double d = PyFloat_Check(v) ? PyFloat_AS_DOUBLE(v) : PyLong_AsDouble(v);
    if (d == -1.0 && PyErr_Occurred()) return false;  // an int no double holds
    o.tag(2, kFixed64);
    o.s.append(reinterpret_cast<const char*>(&d), 8);
    return true;
  }
  if (PyUnicode_Check(v)) return o.str(3, v, true);
  if (depth >= kMaxValueDepth) return false;
  if (IsListOrTuple(v)) {
    const size_t lst = o.begin(6);
    const Py_ssize_t n = PySequence_Fast_GET_SIZE(v);
    for (Py_ssize_t i = 0; i < n; i++) {
      const size_t item = o.begin(1);
      if (!EncodeValueMsg(o, PySequence_Fast_GET_ITEM(v, i), depth + 1)) return false;
      o.end(item);
    }
    o.end(lst);
    return true;
  }
  if (PyDict_Check(v)) {
    if (PyDict_GET_SIZE(v) == 0) return true;  // py_to_value sets no member of the oneof
    const size_t st = o.begin(5);
    PyObject *key, *value;
    Py_ssize_t pos = 0;
    while (PyDict_Next(v, &pos, &key, &value)) {
      const size_t entry = o.begin(1);
      if (!o.str(1, key, true)) return false;
      const size_t val = o.begin(2);
      if (!EncodeValueMsg(o, value, depth + 1)) return false;
      o.end(val);
      o.end(entry);
    }
    o.end(st);
    return true;
  }
  return false;
}

inline uint32_t EffectEnum(PyObject* effect) {
  if (PyUnicode_CompareWithASCIIString(effect, "EFFECT_ALLOW") == 0) return 1;
  if (PyUnicode_CompareWithASCIIString(effect, "EFFECT_NO_MATCH") == 0) return 3;
  return 2;  // EFFECT_DENY, and whatever is no effect: _EFFECT_TO_ENUM.get's default
}

// One ResultEntry: resource 1, actions 2, validation_errors 3, meta 4, outputs 5
bool EncodeResult(Out& o, PyObject* inp, PyObject* out, bool include_meta) {
  Ref res(PyObject_GetAttr(inp, I.resource));
  if (!res) return false;
  size_t at = o.begin(1);
  if (!o.attr(1, res.o, I.id) || !o.attr(2, res.o, I.kind) ||
      !o.attr(3, res.o, I.policy_version) || !o.attr(4, res.o, I.scope))
    return false;
  o.end(at);

  Ref acts(PyObject_GetAttr(out, I.actions));
  if (!acts || !PyDict_Check(acts.o)) return false;
  PyObject *action, *ae;
  Py_ssize_t pos = 0;
  while (PyDict_Next(acts.o, &pos, &action, &ae)) {
    Ref effect(PyObject_GetAttr(ae, I.effect));
    if (!effect || !PyUnicode_Check(effect.o)) return false;
    at = o.begin(2);
    if (!o.str(1, action, true)) return false;
    o.tag(2, kVarint);
    o.varint(EffectEnum(effect.o));
    o.end(at);
  }

  Ref verrs(PyObject_GetAttr(out, I.validation_errors));
  if (!verrs || !IsListOrTuple(verrs.o)) return false;
  for (Py_ssize_t i = 0; i < PySequence_Fast_GET_SIZE(verrs.o); i++) {
    PyObject* ve = PySequence_Fast_GET_ITEM(verrs.o, i);
    Ref source(PyObject_GetAttr(ve, I.source));
    if (!source || !PyUnicode_Check(source.o)) return false;
    at = o.begin(3);
    if (!o.attr(1, ve, I.path) || !o.attr(2, ve, I.message)) return false;
    const uint32_t src = PyUnicode_CompareWithASCIIString(source.o, "SOURCE_PRINCIPAL") == 0   ? 1
                         : PyUnicode_CompareWithASCIIString(source.o, "SOURCE_RESOURCE") == 0 ? 2
                                                                                              : 0;
    if (src) {
      o.tag(3, kVarint);
      o.varint(src);
    }
    o.end(at);
  }

  if (include_meta) {
    // Meta: actions 1 (-> EffectMeta: matched_policy 1, matched_scope 2),
    // effective_derived_roles 2; there even when it holds nothing
    at = o.begin(4);
    pos = 0;
    while (PyDict_Next(acts.o, &pos, &action, &ae)) {
      const size_t entry = o.begin(1);
      if (!o.str(1, action, true)) return false;
      const size_t em = o.begin(2);
      if (!o.attr(1, ae, I.policy) || !o.attr(2, ae, I.scope)) return false;
      o.end(em);
      o.end(entry);
    }
    Ref roles(PyObject_GetAttr(out, I.effective_derived_roles));
    if (!roles || !IsListOrTuple(roles.o)) return false;
    for (Py_ssize_t i = 0; i < PySequence_Fast_GET_SIZE(roles.o); i++)
      if (!o.str(2, PySequence_Fast_GET_ITEM(roles.o, i), true)) return false;
    o.end(at);
  }

  Ref outs(PyObject_GetAttr(out, I.outputs));
  if (!outs || !IsListOrTuple(outs.o)) return false;
  for (Py_ssize_t i = 0; i < PySequence_Fast_GET_SIZE(outs.o); i++) {
    // engine.v1.OutputEntry: src 1, val 2 (where there is no error), action 3, error 4
    PyObject* oe = PySequence_Fast_GET_ITEM(outs.o, i);
    Ref error(PyObject_GetAttr(oe, I.error));
    if (!error || !PyUnicode_Check(error.o)) return false;
    at = o.begin(5);
    if (!o.attr(1, oe, I.src)) return false;
    if (PyUnicode_GET_LENGTH(error.o) == 0) {
      Ref val(PyObject_GetAttr(oe, I.val));
      const size_t v = o.begin(2);
      if (!val || !EncodeValueMsg(o, val.o, 0)) return false;
      o.end(v);
    }
    if (!o.attr(3, oe, I.action) || !o.str(4, error.o)) return false;
    o.end(at);
  }
  return true;
}

}  // namespace wire

// check_request_decode(data, Principal, Resource, CheckInput)
//   -> (inputs, request_id, include_meta, token, key_set_id, violation, data)
//      | None (declined: FromString + convert + wire_validate answer)
PyObject* PyCheckRequestDecode(PyObject*, PyObject* args) {
  PyObject *data, *cls_p, *cls_r, *cls_inp;
  if (!PyArg_ParseTuple(args, "SOOO", &data, &cls_p, &cls_r, &cls_inp)) return nullptr;
  PyObject* out = wire::DecodeRequest(data, cls_p, cls_r, cls_inp);
  if (out) return out;
  // bytes that are no UTF-8, a class that is none: whatever went wrong, the
  // Python path reads the same bytes and raises what is to be raised
  PyErr_Clear();
  Py_RETURN_NONE;
}

// check_reply_encode(request_id, call_id, inputs, outputs, include_meta)
//   -> bytes | None (declined: convert.outputs_to_check_resources_response answers)
// CheckResourcesResponse: request_id 1, results 2, cerbos_call_id 3
PyObject* PyCheckReplyEncode(PyObject*, PyObject* args) {
  PyObject *request_id, *call_id, *inputs, *outputs;
  int include_meta;
  if (!PyArg_ParseTuple(args, "OOOOp", &request_id, &call_id, &inputs, &outputs, &include_meta))
    return nullptr;
  bool ok = wire::IsListOrTuple(inputs) && wire::IsListOrTuple(outputs);
  wire::Out o;
  o.s.reserve(4096);
  ok = ok && o.str(1, request_id);
  if (ok) {
    Py_ssize_t n = PySequence_Fast_GET_SIZE(inputs);
    if (PySequence_Fast_GET_SIZE(outputs) < n) n = PySequence_Fast_GET_SIZE(outputs);
    for (Py_ssize_t i = 0; ok && i < n; i++) {
      const size_t at = o.begin(2);
      ok = wire::EncodeResult(o, PySequence_Fast_GET_ITEM(inputs, i),
                              PySequence_Fast_GET_ITEM(outputs, i), include_meta != 0);
      o.end(at);
    }
  }
  ok = ok && o.str(3, call_id);
  if (!ok) {
    PyErr_Clear();
    Py_RETURN_NONE;
  }
  return PyBytes_FromStringAndSize(o.s.data(), static_cast<Py_ssize_t>(o.s.size()));
}

// -- shared-memory byte ring -------------------------------------------------
//
// One ring per direction per front end, over a file-backed shared mmap. The
// producer and consumer live in different processes; within a process the
// GIL serializes callers (push/pop never release it), so no extra lock is
// needed — "MPSC" on the front end is N request threads serialized by the
// GIL into the single producer role. head/tail are monotonic byte counters
// (used = head - tail); records are contiguous, with a 0xFFFFFFFF skip
// marker when a record would straddle the wrap point. Wakeups are futexes
// on two sequence words (data for the consumer, space for a full producer),
// guarded by waiter counts so the uncontended path makes no syscall.

constexpr uint32_t kRingMagic = 0x63724E31;  // "1Nrc"
constexpr size_t kRingHdrBytes = 256;
constexpr uint32_t kWrapMarker = 0xFFFFFFFFu;
constexpr size_t kRecHdrBytes = 16;  // u32 len, u32 mtype, u64 req_id

struct RingHdr {
  uint32_t magic;
  uint32_t flags;
  uint64_t capacity;
  char pad0[48];
  std::atomic<uint64_t> head;
  char pad1[56];
  std::atomic<uint64_t> tail;
  char pad2[56];
  std::atomic<uint32_t> data_seq;
  std::atomic<uint32_t> data_waiters;
  std::atomic<uint32_t> space_seq;
  std::atomic<uint32_t> space_waiters;
  std::atomic<uint64_t> pushed;
  std::atomic<uint64_t> popped;
  std::atomic<uint64_t> full_events;
  char pad3[24];
};
static_assert(sizeof(RingHdr) == kRingHdrBytes, "ring header layout");

#if defined(__linux__)
void FutexWait(std::atomic<uint32_t>* addr, uint32_t expected, int timeout_ms) {
  timespec ts;
  ts.tv_sec = timeout_ms / 1000;
  ts.tv_nsec = static_cast<long>(timeout_ms % 1000) * 1000000L;
  // non-PRIVATE: the ring is shared across processes
  syscall(SYS_futex, reinterpret_cast<uint32_t*>(addr), FUTEX_WAIT, expected,
          timeout_ms >= 0 ? &ts : nullptr, nullptr, 0);
}
void FutexWakeAll(std::atomic<uint32_t>* addr) {
  syscall(SYS_futex, reinterpret_cast<uint32_t*>(addr), FUTEX_WAKE, INT_MAX,
          nullptr, nullptr, 0);
}
#else
void FutexWait(std::atomic<uint32_t>* addr, uint32_t expected, int timeout_ms) {
  // portable fallback: bounded sleep-poll
  const int step_us = 200;
  int waited_us = 0;
  while (addr->load(std::memory_order_acquire) == expected &&
         (timeout_ms < 0 || waited_us < timeout_ms * 1000)) {
    struct timespec ts = {0, step_us * 1000L};
    nanosleep(&ts, nullptr);
    waited_us += step_us;
  }
}
void FutexWakeAll(std::atomic<uint32_t>*) {}
#endif

RingHdr* RingFromBuffer(Py_buffer* view, bool init) {
  if (static_cast<size_t>(view->len) < kRingHdrBytes + 64) {
    PyErr_SetString(PyExc_ValueError, "ring buffer too small");
    return nullptr;
  }
  RingHdr* h = static_cast<RingHdr*>(view->buf);
  if (!init && (h->magic != kRingMagic ||
                h->capacity != static_cast<uint64_t>(view->len) - kRingHdrBytes)) {
    PyErr_SetString(PyExc_ValueError, "not an initialized ring buffer");
    return nullptr;
  }
  return h;
}

PyObject* PyRingInit(PyObject*, PyObject* args) {
  Py_buffer view;
  if (!PyArg_ParseTuple(args, "w*", &view)) return nullptr;
  RingHdr* h = RingFromBuffer(&view, true);
  if (!h) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  memset(view.buf, 0, kRingHdrBytes);
  h->capacity = static_cast<uint64_t>(view.len) - kRingHdrBytes;
  h->magic = kRingMagic;
  PyBuffer_Release(&view);
  Py_RETURN_NONE;
}

PyObject* PyRingPush(PyObject*, PyObject* args) {
  Py_buffer view;
  unsigned int mtype;
  unsigned long long req_id;
  const char* payload;
  Py_ssize_t plen;
  if (!PyArg_ParseTuple(args, "w*IKy#", &view, &mtype, &req_id, &payload,
                        &plen))
    return nullptr;
  RingHdr* h = RingFromBuffer(&view, false);
  if (!h) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  char* data = static_cast<char*>(view.buf) + kRingHdrBytes;
  const uint64_t cap = h->capacity;
  const size_t need =
      kRecHdrBytes + ((static_cast<size_t>(plen) + 7) & ~static_cast<size_t>(7));
  if (need + kRecHdrBytes >= cap) {
    PyBuffer_Release(&view);
    PyErr_Format(PyExc_ValueError, "frame (%zd bytes) larger than ring", plen);
    return nullptr;
  }
  uint64_t head = h->head.load(std::memory_order_relaxed);
  const uint64_t tail = h->tail.load(std::memory_order_acquire);
  uint64_t pos = head % cap;
  const uint64_t contig = cap - pos;
  const uint64_t skip = contig < need ? contig : 0;
  if ((head - tail) + skip + need > cap) {
    h->full_events.fetch_add(1, std::memory_order_relaxed);
    PyBuffer_Release(&view);
    Py_RETURN_FALSE;
  }
  if (skip) {
    if (contig >= 4)
      memcpy(data + pos, &kWrapMarker, 4);  // consumer skips to the wrap
    head += skip;
    pos = 0;
  }
  const uint32_t len32 = static_cast<uint32_t>(plen);
  const uint32_t mtype32 = static_cast<uint32_t>(mtype);
  const uint64_t rid = static_cast<uint64_t>(req_id);
  memcpy(data + pos, &len32, 4);
  memcpy(data + pos + 4, &mtype32, 4);
  memcpy(data + pos + 8, &rid, 8);
  if (plen) memcpy(data + pos + kRecHdrBytes, payload, plen);
  h->head.store(head + need, std::memory_order_release);
  h->pushed.fetch_add(1, std::memory_order_relaxed);
  h->data_seq.fetch_add(1, std::memory_order_release);
  if (h->data_waiters.load(std::memory_order_acquire))
    FutexWakeAll(&h->data_seq);
  PyBuffer_Release(&view);
  Py_RETURN_TRUE;
}

PyObject* PyRingPop(PyObject*, PyObject* args) {
  Py_buffer view;
  if (!PyArg_ParseTuple(args, "w*", &view)) return nullptr;
  RingHdr* h = RingFromBuffer(&view, false);
  if (!h) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  const char* data = static_cast<const char*>(view.buf) + kRingHdrBytes;
  const uint64_t cap = h->capacity;
  uint64_t tail = h->tail.load(std::memory_order_relaxed);
  uint32_t len = 0;
  uint64_t pos = 0;
  for (;;) {
    const uint64_t head = h->head.load(std::memory_order_acquire);
    if (head == tail) {
      PyBuffer_Release(&view);
      Py_RETURN_NONE;
    }
    pos = tail % cap;
    const uint64_t contig = cap - pos;
    if (contig < 4) {  // producer couldn't even fit a wrap marker
      tail += contig;
      h->tail.store(tail, std::memory_order_release);
      continue;
    }
    memcpy(&len, data + pos, 4);
    if (len == kWrapMarker) {
      tail += contig;
      h->tail.store(tail, std::memory_order_release);
      continue;
    }
    break;
  }
  const size_t need =
      kRecHdrBytes + ((static_cast<size_t>(len) + 7) & ~static_cast<size_t>(7));
  const uint64_t head = h->head.load(std::memory_order_acquire);
  if (need > cap || tail + need > head || cap - pos < need) {
    PyBuffer_Release(&view);
    PyErr_SetString(PyExc_ValueError, "corrupt ring record");
    return nullptr;
  }
  uint32_t mtype;
  uint64_t req_id;
  memcpy(&mtype, data + pos + 4, 4);
  memcpy(&req_id, data + pos + 8, 8);
  PyObject* payload = PyBytes_FromStringAndSize(data + pos + kRecHdrBytes, len);
  if (!payload) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  h->tail.store(tail + need, std::memory_order_release);
  h->popped.fetch_add(1, std::memory_order_relaxed);
  h->space_seq.fetch_add(1, std::memory_order_release);
  if (h->space_waiters.load(std::memory_order_acquire))
    FutexWakeAll(&h->space_seq);
  PyBuffer_Release(&view);
  PyObject* out = Py_BuildValue("(IKN)", mtype, (unsigned long long)req_id,
                                payload);  // N steals payload
  return out;
}

PyObject* PyRingSeq(PyObject*, PyObject* args) {
  Py_buffer view;
  int which;
  if (!PyArg_ParseTuple(args, "w*i", &view, &which)) return nullptr;
  RingHdr* h = RingFromBuffer(&view, false);
  if (!h) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  const uint32_t seq = (which ? h->space_seq : h->data_seq)
                           .load(std::memory_order_acquire);
  PyBuffer_Release(&view);
  return PyLong_FromUnsignedLong(seq);
}

// ring_wait(buf, which, expected_seq, timeout_ms) -> current seq. Blocks
// (GIL released) until the chosen sequence word moves past expected_seq or
// the timeout lapses. Callers capture the seq BEFORE their emptiness check:
// a push landing in between changes the word and the wait returns at once.
PyObject* PyRingWait(PyObject*, PyObject* args) {
  Py_buffer view;
  int which, timeout_ms;
  unsigned int expected;
  if (!PyArg_ParseTuple(args, "w*iIi", &view, &which, &expected, &timeout_ms))
    return nullptr;
  RingHdr* h = RingFromBuffer(&view, false);
  if (!h) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  std::atomic<uint32_t>* seq = which ? &h->space_seq : &h->data_seq;
  std::atomic<uint32_t>* waiters = which ? &h->space_waiters : &h->data_waiters;
  uint32_t cur = seq->load(std::memory_order_acquire);
  if (cur == expected) {
    waiters->fetch_add(1, std::memory_order_acq_rel);
    Py_BEGIN_ALLOW_THREADS
    FutexWait(seq, expected, timeout_ms);
    Py_END_ALLOW_THREADS
    waiters->fetch_sub(1, std::memory_order_acq_rel);
    cur = seq->load(std::memory_order_acquire);
  }
  PyBuffer_Release(&view);
  return PyLong_FromUnsignedLong(cur);
}

// ring_wake(buf, which) — shutdown aid: bump the sequence word and wake all
// waiters so a blocked consumer/producer re-checks its stop flag.
PyObject* PyRingWake(PyObject*, PyObject* args) {
  Py_buffer view;
  int which;
  if (!PyArg_ParseTuple(args, "w*i", &view, &which)) return nullptr;
  RingHdr* h = RingFromBuffer(&view, false);
  if (!h) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  std::atomic<uint32_t>* seq = which ? &h->space_seq : &h->data_seq;
  seq->fetch_add(1, std::memory_order_release);
  FutexWakeAll(seq);
  PyBuffer_Release(&view);
  Py_RETURN_NONE;
}

PyObject* PyRingStats(PyObject*, PyObject* args) {
  Py_buffer view;
  if (!PyArg_ParseTuple(args, "w*", &view)) return nullptr;
  RingHdr* h = RingFromBuffer(&view, false);
  if (!h) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  const uint64_t head = h->head.load(std::memory_order_acquire);
  const uint64_t tail = h->tail.load(std::memory_order_acquire);
  PyObject* out = Py_BuildValue(
      "(KKKKK)", (unsigned long long)(head - tail),
      (unsigned long long)h->capacity,
      (unsigned long long)h->pushed.load(std::memory_order_relaxed),
      (unsigned long long)h->popped.load(std::memory_order_relaxed),
      (unsigned long long)h->full_events.load(std::memory_order_relaxed));
  PyBuffer_Release(&view);
  return out;
}

// -- JSON (CheckResources hot path) ------------------------------------------
//
// A stdlib-compatible subset: json_loads matches json.loads on the request
// grammar (objects/arrays/strings with full escape handling, int vs float
// number semantics, NaN/Infinity constants, strict control-char rejection);
// json_dumps matches json.dumps defaults (ensure_ascii, ", "/": "
// separators, repr floats). Anything either side can't express raises, and
// cerbos_tpu/fastjson.py falls back to the stdlib.

void AppendUtf8(std::string& s, uint32_t c) {
  if (c < 0x80) {
    s.push_back(static_cast<char>(c));
  } else if (c < 0x800) {
    s.push_back(static_cast<char>(0xC0 | (c >> 6)));
    s.push_back(static_cast<char>(0x80 | (c & 0x3F)));
  } else if (c < 0x10000) {
    s.push_back(static_cast<char>(0xE0 | (c >> 12)));
    s.push_back(static_cast<char>(0x80 | ((c >> 6) & 0x3F)));
    s.push_back(static_cast<char>(0x80 | (c & 0x3F)));
  } else {
    s.push_back(static_cast<char>(0xF0 | (c >> 18)));
    s.push_back(static_cast<char>(0x80 | ((c >> 12) & 0x3F)));
    s.push_back(static_cast<char>(0x80 | ((c >> 6) & 0x3F)));
    s.push_back(static_cast<char>(0x80 | (c & 0x3F)));
  }
}

struct JParse {
  const char* p;
  const char* end;
  const char* start;

  void Err(const char* msg) {
    PyErr_Format(PyExc_ValueError, "%s: char %zd", msg,
                 static_cast<Py_ssize_t>(p - start));
  }
  void Ws() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
      p++;
  }
  bool Lit(const char* lit, size_t n) {
    if (static_cast<size_t>(end - p) < n || memcmp(p, lit, n) != 0) {
      Err("invalid JSON literal");
      return false;
    }
    p += n;
    return true;
  }

  PyObject* String() {
    p++;  // opening quote
    std::string out;
    const char* run = p;
    while (p < end) {
      const unsigned char c = static_cast<unsigned char>(*p);
      if (c == '"') {
        out.append(run, p - run);
        p++;
        return PyUnicode_DecodeUTF8(out.data(),
                                    static_cast<Py_ssize_t>(out.size()),
                                    "surrogatepass");
      }
      if (c < 0x20) {
        Err("invalid control character in string");
        return nullptr;
      }
      if (c != '\\') {
        p++;
        continue;
      }
      out.append(run, p - run);
      p++;
      if (p >= end) {
        Err("unterminated string escape");
        return nullptr;
      }
      const char e = *p++;
      switch (e) {
        case '"': out.push_back('"'); break;
        case '\\': out.push_back('\\'); break;
        case '/': out.push_back('/'); break;
        case 'b': out.push_back('\b'); break;
        case 'f': out.push_back('\f'); break;
        case 'n': out.push_back('\n'); break;
        case 'r': out.push_back('\r'); break;
        case 't': out.push_back('\t'); break;
        case 'u': {
          uint32_t cp;
          if (!Hex4(&cp)) return nullptr;
          if (cp >= 0xD800 && cp <= 0xDBFF && end - p >= 6 && p[0] == '\\' &&
              p[1] == 'u') {
            const char* save = p;
            p += 2;
            uint32_t lo;
            if (!Hex4(&lo)) return nullptr;
            if (lo >= 0xDC00 && lo <= 0xDFFF) {
              cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
            } else {
              p = save;  // not a low surrogate: emit the lone high one
            }
          }
          AppendUtf8(out, cp);  // lone surrogates pass through surrogatepass
          break;
        }
        default:
          p--;
          Err("invalid string escape");
          return nullptr;
      }
      run = p;
    }
    Err("unterminated string");
    return nullptr;
  }

  bool Hex4(uint32_t* out) {
    if (end - p < 4) {
      Err("truncated \\u escape");
      return false;
    }
    uint32_t v = 0;
    for (int i = 0; i < 4; i++) {
      const char c = p[i];
      v <<= 4;
      if (c >= '0' && c <= '9') v |= c - '0';
      else if (c >= 'a' && c <= 'f') v |= c - 'a' + 10;
      else if (c >= 'A' && c <= 'F') v |= c - 'A' + 10;
      else {
        Err("invalid \\u escape");
        return false;
      }
    }
    p += 4;
    *out = v;
    return true;
  }

  PyObject* Number() {
    const char* tok = p;
    bool is_float = false;
    if (p < end && *p == '-') p++;
    if (p < end && *p == '0') {
      p++;
    } else if (p < end && *p >= '1' && *p <= '9') {
      while (p < end && *p >= '0' && *p <= '9') p++;
    } else {
      Err("invalid number");
      return nullptr;
    }
    if (p < end && *p == '.') {
      is_float = true;
      p++;
      if (p >= end || *p < '0' || *p > '9') {
        Err("invalid number");
        return nullptr;
      }
      while (p < end && *p >= '0' && *p <= '9') p++;
    }
    if (p < end && (*p == 'e' || *p == 'E')) {
      is_float = true;
      p++;
      if (p < end && (*p == '+' || *p == '-')) p++;
      if (p >= end || *p < '0' || *p > '9') {
        Err("invalid number");
        return nullptr;
      }
      while (p < end && *p >= '0' && *p <= '9') p++;
    }
    std::string s(tok, p - tok);
    if (is_float) {
      const double d = PyOS_string_to_double(s.c_str(), nullptr, nullptr);
      if (d == -1.0 && PyErr_Occurred()) return nullptr;
      return PyFloat_FromDouble(d);
    }
    return PyLong_FromString(s.c_str(), nullptr, 10);
  }

  PyObject* Value(int depth) {
    if (depth > 500) {
      PyErr_SetString(PyExc_ValueError, "JSON nesting too deep");
      return nullptr;
    }
    Ws();
    if (p >= end) {
      Err("unexpected end of JSON");
      return nullptr;
    }
    switch (*p) {
      case '{': {
        p++;
        PyObject* d = PyDict_New();
        if (!d) return nullptr;
        Ws();
        if (p < end && *p == '}') {
          p++;
          return d;
        }
        for (;;) {
          Ws();
          if (p >= end || *p != '"') {
            Err("expecting property name in double quotes");
            Py_DECREF(d);
            return nullptr;
          }
          PyObject* k = String();
          if (!k) {
            Py_DECREF(d);
            return nullptr;
          }
          Ws();
          if (p >= end || *p != ':') {
            Err("expecting ':' delimiter");
            Py_DECREF(k);
            Py_DECREF(d);
            return nullptr;
          }
          p++;
          PyObject* v = Value(depth + 1);
          if (!v) {
            Py_DECREF(k);
            Py_DECREF(d);
            return nullptr;
          }
          const int r = PyDict_SetItem(d, k, v);
          Py_DECREF(k);
          Py_DECREF(v);
          if (r < 0) {
            Py_DECREF(d);
            return nullptr;
          }
          Ws();
          if (p < end && *p == ',') {
            p++;
            continue;
          }
          if (p < end && *p == '}') {
            p++;
            return d;
          }
          Err("expecting ',' delimiter");
          Py_DECREF(d);
          return nullptr;
        }
      }
      case '[': {
        p++;
        PyObject* lst = PyList_New(0);
        if (!lst) return nullptr;
        Ws();
        if (p < end && *p == ']') {
          p++;
          return lst;
        }
        for (;;) {
          PyObject* v = Value(depth + 1);
          if (!v) {
            Py_DECREF(lst);
            return nullptr;
          }
          const int r = PyList_Append(lst, v);
          Py_DECREF(v);
          if (r < 0) {
            Py_DECREF(lst);
            return nullptr;
          }
          Ws();
          if (p < end && *p == ',') {
            p++;
            continue;
          }
          if (p < end && *p == ']') {
            p++;
            return lst;
          }
          Err("expecting ',' delimiter");
          Py_DECREF(lst);
          return nullptr;
        }
      }
      case '"':
        return String();
      case 't':
        if (!Lit("true", 4)) return nullptr;
        Py_RETURN_TRUE;
      case 'f':
        if (!Lit("false", 5)) return nullptr;
        Py_RETURN_FALSE;
      case 'n':
        if (!Lit("null", 4)) return nullptr;
        Py_RETURN_NONE;
      case 'N':
        if (!Lit("NaN", 3)) return nullptr;
        return PyFloat_FromDouble(Py_NAN);
      case 'I':
        if (!Lit("Infinity", 8)) return nullptr;
        return PyFloat_FromDouble(Py_HUGE_VAL);
      case '-':
        if (end - p >= 2 && p[1] == 'I') {
          if (!Lit("-Infinity", 9)) return nullptr;
          return PyFloat_FromDouble(-Py_HUGE_VAL);
        }
        return Number();
      default:
        if (*p >= '0' && *p <= '9') return Number();
        Err("expecting value");
        return nullptr;
    }
  }
};

PyObject* PyJsonLoads(PyObject*, PyObject* args) {
  Py_buffer view;
  if (!PyArg_ParseTuple(args, "s*", &view)) return nullptr;
  JParse jp;
  jp.start = jp.p = static_cast<const char*>(view.buf);
  jp.end = jp.p + view.len;
  PyObject* out = jp.Value(0);
  if (out) {
    jp.Ws();
    if (jp.p != jp.end) {
      jp.Err("extra data");
      Py_CLEAR(out);
    }
  }
  PyBuffer_Release(&view);
  return out;
}

bool JsonDumpValue(std::string& out, PyObject* v, int depth) {
  if (depth > 500) {
    PyErr_SetString(PyExc_ValueError, "JSON nesting too deep (circular?)");
    return false;
  }
  if (v == Py_None) {
    out += "null";
    return true;
  }
  if (v == Py_True) {
    out += "true";
    return true;
  }
  if (v == Py_False) {
    out += "false";
    return true;
  }
  if (PyLong_Check(v)) {
    PyObject* s = PyObject_Str(v);
    if (!s) return false;
    Py_ssize_t n;
    const char* u = PyUnicode_AsUTF8AndSize(s, &n);
    if (!u) {
      Py_DECREF(s);
      return false;
    }
    out.append(u, n);
    Py_DECREF(s);
    return true;
  }
  if (PyFloat_Check(v)) {
    const double d = PyFloat_AS_DOUBLE(v);
    if (d != d) {
      out += "NaN";
    } else if (d == Py_HUGE_VAL) {
      out += "Infinity";
    } else if (d == -Py_HUGE_VAL) {
      out += "-Infinity";
    } else {
      char* s = PyOS_double_to_string(d, 'r', 0, Py_DTSF_ADD_DOT_0, nullptr);
      if (!s) return false;
      out += s;
      PyMem_Free(s);
    }
    return true;
  }
  if (PyUnicode_Check(v)) {
    if (PyUnicode_READY(v) < 0) return false;
    const int kind = PyUnicode_KIND(v);
    const void* data = PyUnicode_DATA(v);
    const Py_ssize_t n = PyUnicode_GET_LENGTH(v);
    out.push_back('"');
    char esc[16];
    for (Py_ssize_t i = 0; i < n; i++) {
      const Py_UCS4 c = PyUnicode_READ(kind, data, i);
      switch (c) {
        case '"': out += "\\\""; break;
        case '\\': out += "\\\\"; break;
        case '\b': out += "\\b"; break;
        case '\f': out += "\\f"; break;
        case '\n': out += "\\n"; break;
        case '\r': out += "\\r"; break;
        case '\t': out += "\\t"; break;
        default:
          if (c < 0x20 || c > 0x7E) {  // ensure_ascii
            if (c > 0xFFFF) {
              const Py_UCS4 x = c - 0x10000;
              snprintf(esc, sizeof esc, "\\u%04x\\u%04x",
                       0xD800 + (x >> 10), 0xDC00 + (x & 0x3FF));
            } else {
              snprintf(esc, sizeof esc, "\\u%04x", c);
            }
            out += esc;
          } else {
            out.push_back(static_cast<char>(c));
          }
      }
    }
    out.push_back('"');
    return true;
  }
  if (PyList_Check(v) || PyTuple_Check(v)) {
    PyObject* fast = PySequence_Fast(v, "sequence");
    if (!fast) return false;
    const Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    out.push_back('[');
    for (Py_ssize_t i = 0; i < n; i++) {
      if (i) out += ", ";
      if (!JsonDumpValue(out, PySequence_Fast_GET_ITEM(fast, i), depth + 1)) {
        Py_DECREF(fast);
        return false;
      }
    }
    Py_DECREF(fast);
    out.push_back(']');
    return true;
  }
  if (PyDict_Check(v)) {
    out.push_back('{');
    PyObject *key, *value;
    Py_ssize_t pos = 0;
    bool first = true;
    while (PyDict_Next(v, &pos, &key, &value)) {
      if (!PyUnicode_Check(key)) {
        // non-str keys (int/bool/None coercion): stdlib fallback handles it
        PyErr_SetString(PyExc_TypeError, "JSON object keys must be str");
        return false;
      }
      if (!first) out += ", ";
      first = false;
      if (!JsonDumpValue(out, key, depth + 1)) return false;
      out += ": ";
      if (!JsonDumpValue(out, value, depth + 1)) return false;
    }
    out.push_back('}');
    return true;
  }
  PyErr_Format(PyExc_TypeError, "Object of type %s is not JSON serializable",
               Py_TYPE(v)->tp_name);
  return false;
}

PyObject* PyJsonDumps(PyObject*, PyObject* args) {
  PyObject* v;
  if (!PyArg_ParseTuple(args, "O", &v)) return nullptr;
  std::string out;
  out.reserve(256);
  if (!JsonDumpValue(out, v, 0)) return nullptr;
  return PyBytes_FromStringAndSize(out.data(),
                                   static_cast<Py_ssize_t>(out.size()));
}

PyMethodDef kMethods[] = {
    {"glob_match", PyGlobMatch, METH_VARARGS,
     "glob_match(pattern, value) -> bool — gobwas-style glob with ':' separator"},
    {"glob_match_many", PyGlobMatchMany, METH_VARARGS,
     "glob_match_many(patterns, value) -> list[int] of matching indices"},
    {"encode_double_keys", PyEncodeDoubleKeys, METH_VARARGS,
     "encode_double_keys(f64 buffer) -> (hi_i32_bytes, lo_i32_bytes, nan_u8_bytes)"},
    {"encode_column", PyEncodeColumn, METH_VARARGS,
     "encode_column(values, interner, missing, err, tags, hi, lo, sid, nan)"},
    {"encode_attr_column", PyEncodeAttrColumn, METH_VARARGS,
     "encode_attr_column(inputs, mode, root, leaf, interner, missing, err, "
     "tags, hi, lo, sid, nan) — fused gather + encode"},
    {"encode_attr_columns_multi", PyEncodeAttrColumnsMulti, METH_VARARGS,
     "encode_attr_columns_multi(inputs, specs, interner, missing, err, "
     "tags[P,n], hi, lo, sid, nan) — all fused columns in one batch pass"},
    {"encode_list_column", PyEncodeListColumn, METH_VARARGS,
     "encode_list_column(inputs, mode, root, leaf, interner, missing, state) "
     "-> (width, sids_bytes) — fused gather + intern for string lists"},
    {"resolve_effects", PyResolveEffects, METH_VARARGS,
     "resolve_effects(...) — fused effect-resolution lattice over the "
     "candidate tensors (numpy-path replacement for _compute's second half)"},
    {"decode_node_pool", PyDecodeNodePool, METH_VARARGS,
     "decode_node_pool(raw_nodes, class_map, dec_value) -> list — linear "
     "decode of the bundle codec node pool without running __init__"},
    {"bitmap_sweep", PyBitmapSweep, METH_VARARGS,
     "bitmap_sweep(words_seq, sums_seq, extra|None, rows|None) -> "
     "(base_any, list) — fused two-level packed-bitmap AND sweep"},
    {"bitmap_any", PyBitmapAny, METH_VARARGS,
     "bitmap_any(words_seq, sums_seq) -> bool — packed-bitmap AND with "
     "first-hit early exit"},
    {"stack_pad_rows", PyStackPadRows, METH_VARARGS,
     "stack_pad_rows(dst, rows) — memcpy each contiguous row into its "
     "padded slot of dst and zero the tail (fused pad+stack fill)"},
    {"ticket_pack", PyTicketPack, METH_VARARGS,
     "ticket_pack(inputs, deadline_rel, traceparent, carry) -> bytes — "
     "CheckInput rows into one binary ticket frame"},
    {"ticket_unpack", PyTicketUnpack, METH_VARARGS,
     "ticket_unpack(data, Principal, Resource, AuxData, CheckInput) -> "
     "(deadline_rel, traceparent, inputs, carry)"},
    {"reply_pack", PyReplyPack, METH_VARARGS,
     "reply_pack(outputs, spec) -> bytes — CheckOutput effect rows + reply "
     "spec into one binary reply frame"},
    {"reply_unpack", PyReplyUnpack, METH_VARARGS,
     "reply_unpack(data, CheckOutput, ActionEffect, ValidationError, "
     "OutputEntry) -> (outputs, spec)"},
    {"check_request_decode", PyCheckRequestDecode, METH_VARARGS,
     "check_request_decode(data, Principal, Resource, CheckInput) -> (inputs, "
     "request_id, include_meta, token, key_set_id, violation, data) | None — a "
     "CheckResourcesRequest's wire bytes into validated CheckInputs"},
    {"check_reply_encode", PyCheckReplyEncode, METH_VARARGS,
     "check_reply_encode(request_id, call_id, inputs, outputs, include_meta) "
     "-> bytes | None — a CheckResourcesResponse's wire bytes from CheckOutputs"},
    {"ring_init", PyRingInit, METH_VARARGS,
     "ring_init(buf) — zero the header and stamp magic/capacity"},
    {"ring_push", PyRingPush, METH_VARARGS,
     "ring_push(buf, mtype, req_id, payload) -> bool — False when full"},
    {"ring_pop", PyRingPop, METH_VARARGS,
     "ring_pop(buf) -> (mtype, req_id, payload) | None"},
    {"ring_seq", PyRingSeq, METH_VARARGS,
     "ring_seq(buf, which) -> int — current data(0)/space(1) sequence word"},
    {"ring_wait", PyRingWait, METH_VARARGS,
     "ring_wait(buf, which, expected_seq, timeout_ms) -> int — futex wait "
     "until the sequence word moves; returns the current value"},
    {"ring_wake", PyRingWake, METH_VARARGS,
     "ring_wake(buf, which) — bump the sequence word and wake all waiters"},
    {"ring_stats", PyRingStats, METH_VARARGS,
     "ring_stats(buf) -> (used, capacity, pushed, popped, full_events)"},
    {"json_loads", PyJsonLoads, METH_VARARGS,
     "json_loads(bytes|str) -> obj — stdlib-compatible JSON parse"},
    {"json_dumps", PyJsonDumps, METH_VARARGS,
     "json_dumps(obj) -> bytes — stdlib-default-compatible JSON encode"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef kModule = {
    PyModuleDef_HEAD_INIT, "cerbos_native",
    "Native host-path helpers for cerbos_tpu", -1, kMethods,
    nullptr, nullptr, nullptr, nullptr,
};

}  // namespace

PyMODINIT_FUNC PyInit_cerbos_native(void) {
  if (!InitTransportStatics()) return nullptr;
  return PyModule_Create(&kModule);
}
