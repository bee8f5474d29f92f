// _gknative — C++ fast path for the host-side packing pipeline.
//
// The TPU driver's cold-path cost is JSON-dict traversal + string interning
// (gatekeeper_tpu/ops/pack.py pack_reviews, ops/columns.py extract_columns).
// Both are pure per-object loops over Python dicts; this module re-implements
// them against the CPython API, filling caller-allocated numpy buffers via
// the buffer protocol.  Semantics are pinned by differential tests against
// the Python implementations (tests/test_native.py).
//
// Interning mutates the Python Interner's own dict/list (under the GIL), so
// ids stay consistent across the C and Python paths.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <initializer_list>
#include <string>
#include <vector>

namespace {

constexpr int32_t ID_MISSING = -1;   // Interner.MISSING
constexpr int32_t ID_NON_STRING = -3;  // Interner.NON_STRING
constexpr int32_t UNDEF = -4;        // pack.py UNDEF

// tcode values (columns.py)
constexpr int8_t T_UNDEF = 0, T_NULL = 1, T_FALSE = 2, T_TRUE = 3,
                 T_NUM = 4, T_STR = 5, T_COMP = 6;

PyObject *g_np_empty = nullptr;   // numpy.empty
PyObject *g_sorted = nullptr;     // builtins.sorted
PyObject *g_str = nullptr;        // builtins.str

// ---- interner ------------------------------------------------------------

int32_t intern(PyObject *ids, PyObject *strings, PyObject *s) {
  PyObject *v = PyDict_GetItemWithError(ids, s);  // borrowed
  if (v) return (int32_t)PyLong_AsLong(v);
  if (PyErr_Occurred()) return ID_MISSING;  // unhashable: caller clears
  Py_ssize_t n = PyList_GET_SIZE(strings);
  PyObject *idobj = PyLong_FromSsize_t(n);
  if (!idobj) return ID_MISSING;
  if (PyDict_SetItem(ids, s, idobj) < 0) {
    Py_DECREF(idobj);
    return ID_MISSING;
  }
  Py_DECREF(idobj);
  if (PyList_Append(strings, s) < 0) return ID_MISSING;
  return (int32_t)n;
}

int32_t intern_value(PyObject *ids, PyObject *strings, PyObject *v) {
  if (v && PyUnicode_Check(v)) return intern(ids, strings, v);
  return ID_NON_STRING;
}

// ---- get_default semantics (target/match.py _get) ------------------------
// missing key or None -> nullptr ("missing"); non-dict container -> missing.

PyObject *get_field(PyObject *obj, const char *field) {  // borrowed or null
  if (!obj || !PyDict_Check(obj)) return nullptr;
  PyObject *v = PyDict_GetItemString(obj, field);
  if (!v || v == Py_None) return nullptr;
  return v;
}

bool is_ns_kind(PyObject *kind) {
  if (!kind || !PyDict_Check(kind)) return false;
  PyObject *g = PyDict_GetItemString(kind, "group");
  PyObject *k = PyDict_GetItemString(kind, "kind");
  if (!g || !k || !PyUnicode_Check(g) || !PyUnicode_Check(k)) return false;
  return PyUnicode_GetLength(g) == 0 &&
         PyUnicode_CompareWithASCIIString(k, "Namespace") == 0;
}

// ---- buffer helpers ------------------------------------------------------

struct Buf {
  Py_buffer view{};
  bool ok = false;
  ~Buf() {
    if (ok) PyBuffer_Release(&view);
  }
  bool acquire(PyObject *obj) {
    if (PyObject_GetBuffer(obj, &view, PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS) <
        0)
      return false;
    ok = true;
    return true;
  }
  int32_t *i32() { return static_cast<int32_t *>(view.buf); }
  int8_t *i8() { return static_cast<int8_t *>(view.buf); }
  double *f64() { return static_cast<double *>(view.buf); }
  bool *b() { return static_cast<bool *>(view.buf); }
};

// allocate a 1-D/2-D int32 numpy array via numpy.empty
PyObject *np_empty_i32(Py_ssize_t a, Py_ssize_t b = -1) {
  PyObject *shape =
      (b < 0) ? Py_BuildValue("(n)", a) : Py_BuildValue("(nn)", a, b);
  if (!shape) return nullptr;
  PyObject *arr = PyObject_CallFunction(g_np_empty, "Os", shape, "int32");
  Py_DECREF(shape);
  return arr;
}

bool fill_i32(PyObject *arr, const std::vector<int32_t> &vals) {
  Buf buf;
  if (!buf.acquire(arr)) return false;
  std::memcpy(buf.view.buf, vals.data(), vals.size() * sizeof(int32_t));
  return true;
}

// ---- label interning (pack.py _intern_labels) ----------------------------
// appends (key_id, value_id) pairs sorted by str(key)

void intern_labels(PyObject *ids, PyObject *strings, PyObject *labels,
                   std::vector<int32_t> &out) {
  if (!labels || !PyDict_Check(labels)) return;
  PyObject *keys = PyDict_Keys(labels);
  if (!keys) {
    PyErr_Clear();
    return;
  }
  bool all_str = true;
  Py_ssize_t n = PyList_GET_SIZE(keys);
  for (Py_ssize_t i = 0; i < n; i++) {
    if (!PyUnicode_Check(PyList_GET_ITEM(keys, i))) {
      all_str = false;
      break;
    }
  }
  if (all_str) {
    if (PyList_Sort(keys) < 0) PyErr_Clear();
  } else {
    // rare: mirror sorted(keys, key=str)
    PyObject *kw = PyDict_New();
    PyDict_SetItemString(kw, "key", g_str);
    PyObject *args = PyTuple_Pack(1, keys);
    PyObject *srt = PyObject_Call(g_sorted, args, kw);
    Py_DECREF(args);
    Py_DECREF(kw);
    if (srt) {
      Py_DECREF(keys);
      keys = srt;
      n = PyList_GET_SIZE(keys);
    } else {
      PyErr_Clear();
    }
  }
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *k = PyList_GET_ITEM(keys, i);
    PyObject *v = PyDict_GetItemWithError(labels, k);
    if (!v) {
      PyErr_Clear();
      continue;
    }
    out.push_back(intern_value(ids, strings, k));
    out.push_back(intern_value(ids, strings, v));
  }
  Py_DECREF(keys);
}

// ==========================================================================
// pack_reviews_core
// ==========================================================================

// cached_ns(ns) through the batch's memo: new reference, nullptr on error
PyObject *cached_lookup(PyObject *cached_ns, PyObject *ns_memo, PyObject *ns) {
  PyObject *memo = PyDict_GetItemWithError(ns_memo, ns);
  if (memo) {
    Py_INCREF(memo);
    return memo;
  }
  PyErr_Clear();
  PyObject *got = PyObject_CallFunctionObjArgs(cached_ns, ns, nullptr);
  if (!got) return nullptr;
  PyDict_SetItem(ns_memo, ns, got);
  return got;
}

// one review's fixed fields (pack.py pack_reviews)
struct ReviewRow {
  int32_t group, kind, ns_name;
  int8_t ns_mode;
  bool always, ns_empty, is_ns, obj_empty, old_empty, autoreject;
};

// fills `row` and appends the review's (key, value) label pairs to the
// three flats, interning in pack_reviews' order; false on a Python error
bool review_row(PyObject *review, PyObject *ids, PyObject *strings,
                PyObject *cached_ns, PyObject *ns_memo, ReviewRow &row,
                std::vector<int32_t> &obj_flat, std::vector<int32_t> &old_flat,
                std::vector<int32_t> &nsl_flat) {
  PyObject *rkind_raw =
      PyDict_Check(review) ? PyDict_GetItemString(review, "kind") : nullptr;
  PyObject *rkind =
      (rkind_raw && PyDict_Check(rkind_raw)) ? rkind_raw : nullptr;
  // NOTE pack.py uses rkind.get("group", _MISSING) — plain get, null is
  // a value here (intern_value -> NON_STRING), matching the original
  PyObject *g = rkind ? PyDict_GetItemString(rkind, "group") : nullptr;
  PyObject *k = rkind ? PyDict_GetItemString(rkind, "kind") : nullptr;
  row.group = g ? intern_value(ids, strings, g) : UNDEF;
  row.kind = k ? intern_value(ids, strings, k) : UNDEF;

  bool isns = is_ns_kind(rkind_raw);
  row.is_ns = isns;

  PyObject *ns = get_field(review, "namespace");  // _get default ""
  bool nsempty = !ns || (PyUnicode_Check(ns) && PyUnicode_GetLength(ns) == 0);
  // _get(review,"namespace","") returns "" for missing; == "" only for
  // string empties; non-string namespace -> not empty
  if (ns && !PyUnicode_Check(ns)) nsempty = false;
  row.ns_empty = nsempty;
  bool alw = !isns && nsempty;
  row.always = alw;

  // get_ns_name
  if (isns) {
    PyObject *obj = get_field(review, "object");
    PyObject *meta = obj ? get_field(obj, "metadata") : nullptr;
    PyObject *nm = meta ? get_field(meta, "name") : nullptr;
    row.ns_name = nm ? intern_value(ids, strings, nm) : UNDEF;
  } else {
    row.ns_name = ns ? intern_value(ids, strings, ns) : UNDEF;
  }

  PyObject *obj = get_field(review, "object");
  PyObject *old = get_field(review, "oldObject");
  row.obj_empty = !obj || (PyDict_Check(obj) && PyDict_GET_SIZE(obj) == 0);
  row.old_empty = !old || (PyDict_Check(old) && PyDict_GET_SIZE(old) == 0);

  PyObject *ometa = obj ? get_field(obj, "metadata") : nullptr;
  intern_labels(ids, strings, ometa ? get_field(ometa, "labels") : nullptr,
                obj_flat);
  PyObject *olmeta = old ? get_field(old, "metadata") : nullptr;
  intern_labels(ids, strings, olmeta ? get_field(olmeta, "labels") : nullptr,
                old_flat);

  // namespaceSelector resolution mode + ns labels
  int8_t mode;
  PyObject *resolved_ns = nullptr;  // new reference when set
  if (isns) {
    mode = 3;
  } else if (alw) {
    mode = 0;
  } else {
    PyObject *unstable = get_field(review, "_unstable");
    PyObject *uns = unstable ? get_field(unstable, "namespace") : nullptr;
    if (uns) {
      resolved_ns = uns;
      Py_INCREF(resolved_ns);
    } else if (ns && PyUnicode_Check(ns)) {
      resolved_ns = cached_lookup(cached_ns, ns_memo, ns);
      if (!resolved_ns) return false;
      if (resolved_ns == Py_None) {
        Py_DECREF(resolved_ns);
        resolved_ns = nullptr;
      }
    }
    if (!resolved_ns) {
      mode = 2;
    } else {
      mode = 1;
      PyObject *nmeta = get_field(resolved_ns, "metadata");
      intern_labels(ids, strings,
                    nmeta ? get_field(nmeta, "labels") : nullptr, nsl_flat);
    }
  }
  Py_XDECREF(resolved_ns);
  row.ns_mode = mode;

  // needs_autoreject for a namespaceSelector constraint (match.py:236):
  bool rejects = true;
  PyObject *nsv =
      PyDict_Check(review) ? PyDict_GetItemString(review, "namespace")
                           : nullptr;
  PyObject *ns_str =
      (nsv && nsv != Py_None && PyUnicode_Check(nsv)) ? nsv : nullptr;
  // treat null like _get: None -> missing
  if (nsv == Py_None) ns_str = nullptr;
  if (ns_str) {
    PyObject *cached = cached_lookup(cached_ns, ns_memo, ns_str);
    if (!cached) return false;
    if (cached != Py_None) rejects = false;
    Py_DECREF(cached);
  }
  if (rejects) {
    PyObject *unstable = review && PyDict_Check(review)
                             ? PyDict_GetItemString(review, "_unstable")
                             : nullptr;
    if (unstable && PyDict_Check(unstable)) {
      PyObject *uv = PyDict_GetItemString(unstable, "namespace");
      if (uv && uv != Py_False) rejects = false;
    }
  }
  if (rejects && ns_str && PyUnicode_GetLength(ns_str) == 0) rejects = false;
  row.autoreject = rejects;
  return true;
}

// Args: reviews(list), ids(dict), strings(list), cached_ns(callable),
//       dict of preallocated 1-D buffers:
//         group,kind,ns_name: int32[rows]; ns_mode: int8[rows];
//         always,ns_empty,is_ns,obj_empty,old_empty,autoreject,valid: bool[rows]
// Returns: (obj_flat[int32 N,2], obj_counts[int32 n],
//           old_flat, old_counts, ns_flat, ns_counts)

PyObject *pack_reviews_core(PyObject *, PyObject *args) {
  PyObject *reviews, *ids, *strings, *cached_ns, *bufs;
  if (!PyArg_ParseTuple(args, "OOOOO", &reviews, &ids, &strings, &cached_ns,
                        &bufs))
    return nullptr;
  if (!PyList_Check(reviews) || !PyDict_Check(ids) || !PyList_Check(strings) ||
      !PyDict_Check(bufs)) {
    PyErr_SetString(PyExc_TypeError, "bad argument types");
    return nullptr;
  }

  Buf group, kind, ns_name, ns_mode, always, ns_empty, is_ns, obj_empty,
      old_empty, autoreject, valid;
  struct {
    const char *name;
    Buf *buf;
  } needed[] = {
      {"group", &group},         {"kind", &kind},
      {"ns_name", &ns_name},     {"ns_mode", &ns_mode},
      {"always", &always},       {"ns_empty", &ns_empty},
      {"is_ns", &is_ns},         {"obj_empty", &obj_empty},
      {"old_empty", &old_empty}, {"autoreject", &autoreject},
      {"valid", &valid},
  };
  for (auto &nb : needed) {
    PyObject *o = PyDict_GetItemString(bufs, nb.name);
    if (!o || !nb.buf->acquire(o)) {
      PyErr_Format(PyExc_ValueError, "missing/bad buffer %s", nb.name);
      return nullptr;
    }
  }

  Py_ssize_t n = PyList_GET_SIZE(reviews);
  std::vector<int32_t> obj_flat, old_flat, nsl_flat;
  std::vector<int32_t> obj_counts(n), old_counts(n), ns_counts(n);

  // memoized cached_namespace lookups for this batch
  PyObject *ns_memo = PyDict_New();
  if (!ns_memo) return nullptr;

  for (Py_ssize_t i = 0; i < n; i++) {
    valid.b()[i] = true;
    size_t obj0 = obj_flat.size(), old0 = old_flat.size(),
           nsl0 = nsl_flat.size();
    ReviewRow row;
    if (!review_row(PyList_GET_ITEM(reviews, i), ids, strings, cached_ns,
                    ns_memo, row, obj_flat, old_flat, nsl_flat)) {
      Py_DECREF(ns_memo);
      return nullptr;
    }
    group.i32()[i] = row.group;
    kind.i32()[i] = row.kind;
    ns_name.i32()[i] = row.ns_name;
    ns_mode.i8()[i] = row.ns_mode;
    always.b()[i] = row.always;
    ns_empty.b()[i] = row.ns_empty;
    is_ns.b()[i] = row.is_ns;
    obj_empty.b()[i] = row.obj_empty;
    old_empty.b()[i] = row.old_empty;
    autoreject.b()[i] = row.autoreject;
    obj_counts[i] = (int32_t)((obj_flat.size() - obj0) / 2);
    old_counts[i] = (int32_t)((old_flat.size() - old0) / 2);
    ns_counts[i] = (int32_t)((nsl_flat.size() - nsl0) / 2);
  }
  Py_DECREF(ns_memo);

  PyObject *ret = PyTuple_New(6);
  struct {
    std::vector<int32_t> *flat;
    std::vector<int32_t> *counts;
  } outs[] = {{&obj_flat, &obj_counts},
              {&old_flat, &old_counts},
              {&nsl_flat, &ns_counts}};
  for (int j = 0; j < 3; j++) {
    PyObject *flat_arr =
        np_empty_i32((Py_ssize_t)outs[j].flat->size() / 2, 2);
    PyObject *counts_arr = np_empty_i32(n);
    if (!flat_arr || !counts_arr || !fill_i32(flat_arr, *outs[j].flat) ||
        !fill_i32(counts_arr, *outs[j].counts)) {
      Py_XDECREF(flat_arr);
      Py_XDECREF(counts_arr);
      Py_DECREF(ret);
      return nullptr;
    }
    PyTuple_SET_ITEM(ret, j * 2, flat_arr);
    PyTuple_SET_ITEM(ret, j * 2 + 1, counts_arr);
  }
  return ret;
}

// ==========================================================================
// extract_columns cores
// ==========================================================================

// walk(obj, path, i): collect values at path; "[]" iterates lists
void walk(PyObject *obj, PyObject *path, Py_ssize_t i,
          std::vector<PyObject *> &out) {  // borrowed refs out
  Py_ssize_t plen = PyTuple_GET_SIZE(path);
  if (i == plen) {
    out.push_back(obj);
    return;
  }
  PyObject *seg = PyTuple_GET_ITEM(path, i);
  if (PyUnicode_CompareWithASCIIString(seg, "[]") == 0) {
    if (PyList_Check(obj)) {
      Py_ssize_t n = PyList_GET_SIZE(obj);
      for (Py_ssize_t j = 0; j < n; j++)
        walk(PyList_GET_ITEM(obj, j), path, i + 1, out);
    }
    return;
  }
  if (PyDict_Check(obj)) {
    PyObject *v = PyDict_GetItemWithError(obj, seg);
    if (!v) {
      PyErr_Clear();
      return;
    }
    walk(v, path, i + 1, out);
  }
}

// _get_rel: []-free path; nullptr = absent (missing key only; None is a value)
PyObject *get_rel(PyObject *obj, PyObject *path) {
  PyObject *cur = obj;
  Py_ssize_t plen = PyTuple_GET_SIZE(path);
  for (Py_ssize_t i = 0; i < plen; i++) {
    if (!PyDict_Check(cur)) return nullptr;
    PyObject *v = PyDict_GetItemWithError(cur, PyTuple_GET_ITEM(path, i));
    if (!v) {
      PyErr_Clear();
      return nullptr;
    }
    cur = v;
  }
  return cur;
}

// one value as a column cell holds it (columns.py _encode): sid is set for
// a string only, num for a number only
struct Encoded {
  int8_t tcode = T_UNDEF;
  int32_t sid = ID_MISSING;
  double num = 0.0;
};

Encoded encode_value(PyObject *v, PyObject *ids, PyObject *strings) {
  Encoded e;
  if (!v) {
    e.tcode = T_UNDEF;
  } else if (v == Py_None) {
    e.tcode = T_NULL;
  } else if (v == Py_True) {
    e.tcode = T_TRUE;
  } else if (v == Py_False) {
    e.tcode = T_FALSE;
  } else if (PyUnicode_Check(v)) {
    e.tcode = T_STR;
    e.sid = intern(ids, strings, v);
  } else if (PyLong_Check(v) || PyFloat_Check(v)) {
    e.tcode = T_NUM;
    e.num = PyFloat_Check(v) ? PyFloat_AS_DOUBLE(v) : PyLong_AsDouble(v);
    if (PyErr_Occurred()) {  // int beyond double range
      PyErr_Clear();
      e.num = HUGE_VAL;
    }
  } else {
    e.tcode = T_COMP;
  }
  return e;
}

// encode one value into tcode/sid/num at index idx (columns.py _encode)
void encode_at(PyObject *v, Py_ssize_t idx, int8_t *tcode, int32_t *sid,
               double *num, PyObject *ids, PyObject *strings) {
  Encoded e = encode_value(v, ids, strings);
  tcode[idx] = e.tcode;
  if (e.tcode == T_STR) sid[idx] = e.sid;
  if (e.tcode == T_NUM) num[idx] = e.num;
}

// the entities one resource yields over a slot group's iteration paths
void entities_of(PyObject *resource, PyObject *iter_paths,
                 std::vector<PyObject *> &hits) {
  Py_ssize_t np_ = PyTuple_GET_SIZE(iter_paths);
  for (Py_ssize_t p = 0; p < np_; p++)
    walk(resource, PyTuple_GET_ITEM(iter_paths, p), 0, hits);
}

// one resource's keyset (columns.py keyset columns): the interned truthy
// string keys at the paths, first occurrence only, minus the exclusions,
// appended to `flat`; false on a Python error
bool keyset_row(PyObject *resource, PyObject *iter_paths, PyObject *rel_path,
                PyObject *exclude, PyObject *ids, PyObject *strings,
                std::vector<PyObject *> &hits, std::vector<int32_t> &flat) {
  hits.clear();
  entities_of(resource, iter_paths, hits);
  PyObject *seen = PySet_New(nullptr);
  if (!seen) return false;
  for (PyObject *h : hits) {
    PyObject *target = PyTuple_GET_SIZE(rel_path) ? get_rel(h, rel_path) : h;
    if (!target || !PyDict_Check(target)) continue;
    PyObject *k, *v;
    Py_ssize_t pos = 0;
    while (PyDict_Next(target, &pos, &k, &v)) {
      if (!PyUnicode_Check(k) || v == Py_False) continue;
      int ex = PySequence_Contains(exclude, k);
      if (ex != 0) {
        if (ex < 0) PyErr_Clear();
        continue;
      }
      int sn = PySet_Contains(seen, k);
      if (sn != 0) {
        if (sn < 0) PyErr_Clear();
        continue;
      }
      PySet_Add(seen, k);
      flat.push_back(intern(ids, strings, k));
    }
  }
  Py_DECREF(seen);
  return true;
}

// extract_scalar(resources, path, tcode_buf, sid_buf, num_buf, ids, strings)
PyObject *extract_scalar(PyObject *, PyObject *args) {
  PyObject *resources, *path, *tc, *si, *nu, *ids, *strings;
  if (!PyArg_ParseTuple(args, "OOOOOOO", &resources, &path, &tc, &si, &nu,
                        &ids, &strings))
    return nullptr;
  Buf tcode, sid, num;
  if (!tcode.acquire(tc) || !sid.acquire(si) || !num.acquire(nu))
    return nullptr;
  Py_ssize_t n = PyList_GET_SIZE(resources);
  std::vector<PyObject *> hits;
  for (Py_ssize_t i = 0; i < n; i++) {
    hits.clear();
    walk(PyList_GET_ITEM(resources, i), path, 0, hits);
    encode_at(hits.empty() ? nullptr : hits[0], i, tcode.i8(), sid.i32(),
              num.f64(), ids, strings);
  }
  Py_RETURN_NONE;
}

// slot_entities(resources, iter_paths) -> (list of list, max_width)
PyObject *slot_entities(PyObject *, PyObject *args) {
  PyObject *resources, *iter_paths;
  if (!PyArg_ParseTuple(args, "OO", &resources, &iter_paths)) return nullptr;
  Py_ssize_t n = PyList_GET_SIZE(resources);
  PyObject *ents = PyList_New(n);
  if (!ents) return nullptr;
  Py_ssize_t maxw = 0;
  std::vector<PyObject *> hits;
  for (Py_ssize_t i = 0; i < n; i++) {
    hits.clear();
    entities_of(PyList_GET_ITEM(resources, i), iter_paths, hits);
    PyObject *row = PyList_New((Py_ssize_t)hits.size());
    if (!row) {
      Py_DECREF(ents);
      return nullptr;
    }
    for (size_t j = 0; j < hits.size(); j++) {
      Py_INCREF(hits[j]);
      PyList_SET_ITEM(row, (Py_ssize_t)j, hits[j]);
    }
    PyList_SET_ITEM(ents, i, row);
    if ((Py_ssize_t)hits.size() > maxw) maxw = (Py_ssize_t)hits.size();
  }
  return Py_BuildValue("(Nn)", ents, maxw);
}

// encode_slots(entities, rel_path, width, tcode[R,W], sid, num, mask(bool),
//              ids, strings)
PyObject *encode_slots(PyObject *, PyObject *args) {
  PyObject *entities, *rel_path, *tc, *si, *nu, *ma, *ids, *strings;
  Py_ssize_t width;
  if (!PyArg_ParseTuple(args, "OOnOOOOOO", &entities, &rel_path, &width, &tc,
                        &si, &nu, &ma, &ids, &strings))
    return nullptr;
  Buf tcode, sid, num, mask;
  if (!tcode.acquire(tc) || !sid.acquire(si) || !num.acquire(nu) ||
      !mask.acquire(ma))
    return nullptr;
  Py_ssize_t n = PyList_GET_SIZE(entities);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *row = PyList_GET_ITEM(entities, i);
    Py_ssize_t rn = PyList_GET_SIZE(row);
    for (Py_ssize_t j = 0; j < width; j++) {
      Py_ssize_t idx = i * width + j;
      if (j < rn) {
        mask.b()[idx] = true;
        PyObject *v = PyTuple_GET_SIZE(rel_path)
                          ? get_rel(PyList_GET_ITEM(row, j), rel_path)
                          : PyList_GET_ITEM(row, j);
        encode_at(v, idx, tcode.i8(), sid.i32(), num.f64(), ids, strings);
      } else {
        encode_at(nullptr, idx, tcode.i8(), sid.i32(), num.f64(), ids,
                  strings);
      }
    }
  }
  Py_RETURN_NONE;
}

// keyset(resources, iter_paths, rel_path, exclude_set, ids, strings)
//   -> (flat int32 array, counts int32 array)
PyObject *keyset(PyObject *, PyObject *args) {
  PyObject *resources, *iter_paths, *rel_path, *exclude, *ids, *strings;
  if (!PyArg_ParseTuple(args, "OOOOOO", &resources, &iter_paths, &rel_path,
                        &exclude, &ids, &strings))
    return nullptr;
  Py_ssize_t n = PyList_GET_SIZE(resources);
  std::vector<int32_t> flat;
  std::vector<int32_t> counts(n);
  std::vector<PyObject *> hits;
  for (Py_ssize_t i = 0; i < n; i++) {
    size_t before = flat.size();
    if (!keyset_row(PyList_GET_ITEM(resources, i), iter_paths, rel_path,
                    exclude, ids, strings, hits, flat))
      return nullptr;
    counts[i] = (int32_t)(flat.size() - before);
  }
  PyObject *flat_arr = np_empty_i32((Py_ssize_t)flat.size());
  PyObject *counts_arr = np_empty_i32(n);
  if (!flat_arr || !counts_arr || !fill_i32(flat_arr, flat) ||
      !fill_i32(counts_arr, counts)) {
    Py_XDECREF(flat_arr);
    Py_XDECREF(counts_arr);
    return nullptr;
  }
  return Py_BuildValue("(NN)", flat_arr, counts_arr);
}

// ==========================================================================
// review_scratch / review_write: a review dispatch's buffer, written direct
// ==========================================================================
//
// The review side of a dispatch is one [rows, width] int32 buffer
// (ops/reviewbuf.py).  pack_reviews_core + the extract_columns cores build
// it as ~46 padded arrays that are then copied into their columns.  These
// two passes skip the arrays: review_scratch extracts every field's values
// per row, as the int32 cells the buffer holds, interning in the order
// pack_reviews followed by extract_columns interns, and returns the padded
// widths the batch's data decides; the caller looks up the write plan
// those widths name, and review_write lays each field into its columns.

constexpr int32_t ID_PAD = -2;  // pack.py PAD == Interner.PAD

// one field: each row's cells (a row may hold fewer than its columns) and
// what every other cell of its columns reads
struct Field {
  int32_t fill = 0;
  std::vector<int32_t> cells;
  std::vector<Py_ssize_t> ends;  // ends[i]: one past row i's last cell
  void end_row() { ends.push_back((Py_ssize_t)cells.size()); }
  void push_row(int32_t v) {
    cells.push_back(v);
    end_row();
  }
};

struct Scratch {
  Py_ssize_t n = 0;
  std::vector<Field> fields;
  // the first of k new fields; no pointer into `fields` outlives the next
  Field *add(std::initializer_list<int32_t> fills) {
    size_t base = fields.size();
    fields.resize(base + fills.size());
    size_t j = base;
    for (int32_t f : fills) fields[j++].fill = f;
    return &fields[base];
  }
};

const char *SCRATCH_NAME = "gk.review_scratch";

void scratch_free(PyObject *cap) {
  delete static_cast<Scratch *>(PyCapsule_GetPointer(cap, SCRATCH_NAME));
}

int32_t f32_bits(double v) {  // numpy's astype(float32), as int32 bits
  float f = (float)v;
  int32_t bits;
  std::memcpy(&bits, &f, sizeof bits);
  return bits;
}

Py_ssize_t bucket(Py_ssize_t n) {  // pack.py _bucket(n, 1)
  Py_ssize_t b = 1;
  while (b < n) b *= 2;
  return b;
}

void push_encoded(Field *f, const Encoded &e) {  // tcode, sid, num
  f[0].cells.push_back(e.tcode);
  f[1].cells.push_back(e.sid);
  f[2].cells.push_back(f32_bits(e.num));
}

// a joinkey column from its Python extraction (columns.py
// _extract_joinkey): array [rows] or [rows, W] of int32 or bool, padded
// already; -> the spec's width (W, 0 for a scalar key)
bool joinkey_field(PyObject *arr, Field &f, Py_ssize_t n, Py_ssize_t &width) {
  Buf b;
  if (!b.acquire(arr)) return false;
  Py_ssize_t w = b.view.ndim == 2 ? b.view.shape[1] : 1;
  width = b.view.ndim == 2 ? w : 0;
  if (b.view.ndim < 1 || b.view.ndim > 2 || b.view.shape[0] < n ||
      (b.view.itemsize != 4 && b.view.itemsize != 1)) {
    PyErr_SetString(PyExc_ValueError, "joinkey column: unexpected array");
    return false;
  }
  for (Py_ssize_t i = 0; i < n; i++) {
    for (Py_ssize_t j = 0; j < w; j++) {
      Py_ssize_t idx = i * w + j;
      f.cells.push_back(b.view.itemsize == 4 ? b.i32()[idx]
                                             : (int32_t)b.i8()[idx]);
    }
    f.end_row();
  }
  return true;
}

// review_scratch(reviews, ids, strings, cached_ns, specs, groups, joinkey)
//   specs: per column spec (kind, rel_path, iter_paths, exclude, group),
//          kind 0 scalar / 1 slot / 2 keyset / 3 joinkey, group the slot
//          group's index in `groups` (the groups' iteration paths)
//   joinkey(i): the dict _extract_joinkey makes of spec i
//   -> (scratch capsule, widths): widths = the three label groups' padded
//      widths, then one per spec (0 for a scalar column)
PyObject *review_scratch(PyObject *, PyObject *args) {
  PyObject *reviews, *ids, *strings, *cached_ns, *specs, *groups, *joinkey;
  if (!PyArg_ParseTuple(args, "OOOOOOO", &reviews, &ids, &strings, &cached_ns,
                        &specs, &groups, &joinkey))
    return nullptr;
  if (!PyList_Check(reviews) || !PyDict_Check(ids) || !PyList_Check(strings) ||
      !PyTuple_Check(specs) || !PyTuple_Check(groups)) {
    PyErr_SetString(PyExc_TypeError, "bad argument types");
    return nullptr;
  }
  Py_ssize_t n = PyList_GET_SIZE(reviews);
  Py_ssize_t nspecs = PyTuple_GET_SIZE(specs);
  auto *sc = new Scratch();
  sc->n = n;
  PyObject *cap = PyCapsule_New(sc, SCRATCH_NAME, scratch_free);
  if (!cap) {
    delete sc;
    return nullptr;
  }
  PyObject *widths = PyTuple_New(3 + nspecs);
  if (!widths) {
    Py_DECREF(cap);
    return nullptr;
  }
  auto fail = [&]() -> PyObject * {
    Py_DECREF(cap);
    Py_DECREF(widths);
    return nullptr;
  };
  auto set_width = [&](Py_ssize_t j, Py_ssize_t w) {
    PyObject *v = PyLong_FromSsize_t(w);
    if (v) PyTuple_SET_ITEM(widths, j, v);
    return v != nullptr;
  };

  // the review's own fields, in reviewbuf.REVIEW_FIELDS' order
  Field *rf = sc->add({UNDEF, UNDEF, UNDEF, 0, 0, 0, 0, 1, 1, 0, 0, ID_PAD,
                       ID_PAD, ID_PAD});
  PyObject *ns_memo = PyDict_New();
  if (!ns_memo) return fail();
  for (Py_ssize_t i = 0; i < n; i++) {
    ReviewRow row;
    if (!review_row(PyList_GET_ITEM(reviews, i), ids, strings, cached_ns,
                    ns_memo, row, rf[11].cells, rf[12].cells, rf[13].cells)) {
      Py_DECREF(ns_memo);
      return fail();
    }
    int32_t vals[11] = {row.group,      row.kind,      row.ns_name,
                        row.ns_mode,    row.always,    row.ns_empty,
                        row.is_ns,      row.obj_empty, row.old_empty,
                        row.autoreject, 1};
    for (int j = 0; j < 11; j++) rf[j].push_row(vals[j]);
    for (int j = 11; j < 14; j++) rf[j].end_row();
  }
  Py_DECREF(ns_memo);
  for (int j = 0; j < 3; j++) {
    Field &lab = rf[11 + j];
    Py_ssize_t most = 0, start = 0;
    for (Py_ssize_t e : lab.ends) {
      most = std::max(most, (e - start) / 2);
      start = e;
    }
    if (!set_width(j, bucket(most))) return fail();
  }

  // every slot group's entities (no interning), as slot_entities finds them;
  // borrowed from `reviews`, which nothing below mutates
  Py_ssize_t ngroups = PyTuple_GET_SIZE(groups);
  std::vector<std::vector<std::vector<PyObject *>>> ents(ngroups);
  std::vector<Py_ssize_t> group_width(ngroups);
  for (Py_ssize_t g = 0; g < ngroups; g++) {
    Py_ssize_t most = 0;
    ents[g].resize(n);
    for (Py_ssize_t i = 0; i < n; i++) {
      entities_of(PyList_GET_ITEM(reviews, i), PyTuple_GET_ITEM(groups, g),
                  ents[g][i]);
      most = std::max(most, (Py_ssize_t)ents[g][i].size());
    }
    group_width[g] = bucket(most);
  }

  // the columns, spec by spec, rows in order: extract_columns' interning
  std::vector<PyObject *> hits;
  for (Py_ssize_t s = 0; s < nspecs; s++) {
    PyObject *spec = PyTuple_GET_ITEM(specs, s);
    if (!PyTuple_Check(spec) || PyTuple_GET_SIZE(spec) != 5 ||
        !PyTuple_Check(PyTuple_GET_ITEM(spec, 1)) ||
        !PyTuple_Check(PyTuple_GET_ITEM(spec, 2))) {
      PyErr_SetString(PyExc_TypeError, "a spec is (kind, rel_path, "
                      "iter_paths, exclude, group) with tuple paths");
      return fail();
    }
    long kind = PyLong_AsLong(PyTuple_GET_ITEM(spec, 0));
    PyObject *rel_path = PyTuple_GET_ITEM(spec, 1);
    PyObject *iter_paths = PyTuple_GET_ITEM(spec, 2);
    Py_ssize_t width = 0;
    if (kind == 0) {  // scalar: tcode, sid, num
      Field *f = sc->add({T_UNDEF, ID_MISSING, 0});
      for (Py_ssize_t i = 0; i < n; i++) {
        hits.clear();
        walk(PyList_GET_ITEM(reviews, i), rel_path, 0, hits);
        push_encoded(f, encode_value(hits.empty() ? nullptr : hits[0], ids,
                                     strings));
        for (int j = 0; j < 3; j++) f[j].end_row();
      }
    } else if (kind == 1) {  // slot: tcode, sid, num, mask per entity
      Py_ssize_t g = PyLong_AsSsize_t(PyTuple_GET_ITEM(spec, 4));
      if (g < 0 || g >= ngroups) {
        PyErr_SetString(PyExc_ValueError, "slot spec without its group");
        return fail();
      }
      Field *f = sc->add({T_UNDEF, ID_MISSING, 0, 0});
      bool rel = PyTuple_GET_SIZE(rel_path) > 0;
      for (Py_ssize_t i = 0; i < n; i++) {
        for (PyObject *ent : ents[g][i]) {
          push_encoded(f, encode_value(rel ? get_rel(ent, rel_path) : ent,
                                       ids, strings));
          f[3].cells.push_back(1);
        }
        for (int j = 0; j < 4; j++) f[j].end_row();
      }
      width = group_width[g];
    } else if (kind == 2) {  // keyset: ids
      Field *f = sc->add({ID_PAD});
      Py_ssize_t most = 0;
      for (Py_ssize_t i = 0; i < n; i++) {
        size_t before = f->cells.size();
        if (!keyset_row(PyList_GET_ITEM(reviews, i), iter_paths, rel_path,
                        PyTuple_GET_ITEM(spec, 3), ids, strings, hits,
                        f->cells))
          return fail();
        most = std::max(most, (Py_ssize_t)(f->cells.size() - before));
        f->end_row();
      }
      width = bucket(most);
    } else if (kind == 3) {  // joinkey: sid (and mask for a slot key)
      PyObject *cols = PyObject_CallFunction(joinkey, "n", s);
      if (!cols) return fail();
      bool slot = PyTuple_GET_SIZE(iter_paths) > 0;
      const char *names[2] = {"sid", "mask"};
      Field *f = slot ? sc->add({ID_MISSING, 0}) : sc->add({ID_MISSING});
      for (int j = 0; j < (slot ? 2 : 1); j++) {
        PyObject *arr =
            PyDict_Check(cols) ? PyDict_GetItemString(cols, names[j]) : nullptr;
        if (!arr) {
          PyErr_Format(PyExc_ValueError, "joinkey column without %s",
                       names[j]);
          Py_DECREF(cols);
          return fail();
        }
        if (!joinkey_field(arr, f[j], n, width)) {
          Py_DECREF(cols);
          return fail();
        }
      }
      Py_DECREF(cols);
    } else {
      PyErr_Format(PyExc_ValueError, "unknown column kind %ld", kind);
      return fail();
    }
    if (!set_width(3 + s, width)) return fail();
  }
  return Py_BuildValue("(NN)", cap, widths);
}

// review_write(scratch, plan, rows, width) -> buf [rows, width] int32
//   plan: int32 [L, 3], per buffer slot (field, column offset, columns);
//   the slots tile the width, so every cell is written
PyObject *review_write(PyObject *, PyObject *args) {
  PyObject *cap, *plan_obj;
  Py_ssize_t rows, width;
  if (!PyArg_ParseTuple(args, "OOnn", &cap, &plan_obj, &rows, &width))
    return nullptr;
  auto *sc = static_cast<Scratch *>(PyCapsule_GetPointer(cap, SCRATCH_NAME));
  if (!sc) return nullptr;
  Buf plan;
  if (!plan.acquire(plan_obj)) return nullptr;
  if (plan.view.ndim != 2 || plan.view.shape[1] != 3 ||
      plan.view.itemsize != 4 || rows < sc->n) {
    PyErr_SetString(PyExc_ValueError, "bad write plan");
    return nullptr;
  }
  PyObject *buf_obj = np_empty_i32(rows, width);
  if (!buf_obj) return nullptr;
  Buf buf;
  if (!buf.acquire(buf_obj)) {
    Py_DECREF(buf_obj);
    return nullptr;
  }
  int32_t *out = buf.i32();
  const int32_t *p = plan.i32();
  for (Py_ssize_t e = 0; e < plan.view.shape[0]; e++, p += 3) {
    int32_t fi = p[0], off = p[1], cols = p[2];
    if (fi < 0 || (size_t)fi >= sc->fields.size() || off < 0 || cols < 0 ||
        off + cols > width) {
      PyErr_SetString(PyExc_ValueError, "write plan out of range");
      Py_DECREF(buf_obj);
      return nullptr;
    }
    const Field &f = sc->fields[fi];
    Py_ssize_t start = 0;
    for (Py_ssize_t i = 0; i < rows; i++) {
      int32_t *dst = out + i * width + off;
      Py_ssize_t len = 0;
      if (i < sc->n) {
        len = f.ends[i] - start;
        if (len > cols) {
          PyErr_SetString(PyExc_ValueError,
                          "a row holds more cells than its columns");
          Py_DECREF(buf_obj);
          return nullptr;
        }
        if (len) std::memcpy(dst, f.cells.data() + start, len * sizeof *dst);
        start = f.ends[i];
      }
      std::fill(dst + len, dst + cols, f.fill);
    }
  }
  return buf_obj;
}

// ---- freeze: JSON-like tree -> frozen Rego value --------------------------
//
// The profiled cold-start cost of data ingestion is engine/value.py
// freeze(): a recursive Python walk over every K8s object.  This C walk
// builds the SAME Python value types (tuples; FrozenDict/RSet instances
// constructed through the classes registered by freeze_init), so
// isinstance checks, hashing, and equality behave identically; parity is
// pinned by tests/test_native.py differential cases.

PyObject *g_frozendict_cls = nullptr;
PyObject *g_rset_cls = nullptr;

PyObject *freeze_rec_guarded(PyObject *v);

PyObject *freeze_rec(PyObject *v);

// snapshot an iterable's items and freeze each into a fresh tuple; shared
// by the list/tuple, set, and RSet branches
PyObject *freeze_items_tuple(PyObject *iterable) {
  PyObject *snap = PySequence_Tuple(iterable);
  if (!snap) return nullptr;
  Py_ssize_t n = PyTuple_GET_SIZE(snap);
  PyObject *out = PyTuple_New(n);
  if (!out) {
    Py_DECREF(snap);
    return nullptr;
  }
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *f = freeze_rec(PyTuple_GET_ITEM(snap, i));
    if (!f) {
      Py_DECREF(snap);
      Py_DECREF(out);
      return nullptr;
    }
    PyTuple_SET_ITEM(out, i, f);
  }
  Py_DECREF(snap);
  return out;
}

// snapshot a dict's items and deep-freeze into a new FrozenDict; iterating
// a live dict across Python re-entry is unsafe under mutation
PyObject *freeze_dict_items(PyObject *d) {
  PyObject *items = PyDict_Items(d);
  if (!items) return nullptr;
  PyObject *inner = PyDict_New();
  if (!inner) {
    Py_DECREF(items);
    return nullptr;
  }
  Py_ssize_t n = PyList_GET_SIZE(items);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *pair = PyList_GET_ITEM(items, i);
    PyObject *fk = freeze_rec(PyTuple_GET_ITEM(pair, 0));
    if (!fk) {
      Py_DECREF(items);
      Py_DECREF(inner);
      return nullptr;
    }
    PyObject *fv = freeze_rec(PyTuple_GET_ITEM(pair, 1));
    if (!fv) {
      Py_DECREF(fk);
      Py_DECREF(items);
      Py_DECREF(inner);
      return nullptr;
    }
    int rc = PyDict_SetItem(inner, fk, fv);
    Py_DECREF(fk);
    Py_DECREF(fv);
    if (rc < 0) {
      Py_DECREF(items);
      Py_DECREF(inner);
      return nullptr;
    }
  }
  Py_DECREF(items);
  PyObject *out = PyObject_CallOneArg(g_frozendict_cls, inner);
  Py_DECREF(inner);
  return out;
}

PyObject *freeze_rec(PyObject *v) {
  // per-level recursion guard: arbitrarily deep user JSON must raise
  // RecursionError, not smash the C stack
  if (Py_EnterRecursiveCall(" in freeze")) return nullptr;
  PyObject *out = freeze_rec_guarded(v);
  Py_LeaveRecursiveCall();
  return out;
}

PyObject *freeze_rec_guarded(PyObject *v) {
  if (v == Py_None || PyBool_Check(v) || PyUnicode_Check(v)) {
    Py_INCREF(v);
    return v;
  }
  if (PyFloat_Check(v)) {
    double d = PyFloat_AS_DOUBLE(v);
    // canonicalize integral floats (JSON "1.0") to ints like value.py
    if (std::isfinite(d) && d == std::floor(d)) return PyLong_FromDouble(d);
    Py_INCREF(v);
    return v;
  }
  if (PyLong_Check(v)) {
    Py_INCREF(v);
    return v;
  }
  if (PyList_Check(v) || PyTuple_Check(v)) {
    // snapshot-before-iterate: freezing nested dicts calls back into
    // Python, which may release the eval lock to a thread mutating this
    // very list — a cached item pointer would dangle
    return freeze_items_tuple(v);
  }
  if (PyDict_Check(v)) {
    // hot path first: plain dicts dominate K8s-object input; the generic
    // isinstance checks below only matter for the rare frozen inputs
    return freeze_dict_items(v);
  }
  // frozen containers are REBUILT like the Python oracle does: a
  // FrozenDict constructed directly around raw values must come out
  // deep-frozen, not passed through with mutables inside
  int is_fd = PyObject_IsInstance(v, g_frozendict_cls);
  if (is_fd < 0) return nullptr;
  int is_rs = is_fd ? 0 : PyObject_IsInstance(v, g_rset_cls);
  if (is_rs < 0) return nullptr;
  if (is_fd) {
    PyObject *d = PyObject_GetAttrString(v, "_d");
    if (!d) return nullptr;
    PyObject *out = freeze_dict_items(d);
    Py_DECREF(d);
    return out;
  }
  if (is_rs) {
    PyObject *s = PyObject_GetAttrString(v, "_s");
    if (!s) return nullptr;
    PyObject *frozen = freeze_items_tuple(s);
    Py_DECREF(s);
    if (!frozen) return nullptr;
    PyObject *out = PyObject_CallOneArg(g_rset_cls, frozen);
    Py_DECREF(frozen);
    return out;
  }
  if (PyAnySet_Check(v)) {
    PyObject *frozen = freeze_items_tuple(v);
    if (!frozen) return nullptr;
    PyObject *out = PyObject_CallOneArg(g_rset_cls, frozen);
    Py_DECREF(frozen);
    return out;
  }
  PyErr_Format(PyExc_TypeError, "cannot freeze %R", (PyObject *)Py_TYPE(v));
  return nullptr;
}

// --------------------------------------------------------------------------
// thaw_core: frozen Rego value -> plain JSON-able Python value
// (engine/value.py thaw).  The audit pack rebuild thaws every cached
// object on a cold start — pure-Python recursion was ~3s per 20k pods.
// Iteration order matches the Python oracle exactly: FrozenDict.items()
// and RSet.sorted_items() yield canonical (compare-sorted) order, so the
// produced dicts/lists are byte-identical in serialization.
// --------------------------------------------------------------------------

PyObject *thaw_rec(PyObject *v);

PyObject *thaw_rec_guarded(PyObject *v) {
  if (v == Py_None || PyBool_Check(v) || PyLong_Check(v) ||
      PyFloat_Check(v) || PyUnicode_Check(v)) {
    Py_INCREF(v);
    return v;
  }
  if (PyTuple_Check(v)) {
    Py_ssize_t n = PyTuple_GET_SIZE(v);
    PyObject *out = PyList_New(n);
    if (!out) return nullptr;
    for (Py_ssize_t i = 0; i < n; i++) {
      PyObject *item = thaw_rec(PyTuple_GET_ITEM(v, i));
      if (!item) {
        Py_DECREF(out);
        return nullptr;
      }
      PyList_SET_ITEM(out, i, item);
    }
    return out;
  }
  int is_fd = PyObject_IsInstance(v, g_frozendict_cls);
  if (is_fd < 0) return nullptr;
  if (is_fd) {
    // all-string-key fast path (the overwhelming K8s-object shape):
    // OPA's compare() on strings is plain lexicographic order, so a
    // native unicode sort of _d's keys reproduces items()' canonical
    // order without the Python sort machinery
    PyObject *d = PyObject_GetAttrString(v, "_d");
    if (d && PyDict_Check(d)) {
      bool all_str = true;
      PyObject *key, *val;
      Py_ssize_t pos = 0;
      while (PyDict_Next(d, &pos, &key, &val)) {
        if (!PyUnicode_Check(key)) {
          all_str = false;
          break;
        }
      }
      if (all_str) {
        PyObject *keys = PyDict_Keys(d);
        if (!keys || PyList_Sort(keys) < 0) {
          Py_XDECREF(keys);
          Py_DECREF(d);
          return nullptr;
        }
        PyObject *out = PyDict_New();
        if (!out) {
          Py_DECREF(keys);
          Py_DECREF(d);
          return nullptr;
        }
        Py_ssize_t n = PyList_GET_SIZE(keys);
        for (Py_ssize_t i = 0; i < n; i++) {
          PyObject *k = PyList_GET_ITEM(keys, i);
          PyObject *dv = PyDict_GetItem(d, k);  // borrowed
          PyObject *tv = dv ? thaw_rec(dv) : nullptr;
          int rc = tv ? PyDict_SetItem(out, k, tv) : -1;
          Py_XDECREF(tv);
          if (rc < 0) {
            Py_DECREF(keys);
            Py_DECREF(d);
            Py_DECREF(out);
            return nullptr;
          }
        }
        Py_DECREF(keys);
        Py_DECREF(d);
        return out;
      }
    }
    Py_XDECREF(d);
    if (PyErr_Occurred()) return nullptr;
    // items() yields canonical sorted order (cached on the FrozenDict)
    PyObject *items = PyObject_CallMethod(v, "items", nullptr);
    if (!items) return nullptr;
    PyObject *out = PyDict_New();
    if (!out) {
      Py_DECREF(items);
      return nullptr;
    }
    PyObject *it = PyObject_GetIter(items);
    Py_DECREF(items);
    if (!it) {
      Py_DECREF(out);
      return nullptr;
    }
    PyObject *pair;
    while ((pair = PyIter_Next(it)) != nullptr) {
      PyObject *tk = thaw_rec(PyTuple_GET_ITEM(pair, 0));
      PyObject *tv = tk ? thaw_rec(PyTuple_GET_ITEM(pair, 1)) : nullptr;
      int rc = (tk && tv) ? PyDict_SetItem(out, tk, tv) : -1;
      Py_XDECREF(tk);
      Py_XDECREF(tv);
      Py_DECREF(pair);
      if (rc < 0) {
        Py_DECREF(it);
        Py_DECREF(out);
        return nullptr;
      }
    }
    Py_DECREF(it);
    if (PyErr_Occurred()) {
      Py_DECREF(out);
      return nullptr;
    }
    return out;
  }
  int is_rs = PyObject_IsInstance(v, g_rset_cls);
  if (is_rs < 0) return nullptr;
  if (is_rs) {
    PyObject *sorted_items = PyObject_CallMethod(v, "sorted_items", nullptr);
    if (!sorted_items) return nullptr;
    Py_ssize_t n = PyList_Check(sorted_items)
                       ? PyList_GET_SIZE(sorted_items)
                       : -1;
    if (n < 0) {
      Py_DECREF(sorted_items);
      PyErr_SetString(PyExc_TypeError, "sorted_items did not return a list");
      return nullptr;
    }
    PyObject *out = PyList_New(n);
    if (!out) {
      Py_DECREF(sorted_items);
      return nullptr;
    }
    for (Py_ssize_t i = 0; i < n; i++) {
      PyObject *item = thaw_rec(PyList_GET_ITEM(sorted_items, i));
      if (!item) {
        Py_DECREF(sorted_items);
        Py_DECREF(out);
        return nullptr;
      }
      PyList_SET_ITEM(out, i, item);
    }
    Py_DECREF(sorted_items);
    return out;
  }
  PyErr_Format(PyExc_TypeError, "cannot thaw %R", (PyObject *)Py_TYPE(v));
  return nullptr;
}

PyObject *thaw_rec(PyObject *v) {
  if (Py_EnterRecursiveCall(" in thaw")) return nullptr;
  PyObject *out = thaw_rec_guarded(v);
  Py_LeaveRecursiveCall();
  return out;
}

PyObject *thaw_core(PyObject *, PyObject *arg) {
  if (!g_frozendict_cls || !g_rset_cls) {
    PyErr_SetString(PyExc_RuntimeError, "freeze_init not called");
    return nullptr;
  }
  return thaw_rec(arg);
}

PyObject *freeze_init(PyObject *, PyObject *args) {
  PyObject *fd, *rs;
  if (!PyArg_ParseTuple(args, "OO", &fd, &rs)) return nullptr;
  Py_XDECREF(g_frozendict_cls);
  Py_XDECREF(g_rset_cls);
  Py_INCREF(fd);
  Py_INCREF(rs);
  g_frozendict_cls = fd;
  g_rset_cls = rs;
  Py_RETURN_NONE;
}

PyObject *freeze_core(PyObject *, PyObject *arg) {
  if (!g_frozendict_cls || !g_rset_cls) {
    PyErr_SetString(PyExc_RuntimeError, "freeze_init not called");
    return nullptr;
  }
  return freeze_rec(arg);  // freeze_rec guards every recursion level
}

PyMethodDef methods[] = {
    {"freeze_init", freeze_init, METH_VARARGS,
     "register the FrozenDict and RSet classes"},
    {"freeze_core", freeze_core, METH_O,
     "JSON-like tree -> frozen Rego value (engine/value.py freeze)"},
    {"thaw_core", thaw_core, METH_O,
     "frozen Rego value -> plain JSON-able value (engine/value.py thaw)"},
    {"pack_reviews_core", pack_reviews_core, METH_VARARGS,
     "fill review-side fixed buffers; returns label pair flats+counts"},
    {"extract_scalar", extract_scalar, METH_VARARGS,
     "encode first-hit path values into tcode/sid/num buffers"},
    {"slot_entities", slot_entities, METH_VARARGS,
     "collect iteration-path entities per resource"},
    {"encode_slots", encode_slots, METH_VARARGS,
     "encode per-entity rel-path values into padded buffers"},
    {"keyset", keyset, METH_VARARGS,
     "interned truthy keys at paths, dedup, minus exclusions"},
    {"review_scratch", review_scratch, METH_VARARGS,
     "a review batch's buffer fields and padded widths, interned in order"},
    {"review_write", review_write, METH_VARARGS,
     "lay a review_scratch into one [rows, width] int32 buffer by a plan"},
    {nullptr, nullptr, 0, nullptr}};

PyModuleDef moduledef = {PyModuleDef_HEAD_INIT, "_gknative",
                         "C++ packing fast path", -1, methods};

}  // namespace

PyMODINIT_FUNC PyInit__gknative(void) {
  PyObject *np = PyImport_ImportModule("numpy");
  if (!np) return nullptr;
  g_np_empty = PyObject_GetAttrString(np, "empty");
  Py_DECREF(np);
  if (!g_np_empty) return nullptr;
  PyObject *builtins = PyImport_ImportModule("builtins");
  if (!builtins) return nullptr;
  g_sorted = PyObject_GetAttrString(builtins, "sorted");
  g_str = PyObject_GetAttrString(builtins, "str");
  Py_DECREF(builtins);
  if (!g_sorted || !g_str) return nullptr;
  return PyModule_Create(&moduledef);
}
