// Native forest traversal for Booster.predict on the CPU backend.
//
// TPU-native replacement for the reference's per-row JNI predict
// (LightGBMBooster.score -> LGBM_BoosterPredictForMat; expected path,
// UNVERIFIED -- SURVEY.md SS3.2, a known perf sore point there too).  The
// jitted gather-walk in booster.py is the accelerator path; on the CPU
// backend XLA lowers the fixed-depth walk to whole-array gathers per
// level, ~2.6 s for the bench shape where this early-exit row walk needs
// well under a second.
//
// Exactness contract: bitwise-identical margins to _predict_forest.  The
// walk uses the same float32 `x <= thr` decision (NaN -> the node's
// ``dleft`` for numeric nodes: booster._nan_left), the same categorical
// bitset semantics as _cat_go_left
// (NaN -> default_left, negative / out-of-range categories -> right),
// and accumulates per-row tree values in the same tree order in float32,
// so every IEEE operation matches the XLA scan.
//
// CPython C API only -- no pybind11 in this image.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cmath>
#include <cstdint>
#include <thread>
#include <vector>

namespace {

struct Buf {
  Py_buffer view;
  bool held = false;
  ~Buf() {
    if (held) PyBuffer_Release(&view);
  }
  bool Get(PyObject* obj, const char* name, int itemsize,
           bool writable = false) {
    const int flags = PyBUF_C_CONTIGUOUS | PyBUF_FORMAT |
                      (writable ? PyBUF_WRITABLE : 0);
    if (PyObject_GetBuffer(obj, &view, flags) != 0) {
      return false;
    }
    held = true;
    if (view.itemsize != itemsize) {
      PyErr_Format(PyExc_TypeError, "%s: expected itemsize %d, got %zd", name,
                   itemsize, view.itemsize);
      return false;
    }
    return true;
  }
};

struct Forest {
  const int32_t* feat;     // (T, m)
  const float* thr;        // (T, m)
  const int32_t* left;     // (T, m)
  const int32_t* right;    // (T, m)
  const float* leaf;       // (T, L)
  const uint8_t* single;   // (T,)
  const int32_t* is_cat;   // (T, m)
  const int32_t* dleft;    // (T, m)
  const int32_t* cat_bnd;  // (T, C1)
  const uint32_t* cat_words;  // (T, W)
  int64_t T, m, L, C1, W;
  int K;
  bool has_cat;
};

inline bool CatGoLeft(float x, int32_t j, int32_t dleft_node,
                      const int32_t* bnd, int64_t C1, const uint32_t* words,
                      int64_t W) {
  if (std::isnan(x)) return dleft_node > 0;
  if (j < 0) j = 0;
  if (j > static_cast<int32_t>(C1) - 2) j = static_cast<int32_t>(C1) - 2;
  const int64_t b0 = bnd[j];
  const int64_t b1 = bnd[j + 1];
  // int32 truncation FIRST, then the sign gate, exactly like the XLA walk:
  // x in (-1, 0) truncates to category 0 (may go left); x <= -1 routes
  // right.  Values outside int32 range route right (the XLA convert's
  // wrap behavior there is garbage-in, not a contract).
  if (!(x > -2147483648.0f && x < 2147483648.0f)) return false;
  const int32_t c = static_cast<int32_t>(x);
  if (c < 0) return false;
  const int64_t widx = b0 + (c >> 5);
  if (widx < 0 || widx >= b1 || widx >= W) return false;
  return (words[widx] >> (c & 31)) & 1u;
}

void PredictRows(const Forest& fr, const float* X, int64_t f, int64_t r0,
                 int64_t r1, float* out) {
  for (int64_t i = r0; i < r1; ++i) {
    const float* xrow = X + i * f;
    float* orow = out + i * fr.K;
    for (int64_t t = 0; t < fr.T; ++t) {
      const int32_t* tfeat = fr.feat + t * fr.m;
      const float* tthr = fr.thr + t * fr.m;
      const int32_t* tleft = fr.left + t * fr.m;
      const int32_t* tright = fr.right + t * fr.m;
      int32_t node = fr.single[t] ? -1 : 0;
      // Corrupt-model hardening, matching the XLA walk where it has a
      // defined behavior: index clamps mirror XLA's clamping gather
      // semantics; the step bound (the XLA walk is a fixed-depth
      // fori_loop) turns a cyclic left/right graph into leaf 0 instead
      // of a hang.
      int64_t steps = 0;
      while (node >= 0) {
        if (node >= fr.m) node = static_cast<int32_t>(fr.m) - 1;
        if (++steps > fr.m) {
          node = -1;
          break;
        }
        int32_t fj = tfeat[node];
        if (fj < 0) fj = 0;
        if (fj >= f) fj = static_cast<int32_t>(f) - 1;
        const float x = xrow[fj];
        bool go_left;
        if (fr.has_cat && fr.is_cat[t * fr.m + node]) {
          go_left = CatGoLeft(x, static_cast<int32_t>(tthr[node]),
                              fr.dleft[t * fr.m + node],
                              fr.cat_bnd + t * fr.C1, fr.C1,
                              fr.cat_words + t * fr.W, fr.W);
        } else {
          // NaN fails the compare and goes by the node's NaN direction,
          // as in the XLA walk
          go_left = std::isnan(x) ? fr.dleft[t * fr.m + node] > 0
                                  : x <= tthr[node];
        }
        node = go_left ? tleft[node] : tright[node];
      }
      int64_t li = -static_cast<int64_t>(node) - 1;
      if (li >= fr.L) li = fr.L - 1;
      orow[t % fr.K] += fr.leaf[t * fr.L + li];
    }
  }
}

PyObject* PredictForest(PyObject*, PyObject* args) {
  PyObject *xo, *feato, *thro, *lefto, *righto, *leafo, *singleo, *is_cato,
      *dlefto, *bndo, *wordso, *outo;
  int K, has_cat, n_threads;
  if (!PyArg_ParseTuple(args, "OOOOOOOOOOOiiiO", &xo, &feato, &thro, &lefto,
                        &righto, &leafo, &singleo, &is_cato, &dlefto, &bndo,
                        &wordso, &K, &has_cat, &n_threads, &outo)) {
    return nullptr;
  }
  Buf x, feat, thr, left, right, leaf, single, is_cat, dleft, bnd, words, out;
  if (!x.Get(xo, "X", 4) || !feat.Get(feato, "feat", 4) ||
      !thr.Get(thro, "thr", 4) || !left.Get(lefto, "left", 4) ||
      !right.Get(righto, "right", 4) || !leaf.Get(leafo, "leaf", 4) ||
      !single.Get(singleo, "single", 1) || !is_cat.Get(is_cato, "is_cat", 4) ||
      !dleft.Get(dlefto, "dleft", 4) || !bnd.Get(bndo, "cat_bnd", 4) ||
      !words.Get(wordso, "cat_words", 4) ||
      !out.Get(outo, "out", 4, /*writable=*/true)) {
    return nullptr;
  }
  if (x.view.ndim != 2 || feat.view.ndim != 2 || leaf.view.ndim != 2 ||
      bnd.view.ndim != 2 || words.view.ndim != 2 || out.view.ndim != 2) {
    PyErr_SetString(PyExc_ValueError, "X/feat/leaf/cat_bnd/cat_words/out "
                                      "must be 2-D");
    return nullptr;
  }
  // Every per-node array must be (T, m) like feat, and every per-tree
  // array must lead with T — the walk indexes them all with feat's
  // extents, so a mismatch is an out-of-bounds read, not a softer bug.
  const int64_t Tn = feat.view.shape[0], mn = feat.view.shape[1];
  const struct { const Py_buffer* v; const char* name; } node_arrs[] = {
      {&thr.view, "thr"},       {&left.view, "left"},
      {&right.view, "right"},   {&is_cat.view, "is_cat"},
      {&dleft.view, "dleft"}};
  for (const auto& a : node_arrs) {
    if (a.v->ndim != 2 || a.v->shape[0] != Tn || a.v->shape[1] != mn) {
      PyErr_Format(PyExc_ValueError, "%s must have feat's shape (T, m)",
                   a.name);
      return nullptr;
    }
  }
  if (single.view.ndim != 1 || single.view.shape[0] != Tn ||
      leaf.view.shape[0] != Tn || bnd.view.shape[0] != Tn ||
      words.view.shape[0] != Tn) {
    PyErr_SetString(PyExc_ValueError,
                    "single/leaf/cat_bnd/cat_words must lead with T trees");
    return nullptr;
  }
  if (leaf.view.shape[1] < 1 || bnd.view.shape[1] < 2 ||
      words.view.shape[1] < 1 || x.view.shape[1] < 1 || K < 1) {
    PyErr_SetString(PyExc_ValueError,
                    "leaf/cat_bnd/cat_words/X widths and K must be >= 1");
    return nullptr;
  }
  Forest fr;
  fr.feat = static_cast<const int32_t*>(feat.view.buf);
  fr.thr = static_cast<const float*>(thr.view.buf);
  fr.left = static_cast<const int32_t*>(left.view.buf);
  fr.right = static_cast<const int32_t*>(right.view.buf);
  fr.leaf = static_cast<const float*>(leaf.view.buf);
  fr.single = static_cast<const uint8_t*>(single.view.buf);
  fr.is_cat = static_cast<const int32_t*>(is_cat.view.buf);
  fr.dleft = static_cast<const int32_t*>(dleft.view.buf);
  fr.cat_bnd = static_cast<const int32_t*>(bnd.view.buf);
  fr.cat_words = static_cast<const uint32_t*>(words.view.buf);
  fr.T = feat.view.shape[0];
  fr.m = feat.view.shape[1];
  fr.L = leaf.view.shape[1];
  fr.C1 = bnd.view.shape[1];
  fr.W = words.view.shape[1];
  fr.K = K;
  fr.has_cat = has_cat != 0;
  const int64_t n = x.view.shape[0];
  const int64_t f = x.view.shape[1];
  const float* X = static_cast<const float*>(x.view.buf);
  float* O = static_cast<float*>(out.view.buf);
  if (out.view.shape[0] != n || out.view.shape[1] != K) {
    PyErr_SetString(PyExc_ValueError, "out must be (n, K)");
    return nullptr;
  }
  Py_BEGIN_ALLOW_THREADS;
  int nt = n_threads > 0 ? n_threads
                         : static_cast<int>(
                               std::thread::hardware_concurrency());
  if (nt < 1) nt = 1;
  if (nt > 1 && n >= 4096) {
    std::vector<std::thread> pool;
    const int64_t step = (n + nt - 1) / nt;
    for (int w = 0; w < nt; ++w) {
      const int64_t r0 = w * step;
      const int64_t r1 = r0 + step < n ? r0 + step : n;
      if (r0 >= r1) break;
      pool.emplace_back(
          [&fr, X, f, r0, r1, O]() { PredictRows(fr, X, f, r0, r1, O); });
    }
    for (auto& th : pool) th.join();
  } else {
    PredictRows(fr, X, f, 0, n, O);
  }
  Py_END_ALLOW_THREADS;
  Py_RETURN_NONE;
}

PyMethodDef kMethods[] = {
    {"predict_forest", PredictForest, METH_VARARGS,
     "Early-exit forest margin accumulation into a preallocated (n, K) "
     "float32 output."},
    {nullptr, nullptr, 0, nullptr}};

PyModuleDef kModule = {PyModuleDef_HEAD_INIT, "_fastforest",
                       "Native forest scorer", -1, kMethods,
                       nullptr, nullptr, nullptr, nullptr};

}  // namespace

PyMODINIT_FUNC PyInit__fastforest() { return PyModule_Create(&kModule); }
