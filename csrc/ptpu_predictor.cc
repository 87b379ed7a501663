// Native-deployment predictor: load a paddle_tpu-exported ONNX artifact
// and execute it from C/C++ with NO Python in the serving process.
//
// Reference counterpart: the C inference API
// (paddle/fluid/inference/capi_exp/pd_inference_api.h:1) over
// AnalysisPredictor (inference/api/analysis_predictor.cc:381). The
// TPU-native deployment artifact is the ONNX wire file emitted by
// paddle_tpu.onnx.export (a jaxpr walk, onnx/converter.py); this TU is a
// dependency-free interpreter for exactly that op subset: a ~150-line
// protobuf wire parser + a dtype-tagged tensor interpreter. Heavy server
// deployments would hand the same artifact to an optimizing runtime; this
// keeps the "C caller, zero Python" contract testable and self-contained.
//
// Build: part of csrc/Makefile -> paddle_tpu/_native_predictor.so
// C ABI at the bottom (ptpu_predictor_*). Thread-compatible: one
// predictor per thread, no globals.
#include <algorithm>
#include <atomic>
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <functional>
#include <fstream>
#include <limits>
#include <map>
#include <memory>
#include <set>
#include <sstream>
#include <stdexcept>
#include <string>
#include <thread>
#include <type_traits>
#include <unordered_map>
#include <vector>

#include "ptpu_arena.h"
#include "ptpu_schedck.h"
#include "ptpu_spill.h"
#include "ptpu_stats.h"
#include "ptpu_sync.h"
#include "ptpu_topo.h"
#include "ptpu_tune.h"

namespace {

// ---------------------------------------------------------------- profiler
// Host-profiler hook: the chrome-trace Profiler singleton lives in
// _native.so (csrc/ptpu_runtime.cc) and this TU must stay
// dependency-free, so the binding layer (core/native.py) hands over
// the three entry points as raw function pointers via
// ptpu_predictor_set_profiler. When wired AND the profiler is
// enabled, every executed op emits a RecordEvent span — a serving run
// lands in the same chrome trace as training ranks
// (profiler/timeline.py merges them).
typedef void (*ProfRecordFn)(const char *, int64_t, int64_t);
typedef int (*ProfEnabledFn)();
std::atomic<ProfRecordFn> g_prof_record{nullptr};
std::atomic<ProfEnabledFn> g_prof_enabled{nullptr};

// ------------------------------------------------------------ protobuf wire
struct Reader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;
  uint64_t varint() {
    uint64_t v = 0;
    int shift = 0;
    while (p < end) {
      uint8_t b = *p++;
      v |= uint64_t(b & 0x7F) << shift;
      if (!(b & 0x80)) return v;
      shift += 7;
      if (shift > 63) break;
    }
    ok = false;
    return 0;
  }
  // iterate fields; cb(field, wire, payload_reader_or_value)
  template <class F>
  void fields(F cb) {
    while (ok && p < end) {
      uint64_t key = varint();
      int field = int(key >> 3), wire = int(key & 7);
      if (wire == 0) {
        uint64_t v = varint();
        cb(field, wire, Reader{nullptr, nullptr}, v);
      } else if (wire == 2) {
        uint64_t len = varint();
        // compare against the REMAINING size: `p + len` overflows the
        // pointer for a hostile 64-bit length (UB; fuzzing finding,
        // ISSUE 11; repro: corpus/onnx/crash-varint-len-overflow.bin)
        if (len > uint64_t(end - p)) { ok = false; return; }
        cb(field, wire, Reader{p, p + len}, 0);
        p += len;
      } else if (wire == 5) {
        if (p + 4 > end) { ok = false; return; }
        cb(field, wire, Reader{p, p + 4}, 0);
        p += 4;
      } else if (wire == 1) {
        if (p + 8 > end) { ok = false; return; }
        cb(field, wire, Reader{p, p + 8}, 0);
        p += 8;
      } else {
        ok = false;
        return;
      }
    }
  }
  std::string str() const {
    // wire-0 fields hand sub-readers a null range: an empty string,
    // never std::string(nullptr, 0) (UB; fuzzing finding, ISSUE 11)
    return p ? std::string((const char*)p, end - p) : std::string();
  }
  std::vector<int64_t> packed_varints() const {
    Reader r{p, end};
    std::vector<int64_t> out;
    while (r.ok && r.p < r.end) {
      uint64_t v = r.varint();
      out.push_back(int64_t(v));  // two's complement for negatives
    }
    return out;
  }
};

// ----------------------------------------------------------------- tensors
// ONNX TensorProto dtype codes (subset)
enum { DT_F32 = 1, DT_U8 = 2, DT_I8 = 3, DT_I32 = 6, DT_I64 = 7,
       DT_BOOL = 9, DT_F64 = 11 };

/* Tensor storage: either an owning vector or a borrowed view into the
 * predictor's planned arena (static memory planner, see plan_memory).
 * Copies always deep-copy into owned storage — a Tensor copied out of
 * `env` (Identity, run outputs) must survive the arena being rewritten
 * by the next run. Moves keep the binding. */
template <class T>
class Buf {
 public:
  Buf() = default;
  Buf(const Buf& o) : own_(o.begin(), o.end()) {}
  Buf(Buf&& o) noexcept = default;
  Buf& operator=(const Buf& o) {
    if (this != &o) {
      own_.assign(o.begin(), o.end());
      ext_ = nullptr;
      extn_ = 0;
    }
    return *this;
  }
  Buf& operator=(Buf&& o) noexcept = default;

  T* data() { return ext_ ? ext_ : own_.data(); }
  const T* data() const { return ext_ ? ext_ : own_.data(); }
  size_t size() const { return ext_ ? extn_ : own_.size(); }
  bool empty() const { return size() == 0; }
  T& operator[](size_t k) { return data()[k]; }
  const T& operator[](size_t k) const { return data()[k]; }
  T* begin() { return data(); }
  T* end() { return data() + size(); }
  const T* begin() const { return data(); }
  const T* end() const { return data() + size(); }

  template <class It,
            class = typename std::enable_if<
                !std::is_integral<It>::value>::type>
  void assign(It first, It last) {
    own_.assign(first, last);
    ext_ = nullptr;
    extn_ = 0;
  }
  void assign(size_t n, T v) {
    own_.assign(n, v);
    ext_ = nullptr;
    extn_ = 0;
  }
  void resize(size_t n) {
    if (ext_) {  // degrade to owning, preserving contents like vector
      own_.assign(ext_, ext_ + std::min(extn_, n));
      ext_ = nullptr;
      extn_ = 0;
    }
    own_.resize(n);
  }
  // borrow arena storage; contents are whatever the arena holds — every
  // op fully writes its output (audited), so no zero-fill is needed
  void bind(T* p, size_t n) {
    own_.clear();
    ext_ = p;
    extn_ = n;
  }

 private:
  T* ext_ = nullptr;
  size_t extn_ = 0;
  std::vector<T> own_;
};

/* Where Tensor::alloc should place the next output: set by the executor
 * per node from the static memory plan; consumed at most once (one
 * output per node). thread_local because predictors are
 * one-per-thread by contract. */
struct AllocHint {
  char* base = nullptr;
  size_t bytes = 0;
  bool used = false;
};
static thread_local AllocHint* g_alloc_hint = nullptr;

struct Tensor {
  std::vector<int64_t> dims;
  int dtype = DT_F32;
  Buf<float> f;    // DT_F32 / DT_F64 (converted)
  Buf<int64_t> i;  // DT_I32 / DT_I64 / DT_BOOL / DT_U8
  int64_t numel() const {
    // hostile artifacts carry arbitrary dims: negative or
    // product-overflowing shapes must surface as a load error, not
    // signed-overflow UB (fuzzing finding, ISSUE 11; repro:
    // csrc/fuzz/corpus/onnx/crash-numel-overflow.bin)
    uint64_t n = 1;
    for (auto d : dims) {
      if (d < 0) throw std::runtime_error("tensor dim < 0");
      if (d != 0 && n > uint64_t(INT64_MAX) / uint64_t(d))
        throw std::runtime_error("tensor element count overflows");
      n *= uint64_t(d);
    }
    return int64_t(n);
  }
  bool is_float() const { return dtype == DT_F32 || dtype == DT_F64; }
  double at(int64_t k) const { return is_float() ? f[k] : double(i[k]); }
  void alloc() {
    const size_t n = size_t(numel());
    const size_t bytes = n * (is_float() ? sizeof(float) : sizeof(int64_t));
    /* Single-tensor sanity cap (fuzzing finding, ISSUE 11; repro:
     * csrc/fuzz/corpus/onnx/crash-expand-petabytes.bin): a hostile
     * graph can COMPUTE a petabyte output shape (broadcast/Expand) —
     * the load-time dry run must fail with an error, not an OOM
     * abort. 8 GiB is far above any real serving tensor and far
     * below the allocator's hard limits. */
    if (bytes > (size_t(1) << 33))
      throw std::runtime_error(
          "tensor allocation of " + std::to_string(bytes) +
          " bytes exceeds the 8 GiB per-tensor sanity cap");
    if (g_alloc_hint && !g_alloc_hint->used && bytes <= g_alloc_hint->bytes) {
      g_alloc_hint->used = true;
      if (is_float()) f.bind(reinterpret_cast<float*>(g_alloc_hint->base), n);
      else i.bind(reinterpret_cast<int64_t*>(g_alloc_hint->base), n);
      return;
    }
    if (is_float()) f.assign(n, 0.f);
    else i.assign(n, int64_t(0));
  }
  void set(int64_t k, double v) {
    if (is_float()) f[k] = float(v);
    else i[k] = int64_t(v);
  }
};

struct Attr {
  float fval = 0;
  int64_t ival = 0;
  std::string sval;
  std::vector<int64_t> ints;
  std::vector<float> floats;
  Tensor t;
  int type = 0;
};

struct Node {
  std::string op;
  std::vector<std::string> inputs, outputs;
  std::map<std::string, Attr> attrs;
  /* Per-node autotune memo (ptpu_tune.h): the resolved kernel config
   * for the last-seen GEMM M (shapes are static per artifact, but the
   * bucket ladder re-plans M per bucket). mutable: exec takes const
   * Node&, and a predictor's run() is thread-compatible (one thread),
   * so the memo needs no lock — the cross-instance source of truth is
   * the locked tune::Registry. */
  mutable int64_t tune_m = -1;
  mutable int32_t tune_path = 0, tune_kc = 0, tune_mult = 0;
};

struct Graph {
  std::vector<Node> nodes;
  std::map<std::string, Tensor> initializers;
  std::vector<std::string> input_names, output_names;
  std::map<std::string, std::vector<int64_t>> input_dims;
  std::map<std::string, int> input_dtypes;
};

Tensor parse_tensor(Reader r) {
  Tensor t;
  std::string raw;
  r.fields([&](int field, int wire, Reader sub, uint64_t v) {
    if (field == 1 && wire == 2) t.dims = sub.packed_varints();
    else if (field == 1 && wire == 0) t.dims.push_back(int64_t(v));
    else if (field == 2) t.dtype = int(v);
    else if (field == 9) raw = sub.str();
  });
  int64_t n = t.numel();
  /* Truncation guard (fuzzing finding, ISSUE 11; repro:
   * corpus/onnx/crash-initializer-claims-tb.bin): the claimed element
   * count must be backed by the raw payload BEFORE the buffer is
   * sized — a 100-byte artifact must not be able to demand a
   * terabyte-scale allocation (and a short raw block used to
   * zero-fill weights SILENTLY, which is corruption, not tolerance).
   * Raw-less initializers (legal: zero tensors) are capped at 16M
   * elements — shape/constant tensors, not weights. */
  {
    const int64_t esz = t.dtype == DT_F64 || t.dtype == DT_I64 ? 8
                        : t.dtype == DT_BOOL || t.dtype == DT_U8 ||
                                t.dtype == DT_I8
                            ? 1
                            : 4;
    if (raw.empty()) {
      if (n > (int64_t(1) << 24))
        throw std::runtime_error(
            "initializer with no raw data claims " + std::to_string(n) +
            " elements");
    } else if (uint64_t(raw.size()) / uint64_t(esz) <
               uint64_t(n)) {  // divide: n * esz could overflow
      throw std::runtime_error(
          "initializer raw data truncated: " + std::to_string(n) +
          " elements claimed, " + std::to_string(raw.size()) +
          " bytes present");
    }
  }
  // n == 0 (a dim of 0): the destination buffer is empty and data()
  // NULL — memcpy(NULL, ..., 0) is UB by declaration and aborts a
  // fail-fast build (fuzzing finding, ISSUE 11; repro:
  // corpus/onnx/crash-zero-elem-initializer.bin). Guard n, not size.
  if (t.dtype == DT_F32) {
    t.f.resize(size_t(n));
    if (n > 0 && raw.size() >= size_t(n) * 4)
      memcpy(t.f.data(), raw.data(), n * 4);
  } else if (t.dtype == DT_F64) {
    // raw sits at an arbitrary protobuf offset: per-element memcpy
    // (one unaligned mov) instead of a cast-deref, which is UB
    t.f.resize(size_t(n));
    if (raw.size() >= size_t(n) * 8)
      for (int64_t k = 0; k < n; ++k) {
        double dv;
        memcpy(&dv, raw.data() + 8 * k, 8);
        t.f[size_t(k)] = float(dv);
      }
    t.dtype = DT_F32;
  } else if (t.dtype == DT_I64) {
    t.i.resize(size_t(n));
    if (n > 0 && raw.size() >= size_t(n) * 8)
      memcpy(t.i.data(), raw.data(), n * 8);
  } else if (t.dtype == DT_I32) {
    t.i.resize(size_t(n));
    if (raw.size() >= size_t(n) * 4)
      for (int64_t k = 0; k < n; ++k) {
        int32_t iv;
        memcpy(&iv, raw.data() + 4 * k, 4);
        t.i[size_t(k)] = iv;
      }
  } else if (t.dtype == DT_BOOL || t.dtype == DT_U8) {
    // raw may legally be ABSENT (zero tensor): the byte loops must
    // not read past an empty string like the word-size branches
    // already don't (fuzzing finding, ISSUE 11; repro:
    // corpus/onnx/crash-u8-no-raw.bin) — resize() zero-fills
    t.i.resize(size_t(n));
    if (int64_t(raw.size()) >= n) {
      const uint8_t* d = (const uint8_t*)raw.data();
      for (int64_t k = 0; k < n; ++k) t.i[size_t(k)] = d[k];
    }
  } else if (t.dtype == DT_I8) {
    t.i.resize(size_t(n));
    if (int64_t(raw.size()) >= n) {
      const int8_t* d = (const int8_t*)raw.data();
      for (int64_t k = 0; k < n; ++k) t.i[size_t(k)] = d[k];
    }
  } else {
    throw std::runtime_error("initializer dtype " +
                             std::to_string(t.dtype) + " unsupported");
  }
  return t;
}

Attr parse_attr(Reader r, std::string* name) {
  Attr a;
  r.fields([&](int field, int wire, Reader sub, uint64_t v) {
    if (field == 1) *name = sub.str();
    else if (field == 2) {
      // AttributeProto.f is wire type 5 (4 bytes); a hostile varint
      // encoding of field 2 hands a null/short reader — reading 4
      // bytes from it is the crash csrc/fuzz/corpus/onnx/
      // crash-attr-f-as-varint.bin reproduces (fuzzing finding)
      if (sub.end - sub.p >= 4) memcpy(&a.fval, sub.p, 4);
    }
    else if (field == 3) a.ival = int64_t(v);
    else if (field == 4) a.sval = sub.str();
    else if (field == 5) a.t = parse_tensor(sub);
    else if (field == 7) {  // packed floats (arbitrary file offset)
      a.floats.resize(size_t(sub.end - sub.p) / 4);
      if (!a.floats.empty())
        memcpy(a.floats.data(), sub.p, a.floats.size() * 4);
    } else if (field == 8) {
      if (wire == 2) a.ints = sub.packed_varints();
      else a.ints.push_back(int64_t(v));
    } else if (field == 20) a.type = int(v);
  });
  return a;
}

Node parse_node(Reader r) {
  Node n;
  r.fields([&](int field, int, Reader sub, uint64_t) {
    if (field == 1) n.inputs.push_back(sub.str());
    else if (field == 2) n.outputs.push_back(sub.str());
    else if (field == 4) n.op = sub.str();
    else if (field == 5) {
      std::string name;
      Attr a = parse_attr(sub, &name);
      n.attrs[name] = a;
    }
  });
  return n;
}

void parse_value_info(Reader r, std::string* name, std::vector<int64_t>* dims,
                      int* dtype) {
  r.fields([&](int field, int, Reader sub, uint64_t) {
    if (field == 1) *name = sub.str();
    else if (field == 2) {  // TypeProto
      sub.fields([&](int f2, int, Reader s2, uint64_t) {
        if (f2 != 1) return;  // tensor_type
        s2.fields([&](int f3, int, Reader s3, uint64_t v3) {
          if (f3 == 1) *dtype = int(v3);
          else if (f3 == 2) {  // shape
            s3.fields([&](int f4, int, Reader s4, uint64_t) {
              if (f4 != 1) return;  // dim
              s4.fields([&](int f5, int, Reader, uint64_t v5) {
                if (f5 == 1) dims->push_back(int64_t(v5));
              });
            });
          }
        });
      });
    }
  });
}

Graph parse_model(const std::string& bytes) {
  Graph g;
  Reader top{(const uint8_t*)bytes.data(),
             (const uint8_t*)bytes.data() + bytes.size()};
  top.fields([&](int field, int, Reader sub, uint64_t) {
    if (field != 7) return;  // ModelProto.graph
    sub.fields([&](int f2, int, Reader s2, uint64_t) {
      if (f2 == 1) g.nodes.push_back(parse_node(s2));
      else if (f2 == 5) {
        // initializer: need the name field (8) too
        std::string name;
        Reader nr = s2;
        nr.fields([&](int f3, int, Reader s3, uint64_t) {
          if (f3 == 8) name = s3.str();
        });
        g.initializers[name] = parse_tensor(s2);
      } else if (f2 == 11 || f2 == 12) {
        std::string name;
        std::vector<int64_t> dims;
        int dt = DT_F32;
        parse_value_info(s2, &name, &dims, &dt);
        if (f2 == 11) {
          g.input_names.push_back(name);
          g.input_dims[name] = dims;
          g.input_dtypes[name] = dt;
        } else {
          g.output_names.push_back(name);
        }
      }
    });
  });
  if (!top.ok) throw std::runtime_error("malformed model protobuf");
  return g;
}

// ------------------------------------------------------------ broadcasting
std::vector<int64_t> bcast_dims(const std::vector<int64_t>& a,
                                const std::vector<int64_t>& b) {
  size_t rank = std::max(a.size(), b.size());
  std::vector<int64_t> out(rank);
  for (size_t k = 0; k < rank; ++k) {
    int64_t da = k < rank - a.size() ? 1 : a[k - (rank - a.size())];
    int64_t db = k < rank - b.size() ? 1 : b[k - (rank - b.size())];
    if (da != db && da != 1 && db != 1)
      throw std::runtime_error("broadcast mismatch");
    // numpy semantics, NOT max(): a ZERO dim against 1 broadcasts to
    // ZERO — max() manufactured elements out of an empty operand and
    // the kernels then read past its storage (fuzzing finding, ISSUE
    // 11; repro: corpus/onnx/crash-reshape-marker-mismatch.bin)
    out[k] = da == 1 ? db : da;
  }
  return out;
}

std::vector<int64_t> strides_for(const std::vector<int64_t>& dims) {
  std::vector<int64_t> s(dims.size());
  // unsigned accumulation: a ZERO-element shape (which passes every
  // numel guard) can still carry huge sibling dims whose partial
  // product overflows int64 — defined wrap instead of UB; strides of
  // an empty tensor are never dereferenced (fuzzing finding, ISSUE
  // 11; repro: csrc/fuzz/corpus/onnx/crash-strides-overflow.bin).
  // Non-empty shapes are safe: every partial product divides numel,
  // which the overflow-checked Tensor::numel() already bounds.
  uint64_t acc = 1;
  for (int k = int(dims.size()) - 1; k >= 0; --k) {
    s[size_t(k)] = int64_t(acc);
    acc *= uint64_t(dims[size_t(k)]);
  }
  return s;
}

// index of `flat` (in out dims) within operand dims (right-aligned bcast)
int64_t bcast_index(int64_t flat, const std::vector<int64_t>& out_dims,
                    const std::vector<int64_t>& in_dims) {
  auto ostr = strides_for(out_dims);
  auto istr = strides_for(in_dims);
  int64_t idx = 0;
  size_t off = out_dims.size() - in_dims.size();
  for (size_t k = 0; k < out_dims.size(); ++k) {
    int64_t coord = (flat / ostr[k]) % out_dims[k];
    if (k >= off) {
      int64_t d = in_dims[k - off];
      idx += (d == 1 ? 0 : coord) * istr[k - off];
    }
  }
  return idx;
}

// ------------------------------------------------------------ fast path
// Deployment-class CPU execution (the reference's native engine is an
// optimized runtime — `inference/api/analysis_predictor.cc:381` runs an
// IR pass pipeline before an optimized executor). This block gives the
// C-ABI interpreter the three levers that matter on CPU: a blocked,
// multi-threaded SGEMM feeding MatMul AND Conv (via im2col), O(1)
// op-code dispatch resolved once per node instead of per-element string
// compares, and odometer index walks instead of per-element div/mod
// broadcasting.

static int num_threads() {
  static const int n = [] {
    const char* e = std::getenv("PTPU_PREDICTOR_THREADS");
    int v = e ? std::atoi(e) : 0;
    if (v <= 0) v = int(std::thread::hardware_concurrency());
    return std::max(1, std::min(v, 64));
  }();
  return n;
}

/* Persistent worker pool: spawning/joining std::threads per GEMM call
 * costs tens of microseconds x threads, paid once per node per
 * inference in a deep model. Workers park on a condition variable
 * between dispatches; the caller thread participates in the chunk
 * loop (chunked-range claiming via the atomic `next_` cursor IS the
 * work stealing — fast workers keep taking chunks until the range is
 * drained). Nested calls from inside a worker run serially
 * (thread_local guard) instead of deadlocking the pool.
 *
 * The default pool is process-global and `dispatch_mu_` serializes
 * whole dispatches (overwriting fn_/n_/chunk_ and resetting done_
 * mid-flight corrupted outputs or deadlocked cv_done_ before). One
 * GEMM can saturate every core, so serialized dispatch loses nothing
 * for a single predictor — but it also means N concurrent predictors
 * serve at 1x aggregate. For concurrent serving, WorkPool is now
 * instantiable: a predictor (or a serving instance) can own a PRIVATE
 * sub-pool of W threads, and run() routes its dispatches there via the
 * thread_local g_active_pool, so two instances with disjoint sub-pools
 * execute truly in parallel instead of queueing on the global
 * dispatch mutex. */
// WorkPool lock classes (rank table: README "Correctness tooling"):
// the dispatch lock is DESIGNED to be held across the cv_done_ wait
// (it serializes whole dispatches) -> kLockAllowBlock; the state lock
// nests inside it and is the leaf of every execution path.
PTPU_LOCK_CLASS(kLockWpDispatch, "wp.dispatch", 60, ptpu::kLockAllowBlock);
PTPU_LOCK_CLASS(kLockWpState, "wp.state", 70);

class WorkPool {
 public:
  explicit WorkPool(int n_workers) {
    for (int t = 0; t < n_workers; ++t)
      workers_.emplace_back([this] { worker(); });
  }

  static WorkPool& inst() {
    static WorkPool p(num_threads() - 1);
    return p;
  }

  void run(int64_t n, int64_t grain,
           const std::function<void(int64_t, int64_t)>& fn) {
    if (workers_.empty() || n <= grain || in_worker_) {
      fn(0, n);
      return;
    }
    ptpu::MutexLock dispatch(dispatch_mu_);
    const int64_t parts = int64_t(workers_.size() + 1) * 4;
    const int64_t chunk = std::max(grain, (n + parts - 1) / parts);
    const int64_t chunks = (n + chunk - 1) / chunk;
    {
      ptpu::MutexLock l(mu_);
      fn_ = &fn;
      n_ = n;
      chunk_ = chunk;
      next_.store(0, std::memory_order_relaxed);
      ++epoch_;
    }
    /* Wake only as many workers as there are chunks beyond the
     * caller's own: a 2-chunk elementwise op used to broadcast-wake
     * the whole pool and then wait for EVERY worker to wake and ack —
     * ~0.5 ms of pure futex traffic per op on a wide box. Workers
     * that stay asleep never join the epoch, and the completion wait
     * below only covers workers that actually claimed work. */
    const int wake = int(std::min<int64_t>(int64_t(workers_.size()),
                                           chunks - 1));
    if (wake >= int(workers_.size())) {
      cv_go_.notify_all();  // one broadcast beats W futex calls
    } else {
      for (int w = 0; w < wake; ++w) cv_go_.notify_one();
    }
    // the caller thread acts as a worker for this dispatch: mark it so
    // a nested parallel_for from inside fn runs serially instead of
    // re-entering run() and self-deadlocking on dispatch_mu_
    in_worker_ = true;
    try {
      drain(fn, n, chunk);
    } catch (...) {
      // fn threw on the caller's chunk: restore the flag and STILL
      // wait for the joined workers — fn_ must not dangle past this
      // frame
      in_worker_ = false;
      ptpu::UniqueLock l(mu_);
      cv_done_.wait(l, [&] {
        return active_ == 0 && next_.load(std::memory_order_relaxed) >= n_;
      });
      fn_ = nullptr;
      throw;
    }
    in_worker_ = false;
    ptpu::UniqueLock l(mu_);
    cv_done_.wait(l, [&] {
      return active_ == 0 && next_.load(std::memory_order_relaxed) >= n_;
    });
    fn_ = nullptr;
  }

  ~WorkPool() {
    {
      ptpu::MutexLock l(mu_);
      stop_ = true;
    }
    cv_go_.notify_all();
    for (auto& t : workers_) t.join();
  }

 private:
  void drain(const std::function<void(int64_t, int64_t)>& fn, int64_t n,
             int64_t chunk) {
    for (;;) {
      const int64_t lo = next_.fetch_add(chunk, std::memory_order_relaxed);
      if (lo >= n) break;
      fn(lo, std::min(n, lo + chunk));
    }
  }

  void worker() {
    in_worker_ = true;
    int seen = 0;
    for (;;) {
      const std::function<void(int64_t, int64_t)>* fn;
      int64_t n, chunk;
      {
        ptpu::UniqueLock l(mu_);
        cv_go_.wait(l, [&] { return stop_ || epoch_ != seen; });
        if (stop_) return;
        seen = epoch_;
        fn = fn_;
        n = n_;
        chunk = chunk_;
        if (!fn) continue;  // dispatch already fully retired
        ++active_;  // joined while fn_ was valid: the caller waits for us
      }
      drain(*fn, n, chunk);
      {
        ptpu::MutexLock l(mu_);
        if (--active_ == 0) cv_done_.notify_one();
      }
    }
  }

  std::vector<std::thread> workers_;
  ptpu::Mutex mu_{kLockWpState}, dispatch_mu_{kLockWpDispatch};
  ptpu::CondVar cv_go_, cv_done_;
  const std::function<void(int64_t, int64_t)>* fn_ = nullptr;
  int64_t n_ = 0, chunk_ = 1;
  std::atomic<int64_t> next_{0};
  int epoch_ = 0, active_ = 0;
  bool stop_ = false;
  static thread_local bool in_worker_;
};

thread_local bool WorkPool::in_worker_ = false;

/* The execution context of the current thread: parallel_for dispatches
 * to the private sub-pool a predictor was created with (PoolScope set
 * by Predictor::run), falling back to the shared global pool. Private
 * pools are what make N predictor instances scale — each instance's
 * GEMMs fan out over its own workers with its own dispatch mutex. */
static thread_local WorkPool* g_active_pool = nullptr;

struct PoolScope {
  WorkPool* prev;
  explicit PoolScope(WorkPool* p) : prev(g_active_pool) {
    if (p) g_active_pool = p;
  }
  ~PoolScope() { g_active_pool = prev; }
};

template <class F>
static void parallel_for(int64_t n, int64_t grain, const F& fn) {
  (g_active_pool ? *g_active_pool : WorkPool::inst()).run(n, grain, fn);
}

/* ------------------------------------------------------------------
 * Packed cache-blocked GEMM: C[M,N] = A[M,K] @ B[K,N], row-major.
 *
 * BLIS-style formulation: both operands are repacked into contiguous
 * panel buffers — A into MR-row panels laid out [panel][k][r], B into
 * NR-column panels laid out [panel][k][c] — so the inner kernel reads
 * both operands with stride-1 and keeps an MR x NR accumulator block
 * entirely in registers across a KC-deep slice (6x16 fp32 = 12 ymm
 * accumulators + broadcast + B row under AVX2). K is blocked by KC so
 * the NR-wide B slice (NR*KC*4 = 20 KB) stays L1-resident while a row
 * block of A panels streams through L2. The k-loop accumulation order
 * is unchanged from the old blocked loop, and there is no zero-skip:
 * 0 * Inf/NaN must stay NaN (IEEE), matching the scalar fallback and
 * XLA on masked/one-hot operands (packed zero PADDING lanes never
 * reach memory, so they cannot launder a NaN).
 *
 * The same machinery serves fp32 and the int8-executing int32 path
 * (int64 multiplies have no AVX2 form; int8 operands with int32
 * accumulation are exact for K < 2^31/128^2, enforced by int8_exact).
 * The epilogue fuses bias (per-row for conv's [oc, P] layout, per-col
 * for MatMul's [M, out_features]) and the activation into the final
 * register-block writeback — the load-time op-fusion pass rewrites
 * conv+bias+relu / gemm+bias+act chains onto these arguments. */
constexpr int64_t MR = 6, NR = 16, KC = 320;

enum { ACT_NONE = 0, ACT_RELU = 1, ACT_SIGMOID = 2, ACT_TANH = 3 };

static inline float act_apply(float v, int act) {
  switch (act) {
    case ACT_RELU: return v > 0.f ? v : 0.f;
    case ACT_SIGMOID: return float(1.0 / (1.0 + std::exp(-double(v))));
    case ACT_TANH: return float(std::tanh(double(v)));
    default: return v;
  }
}
static inline int32_t act_apply(int32_t v, int act) {
  return act == ACT_RELU ? (v > 0 ? v : 0) : v;
}

static inline int64_t a_pack_size(int64_t M, int64_t K) {
  return ((M + MR - 1) / MR) * K * MR;
}
static inline int64_t b_pack_size(int64_t K, int64_t N) {
  return ((N + NR - 1) / NR) * K * NR;
}

// S: source element type (float / int64 widened storage), T: compute type
template <class S, class T>
static void pack_a(const S* A, int64_t M, int64_t K, T* out) {
  const int64_t panels = (M + MR - 1) / MR;
  // a panel costs K*MR element moves: stay serial unless that pays
  // for a pool dispatch
  const int64_t grain =
      std::max<int64_t>(1, 65536 / std::max<int64_t>(K * MR, 1));
  parallel_for(panels, grain, [&](int64_t p0, int64_t p1) {
    for (int64_t p = p0; p < p1; ++p) {
      T* dst = out + p * K * MR;
      const int64_t mr = std::min(MR, M - p * MR);
      for (int64_t r = 0; r < mr; ++r) {
        const S* src = A + (p * MR + r) * K;
        for (int64_t k = 0; k < K; ++k) dst[k * MR + r] = T(src[k]);
      }
      for (int64_t r = mr; r < MR; ++r)  // fringe rows pad with zeros
        for (int64_t k = 0; k < K; ++k) dst[k * MR + r] = T(0);
    }
  });
}

template <class S, class T>
static void pack_b(const S* B, int64_t K, int64_t N, T* out) {
  const int64_t panels = (N + NR - 1) / NR;
  const int64_t grain =
      std::max<int64_t>(1, 65536 / std::max<int64_t>(K * NR, 1));
  parallel_for(panels, grain, [&](int64_t p0, int64_t p1) {
    for (int64_t p = p0; p < p1; ++p) {
      T* dst = out + p * K * NR;
      const int64_t j0 = p * NR, w = std::min(NR, N - j0);
      for (int64_t k = 0; k < K; ++k) {
        const S* src = B + k * N + j0;
        T* d = dst + k * NR;
        for (int64_t c = 0; c < w; ++c) d[c] = T(src[c]);
        for (int64_t c = w; c < NR; ++c) d[c] = T(0);
      }
    }
  });
}

/* One MR x NR register tile over a KC-deep panel slice. `first` zeroes
 * the accumulator (k0 == 0), otherwise the partial C block is loaded;
 * `last` applies the fused bias/activation epilogue on writeback.
 * bias_n/bias_m are pre-offset to this tile's column/row origin. */
template <class T>
static inline void micro_kernel(const T* Ap, const T* Bp, T* C, int64_t ldc,
                                int64_t kc, int64_t mr, int64_t nr,
                                bool first, bool last, const T* bias_n,
                                const T* bias_m, int act) {
  T acc[MR][NR];
  for (int r = 0; r < MR; ++r)
    for (int c = 0; c < NR; ++c) acc[r][c] = T(0);
  if (!first)
    for (int64_t r = 0; r < mr; ++r)
      for (int64_t c = 0; c < nr; ++c) acc[r][c] = C[r * ldc + c];
  for (int64_t k = 0; k < kc; ++k) {
    const T* a = Ap + k * MR;
    const T* b = Bp + k * NR;
    for (int r = 0; r < MR; ++r) {
      const T av = a[r];
      for (int c = 0; c < NR; ++c) acc[r][c] += av * b[c];
    }
  }
  if (last && (bias_n || bias_m || act != ACT_NONE)) {
    for (int64_t r = 0; r < mr; ++r) {
      const T bm = bias_m ? bias_m[r] : T(0);
      for (int64_t c = 0; c < nr; ++c) {
        const T v = acc[r][c] + bm + (bias_n ? bias_n[c] : T(0));
        C[r * ldc + c] = act_apply(v, act);
      }
    }
  } else {
    for (int64_t r = 0; r < mr; ++r)
      for (int64_t c = 0; c < nr; ++c) C[r * ldc + c] = acc[r][c];
  }
}

#if defined(__x86_64__) || defined(__i386__)
#define PTPU_X86 1
/* gcc 12's avx512fintrin.h spells "undefined" as `__m512 __Y = __Y;`,
 * which g++ 12 reports as -Wmaybe-uninitialized wherever an AVX-512
 * intrinsic is inlined (GCC PR 105593, fixed in 13). The diagnostic's
 * location is the header, so silencing it around the include covers
 * exactly the header's own code and keeps -Werror whole for ours. */
#pragma GCC diagnostic push
#pragma GCC diagnostic ignored "-Wmaybe-uninitialized"
#include <immintrin.h>
#pragma GCC diagnostic pop
#endif

/* Runtime ISA dispatch (ISSUE r9 tentpole b). The shipped .so builds
 * at the portable x86-64-v2 baseline, which used to mean NO vector
 * micro-kernel at all unless the user rebuilt with -march=native. The
 * vector kernels now compile unconditionally behind function-level
 * `target` attributes (usable since GCC 4.9 without -mavx* on the
 * command line) and ONE load-time cpuid probe picks the widest level
 * the machine actually has: AVX-512F (one zmm per accumulator row),
 * AVX2+FMA (the classic 12-ymm tile), or the portable scalar kernel.
 * PTPU_ISA=generic|avx2|avx512 caps the level for parity testing —
 * it can only lower, never enable what cpuid denies. */
enum { ISA_GENERIC = 0, ISA_AVX2 = 1, ISA_AVX512 = 2 };

static int isa_level() {
#ifdef PTPU_X86
  static const int lvl = [] {
    const bool avx2 = __builtin_cpu_supports("avx2") &&
                      __builtin_cpu_supports("fma");
    const bool avx512 = avx2 && __builtin_cpu_supports("avx512f") &&
                        __builtin_cpu_supports("avx512bw");
    int got = avx512 ? ISA_AVX512 : avx2 ? ISA_AVX2 : ISA_GENERIC;
    const char* e = std::getenv("PTPU_ISA");
    if (e) {
      if (!std::strcmp(e, "generic")) got = ISA_GENERIC;
      else if (!std::strcmp(e, "avx2")) got = std::min(got, int(ISA_AVX2));
    }
    return got;
  }();
  return lvl;
#else
  return ISA_GENERIC;
#endif
}

// AVX-512-VNNI int8 dot-product path (vpdpwssd over int16 pairs —
// exact for int8 operands with int32 accumulation, same bound as
// int8_depth_ok). PTPU_ISA / PTPU_ISA_VNNI=0 disable it for parity
// runs; the int32 packed path remains the fallback everywhere.
static bool isa_vnni() {
#ifdef PTPU_X86
  static const bool v = [] {
    const char* e = std::getenv("PTPU_ISA_VNNI");
    if (e && !std::strcmp(e, "0")) return false;
    return isa_level() == ISA_AVX512 &&
           bool(__builtin_cpu_supports("avx512vnni"));
  }();
  return v;
#else
  return false;
#endif
}

#ifdef PTPU_X86
/* Hand-vectorized full-tile fp32 micro-kernel: 6x16 = 12 ymm
 * accumulators + 2 B lanes + 1 broadcast — 15 of 16 registers, the
 * classic AVX2 register allocation. GCC only partially promotes the
 * generic template's accumulator array (measured ~5 GFLOP/s/core vs
 * ~50 here), so the hot full tiles get intrinsics; fringe tiles stay
 * on the generic kernel. */
__attribute__((target("avx2,fma")))
static void micro_tile_avx2(const float* Ap, const float* Bp,
                                   float* C, int64_t ldc, int64_t kc,
                                   bool first, bool last,
                                   const float* bias_n, const float* bias_m,
                                   int act) {
  __m256 acc[MR][2];
  if (first) {
    for (int r = 0; r < MR; ++r)
      acc[r][0] = acc[r][1] = _mm256_setzero_ps();
  } else {
    for (int r = 0; r < MR; ++r) {
      acc[r][0] = _mm256_loadu_ps(C + r * ldc);
      acc[r][1] = _mm256_loadu_ps(C + r * ldc + 8);
    }
  }
  for (int64_t k = 0; k < kc; ++k) {
    const __m256 b0 = _mm256_loadu_ps(Bp + k * NR);
    const __m256 b1 = _mm256_loadu_ps(Bp + k * NR + 8);
    const float* a = Ap + k * MR;
    for (int r = 0; r < MR; ++r) {
      const __m256 av = _mm256_broadcast_ss(a + r);
      acc[r][0] = _mm256_fmadd_ps(av, b0, acc[r][0]);
      acc[r][1] = _mm256_fmadd_ps(av, b1, acc[r][1]);
    }
  }
  if (last && (bias_n || bias_m || act != ACT_NONE)) {
    if (act == ACT_NONE || act == ACT_RELU) {
      const __m256 zero = _mm256_setzero_ps();
      const __m256 bn0 = bias_n ? _mm256_loadu_ps(bias_n) : zero;
      const __m256 bn1 = bias_n ? _mm256_loadu_ps(bias_n + 8) : zero;
      for (int r = 0; r < MR; ++r) {
        const __m256 bm =
            bias_m ? _mm256_broadcast_ss(bias_m + r) : zero;
        __m256 v0 = _mm256_add_ps(_mm256_add_ps(acc[r][0], bn0), bm);
        __m256 v1 = _mm256_add_ps(_mm256_add_ps(acc[r][1], bn1), bm);
        if (act == ACT_RELU) {
          v0 = _mm256_max_ps(v0, zero);
          v1 = _mm256_max_ps(v1, zero);
        }
        _mm256_storeu_ps(C + r * ldc, v0);
        _mm256_storeu_ps(C + r * ldc + 8, v1);
      }
    } else {  // transcendental epilogue: spill the tile, apply scalar
      float tile[MR][NR];
      for (int r = 0; r < MR; ++r) {
        _mm256_storeu_ps(tile[r], acc[r][0]);
        _mm256_storeu_ps(tile[r] + 8, acc[r][1]);
      }
      for (int r = 0; r < MR; ++r) {
        const float bm = bias_m ? bias_m[r] : 0.f;
        for (int c = 0; c < NR; ++c)
          C[r * ldc + c] = act_apply(
              tile[r][c] + bm + (bias_n ? bias_n[c] : 0.f), act);
      }
    }
  } else {
    for (int r = 0; r < MR; ++r) {
      _mm256_storeu_ps(C + r * ldc, acc[r][0]);
      _mm256_storeu_ps(C + r * ldc + 8, acc[r][1]);
    }
  }
}
/* int32 sibling (the int8-executing artifacts): vpmulld + vpaddd, same
 * 6x16 register tiling. No bias/act epilogue — the integer paths are
 * never fusion targets (their dequant chains carry Casts). */
__attribute__((target("avx2")))
static void micro_tile_avx2_i32(const int32_t* Ap, const int32_t* Bp,
                                       int32_t* C, int64_t ldc, int64_t kc,
                                       bool first) {
  __m256i acc[MR][2];
  if (first) {
    for (int r = 0; r < MR; ++r)
      acc[r][0] = acc[r][1] = _mm256_setzero_si256();
  } else {
    for (int r = 0; r < MR; ++r) {
      acc[r][0] =
          _mm256_loadu_si256(reinterpret_cast<const __m256i*>(C + r * ldc));
      acc[r][1] = _mm256_loadu_si256(
          reinterpret_cast<const __m256i*>(C + r * ldc + 8));
    }
  }
  for (int64_t k = 0; k < kc; ++k) {
    const __m256i b0 =
        _mm256_loadu_si256(reinterpret_cast<const __m256i*>(Bp + k * NR));
    const __m256i b1 = _mm256_loadu_si256(
        reinterpret_cast<const __m256i*>(Bp + k * NR + 8));
    const int32_t* a = Ap + k * MR;
    for (int r = 0; r < MR; ++r) {
      const __m256i av = _mm256_set1_epi32(a[r]);
      acc[r][0] = _mm256_add_epi32(acc[r][0], _mm256_mullo_epi32(av, b0));
      acc[r][1] = _mm256_add_epi32(acc[r][1], _mm256_mullo_epi32(av, b1));
    }
  }
  for (int r = 0; r < MR; ++r) {
    _mm256_storeu_si256(reinterpret_cast<__m256i*>(C + r * ldc),
                        acc[r][0]);
    _mm256_storeu_si256(reinterpret_cast<__m256i*>(C + r * ldc + 8),
                        acc[r][1]);
  }
}

/* AVX-512 full tile: NR == 16 floats is exactly one zmm, so the 6x16
 * tile is 6 zmm accumulators + 1 B lane + 1 broadcast — half the FMA
 * issue count of the twin-ymm AVX2 form per k step on 512-bit FMA
 * hardware. Same accumulation order, same epilogue semantics. */
__attribute__((target("avx512f")))
static void micro_tile_avx512(const float* Ap, const float* Bp, float* C,
                              int64_t ldc, int64_t kc, bool first,
                              bool last, const float* bias_n,
                              const float* bias_m, int act) {
  __m512 acc[MR];
  if (first) {
    for (int r = 0; r < MR; ++r) acc[r] = _mm512_setzero_ps();
  } else {
    for (int r = 0; r < MR; ++r) acc[r] = _mm512_loadu_ps(C + r * ldc);
  }
  for (int64_t k = 0; k < kc; ++k) {
    const __m512 b = _mm512_loadu_ps(Bp + k * NR);
    const float* a = Ap + k * MR;
    for (int r = 0; r < MR; ++r)
      acc[r] = _mm512_fmadd_ps(_mm512_set1_ps(a[r]), b, acc[r]);
  }
  if (last && (bias_n || bias_m || act != ACT_NONE)) {
    if (act == ACT_NONE || act == ACT_RELU) {
      const __m512 zero = _mm512_setzero_ps();
      const __m512 bn = bias_n ? _mm512_loadu_ps(bias_n) : zero;
      for (int r = 0; r < MR; ++r) {
        const __m512 bm = bias_m ? _mm512_set1_ps(bias_m[r]) : zero;
        __m512 v = _mm512_add_ps(_mm512_add_ps(acc[r], bn), bm);
        if (act == ACT_RELU) v = _mm512_max_ps(v, zero);
        _mm512_storeu_ps(C + r * ldc, v);
      }
    } else {  // transcendental epilogue: spill the tile, apply scalar
      float tile[MR][NR];
      for (int r = 0; r < MR; ++r) _mm512_storeu_ps(tile[r], acc[r]);
      for (int r = 0; r < MR; ++r) {
        const float bm = bias_m ? bias_m[r] : 0.f;
        for (int c = 0; c < NR; ++c)
          C[r * ldc + c] = act_apply(
              tile[r][c] + bm + (bias_n ? bias_n[c] : 0.f), act);
      }
    }
  } else {
    for (int r = 0; r < MR; ++r) _mm512_storeu_ps(C + r * ldc, acc[r]);
  }
}

__attribute__((target("avx512f")))
static void micro_tile_avx512_i32(const int32_t* Ap, const int32_t* Bp,
                                  int32_t* C, int64_t ldc, int64_t kc,
                                  bool first) {
  __m512i acc[MR];
  if (first) {
    for (int r = 0; r < MR; ++r) acc[r] = _mm512_setzero_si512();
  } else {
    for (int r = 0; r < MR; ++r)
      acc[r] = _mm512_loadu_si512(
          reinterpret_cast<const void*>(C + r * ldc));
  }
  for (int64_t k = 0; k < kc; ++k) {
    const __m512i b = _mm512_loadu_si512(
        reinterpret_cast<const void*>(Bp + k * NR));
    const int32_t* a = Ap + k * MR;
    for (int r = 0; r < MR; ++r)
      acc[r] = _mm512_add_epi32(
          acc[r], _mm512_mullo_epi32(_mm512_set1_epi32(a[r]), b));
  }
  for (int r = 0; r < MR; ++r)
    _mm512_storeu_si512(reinterpret_cast<void*>(C + r * ldc), acc[r]);
}
#endif  // PTPU_X86

// full-tile dispatch: fp32/int32 route to the widest intrinsics kernel
// the load-time cpuid probe admitted; fringe tiles stay generic
template <class T>
static inline void micro_tile(const T* Ap, const T* Bp, T* C, int64_t ldc,
                              int64_t kc, int64_t mr, int64_t nr,
                              bool first, bool last, const T* bias_n,
                              const T* bias_m, int act) {
  micro_kernel(Ap, Bp, C, ldc, kc, mr, nr, first, last, bias_n, bias_m,
               act);
}
#ifdef PTPU_X86
static inline void micro_tile(const float* Ap, const float* Bp, float* C,
                              int64_t ldc, int64_t kc, int64_t mr,
                              int64_t nr, bool first, bool last,
                              const float* bias_n, const float* bias_m,
                              int act) {
  if (mr == MR && nr == NR) {
    const int lvl = isa_level();
    if (lvl == ISA_AVX512) {
      micro_tile_avx512(Ap, Bp, C, ldc, kc, first, last, bias_n, bias_m,
                        act);
      return;
    }
    if (lvl == ISA_AVX2) {
      micro_tile_avx2(Ap, Bp, C, ldc, kc, first, last, bias_n, bias_m,
                      act);
      return;
    }
  }
  micro_kernel(Ap, Bp, C, ldc, kc, mr, nr, first, last, bias_n, bias_m,
               act);
}
static inline void micro_tile(const int32_t* Ap, const int32_t* Bp,
                              int32_t* C, int64_t ldc, int64_t kc,
                              int64_t mr, int64_t nr, bool first,
                              bool last, const int32_t* bias_n,
                              const int32_t* bias_m, int act) {
  if (mr == MR && nr == NR && !bias_n && !bias_m && act == ACT_NONE) {
    const int lvl = isa_level();
    if (lvl == ISA_AVX512) {
      micro_tile_avx512_i32(Ap, Bp, C, ldc, kc, first);
      return;
    }
    if (lvl == ISA_AVX2) {
      micro_tile_avx2_i32(Ap, Bp, C, ldc, kc, first);
      return;
    }
  }
  micro_kernel(Ap, Bp, C, ldc, kc, mr, nr, first, last, bias_n, bias_m,
               act);
}
#endif

/* Macro-kernel over pre-packed panels. Work is a 2-D grid of
 * (column-tile, row-block) tasks sized to ~3 tasks per thread so the
 * WorkPool's chunked-range stealing load-balances ragged shapes (late
 * ResNet convs: P = 49 columns but 512 rows; early: the reverse). */
/* kc_blk / task_mult <= 0 keep the compile-time defaults (KC, 3
 * tasks per thread). Nonzero values come from the per-machine
 * autotuner (ptpu_tune.h): both knobs only re-block the SAME
 * k-ascending accumulation, so every config computes bitwise-equal
 * fp32 results — a stale tuning cache can cost time, never bits. */
template <class T>
static void gemm_compute(const T* Apack, const T* Bpack, T* C,
                         int64_t M, int64_t N, int64_t K,
                         const T* bias_n, const T* bias_m, int act,
                         int64_t kc_blk = 0, int64_t task_mult = 0) {
  // degenerate extents (a hostile artifact can drive N or K to 0
  // through a zero dim): the tile-count arithmetic below divides by
  // the N tile count (fuzzing finding, ISSUE 11; repro:
  // corpus/onnx/crash-gemm-zero-n.bin). M or N zero leaves an empty
  // C; K zero is an EMPTY SUM — C still has M*N elements and the
  // arena planner never zero-fills (every op fully writes its
  // output), so the epilogue must run over acc == 0 or stale arena
  // bytes leak into the output.
  if (M <= 0 || N <= 0) return;
  if (K <= 0) {
    for (int64_t i = 0; i < M; ++i)
      for (int64_t j = 0; j < N; ++j) {
        const T v = (bias_m ? bias_m[i] : T(0)) +
                    (bias_n ? bias_n[j] : T(0));
        C[i * N + j] = act_apply(v, act);
      }
    return;
  }
  const int64_t kcb = kc_blk > 0 ? kc_blk : KC;
  const int64_t ntn = (N + NR - 1) / NR;
  const int64_t mp = (M + MR - 1) / MR;
  const int64_t want =
      (task_mult > 0 ? task_mult : int64_t(3)) * num_threads();
  int64_t nbm = std::max<int64_t>(
      int64_t(1), std::min(mp, (want + ntn - 1) / ntn));
  const int64_t per_blk = (mp + nbm - 1) / nbm;
  nbm = (mp + per_blk - 1) / per_blk;
  // small problems (attention-head matmuls) run serially: the compute
  // is microseconds, a pool dispatch is not
  const int64_t grain = M * N * K < (int64_t(1) << 21) ? ntn * nbm : 1;
  parallel_for(ntn * nbm, grain, [&](int64_t t0, int64_t t1) {
    for (int64_t t = t0; t < t1; ++t) {
      const int64_t np = t % ntn, mb = t / ntn;
      const int64_t p_lo = mb * per_blk;
      const int64_t p_hi = std::min(mp, p_lo + per_blk);
      const int64_t j0 = np * NR, nr = std::min(NR, N - j0);
      for (int64_t k0 = 0; k0 < K; k0 += kcb) {
        const int64_t kc = std::min(kcb, K - k0);
        const bool first = k0 == 0, last = k0 + kc == K;
        for (int64_t p = p_lo; p < p_hi; ++p) {
          const int64_t m0 = p * MR, mr = std::min(MR, M - m0);
          micro_tile(Apack + p * K * MR + k0 * MR,
                     Bpack + np * K * NR + k0 * NR, C + m0 * N + j0, N,
                     kc, mr, nr, first, last,
                     bias_n ? bias_n + j0 : nullptr,
                     bias_m ? bias_m + m0 : nullptr, act);
        }
      }
    }
  });
}

template <class T>
static std::vector<T>& pack_scratch(int which) {
  static thread_local std::vector<T> bufs[2];
  return bufs[which];
}

/* M == 1 GEMV: the batch-1 serving shape. The macro-kernel pads a
 * single row up to the MR=6 register tile — 6x wasted MACs through
 * the non-vectorized fringe kernel (measured 5.8 ms for the batch-1
 * MLP vs 2.9 ms for batch SIXTY-FOUR). These paths compute the one
 * row directly: per packed B panel (or raw row-major B), broadcast
 * x[k] and axpy 16-wide — auto-vectorizable fixed-bound inner loops.
 * Accumulation stays k-ascending per output, the macro-kernel's
 * order. */
template <class T, class SA>
static void gemv_packed(const SA* A, const T* Bpack, T* C, int64_t N,
                        int64_t K, const T* bias_n, T bias_m0,
                        int act) {
  const int64_t ntn = (N + NR - 1) / NR;
  const int64_t grain = N * K < (int64_t(1) << 21) ? ntn : 1;
  parallel_for(ntn, grain, [&](int64_t p0, int64_t p1) {
    for (int64_t p = p0; p < p1; ++p) {
      const T* Bp = Bpack + p * K * NR;
      T acc[NR] = {};
      for (int64_t k = 0; k < K; ++k) {
        const T av = T(A[k]);
        const T* b = Bp + k * NR;
        for (int c = 0; c < NR; ++c) acc[c] += av * b[c];
      }
      const int64_t j0 = p * NR, nr = std::min(NR, N - j0);
      for (int64_t c = 0; c < nr; ++c) {
        const T v =
            acc[c] + bias_m0 + (bias_n ? bias_n[j0 + c] : T(0));
        C[j0 + c] = act_apply(v, act);
      }
    }
  });
}

template <class T, class SA, class SB>
static void gemv_raw(const SA* A, const SB* B, T* C, int64_t N,
                     int64_t K, const T* bias_n, T bias_m0, int act) {
  // no pre-packed panel: stream row-major B once (packing it first
  // would cost more than the whole product)
  const int64_t chunk = 512;
  const int64_t nch = (N + chunk - 1) / chunk;
  const int64_t grain = N * K < (int64_t(1) << 21) ? nch : 1;
  parallel_for(nch, grain, [&](int64_t c0, int64_t c1) {
    for (int64_t ch = c0; ch < c1; ++ch) {
      const int64_t j0 = ch * chunk, j1 = std::min(N, j0 + chunk);
      T acc[chunk];
      for (int64_t j = j0; j < j1; ++j) acc[j - j0] = T(0);
      for (int64_t k = 0; k < K; ++k) {
        const T av = T(A[k]);
        const SB* row = B + k * N;
        for (int64_t j = j0; j < j1; ++j)
          acc[j - j0] += av * T(row[j]);
      }
      for (int64_t j = j0; j < j1; ++j) {
        const T v =
            acc[j - j0] + bias_m0 + (bias_n ? bias_n[j] : T(0));
        C[j] = act_apply(v, act);
      }
    }
  });
}

/* Full GEMM: packs whichever operand has no pre-packed panel (weights
 * are pre-packed ONCE at load time by Predictor::prepack_weights) and
 * runs the macro-kernel. */
template <class T, class SA, class SB>
static void gemm_bias_act(const SA* A, const SB* B, T* C, int64_t M,
                          int64_t N, int64_t K, const T* Apack_pre,
                          const T* Bpack_pre, const T* bias_n,
                          const T* bias_m, int act,
                          const ptpu::tune::TuneConfig* cfg = nullptr) {
  if (M == 1 && !Apack_pre) {  // batch-1 serving: direct GEMV
    const T bm0 = bias_m ? bias_m[0] : T(0);
    if (Bpack_pre)
      gemv_packed<T, SA>(A, Bpack_pre, C, N, K, bias_n, bm0, act);
    else
      gemv_raw<T, SA, SB>(A, B, C, N, K, bias_n, bm0, act);
    return;
  }
  /* Autotuned alternate path (kPathAlt) for small-M over pre-packed
   * weights: the MR=6 macro tile pads M=2..5 with zero rows — up to
   * 3x wasted MACs on exactly the decode-ladder bucket shapes — so
   * run each row as a packed GEMV instead. Per-row accumulation keeps
   * the macro kernel's k-ascending order (zero PADDING rows never
   * reach memory either way); only FMA contraction may differ between
   * the intrinsics tile and the auto-vectorized GEMV loop, a sub-ulp-
   * per-step effect the kernel parity selftest bounds. */
  if (cfg != nullptr && cfg->path == ptpu::tune::kPathAlt &&
      Bpack_pre != nullptr && Apack_pre == nullptr && K > 0 && N > 0) {
    for (int64_t r = 0; r < M; ++r) {
      const T bm0 = bias_m ? bias_m[r] : T(0);
      gemv_packed<T, SA>(A + r * K, Bpack_pre, C + r * N, N, K, bias_n,
                         bm0, act);
    }
    return;
  }
  const T* Ap = Apack_pre;
  const T* Bp = Bpack_pre;
  if (!Ap) {
    auto& buf = pack_scratch<T>(0);
    buf.resize(size_t(a_pack_size(M, K)));
    pack_a<SA, T>(A, M, K, buf.data());
    Ap = buf.data();
  }
  if (!Bp) {
    auto& buf = pack_scratch<T>(1);
    buf.resize(size_t(b_pack_size(K, N)));
    pack_b<SB, T>(B, K, N, buf.data());
    Bp = buf.data();
  }
  gemm_compute(Ap, Bp, C, M, N, K, bias_n, bias_m, act,
               cfg != nullptr ? cfg->kc : 0,
               cfg != nullptr ? cfg->mult : 0);
}

// plain entry points (the selftest surface; the executor calls
// gemm_bias_act directly to thread pre-packed panels and epilogues)
[[maybe_unused]] static void sgemm(const float* A, const float* B,
                                   float* C, int64_t M, int64_t N,
                                   int64_t K) {
  gemm_bias_act<float>(A, B, C, M, N, K, nullptr, nullptr, nullptr,
                       nullptr, ACT_NONE);
}
[[maybe_unused]] static void igemm(const int32_t* A, const int32_t* B,
                                   int32_t* C, int64_t M, int64_t N,
                                   int64_t K) {
  gemm_bias_act<int32_t>(A, B, C, M, N, K, nullptr, nullptr, nullptr,
                         nullptr, ACT_NONE);
}

/* ------------------------------------------------------------------
 * Weight-only int4 (ISSUE 16 tentpole a).
 *
 * Decode is GEMV-bound: every generated token streams the full weight
 * set through the core, so weight BYTES are the roofline. Group-wise
 * asymmetric 4-bit quantization cuts them 8x vs fp32: along each
 * B column, K is split into groups of Q4G values sharing one fp32
 * scale + zero-point (v ~ scale*q + zp, q in 0..15, zp = group min so
 * an all-equal group takes scale 0 and reconstructs EXACTLY — zero
 * columns and the NR-padding lanes stay bitwise 0.0f).
 *
 * Layout rides the existing per-machine prepack: the same NR=16
 * column panels as pack_b, 16 nibbles per k row packed into 8 bytes
 * (byte j = col j low nibble | col j+8 high nibble — one vpmovzxbd
 * plus shift/mask decodes a full row on AVX2/AVX-512), scales and
 * zero-points as [panel][group][NR] fp32 planes. Activations stay
 * fp32; products dequant IN REGISTER, and the per-group algebra is
 * factored as
 *     acc[c] += scale[g][c] * sum_k(a[k]*q[k][c]) + zp[g][c] * sum_k(a[k])
 * so the hot loop is pure fmadd on the quantized lanes. int4 is
 * LOSSY: the path is opt-in (PTPU_INT4=1) and gated by a measured
 * quality bound, not bitwise parity (tools/decode_bench.py --int4,
 * README "Quantization & autotuning"). */

constexpr int64_t Q4_DEFAULT_GROUP = 64;
// below this weight size the pack/scale overhead outweighs the
// bandwidth win (and tiny weights are never the decode bottleneck)
constexpr int64_t Q4_MIN_ELEMS = 1024;

// opt-in knob, read per predictor load (NOT once per process: tests
// and the A/B benches load fp32 and int4 predictors side by side)
static bool int4_enabled() {
  const char* e = std::getenv("PTPU_INT4");
  return e != nullptr && !std::strcmp(e, "1");
}
static int64_t int4_group_env() {
  const char* e = std::getenv("PTPU_INT4_GROUP");
  if (e == nullptr || e[0] == '\0') return 0;  // 0 = tune or default
  const long v = std::atol(e);
  return (v >= 1 && v <= 4096) ? int64_t(v) : 0;
}

static inline int64_t q4_groups(int64_t K, int64_t G) {
  return G > 0 ? (K + G - 1) / G : 0;
}
static inline int64_t q4_data_size(int64_t K, int64_t N) {
  return ((N + NR - 1) / NR) * K * (NR / 2);
}
static inline int64_t q4_scale_size(int64_t K, int64_t N, int64_t G) {
  return ((N + NR - 1) / NR) * q4_groups(K, G) * NR;
}

/* Quantize row-major B[K,N] into nibble panels + scale/zp planes.
 * Returns false (leaving outputs untouched) when B holds a non-finite
 * value — min/max quantization would launder Inf/NaN into garbage, so
 * such weights stay on the fp32 path. */
static bool pack_b_q4(const float* B, int64_t K, int64_t N, int64_t G,
                      uint8_t* q4, float* scale, float* zp) {
  for (int64_t i = 0; i < K * N; ++i)
    if (!std::isfinite(B[i])) return false;
  const int64_t panels = (N + NR - 1) / NR;
  const int64_t ng = q4_groups(K, G);
  const int64_t grain =
      std::max<int64_t>(1, 65536 / std::max<int64_t>(K * NR, 1));
  parallel_for(panels, grain, [&](int64_t p0, int64_t p1) {
    for (int64_t p = p0; p < p1; ++p) {
      const int64_t j0 = p * NR, w = std::min(NR, N - j0);
      uint8_t* dst = q4 + p * K * (NR / 2);
      for (int64_t g = 0; g < ng; ++g) {
        const int64_t k0 = g * G, k1 = std::min(K, k0 + G);
        float* s = scale + (p * ng + g) * NR;
        float* z = zp + (p * ng + g) * NR;
        float inv[NR];
        for (int64_t c = 0; c < NR; ++c) {
          float mn = 0.f, mx = 0.f;
          if (c < w && k1 > k0) {
            mn = mx = B[k0 * N + j0 + c];
            for (int64_t k = k0 + 1; k < k1; ++k) {
              const float v = B[k * N + j0 + c];
              mn = std::min(mn, v);
              mx = std::max(mx, v);
            }
          }
          const float sc = (mx - mn) / 15.0f;
          s[c] = sc;
          z[c] = mn;
          inv[c] = sc > 0.f ? 1.0f / sc : 0.f;
        }
        for (int64_t k = k0; k < k1; ++k) {
          uint8_t* row = dst + k * (NR / 2);
          for (int64_t j = 0; j < NR / 2; ++j) {
            uint32_t qlo = 0, qhi = 0;
            if (j < w) {
              const long q = std::lround(
                  (B[k * N + j0 + j] - z[j]) * inv[j]);
              qlo = uint32_t(q < 0 ? 0 : q > 15 ? 15 : q);
            }
            if (j + 8 < w) {
              const long q = std::lround(
                  (B[k * N + j0 + j + 8] - z[j + 8]) * inv[j + 8]);
              qhi = uint32_t(q < 0 ? 0 : q > 15 ? 15 : q);
            }
            row[j] = uint8_t(qlo | (qhi << 4));
          }
        }
      }
    }
  });
  return true;
}

/* Dequantize rows [k0, k0+kc) of one nibble panel into pack_b float
 * panel layout ([k][c], NR-wide) — the M > 1 int4 path feeds these
 * KC-deep slices straight into the existing fp32 macro tile, so the
 * compute kernel (and its epilogue semantics) is shared with fp32. */
static void q4_dequant_rows_generic(const uint8_t* panel,
                                    const float* scale, const float* zp,
                                    int64_t K, int64_t G, int64_t ng,
                                    int64_t k0, int64_t kc, float* out) {
  for (int64_t k = k0; k < k0 + kc; ++k) {
    const uint8_t* row = panel + k * (NR / 2);
    const int64_t g = k / G;
    const float* s = scale + g * NR;
    const float* z = zp + g * NR;
    float* d = out + (k - k0) * NR;
    for (int64_t j = 0; j < NR / 2; ++j) {
      const uint32_t b = row[j];
      d[j] = s[j] * float(b & 0xF) + z[j];
      d[j + 8] = s[j + 8] * float(b >> 4) + z[j + 8];
    }
  }
  (void)K;
  (void)ng;
}

#ifdef PTPU_X86
__attribute__((target("avx2,fma")))
static void q4_dequant_rows_avx2(const uint8_t* panel, const float* scale,
                                 const float* zp, int64_t K, int64_t G,
                                 int64_t ng, int64_t k0, int64_t kc,
                                 float* out) {
  const __m256i mask = _mm256_set1_epi32(0xF);
  for (int64_t k = k0; k < k0 + kc; ++k) {
    const uint8_t* row = panel + k * (NR / 2);
    const int64_t g = k / G;
    const float* s = scale + g * NR;
    const float* z = zp + g * NR;
    float* d = out + (k - k0) * NR;
    const __m128i bytes =
        _mm_loadl_epi64(reinterpret_cast<const __m128i*>(row));
    const __m256i w = _mm256_cvtepu8_epi32(bytes);
    const __m256 lo =
        _mm256_cvtepi32_ps(_mm256_and_si256(w, mask));
    const __m256 hi =
        _mm256_cvtepi32_ps(_mm256_and_si256(_mm256_srli_epi32(w, 4), mask));
    _mm256_storeu_ps(
        d, _mm256_fmadd_ps(_mm256_loadu_ps(s), lo, _mm256_loadu_ps(z)));
    _mm256_storeu_ps(d + 8,
                     _mm256_fmadd_ps(_mm256_loadu_ps(s + 8), hi,
                                     _mm256_loadu_ps(z + 8)));
  }
  (void)K;
  (void)ng;
}
#endif  // PTPU_X86

static inline void q4_dequant_rows(const uint8_t* panel, const float* scale,
                                   const float* zp, int64_t K, int64_t G,
                                   int64_t ng, int64_t k0, int64_t kc,
                                   float* out) {
#ifdef PTPU_X86
  if (isa_level() >= ISA_AVX2) {
    q4_dequant_rows_avx2(panel, scale, zp, K, G, ng, k0, kc, out);
    return;
  }
#endif
  q4_dequant_rows_generic(panel, scale, zp, K, G, ng, k0, kc, out);
}

/* int4 GEMV: the decode shape (M == 1). One pass over the nibble
 * panels — 8 bytes per k row instead of 64 — with the per-group
 * scale/zp algebra applied once per group. asum (the group-wise
 * activation sums) depends only on A, so it is computed once and
 * shared across every panel. */
#ifdef PTPU_X86
__attribute__((target("avx2,fma")))
static void gemv_q4_panel_avx2(const float* A, const uint8_t* panel,
                               const float* scale, const float* zp,
                               const float* asum, int64_t K, int64_t G,
                               int64_t ng, float* acc16) {
  const __m256i mask = _mm256_set1_epi32(0xF);
  __m256 acc0 = _mm256_setzero_ps(), acc1 = _mm256_setzero_ps();
  for (int64_t g = 0; g < ng; ++g) {
    const int64_t k0 = g * G, k1 = std::min(K, k0 + G);
    __m256 q0 = _mm256_setzero_ps(), q1 = _mm256_setzero_ps();
    for (int64_t k = k0; k < k1; ++k) {
      const __m256 av = _mm256_broadcast_ss(A + k);
      const __m128i bytes = _mm_loadl_epi64(
          reinterpret_cast<const __m128i*>(panel + k * (NR / 2)));
      const __m256i w = _mm256_cvtepu8_epi32(bytes);
      const __m256 lo = _mm256_cvtepi32_ps(_mm256_and_si256(w, mask));
      const __m256 hi = _mm256_cvtepi32_ps(
          _mm256_and_si256(_mm256_srli_epi32(w, 4), mask));
      q0 = _mm256_fmadd_ps(av, lo, q0);
      q1 = _mm256_fmadd_ps(av, hi, q1);
    }
    const float* s = scale + g * NR;
    const float* z = zp + g * NR;
    const __m256 za = _mm256_broadcast_ss(asum + g);
    acc0 = _mm256_fmadd_ps(_mm256_loadu_ps(s), q0, acc0);
    acc0 = _mm256_fmadd_ps(_mm256_loadu_ps(z), za, acc0);
    acc1 = _mm256_fmadd_ps(_mm256_loadu_ps(s + 8), q1, acc1);
    acc1 = _mm256_fmadd_ps(_mm256_loadu_ps(z + 8), za, acc1);
  }
  _mm256_storeu_ps(acc16, acc0);
  _mm256_storeu_ps(acc16 + 8, acc1);
}

__attribute__((target("avx512f")))
static void gemv_q4_panel_avx512(const float* A, const uint8_t* panel,
                                 const float* scale, const float* zp,
                                 const float* asum, int64_t K, int64_t G,
                                 int64_t ng, float* acc16) {
  // one zmm covers the panel: bytes 0..7 duplicated into lanes 8..15,
  // then a per-lane shift {0 x8, 4 x8} + mask isolates each nibble
  const __m512i mask = _mm512_set1_epi32(0xF);
  const __m512i shifts = _mm512_set_epi32(4, 4, 4, 4, 4, 4, 4, 4,
                                          0, 0, 0, 0, 0, 0, 0, 0);
  __m512 acc = _mm512_setzero_ps();
  for (int64_t g = 0; g < ng; ++g) {
    const int64_t k0 = g * G, k1 = std::min(K, k0 + G);
    __m512 q = _mm512_setzero_ps();
    for (int64_t k = k0; k < k1; ++k) {
      __m128i b8 = _mm_loadl_epi64(
          reinterpret_cast<const __m128i*>(panel + k * (NR / 2)));
      b8 = _mm_unpacklo_epi64(b8, b8);
      const __m512i w = _mm512_cvtepu8_epi32(b8);
      const __m512 qf = _mm512_cvtepi32_ps(
          _mm512_and_si512(_mm512_srlv_epi32(w, shifts), mask));
      q = _mm512_fmadd_ps(_mm512_set1_ps(A[k]), qf, q);
    }
    acc = _mm512_fmadd_ps(_mm512_loadu_ps(scale + g * NR), q, acc);
    acc = _mm512_fmadd_ps(_mm512_loadu_ps(zp + g * NR),
                          _mm512_set1_ps(asum[g]), acc);
  }
  _mm512_storeu_ps(acc16, acc);
}
#endif  // PTPU_X86

static void gemv_q4_panel_generic(const float* A, const uint8_t* panel,
                                  const float* scale, const float* zp,
                                  const float* asum, int64_t K, int64_t G,
                                  int64_t ng, float* acc16) {
  float acc[NR] = {};
  for (int64_t g = 0; g < ng; ++g) {
    const int64_t k0 = g * G, k1 = std::min(K, k0 + G);
    float qacc[NR] = {};
    for (int64_t k = k0; k < k1; ++k) {
      const float av = A[k];
      const uint8_t* row = panel + k * (NR / 2);
      for (int64_t j = 0; j < NR / 2; ++j) {
        const uint32_t b = row[j];
        qacc[j] += av * float(b & 0xF);
        qacc[j + 8] += av * float(b >> 4);
      }
    }
    const float* s = scale + g * NR;
    const float* z = zp + g * NR;
    for (int64_t c = 0; c < NR; ++c)
      acc[c] += s[c] * qacc[c] + z[c] * asum[g];
  }
  for (int64_t c = 0; c < NR; ++c) acc16[c] = acc[c];
}

static void gemv_q4(const float* A, const uint8_t* q4, const float* scale,
                    const float* zp, float* C, int64_t N, int64_t K,
                    int64_t G, const float* bias_n, float bm0, int act) {
  const int64_t ntn = (N + NR - 1) / NR;
  const int64_t ng = q4_groups(K, G);
  // group-wise activation sums: A-only, shared by every panel
  static thread_local std::vector<float> asum_buf;
  asum_buf.assign(size_t(std::max<int64_t>(ng, 1)), 0.f);
  float* asum = asum_buf.data();
  for (int64_t g = 0; g < ng; ++g) {
    const int64_t k0 = g * G, k1 = std::min(K, k0 + G);
    float s = 0.f;
    for (int64_t k = k0; k < k1; ++k) s += A[k];
    asum[g] = s;
  }
  const int64_t grain = N * K < (int64_t(1) << 21) ? ntn : 1;
  parallel_for(ntn, grain, [&](int64_t p0, int64_t p1) {
    for (int64_t p = p0; p < p1; ++p) {
      float acc16[NR];
      const uint8_t* panel = q4 + p * K * (NR / 2);
      const float* s = scale + p * ng * NR;
      const float* z = zp + p * ng * NR;
#ifdef PTPU_X86
      const int lvl = isa_level();
      if (lvl == ISA_AVX512)
        gemv_q4_panel_avx512(A, panel, s, z, asum, K, G, ng, acc16);
      else if (lvl == ISA_AVX2)
        gemv_q4_panel_avx2(A, panel, s, z, asum, K, G, ng, acc16);
      else
#endif
        gemv_q4_panel_generic(A, panel, s, z, asum, K, G, ng, acc16);
      const int64_t j0 = p * NR, nr = std::min(NR, N - j0);
      for (int64_t c = 0; c < nr; ++c) {
        const float v = acc16[c] + bm0 + (bias_n ? bias_n[j0 + c] : 0.f);
        C[j0 + c] = act_apply(v, act);
      }
    }
  });
}

/* int4 GEMM, M > 1 (prefill / batched decode): same task grid as
 * gemm_compute, but each (panel, k-slice) step first dequantizes the
 * 8-byte rows into a thread-local float panel slice and then runs the
 * existing fp32 micro tile — weight DRAM traffic stays 4-bit, the
 * dequant target stays L1-resident. kPathAlt instead runs each row as
 * an int4 GEMV (the small-M decode buckets where the MR=6 tile pads
 * 3x). Zero-extent semantics match gemm_compute: K == 0 is an empty
 * sum whose epilogue still fills C (r11 invariant). */
static void gemm_q4(const float* A, const uint8_t* q4, const float* scale,
                    const float* zp, float* C, int64_t M, int64_t N,
                    int64_t K, int64_t G, const float* bias_n, int act,
                    const ptpu::tune::TuneConfig* cfg) {
  if (M <= 0 || N <= 0) return;
  if (K <= 0) {
    for (int64_t i = 0; i < M; ++i)
      for (int64_t j = 0; j < N; ++j)
        C[i * N + j] = act_apply(bias_n ? bias_n[j] : 0.f, act);
    return;
  }
  if (M == 1 ||
      (cfg != nullptr && cfg->path == ptpu::tune::kPathAlt)) {
    for (int64_t r = 0; r < M; ++r)
      gemv_q4(A + r * K, q4, scale, zp, C + r * N, N, K, G, bias_n, 0.f,
              act);
    return;
  }
  const int64_t kcb = cfg != nullptr && cfg->kc > 0 ? cfg->kc : KC;
  const int64_t ng = q4_groups(K, G);
  auto& abuf = pack_scratch<float>(0);
  abuf.resize(size_t(a_pack_size(M, K)));
  pack_a<float, float>(A, M, K, abuf.data());
  const float* Apack = abuf.data();
  const int64_t ntn = (N + NR - 1) / NR;
  const int64_t mp = (M + MR - 1) / MR;
  const int64_t want =
      (cfg != nullptr && cfg->mult > 0 ? int64_t(cfg->mult) : int64_t(3)) *
      num_threads();
  int64_t nbm = std::max<int64_t>(
      int64_t(1), std::min(mp, (want + ntn - 1) / ntn));
  const int64_t per_blk = (mp + nbm - 1) / nbm;
  nbm = (mp + per_blk - 1) / per_blk;
  const int64_t grain = M * N * K < (int64_t(1) << 21) ? ntn * nbm : 1;
  parallel_for(ntn * nbm, grain, [&](int64_t t0, int64_t t1) {
    static thread_local std::vector<float> deq;
    deq.resize(size_t(kcb * NR));
    for (int64_t t = t0; t < t1; ++t) {
      const int64_t np = t % ntn, mb = t / ntn;
      const int64_t p_lo = mb * per_blk;
      const int64_t p_hi = std::min(mp, p_lo + per_blk);
      const int64_t j0 = np * NR, nr = std::min(NR, N - j0);
      const uint8_t* panel = q4 + np * K * (NR / 2);
      const float* s = scale + np * ng * NR;
      const float* z = zp + np * ng * NR;
      for (int64_t k0 = 0; k0 < K; k0 += kcb) {
        const int64_t kc = std::min(kcb, K - k0);
        const bool first = k0 == 0, last = k0 + kc == K;
        q4_dequant_rows(panel, s, z, K, G, ng, k0, kc, deq.data());
        for (int64_t p = p_lo; p < p_hi; ++p) {
          const int64_t m0 = p * MR, mr = std::min(MR, M - m0);
          micro_tile(Apack + p * K * MR + k0 * MR, deq.data(),
                     C + m0 * N + j0, N, kc, mr, nr, first, last,
                     bias_n ? bias_n + j0 : nullptr, nullptr, act);
        }
      }
    }
  });
}

/* Pick the int4 group size for a [K, N] weight: PTPU_INT4_GROUP wins,
 * then a cached tuning-cache entry (key {0, N, K, q4pack}), then —
 * with PTPU_TUNE=1 — a load-time probe that packs each candidate and
 * times the decode GEMV over it (smaller groups cost scale-plane
 * bytes, larger ones lose accuracy and L1 residency of the planes;
 * which wins is a machine property). Without tuning: 64. */
static int64_t q4_pick_group(const float* B, int64_t K, int64_t N) {
  const int64_t genv = int4_group_env();
  if (genv > 0) return genv;
  namespace tn = ptpu::tune;
  if (!tn::Registry::Enabled() || K <= 0 || N <= 0)
    return Q4_DEFAULT_GROUP;
  tn::TuneKey key;
  key.m = 0;
  key.n = N;
  key.k = K;
  key.dtype = tn::kDtQ4Pack;
  tn::TuneConfig cfg;
  if (tn::Registry::Inst().Lookup(key, &cfg) && cfg.group > 0)
    return cfg.group;
  static const int64_t cands[] = {32, 64, 128};
  std::vector<float> a(size_t(K), 1.0f), c(size_t(N), 0.f);
  std::vector<uint8_t> q4(size_t(q4_data_size(K, N)));
  std::vector<float> qs, qz;
  int64_t best_g = Q4_DEFAULT_GROUP;
  uint64_t best_us = ~0ull;
  const uint64_t probe0 = tn::NowUs();
  for (const int64_t g : cands) {
    qs.assign(size_t(q4_scale_size(K, N, g)), 0.f);
    qz.assign(qs.size(), 0.f);
    if (!pack_b_q4(B, K, N, g, q4.data(), qs.data(), qz.data()))
      return Q4_DEFAULT_GROUP;  // non-finite: caller falls back to fp32
    uint64_t best = ~0ull;
    for (int rep = 0; rep < 3; ++rep) {
      const uint64_t t0 = tn::NowUs();
      gemv_q4(a.data(), q4.data(), qs.data(), qz.data(), c.data(), N, K,
              g, nullptr, 0.f, ACT_NONE);
      const uint64_t dt = tn::NowUs() - t0;
      if (dt < best) best = dt;
    }
    if (best < best_us) {
      best_us = best;
      best_g = g;
    }
  }
  cfg = tn::TuneConfig();
  cfg.group = int32_t(best_g);
  tn::Registry::Inst().Insert(key, cfg);
  tn::Registry::Inst().NoteProbe(tn::NowUs() - probe0);
  return best_g;
}

/* Time the kernel-config candidate grid ON THE REAL OPERANDS of a
 * cache-missing GEMM shape and return the winner. Fires through the
 * load-time dry run (plan_memory executes every node) and the serving
 * ladder's start-up bucket probes — steady-state traffic only ever
 * sees memo/cache hits. Every candidate computes the full, correct
 * output (fp32 configs are bitwise-identical; the caller reruns the
 * winner after Insert so the node's output always comes from the
 * config that every later run will use). */
template <class RunFn>
static ptpu::tune::TuneConfig probe_gemm_cfg(int64_t M, const RunFn& run) {
  namespace tn = ptpu::tune;
  std::vector<tn::TuneConfig> cands;
  cands.emplace_back();  // candidate 0: the compile-time defaults
  static const int32_t kcs[] = {160, 320, 640};
  const bool multi = num_threads() > 1;
  for (const int32_t kc : kcs) {
    for (const int32_t mult : {2, 3, 4}) {
      if (!multi && mult != 3) continue;  // task grain is moot on 1 core
      if (kc == KC && mult == 3) continue;  // == candidate 0
      tn::TuneConfig c;
      c.path = tn::kPathDefault;
      c.kc = kc;
      c.mult = multi ? mult : 0;
      cands.push_back(c);
    }
  }
  if (M <= 2 * MR) {  // per-row GEMV only plausibly wins at small M
    tn::TuneConfig c;
    c.path = tn::kPathAlt;
    cands.push_back(c);
  }
  const uint64_t probe0 = tn::NowUs();
  tn::TuneConfig best = cands[0];
  uint64_t best_us = ~0ull;
  for (const auto& c : cands) {
    uint64_t us = ~0ull;
    for (int rep = 0; rep < 2; ++rep) {
      const uint64_t t0 = tn::NowUs();
      run(&c);
      const uint64_t dt = tn::NowUs() - t0;
      if (dt < us) us = dt;
    }
    if (us < best_us) {
      best_us = us;
      best = c;
    }
  }
  tn::Registry::Inst().NoteProbe(tn::NowUs() - probe0);
  return best;
}

/* ------------------------------------------------------------------
 * int8 VNNI path: int16 PAIR-packed panels + vpdpwssd macro-kernel.
 *
 * vpdpwssd multiplies 32 int16 lanes pairwise, sums each pair in
 * int32 and accumulates — two k steps per instruction. Both operands
 * are int8-range (the same int8_exact precondition as the int32
 * path), so the int16 products are exact and the accumulation bound
 * is unchanged (2 * 128^2 per pair, K/2 pairs == 128^2 * K). Panel
 * layout interleaves k pairs: A [panel][k2][r][2], B [panel][k2][c][2]
 * — one 64-byte B load covers all NR columns' pairs, and each A row's
 * pair broadcasts as a single 32-bit element. Odd K pads the trailing
 * half-pair with zeros (exact). Integer addition is associative, so
 * this path is BITWISE-equal to the int32 kernel, only faster. */
static inline int64_t kpairs(int64_t K) { return (K + 1) / 2; }
static inline int64_t a_pack16_size(int64_t M, int64_t K) {
  return ((M + MR - 1) / MR) * kpairs(K) * MR * 2;
}
static inline int64_t b_pack16_size(int64_t K, int64_t N) {
  return ((N + NR - 1) / NR) * kpairs(K) * NR * 2;
}

static void pack_a16(const int64_t* A, int64_t M, int64_t K,
                     int16_t* out) {
  const int64_t K2 = kpairs(K);
  const int64_t panels = (M + MR - 1) / MR;
  const int64_t grain =
      std::max<int64_t>(1, 65536 / std::max<int64_t>(K2 * MR, 1));
  parallel_for(panels, grain, [&](int64_t p0, int64_t p1) {
    for (int64_t p = p0; p < p1; ++p) {
      int16_t* dst = out + p * K2 * MR * 2;
      const int64_t mr = std::min(MR, M - p * MR);
      for (int64_t r = 0; r < mr; ++r) {
        const int64_t* src = A + (p * MR + r) * K;
        for (int64_t k2 = 0; k2 < K2; ++k2) {
          dst[(k2 * MR + r) * 2] = int16_t(src[2 * k2]);
          dst[(k2 * MR + r) * 2 + 1] =
              2 * k2 + 1 < K ? int16_t(src[2 * k2 + 1]) : int16_t(0);
        }
      }
      for (int64_t r = mr; r < MR; ++r)
        for (int64_t k2 = 0; k2 < K2; ++k2)
          dst[(k2 * MR + r) * 2] = dst[(k2 * MR + r) * 2 + 1] = 0;
    }
  });
}

static void pack_b16(const int64_t* B, int64_t K, int64_t N,
                     int16_t* out) {
  const int64_t K2 = kpairs(K);
  const int64_t panels = (N + NR - 1) / NR;
  const int64_t grain =
      std::max<int64_t>(1, 65536 / std::max<int64_t>(K2 * NR, 1));
  parallel_for(panels, grain, [&](int64_t p0, int64_t p1) {
    for (int64_t p = p0; p < p1; ++p) {
      int16_t* dst = out + p * K2 * NR * 2;
      const int64_t j0 = p * NR, w = std::min(NR, N - j0);
      for (int64_t k2 = 0; k2 < K2; ++k2) {
        const int64_t* r0 = B + (2 * k2) * N + j0;
        const int64_t* r1 =
            2 * k2 + 1 < K ? B + (2 * k2 + 1) * N + j0 : nullptr;
        int16_t* d = dst + k2 * NR * 2;
        for (int64_t c = 0; c < w; ++c) {
          d[c * 2] = int16_t(r0[c]);
          d[c * 2 + 1] = r1 ? int16_t(r1[c]) : int16_t(0);
        }
        for (int64_t c = w; c < NR; ++c) d[c * 2] = d[c * 2 + 1] = 0;
      }
    }
  });
}

// portable pair kernel (fringe tiles + non-VNNI parity testing)
static inline void micro_kernel_i16(const int16_t* Ap, const int16_t* Bp,
                                    int32_t* C, int64_t ldc, int64_t k2c,
                                    int64_t mr, int64_t nr, bool first) {
  int32_t acc[MR][NR];
  for (int r = 0; r < MR; ++r)
    for (int c = 0; c < NR; ++c) acc[r][c] = 0;
  if (!first)
    for (int64_t r = 0; r < mr; ++r)
      for (int64_t c = 0; c < nr; ++c) acc[r][c] = C[r * ldc + c];
  for (int64_t k2 = 0; k2 < k2c; ++k2) {
    const int16_t* a = Ap + k2 * MR * 2;
    const int16_t* b = Bp + k2 * NR * 2;
    for (int r = 0; r < MR; ++r) {
      const int32_t a0 = a[r * 2], a1 = a[r * 2 + 1];
      for (int c = 0; c < NR; ++c)
        acc[r][c] += a0 * int32_t(b[c * 2]) + a1 * int32_t(b[c * 2 + 1]);
    }
  }
  for (int64_t r = 0; r < mr; ++r)
    for (int64_t c = 0; c < nr; ++c) C[r * ldc + c] = acc[r][c];
}

#ifdef PTPU_X86
__attribute__((target("avx512f,avx512bw,avx512vnni")))
static void micro_tile_vnni(const int16_t* Ap, const int16_t* Bp,
                            int32_t* C, int64_t ldc, int64_t k2c,
                            bool first) {
  __m512i acc[MR];
  if (first) {
    for (int r = 0; r < MR; ++r) acc[r] = _mm512_setzero_si512();
  } else {
    for (int r = 0; r < MR; ++r)
      acc[r] = _mm512_loadu_si512(
          reinterpret_cast<const void*>(C + r * ldc));
  }
  for (int64_t k2 = 0; k2 < k2c; ++k2) {
    const __m512i b = _mm512_loadu_si512(
        reinterpret_cast<const void*>(Bp + k2 * NR * 2));
    const int16_t* a = Ap + k2 * MR * 2;
    for (int r = 0; r < MR; ++r) {
      int32_t pair;  // (a[2k], a[2k+1]) as one 32-bit broadcast element
      std::memcpy(&pair, a + r * 2, 4);
      acc[r] = _mm512_dpwssd_epi32(acc[r], _mm512_set1_epi32(pair), b);
    }
  }
  for (int r = 0; r < MR; ++r)
    _mm512_storeu_si512(reinterpret_cast<void*>(C + r * ldc), acc[r]);
}
#endif

static inline void micro_tile_i16(const int16_t* Ap, const int16_t* Bp,
                                  int32_t* C, int64_t ldc, int64_t k2c,
                                  int64_t mr, int64_t nr, bool first) {
#ifdef PTPU_X86
  if (mr == MR && nr == NR && isa_vnni()) {
    micro_tile_vnni(Ap, Bp, C, ldc, k2c, first);
    return;
  }
#endif
  micro_kernel_i16(Ap, Bp, C, ldc, k2c, mr, nr, first);
}

/* Pair-panel macro-kernel: same (column-tile, row-block) task grid as
 * gemm_compute. No KC blocking — the int8 artifacts' K (<= a few
 * thousand) keeps a full B panel slice L2-resident, and the pair
 * interleave already halves the k-loop trip count. */
static void gemm_compute_i16(const int16_t* Apack, const int16_t* Bpack,
                             int32_t* C, int64_t M, int64_t N,
                             int64_t K) {
  // same degenerate-extent guard as gemm_compute (fuzzing finding,
  // ISSUE 11; repro: csrc/fuzz/corpus/onnx/crash-gemm-i16-zero-n.bin);
  // zero K is an empty sum, and C must still be fully written (the
  // arena is not zero-filled)
  if (M <= 0 || N <= 0) return;
  if (K <= 0) {
    std::fill(C, C + M * N, int32_t(0));
    return;
  }
  const int64_t K2 = kpairs(K);
  const int64_t ntn = (N + NR - 1) / NR;
  const int64_t mp = (M + MR - 1) / MR;
  const int64_t want = int64_t(3) * num_threads();
  int64_t nbm = std::max<int64_t>(
      int64_t(1), std::min(mp, (want + ntn - 1) / ntn));
  const int64_t per_blk = (mp + nbm - 1) / nbm;
  nbm = (mp + per_blk - 1) / per_blk;
  const int64_t grain = M * N * K < (int64_t(1) << 21) ? ntn * nbm : 1;
  parallel_for(ntn * nbm, grain, [&](int64_t t0, int64_t t1) {
    for (int64_t t = t0; t < t1; ++t) {
      const int64_t np = t % ntn, mb = t / ntn;
      const int64_t p_lo = mb * per_blk;
      const int64_t p_hi = std::min(mp, p_lo + per_blk);
      const int64_t j0 = np * NR, nr = std::min(NR, N - j0);
      for (int64_t p = p_lo; p < p_hi; ++p) {
        const int64_t m0 = p * MR, mr = std::min(MR, M - m0);
        micro_tile_i16(Apack + p * K2 * MR * 2,
                       Bpack + np * K2 * NR * 2, C + m0 * N + j0, N,
                       K2, mr, nr, true);
      }
    }
  });
}

/* int8-exact GEMM over the VNNI pair path: packs whichever operand has
 * no pre-packed panel (B-side weights come pre-packed from
 * prepack_weights when the load-time probe admitted VNNI). */
static void gemm_i16(const int64_t* A, const int64_t* B, int32_t* C,
                     int64_t M, int64_t N, int64_t K,
                     const int16_t* Bpack_pre) {
  auto& abuf = pack_scratch<int16_t>(0);
  abuf.resize(size_t(a_pack16_size(M, K)));
  pack_a16(A, M, K, abuf.data());
  const int16_t* Bp = Bpack_pre;
  if (!Bp) {
    auto& bbuf = pack_scratch<int16_t>(1);
    bbuf.resize(size_t(b_pack16_size(K, N)));
    pack_b16(B, K, N, bbuf.data());
    Bp = bbuf.data();
  }
  gemm_compute_i16(abuf.data(), Bp, C, M, N, K);
}

/* Implicit im2col: pack the conv patch matrix col[CK, P] for one
 * (image, group) DIRECTLY into B-panel layout, skipping the col
 * materialization entirely (one pass over CK*P instead of im2col +
 * pack). Row r of col maps to (ic, kh, kw); columns walk (oh, ow) in
 * SEGMENTS — for unit horizontal stride each output row is a zero-pad
 * | contiguous-copy | zero-pad triple, so the hot path is straight-line
 * copies through a column-tile cursor instead of per-element bounds
 * checks. Out-of-image taps and the last tile's fringe zero-fill. */
template <class S, class T>
static void pack_b_im2col(const S* xg, int64_t ICG, int64_t H, int64_t W,
                          int64_t KH, int64_t KW, int64_t OH, int64_t OW,
                          int64_t sh, int64_t sw, int64_t ph, int64_t pw,
                          int64_t dh, int64_t dw, T* out) {
  const int64_t CK = ICG * KH * KW;
  const int64_t tile_step = CK * NR;
  parallel_for(CK, 8, [&](int64_t r0, int64_t r1) {
    for (int64_t r = r0; r < r1; ++r) {
      const int64_t ic = r / (KH * KW);
      const int64_t kh = (r / KW) % KH, kw = r % KW;
      const S* plane = xg + ic * H * W;
      const int64_t ih_off = kh * dh - ph, iw_off = kw * dw - pw;
      // cursor into the packed layout: row r of the current column
      // tile; c wraps at NR, advancing one tile per wrap
      T* dst = out + r * NR;
      int64_t c = 0;
      const auto put_zeros = [&](int64_t len) {
        while (len > 0) {
          const int64_t take = std::min(len, NR - c);
          for (int64_t t = 0; t < take; ++t) dst[c + t] = T(0);
          c += take;
          len -= take;
          if (c == NR) {
            c = 0;
            dst += tile_step;
          }
        }
      };
      const auto put_run = [&](const S* src, int64_t len) {
        while (len > 0) {
          const int64_t take = std::min(len, NR - c);
          for (int64_t t = 0; t < take; ++t) dst[c + t] = T(src[t]);
          src += take;
          c += take;
          len -= take;
          if (c == NR) {
            c = 0;
            dst += tile_step;
          }
        }
      };
      for (int64_t oh = 0; oh < OH; ++oh) {
        const int64_t ih = oh * sh + ih_off;
        if (ih < 0 || ih >= H) {
          put_zeros(OW);
          continue;
        }
        const S* row = plane + ih * W;
        if (sw == 1) {
          const int64_t lo = std::max<int64_t>(0, -iw_off);
          const int64_t hi = std::min(OW, W - iw_off);
          if (hi <= lo) {
            put_zeros(OW);
            continue;
          }
          put_zeros(lo);
          put_run(row + lo + iw_off, hi - lo);
          put_zeros(OW - hi);
        } else {
          for (int64_t ow = 0; ow < OW; ++ow) {
            const int64_t iw = ow * sw + iw_off;
            dst[c] = (iw < 0 || iw >= W) ? T(0) : T(row[iw]);
            if (++c == NR) {
              c = 0;
              dst += tile_step;
            }
          }
        }
      }
      if (c)  // zero-pad the last tile's fringe columns
        for (; c < NR; ++c) dst[c] = T(0);
    }
  });
}

/* Exact-int8 eligibility for the int32 GEMM paths (MatMul and Conv
 * share this): all operand values must fit int8, and the reduction
 * depth K must keep the worst-case accumulation 128*128*K strictly
 * below 2^31 (strict '<': K == 2^31/128^2 would reach exactly
 * INT32_MAX+1). Split so prepack_weights can cache the (expensive)
 * value scan for constant weights. */
static bool int8_depth_ok(int64_t K) {
  return K < (int64_t(1) << 31) / (128 * 128);
}
static bool int8_vals_ok(const int64_t* v, size_t n) {
  for (size_t k = 0; k < n; ++k)
    if (v[k] < -128 || v[k] > 127) return false;
  return true;
}
template <class VA, class VB>  // Buf or std::vector int64 storage
static bool int8_exact(const VA& av, const VB& bv, int64_t K) {
  return int8_depth_ok(K) && int8_vals_ok(av.data(), av.size()) &&
         int8_vals_ok(bv.data(), bv.size());
}

// op-code dispatch: resolved ONCE per node (see apply_binary/apply_unary
// below for the name->code mapping)
enum BinCode {
  B_ADD, B_SUB, B_MUL, B_DIV, B_MAX, B_MIN, B_POW, B_MOD, B_LT, B_LE,
  B_GT, B_GE, B_EQ, B_AND, B_OR, B_XOR, B_NONE
};
enum UnCode {
  U_NEG, U_ABS, U_EXP, U_LOG, U_SQRT, U_RECIP, U_SIGMOID, U_TANH, U_ERF,
  U_FLOOR, U_CEIL, U_ROUND, U_SIGN, U_RELU, U_NOT, U_SIN, U_COS, U_TAN,
  U_ASIN, U_ACOS, U_ATAN, U_SINH, U_COSH, U_ASINH, U_ACOSH, U_ATANH,
  U_NONE
};

static BinCode bin_code(const std::string& op) {
  static const std::map<std::string, BinCode> m = {
      {"Add", B_ADD}, {"Sub", B_SUB}, {"Mul", B_MUL}, {"Div", B_DIV},
      {"Max", B_MAX}, {"Min", B_MIN}, {"Pow", B_POW}, {"Mod", B_MOD},
      {"Less", B_LT}, {"LessOrEqual", B_LE}, {"Greater", B_GT},
      {"GreaterOrEqual", B_GE}, {"Equal", B_EQ}, {"And", B_AND},
      {"Or", B_OR}, {"Xor", B_XOR}};
  auto it = m.find(op);
  return it == m.end() ? B_NONE : it->second;
}

static UnCode un_code(const std::string& op) {
  static const std::map<std::string, UnCode> m = {
      {"Neg", U_NEG}, {"Abs", U_ABS}, {"Exp", U_EXP}, {"Log", U_LOG},
      {"Sqrt", U_SQRT}, {"Reciprocal", U_RECIP}, {"Sigmoid", U_SIGMOID},
      {"Tanh", U_TANH}, {"Erf", U_ERF}, {"Floor", U_FLOOR},
      {"Ceil", U_CEIL}, {"Round", U_ROUND}, {"Sign", U_SIGN},
      {"Relu", U_RELU}, {"Not", U_NOT}, {"Sin", U_SIN}, {"Cos", U_COS},
      {"Tan", U_TAN}, {"Asin", U_ASIN}, {"Acos", U_ACOS},
      {"Atan", U_ATAN}, {"Sinh", U_SINH}, {"Cosh", U_COSH},
      {"Asinh", U_ASINH}, {"Acosh", U_ACOSH}, {"Atanh", U_ATANH}};
  auto it = m.find(op);
  return it == m.end() ? U_NONE : it->second;
}

/* Specialization dispatchers for the float elementwise fast paths: the
 * binary op and the fused activation become template parameters of the
 * inner loops, so they vectorize. The old form — a per-element switch
 * on runtime codes — measured ~10x slower than the specialized loops
 * (1.4 ns/elem vs 0.15) and dominated int8 artifacts, whose
 * quant/dequant epilogues are pure elementwise traffic. The float
 * arithmetic and operand order are IDENTICAL to the generic forms
 * (std::max/min keep their NaN-ordering semantics). */
template <class F>
static void with_bin_op(int code, F&& f) {
  switch (code) {
    case B_ADD: f([](float x, float y) { return x + y; }); break;
    case B_SUB: f([](float x, float y) { return x - y; }); break;
    case B_MUL: f([](float x, float y) { return x * y; }); break;
    case B_DIV: f([](float x, float y) { return x / y; }); break;
    case B_MAX: f([](float x, float y) { return std::max(x, y); }); break;
    default: f([](float x, float y) { return std::min(x, y); }); break;
  }
}

template <class F>
static void with_act(int act, F&& f) {
  switch (act) {
    case ACT_RELU: f([](float v) { return v > 0.f ? v : 0.f; }); break;
    case ACT_SIGMOID:
      f([](float v) { return act_apply(v, ACT_SIGMOID); });
      break;
    case ACT_TANH: f([](float v) { return act_apply(v, ACT_TANH); }); break;
    default: f([](float v) { return v; }); break;
  }
}

static double apply_bin_code(BinCode c, double a, double b) {
  switch (c) {
    case B_ADD: return a + b;
    case B_SUB: return a - b;
    case B_MUL: return a * b;
    case B_DIV: return a / b;
    case B_MAX: return std::max(a, b);
    case B_MIN: return std::min(a, b);
    case B_POW: return std::pow(a, b);
    case B_MOD: return std::fmod(a, b);
    case B_LT: return a < b;
    case B_LE: return a <= b;
    case B_GT: return a > b;
    case B_GE: return a >= b;
    case B_EQ: return a == b;
    case B_AND: return (a != 0) && (b != 0);
    case B_OR: return (a != 0) || (b != 0);
    case B_XOR: return (a != 0) != (b != 0);
    default: throw std::runtime_error("bad binary code");
  }
}

static double apply_un_code(UnCode c, double a) {
  switch (c) {
    case U_NEG: return -a;
    case U_ABS: return std::fabs(a);
    case U_EXP: return std::exp(a);
    case U_LOG: return std::log(a);
    case U_SQRT: return std::sqrt(a);
    case U_RECIP: return 1.0 / a;
    case U_SIGMOID: return 1.0 / (1.0 + std::exp(-a));
    case U_TANH: return std::tanh(a);
    case U_ERF: return std::erf(a);
    case U_FLOOR: return std::floor(a);
    case U_CEIL: return std::ceil(a);
    case U_ROUND: return std::nearbyint(a);
    case U_SIGN: return a > 0 ? 1 : (a < 0 ? -1 : 0);
    case U_RELU: return a > 0 ? a : 0;
    case U_NOT: return a == 0;
    case U_SIN: return std::sin(a);
    case U_COS: return std::cos(a);
    case U_TAN: return std::tan(a);
    case U_ASIN: return std::asin(a);
    case U_ACOS: return std::acos(a);
    case U_ATAN: return std::atan(a);
    case U_SINH: return std::sinh(a);
    case U_COSH: return std::cosh(a);
    case U_ASINH: return std::asinh(a);
    case U_ACOSH: return std::acosh(a);
    case U_ATANH: return std::atanh(a);
    default: throw std::runtime_error("bad unary code");
  }
}

/* Walk every element of the broadcast output, handing the callback the
 * flat output index plus both operand indices — incremental odometer
 * carries instead of the old per-element div/mod chains. Large outputs
 * are chunked across the WorkPool: each chunk pays one div/mod
 * decomposition to seed its odometer, then walks incrementally. The
 * callback must write only its own output element. */
template <class F>
static void bcast_walk(const std::vector<int64_t>& odims,
                       const std::vector<int64_t>& adims,
                       const std::vector<int64_t>& bdims, const F& f) {
  const size_t r = odims.size();
  int64_t total = 1;
  for (auto d : odims) total *= d;
  // empty output (a zero dim): nothing to walk — the odometer seed
  // below takes % odims[d] and a zero dim divides by zero (fuzzing
  // finding, ISSUE 11; repro: corpus/onnx/crash-bcast-zero-dim.bin)
  if (total == 0) return;
  if (r == 0) {
    if (total) f(int64_t(0), int64_t(0), int64_t(0));
    return;
  }
  auto as = strides_for(adims), bs = strides_for(bdims);
  auto ostr = strides_for(odims);
  std::vector<int64_t> ast(r, 0), bst(r, 0);
  const size_t ao = r - adims.size(), bo = r - bdims.size();
  for (size_t d = 0; d < r; ++d) {
    if (d >= ao && adims[d - ao] != 1) ast[d] = as[d - ao];
    if (d >= bo && bdims[d - bo] != 1) bst[d] = bs[d - bo];
  }
  parallel_for(total, 1 << 15, [&](int64_t lo, int64_t hi) {
    std::vector<int64_t> ctr(r, 0);
    int64_t ai = 0, bi = 0;
    for (size_t d = 0; d < r; ++d) {
      ctr[d] = (lo / ostr[d]) % odims[d];
      ai += ctr[d] * ast[d];
      bi += ctr[d] * bst[d];
    }
    for (int64_t k = lo; k < hi; ++k) {
      f(k, ai, bi);
      for (size_t d = r; d-- > 0;) {
        ++ctr[d];
        ai += ast[d];
        bi += bst[d];
        if (ctr[d] < odims[d]) break;
        ai -= ast[d] * odims[d];
        bi -= bst[d] * odims[d];
        ctr[d] = 0;
      }
    }
  });
}

/* ------------------------------------------------------------------
 * Paged KV pool (ISSUE 12 tentpole) — the generation-engine memory
 * backend. The r9 decode engine allocated one fixed max-context slot
 * per session (sessions x layers x 2 x P*H*D floats, zeroed at plan
 * time), so RAM scaled with sessions x max-context no matter how many
 * tokens a session actually held. This pool stores KV in fixed-size
 * PAGE GROUPS of `page_tokens` positions spanning every layer and
 * both k/v ([layer][k|v][token][H][D] within a group), handed out
 * from one slab on demand: a session's block table maps logical page
 * index -> group id, so RAM scales with tokens held and thousands of
 * short sessions fit where 64 fixed slots did.
 *
 * On top of the pager:
 *   - prefix/prompt caching: full PROMPT pages can be published into
 *     a hash-indexed cache and adopted by later sessions with the
 *     same prompt prefix (refcount++ — a system prompt shared by
 *     thousands of sessions costs one copy). Adoption is EXACT, not
 *     hash-trusting: the hash only indexes; a hit must match the
 *     page's stored token ids AND its parent link ((gid, gen) of the
 *     previous page group), so collisions can only miss, never serve
 *     wrong KV.
 *   - copy-on-write: fork() clones a session sharing every group
 *     including the partial tail; the next append into a shared tail
 *     group copies it first (cow_copies counter). Published groups
 *     are always full pages and never written again, so they are
 *     never COW'd.
 *   - reclaim/backpressure: a freed group returns to the free list
 *     when its refcount drops to zero; when the free list is empty,
 *     allocation evicts the least-recently-used published group that
 *     only the cache still references; if nothing is evictable the
 *     caller sees "kv pool exhausted" (the serving layer answers a
 *     soft per-row error — backpressure, not a crash).
 *
 * Pages are NOT zeroed on (re)allocation: a position is readable only
 * after its append advanced the session length, and both read paths
 * (the block-table-aware PtpuPagedAttention kernel and the gather
 * fallback) touch positions < len only — the same every-byte-written
 * invariant the planned arena relies on.
 *
 * Thread contract: registry ops (open/close/fork/adopt/publish/
 * ensure_append/advance) serialize on mu_; reads during a predictor
 * run (gather/row_ptr/the kernel's table view) are lock-free, so
 * callers must not mutate a session concurrently with a decode step
 * that touches it — the serving layer's sv.kv lock (rank 10, below
 * kv.pool) already serializes the whole decode plane. */
// rank 25: the serving layer acquires sv.kv (10) -> sv.sess (20)
// before pool registry ops (open/close/adopt during eviction and
// prefill bookkeeping), and pool ops never take batcher (30) or
// WorkPool (60+) locks
PTPU_LOCK_CLASS(kLockKvPool, "kv.pool", 25);

class KvPool {
 public:
  KvPool(int64_t pool_tokens, int page_tokens, int max_sessions,
         bool prefix_on)
      : cfg_pool_tokens_(pool_tokens),
        page_(page_tokens),
        max_sessions_(max_sessions),
        prefix_on_(prefix_on) {
    if (page_ < 1) throw std::runtime_error("kvpool: page_tokens < 1");
    if (max_sessions_ < 1)
      throw std::runtime_error("kvpool: max_sessions < 1");
  }

  // geometry is fixed by the FIRST attached decode artifact; later
  // attaches (other ladder buckets of the same artifact) must agree
  void attach_geom(int64_t ctx, int64_t heads, int64_t hdim,
                   int layers) {
    ptpu::MutexLock l(mu_);
    if (layers_ == 0) {
      if (ctx < 1 || heads < 1 || hdim < 1 || layers < 1)
        throw std::runtime_error("kvpool: degenerate geometry");
      ctx_ = ctx;
      heads_ = heads;
      hdim_ = hdim;
      layers_ = layers;
      int64_t pt = cfg_pool_tokens_;
      if (pt <= 0) pt = 64 * ctx_;  // the r9 default RAM envelope
      npages_ = std::max<int64_t>(1, pt / page_);
      group_elems_ = int64_t(layers_) * 2 * page_ * heads_ * hdim_;
      if (group_elems_ > 0 &&
          npages_ > int64_t((size_t(1) << 46) / size_t(group_elems_)))
        throw std::runtime_error("kvpool: pool size overflows");
      pool_.assign(size_t(npages_) * size_t(group_elems_), 0.f);
      groups_.assign(size_t(npages_), Group{});
      free_.clear();
      for (int64_t gid = npages_; gid-- > 0;)
        free_.push_back(int32_t(gid));
      sess_.assign(size_t(max_sessions_), Sess{});
    } else if (ctx != ctx_ || heads != heads_ || hdim != hdim_ ||
               layers != layers_) {
      throw std::runtime_error(
          "kvpool: attached artifacts disagree on [P, H, D, layers]");
    }
  }

  int64_t ctx() const { return ctx_; }
  int64_t page_tokens() const { return page_; }
  int max_sessions() const { return max_sessions_; }
  int64_t max_groups() const { return (ctx_ + page_ - 1) / page_; }
  int64_t group_elems() const { return group_elems_; }
  const float* base() const { return pool_.data(); }

  int open() {
    ptpu::MutexLock l(mu_);
    if (layers_ == 0) return -1;
    for (int s = 0; s < int(sess_.size()); ++s)
      if (!sess_[size_t(s)].open) {
        sess_[size_t(s)].open = true;
        sess_[size_t(s)].len = 0;
        sess_[size_t(s)].table.clear();
        ++opens_;
        return s;
      }
    return -1;
  }

  /* Clone `src` into a fresh session sharing every group (refcount++)
   * including the partial tail — beam search / parallel sampling from
   * one prompt. The first append into the shared tail COWs it. */
  int fork(int src) {
    ptpu::MutexLock l(mu_);
    // sess_ is sized by the first attach_geom: empty (and everything
    // below out of bounds) until a predictor attaches
    if (src < 0 || src >= int(sess_.size()) || !sess_[size_t(src)].open)
      return -1;
    for (int s = 0; s < int(sess_.size()); ++s)
      if (!sess_[size_t(s)].open) {
        sess_[size_t(s)].open = true;
        sess_[size_t(s)].len = sess_[size_t(src)].len;
        sess_[size_t(s)].table = sess_[size_t(src)].table;
        for (int32_t gid : sess_[size_t(s)].table) {
          PTPU_SCHED_POINT();  // COW fork mid-refcount walk
          ++groups_[size_t(gid)].ref;
        }
        ++forks_;
        return s;
      }
    return -1;
  }

  void close(int sid) {
    ptpu::MutexLock l(mu_);
    if (sid < 0 || sid >= int(sess_.size()) ||
        !sess_[size_t(sid)].open)
      return;
    for (int32_t gid : sess_[size_t(sid)].table) unref(gid);
    sess_[size_t(sid)].open = false;
    sess_[size_t(sid)].len = 0;
    sess_[size_t(sid)].table.clear();
    ++closes_;
  }

  int64_t len(int sid) const {
    ptpu::MutexLock l(mu_);
    if (sid < 0 || sid >= int(sess_.size()) ||
        !sess_[size_t(sid)].open)
      return -1;
    return sess_[size_t(sid)].len;
  }

  bool is_open(int sid) const {
    ptpu::MutexLock l(mu_);
    return sid >= 0 && sid < int(sess_.size()) &&
           sess_[size_t(sid)].open;
  }

  // allocated page groups (may exceed ceil(len/page) transiently
  // after a failed step) — sizes the hibernation record exactly
  int64_t table_groups(int sid) const {
    ptpu::MutexLock l(mu_);
    if (sid < 0 || sid >= int(sess_.size()) ||
        !sess_[size_t(sid)].open)
      return -1;
    return int64_t(sess_[size_t(sid)].table.size());
  }

  /* Make positions `len .. len+count-1` writable for `sid`: allocate
   * fresh tail groups at page boundaries, and COW the current tail if
   * it is shared (fork divergence, or a trim back into an adopted
   * prefix page — published pages are NEVER written in place).
   * Idempotent — a batch that failed part-way retries without
   * double-allocating. Throws "kv pool exhausted" when no group can
   * be found (counted). */
  void ensure_append(int sid, int64_t count = 1) {
    ptpu::MutexLock l(mu_);
    Sess& s = sess_at(sid);
    if (count < 1) return;
    if (s.len + count > ctx_)
      throw std::runtime_error("kvpool: session context is full");
    // COW the partially-filled shared tail we are about to write into
    const int64_t tail_pg = s.len / page_;
    if (s.len % page_ != 0 && int64_t(s.table.size()) > tail_pg) {
      Group& tail = groups_[size_t(s.table[size_t(tail_pg)])];
      if (tail.ref > 1) {
        const int32_t ng = alloc_group();
        std::memcpy(&pool_[size_t(ng) * size_t(group_elems_)],
                    &pool_[size_t(s.table[size_t(tail_pg)]) *
                           size_t(group_elems_)],
                    size_t(group_elems_) * sizeof(float));
        unref(s.table[size_t(tail_pg)]);
        s.table[size_t(tail_pg)] = ng;
        ++cow_copies_;
      }
    }
    const int64_t last = (s.len + count - 1) / page_;
    while (int64_t(s.table.size()) <= last)
      s.table.push_back(alloc_group());
  }

  void advance(int sid, int64_t count = 1) {
    ptpu::MutexLock l(mu_);
    Sess& s = sess_at(sid);
    if (s.len + count > int64_t(s.table.size()) * page_)
      throw std::runtime_error("kvpool: advance past allocated pages");
    s.len += count;
  }

  /* Truncate `sid` to `new_len` positions — the speculative-decoding
   * rollback: rejected draft tokens' KV rows become unreadable (every
   * read path touches positions < len only) and whole page groups
   * past the new tail are released (or merely unreferenced when
   * shared — a published prefix page or a fork sibling keeps its
   * copy; the r12 refcount machinery already handles both). The kept
   * tail group is NOT copied here: the next append COWs it via
   * ensure_append if it is still shared. No-op when new_len >= len. */
  void trim(int sid, int64_t new_len) {
    ptpu::MutexLock l(mu_);
    Sess& s = sess_at(sid);
    if (new_len < 0)
      throw std::runtime_error("kvpool: trim to negative length");
    if (new_len >= s.len) return;
    const int64_t keep =
        new_len == 0 ? 0 : (new_len - 1) / page_ + 1;
    while (int64_t(s.table.size()) > keep) {
      unref(s.table.back());
      s.table.pop_back();
    }
    s.len = new_len;
    ++trims_;
  }

  /* Write address of (sid, layer, k|v, pos) — pos must be covered by
   * ensure_append. Lock-free by the thread contract above. */
  float* row_ptr(int sid, int layer, int which, int64_t pos) {
    const Sess& s = sess_[size_t(sid)];
    const int32_t gid = s.table[size_t(pos / page_)];
    return pool_.data() + size_t(gid) * size_t(group_elems_) +
           size_t(((int64_t(layer) * 2 + which) * page_ + pos % page_) *
                  heads_ * hdim_);
  }

  // gather a session's first `n` positions of (layer, which) into a
  // contiguous [n, H, D] destination — the fallback read path for
  // decode artifacts whose attention did not rewrite to the paged
  // kernel (hand-rolled artifacts, PTPU_PREDICTOR_OPT=0 graphs)
  void gather(int sid, int layer, int which, int64_t n, float* dst) {
    const Sess& s = sess_[size_t(sid)];
    const int64_t row = heads_ * hdim_;
    for (int64_t p0 = 0; p0 < n; p0 += page_) {
      const int64_t cnt = std::min(page_, n - p0);
      const int32_t gid = s.table[size_t(p0 / page_)];
      std::memcpy(
          dst + p0 * row,
          pool_.data() + size_t(gid) * size_t(group_elems_) +
              size_t((int64_t(layer) * 2 + which) * page_ * row),
          size_t(cnt * row) * sizeof(float));
    }
  }

  // copy the session's block table into a caller-owned flat view for
  // the paged attention kernel (called pre-run, under mu_)
  int64_t view(int sid, int32_t* tab, int64_t cap) {
    ptpu::MutexLock l(mu_);
    const Sess& s = sess_at(sid);
    const int64_t ng = int64_t(s.table.size());
    if (ng > cap)
      throw std::runtime_error("kvpool: view capacity too small");
    if (ng > 0)
      std::memcpy(tab, s.table.data(), size_t(ng) * sizeof(int32_t));
    return s.len;
  }

  /* Prefix adoption: extend a page-aligned session with published
   * groups matching `tokens` page by page. Caps at n-1 tokens — the
   * final prompt token must be STEPPED so its logits exist. Returns
   * tokens adopted this call. */
  int64_t adopt(int sid, const int64_t* tokens, int64_t n) {
    if (!prefix_on_) return 0;
    ptpu::MutexLock l(mu_);
    Sess& s = sess_at(sid);
    int64_t adopted = 0;
    if (s.len % page_ != 0) return 0;  // only page-aligned sessions
    // rebuild the chain over the session's already-held prefix: the
    // caller passes the WHOLE prompt every time, so hashes for pages
    // [0, len/page) recompute from `tokens` directly
    uint64_t h = kChainSeed;
    for (int64_t k = 0; k < s.len / page_; ++k) {
      if ((k + 1) * page_ > n) return 0;
      h = page_hash(h, tokens + k * page_, page_);
    }
    for (int64_t k = s.len / page_; (k + 1) * page_ <= n - 1; ++k) {
      h = page_hash(h, tokens + k * page_, page_);
      auto it = prefix_.find(h);
      if (it == prefix_.end()) break;
      Group& g = groups_[size_t(it->second)];
      // exact-match gate: page tokens AND parent linkage must agree
      if (!g.published ||
          !std::equal(g.toks.begin(), g.toks.end(), tokens + k * page_))
        break;
      if (k == 0) {
        if (g.parent_gid != -1) break;
      } else {
        const int32_t prev = s.table[size_t(k - 1)];
        if (g.parent_gid != prev ||
            g.parent_gen != groups_[size_t(prev)].gen)
          break;
      }
      ++g.ref;
      g.lru = ++tick_;
      s.table.push_back(it->second);
      s.len += page_;
      adopted += page_;
      ++prefix_hits_;
    }
    prefix_hit_tokens_ += uint64_t(adopted);
    return adopted;
  }

  /* Publish every full PROMPT page of `sid` (tokens [0, n)) into the
   * prefix cache. Generated tokens are the caller's to exclude by
   * passing only the prompt length. */
  void publish(int sid, const int64_t* tokens, int64_t n) {
    if (!prefix_on_) return;
    ptpu::MutexLock l(mu_);
    Sess& s = sess_at(sid);
    uint64_t h = kChainSeed;
    const int64_t pages = std::min(n / page_, s.len / page_);
    for (int64_t k = 0; k < pages; ++k) {
      h = page_hash(h, tokens + k * page_, page_);
      const int32_t gid = s.table[size_t(k)];
      Group& g = groups_[size_t(gid)];
      if (g.published) continue;   // adopted or already shared
      auto it = prefix_.find(h);
      if (it != prefix_.end()) continue;  // another chain owns the slot
      g.published = true;
      g.hash = h;
      g.toks.assign(tokens + k * page_, tokens + (k + 1) * page_);
      if (k == 0) {
        g.parent_gid = -1;
        g.parent_gen = 0;
      } else {
        g.parent_gid = s.table[size_t(k - 1)];
        g.parent_gen = groups_[size_t(g.parent_gid)].gen;
      }
      g.lru = ++tick_;
      ++g.ref;  // the cache's own reference
      prefix_[h] = gid;
      ++published_;
    }
  }

  // ---- KV tiering + session hibernation (r19) -----------------------

  /* Attach the disk tier. Geometry must already be fixed (a decode
   * artifact attached): the spill slot size IS the page-group slab
   * size. max_bytes==0 means unbounded. */
  void spill_attach(const std::string& path, uint64_t max_bytes) {
    ptpu::MutexLock l(mu_);
    if (layers_ == 0)
      throw std::runtime_error(
          "kvpool: spill_attach before a decode artifact fixed the "
          "geometry");
    std::string err;
    if (!spill_.Attach(path, max_bytes, geom_locked(), &err))
      throw std::runtime_error("kvpool: " + err);
  }

  bool spill_on() const { return spill_.attached(); }

  /* Serialize `sid` out of the pool. Sole-owner groups (ref==1 —
   * necessarily unpublished, since published pages always carry the
   * cache's own ref) spill to disk slots and their pages free;
   * shared groups (fork siblings, adopted prefix pages) stay
   * resident with THIS session's ref transferred into the record.
   * The session slot itself frees — hibernated sessions do not count
   * against max_sessions, which is exactly how far more
   * conversations than session slots stay open at bounded RSS.
   * Throws the soft retryable "kv spill exhausted" error on the byte
   * cap with every spill slot taken so far rolled back: the pool is
   * untouched on failure. */
  std::vector<uint8_t> hibernate(int sid, int64_t cap, int64_t* need) {
    ptpu::MutexLock l(mu_);
    Sess& s = sess_at(sid);
    if (!spill_.attached())
      throw std::runtime_error("kvpool: spill tier is not attached");
    // size query and execute decide under ONE lock hold, so the
    // caller's buffer can never be outgrown between the two
    *need = int64_t(ptpu::spill::kHibHeaderBytes +
                    s.table.size() * ptpu::spill::kHibRecordBytes);
    if (cap < *need) return {};
    ptpu::spill::HibRecord rec;
    rec.hib_id = next_hib_id_;
    rec.len = uint64_t(s.len);
    rec.groups.resize(s.table.size());
    // pass 1: classify + take spill slots — rollbackable, no pool
    // mutation until every write landed
    for (size_t k = 0; k < s.table.size(); ++k) {
      const int32_t gid = s.table[k];
      auto& hg = rec.groups[k];
      if (groups_[size_t(gid)].ref == 1) {
        const int64_t slot = spill_.Alloc();
        if (slot < 0 ||
            !spill_.Write(slot,
                          &pool_[size_t(gid) * size_t(group_elems_)],
                          size_t(group_elems_))) {
          if (slot >= 0) spill_.Free(slot);
          for (size_t j = 0; j < k; ++j)
            if (rec.groups[j].kind == ptpu::spill::kHibKindSpilled)
              spill_.Free(rec.groups[j].a);
          ++spill_exhausted_;
          throw std::runtime_error(
              "kv spill exhausted (raise PTPU_KV_SPILL_MAX_BYTES or "
              "close sessions)");
        }
        hg.kind = ptpu::spill::kHibKindSpilled;
        hg.a = slot;
        hg.b = 0;
      } else {
        hg.kind = ptpu::spill::kHibKindShared;
        hg.a = gid;
        hg.b = groups_[size_t(gid)].gen;
      }
    }
    // pass 2: commit — spilled pages free, shared refs transfer into
    // the record, the session slot opens up
    PTPU_SCHED_POINT();  // hibernate-vs-evict ordering
    for (size_t k = 0; k < s.table.size(); ++k)
      if (rec.groups[k].kind == ptpu::spill::kHibKindSpilled)
        unref(s.table[k]);
    s.open = false;
    s.len = 0;
    s.table.clear();
    ++next_hib_id_;
    ++hibernates_;
    std::vector<uint8_t> out;
    ptpu::spill::SerializeHib(rec, &out);
    hib_.emplace(rec.hib_id, std::move(rec));
    return out;
  }

  /* Re-materialize a hibernated session. The bytes are a handle, not
   * a capability: every field is cross-validated against the
   * RAM-side registry entry, and any mismatch rejects WITHOUT
   * touching the pool. Returns the new sid, or -1 when every session
   * slot is taken (the open() contract — caller frees one and
   * retries). Pool exhaustion mid-restore rolls back the freshly
   * allocated pages, KEEPS the record + spill slots intact, and
   * rethrows the soft "kv pool exhausted" error. */
  int restore(const uint8_t* data, size_t size) {
    ptpu::MutexLock l(mu_);
    ptpu::spill::HibRecord rec;
    if (ptpu::spill::ParseHibBytes(data, size, &rec) !=
        ptpu::spill::ParseResult::kOk) {
      ++hib_rejects_;
      throw std::runtime_error("kvpool: hibernation record corrupt");
    }
    auto it = hib_.find(rec.hib_id);
    bool match = it != hib_.end() && it->second.len == rec.len &&
                 it->second.groups.size() == rec.groups.size();
    for (size_t k = 0; match && k < rec.groups.size(); ++k)
      match = it->second.groups[k].kind == rec.groups[k].kind &&
              it->second.groups[k].a == rec.groups[k].a &&
              it->second.groups[k].b == rec.groups[k].b;
    if (!match) {
      ++hib_rejects_;
      throw std::runtime_error("kvpool: hibernation record corrupt");
    }
    int sid = -1;
    for (int s2 = 0; s2 < int(sess_.size()); ++s2)
      if (!sess_[size_t(s2)].open) {
        sid = s2;
        break;
      }
    if (sid < 0) return -1;
    // pass 1: pages for the spilled groups (rollbackable)
    std::vector<int32_t> table(rec.groups.size(), -1);
    for (size_t k = 0; k < rec.groups.size(); ++k) {
      const auto& hg = rec.groups[k];
      if (hg.kind == ptpu::spill::kHibKindShared) {
        // the record holds a ref, so the group cannot have been
        // freed/reused — the gen must still match
        if (hg.a >= int64_t(groups_.size()) ||
            groups_[size_t(hg.a)].gen != hg.b) {
          ++hib_rejects_;
          throw std::runtime_error(
              "kvpool: hibernation record corrupt");
        }
        table[k] = int32_t(hg.a);
      } else {
        try {
          table[k] = alloc_group();
        } catch (...) {
          for (size_t j = 0; j < k; ++j)
            if (rec.groups[j].kind == ptpu::spill::kHibKindSpilled &&
                table[j] >= 0)
              unref(table[j]);
          throw;
        }
      }
    }
    // pass 2: payloads back from disk, then the slots free
    for (size_t k = 0; k < rec.groups.size(); ++k)
      if (rec.groups[k].kind == ptpu::spill::kHibKindSpilled &&
          !spill_.Read(rec.groups[k].a,
                       &pool_[size_t(table[k]) * size_t(group_elems_)],
                       size_t(group_elems_))) {
        for (size_t j = 0; j < rec.groups.size(); ++j)
          if (rec.groups[j].kind == ptpu::spill::kHibKindSpilled &&
              table[j] >= 0)
            unref(table[j]);
        ++hib_rejects_;
        throw std::runtime_error("kvpool: hibernation record corrupt");
      }
    PTPU_SCHED_POINT();  // restore-vs-close ordering
    for (size_t k = 0; k < rec.groups.size(); ++k)
      if (rec.groups[k].kind == ptpu::spill::kHibKindSpilled)
        spill_.Free(rec.groups[k].a);
    Sess& s = sess_[size_t(sid)];
    s.open = true;
    s.len = int64_t(rec.len);
    s.table.assign(table.begin(), table.end());
    hib_.erase(it);
    ++restores_;
    return sid;
  }

  /* Discard a hibernation record without restoring — the hibernated
   * session was closed. Spill slots free, shared refs drop. Invalid
   * or unknown bytes are counted and ignored (close is never an
   * error path). */
  void hibernate_drop(const uint8_t* data, size_t size) {
    ptpu::MutexLock l(mu_);
    ptpu::spill::HibRecord rec;
    if (ptpu::spill::ParseHibBytes(data, size, &rec) !=
        ptpu::spill::ParseResult::kOk) {
      ++hib_rejects_;
      return;
    }
    auto it = hib_.find(rec.hib_id);
    if (it == hib_.end()) {
      ++hib_rejects_;
      return;
    }
    // act on the REGISTRY copy, never the caller's bytes
    for (const auto& hg : it->second.groups) {
      if (hg.kind == ptpu::spill::kHibKindSpilled)
        spill_.Free(hg.a);
      else
        unref(int32_t(hg.a));
    }
    hib_.erase(it);
    ++hib_drops_;
  }

  int64_t hibernated() const {
    ptpu::MutexLock l(mu_);
    return int64_t(hib_.size());
  }

  /* Persist the content-addressed adopt index (parent-before-child,
   * tmp+rename). Returns records written, -1 on I/O failure. */
  int64_t prefix_save(const std::string& path) {
    ptpu::MutexLock l(mu_);
    if (layers_ == 0 || !prefix_on_) return 0;
    const ptpu::spill::SpillGeom g = geom_locked();
    if (!ptpu::spill::GeomValid(g)) return 0;
    // roots first, then children whose parent is already emitted —
    // the cache is a forest, so passes converge within chain depth
    std::vector<int32_t> pending;
    for (const auto& kv : prefix_) pending.push_back(kv.second);
    std::vector<ptpu::spill::PrefixRec> recs;
    std::unordered_map<int32_t, uint32_t> idx;
    bool progress = true;
    while (progress) {
      progress = false;
      std::vector<int32_t> next;
      for (const int32_t gid : pending) {
        const Group& gr = groups_[size_t(gid)];
        uint32_t parent = ptpu::spill::kPrefixRootParent;
        if (gr.parent_gid >= 0) {
          auto pit = idx.find(gr.parent_gid);
          // a child only persists under a LIVE emitted parent (gen
          // match rules out ABA reuse of the parent's gid)
          if (pit == idx.end() ||
              groups_[size_t(gr.parent_gid)].gen != gr.parent_gen) {
            next.push_back(gid);
            continue;
          }
          parent = pit->second;
        }
        if (recs.size() >= ptpu::spill::kPrefixMaxRecords) continue;
        ptpu::spill::PrefixRec r;
        r.parent = parent;
        r.toks = gr.toks;
        r.vals.assign(
            &pool_[size_t(gid) * size_t(group_elems_)],
            &pool_[size_t(gid) * size_t(group_elems_)] + group_elems_);
        idx.emplace(gid, uint32_t(recs.size()));
        recs.push_back(std::move(r));
        progress = true;
      }
      pending.swap(next);
    }
    std::vector<uint8_t> bytes;
    ptpu::spill::SerializePrefix(recs, g, &bytes);
    const std::string tmp =
        path + ".tmp." + std::to_string(uint64_t(::getpid()));
    std::FILE* f = std::fopen(tmp.c_str(), "wb");
    if (f == nullptr) return -1;
    const bool ok =
        std::fwrite(bytes.data(), 1, bytes.size(), f) == bytes.size();
    if (std::fclose(f) != 0 || !ok ||
        std::rename(tmp.c_str(), path.c_str()) != 0) {
      std::remove(tmp.c_str());
      return -1;
    }
    prefix_saved_ += recs.size();
    return int64_t(recs.size());
  }

  /* Warm the adopt index from a persisted file. A missing file is a
   * cold start (0); ANY malformed byte rejects the whole file
   * (counted). The chain hash is recomputed FROM THE TOKEN IDS —
   * never read from disk — and parent linkage is rebuilt against the
   * freshly allocated groups, so a warmed cache can only miss, never
   * serve wrong KV. Loading stops silently at pool exhaustion: a
   * partial warm cache is still just a cache. */
  int64_t prefix_load(const std::string& path) {
    ptpu::MutexLock l(mu_);
    if (layers_ == 0 || !prefix_on_) return 0;
    const ptpu::spill::SpillGeom g = geom_locked();
    if (!ptpu::spill::GeomValid(g)) return 0;
    std::FILE* f = std::fopen(path.c_str(), "rb");
    if (f == nullptr) return 0;
    // bounded read: cap + 1 sentinel so an oversized file fails the
    // exact-size check instead of growing the buffer without limit
    const uint64_t cap =
        ptpu::spill::kPrefixHeaderBytes +
        uint64_t(ptpu::spill::kPrefixMaxRecords) *
            ptpu::spill::PrefixRecordBytes(g) +
        1;
    std::vector<uint8_t> bytes;
    uint8_t buf[4096];
    size_t got;
    while ((got = std::fread(buf, 1, sizeof buf, f)) > 0) {
      bytes.insert(bytes.end(), buf, buf + got);
      if (uint64_t(bytes.size()) > cap) break;
    }
    std::fclose(f);
    std::vector<ptpu::spill::PrefixRec> recs;
    if (uint64_t(bytes.size()) > cap ||
        ptpu::spill::ParsePrefixBytes(bytes.data(), bytes.size(), g,
                                      &recs) !=
            ptpu::spill::ParseResult::kOk) {
      ++prefix_persist_rejects_;
      return 0;
    }
    std::vector<int32_t> gid_of(recs.size(), -1);
    std::vector<uint64_t> hash_of(recs.size(), 0);
    int64_t loaded = 0;
    for (size_t i = 0; i < recs.size(); ++i) {
      const auto& r = recs[i];
      int32_t parent_gid = -1;
      uint64_t h = kChainSeed;
      if (r.parent != ptpu::spill::kPrefixRootParent) {
        parent_gid = gid_of[r.parent];
        // parent skipped, or evicted again by alloc pressure during
        // this very load -> the child cannot link, skip it
        if (parent_gid < 0 ||
            !groups_[size_t(parent_gid)].published ||
            groups_[size_t(parent_gid)].hash != hash_of[r.parent])
          continue;
        h = hash_of[r.parent];
      }
      h = page_hash(h, r.toks.data(), page_);
      if (prefix_.count(h)) continue;  // already warm
      int32_t gid;
      try {
        gid = alloc_group();
      } catch (...) {
        break;  // pool full: stop, keep what warmed
      }
      std::memcpy(&pool_[size_t(gid) * size_t(group_elems_)],
                  r.vals.data(),
                  size_t(group_elems_) * sizeof(float));
      Group& gr = groups_[size_t(gid)];
      gr.published = true;
      gr.hash = h;
      gr.toks = r.toks;
      gr.parent_gid = parent_gid;
      gr.parent_gen =
          parent_gid >= 0 ? groups_[size_t(parent_gid)].gen : 0;
      gr.lru = ++tick_;
      // gr.ref stays 1 from alloc_group — that IS the cache ref
      prefix_[h] = gid;
      gid_of[i] = gid;
      hash_of[i] = h;
      ++loaded;
    }
    prefix_loaded_ += uint64_t(loaded);
    return loaded;
  }

  std::string stats_json() {
    ptpu::MutexLock l(mu_);
    int64_t cached = 0, live_sess = 0;
    for (const auto& g : groups_)
      if (g.published && g.ref == 1) ++cached;
    for (const auto& s : sess_)
      if (s.open) ++live_sess;
    std::string out = "{";
    ptpu::AppendJsonU64(&out, "pages_total", uint64_t(npages_));
    out += ",";
    ptpu::AppendJsonU64(&out, "pages_in_use",
                        uint64_t(npages_ - int64_t(free_.size())));
    out += ",";
    // Emitted so page_balance (csrc/ptpu_invar.h) can check
    // pages_total == pages_in_use + pages_free from the snapshot alone.
    ptpu::AppendJsonU64(&out, "pages_free", uint64_t(free_.size()));
    out += ",";
    ptpu::AppendJsonU64(&out, "pages_cached", uint64_t(cached));
    out += ",";
    ptpu::AppendJsonU64(&out, "page_tokens", uint64_t(page_));
    out += ",";
    ptpu::AppendJsonU64(&out, "pool_tokens",
                        uint64_t(npages_ * page_));
    out += ",";
    ptpu::AppendJsonU64(&out, "max_sessions", uint64_t(max_sessions_));
    out += ",";
    ptpu::AppendJsonU64(&out, "sessions_active", uint64_t(live_sess));
    out += ",";
    ptpu::AppendJsonU64(&out, "prefix_hits", prefix_hits_);
    out += ",";
    ptpu::AppendJsonU64(&out, "prefix_hit_tokens", prefix_hit_tokens_);
    out += ",";
    ptpu::AppendJsonU64(&out, "prefix_published", published_);
    out += ",";
    ptpu::AppendJsonU64(&out, "prefix_evictions", prefix_evictions_);
    out += ",";
    ptpu::AppendJsonU64(&out, "cow_copies", cow_copies_);
    out += ",";
    ptpu::AppendJsonU64(&out, "forks", forks_);
    out += ",";
    ptpu::AppendJsonU64(&out, "trims", trims_);
    out += ",";
    ptpu::AppendJsonU64(&out, "pool_exhausted", exhausted_);
    out += ",";
    ptpu::AppendJsonU64(&out, "sessions_hibernated",
                        uint64_t(hib_.size()));
    out += ",";
    ptpu::AppendJsonU64(&out, "hibernates", hibernates_);
    out += ",";
    ptpu::AppendJsonU64(&out, "restores", restores_);
    out += ",";
    ptpu::AppendJsonU64(&out, "hib_drops", hib_drops_);
    out += ",";
    ptpu::AppendJsonU64(&out, "hib_rejects", hib_rejects_);
    out += ",";
    ptpu::AppendJsonU64(&out, "spill_exhausted", spill_exhausted_);
    const ptpu::spill::SpillFile::Stats sp = spill_.Snapshot();
    out += ",";
    ptpu::AppendJsonU64(&out, "spill_attached", sp.attached ? 1 : 0);
    out += ",";
    ptpu::AppendJsonU64(&out, "spill_slots_total", sp.slots_total);
    out += ",";
    ptpu::AppendJsonU64(&out, "spill_slots_in_use", sp.slots_in_use);
    out += ",";
    ptpu::AppendJsonU64(&out, "spill_bytes", sp.bytes_mapped);
    out += ",";
    ptpu::AppendJsonU64(&out, "spill_writes", sp.writes);
    out += ",";
    ptpu::AppendJsonU64(&out, "spill_reads", sp.reads);
    out += ",";
    ptpu::AppendJsonU64(&out, "spill_header_rejects",
                        sp.header_rejects);
    out += ",";
    ptpu::AppendJsonU64(&out, "prefix_persist_saved", prefix_saved_);
    out += ",";
    ptpu::AppendJsonU64(&out, "prefix_persist_loaded", prefix_loaded_);
    out += ",";
    ptpu::AppendJsonU64(&out, "prefix_persist_rejects",
                        prefix_persist_rejects_);
    out += "}";
    return out;
  }

  // the C ABI hands out a pointer into this cached snapshot
  std::string stats_json_;

 private:
  struct Group {
    int32_t ref = 0;
    uint64_t gen = 0;       // bumped per allocation: ABA guard for
                            // parent links after reuse
    bool published = false;
    uint64_t hash = 0;
    uint64_t lru = 0;
    int32_t parent_gid = -1;
    uint64_t parent_gen = 0;
    std::vector<int64_t> toks;  // published pages keep their ids for
                                // exact adoption matching
  };
  struct Sess {
    bool open = false;
    int64_t len = 0;
    std::vector<int32_t> table;  // logical page index -> group id
  };

  static constexpr uint64_t kChainSeed = 0xcbf29ce484222325ull;
  static uint64_t page_hash(uint64_t h, const int64_t* toks,
                            int64_t n) {
    for (int64_t i = 0; i < n; ++i) {
      uint64_t v = uint64_t(toks[i]);
      for (int b = 0; b < 8; ++b) {
        h ^= (v >> (8 * b)) & 0xff;
        h *= 0x100000001b3ull;
      }
    }
    return h;
  }

  Sess& sess_at(int sid) {
    if (sid < 0 || sid >= int(sess_.size()) || !sess_[size_t(sid)].open)
      throw std::runtime_error("kvpool: session " +
                               std::to_string(sid) + " is not open");
    return sess_[size_t(sid)];
  }

  int32_t alloc_group() {
    if (free_.empty()) evict_one_cached();
    if (free_.empty()) {
      ++exhausted_;
      throw std::runtime_error(
          "kv pool exhausted (pages_total=" + std::to_string(npages_) +
          "; raise PTPU_KV_POOL_TOKENS or close sessions)");
    }
    const int32_t gid = free_.back();
    free_.pop_back();
    Group& g = groups_[size_t(gid)];
    ++g.gen;
    g.ref = 1;
    g.published = false;
    g.parent_gid = -1;
    g.parent_gen = 0;
    g.toks.clear();
    return gid;
  }

  void unref(int32_t gid) {
    Group& g = groups_[size_t(gid)];
    PTPU_SCHED_POINT();  // drop-vs-evict ordering on the group ref
    if (--g.ref == 0) {
      // published groups always hold the cache ref, so ref==0 means
      // unpublished (or just unpublished by eviction)
      free_.push_back(gid);
    }
  }

  // LRU-evict one published group only the cache still references
  void evict_one_cached() {
    int32_t victim = -1;
    uint64_t oldest = UINT64_MAX;
    for (size_t gid = 0; gid < groups_.size(); ++gid) {
      const Group& g = groups_[gid];
      if (g.published && g.ref == 1 && g.lru < oldest) {
        oldest = g.lru;
        victim = int32_t(gid);
      }
    }
    if (victim < 0) return;
    Group& g = groups_[size_t(victim)];
    prefix_.erase(g.hash);
    g.published = false;
    g.toks.clear();
    ++prefix_evictions_;
    unref(victim);
  }

  const int64_t cfg_pool_tokens_;
  const int64_t page_;
  const int max_sessions_;
  const bool prefix_on_;
  int64_t ctx_ = 0, heads_ = 0, hdim_ = 0;
  int layers_ = 0;
  int64_t npages_ = 0, group_elems_ = 0;
  std::vector<float> pool_;
  std::vector<Group> groups_;
  std::vector<int32_t> free_;
  std::vector<Sess> sess_;
  std::unordered_map<uint64_t, int32_t> prefix_;
  uint64_t tick_ = 0;
  uint64_t opens_ = 0, closes_ = 0, forks_ = 0, cow_copies_ = 0;
  uint64_t trims_ = 0;
  uint64_t prefix_hits_ = 0, prefix_hit_tokens_ = 0, published_ = 0;
  uint64_t prefix_evictions_ = 0, exhausted_ = 0;
  // ---- KV tiering (r19) ----
  ptpu::spill::SpillGeom geom_locked() const {
    ptpu::spill::SpillGeom g;
    g.page = uint32_t(page_);
    g.layers = uint32_t(layers_);
    g.heads = uint32_t(heads_);
    g.hdim = uint32_t(hdim_);
    g.slot_bytes = uint64_t(group_elems_) * sizeof(float);
    return g;
  }
  ptpu::spill::SpillFile spill_;
  std::unordered_map<uint64_t, ptpu::spill::HibRecord> hib_;
  uint64_t next_hib_id_ = 1;
  uint64_t hibernates_ = 0, restores_ = 0, hib_drops_ = 0;
  uint64_t hib_rejects_ = 0, spill_exhausted_ = 0;
  uint64_t prefix_saved_ = 0, prefix_loaded_ = 0;
  uint64_t prefix_persist_rejects_ = 0;
  mutable ptpu::Mutex mu_{kLockKvPool};
};

// ----------------------------------------------------------------- executor
struct Predictor {
  Graph g;
  std::map<std::string, Tensor> env;
  std::vector<Tensor> outputs;
  std::vector<std::string> last_err_names;

  /* Weights pre-packed at load time into GEMM panel layout (A-side for
   * Conv's [ocg, CK] filters per group, B-side for MatMul's [K, N]),
   * keyed by initializer name (+ group for conv). Serving then never
   * repacks or rescans a constant operand. */
  struct PackedMat {
    std::vector<float> f;
    std::vector<int32_t> i;
    std::vector<int16_t> i16;  // VNNI pair panels (isa_vnni() loads)
    bool int8_ok = false;
    /* Weight-only int4 (PTPU_INT4=1): nibble panels + per-group
     * scale/zp planes, replacing the fp32 panels for eligible MatMul
     * weights — q4 non-empty means pm.f was NOT packed (the panels
     * are the only hot-loop read; the artifact's fp32 initializer
     * stays in env for the scalar fallback paths). */
    std::vector<uint8_t> q4;
    std::vector<float> q4s, q4z;
    int64_t q4_group = 0;
  };
  std::map<std::string, PackedMat> packed_w_;

  /* Static memory plan: one byte offset per node output into a single
   * arena sized to the peak over the lifetime walk (see plan_memory). */
  struct PlanSlot {
    uint64_t off = 0;
    size_t bytes = 0;
    bool valid = false;
  };
  std::vector<PlanSlot> plan_;
  std::vector<char> arena_storage_;
  char* arena_base_ = nullptr;
  uint64_t arena_bytes_ = 0;
  bool planned_ = false;
  // bucket-ladder batch override: export batch -> planned batch (0 =
  // no override); the Reshape kernel repairs batch-baked targets
  int64_t bo_from_ = 0, bo_to_ = 0;
  int fused_nodes_ = 0;

  /* Private execution context (nullptr = shared global pool). Owned
   * when created via ptpu_predictor_create_opts(threads > 0), borrowed
   * when attached via ptpu_predictor_set_pool (the serving runtime
   * shares one sub-pool across an instance's bucket predictors). */
  WorkPool* pool_ = nullptr;
  std::unique_ptr<WorkPool> owned_pool_;

  /* Serving stats (csrc/ptpu_stats.h): per-op-type cumulative calls /
   * wall time / output bytes plus a per-run latency histogram.
   * Always-on — two steady-clock reads and a pointer bump per node
   * (run() is single-threaded per instance, so the op aggregates are
   * plain integers; the histogram shares the lock-free type the PS
   * server uses). Exposed via ptpu_predictor_stats_json/reset. */
  struct OpStat {
    uint64_t calls = 0;
    uint64_t time_us = 0;
    uint64_t bytes = 0;
  };
  std::map<std::string, OpStat> op_stats_;
  std::vector<OpStat*> node_stat_;  // per-node pointer into op_stats_
  ptpu::Histogram run_us_;
  uint64_t runs_ = 0;
  uint64_t run_time_us_ = 0;
  /* Runs that missed the planned-arena zero-alloc path (dynamic
   * shapes, or inputs bound with dims differing from the plan) — the
   * bucket-ladder coverage signal the serving runtime polls. Atomic:
   * unlike the rest of the stats (read via stats_json on the owning
   * thread), the serving runtime reads this one CROSS-THREAD while an
   * instance worker is mid-run. */
  std::atomic<uint64_t> dyn_fallback_runs_{0};
  std::string stats_json_;

  /* ---------------- KV-cached autoregressive decode ----------------
   * A decode-step artifact (paddle_tpu.models.gpt.export_gpt_decode)
   * follows a fixed input/output convention:
   *   inputs : [ids (B,1) int] [pos (B) or (B,1) int]
   *            then per layer l: [k_cache (B,P,H,D) f32]
   *                              [v_cache (B,P,H,D) f32]
   *   outputs: [logits (B, ...)] then per layer l:
   *            [new_k (B,1,H,D)] [new_v (B,1,H,D)]
   * kv_plan() validates the convention and allocates ONE zeroed cache
   * block of `sessions` x layers x 2 x P*H*D floats plus per-input
   * staging buffers — after that, a decode step performs ZERO
   * allocation: stage row copies bound into env via Buf::bind, the
   * planned-arena run, and append-position writes of each new k/v row
   * into its session slot. Sessions are slots: open() hands out a free
   * one (len 0), close() frees it; eviction policy lives in the
   * serving layer. Thread-compatibility contract is run()'s: one
   * thread at a time per predictor. */
  struct KvSession {
    bool open = false;
    int64_t len = 0;
  };
  int kv_sessions_ = 0;
  int64_t kv_batch_ = 0, kv_ctx_ = 0, kv_heads_ = 0, kv_hdim_ = 0;
  int64_t kv_width_ = 1;   // positions fed per session per step
  int kv_layers_ = 0;
  int kv_ids_dtype_ = DT_I32, kv_pos_dtype_ = DT_I32;
  std::vector<int64_t> kv_pos_dims_;
  std::vector<float> kv_block_;
  std::vector<KvSession> kv_sess_;
  std::vector<std::vector<float>> kv_stage_;   // one per cache input
  std::vector<int64_t> kv_ids_stage_, kv_pos_stage_;
  bool kv_out_checked_ = false;

  /* ---- paged decode mode (ISSUE 12) ----
   * kv_attach() binds this predictor to a shared KvPool instead of
   * the fixed per-session slab: sessions live in the pool (several
   * ladder-bucket predictors of the same artifact share one pool and
   * one session space). Two read paths:
   *   direct  rewrite_paged_attention() replaced every
   *           PtpuAttention(q, Concat(cache, new), ...) with a
   *           PtpuPagedAttention that reads cache rows THROUGH the
   *           block-table view — no gather copy, no concat copy, and
   *           the dead cache inputs are never staged or bound;
   *   gather  any artifact whose attention did not rewrite (hand-
   *           rolled graphs, PTPU_PREDICTOR_OPT=0) stages pages into
   *           the contiguous kv_stage_ buffers exactly like the
   *           unpaged path — memory still scales with tokens held.
   */
  KvPool* kv_pool_ = nullptr;       // borrowed; owned by the C handle
  bool kv_direct_ = false;
  std::set<std::string> dead_inputs_;  // unconsumed after the rewrite
  std::vector<int32_t> kv_view_tab_;   // [B x max_groups] block tables
  std::vector<int64_t> kv_view_len_;   // per row; -1 = no live view
  const float* kv_pool_base_ = nullptr;
  int64_t kv_group_elems_ = 0, kv_page_tokens_ = 0, kv_max_groups_ = 0;

  void kv_attach(KvPool* pool) {
    if (kv_sessions_ > 0)
      throw std::runtime_error(
          "kv_attach: predictor already kv_plan()ed (fixed slots)");
    if (kv_pool_)
      throw std::runtime_error("kv_attach: pool already attached");
    kv_validate();
    pool->attach_geom(kv_ctx_, kv_heads_, kv_hdim_, kv_layers_);
    kv_pool_ = pool;
    const char* dz = std::getenv("PTPU_KV_DIRECT");
    const bool want_direct = !(dz && std::strcmp(dz, "0") == 0);
    if (want_direct && rewrite_paged_attention()) {
      kv_direct_ = true;
      compute_dead_inputs();
      plan_memory();     // concat outputs left the lifetime walk
      build_stats_index();
    } else {
      kv_stage_.assign(size_t(2 * kv_layers_),
                       std::vector<float>(size_t(kv_batch_) *
                                              size_t(kv_slot_elems()),
                                          0.f));
    }
    kv_pool_base_ = pool->base();
    kv_group_elems_ = pool->group_elems();
    kv_page_tokens_ = pool->page_tokens();
    kv_max_groups_ = pool->max_groups();
    kv_view_tab_.assign(size_t(kv_batch_ * kv_max_groups_), 0);
    kv_view_len_.assign(size_t(kv_batch_), -1);
    kv_ids_stage_.assign(size_t(kv_batch_ * kv_width_), 0);
    kv_pos_stage_.assign(size_t(kv_batch_), 0);
    kv_out_checked_ = false;
  }

  // inputs no surviving node consumes (the rewritten-away cache
  // inputs): the planner and the planned-run input check skip them
  void compute_dead_inputs() {
    dead_inputs_.clear();
    std::set<std::string> used(g.output_names.begin(),
                               g.output_names.end());
    for (const auto& n : g.nodes)
      used.insert(n.inputs.begin(), n.inputs.end());
    for (const auto& name : g.input_names)
      if (!used.count(name)) dead_inputs_.insert(name);
  }

  void decode_step_paged(const int64_t* sids, const int64_t* tokens,
                         int n) {
    KvPool& pool = *kv_pool_;
    const int64_t W = kv_width_;
    if (n < 1 || int64_t(n) > kv_batch_)
      throw std::runtime_error("decode_step: n outside [1, B=" +
                               std::to_string(kv_batch_) + "]");
    for (int r = 0; r < n; ++r) {
      const int64_t s = sids[r];
      if (s < 0 || s >= pool.max_sessions() || !pool.is_open(int(s)))
        throw std::runtime_error("decode_step: session " +
                                 std::to_string(s) + " is not open");
      if (pool.len(int(s)) + W > kv_ctx_)
        throw std::runtime_error("decode_step: session " +
                                 std::to_string(s) +
                                 " context is full (P=" +
                                 std::to_string(kv_ctx_) + ")");
      for (int r2 = 0; r2 < r; ++r2)
        if (sids[r2] == s)
          throw std::runtime_error(
              "decode_step: duplicate session " + std::to_string(s) +
              " in one batch (steps of one session are ordered)");
    }
    /* Make every row's append window writable BEFORE any compute:
     * allocation (and COW of shared tails) throws "kv pool exhausted"
     * here, idempotently, so a partially-provisioned batch can retry
     * row-by-row without double-allocating. */
    for (int r = 0; r < n; ++r) pool.ensure_append(int(sids[r]), W);
    const int64_t row_hd = kv_heads_ * kv_hdim_;
    for (int64_t r = 0; r < kv_batch_; ++r) {
      for (int64_t w = 0; w < W; ++w)
        kv_ids_stage_[size_t(r * W + w)] =
            r < n ? tokens[r * W + w] : 0;
      kv_pos_stage_[size_t(r)] =
          r < n ? pool.len(int(sids[r])) : 0;
    }
    if (kv_direct_) {
      for (int64_t r = 0; r < kv_batch_; ++r)
        kv_view_len_[size_t(r)] =
            r < n ? pool.view(int(sids[r]),
                              &kv_view_tab_[size_t(r * kv_max_groups_)],
                              kv_max_groups_)
                  : 0;
    } else {
      const int64_t per = kv_slot_elems();
      for (int l = 0; l < kv_layers_; ++l)
        for (int w = 0; w < 2; ++w) {
          float* stage = kv_stage_[size_t(2 * l + w)].data();
          for (int64_t r = 0; r < kv_batch_; ++r) {
            const int64_t len = r < n ? pool.len(int(sids[r])) : 0;
            if (len > 0)
              pool.gather(int(sids[r]), l, w, len, stage + r * per);
            // same contract as the slab path: rows past len read ZERO
            if (len < kv_ctx_)
              std::memset(stage + r * per + len * row_hd, 0,
                          size_t((kv_ctx_ - len) * row_hd) *
                              sizeof(float));
          }
        }
      for (int i = 2; i < int(g.input_names.size()); ++i) {
        Tensor t;
        t.dtype = DT_F32;
        t.dims = {kv_batch_, kv_ctx_, kv_heads_, kv_hdim_};
        t.f.bind(kv_stage_[size_t(i - 2)].data(),
                 size_t(kv_batch_ * per));
        env[g.input_names[size_t(i)]] = std::move(t);
      }
    }
    {
      Tensor t;
      t.dtype = kv_ids_dtype_;
      t.dims = {kv_batch_, W};
      t.i.bind(kv_ids_stage_.data(), size_t(kv_batch_ * W));
      env[g.input_names[0]] = std::move(t);
    }
    {
      Tensor t;
      t.dtype = kv_pos_dtype_;
      t.dims = kv_pos_dims_;
      t.i.bind(kv_pos_stage_.data(), size_t(kv_batch_));
      env[g.input_names[1]] = std::move(t);
    }
    try {
      run();
    } catch (...) {
      std::fill(kv_view_len_.begin(), kv_view_len_.end(), -1);
      throw;
    }
    std::fill(kv_view_len_.begin(), kv_view_len_.end(), -1);
    if (!kv_out_checked_) {
      for (int l = 0; l < kv_layers_; ++l)
        for (int w = 0; w < 2; ++w) {
          const Tensor& t = outputs[size_t(1 + 2 * l + w)];
          const std::vector<int64_t> want = {kv_batch_, W, kv_heads_,
                                             kv_hdim_};
          if (!t.is_float() || t.dims != want)
            throw std::runtime_error(
                "decode_step: output " + std::to_string(1 + 2 * l + w) +
                " is not a [B,W,H,D] f32 cache append");
        }
      kv_out_checked_ = true;
    }
    for (int l = 0; l < kv_layers_; ++l)
      for (int w = 0; w < 2; ++w) {
        const Tensor& t = outputs[size_t(1 + 2 * l + w)];
        for (int r = 0; r < n; ++r) {
          const int64_t len = pool.len(int(sids[r]));
          for (int64_t q = 0; q < W; ++q)
            std::memcpy(pool.row_ptr(int(sids[r]), l, w, len + q),
                        t.f.data() + (int64_t(r) * W + q) * row_hd,
                        size_t(row_hd) * sizeof(float));
        }
      }
    for (int r = 0; r < n; ++r) pool.advance(int(sids[r]), W);
  }

  int64_t kv_slot_elems() const { return kv_ctx_ * kv_heads_ * kv_hdim_; }
  float* kv_slot(int sid, int layer, int which /*0=k,1=v*/) {
    const int64_t per = kv_slot_elems();
    return kv_block_.data() +
           ((int64_t(sid) * kv_layers_ + layer) * 2 + which) * per;
  }

  // decode-artifact convention check shared by the fixed-slot plan
  // (kv_plan) and the paged-pool attach (kv_attach): fills the kv_*
  // geometry fields without allocating anything
  void kv_validate() {
    const int nin = int(g.input_names.size());
    if (nin < 4 || (nin - 2) % 2)
      throw std::runtime_error(
          "kv_plan: not a decode artifact (want inputs "
          "[ids][pos][k0][v0]...)");
    kv_layers_ = (nin - 2) / 2;
    const auto in_dims = [&](int i) -> const std::vector<int64_t>& {
      auto it = g.input_dims.find(g.input_names[size_t(i)]);
      if (it == g.input_dims.end())
        throw std::runtime_error("kv_plan: input " + std::to_string(i) +
                                 " has no dims");
      return it->second;
    };
    const auto in_dtype = [&](int i) {
      auto it = g.input_dtypes.find(g.input_names[size_t(i)]);
      return it == g.input_dtypes.end() ? DT_F32 : it->second;
    };
    const auto& idd = in_dims(0);
    if (idd.size() != 2 || idd[1] < 1 || idd[0] < 1)
      throw std::runtime_error("kv_plan: ids input must be [B, W>=1]");
    kv_batch_ = idd[0];
    kv_width_ = idd[1];   // tokens fed per session per step (W > 1:
                          // the speculative-verify artifact shape)
    kv_ids_dtype_ = in_dtype(0);
    if (kv_ids_dtype_ != DT_I32 && kv_ids_dtype_ != DT_I64)
      throw std::runtime_error("kv_plan: ids input must be int32/int64");
    const auto& pdd = in_dims(1);
    if (!(pdd == std::vector<int64_t>{kv_batch_} ||
          pdd == std::vector<int64_t>{kv_batch_, 1}))
      throw std::runtime_error("kv_plan: pos input must be [B] or [B,1]");
    kv_pos_dims_ = pdd;
    kv_pos_dtype_ = in_dtype(1);
    if (kv_pos_dtype_ != DT_I32 && kv_pos_dtype_ != DT_I64)
      throw std::runtime_error("kv_plan: pos input must be int32/int64");
    for (int l = 0; l < kv_layers_; ++l)
      for (int w = 0; w < 2; ++w) {
        const int i = 2 + 2 * l + w;
        const auto& cd = in_dims(i);
        if (cd.size() != 4 || cd[0] != kv_batch_)
          throw std::runtime_error("kv_plan: cache input " +
                                   std::to_string(i) +
                                   " must be [B, P, H, D]");
        if (l == 0 && w == 0) {
          kv_ctx_ = cd[1];
          kv_heads_ = cd[2];
          kv_hdim_ = cd[3];
          if (kv_ctx_ < 1 || kv_heads_ < 1 || kv_hdim_ < 1)
            throw std::runtime_error("kv_plan: degenerate cache dims");
        } else if (cd[1] != kv_ctx_ || cd[2] != kv_heads_ ||
                   cd[3] != kv_hdim_) {
          throw std::runtime_error(
              "kv_plan: cache inputs disagree on [P, H, D]");
        }
        if (in_dtype(i) != DT_F32)
          throw std::runtime_error("kv_plan: cache inputs must be f32");
      }
    if (int(g.output_names.size()) != 1 + 2 * kv_layers_)
      throw std::runtime_error(
          "kv_plan: decode artifact must have 1 + 2*layers outputs, got " +
          std::to_string(g.output_names.size()));
  }

  void kv_plan(int sessions) {
    if (sessions < 1) throw std::runtime_error("kv_plan: sessions < 1");
    if (kv_pool_)
      throw std::runtime_error(
          "kv_plan: predictor already attached to a paged pool");
    kv_validate();
    kv_sessions_ = sessions;
    kv_sess_.assign(size_t(sessions), KvSession{});
    // the pre-planned cache block: zero-filled once; append-position
    // writes only from here on (no per-step realloc)
    kv_block_.assign(size_t(sessions) * size_t(kv_layers_) * 2 *
                         size_t(kv_slot_elems()),
                     0.f);
    kv_stage_.assign(size_t(2 * kv_layers_),
                     std::vector<float>(size_t(kv_batch_) *
                                            size_t(kv_slot_elems()),
                                        0.f));
    kv_ids_stage_.assign(size_t(kv_batch_ * kv_width_), 0);
    kv_pos_stage_.assign(size_t(kv_batch_), 0);
    kv_out_checked_ = false;
  }

  /* Truncate a session to `new_len` — the speculative-decoding
   * rollback shared by both engines. Paged mode releases/unrefs page
   * groups in the pool; slab mode just moves the length fence (the
   * staging path re-zeroes [len, ctx) on every step, so rolled-back
   * rows are unreadable either way). */
  void kv_trim(int sid, int64_t new_len) {
    if (kv_pool_) return kv_pool_->trim(sid, new_len);
    if (kv_sessions_ == 0)
      throw std::runtime_error(
          "kv_trim: kv_plan()/kv_attach() not called");
    if (sid < 0 || sid >= kv_sessions_ || !kv_sess_[size_t(sid)].open)
      throw std::runtime_error("kv_trim: session " +
                               std::to_string(sid) + " is not open");
    if (new_len < 0)
      throw std::runtime_error("kv_trim: negative length");
    if (new_len < kv_sess_[size_t(sid)].len)
      kv_sess_[size_t(sid)].len = new_len;
  }

  int kv_open() {
    for (int s = 0; s < kv_sessions_; ++s)
      if (!kv_sess_[size_t(s)].open) {
        kv_sess_[size_t(s)].open = true;
        kv_sess_[size_t(s)].len = 0;
        return s;
      }
    return -1;
  }

  void kv_close(int sid) {
    if (sid < 0 || sid >= kv_sessions_) return;
    kv_sess_[size_t(sid)].open = false;
    kv_sess_[size_t(sid)].len = 0;
    // scrub the slot so a reused session never attends over a previous
    // occupant's rows (they are masked, but stale NaN/Inf garbage must
    // not exist to begin with)
    for (int l = 0; l < kv_layers_; ++l)
      for (int w = 0; w < 2; ++w)
        std::memset(kv_slot(sid, l, w), 0,
                    size_t(kv_slot_elems()) * sizeof(float));
  }

  /* One batched decode step over n <= B sessions. Row r binds session
   * sids[r] feeding tokens[r*W .. r*W+W-1] (W == the artifact's step
   * width, 1 for the classic autoregressive step); rows n..B-1 are
   * zero padding whose outputs are discarded. Appends each real row's
   * new k/v into its slot and advances len by W; logits stay readable
   * via the normal output accessors (row r of output 0). */
  void decode_step(const int64_t* sids, const int64_t* tokens, int n) {
    if (kv_pool_) return decode_step_paged(sids, tokens, n);
    if (kv_sessions_ == 0)
      throw std::runtime_error(
          "decode_step: kv_plan()/kv_attach() not called");
    const int64_t W = kv_width_;
    if (n < 1 || int64_t(n) > kv_batch_)
      throw std::runtime_error("decode_step: n outside [1, B=" +
                               std::to_string(kv_batch_) + "]");
    for (int r = 0; r < n; ++r) {
      const int64_t s = sids[r];
      if (s < 0 || s >= kv_sessions_ || !kv_sess_[size_t(s)].open)
        throw std::runtime_error("decode_step: session " +
                                 std::to_string(s) + " is not open");
      if (kv_sess_[size_t(s)].len + W > kv_ctx_)
        throw std::runtime_error("decode_step: session " +
                                 std::to_string(s) +
                                 " context is full (P=" +
                                 std::to_string(kv_ctx_) + ")");
      for (int r2 = 0; r2 < r; ++r2)
        if (sids[r2] == s)
          throw std::runtime_error(
              "decode_step: duplicate session " + std::to_string(s) +
              " in one batch (steps of one session are ordered)");
    }
    const int64_t per = kv_slot_elems();
    const int64_t row_hd = kv_heads_ * kv_hdim_;
    // stage: ids/pos plus each session's live cache rows (rows past a
    // session's len are masked by the graph — stale stage contents are
    // value-irrelevant and never NaN: slots zero on open)
    for (int64_t r = 0; r < kv_batch_; ++r) {
      for (int64_t w = 0; w < W; ++w)
        kv_ids_stage_[size_t(r * W + w)] =
            r < n ? tokens[r * W + w] : 0;
      kv_pos_stage_[size_t(r)] =
          r < n ? kv_sess_[size_t(sids[r])].len : 0;
    }
    for (int l = 0; l < kv_layers_; ++l)
      for (int w = 0; w < 2; ++w) {
        float* stage = kv_stage_[size_t(2 * l + w)].data();
        for (int64_t r = 0; r < kv_batch_; ++r) {
          const int64_t len =
              r < n ? kv_sess_[size_t(sids[r])].len : 0;
          if (len > 0)
            std::memcpy(stage + r * per, kv_slot(int(sids[r]), l, w),
                        size_t(len * row_hd) * sizeof(float));
          // contract: cache rows past a session's len read as ZERO
          // (not whatever the previous batch staged there) — decode
          // graphs mask them anyway, but the artifact convention must
          // not depend on that
          if (len < kv_ctx_)
            std::memset(stage + r * per + len * row_hd, 0,
                        size_t((kv_ctx_ - len) * row_hd) *
                            sizeof(float));
        }
      }
    // bind inputs (no copies: Buf::bind borrows the staging storage)
    {
      Tensor t;
      t.dtype = kv_ids_dtype_;
      t.dims = {kv_batch_, W};
      t.i.bind(kv_ids_stage_.data(), size_t(kv_batch_ * W));
      env[g.input_names[0]] = std::move(t);
    }
    {
      Tensor t;
      t.dtype = kv_pos_dtype_;
      t.dims = kv_pos_dims_;
      t.i.bind(kv_pos_stage_.data(), size_t(kv_batch_));
      env[g.input_names[1]] = std::move(t);
    }
    for (int i = 2; i < int(g.input_names.size()); ++i) {
      Tensor t;
      t.dtype = DT_F32;
      t.dims = {kv_batch_, kv_ctx_, kv_heads_, kv_hdim_};
      t.f.bind(kv_stage_[size_t(i - 2)].data(),
               size_t(kv_batch_ * per));
      env[g.input_names[size_t(i)]] = std::move(t);
    }
    run();
    if (!kv_out_checked_) {
      for (int l = 0; l < kv_layers_; ++l)
        for (int w = 0; w < 2; ++w) {
          const Tensor& t = outputs[size_t(1 + 2 * l + w)];
          const std::vector<int64_t> want = {kv_batch_, W, kv_heads_,
                                             kv_hdim_};
          if (!t.is_float() || t.dims != want)
            throw std::runtime_error(
                "decode_step: output " + std::to_string(1 + 2 * l + w) +
                " is not a [B,W,H,D] f32 cache append");
        }
      kv_out_checked_ = true;
    }
    // append-position writes into the pre-planned cache block
    for (int l = 0; l < kv_layers_; ++l)
      for (int w = 0; w < 2; ++w) {
        const Tensor& t = outputs[size_t(1 + 2 * l + w)];
        for (int r = 0; r < n; ++r) {
          const int64_t len = kv_sess_[size_t(sids[r])].len;
          std::memcpy(kv_slot(int(sids[r]), l, w) + len * row_hd,
                      t.f.data() + int64_t(r) * W * row_hd,
                      size_t(W * row_hd) * sizeof(float));
        }
      }
    for (int r = 0; r < n; ++r) kv_sess_[size_t(sids[r])].len += W;
  }

  /* Rebuild the node -> OpStat index after the load-time rewrites
   * settle the node list (fusion renames/removes nodes). std::map
   * nodes are pointer-stable, so the hot loop never rehashes. */
  void build_stats_index() {
    node_stat_.clear();
    node_stat_.reserve(g.nodes.size());
    for (const auto& n : g.nodes)
      node_stat_.push_back(&op_stats_[n.op]);
  }

  void reset_stats() {
    op_stats_.clear();
    run_us_.Reset();
    runs_ = 0;
    run_time_us_ = 0;
    dyn_fallback_runs_.store(0, std::memory_order_relaxed);
    build_stats_index();
  }

  const Tensor& in(const Node& n, size_t k) {
    // arity guard BEFORE the access: a hostile artifact can carry a
    // node with fewer inputs than its op implies — n.inputs[k] would
    // read past the vector (ASan-caught in the load-time dry run;
    // fuzzing finding, ISSUE 11; repro:
    // csrc/fuzz/corpus/onnx/crash-binary-op-missing-input.bin)
    if (k >= n.inputs.size())
      throw std::runtime_error("op " + n.op + " expects input #" +
                               std::to_string(k) + " but the node has " +
                               std::to_string(n.inputs.size()));
    auto it = env.find(n.inputs[k]);
    if (it == env.end())
      throw std::runtime_error("missing input tensor '" + n.inputs[k] +
                               "' for op " + n.op);
    /* Dims-vs-storage invariant at the ONE consumption chokepoint
     * (fuzzing finding, ISSUE 11; repro:
     * csrc/fuzz/corpus/onnx/crash-reshape-marker-mismatch.bin): a
     * hostile graph can launder a dims/storage mismatch through ops
     * that carry storage while rewriting dims (Reshape's dynamic
     * 0/-1 marker path) — every kernel indexes by dims, so a
     * mismatched operand is an OOB read wherever it is consumed.
     * Catch the whole class here instead of auditing every producer. */
    const Tensor& t = it->second;
    const size_t have = t.is_float() ? t.f.size() : t.i.size();
    if (size_t(t.numel()) > have)
      throw std::runtime_error(
          "tensor '" + n.inputs[k] + "' claims " +
          std::to_string(t.numel()) + " elements but holds " +
          std::to_string(have) + " (dims/storage mismatch)");
    return t;
  }

  static int64_t attr_i(const Node& n, const char* name, int64_t dflt) {
    auto it = n.attrs.find(name);
    return it == n.attrs.end() ? dflt : it->second.ival;
  }
  static float attr_f(const Node& n, const char* name, float dflt) {
    auto it = n.attrs.find(name);
    return it == n.attrs.end() ? dflt : it->second.fval;
  }
  static std::vector<int64_t> attr_ints(const Node& n, const char* name) {
    auto it = n.attrs.find(name);
    return it == n.attrs.end() ? std::vector<int64_t>{} : it->second.ints;
  }

  const PackedMat* packed_lookup(const std::string& key) const {
    auto it = packed_w_.find(key);
    return it == packed_w_.end() ? nullptr : &it->second;
  }

  /* An initializer sharing a name with a graph INPUT is only the
   * caller-overridable default (ONNX semantics): nothing at load time
   * may treat it as a constant — not the folder, not the fuser, not
   * weight pre-packing. */
  std::set<std::string> overridable_;

  const Tensor* const_initializer(const std::string& name) const {
    if (overridable_.count(name)) return nullptr;
    auto it = g.initializers.find(name);
    return it == g.initializers.end() ? nullptr : &it->second;
  }

  void run_node(const Node& n);

  void add_initializer(const std::string& name, Tensor t) {
    env[name] = t;
    g.initializers[name] = std::move(t);
  }

  // drop initializers (and their env copies) no surviving node reads
  void prune_dead_initializers() {
    std::map<std::string, int> live;
    for (const auto& n : g.nodes)
      for (const auto& i : n.inputs) ++live[i];
    for (const auto& name : g.output_names) ++live[name];
    for (auto it = g.initializers.begin(); it != g.initializers.end();) {
      if (!live.count(it->first)) {
        env.erase(it->first);
        it = g.initializers.erase(it);
      } else {
        ++it;
      }
    }
  }

  /* Constant folding — the load-time optimization pass (reference:
   * AnalysisPredictor::OptimizeInferenceProgram's pass pipeline,
   * `inference/api/analysis_predictor.cc:621`). Any node whose inputs
   * are all initializers (or folded outputs) runs ONCE here and its
   * outputs become initializers. The big win is int8 artifacts: the
   * whole weight-quantization subgraph (Abs/ReduceMax/Div/Round/Clip/
   * Cast over every weight matrix) folds away, leaving only activation
   * quantization + the integer GEMM at serve time.
   *
   * An initializer that shares a name with a graph INPUT is only a
   * default value the caller may override (ONNX semantics), so it is
   * NOT constant: folding it would silently ignore a later
   * ptpu_predictor_set_input on that name. */
  void fold_constants() {
    overridable_.clear();
    overridable_.insert(g.input_names.begin(), g.input_names.end());
    std::vector<Node> kept;
    for (const auto& n : g.nodes) {
      bool all_const = true;
      for (const auto& i : n.inputs)
        if (!const_initializer(i)) {
          all_const = false;
          break;
        }
      if (!all_const) {
        kept.push_back(n);
        continue;
      }
      try {
        run_node(n);
      } catch (const std::exception&) {
        kept.push_back(n);  // unsupported here -> fails at run() as before
        continue;
      }
      for (const auto& o : n.outputs) g.initializers[o] = env[o];
    }
    g.nodes.swap(kept);
    prune_dead_initializers();
  }

  bool act_code_of(const Node& n, int* act) const {
    if (n.op == "Relu") { *act = ACT_RELU; return true; }
    if (n.op == "Sigmoid") { *act = ACT_SIGMOID; return true; }
    if (n.op == "Tanh") { *act = ACT_TANH; return true; }
    if (n.op == "Max" && n.inputs.size() == 2) {
      // the exporter lowers relu to Max(x, 0-scalar-const)
      for (int side = 0; side < 2; ++side) {
        const Tensor* t = const_initializer(n.inputs[size_t(side)]);
        if (t && t->is_float() && t->numel() == 1 && t->f[0] == 0.f) {
          *act = ACT_RELU;
          return true;
        }
      }
    }
    return false;
  }

  // true when `name` is a float initializer broadcasting per-channel
  // over NCHW (scalar, [C,1,1] or [1,C,1,1]); fills out[C]
  bool channel_const(const std::string& name, int64_t C,
                     std::vector<float>* out) const {
    const Tensor* tp = const_initializer(name);
    if (!tp || !tp->is_float()) return false;
    const Tensor& t = *tp;
    if (t.numel() == 1) {
      out->assign(size_t(C), t.f[0]);
      return true;
    }
    if (t.numel() != C) return false;
    const auto& d = t.dims;
    if (d.size() < 3 || d.size() > 4) return false;
    const size_t off = 4 - d.size();
    for (size_t k = 0; k < d.size(); ++k)
      if (d[k] != ((k + off == 1) ? C : 1)) return false;
    out->assign(t.f.begin(), t.f.end());
    return true;
  }

  // float initializer broadcasting per-last-dim over a GEMM output
  // (scalar or dims all 1 except last == N); fills out[N]
  bool lastdim_const(const std::string& name, int64_t N,
                     std::vector<float>* out) const {
    const Tensor* tp = const_initializer(name);
    if (!tp || !tp->is_float()) return false;
    const Tensor& t = *tp;
    if (t.numel() == 1) {
      out->assign(size_t(N), t.f[0]);
      return true;
    }
    if (t.numel() != N || t.dims.empty() || t.dims.back() != N)
      return false;
    for (size_t k = 0; k + 1 < t.dims.size(); ++k)
      if (t.dims[k] != 1) return false;
    out->assign(t.f.begin(), t.f.end());
    return true;
  }

  // scalar float initializer (numel 1) — quant-chain operands
  const Tensor* scalar_const(const std::string& name) const {
    const Tensor* t = const_initializer(name);
    return t && t->is_float() && t->numel() == 1 ? t : nullptr;
  }

  /* int8 activation-quantization chain fusion. The convert_to_int8
   * artifacts spend more serve time OUTSIDE the integer GEMM than in
   * it: per layer the exporter emits Div(x,s) -> Round -> Max(lo,.) ->
   * Min(hi,.) -> Cast(int8) to quantize the activation and
   * Cast(float) -> Mul(scale) to dequantize the GEMM output — seven
   * full memory-bound tensor passes (plus seven pool dispatches) per
   * layer, which measured ~6.3 of the int8 MLP's 9.7 ms while the
   * GEMMs took ~3 (BENCH_SELF_r06 regression, ISSUE r8 satellite).
   * Collapsing each chain into one fused single-pass op (PtpuQuantize
   * / PtpuDequant) removes ~10 passes per layer; the executor
   * replicates the exact per-element arithmetic of the original node
   * sequence, so optimized output stays BITWISE equal to the
   * PTPU_PREDICTOR_OPT=0 baseline (asserted by
   * tests/test_native_predictor.py::test_fused_planned_parity_int8). */
  void fuse_quant_ops() {
    const std::set<std::string> outset(g.output_names.begin(),
                                       g.output_names.end());
    std::map<std::string, int> use_count;
    std::map<std::string, size_t> consumer;
    for (size_t k = 0; k < g.nodes.size(); ++k)
      for (const auto& i : g.nodes[k].inputs) {
        ++use_count[i];
        consumer[i] = k;
      }
    for (const auto& name : g.output_names) ++use_count[name];

    std::vector<char> dead(g.nodes.size(), 0);
    std::map<size_t, Node> placed;

    // single-consumer successor of `cur` past position idx, or npos
    const auto next_of = [&](const std::string& cur, size_t idx) {
      if (outset.count(cur) || use_count[cur] != 1) return size_t(-1);
      auto it = consumer.find(cur);
      if (it == consumer.end() || it->second <= idx || dead[it->second])
        return size_t(-1);
      return it->second;
    };

    for (size_t idx = 0; idx < g.nodes.size(); ++idx) {
      Node& n = g.nodes[idx];
      if (dead[idx] || n.outputs.size() != 1) continue;

      if (n.op == "Div" && n.inputs.size() == 2 &&
          scalar_const(n.inputs[1])) {
        // Div(x, s) -> Round -> Max(lo,.) -> Min(hi,.) -> Cast(int8)
        const size_t j1 = next_of(n.outputs[0], idx);
        if (j1 == size_t(-1) || g.nodes[j1].op != "Round" ||
            g.nodes[j1].outputs.size() != 1)
          continue;
        const size_t j2 = next_of(g.nodes[j1].outputs[0], j1);
        if (j2 == size_t(-1) || g.nodes[j2].op != "Max" ||
            g.nodes[j2].inputs.size() != 2 ||
            g.nodes[j2].outputs.size() != 1)
          continue;
        const Node& mx = g.nodes[j2];
        const bool max_cfirst = scalar_const(mx.inputs[0]) != nullptr;
        const std::string lo_name =
            max_cfirst ? mx.inputs[0] : mx.inputs[1];
        if (!scalar_const(lo_name)) continue;
        const size_t j3 = next_of(mx.outputs[0], j2);
        if (j3 == size_t(-1) || g.nodes[j3].op != "Min" ||
            g.nodes[j3].inputs.size() != 2 ||
            g.nodes[j3].outputs.size() != 1)
          continue;
        const Node& mn = g.nodes[j3];
        const bool min_cfirst = scalar_const(mn.inputs[0]) != nullptr;
        const std::string hi_name =
            min_cfirst ? mn.inputs[0] : mn.inputs[1];
        if (!scalar_const(hi_name)) continue;
        const size_t j4 = next_of(mn.outputs[0], j3);
        if (j4 == size_t(-1) || g.nodes[j4].op != "Cast" ||
            g.nodes[j4].outputs.size() != 1 ||
            attr_i(g.nodes[j4], "to", DT_F32) != DT_I8)
          continue;
        Node f;
        f.op = "PtpuQuantize";
        f.inputs = {n.inputs[0], n.inputs[1], lo_name, hi_name};
        f.outputs = {g.nodes[j4].outputs[0]};
        Attr amc;
        amc.ival = max_cfirst ? 1 : 0;
        f.attrs["q_max_cfirst"] = amc;
        Attr anc;
        anc.ival = min_cfirst ? 1 : 0;
        f.attrs["q_min_cfirst"] = anc;
        dead[idx] = dead[j1] = dead[j2] = dead[j3] = 1;
        dead[j4] = 1;
        fused_nodes_ += 4;
        placed[j4] = std::move(f);

      } else if (n.op == "Cast" && n.inputs.size() == 1 &&
                 attr_i(n, "to", DT_F32) == DT_F32) {
        // Cast(int -> float) -> Mul(scale const, per-last-dim or
        // scalar): the dequantization of an integer GEMM output
        const size_t j1 = next_of(n.outputs[0], idx);
        if (j1 == size_t(-1) || g.nodes[j1].op != "Mul" ||
            g.nodes[j1].inputs.size() != 2 ||
            g.nodes[j1].outputs.size() != 1)
          continue;
        const Node& m = g.nodes[j1];
        const bool cur_first = m.inputs[0] == n.outputs[0];
        const std::string& sname = m.inputs[cur_first ? 1 : 0];
        const Tensor* st = const_initializer(sname);
        if (!st || !st->is_float()) continue;
        bool lastdim = st->numel() == 1;
        if (!lastdim && !st->dims.empty() &&
            st->dims.back() == st->numel()) {
          lastdim = true;
          for (size_t d = 0; d + 1 < st->dims.size(); ++d)
            if (st->dims[d] != 1) lastdim = false;
        }
        if (!lastdim) continue;
        Node f;
        f.op = "PtpuDequant";
        f.inputs = {n.inputs[0], sname};
        f.outputs = {m.outputs[0]};
        dead[idx] = dead[j1] = 1;
        fused_nodes_ += 1;
        placed[j1] = std::move(f);
      }
    }

    if (placed.empty()) return;
    std::vector<Node> rebuilt;
    rebuilt.reserve(g.nodes.size());
    for (size_t k = 0; k < g.nodes.size(); ++k) {
      auto it = placed.find(k);
      if (it != placed.end())
        rebuilt.push_back(std::move(it->second));
      else if (!dead[k])
        rebuilt.push_back(std::move(g.nodes[k]));
    }
    g.nodes.swap(rebuilt);
    prune_dead_initializers();
  }

  /* ------------------------------------------------------------------
   * Transformer fusion (ISSUE r9 tentpole a). The exporter lowers every
   * attention head through a rigid ~20-node Transpose/Reshape/batched-
   * MatMul/scale(/mask)/softmax/batched-MatMul block and every
   * LayerNorm through a ~16-node Sub/Mul/ReduceSum/Sqrt/Pow chain —
   * all memory-bound single-pass ops plus a full [q,k] score
   * materialization per head. These two load-time passes recognize
   * exactly those exported shapes (validated against dims recorded by
   * a load-time dry run — no structural guessing) and collapse each
   * into one fused op:
   *
   *   PtpuAttention  tiled flash-style kernel — online softmax, no
   *                  [q,k] score tensor, row blocks threaded across
   *                  (batch, head) on the WorkPool (the per-head tiny
   *                  GEMMs used to run serially inside one batched
   *                  MatMul dispatch).
   *   PtpuLayerNorm  one pass per row: mean/var/normalize/affine.
   *
   * Both replicate the original float arithmetic closely enough for
   * allclose parity against PTPU_PREDICTOR_OPT=0 (asserted by
   * tests/test_attention_fusion.py); near-miss subgraphs (wrong axis,
   * non-scalar scale, wrong Pow exponent...) fail the checks and stay
   * unfused. */

  /* One dry run with dummy zero inputs records every value's dims —
   * the fusion matchers validate reshape/transpose dims against these
   * instead of inferring shapes structurally. Returns false (no
   * recording) for dynamic-shape artifacts, which then skip the
   * transformer fusions the same way they skip the memory plan. */
  bool dry_run_shapes(std::map<std::string, std::vector<int64_t>>* shp,
                      std::map<std::string, int>* dty) {
    if (g.nodes.empty()) return false;
    for (const auto& name : g.input_names) {
      auto it = g.input_dims.find(name);
      if (it == g.input_dims.end()) return false;
      for (auto d : it->second)
        if (d <= 0) return false;
    }
    std::vector<std::string> dummies;
    for (const auto& name : g.input_names) {
      if (g.initializers.count(name)) continue;
      Tensor t;
      t.dims = g.input_dims[name];
      auto dt = g.input_dtypes.find(name);
      t.dtype = dt == g.input_dtypes.end() ? DT_F32 : dt->second;
      if (t.dtype == DT_F64) t.dtype = DT_F32;
      t.alloc();
      env[name] = std::move(t);
      dummies.push_back(name);
    }
    const auto scrub = [&] {
      for (const auto& name : dummies) env.erase(name);
      for (const auto& n : g.nodes)
        for (const auto& o : n.outputs)
          if (!g.initializers.count(o)) env.erase(o);
    };
    try {
      for (const auto& n : g.nodes) {
        run_node(n);
        for (const auto& o : n.outputs) {
          (*shp)[o] = env[o].dims;
          (*dty)[o] = env[o].dtype;
        }
      }
    } catch (const std::exception&) {
      scrub();
      return false;
    }
    scrub();
    for (const auto& name : g.input_names) {
      (*shp)[name] = g.input_dims[name];
      auto it = g.input_dtypes.find(name);
      const int dt = it == g.input_dtypes.end() ? DT_F32 : it->second;
      (*dty)[name] = dt == DT_F64 ? DT_F32 : dt;
    }
    for (const auto& kv : g.initializers) {
      (*shp)[kv.first] = kv.second.dims;
      (*dty)[kv.first] = kv.second.dtype;
    }
    return true;
  }

  /* bf16 models export their compute-dtype casts as float32->float32
   * Cast nodes (bf16 has no ONNX surface here) — full-tensor copy
   * passes that do nothing. With dry-run dtypes in hand they are
   * provably no-ops: alias them away like Identity. Only the
   * float->float case is touched — integer-width casts carry dtype
   * metadata the quant paths key on. */
  void eliminate_noop_casts(const std::map<std::string, int>& dty) {
    const std::set<std::string> outset(g.output_names.begin(),
                                       g.output_names.end());
    std::map<std::string, std::string> alias;
    std::vector<Node> kept;
    for (auto& n : g.nodes) {
      for (auto& i : n.inputs) {
        auto it = alias.find(i);
        if (it != alias.end()) i = it->second;
      }
      bool drop = false;
      if (n.op == "Cast" && n.inputs.size() == 1 &&
          n.outputs.size() == 1 && !outset.count(n.outputs[0])) {
        int64_t to = attr_i(n, "to", DT_F32);
        if (to == DT_F64) to = DT_F32;
        auto dt = dty.find(n.inputs[0]);
        if (dt != dty.end() && to == DT_F32 && dt->second == DT_F32) {
          alias[n.outputs[0]] = n.inputs[0];
          drop = true;
          ++fused_nodes_;
        }
      }
      if (!drop) kept.push_back(std::move(n));
    }
    g.nodes.swap(kept);
  }

  // shared index for the two transformer matchers
  struct FuseIdx {
    std::map<std::string, size_t> producer;
    std::map<std::string, std::vector<size_t>> uses;
    std::set<std::string> outset;
  };
  FuseIdx build_fuse_idx() const {
    FuseIdx ix;
    ix.outset.insert(g.output_names.begin(), g.output_names.end());
    for (size_t k = 0; k < g.nodes.size(); ++k) {
      for (const auto& o : g.nodes[k].outputs) ix.producer[o] = k;
      for (const auto& i : g.nodes[k].inputs) ix.uses[i].push_back(k);
    }
    return ix;
  }

  // shared rewrite applier for the pattern passes: drop dead nodes and
  // splice each fused node in at its chain's last position
  void apply_rewrite(const std::vector<char>& dead,
                     std::map<size_t, Node>* placed) {
    if (placed->empty()) return;
    std::vector<Node> rebuilt;
    rebuilt.reserve(g.nodes.size());
    for (size_t k = 0; k < g.nodes.size(); ++k) {
      auto it = placed->find(k);
      if (it != placed->end()) rebuilt.push_back(std::move(it->second));
      else if (!dead[k]) rebuilt.push_back(std::move(g.nodes[k]));
    }
    g.nodes.swap(rebuilt);
    prune_dead_initializers();
  }

  // axes of a Reduce node (attr form or axes-input form)
  std::vector<int64_t> reduce_axes(const Node& rn) const {
    std::vector<int64_t> axes = attr_ints(rn, "axes");
    if (axes.empty() && rn.inputs.size() > 1) {
      const Tensor* t = const_initializer(rn.inputs[1]);
      if (t) axes.assign(t->i.begin(), t->i.end());
    }
    return axes;
  }
  bool last_axis_reduce(const Node& rn,
                        const std::vector<int64_t>& in_dims) const {
    if (attr_i(rn, "keepdims", 1) != 0) return false;
    auto axes = reduce_axes(rn);
    if (axes.size() != 1) return false;
    const int64_t ax =
        axes[0] < 0 ? axes[0] + int64_t(in_dims.size()) : axes[0];
    return ax == int64_t(in_dims.size()) - 1;
  }

  // float const broadcasting exactly per-last-dim (numel == D, last
  // dim D, leading dims 1) — the LN gamma/beta shape after folding
  bool lastdim_vec_const(const std::string& name, int64_t D) const {
    const Tensor* t = const_initializer(name);
    if (!t || !t->is_float() || t->numel() != D) return false;
    if (t->dims.empty() || t->dims.back() != D) return false;
    for (size_t k = 0; k + 1 < t->dims.size(); ++k)
      if (t->dims[k] != 1) return false;
    return true;
  }

  void fuse_attention(const std::map<std::string,
                                     std::vector<int64_t>>& shp) {
    FuseIdx ix = build_fuse_idx();
    std::vector<char> dead(g.nodes.size(), 0);
    std::map<size_t, Node> placed;
    const size_t npos = size_t(-1);

    const auto dims_of =
        [&](const std::string& nm) -> const std::vector<int64_t>* {
      auto it = shp.find(nm);
      return it == shp.end() ? nullptr : &it->second;
    };
    const auto mid1 = [&](const std::string& nm) {
      auto u = ix.uses.find(nm);
      return !ix.outset.count(nm) && !g.initializers.count(nm) &&
             u != ix.uses.end() && u->second.size() == 1;
    };
    const auto prod = [&](const std::string& nm) -> size_t {
      auto it = ix.producer.find(nm);
      if (it == ix.producer.end() || dead[it->second]) return npos;
      return it->second;
    };
    const auto cons1 = [&](const std::string& nm) -> size_t {
      if (!mid1(nm)) return npos;
      const size_t j = ix.uses.find(nm)->second[0];
      return dead[j] ? npos : j;
    };
    // walk UP through single-use Transposes; composed perm maps final
    // axis j -> source axis perm[j]
    const auto up_transposes = [&](std::string nm,
                                   std::vector<int64_t>* perm_out,
                                   std::string* src,
                                   std::vector<size_t>* tchain) -> bool {
      std::vector<int64_t> comb;
      bool first = true;
      for (;;) {
        const size_t j = prod(nm);
        if (j == npos || g.nodes[j].op != "Transpose") {
          if (first) return false;
          *perm_out = comb;
          *src = nm;
          return true;
        }
        const Node& t = g.nodes[j];
        const auto* din = dims_of(t.inputs[0]);
        if (!din) return false;
        std::vector<int64_t> p = attr_ints(t, "perm");
        if (p.empty())
          for (size_t d2 = din->size(); d2-- > 0;)
            p.push_back(int64_t(d2));
        if (first) {
          comb = p;
          first = false;
        } else {
          for (auto& c : comb) {
            if (c < 0 || size_t(c) >= p.size()) return false;
            c = p[size_t(c)];
          }
        }
        tchain->push_back(j);
        nm = t.inputs[0];
        // inner chain links must be single-use; the SOURCE may be
        // shared (q/k/v slices feed nothing else, but stay safe)
        const size_t jup = prod(nm);
        if (jup != npos && g.nodes[jup].op == "Transpose" && !mid1(nm)) {
          *perm_out = comb;
          *src = nm;
          return true;
        }
      }
    };
    // walk DOWN through single-consumer Transposes; composed perm maps
    // final axis j -> source axis perm[j]
    const auto down_transposes =
        [&](std::string nm, std::vector<int64_t>* perm_out,
            std::string* dst, std::vector<size_t>* tchain) -> bool {
      std::vector<int64_t> comb;
      bool first = true;
      for (;;) {
        const size_t j = cons1(nm);
        if (j == npos || g.nodes[j].op != "Transpose" ||
            g.nodes[j].inputs[0] != nm)
          break;
        const auto* din = dims_of(nm);
        if (!din) return false;
        std::vector<int64_t> p = attr_ints(g.nodes[j], "perm");
        if (p.empty())
          for (size_t d2 = din->size(); d2-- > 0;)
            p.push_back(int64_t(d2));
        if (first) {
          comb = p;
          first = false;
        } else {
          std::vector<int64_t> nc(comb.size());
          for (size_t q2 = 0; q2 < p.size(); ++q2) {
            if (p[q2] < 0 || size_t(p[q2]) >= comb.size()) return false;
            nc[q2] = comb[size_t(p[q2])];
          }
          comb = nc;
        }
        tchain->push_back(j);
        nm = g.nodes[j].outputs[0];
      }
      if (first) return false;
      *perm_out = comb;
      *dst = nm;
      return true;
    };
    // Reshape([x0,x1,x2,x3] -> [x0*x1, x2, x3]) of an up-transpose
    // chain with the wanted composed perm
    const auto side = [&](const std::string& rname,
                          const std::vector<int64_t>& want_perm,
                          const std::vector<int64_t>& want_3d,
                          std::string* src,
                          std::vector<size_t>* side_chain) -> bool {
      if (!mid1(rname)) return false;
      const size_t rj = prod(rname);
      if (rj == npos || g.nodes[rj].op != "Reshape") return false;
      const auto* rd = dims_of(rname);
      if (!rd || *rd != want_3d) return false;
      const std::string tname = g.nodes[rj].inputs[0];
      if (!mid1(tname)) return false;
      std::vector<int64_t> perm;
      std::vector<size_t> tchain;
      std::string s;
      if (!up_transposes(tname, &perm, &s, &tchain)) return false;
      if (perm != want_perm) return false;
      const auto* td = dims_of(tname);
      if (!td || td->size() != 4) return false;
      if ((*td)[0] * (*td)[1] != want_3d[0] || (*td)[2] != want_3d[1] ||
          (*td)[3] != want_3d[2])
        return false;
      side_chain->push_back(rj);
      side_chain->insert(side_chain->end(), tchain.begin(), tchain.end());
      *src = s;
      return true;
    };

    for (size_t idx = 0; idx < g.nodes.size(); ++idx) {
      if (dead[idx]) continue;
      const Node& dv = g.nodes[idx];
      if (dv.op != "Div" || dv.inputs.size() != 2 ||
          dv.outputs.size() != 1)
        continue;
      std::vector<size_t> chain;
      // ---- softmax tail: Div(exp, Reshape(ReduceSum(exp, last)))
      const std::string exp_name = dv.inputs[0];
      const size_t eidx = prod(exp_name);
      if (eidx == npos || g.nodes[eidx].op != "Exp") continue;
      {
        auto u = ix.uses.find(exp_name);
        if (ix.outset.count(exp_name) || u == ix.uses.end() ||
            u->second.size() != 2)
          continue;
      }
      const auto* exp_dims = dims_of(exp_name);
      if (!exp_dims || exp_dims->size() != 4) continue;
      std::vector<int64_t> want_keep = *exp_dims;
      want_keep.back() = 1;
      const size_t sridx = prod(dv.inputs[1]);
      if (sridx == npos || g.nodes[sridx].op != "Reshape" ||
          !mid1(dv.inputs[1]))
        continue;
      {
        const auto* srd = dims_of(dv.inputs[1]);
        if (!srd || *srd != want_keep) continue;
      }
      const std::string rs_name = g.nodes[sridx].inputs[0];
      const size_t rsidx = prod(rs_name);
      if (rsidx == npos || g.nodes[rsidx].op != "ReduceSum" ||
          !mid1(rs_name) || g.nodes[rsidx].inputs.empty() ||
          g.nodes[rsidx].inputs[0] != exp_name ||
          !last_axis_reduce(g.nodes[rsidx], *exp_dims))
        continue;
      // ---- Sub(scores, Reshape(Max(init, ReduceMax(scores, last))))
      const size_t subidx = prod(g.nodes[eidx].inputs[0]);
      if (subidx == npos || g.nodes[subidx].op != "Sub" ||
          !mid1(g.nodes[eidx].inputs[0]))
        continue;
      const Node& sb = g.nodes[subidx];
      const size_t mridx = prod(sb.inputs[1]);
      if (mridx == npos || g.nodes[mridx].op != "Reshape" ||
          !mid1(sb.inputs[1]))
        continue;
      {
        const auto* mrd = dims_of(sb.inputs[1]);
        if (!mrd || *mrd != want_keep) continue;
      }
      const std::string mx_name = g.nodes[mridx].inputs[0];
      const size_t mxidx = prod(mx_name);
      if (mxidx == npos || g.nodes[mxidx].op != "Max" ||
          !mid1(mx_name) || g.nodes[mxidx].inputs.size() != 2)
        continue;
      float sm_init = 0.f;
      std::string rm_name;
      {
        const Tensor* c0 = scalar_const(g.nodes[mxidx].inputs[0]);
        const Tensor* c1 = scalar_const(g.nodes[mxidx].inputs[1]);
        if (c0 && !c1) {
          sm_init = c0->f[0];
          rm_name = g.nodes[mxidx].inputs[1];
        } else if (c1 && !c0) {
          sm_init = c1->f[0];
          rm_name = g.nodes[mxidx].inputs[0];
        } else {
          continue;
        }
      }
      const size_t rmidx = prod(rm_name);
      if (rmidx == npos || g.nodes[rmidx].op != "ReduceMax" ||
          !mid1(rm_name))
        continue;
      const std::string scores = sb.inputs[0];
      if (g.nodes[rmidx].inputs[0] != scores ||
          !last_axis_reduce(g.nodes[rmidx], *exp_dims))
        continue;
      {
        auto u = ix.uses.find(scores);
        if (ix.outset.count(scores) || u == ix.uses.end() ||
            u->second.size() != 2)
          continue;
      }
      // ---- scores <- [Where(mask, ., neg)] <- Mul(scale) <- Reshape
      //      <- MatMul(QR, KR)
      std::string cur = scores, mask_name, neg_name;
      {
        const size_t whidx = prod(cur);
        if (whidx != npos && g.nodes[whidx].op == "Where") {
          const Node& wh = g.nodes[whidx];
          if (wh.inputs.size() != 3) continue;
          const Tensor* negc = const_initializer(wh.inputs[2]);
          if (!negc || !negc->is_float()) continue;
          mask_name = wh.inputs[0];
          neg_name = wh.inputs[2];
          cur = wh.inputs[1];
          if (!mid1(cur)) continue;
          chain.push_back(whidx);
        }
      }
      const size_t mlidx = prod(cur);
      if (mlidx == npos || g.nodes[mlidx].op != "Mul" ||
          g.nodes[mlidx].inputs.size() != 2)
        continue;
      float scale = 1.f;
      std::string mm_r;
      {
        const Node& ml = g.nodes[mlidx];
        const Tensor* c0 = scalar_const(ml.inputs[0]);
        const Tensor* c1 = scalar_const(ml.inputs[1]);
        if (c1 && !c0) {
          scale = c1->f[0];
          mm_r = ml.inputs[0];
        } else if (c0 && !c1) {
          scale = c0->f[0];
          mm_r = ml.inputs[1];
        } else {
          continue;
        }
      }
      if (!mid1(mm_r)) continue;
      const size_t rshidx = prod(mm_r);
      if (rshidx == npos || g.nodes[rshidx].op != "Reshape") continue;
      const std::string mm1_name = g.nodes[rshidx].inputs[0];
      if (!mid1(mm1_name)) continue;
      const size_t mm1idx = prod(mm1_name);
      if (mm1idx == npos || g.nodes[mm1idx].op != "MatMul" ||
          g.nodes[mm1idx].inputs.size() != 2)
        continue;
      const int64_t b = (*exp_dims)[0], hh = (*exp_dims)[1];
      const int64_t sq = (*exp_dims)[2], sk = (*exp_dims)[3];
      {
        const auto* mmd = dims_of(mm1_name);
        if (!mmd || mmd->size() != 3 || (*mmd)[0] != b * hh ||
            (*mmd)[1] != sq || (*mmd)[2] != sk)
          continue;
      }
      const auto* qr_dims = dims_of(g.nodes[mm1idx].inputs[0]);
      if (!qr_dims || qr_dims->size() != 3) continue;
      const int64_t dd = (*qr_dims)[2];
      if (dd < 1 || dd > 1024) continue;
      std::string q_src, k_src, v_src;
      std::vector<size_t> qch, kch, vch;
      if (!side(g.nodes[mm1idx].inputs[0], {0, 2, 1, 3},
                {b * hh, sq, dd}, &q_src, &qch))
        continue;
      if (!side(g.nodes[mm1idx].inputs[1], {0, 2, 3, 1},
                {b * hh, dd, sk}, &k_src, &kch))
        continue;
      const auto* qs_dims = dims_of(q_src);
      const auto* ks_dims = dims_of(k_src);
      if (!qs_dims || !ks_dims ||
          *qs_dims != std::vector<int64_t>({b, sq, hh, dd}) ||
          *ks_dims != std::vector<int64_t>({b, sk, hh, dd}))
        continue;
      // ---- down: Div -> Transpose(identity) -> Reshape [bh,q,k] ->
      //      MatMul(probs, VR) -> Reshape [b,h,q,d] ->
      //      Transpose{0,2,1,3} -> (optional) Reshape [b,q,h*d]
      std::vector<int64_t> dperm;
      std::string probs4;
      std::vector<size_t> dchain;
      if (!down_transposes(dv.outputs[0], &dperm, &probs4, &dchain))
        continue;
      {
        bool ident = dperm.size() == 4;
        for (size_t q2 = 0; ident && q2 < dperm.size(); ++q2)
          if (dperm[q2] != int64_t(q2)) ident = false;
        if (!ident) continue;
      }
      const size_t pridx = cons1(probs4);
      if (pridx == npos || g.nodes[pridx].op != "Reshape") continue;
      const std::string pr_name = g.nodes[pridx].outputs[0];
      {
        const auto* prd = dims_of(pr_name);
        if (!prd || prd->size() != 3 || (*prd)[0] != b * hh ||
            (*prd)[1] != sq || (*prd)[2] != sk)
          continue;
      }
      const size_t mm2idx = cons1(pr_name);
      if (mm2idx == npos || g.nodes[mm2idx].op != "MatMul" ||
          g.nodes[mm2idx].inputs.size() != 2 ||
          g.nodes[mm2idx].inputs[0] != pr_name)
        continue;
      if (!side(g.nodes[mm2idx].inputs[1], {0, 2, 1, 3},
                {b * hh, sk, dd}, &v_src, &vch))
        continue;
      {
        const auto* vsd = dims_of(v_src);
        if (!vsd || *vsd != *ks_dims) continue;
      }
      const std::string mm2_name = g.nodes[mm2idx].outputs[0];
      const size_t oridx = cons1(mm2_name);
      if (oridx == npos || g.nodes[oridx].op != "Reshape") continue;
      {
        const auto* ord = dims_of(g.nodes[oridx].outputs[0]);
        if (!ord || *ord != std::vector<int64_t>({b, hh, sq, dd}))
          continue;
      }
      std::vector<int64_t> operm;
      std::string out_name;
      std::vector<size_t> ochain;
      if (!down_transposes(g.nodes[oridx].outputs[0], &operm, &out_name,
                           &ochain))
        continue;
      if (operm != std::vector<int64_t>({0, 2, 1, 3})) continue;
      int64_t flat_out = 0;
      std::vector<size_t> frchain;
      {
        const size_t fj = cons1(out_name);
        if (fj != npos && g.nodes[fj].op == "Reshape") {
          const auto* fd = dims_of(g.nodes[fj].outputs[0]);
          if (fd && *fd == std::vector<int64_t>({b, sq, hh * dd})) {
            frchain.push_back(fj);
            out_name = g.nodes[fj].outputs[0];
            flat_out = 1;
          }
        }
      }
      // mask/neg must be right-aligned-broadcastable to [b,h,q,k]
      if (!mask_name.empty()) {
        const auto bc_ok = [&](const std::vector<int64_t>* dm) {
          if (!dm || dm->size() > 4 || dm->empty()) return false;
          const int64_t want[4] = {b, hh, sq, sk};
          const size_t off = 4 - dm->size();
          for (size_t q2 = 0; q2 < dm->size(); ++q2)
            if ((*dm)[q2] != 1 && (*dm)[q2] != want[q2 + off])
              return false;
          return true;
        };
        if (!bc_ok(dims_of(mask_name)) || !bc_ok(dims_of(neg_name)))
          continue;
      }
      // ---- all checks passed: emit the fused node
      chain.insert(chain.end(),
                   {idx, eidx, sridx, rsidx, subidx, mridx, mxidx, rmidx,
                    mlidx, rshidx, mm1idx, pridx, mm2idx, oridx});
      for (auto& ch : {qch, kch, vch, dchain, ochain, frchain})
        chain.insert(chain.end(), ch.begin(), ch.end());
      Node f;
      f.op = "PtpuAttention";
      f.inputs = {q_src, k_src, v_src};
      if (!mask_name.empty()) {
        f.inputs.push_back(mask_name);
        f.inputs.push_back(neg_name);
      }
      f.outputs = {out_name};
      Attr asc;
      asc.fval = scale;
      f.attrs["ptpu_scale"] = asc;
      Attr ain;
      ain.fval = sm_init;
      f.attrs["ptpu_sm_init"] = ain;
      Attr afl;
      afl.ival = flat_out;
      f.attrs["ptpu_flat_out"] = afl;
      size_t last = 0;
      for (size_t j : chain) {
        dead[j] = 1;
        last = std::max(last, j);
      }
      fused_nodes_ += int(chain.size()) - 1;
      placed[last] = std::move(f);
    }

    apply_rewrite(dead, &placed);
  }

  /* kv_attach-time rewrite for the paged direct read path: every
   * layer's
   *   PtpuAttention(q, Concat1(k_cache_in, new_k),
   *                    Concat1(v_cache_in, new_v)[, mask, neg])
   * where k_cache_in/v_cache_in are the layer's cache GRAPH INPUTS and
   * new_k/new_v are its append GRAPH OUTPUTS (the decode convention
   * kv_validate pinned), becomes
   *   PtpuPagedAttention(q, new_k, new_v[, mask, neg])
   * reading cache rows through the pool block table at run time. The
   * two Concat nodes die and the cache inputs lose their last
   * consumer — decode steps stop staging ANY cache bytes. All-or-
   * nothing: applied only when every layer matches (a half-paged
   * graph would read half its cache from unbound inputs). Returns
   * whether the rewrite fired. */
  bool rewrite_paged_attention() {
    if (kv_layers_ < 1) return false;
    FuseIdx ix = build_fuse_idx();
    const auto concat1_of =
        [&](const std::string& name) -> const Node* {
      auto p = ix.producer.find(name);
      if (p == ix.producer.end()) return nullptr;
      const Node& c = g.nodes[p->second];
      if (c.op != "Concat" || c.inputs.size() != 2 ||
          attr_i(c, "axis", 0) != 1)
        return nullptr;
      auto u = ix.uses.find(name);
      if (u == ix.uses.end() || u->second.size() != 1 ||
          ix.outset.count(name))
        return nullptr;
      return &c;
    };
    std::vector<char> dead(g.nodes.size(), 0);
    std::map<size_t, Node> placed;
    std::set<int> matched;
    for (size_t k = 0; k < g.nodes.size(); ++k) {
      const Node& a = g.nodes[k];
      if (a.op != "PtpuAttention" ||
          (a.inputs.size() != 3 && a.inputs.size() != 5))
        continue;
      const Node* kc = concat1_of(a.inputs[1]);
      const Node* vc = concat1_of(a.inputs[2]);
      if (!kc || !vc || kc == vc) continue;
      int layer = -1;
      for (int l = 0; l < kv_layers_; ++l)
        if (kc->inputs[0] == g.input_names[size_t(2 + 2 * l)] &&
            kc->inputs[1] == g.output_names[size_t(1 + 2 * l)] &&
            vc->inputs[0] == g.input_names[size_t(3 + 2 * l)] &&
            vc->inputs[1] == g.output_names[size_t(2 + 2 * l)]) {
          layer = l;
          break;
        }
      if (layer < 0 || matched.count(layer)) continue;
      // the cache inputs must have no OTHER consumer (they die here)
      const auto sole_use = [&](const std::string& nm) {
        auto u = ix.uses.find(nm);
        return u != ix.uses.end() && u->second.size() == 1 &&
               !ix.outset.count(nm);
      };
      if (!sole_use(kc->inputs[0]) || !sole_use(vc->inputs[0]))
        continue;
      Node f;
      f.op = "PtpuPagedAttention";
      f.inputs = {a.inputs[0], kc->inputs[1], vc->inputs[1]};
      if (a.inputs.size() == 5) {
        f.inputs.push_back(a.inputs[3]);
        f.inputs.push_back(a.inputs[4]);
      }
      f.outputs = a.outputs;
      f.attrs = a.attrs;
      Attr al;
      al.ival = layer;
      f.attrs["ptpu_kv_layer"] = al;
      Attr ask;
      // concat key space: P cache rows + the W fed-window rows
      ask.ival = kv_ctx_ + kv_width_;
      f.attrs["ptpu_sk"] = ask;
      matched.insert(layer);
      dead[ix.producer[a.inputs[1]]] = 1;
      dead[ix.producer[a.inputs[2]]] = 1;
      dead[k] = 1;
      placed[k] = std::move(f);
    }
    if (int(matched.size()) != kv_layers_) return false;
    fused_nodes_ += int(placed.size()) * 2;
    apply_rewrite(dead, &placed);
    return true;
  }

  void fuse_layernorm(const std::map<std::string,
                                     std::vector<int64_t>>& shp) {
    FuseIdx ix = build_fuse_idx();
    std::vector<char> dead(g.nodes.size(), 0);
    std::map<size_t, Node> placed;
    const size_t npos = size_t(-1);

    const auto dims_of =
        [&](const std::string& nm) -> const std::vector<int64_t>* {
      auto it = shp.find(nm);
      return it == shp.end() ? nullptr : &it->second;
    };
    const auto mid1 = [&](const std::string& nm) {
      auto u = ix.uses.find(nm);
      return !ix.outset.count(nm) && !g.initializers.count(nm) &&
             u != ix.uses.end() && u->second.size() == 1;
    };
    const auto only_used_by = [&](const std::string& nm, size_t j) {
      if (ix.outset.count(nm) || g.initializers.count(nm)) return false;
      auto u = ix.uses.find(nm);
      if (u == ix.uses.end()) return false;
      for (size_t z : u->second)
        if (z != j) return false;
      return true;
    };
    const auto prod = [&](const std::string& nm) -> size_t {
      auto it = ix.producer.find(nm);
      if (it == ix.producer.end() || dead[it->second]) return npos;
      return it->second;
    };
    const auto cons1 = [&](const std::string& nm) -> size_t {
      if (!mid1(nm)) return npos;
      const size_t j = ix.uses.find(nm)->second[0];
      return dead[j] ? npos : j;
    };
    // mname = Div(Reshape(ReduceSum(x, last-axis, keepdims=0)), scalar
    // const): the exported mean-over-last-dim. Fills x + the divisor.
    const auto match_mean = [&](const std::string& mname, std::string* xn,
                                float* divv,
                                std::vector<size_t>* ch) -> bool {
      if (!mid1(mname)) return false;
      const size_t dj = prod(mname);
      if (dj == npos || g.nodes[dj].op != "Div" ||
          g.nodes[dj].inputs.size() != 2)
        return false;
      const Tensor* dc = scalar_const(g.nodes[dj].inputs[1]);
      if (!dc) return false;
      const std::string rn = g.nodes[dj].inputs[0];
      if (!mid1(rn)) return false;
      const size_t rj = prod(rn);
      if (rj == npos || g.nodes[rj].op != "Reshape") return false;
      const std::string sn = g.nodes[rj].inputs[0];
      if (!mid1(sn)) return false;
      const size_t sj = prod(sn);
      if (sj == npos || g.nodes[sj].op != "ReduceSum" ||
          g.nodes[sj].inputs.empty())
        return false;
      const std::string x = g.nodes[sj].inputs[0];
      const auto* xd = dims_of(x);
      if (!xd || xd->size() < 2) return false;
      if (!last_axis_reduce(g.nodes[sj], *xd)) return false;
      std::vector<int64_t> want = *xd;
      want.back() = 1;
      const auto* rrd = dims_of(rn);
      if (!rrd || *rrd != want) return false;
      *xn = x;
      *divv = dc->f[0];
      ch->push_back(dj);
      ch->push_back(rj);
      ch->push_back(sj);
      return true;
    };

    for (size_t idx = 0; idx < g.nodes.size(); ++idx) {
      if (dead[idx]) continue;
      const Node& sq = g.nodes[idx];
      if (sq.op != "Sqrt" || sq.outputs.size() != 1) continue;
      std::vector<size_t> chain;
      // ---- up: Sqrt(Add(var_guarded, eps))
      if (!mid1(sq.inputs[0])) continue;
      const size_t aidx = prod(sq.inputs[0]);
      if (aidx == npos || g.nodes[aidx].op != "Add" ||
          g.nodes[aidx].inputs.size() != 2)
        continue;
      float eps = 0.f;
      std::string var_g;
      {
        const Tensor* c0 = scalar_const(g.nodes[aidx].inputs[0]);
        const Tensor* c1 = scalar_const(g.nodes[aidx].inputs[1]);
        if (c1 && !c0) {
          eps = c1->f[0];
          var_g = g.nodes[aidx].inputs[0];
        } else if (c0 && !c1) {
          eps = c0->f[0];
          var_g = g.nodes[aidx].inputs[1];
        } else {
          continue;
        }
      }
      if (!mid1(var_g)) continue;
      // optional denominator guard: Where(all-true const, var, const)
      std::string var_name = var_g;
      {
        const size_t wj = prod(var_g);
        if (wj != npos && g.nodes[wj].op == "Where" &&
            g.nodes[wj].inputs.size() == 3) {
          const Tensor* cd = const_initializer(g.nodes[wj].inputs[0]);
          const Tensor* alt = const_initializer(g.nodes[wj].inputs[2]);
          if (!cd || !alt) continue;
          bool all = true;
          for (int64_t k = 0; all && k < cd->numel(); ++k)
            if (cd->at(k) == 0) all = false;
          if (!all) continue;  // guard can actually fire: keep unfused
          var_name = g.nodes[wj].inputs[1];
          if (!mid1(var_name)) continue;
          chain.push_back(wj);
        }
      }
      // var = Div(Reshape(ReduceSum(sqdiff, last)), const)
      std::string sq_name;
      float var_div = 1.f;
      if (!match_mean(var_name, &sq_name, &var_div, &chain)) continue;
      if (!mid1(sq_name)) continue;
      const size_t mj = prod(sq_name);
      if (mj == npos || g.nodes[mj].op != "Mul" ||
          g.nodes[mj].inputs.size() != 2 ||
          g.nodes[mj].inputs[0] != g.nodes[mj].inputs[1])
        continue;
      const std::string c2 = g.nodes[mj].inputs[0];
      if (!only_used_by(c2, mj)) continue;
      const size_t c2j = prod(c2);
      if (c2j == npos || g.nodes[c2j].op != "Sub" ||
          g.nodes[c2j].inputs.size() != 2)
        continue;
      std::string x = g.nodes[c2j].inputs[0];
      std::string xB;
      float mdivB = 1.f;
      if (!match_mean(g.nodes[c2j].inputs[1], &xB, &mdivB, &chain))
        continue;
      if (xB != x) continue;
      chain.push_back(mj);
      chain.push_back(c2j);
      // ---- down: Sqrt -> Pow(., -1) -> Mul(Sub(x, meanA), .)
      const size_t pj = cons1(sq.outputs[0]);
      if (pj == npos || g.nodes[pj].op != "Pow" ||
          g.nodes[pj].inputs.size() != 2 ||
          g.nodes[pj].inputs[0] != sq.outputs[0])
        continue;
      {
        const Tensor* ec = scalar_const(g.nodes[pj].inputs[1]);
        if (!ec || ec->f[0] != -1.0f) continue;
      }
      const std::string pw_name = g.nodes[pj].outputs[0];
      const size_t m1j = cons1(pw_name);
      if (m1j == npos || g.nodes[m1j].op != "Mul" ||
          g.nodes[m1j].inputs.size() != 2)
        continue;
      const std::string c1 =
          g.nodes[m1j].inputs[0] == pw_name ? g.nodes[m1j].inputs[1]
                                            : g.nodes[m1j].inputs[0];
      if (c1 == pw_name || !mid1(c1)) continue;
      const size_t c1j = prod(c1);
      if (c1j == npos || g.nodes[c1j].op != "Sub" ||
          g.nodes[c1j].inputs.size() != 2 ||
          g.nodes[c1j].inputs[0] != x)
        continue;
      std::string xA;
      float mdivA = 1.f;
      if (!match_mean(g.nodes[c1j].inputs[1], &xA, &mdivA, &chain))
        continue;
      if (xA != x) continue;
      const auto* xd = dims_of(x);
      if (!xd || xd->size() < 2) continue;
      const int64_t D = xd->back();
      // ---- optional affine tail: Mul(gamma) then Add(beta)
      std::string out_name = g.nodes[m1j].outputs[0];
      std::string gamma, beta;
      {
        const size_t gj = cons1(out_name);
        if (gj != npos && g.nodes[gj].op == "Mul" &&
            g.nodes[gj].inputs.size() == 2 &&
            g.nodes[gj].outputs.size() == 1) {
          const std::string other =
              g.nodes[gj].inputs[0] == out_name ? g.nodes[gj].inputs[1]
                                                : g.nodes[gj].inputs[0];
          if (lastdim_vec_const(other, D)) {
            gamma = other;
            chain.push_back(gj);
            out_name = g.nodes[gj].outputs[0];
          }
        }
      }
      if (!gamma.empty()) {
        const size_t bj = cons1(out_name);
        if (bj != npos && g.nodes[bj].op == "Add" &&
            g.nodes[bj].inputs.size() == 2 &&
            g.nodes[bj].outputs.size() == 1) {
          const std::string other =
              g.nodes[bj].inputs[0] == out_name ? g.nodes[bj].inputs[1]
                                                : g.nodes[bj].inputs[0];
          if (lastdim_vec_const(other, D)) {
            beta = other;
            chain.push_back(bj);
            out_name = g.nodes[bj].outputs[0];
          }
        }
      }
      chain.insert(chain.end(), {idx, aidx, pj, m1j, c1j});
      Node f;
      f.op = "PtpuLayerNorm";
      f.inputs = {x};
      if (!gamma.empty()) f.inputs.push_back(gamma);
      if (!beta.empty()) f.inputs.push_back(beta);
      f.outputs = {out_name};
      Attr ae;
      ae.fval = eps;
      f.attrs["ln_eps"] = ae;
      Attr ama;
      ama.fval = mdivA;
      f.attrs["ln_mdiv"] = ama;
      Attr amb;
      amb.fval = mdivB;
      f.attrs["ln_mdiv2"] = amb;
      Attr av;
      av.fval = var_div;
      f.attrs["ln_vdiv"] = av;
      Attr ag;
      ag.ival = gamma.empty() ? 0 : 1;
      f.attrs["ln_gamma"] = ag;
      Attr ab;
      ab.ival = beta.empty() ? 0 : 1;
      f.attrs["ln_beta"] = ab;
      size_t last = 0;
      for (size_t j : chain) {
        dead[j] = 1;
        last = std::max(last, j);
      }
      fused_nodes_ += int(chain.size()) - 1;
      placed[last] = std::move(f);
    }

    apply_rewrite(dead, &placed);
  }

  /* Tanh-approximate GELU: the exporter emits
   *   Pow(x,3) -> Mul(c1) -> Add(x) -> Mul(c2) -> Tanh -> Add(c3) ->
   *   Mul(c4) -> Mul(x)
   * — eight full-tensor passes per FFN (one of them a serial pow and
   * one a transcendental) for one elementwise function. The fused
   * PtpuGelu replays the identical float ops in the identical order,
   * so it is BITWISE equal to the chain, in one threaded pass. */
  void fuse_gelu() {
    FuseIdx ix = build_fuse_idx();
    std::vector<char> dead(g.nodes.size(), 0);
    std::map<size_t, Node> placed;
    const size_t npos = size_t(-1);
    const auto mid1 = [&](const std::string& nm) {
      auto u = ix.uses.find(nm);
      return !ix.outset.count(nm) && !g.initializers.count(nm) &&
             u != ix.uses.end() && u->second.size() == 1;
    };
    const auto cons1 = [&](const std::string& nm) -> size_t {
      if (!mid1(nm)) return npos;
      const size_t j = ix.uses.find(nm)->second[0];
      return dead[j] ? npos : j;
    };
    // j = single consumer of nm, must be `op` with nm + a scalar const
    // (either order); returns the const value via *c
    const auto scalar_step = [&](const std::string& nm, const char* op2,
                                 float* c) -> size_t {
      const size_t j = cons1(nm);
      if (j == npos || g.nodes[j].op != op2 ||
          g.nodes[j].inputs.size() != 2 || g.nodes[j].outputs.size() != 1)
        return npos;
      const std::string& other = g.nodes[j].inputs[0] == nm
                                     ? g.nodes[j].inputs[1]
                                     : g.nodes[j].inputs[0];
      const Tensor* t = scalar_const(other);
      if (!t || other == nm) return npos;
      *c = t->f[0];
      return j;
    };
    for (size_t idx = 0; idx < g.nodes.size(); ++idx) {
      if (dead[idx]) continue;
      const Node& pw = g.nodes[idx];
      if (pw.op != "Pow" || pw.inputs.size() != 2 ||
          pw.outputs.size() != 1)
        continue;
      const Tensor* e = scalar_const(pw.inputs[1]);
      if (!e || e->f[0] != 3.0f) continue;
      const std::string x = pw.inputs[0];
      float c1, c2, c3, c4;
      const size_t m1j = scalar_step(pw.outputs[0], "Mul", &c1);
      if (m1j == npos) continue;
      // Add(x, c1*x^3) — the non-chain operand must be x itself
      const size_t a1j = cons1(g.nodes[m1j].outputs[0]);
      if (a1j == npos || g.nodes[a1j].op != "Add" ||
          g.nodes[a1j].inputs.size() != 2 ||
          g.nodes[a1j].outputs.size() != 1)
        continue;
      {
        const std::string& other =
            g.nodes[a1j].inputs[0] == g.nodes[m1j].outputs[0]
                ? g.nodes[a1j].inputs[1]
                : g.nodes[a1j].inputs[0];
        if (other != x) continue;
      }
      const size_t m2j = scalar_step(g.nodes[a1j].outputs[0], "Mul", &c2);
      if (m2j == npos) continue;
      const size_t tj = cons1(g.nodes[m2j].outputs[0]);
      if (tj == npos || g.nodes[tj].op != "Tanh" ||
          g.nodes[tj].outputs.size() != 1)
        continue;
      const size_t a2j = scalar_step(g.nodes[tj].outputs[0], "Add", &c3);
      if (a2j == npos) continue;
      const size_t m3j = scalar_step(g.nodes[a2j].outputs[0], "Mul", &c4);
      if (m3j == npos) continue;
      const size_t m4j = cons1(g.nodes[m3j].outputs[0]);
      if (m4j == npos || g.nodes[m4j].op != "Mul" ||
          g.nodes[m4j].inputs.size() != 2 ||
          g.nodes[m4j].outputs.size() != 1)
        continue;
      {
        const std::string& other =
            g.nodes[m4j].inputs[0] == g.nodes[m3j].outputs[0]
                ? g.nodes[m4j].inputs[1]
                : g.nodes[m4j].inputs[0];
        if (other != x) continue;
      }
      Node f;
      f.op = "PtpuGelu";
      f.inputs = {x};
      f.outputs = {g.nodes[m4j].outputs[0]};
      Attr a1a;
      a1a.fval = c1;
      f.attrs["gelu_c1"] = a1a;
      Attr a2a;
      a2a.fval = c2;
      f.attrs["gelu_c2"] = a2a;
      Attr a3a;
      a3a.fval = c3;
      f.attrs["gelu_c3"] = a3a;
      Attr a4a;
      a4a.fval = c4;
      f.attrs["gelu_c4"] = a4a;
      const size_t chain[] = {idx, m1j, a1j, m2j, tj, a2j, m3j, m4j};
      size_t last = 0;
      for (size_t j : chain) {
        dead[j] = 1;
        last = std::max(last, j);
      }
      fused_nodes_ += int(sizeof(chain) / sizeof(chain[0])) - 1;
      placed[last] = std::move(f);
    }
    apply_rewrite(dead, &placed);
  }

  /* Load-time graph rewrite (reference: the conv_bn_fuse /
   * conv_elementwise_add_act_fuse IR passes the AnalysisPredictor runs
   * before serving). Three rewrites, in order:
   *   1. Identity elimination (the exporter emits copy chains).
   *   2. Conv + per-channel affine chain + relu -> PtpuFusedConv: the
   *      eval-mode batchnorm lowers to Sub/Mul/Mul/Add over per-channel
   *      constants; the multiplicative part folds into the conv WEIGHTS
   *      and the additive part becomes a fused bias, so the whole chain
   *      collapses into the GEMM epilogue.
   *   3. MatMul + bias Add (+ activation) -> PtpuFusedGemm.
   * Only single-consumer, non-graph-output intermediates fuse; every
   * eliminated node removes a full-tensor materialization pass from the
   * serving hot path. */
  // Identity elimination: rewrite consumers through the alias. Runs
  // before BOTH fusion passes (the exporter's copy chains interleave
  // the quantize patterns too).
  void eliminate_identities() {
    const std::set<std::string> outset(g.output_names.begin(),
                                       g.output_names.end());
    std::map<std::string, std::string> alias;
    std::vector<Node> kept;
    for (auto& n : g.nodes) {
      for (auto& i : n.inputs) {
        auto it = alias.find(i);
        if (it != alias.end()) i = it->second;
      }
      if (n.op == "Identity" && !outset.count(n.outputs[0]))
        alias[n.outputs[0]] = n.inputs[0];
      else
        kept.push_back(std::move(n));
    }
    g.nodes.swap(kept);
  }

  // precondition: eliminate_identities() already ran (create calls
  // it once, before fuse_quant_ops — copy chains interleave BOTH
  // passes' patterns)
  void fuse_ops() {
    const std::set<std::string> outset(g.output_names.begin(),
                                       g.output_names.end());
    std::map<std::string, int> use_count;
    std::map<std::string, size_t> consumer;  // name -> unique consumer idx
    for (size_t k = 0; k < g.nodes.size(); ++k)
      for (const auto& i : g.nodes[k].inputs) {
        ++use_count[i];
        consumer[i] = k;
      }
    for (const auto& name : g.output_names) ++use_count[name];

    std::vector<char> dead(g.nodes.size(), 0);
    std::map<size_t, Node> placed;  // last chain position -> fused node

    for (size_t idx = 0; idx < g.nodes.size(); ++idx) {
      Node& n = g.nodes[idx];
      if (dead[idx] || n.outputs.size() != 1) continue;

      if (n.op == "Conv" && n.inputs.size() == 2) {
        const Tensor* wt = const_initializer(n.inputs[1]);
        if (!wt || !wt->is_float() || wt->dims.size() != 4) continue;
        const int64_t OC = wt->dims[0];
        std::vector<float> scale(size_t(OC), 1.f), bias(size_t(OC), 0.f);
        std::vector<float> c;
        int act = ACT_NONE;
        bool scaled = false;
        std::vector<size_t> chain;
        std::string cur = n.outputs[0];
        while (!outset.count(cur) && use_count[cur] == 1) {
          const size_t j = consumer[cur];
          if (j <= idx || dead[j]) break;
          const Node& m = g.nodes[j];
          if (m.outputs.size() != 1) break;
          if (act_code_of(m, &act)) {
            chain.push_back(j);
            cur = m.outputs[0];
            break;  // affine cannot fold through a nonlinearity
          }
          if (m.inputs.size() != 2) break;
          const bool cur_first = m.inputs[0] == cur;
          const std::string& other = m.inputs[cur_first ? 1 : 0];
          if (!channel_const(other, OC, &c)) break;
          if (m.op == "Add") {
            for (int64_t q = 0; q < OC; ++q) bias[size_t(q)] += c[size_t(q)];
          } else if (m.op == "Sub" && cur_first) {
            for (int64_t q = 0; q < OC; ++q) bias[size_t(q)] -= c[size_t(q)];
          } else if (m.op == "Sub") {  // c - cur
            for (int64_t q = 0; q < OC; ++q) {
              scale[size_t(q)] = -scale[size_t(q)];
              bias[size_t(q)] = c[size_t(q)] - bias[size_t(q)];
            }
            scaled = true;
          } else if (m.op == "Mul") {
            for (int64_t q = 0; q < OC; ++q) {
              scale[size_t(q)] *= c[size_t(q)];
              bias[size_t(q)] *= c[size_t(q)];
            }
            scaled = true;
          } else if (m.op == "Div" && cur_first) {
            for (int64_t q = 0; q < OC; ++q) {
              scale[size_t(q)] /= c[size_t(q)];
              bias[size_t(q)] /= c[size_t(q)];
            }
            scaled = true;
          } else {
            break;
          }
          chain.push_back(j);
          cur = m.outputs[0];
        }
        if (chain.empty()) continue;
        Node f;
        f.op = "PtpuFusedConv";
        f.attrs = n.attrs;
        Attr aa;
        aa.ival = act;
        f.attrs["ptpu_act"] = aa;
        std::string wname = n.inputs[1];
        if (scaled) {
          Tensor w2 = *wt;
          const int64_t per_oc = w2.numel() / OC;
          for (int64_t q = 0; q < OC; ++q)
            for (int64_t t = 0; t < per_oc; ++t)
              w2.f[size_t(q * per_oc + t)] *= scale[size_t(q)];
          wname = n.inputs[1] + "__bnfold" + std::to_string(idx);
          add_initializer(wname, std::move(w2));
        }
        const std::string bname = "__ptpu_bias_" + std::to_string(idx);
        Tensor bt;
        bt.dtype = DT_F32;
        bt.dims = {OC};
        bt.f.assign(bias.begin(), bias.end());
        add_initializer(bname, std::move(bt));
        f.inputs = {n.inputs[0], wname, bname};
        f.outputs = {cur};
        dead[idx] = 1;
        for (size_t j : chain) dead[j] = 1;
        fused_nodes_ += int(chain.size());
        placed[chain.back()] = std::move(f);

      } else if (n.op == "MatMul" && n.inputs.size() == 2) {
        const Tensor* bt2 = const_initializer(n.inputs[1]);
        if (!bt2 || !bt2->is_float() || bt2->dims.size() < 2) continue;
        const int64_t N = bt2->dims.back();
        std::vector<float> bias;
        int act = ACT_NONE;
        std::vector<size_t> chain;
        std::string cur = n.outputs[0];
        // optional bias Add
        if (!outset.count(cur) && use_count[cur] == 1) {
          const size_t j = consumer[cur];
          if (j > idx && !dead[j] && g.nodes[j].op == "Add" &&
              g.nodes[j].outputs.size() == 1 &&
              g.nodes[j].inputs.size() == 2) {
            const Node& m = g.nodes[j];
            const bool cur_first = m.inputs[0] == cur;
            if (lastdim_const(m.inputs[cur_first ? 1 : 0], N, &bias)) {
              chain.push_back(j);
              cur = m.outputs[0];
            }
          }
        }
        // optional activation
        if (!outset.count(cur) && use_count[cur] == 1) {
          const size_t j = consumer[cur];
          if (j > idx && !dead[j] && g.nodes[j].outputs.size() == 1) {
            int a2 = ACT_NONE;
            if (act_code_of(g.nodes[j], &a2)) {
              act = a2;
              chain.push_back(j);
              cur = g.nodes[j].outputs[0];
            }
          }
        }
        if (chain.empty()) continue;
        if (bias.empty()) bias.assign(size_t(N), 0.f);
        Node f;
        f.op = "PtpuFusedGemm";
        Attr aa;
        aa.ival = act;
        f.attrs["ptpu_act"] = aa;
        const std::string bname = "__ptpu_bias_" + std::to_string(idx);
        Tensor bt;
        bt.dtype = DT_F32;
        bt.dims = {N};
        bt.f.assign(bias.begin(), bias.end());
        add_initializer(bname, std::move(bt));
        f.inputs = {n.inputs[0], n.inputs[1], bname};
        f.outputs = {cur};
        dead[idx] = 1;
        for (size_t j : chain) dead[j] = 1;
        fused_nodes_ += int(chain.size());
        placed[chain.back()] = std::move(f);

      } else if (bin_code(n.op) != B_NONE && bin_code(n.op) <= B_MIN &&
                 n.inputs.size() == 2) {
        // arithmetic binary + activation (the residual-join Add + relu
        // every ResNet block ends with): one fused elementwise pass
        const std::string& cur = n.outputs[0];
        if (outset.count(cur) || use_count[cur] != 1) continue;
        const size_t j = consumer[cur];
        if (j <= idx || dead[j] || g.nodes[j].outputs.size() != 1)
          continue;
        int act = ACT_NONE;
        if (!act_code_of(g.nodes[j], &act)) continue;
        Node f;
        f.op = "PtpuFusedBinary";
        Attr ab;
        ab.ival = bin_code(n.op);
        f.attrs["ptpu_bin"] = ab;
        Attr aa;
        aa.ival = act;
        f.attrs["ptpu_act"] = aa;
        f.inputs = n.inputs;
        f.outputs = {g.nodes[j].outputs[0]};
        dead[idx] = 1;
        dead[j] = 1;
        fused_nodes_ += 1;
        placed[j] = std::move(f);
      }
    }

    if (placed.empty() && std::none_of(dead.begin(), dead.end(),
                                       [](char d) { return d != 0; })) {
      prune_dead_initializers();
      return;
    }
    std::vector<Node> rebuilt;
    rebuilt.reserve(g.nodes.size());
    for (size_t k = 0; k < g.nodes.size(); ++k) {
      auto it = placed.find(k);
      if (it != placed.end())
        rebuilt.push_back(std::move(it->second));
      else if (!dead[k])
        rebuilt.push_back(std::move(g.nodes[k]));
    }
    g.nodes.swap(rebuilt);
    prune_dead_initializers();
  }

  /* Pre-pack constant GEMM operands into panel layout once at load
   * (weights dominate pack traffic at serve time otherwise); for int
   * weights the int8 value scan result is cached too, so the serve-time
   * exactness check only scans activations. */
  void prepack_weights() {
    for (const auto& n : g.nodes) {
      if ((n.op == "Conv" || n.op == "PtpuFusedConv") &&
          n.inputs.size() >= 2) {
        const Tensor* wp = const_initializer(n.inputs[1]);
        if (!wp || wp->dims.size() != 4) continue;
        const Tensor& w = *wp;
        const int64_t group = attr_i(n, "group", 1);
        const int64_t OC = w.dims[0];
        if (group <= 0 || OC % group) continue;
        const int64_t ocg = OC / group;
        const int64_t CK = w.dims[1] * w.dims[2] * w.dims[3];
        const std::string key =
            "a:" + n.inputs[1] + ":" + std::to_string(group);
        if (packed_w_.count(key)) continue;
        PackedMat pm;
        const int64_t apsz = a_pack_size(ocg, CK);
        if (w.is_float()) {
          pm.f.resize(size_t(apsz * group));
          for (int64_t gi = 0; gi < group; ++gi)
            pack_a<float, float>(w.f.data() + gi * ocg * CK, ocg, CK,
                                 pm.f.data() + gi * apsz);
        } else {
          pm.int8_ok = int8_vals_ok(w.i.data(), w.i.size());
          if (pm.int8_ok) {
            pm.i.resize(size_t(apsz * group));
            for (int64_t gi = 0; gi < group; ++gi)
              pack_a<int64_t, int32_t>(w.i.data() + gi * ocg * CK, ocg, CK,
                                       pm.i.data() + gi * apsz);
          }
        }
        packed_w_[key] = std::move(pm);
      } else if ((n.op == "MatMul" || n.op == "PtpuFusedGemm") &&
                 n.inputs.size() >= 2) {
        const Tensor* bp = const_initializer(n.inputs[1]);
        if (!bp || bp->dims.size() != 2) continue;
        const Tensor& b = *bp;
        const int64_t K = b.dims[0], N = b.dims[1];
        const std::string key = "b:" + n.inputs[1];
        if (packed_w_.count(key)) continue;
        PackedMat pm;
        if (b.is_float()) {
          // weight-only int4 (opt-in, PTPU_INT4=1): quantize eligible
          // projection weights into nibble panels INSTEAD of fp32
          // panels — 8x less weight traffic on the decode GEMV. Tiny
          // or non-finite weights keep the exact fp32 panels.
          bool q4_done = false;
          if (int4_enabled() && K * N >= Q4_MIN_ELEMS) {
            const int64_t G = q4_pick_group(b.f.data(), K, N);
            PackedMat qm;
            qm.q4.resize(size_t(q4_data_size(K, N)));
            qm.q4s.assign(size_t(q4_scale_size(K, N, G)), 0.f);
            qm.q4z.assign(qm.q4s.size(), 0.f);
            if (pack_b_q4(b.f.data(), K, N, G, qm.q4.data(),
                          qm.q4s.data(), qm.q4z.data())) {
              qm.q4_group = G;
              pm = std::move(qm);
              q4_done = true;
            }
          }
          if (!q4_done) {
            pm.f.resize(size_t(b_pack_size(K, N)));
            pack_b<float, float>(b.f.data(), K, N, pm.f.data());
          }
        } else {
          pm.int8_ok = int8_vals_ok(b.i.data(), b.i.size());
          if (pm.int8_ok) {
            // int32 panels always (the batch-1 GEMV path reads them
            // regardless of ISA); VNNI machines ADD the pair layout
            // for the M > 1 vpdpwssd kernel — ~1.5x weight-pack bytes
            // on exactly the machines with the most cache to spare
            pm.i.resize(size_t(b_pack_size(K, N)));
            pack_b<int64_t, int32_t>(b.i.data(), K, N, pm.i.data());
            if (isa_vnni()) {
              pm.i16.resize(size_t(b_pack16_size(K, N)));
              pack_b16(b.i.data(), K, N, pm.i16.data());
            }
          }
        }
        packed_w_[key] = std::move(pm);
      }
    }
  }

  /* Static memory planner (reference: memory_optimize_pass computing
   * tensor lifetimes over the IR graph and assigning shared offsets).
   * The exported artifact has static input shapes, so one load-time
   * dry run with dummy inputs yields every intermediate's exact byte
   * size; a def/last-use walk over the node list then assigns each
   * output an offset in one arena via the shared best-fit machinery
   * (ptpu::PlanArena over csrc/ptpu_arena.h). Serving binds outputs
   * into the arena — zero per-run allocation or zero-fill on the hot
   * path. Falls back to per-tensor allocation whenever shapes are
   * dynamic or the caller binds inputs with different dims. */
  void plan_memory() {
    planned_ = false;
    if (g.nodes.empty()) return;
    for (const auto& name : g.input_names) {
      auto it = g.input_dims.find(name);
      if (it == g.input_dims.end()) return;
      for (auto d : it->second)
        if (d <= 0) return;  // symbolic/dynamic dim: no static plan
    }
    for (const auto& n : g.nodes)
      if (n.outputs.size() != 1) return;
    // dummy zero inputs (initializer-shadowed inputs keep the default;
    // inputs with no surviving consumer — the paged rewrite's cache
    // inputs — are never bound, so they cost neither plan-time
    // allocation nor a run-time binding)
    std::vector<std::string> dummies;
    for (const auto& name : g.input_names) {
      if (g.initializers.count(name)) continue;
      if (dead_inputs_.count(name)) continue;
      Tensor t;
      t.dims = g.input_dims[name];
      auto dt = g.input_dtypes.find(name);
      t.dtype = dt == g.input_dtypes.end() ? DT_F32 : dt->second;
      if (t.dtype == DT_F64) t.dtype = DT_F32;
      t.alloc();
      env[name] = std::move(t);
      dummies.push_back(name);
    }
    // whatever happens, the dry run must not leak into serving state: a
    // run() without set_input must still fail 'missing input tensor'
    // (not silently compute f(0)), and the dry-run intermediates must
    // not sit in memory until the first real run
    const auto scrub = [&] {
      for (const auto& name : dummies) env.erase(name);
      for (const auto& n : g.nodes)
        for (const auto& o : n.outputs)
          if (!g.initializers.count(o)) env.erase(o);
    };
    std::vector<size_t> bytes(g.nodes.size(), 0);
    try {
      for (size_t k = 0; k < g.nodes.size(); ++k) {
        run_node(g.nodes[k]);
        const Tensor& t = env[g.nodes[k].outputs[0]];
        bytes[k] = size_t(t.numel()) *
                   (t.is_float() ? sizeof(float) : sizeof(int64_t));
      }
    } catch (const std::exception&) {
      scrub();
      return;  // a data-dependent op at zero input: serve unplanned
    }
    scrub();
    std::map<std::string, size_t> def_of, last_use;
    for (size_t k = 0; k < g.nodes.size(); ++k)
      def_of[g.nodes[k].outputs[0]] = k;
    for (size_t k = 0; k < g.nodes.size(); ++k)
      for (const auto& i : g.nodes[k].inputs)
        if (def_of.count(i)) last_use[i] = k;
    for (const auto& name : g.output_names)
      last_use[name] = g.nodes.size();  // outputs live to the end
    ptpu::PlanArena arena(64);
    plan_.assign(g.nodes.size(), PlanSlot{});
    for (size_t k = 0; k < g.nodes.size(); ++k) {
      plan_[k].bytes = bytes[k];
      plan_[k].off = arena.Alloc(bytes[k]);
      plan_[k].valid = true;
      std::set<std::string> ended(g.nodes[k].inputs.begin(),
                                  g.nodes[k].inputs.end());
      ended.insert(g.nodes[k].outputs[0]);  // dead output frees at once
      for (const auto& nm : ended) {
        auto d = def_of.find(nm);
        if (d == def_of.end()) continue;
        auto lu = last_use.find(nm);
        const size_t last = lu == last_use.end() ? d->second : lu->second;
        if (last == k)
          arena.Free(plan_[d->second].off, plan_[d->second].bytes);
      }
    }
    arena_bytes_ = arena.Size();
    arena_storage_.assign(size_t(arena_bytes_) + 64, 0);
    arena_base_ = arena_storage_.data();
    arena_base_ += (64 - (reinterpret_cast<uintptr_t>(arena_base_) & 63)) & 63;
    planned_ = true;
  }

  bool inputs_match_plan() const {
    for (const auto& name : g.input_names) {
      if (dead_inputs_.count(name)) continue;  // rewritten-away: no
                                               // node reads them
      auto it = env.find(name);
      auto want = g.input_dims.find(name);
      if (it == env.end() || want == g.input_dims.end()) return false;
      if (it->second.dims != want->second) return false;
    }
    return true;
  }

  void run() {
    outputs.clear();
    static const bool profile =
        std::getenv("PTPU_PREDICTOR_PROFILE") != nullptr;
    // route this run's parallel_for dispatches to the private sub-pool
    PoolScope pool_scope(pool_);
    const bool use_plan = planned_ && inputs_match_plan();
    if (!use_plan)
      dyn_fallback_runs_.fetch_add(1, std::memory_order_relaxed);
    if (node_stat_.size() != g.nodes.size()) build_stats_index();
    const ProfEnabledFn enabled_fn =
        g_prof_enabled.load(std::memory_order_relaxed);
    const ProfRecordFn record_fn =
        g_prof_record.load(std::memory_order_relaxed);
    // RecordEvent spans only when the host profiler is wired AND on
    const bool trace = enabled_fn && record_fn && enabled_fn();
    const int64_t run_t0 = ptpu::NowUs();
    try {
      for (size_t k = 0; k < g.nodes.size(); ++k) {
        AllocHint hint{use_plan && plan_[k].valid
                           ? arena_base_ + plan_[k].off
                           : nullptr,
                       use_plan && plan_[k].valid ? plan_[k].bytes : 0,
                       false};
        g_alloc_hint = hint.base ? &hint : nullptr;
        const Node& n = g.nodes[k];
        const int64_t t0 = ptpu::NowUs();
        run_node(n);
        static const bool shp_dbg =
            std::getenv("PTPU_TRACE_SHAPES") != nullptr;
        if (shp_dbg && !n.outputs.empty() && env.count(n.outputs[0])) {
          std::string d;
          for (auto v : env[n.outputs[0]].dims)
            d += std::to_string(v) + ",";
          std::fprintf(stderr, "[shape] %s -> %s [%s]\n", n.op.c_str(),
                       n.outputs[0].c_str(), d.c_str());
        }
        const int64_t t1 = ptpu::NowUs();
        g_alloc_hint = nullptr;
        OpStat* s = node_stat_[k];
        s->calls += 1;
        s->time_us += uint64_t(t1 - t0);
        if (!n.outputs.empty()) {
          auto it = env.find(n.outputs[0]);
          if (it != env.end()) {
            const Tensor& t = it->second;
            s->bytes += uint64_t(t.numel()) *
                        (t.is_float() ? sizeof(float) : sizeof(int64_t));
          }
        }
        if (trace) record_fn(n.op.c_str(), t0, t1);
      }
    } catch (...) {
      g_alloc_hint = nullptr;  // never leave a dangling stack hint
      throw;
    }
    const int64_t run_t1 = ptpu::NowUs();
    runs_ += 1;
    run_time_us_ += uint64_t(run_t1 - run_t0);
    run_us_.Observe(uint64_t(run_t1 - run_t0));
    if (trace) record_fn("predictor::run", run_t0, run_t1);
    if (profile)
      // per-op-type cumulative wall time to stderr — the doctor's view
      // for "which op dominates this artifact"
      for (const auto& kv : op_stats_)
        std::fprintf(stderr, "ptpu_profile %-20s %.3f ms (%llu calls)\n",
                     kv.first.c_str(), double(kv.second.time_us) * 1e-3,
                     (unsigned long long)kv.second.calls);
    for (const auto& name : g.output_names) {
      auto it = env.find(name);
      if (it == env.end())
        throw std::runtime_error("output '" + name + "' never produced");
      // same dims-vs-storage invariant as in(): callers copy
      // numel()-many elements out of this buffer
      const Tensor& t = it->second;
      const size_t have = t.is_float() ? t.f.size() : t.i.size();
      if (size_t(t.numel()) > have)
        throw std::runtime_error(
            "output '" + name + "' claims " + std::to_string(t.numel()) +
            " elements but holds " + std::to_string(have) +
            " (dims/storage mismatch)");
      outputs.push_back(t);
    }
  }
};


static const char* kBinaryOps[] = {
    "Add", "Sub", "Mul", "Div", "Max", "Min", "Pow", "Mod", "Less",
    "LessOrEqual", "Greater", "GreaterOrEqual", "Equal", "And", "Or",
    "Xor"};
static const char* kUnaryOps[] = {
    "Neg", "Abs", "Exp", "Log", "Sqrt", "Reciprocal", "Sigmoid", "Tanh",
    "Erf", "Floor", "Ceil", "Round", "Sign", "Relu", "Not", "Sin", "Cos",
    "Tan", "Asin", "Acos", "Atan", "Sinh", "Cosh", "Asinh", "Acosh",
    "Atanh"};

bool contains(const char* const* arr, size_t n, const std::string& s) {
  for (size_t k = 0; k < n; ++k)
    if (s == arr[k]) return true;
  return false;
}

void Predictor::run_node(const Node& n) {
  const std::string& op = n.op;
  // same hostile-artifact guard as Predictor::in(): every op writes
  // n.outputs[0] (multi-output ops index further and are checked at
  // their sites)
  if (n.outputs.empty())
    throw std::runtime_error("op " + op + " has no outputs");
  auto out = [&](Tensor t) { env[n.outputs[0]] = std::move(t); };

  if (op == "Identity") {
    env[n.outputs[0]] = in(n, 0);
  } else if (op == "PtpuFusedBinary" ||
             contains(kBinaryOps, sizeof(kBinaryOps) / sizeof(char*), op)) {
    const Tensor &a = in(n, 0), &b = in(n, 1);
    const bool fusedb = op == "PtpuFusedBinary";
    // resolved once, not per element (fused nodes carry the code)
    const BinCode code =
        fusedb ? BinCode(attr_i(n, "ptpu_bin", B_ADD)) : bin_code(op);
    const int bact =
        fusedb ? int(attr_i(n, "ptpu_act", ACT_NONE)) : ACT_NONE;
    Tensor o;
    o.dims = bcast_dims(a.dims, b.dims);
    bool cmp = code >= B_LT && code <= B_XOR;
    o.dtype = cmp ? DT_BOOL
                  : ((a.is_float() || b.is_float()) ? DT_F32 : a.dtype);
    o.alloc();
    if (a.is_float() && b.is_float() && o.dtype == DT_F32 &&
        code <= B_MIN &&
        (a.dims == b.dims || a.numel() == 1 || b.numel() == 1)) {
      /* same-shape or scalar-operand arithmetic (residual joins,
       * attention scaling): flat loop — serial when small (a pool
       * dispatch costs more than the op), threaded chunks when big —
       * with the fused activation applied in the same pass; these are
       * memory-bound, so one pass instead of the op-then-relu pair
       * halves the traffic. */
      const bool as = a.numel() == 1 && o.numel() != 1;
      const bool bs = b.numel() == 1 && o.numel() != 1;
      const float *af = a.f.data(), *bf = b.f.data();
      float* of = o.f.data();
      // transcendental fused activations (the GELU tanh) are
      // compute-bound: thread them at the Exp/Erf grain, not the
      // memory-bound elementwise grain (measured ~1.2 ms/pass on a
      // 256k-element tanh at the coarse grain — 4 chunks on 24 cores)
      const int64_t bin_grain =
          (bact == ACT_SIGMOID || bact == ACT_TANH) ? (1 << 13)
                                                    : (1 << 16);
      with_bin_op(code, [&](auto op) {
        with_act(bact, [&](auto act) {
          parallel_for(o.numel(), bin_grain, [&](int64_t lo, int64_t hi) {
            if (as) {
              const float av = af[0];
              for (int64_t k = lo; k < hi; ++k)
                of[k] = act(op(av, bf[k]));
            } else if (bs) {
              const float bv = bf[0];
              for (int64_t k = lo; k < hi; ++k)
                of[k] = act(op(af[k], bv));
            } else {
              for (int64_t k = lo; k < hi; ++k)
                of[k] = act(op(af[k], bf[k]));
            }
          });
        });
      });
      out(std::move(o));
      return;
    }
    if (a.is_float() && b.is_float() && o.dtype == DT_F32 &&
        code <= B_MIN && o.dims.size() >= 2 && o.dims.back() > 1) {
      /* row-broadcast: one operand is constant along the last axis
       * (layernorm's mean/rstd [.., 1] against [.., D]) — one operand
       * index per ROW, flat vectorizable inner loops. */
      const auto row_const = [](const Tensor& t) {
        return t.dims.empty() || t.dims.back() == 1;
      };
      const bool b_row = a.dims == o.dims && row_const(b);
      const bool a_row = !b_row && b.dims == o.dims && row_const(a);
      if (b_row || a_row) {
        const int64_t inner = o.dims.back();
        const int64_t rows = o.numel() / inner;
        const Tensor& full = b_row ? a : b;
        const Tensor& rc = b_row ? b : a;
        const float* ff = full.f.data();
        const float* rf = rc.f.data();
        float* of = o.f.data();
        with_bin_op(code, [&](auto op) {
          with_act(bact, [&](auto act) {
            parallel_for(
                rows, std::max<int64_t>(1, 65536 / inner),
                [&](int64_t r0, int64_t r1) {
              for (int64_t row = r0; row < r1; ++row) {
                const float rv =
                    rf[bcast_index(row * inner, o.dims, rc.dims)];
                const float* src = ff + row * inner;
                float* dst = of + row * inner;
                if (b_row) {
                  for (int64_t j = 0; j < inner; ++j)
                    dst[j] = act(op(src[j], rv));
                } else {
                  for (int64_t j = 0; j < inner; ++j)
                    dst[j] = act(op(rv, src[j]));
                }
              }
            });
          });
        });
        out(std::move(o));
        return;
      }
    }
    if (a.is_float() && b.is_float() && o.dtype == DT_F32 &&
        code <= B_MIN && o.dims.size() >= 2 && o.dims.back() > 0) {
      // ^ dims.back() > 0: a zero last axis divides rows by zero
      // below (fuzzing finding, ISSUE 11; repro:
      // csrc/fuzz/corpus/onnx/crash-rowbcast-zero-axis.bin)
      /* last-axis vector broadcast: one operand is a [1,..,N] vector
       * against a full [..,N] tensor — the bias-add (+act) epilogue
       * shape of every un-fusable GEMM/dequant chain. One vector
       * lookup per column, flat row loops, act applied in the same
       * pass (the generic walk below computes in double and cannot
       * carry the fused activation). */
      const int64_t inner = o.dims.back();
      const auto vec_like = [&](const Tensor& t) {
        return t.numel() == inner && !t.dims.empty() &&
               t.dims.back() == inner;
      };
      const bool b_vec = a.dims == o.dims && vec_like(b);
      const bool a_vec = !b_vec && b.dims == o.dims && vec_like(a);
      if (b_vec || a_vec) {
        const int64_t rows = o.numel() / inner;
        const float* ff = (b_vec ? a : b).f.data();
        const float* vf = (b_vec ? b : a).f.data();
        float* of = o.f.data();
        with_bin_op(code, [&](auto op) {
          with_act(bact, [&](auto act) {
            parallel_for(
                rows,
                std::max<int64_t>(1, 65536 / std::max<int64_t>(inner, 1)),
                [&](int64_t r0, int64_t r1) {
              for (int64_t row = r0; row < r1; ++row) {
                const float* src = ff + row * inner;
                float* dst = of + row * inner;
                if (b_vec) {
                  for (int64_t j = 0; j < inner; ++j)
                    dst[j] = act(op(src[j], vf[j]));
                } else {
                  for (int64_t j = 0; j < inner; ++j)
                    dst[j] = act(op(vf[j], src[j]));
                }
              }
            });
          });
        });
        out(std::move(o));
        return;
      }
    }
    if (a.is_float() && b.is_float() && o.dtype == DT_F32 &&
        bact == ACT_NONE) {
      const float *af = a.f.data(), *bf = b.f.data();
      float* of = o.f.data();
      switch (code) {  // the arithmetic hot set gets branch-free loops
        case B_ADD:
          bcast_walk(o.dims, a.dims, b.dims, [&](int64_t k, int64_t ai,
              int64_t bi) { of[k] = af[ai] + bf[bi]; });
          break;
        case B_SUB:
          bcast_walk(o.dims, a.dims, b.dims, [&](int64_t k, int64_t ai,
              int64_t bi) { of[k] = af[ai] - bf[bi]; });
          break;
        case B_MUL:
          bcast_walk(o.dims, a.dims, b.dims, [&](int64_t k, int64_t ai,
              int64_t bi) { of[k] = af[ai] * bf[bi]; });
          break;
        case B_DIV:
          bcast_walk(o.dims, a.dims, b.dims, [&](int64_t k, int64_t ai,
              int64_t bi) { of[k] = af[ai] / bf[bi]; });
          break;
        case B_MAX:
          bcast_walk(o.dims, a.dims, b.dims, [&](int64_t k, int64_t ai,
              int64_t bi) { of[k] = std::max(af[ai], bf[bi]); });
          break;
        case B_MIN:
          bcast_walk(o.dims, a.dims, b.dims, [&](int64_t k, int64_t ai,
              int64_t bi) { of[k] = std::min(af[ai], bf[bi]); });
          break;
        case B_POW:
          // GELU/LN graphs are full of pow(x, 2|3|0.5) with a scalar
          // exponent — std::pow per element is ~20x a multiply
          if (b.numel() == 1 && bf[0] == 2.0f) {
            for (int64_t k = 0; k < o.numel(); ++k)
              of[k] = af[k] * af[k];
          } else if (b.numel() == 1 && bf[0] == 3.0f) {
            for (int64_t k = 0; k < o.numel(); ++k)
              of[k] = af[k] * af[k] * af[k];
          } else {
            // no sqrt shortcut for exponent 0.5: IEEE pow(-inf, .5)
            // is +inf and pow(-0., .5) is +0., sqrt disagrees on both
            bcast_walk(o.dims, a.dims, b.dims, [&](int64_t k, int64_t ai,
                int64_t bi) { of[k] = std::pow(af[ai], bf[bi]); });
          }
          break;
        default:
          bcast_walk(o.dims, a.dims, b.dims, [&](int64_t k, int64_t ai,
              int64_t bi) {
            o.set(k, apply_bin_code(code, af[ai], bf[bi]));
          });
      }
    } else {
      bcast_walk(o.dims, a.dims, b.dims,
                 [&](int64_t k, int64_t ai, int64_t bi) {
        double v = apply_bin_code(code, a.at(ai), b.at(bi));
        if (bact != ACT_NONE) v = act_apply(float(v), bact);
        o.set(k, v);
      });
    }
    out(std::move(o));
  } else if (contains(kUnaryOps, sizeof(kUnaryOps) / sizeof(char*), op)) {
    const Tensor& a = in(n, 0);
    Tensor o;
    o.dims = a.dims;
    o.dtype = (op == "Not") ? DT_BOOL : a.dtype;
    o.alloc();
    const UnCode code = un_code(op);
    const int64_t nel = o.numel();
    if (a.is_float() && o.is_float()) {
      const float* af = a.f.data();
      float* of = o.f.data();
      // threaded element chunks: the transcendental set (Exp in every
      // softmax, Erf in every GELU) is compute-bound and scales; the
      // cheap set is memory-bound, so it needs much more work per
      // chunk before a pool dispatch pays off
      const bool cheap = code == U_RELU || code == U_NEG ||
                         code == U_ABS || code == U_SQRT ||
                         code == U_FLOOR || code == U_CEIL ||
                         code == U_ROUND || code == U_SIGN ||
                         code == U_NOT;
      parallel_for(nel, cheap ? (1 << 16) : (1 << 13),
                   [&](int64_t lo, int64_t hi) {
        switch (code) {
          case U_RELU:
            for (int64_t k = lo; k < hi; ++k)
              of[k] = af[k] > 0.f ? af[k] : 0.f;
            break;
          case U_NEG:
            for (int64_t k = lo; k < hi; ++k) of[k] = -af[k];
            break;
          case U_ABS:
            for (int64_t k = lo; k < hi; ++k) of[k] = std::fabs(af[k]);
            break;
          case U_SQRT:
            for (int64_t k = lo; k < hi; ++k) of[k] = std::sqrt(af[k]);
            break;
          default:
            for (int64_t k = lo; k < hi; ++k)
              of[k] = float(apply_un_code(code, af[k]));
        }
      });
    } else {
      for (int64_t k = 0; k < nel; ++k)
        o.set(k, apply_un_code(code, a.at(k)));
    }
    out(std::move(o));
  } else if (op == "Clip") {
    const Tensor& a = in(n, 0);
    double lo = in(n, 1).at(0), hi = in(n, 2).at(0);
    Tensor o = a;
    for (int64_t k = 0; k < o.numel(); ++k)
      o.set(k, std::min(hi, std::max(lo, a.at(k))));
    out(std::move(o));
  } else if (op == "Where") {
    const Tensor &c = in(n, 0), &x = in(n, 1), &y = in(n, 2);
    Tensor o;
    o.dims = bcast_dims(bcast_dims(c.dims, x.dims), y.dims);
    o.dtype = x.dtype;
    o.alloc();
    for (int64_t k = 0; k < o.numel(); ++k) {
      bool cond = c.at(bcast_index(k, o.dims, c.dims)) != 0;
      o.set(k, cond ? x.at(bcast_index(k, o.dims, x.dims))
                    : y.at(bcast_index(k, o.dims, y.dims)));
    }
    out(std::move(o));
  } else if (op == "Cast") {
    const Tensor& a = in(n, 0);
    Tensor o;
    o.dims = a.dims;
    o.dtype = int(attr_i(n, "to", DT_F32));
    if (o.dtype == DT_F64) o.dtype = DT_F32;
    o.alloc();
    // threaded typed loops: int8 artifacts cast every activation
    // tensor twice per layer (quantize + dequantize) — the old serial
    // double-dispatch loop was their top serving cost
    const int64_t nel = o.numel();
    const int od = o.dtype;
    const float* af = a.f.data();
    const int64_t* ai = a.i.data();
    float* of = o.f.data();
    int64_t* oi = o.i.data();
    const bool aflt = a.is_float(), oflt = o.is_float();
    parallel_for(nel, 1 << 15, [&](int64_t lo, int64_t hi) {
      for (int64_t k = lo; k < hi; ++k) {
        const double v = aflt ? double(af[k]) : double(ai[k]);
        if (oflt) {
          of[k] = float(v);
        } else if (od == DT_BOOL) {
          oi[k] = v != 0;
        } else if (od == DT_I8) {  // wrap like a C int8_t conversion
          oi[k] = int8_t(int64_t(v));
        } else {
          oi[k] = int64_t(v);
        }
      }
    });
    out(std::move(o));
  } else if (op == "Reshape") {
    const Tensor& a = in(n, 0);
    const Tensor& shp = in(n, 1);
    std::vector<int64_t> want(shp.i.begin(), shp.i.end());
    // overflow-checked product (fuzzing finding, ISSUE 11; repro:
    // csrc/fuzz/corpus/onnx/crash-reshape-overflow.bin); a CONCRETE
    // shape that does not match the element count is an error, not a
    // dims/storage-mismatched tensor for a later op to index with
    uint64_t wn_u = 1;
    bool concrete = true;
    for (auto d : want) {
      if (d <= 0) {
        concrete = false;
        continue;
      }
      if (wn_u > uint64_t(INT64_MAX) / uint64_t(d))
        throw std::runtime_error("Reshape: target shape overflows");
      wn_u *= uint64_t(d);
    }
    int64_t wn = int64_t(wn_u);
    /* Batch repair under a bucket-ladder override (bo_from_ ->
     * bo_to_): exporters bake the trace batch into shape constants,
     * so a batch-carrying Reshape target arrives with the EXPORT
     * batch folded into one of its dims ([B,1,heads,hd] head splits,
     * [B*heads,W,hd] attention flattenings, [1,B*M,K] matmul
     * flattenings). The element count disambiguates: repair only
     * fires when the target is off by exactly the export/override
     * ratio, and the dim to scale is the LEFTMOST one divisible by
     * the export batch — the exporter's layouts lead with the batch
     * (possibly folded into a product like B*heads). Preferring a
     * later dim merely EQUAL to the batch mis-repaired width-k decode
     * artifacts whose window width numerically equals the batch
     * ([B, W, 3, heads, hd] with W == B scaled W instead of B). A
     * graph the rule cannot carry still throws below and the serving
     * layer drops that bucket at probe time — never silent wrong
     * shapes. */
    if (concrete && wn != a.numel() && bo_from_ > 1 &&
        bo_to_ != bo_from_ && wn % bo_from_ == 0 &&
        wn / bo_from_ * bo_to_ == a.numel()) {
      int pick = -1;
      for (size_t z = 0; pick < 0 && z < want.size(); ++z)
        if (want[z] > 0 && want[z] % bo_from_ == 0) pick = int(z);
      if (pick >= 0) {
        want[size_t(pick)] = want[size_t(pick)] / bo_from_ * bo_to_;
        wn = wn / bo_from_ * bo_to_;
      }
    }
    if (concrete && wn != a.numel())
      throw std::runtime_error(
          "Reshape: target shape has " + std::to_string(wn) +
          " elements, tensor has " + std::to_string(a.numel()));
    if (concrete) {  // mismatches threw above; dynamic markers fall through
      // plain copy into the (possibly arena-bound) output — threaded
      // memcpy instead of a per-run owning deep copy
      Tensor o;
      o.dtype = a.dtype;
      o.dims = std::move(want);
      o.alloc();
      const int64_t esz = a.is_float() ? 4 : 8;
      const char* src = a.is_float()
                            ? reinterpret_cast<const char*>(a.f.data())
                            : reinterpret_cast<const char*>(a.i.data());
      char* dst = o.is_float() ? reinterpret_cast<char*>(o.f.data())
                               : reinterpret_cast<char*>(o.i.data());
      parallel_for(wn, 1 << 16, [&](int64_t lo, int64_t hi) {
        std::memcpy(dst + lo * esz, src + lo * esz,
                    size_t(hi - lo) * size_t(esz));
      });
      out(std::move(o));
    } else {  // 0/-1 markers: keep the legacy storage-carrying copy
      Tensor o = a;
      o.dims = std::move(want);
      out(std::move(o));
    }
  } else if (op == "Transpose") {
    const Tensor& a = in(n, 0);
    auto perm = attr_ints(n, "perm");
    if (perm.empty())  // ONNX default: reverse the axes
      for (size_t d = a.dims.size(); d-- > 0;)
        perm.push_back(int64_t(d));
    // hostile perms: wrong length or out-of-range axes index past
    // both dims vectors (fuzzing audit alongside the Reshape finding;
    // repro: csrc/fuzz/corpus/onnx/crash-transpose-bad-perm.bin)
    if (perm.size() != a.dims.size())
      throw std::runtime_error("Transpose: perm length != rank");
    for (auto p : perm)
      if (p < 0 || p >= int64_t(a.dims.size()))
        throw std::runtime_error("Transpose: perm axis out of range");
    Tensor o;
    o.dtype = a.dtype;
    o.dims.resize(a.dims.size());
    for (size_t k = 0; k < perm.size(); ++k)
      o.dims[k] = a.dims[size_t(perm[k])];
    o.alloc();
    // empty output: done — the row-partition below iterates over the
    // product of LEADING dims, which a hostile zero-element shape can
    // still drive to 2^50+ empty iterations (load-time CPU DoS;
    // fuzzing finding, ISSUE 11; repro:
    // csrc/fuzz/corpus/onnx/crash-transpose-empty-spin.bin)
    if (o.numel() == 0) {
      out(std::move(o));
      return;
    }
    // odometer walk: src index updated incrementally per output
    // element (every attention matmul lowers through Transpose — the
    // old per-element div/mod chain dominated transformer serving);
    // parallel over slabs of the outermost output axis
    auto istr = strides_for(a.dims);
    const size_t r = o.dims.size();
    std::vector<int64_t> sstr(r);
    for (size_t d = 0; d < r; ++d) sstr[d] = istr[size_t(perm[d])];
    const int64_t nel = o.numel();
    // flatten leading output axes into parallel "rows" until there is
    // enough of them to spread across the pool; each row seeds its
    // source index once (div/mod), then walks the tail incrementally
    size_t split = 0;
    int64_t rows = 1;
    while (split + 1 < r && rows < 4 * int64_t(num_threads()))
      rows *= o.dims[split++];
    const int64_t slab = rows ? nel / rows : 0;
    const bool flt = a.is_float();
    const float* af = a.f.data();
    const int64_t* ai = a.i.data();
    float* of = o.f.data();
    int64_t* oi = o.i.data();
    parallel_for(rows, std::max<int64_t>(1, 65536 / std::max<int64_t>(
                                                       slab, 1)),
                 [&](int64_t c0, int64_t c1) {
      std::vector<int64_t> ctr(r, 0);
      for (int64_t cc = c0; cc < c1; ++cc) {
        ctr.assign(r, 0);
        int64_t src = 0, rem = cc;
        for (size_t d = split; d-- > 0;) {
          const int64_t coord = rem % o.dims[d];
          rem /= o.dims[d];
          src += coord * sstr[d];
        }
        const int64_t k0 = cc * slab;
        for (int64_t k = 0; k < slab; ++k) {
          if (flt) of[size_t(k0 + k)] = af[size_t(src)];
          else oi[size_t(k0 + k)] = ai[size_t(src)];
          for (size_t d = r; d-- > split;) {
            ++ctr[d];
            src += sstr[d];
            if (ctr[d] < o.dims[d]) break;
            src -= sstr[d] * o.dims[d];
            ctr[d] = 0;
          }
        }
      }
    });
    out(std::move(o));
  } else if (op == "Concat") {
    int64_t rank = int64_t(in(n, 0).dims.size());
    int64_t axis = attr_i(n, "axis", 0);
    if (axis < 0) axis += rank;
    // hostile-artifact guards (fuzzing audit with the ArgMax axis
    // finding, ISSUE 11): axis in range, every operand of equal rank
    if (axis < 0 || axis >= rank)
      throw std::runtime_error("Concat: axis out of range");
    Tensor o;
    o.dtype = in(n, 0).dtype;
    o.dims = in(n, 0).dims;
    int64_t total = 0;
    for (size_t k = 0; k < n.inputs.size(); ++k) {
      if (int64_t(in(n, k).dims.size()) != rank)
        throw std::runtime_error("Concat: operand ranks differ");
      total += in(n, k).dims[size_t(axis)];
    }
    o.dims[size_t(axis)] = total;
    o.alloc();
    /* Same-dtype inputs (the KV-decode cache append, every exporter
     * concat): each (outer, input) pair is ONE contiguous block of
     * ax_t * inner elements — plain memcpys instead of the per-element
     * rank-deep div/mod walk (measured ~0.5 ms per 16k-element cache
     * concat on the old loop, the decode hot path's top cost). */
    bool same_dt = true;
    for (size_t t = 0; t < n.inputs.size(); ++t)
      if (in(n, t).dtype != o.dtype ||
          in(n, t).is_float() != o.is_float())
        same_dt = false;
    if (same_dt) {
      int64_t outer = 1, inner = 1;
      for (int64_t d = 0; d < axis; ++d) outer *= o.dims[size_t(d)];
      for (size_t d = size_t(axis) + 1; d < o.dims.size(); ++d)
        inner *= o.dims[d];
      const int64_t esz = o.is_float() ? 4 : 8;
      char* ob = o.is_float() ? reinterpret_cast<char*>(o.f.data())
                              : reinterpret_cast<char*>(o.i.data());
      int64_t off_ax = 0;
      for (size_t t = 0; t < n.inputs.size(); ++t) {
        const Tensor& a = in(n, t);
        const int64_t ax = a.dims[size_t(axis)];
        const char* ab = a.is_float()
                             ? reinterpret_cast<const char*>(a.f.data())
                             : reinterpret_cast<const char*>(a.i.data());
        for (int64_t ou = 0; ou < outer; ++ou)
          std::memcpy(ob + ((ou * total + off_ax) * inner) * esz,
                      ab + (ou * ax * inner) * esz,
                      size_t(ax * inner * esz));
        off_ax += ax;
      }
      out(std::move(o));
      return;
    }
    auto ostr = strides_for(o.dims);
    int64_t offset = 0;
    for (size_t t = 0; t < n.inputs.size(); ++t) {
      const Tensor& a = in(n, t);
      auto istr = strides_for(a.dims);
      for (int64_t k = 0; k < a.numel(); ++k) {
        int64_t dst = 0;
        for (size_t d = 0; d < a.dims.size(); ++d) {
          int64_t coord = (k / istr[d]) % a.dims[d];
          if (int64_t(d) == axis) coord += offset;
          dst += coord * ostr[d];
        }
        o.set(dst, a.at(k));
      }
      offset += a.dims[size_t(axis)];
    }
    out(std::move(o));
  } else if (op == "Expand") {
    const Tensor& a = in(n, 0);
    const Tensor& shp = in(n, 1);
    std::vector<int64_t> want(shp.i.begin(), shp.i.end());
    /* Batch repair under a bucket-ladder override (see the Reshape
     * twin): exporters also bake the trace batch into Expand targets
     * (broadcast materializations like eps -> [B,1,1]). A target dim
     * EQUAL to the export batch whose right-aligned source dim
     * broadcasts (1 or absent) rewrites to the override batch —
     * expanding less before a broadcasting consumer is semantically
     * free, and strict-shape consumers fail the bucket probe rather
     * than serve wrong shapes. */
    if (bo_from_ > 1 && bo_to_ != bo_from_) {
      // only the LEFTMOST qualifying dim is the batch — exporter
      // broadcast targets lead with it, and a non-batch dim that
      // coincides with the export batch (heads == batch) must stay
      for (size_t z = 0; z < want.size(); ++z) {
        if (want[z] != bo_from_) continue;
        const size_t ra = a.dims.size();
        const int64_t src =
            z + ra >= want.size() ? a.dims[z + ra - want.size()] : 1;
        if (src == 1 || src == bo_to_) want[z] = bo_to_;
        break;
      }
    }
    Tensor o;
    o.dims = bcast_dims(a.dims, want);
    o.dtype = a.dtype;
    o.alloc();
    for (int64_t k = 0; k < o.numel(); ++k)
      o.set(k, a.at(bcast_index(k, o.dims, a.dims)));
    out(std::move(o));
  } else if (op == "Slice") {
    const Tensor& a = in(n, 0);
    const Tensor &st = in(n, 1), &en = in(n, 2);
    std::vector<int64_t> axes, steps;
    if (n.inputs.size() > 3)
      axes.assign(in(n, 3).i.begin(), in(n, 3).i.end());
    else
      for (size_t k = 0; k < st.i.size(); ++k) axes.push_back(int64_t(k));
    if (n.inputs.size() > 4)
      steps.assign(in(n, 4).i.begin(), in(n, 4).i.end());
    else
      steps.assign(axes.size(), 1);
    std::vector<int64_t> begin(a.dims.size(), 0), stride(a.dims.size(), 1),
        count = a.dims;
    for (size_t k = 0; k < axes.size(); ++k) {
      int64_t ax = axes[k] < 0 ? axes[k] + int64_t(a.dims.size()) : axes[k];
      int64_t dim = a.dims[size_t(ax)];
      int64_t s = st.i[k], e = en.i[k], sp = steps[k];
      if (s < 0) s += dim;
      if (e < -dim) e = sp < 0 ? -1 : 0;  // INT64_MIN+1 marker for reverse
      else if (e < 0) e += dim;
      if (sp > 0) {
        s = std::min(std::max(s, int64_t(0)), dim);
        e = std::min(std::max(e, int64_t(0)), dim);
        count[size_t(ax)] = std::max(int64_t(0), (e - s + sp - 1) / sp);
      } else {
        s = std::min(std::max(s, int64_t(0)), dim - 1);
        e = std::max(e, int64_t(-1));
        count[size_t(ax)] = std::max(int64_t(0), (s - e - sp - 1) / (-sp));
      }
      begin[size_t(ax)] = s;
      stride[size_t(ax)] = sp;
    }
    Tensor o;
    o.dims = count;
    o.dtype = a.dtype;
    o.alloc();
    auto istr = strides_for(a.dims);
    const size_t r = o.dims.size();
    /* odometer + contiguous-tail memcpy: find the longest suffix of
     * unit-step, full-width axes — those positions copy as one run. */
    size_t tail = r;
    int64_t run = 1;
    while (tail > 0 && stride[tail - 1] == 1 && begin[tail - 1] == 0 &&
           count[tail - 1] == a.dims[tail - 1]) {
      --tail;
      run *= count[tail];
    }
    // src base index for the block at the current odometer position
    std::vector<int64_t> ctr(r, 0);
    int64_t base = 0;
    for (size_t d = 0; d < tail; ++d) base += begin[d] * istr[d];
    const int64_t blocks = o.numel() / std::max<int64_t>(run, 1);
    const bool flt = a.is_float();
    for (int64_t b = 0; b < blocks; ++b) {
      if (flt)
        std::memcpy(o.f.data() + b * run, a.f.data() + base,
                    size_t(run) * sizeof(float));
      else
        std::memcpy(o.i.data() + b * run, a.i.data() + base,
                    size_t(run) * sizeof(int64_t));
      for (size_t d = tail; d-- > 0;) {
        ++ctr[d];
        base += stride[d] * istr[d];
        if (ctr[d] < count[d]) break;
        base -= stride[d] * istr[d] * count[d];
        ctr[d] = 0;
      }
    }
    out(std::move(o));
  } else if (op == "Gather") {
    const Tensor &a = in(n, 0), &idx = in(n, 1);
    int64_t axis = attr_i(n, "axis", 0);
    if (axis < 0) axis += int64_t(a.dims.size());
    if (axis < 0 || axis >= int64_t(a.dims.size()))
      throw std::runtime_error("Gather: axis out of range");
    Tensor o;
    o.dtype = a.dtype;
    for (int64_t d = 0; d < axis; ++d) o.dims.push_back(a.dims[size_t(d)]);
    for (auto d : idx.dims) o.dims.push_back(d);
    for (size_t d = size_t(axis) + 1; d < a.dims.size(); ++d)
      o.dims.push_back(a.dims[d]);
    o.alloc();
    int64_t ax_dim = a.dims[size_t(axis)];
    /* row-copy formulation: output = [outer, idx..., inner] where
     * inner = contiguous tail of `a` after `axis` — copy `inner`
     * elements per (outer, index) pair instead of re-deriving every
     * coordinate per element. */
    int64_t inner = 1;
    for (size_t d = size_t(axis) + 1; d < a.dims.size(); ++d)
      inner *= a.dims[d];
    int64_t outer = 1;
    for (int64_t d = 0; d < axis; ++d) outer *= a.dims[size_t(d)];
    const int64_t nidx = idx.numel();
    for (int64_t ou = 0; ou < outer; ++ou)
      for (int64_t j = 0; j < nidx; ++j) {
        int64_t iv = idx.i.empty() ? int64_t(idx.at(j)) : idx.i[size_t(j)];
        if (iv < 0) iv += ax_dim;
        // indices arrive over the C ABI (token ids etc.) and are
        // untrusted: an out-of-range id would read (memcpy!) a full
        // row out of bounds — throw like check_dims does for dims
        if (iv < 0 || iv >= ax_dim)
          throw std::runtime_error(
              "Gather: index " +
              std::to_string(idx.i.empty() ? int64_t(idx.at(j))
                                           : idx.i[size_t(j)]) +
              " out of range for axis dim " + std::to_string(ax_dim));
        const int64_t src = (ou * ax_dim + iv) * inner;
        const int64_t dst = (ou * nidx + j) * inner;
        if (a.is_float())
          std::memcpy(o.f.data() + dst, a.f.data() + src,
                      size_t(inner) * sizeof(float));
        else
          std::memcpy(o.i.data() + dst, a.i.data() + src,
                      size_t(inner) * sizeof(int64_t));
      }
    out(std::move(o));
  } else if (op == "MatMul" || op == "PtpuFusedGemm") {
    const Tensor &a = in(n, 0), &b = in(n, 1);
    const bool fused = op == "PtpuFusedGemm";
    const Tensor* fb = fused ? &in(n, 2) : nullptr;
    const int act = fused ? int(attr_i(n, "ptpu_act", ACT_NONE)) : ACT_NONE;
    const size_t ra = a.dims.size(), rb = b.dims.size();
    // rank guard: a hostile artifact can feed MatMul a SCALAR operand
    // — dims.back() on an empty vector is UB (fuzzing finding, ISSUE
    // 11; repro: csrc/fuzz/corpus/onnx/crash-matmul-scalar.bin)
    if (ra == 0 || rb == 0)
      throw std::runtime_error("MatMul: operands must have rank >= 1");
    const bool batched_b = rb > 2;
    int64_t k_d = a.dims.back();
    int64_t m = ra >= 2 ? a.dims[ra - 2] : 1;
    int64_t nn, batch;
    Tensor o;
    o.dtype = DT_F32;
    if (batched_b) {
      /* [B..., M, K] x [B..., K, N] — the ONNX exporter lowers every
       * jax dot_general (attention included) to this via
       * transpose/reshape, so transformer artifacts serve natively. */
      if (ra != rb) throw std::runtime_error("MatMul: batched ranks differ");
      batch = 1;
      for (size_t d = 0; d + 2 < ra; ++d) {
        if (a.dims[d] != b.dims[d])
          throw std::runtime_error(
              "MatMul: batch dims differ (" + n.inputs[0] + "," + n.inputs[1] + " " + std::to_string(a.dims[d]) +
              " vs " + std::to_string(b.dims[d]) + " at axis " +
              std::to_string(d) + ")");
        batch *= a.dims[d];
      }
      if (b.dims[rb - 2] != k_d)
        throw std::runtime_error("MatMul: inner dims differ");
      nn = b.dims[rb - 1];
      o.dims.assign(a.dims.begin(), a.dims.end() - 1);
      o.dims.push_back(nn);
    } else {
      // inner-dim agreement holds for rank-1/2 B too — without it the
      // kernels index B past its storage (fuzzing finding, ISSUE 11;
      // repro: csrc/fuzz/corpus/onnx/crash-matmul-inner-dim.bin)
      if (b.dims[0] != k_d)
        throw std::runtime_error("MatMul: inner dims differ");
      nn = rb == 2 ? b.dims[1] : 1;
      // the leading dims collapse into the GEMM's M — computed as a
      // direct product, NOT numel()/(k_d*m): a zero k_d would zero
      // the divisor and silently drop the batch, leaving o's elements
      // unwritten (stale arena; code-review finding on the ISSUE 11
      // zero-extent guards). In-order leading products are prefix
      // products, which Tensor::numel() already bounds.
      batch = 1;
      if (ra >= 2)
        for (size_t d = 0; d + 2 < ra; ++d) batch *= a.dims[d];
      o.dims.assign(a.dims.begin(), a.dims.end() - 1);
      if (rb == 2) o.dims.push_back(nn);
    }
    o.alloc();
    const float* bias_n =
        fb && fb->is_float() && fb->numel() == nn ? fb->f.data() : nullptr;
    const PackedMat* pw =
        batched_b ? nullptr : packed_lookup("b:" + n.inputs[1]);
    if (a.is_float() && b.is_float() && rb >= 2) {
      if (!batched_b) {
        // leading dims of A collapse into M: one packed macro-kernel
        // call over the whole batch, one shared (pre-packed) B panel
        // (int4-packed when the load quantized this weight), config
        // steered by the per-machine autotuner when PTPU_TUNE=1
        const bool q4w = pw != nullptr && !pw->q4.empty();
        const int64_t gm = batch * m;
        namespace tn = ptpu::tune;
        auto run_cfg = [&](const tn::TuneConfig* c) {
          if (q4w)
            gemm_q4(a.f.data(), pw->q4.data(), pw->q4s.data(),
                    pw->q4z.data(), o.f.data(), gm, nn, k_d,
                    pw->q4_group, bias_n, act, c);
          else
            gemm_bias_act<float>(a.f.data(), b.f.data(), o.f.data(), gm,
                                 nn, k_d, nullptr,
                                 pw && !pw->f.empty() ? pw->f.data()
                                                      : nullptr,
                                 bias_n, nullptr, act, c);
        };
        // autotune only steers shapes with blocking freedom: M > 1
        // over a pre-packed weight (M == 1 is already the GEMV
        // special case; unpacked B is a one-shot activation GEMM)
        const bool tunable = tn::Registry::Enabled() && gm > 1 &&
                             pw != nullptr && (q4w || !pw->f.empty()) &&
                             k_d > 0 && nn > 0;
        if (!tunable) {
          run_cfg(nullptr);
        } else if (n.tune_m == gm) {  // per-node memo: steady serving
          tn::TuneConfig cfg;
          cfg.path = n.tune_path;
          cfg.kc = n.tune_kc;
          cfg.mult = n.tune_mult;
          run_cfg(&cfg);
        } else {
          tn::TuneKey key;
          key.m = gm;
          key.n = nn;
          key.k = k_d;
          key.dtype = q4w ? tn::kDtQ4 : tn::kDtF32;
          tn::TuneConfig cfg;
          if (!tn::Registry::Inst().Lookup(key, &cfg)) {
            cfg = probe_gemm_cfg(gm, run_cfg);
            tn::Registry::Inst().Insert(key, cfg);
            // Insert may lose a first-wins race with another instance
            // probing the same shape; adopt the canonical entry so
            // the whole process agrees on one config
            tn::Registry::Inst().Lookup(key, &cfg);
            run_cfg(&cfg);  // output must come from the adopted config
          } else {
            run_cfg(&cfg);
          }
          n.tune_m = gm;
          n.tune_path = cfg.path;
          n.tune_kc = cfg.kc;
          n.tune_mult = cfg.mult;
        }
      } else {
        // batched (attention heads): the per-element GEMMs are tiny, so
        // parallelism comes from the BATCH axis — each worker packs and
        // computes its elements serially (in_worker_ keeps the inner
        // parallel_fors from re-dispatching)
        parallel_for(batch, 1, [&](int64_t b0, int64_t b1) {
          for (int64_t bb = b0; bb < b1; ++bb)
            gemm_bias_act<float>(a.f.data() + bb * m * k_d,
                                 b.f.data() + bb * k_d * nn,
                                 o.f.data() + bb * m * nn, m, nn, k_d,
                                 nullptr, nullptr, bias_n, nullptr, act);
        });
      }
    } else if (!a.is_float() && !b.is_float() && rb >= 2 &&
               // int8-range guard: this path is EXACT only for int8
               // operands; int64 index/counter arithmetic must keep
               // the exact double-accumulating scalar path. A load-time
               // packed weight caches its value scan in int8_ok.
               int8_depth_ok(k_d) && int8_vals_ok(a.i.data(), a.i.size()) &&
               (pw ? pw->int8_ok
                   : int8_vals_ok(b.i.data(), b.i.size()))) {
      // int8-executing artifacts: packed int32 GEMM, widening directly
      // from the int64 storage into the panel buffers
      if (!batched_b) {
        std::vector<int32_t> acc(size_t(batch * m * nn));
        // VNNI dot-product path when the machine has it and the shape
        // is past the GEMV special case; bitwise-equal (integer adds
        // are associative) to the int32 packed kernel it replaces
        if (isa_vnni() && batch * m > 1) {
          gemm_i16(a.i.data(), b.i.data(), acc.data(), batch * m, nn,
                   k_d,
                   pw && !pw->i16.empty() ? pw->i16.data() : nullptr);
        } else {
          gemm_bias_act<int32_t, int64_t, int64_t>(
              a.i.data(), b.i.data(), acc.data(), batch * m, nn, k_d,
              nullptr, pw && !pw->i.empty() ? pw->i.data() : nullptr,
              nullptr, nullptr, ACT_NONE);
        }
        float* of = o.f.data();
        for (int64_t k = 0; k < batch * m * nn; ++k)
          of[k] = float(acc[size_t(k)]);
      } else {
        parallel_for(batch, 1, [&](int64_t b0, int64_t b1) {
          std::vector<int32_t> bacc(size_t(m * nn));
          for (int64_t bb = b0; bb < b1; ++bb) {
            gemm_bias_act<int32_t, int64_t, int64_t>(
                a.i.data() + bb * m * k_d, b.i.data() + bb * k_d * nn,
                bacc.data(), m, nn, k_d, nullptr, nullptr, nullptr,
                nullptr, ACT_NONE);
            float* of = o.f.data() + bb * m * nn;
            for (int64_t k = 0; k < m * nn; ++k)
              of[k] = float(bacc[size_t(k)]);
          }
        });
      }
    } else {
      for (int64_t bb = 0; bb < batch; ++bb)
        for (int64_t mm = 0; mm < m; ++mm)
          for (int64_t jj = 0; jj < nn; ++jj) {
            double acc = 0;
            for (int64_t kk = 0; kk < k_d; ++kk)
              acc += a.at((bb * m + mm) * k_d + kk) *
                     b.at(batched_b ? (bb * k_d + kk) * nn + jj
                                    : (rb == 2 ? kk * nn + jj : kk));
            if (fb) acc = act_apply(float(acc + fb->at(jj % fb->numel())),
                                    act);
            o.set((bb * m + mm) * nn + jj, acc);
          }
    }
    out(std::move(o));
  } else if (op == "Conv" || op == "PtpuFusedConv") {
    const Tensor &x = in(n, 0), &w = in(n, 1);
    const bool fused = op == "PtpuFusedConv";
    const Tensor* fb = fused ? &in(n, 2) : nullptr;
    const int act = fused ? int(attr_i(n, "ptpu_act", ACT_NONE)) : ACT_NONE;
    if (x.dims.size() != 4) throw std::runtime_error("Conv: only 2-D");
    auto strides = attr_ints(n, "strides");
    auto pads = attr_ints(n, "pads");
    auto dil = attr_ints(n, "dilations");
    int64_t group = attr_i(n, "group", 1);
    if (strides.empty()) strides = {1, 1};
    if (pads.empty()) pads = {0, 0, 0, 0};
    if (dil.empty()) dil = {1, 1};
    int64_t N = x.dims[0], C = x.dims[1], H = x.dims[2], W = x.dims[3];
    int64_t OC = w.dims[0], ICG = w.dims[1], KH = w.dims[2], KW = w.dims[3];
    int64_t OH = (H + pads[0] + pads[2] - dil[0] * (KH - 1) - 1) /
                     strides[0] + 1;
    int64_t OW = (W + pads[1] + pads[3] - dil[1] * (KW - 1) - 1) /
                     strides[1] + 1;
    int64_t ocg = OC / group;
    Tensor o;
    o.dtype = DT_F32;
    o.dims = {N, OC, OH, OW};
    o.alloc();
    const PackedMat* pw =
        packed_lookup("a:" + n.inputs[1] + ":" + std::to_string(group));
    const int64_t P = OH * OW, CK = ICG * KH * KW;
    const int64_t apsz = a_pack_size(ocg, CK);
    const bool unit = (KH == 1 && KW == 1 && strides[0] == 1 &&
                       strides[1] == 1 && pads[0] == 0 && pads[1] == 0 &&
                       pads[2] == 0 && pads[3] == 0);
    if (x.is_float() && w.is_float()) {
      /* Implicit im2col + packed GEMM: per (image, group) the patch
       * matrix col[ICG*KH*KW, OH*OW] is packed straight into B-panel
       * layout (no col materialization), then the conv is one packed
       * GEMM of the group's pre-packed [ocg, CK] filter panels against
       * it — the MXU-style formulation on the cache-blocked CPU
       * micro-kernel, with the fused bias+activation applied in the
       * epilogue writeback. */
      auto& bbuf = pack_scratch<float>(1);
      bbuf.resize(size_t(b_pack_size(CK, P)));
      for (int64_t nn = 0; nn < N; ++nn)
        for (int64_t g = 0; g < group; ++g) {
          const float* xg = x.f.data() + (nn * C + g * ICG) * H * W;
          if (unit)  // the input slice IS the col matrix: plain pack
            pack_b<float, float>(xg, CK, P, bbuf.data());
          else
            pack_b_im2col<float, float>(xg, ICG, H, W, KH, KW, OH, OW,
                                        strides[0], strides[1], pads[0],
                                        pads[1], dil[0], dil[1],
                                        bbuf.data());
          gemm_bias_act<float>(
              w.f.data() + g * ocg * CK, xg,
              o.f.data() + (nn * OC + g * ocg) * P, ocg, P, CK,
              pw && !pw->f.empty() ? pw->f.data() + g * apsz : nullptr,
              bbuf.data(), nullptr,
              fb ? fb->f.data() + g * ocg : nullptr, act);
        }
    } else if (!x.is_float() && !w.is_float() && int8_depth_ok(CK) &&
               int8_vals_ok(x.i.data(), x.i.size()) &&
               (pw ? pw->int8_ok
                   : int8_vals_ok(w.i.data(), w.i.size()))) {
      /* int8-executing conv (QAT convert_to_int8 artifacts): identical
       * packed formulation on int32 lanes — exact for int8 operands
       * with int32 accumulation. The panel packers widen straight from
       * the int64 storage; pre-packed weights skip the per-run value
       * scan via the cached int8_ok. */
      auto& bbuf = pack_scratch<int32_t>(1);
      bbuf.resize(size_t(b_pack_size(CK, P)));
      std::vector<int32_t> acc(size_t(ocg * P));
      for (int64_t nn = 0; nn < N; ++nn)
        for (int64_t g = 0; g < group; ++g) {
          const int64_t* xg = x.i.data() + (nn * C + g * ICG) * H * W;
          if (unit)
            pack_b<int64_t, int32_t>(xg, CK, P, bbuf.data());
          else
            pack_b_im2col<int64_t, int32_t>(xg, ICG, H, W, KH, KW, OH, OW,
                                            strides[0], strides[1],
                                            pads[0], pads[1], dil[0],
                                            dil[1], bbuf.data());
          gemm_bias_act<int32_t, int64_t, int64_t>(
              w.i.data() + g * ocg * CK, xg, acc.data(), ocg, P, CK,
              pw && !pw->i.empty() ? pw->i.data() + g * apsz : nullptr,
              bbuf.data(), nullptr, nullptr, ACT_NONE);
          float* of = o.f.data() + (nn * OC + g * ocg) * P;
          for (int64_t k = 0; k < ocg * P; ++k)
            of[k] = float(acc[size_t(k)]);
        }
    } else {
      for (int64_t nn = 0; nn < N; ++nn)
        for (int64_t oc = 0; oc < OC; ++oc) {
          int64_t g0 = (oc / ocg) * ICG;  // first input channel of group
          for (int64_t oh = 0; oh < OH; ++oh)
            for (int64_t ow = 0; ow < OW; ++ow) {
              double acc = 0;
              for (int64_t ic = 0; ic < ICG; ++ic)
                for (int64_t kh = 0; kh < KH; ++kh) {
                  int64_t ih = oh * strides[0] - pads[0] + kh * dil[0];
                  if (ih < 0 || ih >= H) continue;
                  for (int64_t kw = 0; kw < KW; ++kw) {
                    int64_t iw = ow * strides[1] - pads[1] + kw * dil[1];
                    if (iw < 0 || iw >= W) continue;
                    acc += x.at(((nn * C + g0 + ic) * H + ih) * W + iw) *
                           w.at(((oc * ICG + ic) * KH + kh) * KW + kw);
                  }
                }
              float v = float(acc);
              if (fb) v = act_apply(v + fb->f[size_t(oc)], act);
              o.f[size_t(((nn * OC + oc) * OH + oh) * OW + ow)] = v;
            }
        }
    }
    out(std::move(o));
  } else if (op == "MaxPool" || op == "AveragePool") {
    const Tensor& x = in(n, 0);
    auto ks = attr_ints(n, "kernel_shape");
    auto strides = attr_ints(n, "strides");
    auto pads = attr_ints(n, "pads");
    if (strides.empty()) strides.assign(ks.size(), 1);
    if (pads.empty()) pads.assign(ks.size() * 2, 0);
    if (x.dims.size() != 4 || ks.size() != 2)
      throw std::runtime_error(op + ": only 2-D");
    bool include_pad = attr_i(n, "count_include_pad", 0) != 0;
    int64_t N = x.dims[0], C = x.dims[1], H = x.dims[2], W = x.dims[3];
    int64_t OH = (H + pads[0] + pads[2] - ks[0]) / strides[0] + 1;
    int64_t OW = (W + pads[1] + pads[3] - ks[1]) / strides[1] + 1;
    Tensor o;
    o.dtype = DT_F32;
    o.dims = {N, C, OH, OW};
    o.alloc();
    const bool is_max = op == "MaxPool";
    if (x.is_float()) {
      // plane-parallel float pooling: the window walk reads the input
      // plane directly (no per-element dtype dispatch)
      const float* xf = x.f.data();
      float* of = o.f.data();
      parallel_for(N * C, 1, [&](int64_t p0, int64_t p1) {
        for (int64_t pl = p0; pl < p1; ++pl) {
          const float* plane = xf + pl * H * W;
          float* dst = of + pl * OH * OW;
          for (int64_t oh = 0; oh < OH; ++oh) {
            const int64_t h0 = std::max<int64_t>(0, oh * strides[0] -
                                                        pads[0]);
            const int64_t h1 = std::min(H, oh * strides[0] - pads[0] +
                                               ks[0]);
            for (int64_t ow = 0; ow < OW; ++ow) {
              const int64_t w0 = std::max<int64_t>(0, ow * strides[1] -
                                                          pads[1]);
              const int64_t w1 = std::min(W, ow * strides[1] - pads[1] +
                                                 ks[1]);
              float best = -1e30f;  // matches the generic path's init
              double sum = 0;
              for (int64_t ih = h0; ih < h1; ++ih) {
                const float* row = plane + ih * W;
                for (int64_t iw = w0; iw < w1; ++iw) {
                  best = std::max(best, row[iw]);
                  sum += row[iw];
                }
              }
              const int64_t cnt = (h1 - h0) * (w1 - w0);
              const double denom =
                  include_pad ? double(ks[0] * ks[1])
                              : double(std::max(cnt, int64_t(1)));
              dst[oh * OW + ow] = is_max ? best : float(sum / denom);
            }
          }
        }
      });
      out(std::move(o));
      return;
    }
    for (int64_t nn = 0; nn < N; ++nn)
      for (int64_t c = 0; c < C; ++c)
        for (int64_t oh = 0; oh < OH; ++oh)
          for (int64_t ow = 0; ow < OW; ++ow) {
            double best = -1e30, sum = 0;
            int64_t cnt = 0;
            for (int64_t kh = 0; kh < ks[0]; ++kh)
              for (int64_t kw = 0; kw < ks[1]; ++kw) {
                int64_t ih = oh * strides[0] - pads[0] + kh;
                int64_t iw = ow * strides[1] - pads[1] + kw;
                if (ih < 0 || ih >= H || iw < 0 || iw >= W) continue;
                double v = x.at(((nn * C + c) * H + ih) * W + iw);
                best = std::max(best, v);
                sum += v;
                ++cnt;
              }
            double denom = include_pad ? double(ks[0] * ks[1])
                                       : double(std::max(cnt, int64_t(1)));
            o.f[size_t(((nn * C + c) * OH + oh) * OW + ow)] =
                float(is_max ? best : sum / denom);
          }
    out(std::move(o));
  } else if (op == "ReduceSum" || op == "ReduceMax" || op == "ReduceMin" ||
             op == "ReduceProd" || op == "ReduceMean") {
    const Tensor& a = in(n, 0);
    std::vector<int64_t> axes = attr_ints(n, "axes");
    if (axes.empty() && n.inputs.size() > 1)
      axes.assign(in(n, 1).i.begin(), in(n, 1).i.end());
    bool keep = attr_i(n, "keepdims", 1) != 0;
    std::vector<bool> red(a.dims.size(), axes.empty());
    for (auto ax : axes) {
      // axis bounds BEFORE the write: hostile axes scribble past the
      // vector (fuzzing finding, ISSUE 11; repro:
      // csrc/fuzz/corpus/onnx/crash-reduce-axis-oob.bin)
      const int64_t ax2 = ax < 0 ? ax + int64_t(a.dims.size()) : ax;
      if (ax2 < 0 || ax2 >= int64_t(a.dims.size()))
        throw std::runtime_error("Reduce: axis " + std::to_string(ax) +
                                 " out of range for rank " +
                                 std::to_string(a.dims.size()));
      red[size_t(ax2)] = true;
    }
    Tensor o;
    o.dtype = a.dtype;
    for (size_t d = 0; d < a.dims.size(); ++d) {
      if (!red[d]) o.dims.push_back(a.dims[d]);
      else if (keep) o.dims.push_back(1);
    }
    o.alloc();
    const int rc = op == "ReduceMax" ? 1 : op == "ReduceMin" ? 2
                   : op == "ReduceProd" ? 3 : op == "ReduceMean" ? 4 : 0;
    const double init = rc == 1 ? -1e300 : rc == 2 ? 1e300
                        : rc == 3 ? 1.0 : 0.0;
    // fast path: reduced axes form a contiguous SUFFIX (softmax/LN
    // reductions after export are all last-axis) — contiguous row
    // scans instead of per-element rank-deep div/mod
    size_t split = a.dims.size();
    while (split > 0 && red[split - 1]) --split;
    bool suffix = true;
    for (size_t d = 0; d < split; ++d)
      if (red[d]) { suffix = false; break; }
    if (suffix && a.is_float()) {
      int64_t inner = 1, outer = 1;
      for (size_t d = split; d < a.dims.size(); ++d) inner *= a.dims[d];
      for (size_t d = 0; d < split; ++d) outer *= a.dims[d];
      const float* af = a.f.data();
      float* of = o.f.data();
      parallel_for(outer,
                   std::max<int64_t>(1, 65536 / std::max<int64_t>(inner, 1)),
                   [&](int64_t o0, int64_t o1) {
        for (int64_t ou = o0; ou < o1; ++ou) {
          const float* row = af + ou * inner;
          double accv = init;
          switch (rc) {
            case 1:
              for (int64_t j = 0; j < inner; ++j)
                accv = std::max(accv, double(row[j]));
              break;
            case 2:
              for (int64_t j = 0; j < inner; ++j)
                accv = std::min(accv, double(row[j]));
              break;
            case 3:
              for (int64_t j = 0; j < inner; ++j) accv *= row[j];
              break;
            default:
              for (int64_t j = 0; j < inner; ++j) accv += row[j];
          }
          if (rc == 4) accv /= double(inner);
          of[ou] = float(accv);
        }
      });
      out(std::move(o));
      return;
    }
    std::vector<double> acc(size_t(o.numel()), init);
    std::vector<int64_t> counts(size_t(o.numel()), 0);
    auto istr = strides_for(a.dims);
    auto ostr = strides_for(o.dims);
    for (int64_t k = 0; k < a.numel(); ++k) {
      int64_t dst = 0;
      size_t od = 0;
      for (size_t d = 0; d < a.dims.size(); ++d) {
        int64_t coord = (k / istr[d]) % a.dims[d];
        if (!red[d]) dst += coord * ostr[od++];
        else if (keep) od++;  // coord 0
      }
      double v = a.at(k);
      switch (rc) {
        case 1: acc[size_t(dst)] = std::max(acc[size_t(dst)], v); break;
        case 2: acc[size_t(dst)] = std::min(acc[size_t(dst)], v); break;
        case 3: acc[size_t(dst)] *= v; break;
        default: acc[size_t(dst)] += v;
      }
      counts[size_t(dst)]++;
    }
    for (int64_t k = 0; k < o.numel(); ++k)
      o.set(k, rc == 4 ? acc[size_t(k)] / double(counts[size_t(k)])
                       : acc[size_t(k)]);
    out(std::move(o));
  } else if (op == "ArgMax" || op == "ArgMin") {
    const Tensor& a = in(n, 0);
    int64_t axis = attr_i(n, "axis", 0);
    if (axis < 0) axis += int64_t(a.dims.size());
    // hostile axis: out of range (or a scalar input) indexes past
    // dims (fuzzing finding, ISSUE 11; repro:
    // csrc/fuzz/corpus/onnx/crash-argmax-axis-oob.bin)
    if (axis < 0 || axis >= int64_t(a.dims.size()))
      throw std::runtime_error(op + ": axis out of range");
    bool keep = attr_i(n, "keepdims", 1) != 0;
    Tensor o;
    o.dtype = DT_I64;
    for (size_t d = 0; d < a.dims.size(); ++d) {
      if (int64_t(d) != axis) o.dims.push_back(a.dims[d]);
      else if (keep) o.dims.push_back(1);
    }
    o.alloc();
    auto istr = strides_for(a.dims);
    int64_t ax_dim = a.dims[size_t(axis)];
    for (int64_t k = 0; k < o.numel(); ++k) {
      // decompose k into non-axis coords
      int64_t base = 0;
      size_t od = 0;
      auto ostr = strides_for(o.dims);
      for (size_t d = 0; d < a.dims.size(); ++d) {
        if (int64_t(d) == axis) { if (keep) od++; continue; }
        base += ((k / ostr[od]) % o.dims[od]) * istr[d];
        od++;
      }
      double best = op == "ArgMax" ? -1e300 : 1e300;
      int64_t arg = 0;
      for (int64_t j = 0; j < ax_dim; ++j) {
        double v = a.at(base + j * istr[size_t(axis)]);
        if ((op == "ArgMax" && v > best) || (op == "ArgMin" && v < best)) {
          best = v;
          arg = j;
        }
      }
      o.i[size_t(k)] = arg;
    }
    out(std::move(o));
  } else if (op == "CumSum") {
    const Tensor& a = in(n, 0);
    if (in(n, 1).numel() < 1)
      throw std::runtime_error("CumSum: missing axis input");
    int64_t axis = int64_t(in(n, 1).at(0));
    if (axis < 0) axis += int64_t(a.dims.size());
    if (axis < 0 || axis >= int64_t(a.dims.size()))
      throw std::runtime_error("CumSum: axis out of range");
    Tensor o = a;
    auto istr = strides_for(a.dims);
    int64_t ax_dim = a.dims[size_t(axis)];
    for (int64_t k = 0; k < a.numel(); ++k) {
      int64_t coord = (k / istr[size_t(axis)]) % ax_dim;
      if (coord > 0) o.set(k, o.at(k) + o.at(k - istr[size_t(axis)]));
    }
    out(std::move(o));
  } else if (op == "Pad") {
    const Tensor& a = in(n, 0);
    const Tensor& pads = in(n, 1);
    double cval = n.inputs.size() > 2 ? in(n, 2).at(0) : 0.0;
    size_t rank = a.dims.size();
    Tensor o;
    o.dtype = a.dtype;
    if (pads.i.size() < 2 * rank)
      throw std::runtime_error("Pad: pads input needs 2*rank entries");
    for (size_t d = 0; d < rank; ++d)
      o.dims.push_back(a.dims[d] + pads.i[d] + pads.i[d + rank]);
    o.alloc();
    for (int64_t k = 0; k < o.numel(); ++k) o.set(k, cval);
    auto istr = strides_for(a.dims);
    auto ostr = strides_for(o.dims);
    for (int64_t k = 0; k < a.numel(); ++k) {
      int64_t dst = 0;
      for (size_t d = 0; d < rank; ++d)
        dst += (((k / istr[d]) % a.dims[d]) + pads.i[d]) * ostr[d];
      o.set(dst, a.at(k));
    }
    out(std::move(o));
  } else if (op == "Softmax") {
    const Tensor& a = in(n, 0);
    int64_t axis = attr_i(n, "axis", -1);
    if (axis < 0) axis += int64_t(a.dims.size());
    if (axis < 0 || axis >= int64_t(a.dims.size()))
      throw std::runtime_error("Softmax: axis out of range");
    Tensor o = a;
    auto istr = strides_for(a.dims);
    int64_t ax_dim = a.dims[size_t(axis)];
    int64_t outer = ax_dim > 0 ? a.numel() / ax_dim : 0;
    for (int64_t b = 0; b < outer; ++b) {
      // map outer index to base offset
      int64_t base = 0, rem = b;
      for (size_t d = 0; d < a.dims.size(); ++d) {
        if (int64_t(d) == axis) continue;
        int64_t sz = a.dims[d];
        // recompute strides over non-axis dims (row-major)
        int64_t block = 1;
        for (size_t d2 = d + 1; d2 < a.dims.size(); ++d2)
          if (int64_t(d2) != axis) block *= a.dims[d2];
        int64_t coord = (rem / block) % sz;
        base += coord * istr[d];
      }
      double mx = -1e300;
      for (int64_t j = 0; j < ax_dim; ++j)
        mx = std::max(mx, a.at(base + j * istr[size_t(axis)]));
      double sum = 0;
      for (int64_t j = 0; j < ax_dim; ++j)
        sum += std::exp(a.at(base + j * istr[size_t(axis)]) - mx);
      for (int64_t j = 0; j < ax_dim; ++j) {
        int64_t at = base + j * istr[size_t(axis)];
        o.set(at, std::exp(a.at(at) - mx) / sum);
      }
    }
    out(std::move(o));
  } else if (op == "PtpuQuantize") {
    /* Fused int8 activation quantization (Div/Round/Max/Min/Cast in
     * ONE pass). The per-element arithmetic replays the original node
     * sequence step for step — float division, nearbyint on double,
     * std::max/min in the original operand order, the Cast's
     * int8_t(int64_t(double)) wrap — so the fused output is bitwise
     * identical to the unfused chain. */
    const Tensor& a = in(n, 0);
    const float s = in(n, 1).f[0];
    const float lo = in(n, 2).f[0], hi = in(n, 3).f[0];
    const bool max_cf = attr_i(n, "q_max_cfirst", 1) != 0;
    const bool min_cf = attr_i(n, "q_min_cfirst", 1) != 0;
    Tensor o;
    o.dims = a.dims;
    o.dtype = DT_I8;
    o.alloc();
    int64_t* oi = o.i.data();
    const auto quant = [&](float d) {
      const float r = float(std::nearbyint(double(d)));
      const float m = max_cf ? std::max(lo, r) : std::max(r, lo);
      const float c = min_cf ? std::min(hi, m) : std::min(m, hi);
      return int64_t(int8_t(int64_t(double(c))));
    };
    if (a.is_float()) {
      const float* af = a.f.data();
      parallel_for(o.numel(), 1 << 15, [&](int64_t k0, int64_t k1) {
        for (int64_t k = k0; k < k1; ++k) oi[k] = quant(af[k] / s);
      });
    } else {  // integer input took the generic double-div path before
      parallel_for(o.numel(), 1 << 15, [&](int64_t k0, int64_t k1) {
        for (int64_t k = k0; k < k1; ++k)
          oi[k] = quant(float(a.at(k) / double(s)));
      });
    }
    out(std::move(o));
  } else if (op == "PtpuDequant") {
    /* Fused dequantization: Cast(int -> float) + Mul by a scalar or
     * per-last-dim scale vector in ONE pass. float(int64) rounds the
     * same integer the old Cast's float(double(int64)) did, and the
     * multiply is the same float multiply the bcast Mul ran. */
    const Tensor& a = in(n, 0);
    const Tensor& sc = in(n, 1);
    const int64_t ns = sc.numel();
    if (ns != 1 && (a.dims.empty() || a.dims.back() != ns))
      throw std::runtime_error("PtpuDequant: scale length " +
                               std::to_string(ns) +
                               " does not match the last input dim");
    Tensor o;
    o.dims = a.dims;
    o.dtype = DT_F32;
    o.alloc();
    float* of = o.f.data();
    const float* sf = sc.f.data();
    const bool aflt = a.is_float();
    const float* af = a.f.data();
    const int64_t* ai = a.i.data();
    parallel_for(o.numel(), 1 << 15, [&](int64_t k0, int64_t k1) {
      for (int64_t k = k0; k < k1; ++k) {
        const float v = aflt ? af[k] : float(ai[k]);
        of[k] = v * (ns == 1 ? sf[0] : sf[k % ns]);
      }
    });
    out(std::move(o));
  } else if (op == "PtpuAttention") {
    /* Fused flash-style attention (load-time fuse_attention): q/k/v in
     * the exporter's [batch, seq, heads, head_dim] layout, output in
     * [b, q, h, d] (== the post-attention Transpose+Reshape memory
     * layout, so the flat [b, q, h*d] form is the same bytes). Online
     * softmax over k blocks — the [q, k] score matrix never
     * materializes — with (batch, head, row-block) tasks spread over
     * the WorkPool; the unfused path ran each head's GEMMs serially.
     * Mask semantics replicate the Where node: masked positions take
     * the `neg` operand's value BEFORE the row max, so fully-masked
     * rows produce the same NaN the unfused softmax does. */
    const Tensor &q = in(n, 0), &k = in(n, 1), &v = in(n, 2);
    const bool has_mask = n.inputs.size() >= 5;
    const Tensor* mk = has_mask ? &in(n, 3) : nullptr;
    const Tensor* ng = has_mask ? &in(n, 4) : nullptr;
    if (!q.is_float() || !k.is_float() || !v.is_float() ||
        q.dims.size() != 4)
      throw std::runtime_error("PtpuAttention: non-float or non-rank-4 "
                               "operands at run time");
    const float scale = attr_f(n, "ptpu_scale", 1.f);
    const float sm_init = attr_f(n, "ptpu_sm_init",
                                 -std::numeric_limits<float>::infinity());
    const int64_t b = q.dims[0], sq = q.dims[1];
    const int64_t h = q.dims[2], d = q.dims[3];
    const int64_t sk = k.dims[1];
    Tensor o;
    o.dtype = DT_F32;
    o.dims = attr_i(n, "ptpu_flat_out", 0)
                 ? std::vector<int64_t>{b, sq, h * d}
                 : std::vector<int64_t>{b, sq, h, d};
    o.alloc();
    // right-aligned broadcast strides over [b, h, q, k] for mask/neg
    int64_t mst[4] = {0, 0, 0, 0}, nst[4] = {0, 0, 0, 0};
    const auto bstr = [](const Tensor& t, int64_t st[4]) {
      const size_t r = t.dims.size();
      int64_t acc = 1;
      for (size_t z = r; z-- > 0;) {
        st[z + 4 - r] = t.dims[z] == 1 ? 0 : acc;
        acc *= t.dims[z];
      }
    };
    if (mk) bstr(*mk, mst);
    if (ng) bstr(*ng, nst);
    const float* qf = q.f.data();
    const float* kf = k.f.data();
    const float* vf = v.f.data();
    float* of = o.f.data();
    const float* ngf = ng ? ng->f.data() : nullptr;
    const int64_t* mki = mk && !mk->is_float() ? mk->i.data() : nullptr;
    const float* mkf = mk && mk->is_float() ? mk->f.data() : nullptr;
    constexpr int64_t QB = 16, KB = 64;
    const int64_t nqb = (sq + QB - 1) / QB;
    // decode-sized blocks (q_len 1, tiny d) are microseconds of
    // compute: run serially rather than paying a pool dispatch
    const int64_t atn_grain =
        b * h * sq * sk * d < (int64_t(1) << 18) ? b * h * nqb : 1;
    parallel_for(b * h * nqb, atn_grain, [&](int64_t t0, int64_t t1) {
      std::vector<float> acc(size_t(d), 0.f);
      float s[KB];
      for (int64_t t = t0; t < t1; ++t) {
        const int64_t qb = t % nqb, bh = t / nqb;
        const int64_t hh = bh % h, bb = bh / h;
        const int64_t i1 = std::min(sq, (qb + 1) * QB);
        for (int64_t i = qb * QB; i < i1; ++i) {
          const float* qi = qf + ((bb * sq + i) * h + hh) * d;
          float m = sm_init;
          double l = 0.0;
          for (int64_t z = 0; z < d; ++z) acc[size_t(z)] = 0.f;
          for (int64_t j0 = 0; j0 < sk; j0 += KB) {
            const int64_t jn = std::min(sk, j0 + KB) - j0;
            for (int64_t jj = 0; jj < jn; ++jj) {
              const float* kj = kf + ((bb * sk + j0 + jj) * h + hh) * d;
              float dot = 0.f;
              for (int64_t z = 0; z < d; ++z) dot += qi[z] * kj[z];
              s[jj] = dot * scale;
            }
            if (mk) {
              for (int64_t jj = 0; jj < jn; ++jj) {
                const int64_t j = j0 + jj;
                const int64_t mi =
                    bb * mst[0] + hh * mst[1] + i * mst[2] + j * mst[3];
                const bool keep =
                    mki ? mki[mi] != 0 : mkf[mi] != 0.f;
                if (!keep)
                  s[jj] = ngf[bb * nst[0] + hh * nst[1] + i * nst[2] +
                              j * nst[3]];
              }
            }
            float bm = m;
            for (int64_t jj = 0; jj < jn; ++jj)
              bm = std::max(bm, s[jj]);
            if (bm > m) {
              const float r = float(std::exp(double(m) - double(bm)));
              l *= double(r);
              for (int64_t z = 0; z < d; ++z) acc[size_t(z)] *= r;
              m = bm;
            }
            /* m still -inf => every score seen so far (this block
             * included) is -inf. Against any later finite score these
             * terms are exp(-inf - finite) == 0, so skipping them is
             * exact; computing them here would be exp(-inf - -inf) ==
             * NaN (a fully-masked k PREFIX spanning a whole block —
             * the fresh-session decode shape). A row that stays -inf
             * to the end keeps l == 0 and divides 0/0 below — the
             * same NaN the unfused softmax yields for an all-masked
             * row. */
            if (std::isinf(m) && m < 0.f) continue;
            for (int64_t jj = 0; jj < jn; ++jj) {
              const float p =
                  float(std::exp(double(s[jj]) - double(m)));
              l += double(p);
              const float* vj = vf + ((bb * sk + j0 + jj) * h + hh) * d;
              for (int64_t z = 0; z < d; ++z)
                acc[size_t(z)] += p * vj[z];
            }
          }
          float* oi = of + ((bb * sq + i) * h + hh) * d;
          const float lf = float(l);
          for (int64_t z = 0; z < d; ++z)
            oi[z] = acc[size_t(z)] / lf;
        }
      }
    });
    out(std::move(o));
  } else if (op == "PtpuPagedAttention") {
    /* Block-table-aware flash attention (kv_attach rewrite,
     * rewrite_paged_attention): q and the freshly projected new_k /
     * new_v arrive as inputs; CACHE rows are read straight through
     * the attached KvPool's per-row block-table views — no gather
     * staging, no concat copy. The key index space replicates the
     * rewritten Concat layout exactly: key j < len(row) reads the
     * pool page, j in [len, P) is the zero tail the slab path staged
     * (dot == +/-0, then the mask applies — decode masks always drop
     * these), and j >= P reads new_k row j-P. Bit-identical to
     * PtpuAttention over the staged concat: same KB blocking, same
     * mask/neg semantics, same online-softmax order; the only
     * substitution is zero storage for [len, P), whose score the
     * contiguous kernel also computed as a zero dot and whose value
     * rows contributed exactly +0 to the accumulators (skipping the
     * add is IEEE-identical). Without a live view (memory-plan dry
     * run, or a hostile artifact naming this op directly) every row
     * reads len 0 and the kernel touches only its declared inputs. */
    const Tensor &q = in(n, 0), &nk = in(n, 1), &nv = in(n, 2);
    const bool has_mask = n.inputs.size() >= 5;
    const Tensor* mk = has_mask ? &in(n, 3) : nullptr;
    const Tensor* ng = has_mask ? &in(n, 4) : nullptr;
    if (!q.is_float() || !nk.is_float() || !nv.is_float() ||
        q.dims.size() != 4)
      throw std::runtime_error("PtpuPagedAttention: non-float or "
                               "non-rank-4 operands at run time");
    if (nk.dims != q.dims || nv.dims != q.dims)
      throw std::runtime_error("PtpuPagedAttention: new k/v dims must "
                               "equal q dims at run time");
    const float scale = attr_f(n, "ptpu_scale", 1.f);
    const float sm_init = attr_f(n, "ptpu_sm_init",
                                 -std::numeric_limits<float>::infinity());
    const int64_t b = q.dims[0], sq = q.dims[1];
    const int64_t h = q.dims[2], d = q.dims[3];
    const int64_t sk = attr_i(n, "ptpu_sk", 0);
    const int64_t layer = attr_i(n, "ptpu_kv_layer", 0);
    const int64_t P = sk - sq;
    if (sq < 1 || P < 0)
      throw std::runtime_error(
          "PtpuPagedAttention: ptpu_sk must cover the query width");
    /* A live view requires the geometry the pool allocated for —
     * anything else (hostile attrs, artifact-declared op) degrades to
     * len 0 so only declared inputs are ever dereferenced. */
    const bool viewed = kv_pool_base_ && kv_max_groups_ > 0 &&
                        int64_t(kv_view_len_.size()) >= b &&
                        layer >= 0 && layer < kv_layers_ &&
                        P == kv_ctx_ && h == kv_heads_ &&
                        d == kv_hdim_;
    Tensor o;
    o.dtype = DT_F32;
    o.dims = attr_i(n, "ptpu_flat_out", 0)
                 ? std::vector<int64_t>{b, sq, h * d}
                 : std::vector<int64_t>{b, sq, h, d};
    o.alloc();
    int64_t mst[4] = {0, 0, 0, 0}, nst[4] = {0, 0, 0, 0};
    const auto bstr = [](const Tensor& t, int64_t st[4]) {
      const size_t r = t.dims.size();
      int64_t acc = 1;
      for (size_t z = r; z-- > 0;) {
        st[z + 4 - r] = t.dims[z] == 1 ? 0 : acc;
        acc *= t.dims[z];
      }
    };
    if (mk) bstr(*mk, mst);
    if (ng) bstr(*ng, nst);
    // the mask/neg index space is [b, h, q, sk]: any non-1 dim must
    // match it or the strided reads walk out of the operand
    if (mk) {
      const auto bc_ok = [&](const Tensor& t) {
        if (t.dims.empty() || t.dims.size() > 4) return false;
        const int64_t want[4] = {b, h, sq, sk};
        const size_t off = 4 - t.dims.size();
        for (size_t z = 0; z < t.dims.size(); ++z)
          if (t.dims[z] != 1 && t.dims[z] != want[z + off])
            return false;
        return true;
      };
      if (!bc_ok(*mk) || !bc_ok(*ng))
        throw std::runtime_error(
            "PtpuPagedAttention: mask/neg not broadcastable to "
            "[b, h, q, ptpu_sk]");
    }
    const float* qf = q.f.data();
    const float* nkf = nk.f.data();
    const float* nvf = nv.f.data();
    float* of = o.f.data();
    const float* ngf = ng ? ng->f.data() : nullptr;
    const int64_t* mki = mk && !mk->is_float() ? mk->i.data() : nullptr;
    const float* mkf = mk && mk->is_float() ? mk->f.data() : nullptr;
    const float* pb = kv_pool_base_;
    const int64_t pgt = kv_page_tokens_;
    const int64_t ge = kv_group_elems_;
    const int64_t ktok0 = (layer * 2 + 0) * pgt;  // group-local token
    const int64_t vtok0 = (layer * 2 + 1) * pgt;  // offsets of k and v
    constexpr int64_t QB = 16, KB = 64;
    const int64_t nqb = (sq + QB - 1) / QB;
    const int64_t atn_grain =
        b * h * sq * sk * d < (int64_t(1) << 18) ? b * h * nqb : 1;
    parallel_for(b * h * nqb, atn_grain, [&](int64_t t0, int64_t t1) {
      std::vector<float> acc(size_t(d), 0.f);
      float s[KB];
      for (int64_t t = t0; t < t1; ++t) {
        const int64_t qb = t % nqb, bh = t / nqb;
        const int64_t hh = bh % h, bb = bh / h;
        const int64_t len =
            viewed ? std::max<int64_t>(0, kv_view_len_[size_t(bb)]) : 0;
        const int32_t* tab =
            viewed ? &kv_view_tab_[size_t(bb * kv_max_groups_)]
                   : nullptr;
        const int64_t i1 = std::min(sq, (qb + 1) * QB);
        for (int64_t i = qb * QB; i < i1; ++i) {
          const float* qi = qf + ((bb * sq + i) * h + hh) * d;
          float m = sm_init;
          double l = 0.0;
          for (int64_t z = 0; z < d; ++z) acc[size_t(z)] = 0.f;
          for (int64_t j0 = 0; j0 < sk; j0 += KB) {
            const int64_t jn = std::min(sk, j0 + KB) - j0;
            for (int64_t jj = 0; jj < jn; ++jj) {
              const int64_t j = j0 + jj;
              const float* kj =
                  j < len
                      ? pb + size_t(tab[j / pgt]) * size_t(ge) +
                            size_t(((ktok0 + j % pgt) * h + hh) * d)
                  : j >= P
                      ? nkf + ((bb * sq + (j - P)) * h + hh) * d
                      : nullptr;
              float dot = 0.f;
              if (kj)
                for (int64_t z = 0; z < d; ++z) dot += qi[z] * kj[z];
              s[jj] = dot * scale;
            }
            if (mk) {
              for (int64_t jj = 0; jj < jn; ++jj) {
                const int64_t j = j0 + jj;
                const int64_t mi =
                    bb * mst[0] + hh * mst[1] + i * mst[2] + j * mst[3];
                const bool keep =
                    mki ? mki[mi] != 0 : mkf[mi] != 0.f;
                if (!keep)
                  s[jj] = ngf[bb * nst[0] + hh * nst[1] + i * nst[2] +
                              j * nst[3]];
              }
            }
            float bm = m;
            for (int64_t jj = 0; jj < jn; ++jj)
              bm = std::max(bm, s[jj]);
            if (bm > m) {
              const float r = float(std::exp(double(m) - double(bm)));
              l *= double(r);
              for (int64_t z = 0; z < d; ++z) acc[size_t(z)] *= r;
              m = bm;
            }
            // see PtpuAttention: a still--inf running max means every
            // score so far is -inf; skipping is exact, computing would
            // NaN on exp(-inf - -inf) (the fresh-session shape)
            if (std::isinf(m) && m < 0.f) continue;
            for (int64_t jj = 0; jj < jn; ++jj) {
              const int64_t j = j0 + jj;
              const float p =
                  float(std::exp(double(s[jj]) - double(m)));
              l += double(p);
              const float* vj =
                  j < len
                      ? pb + size_t(tab[j / pgt]) * size_t(ge) +
                            size_t(((vtok0 + j % pgt) * h + hh) * d)
                  : j >= P
                      ? nvf + ((bb * sq + (j - P)) * h + hh) * d
                      : nullptr;
              if (vj)
                for (int64_t z = 0; z < d; ++z)
                  acc[size_t(z)] += p * vj[z];
            }
          }
          float* oi = of + ((bb * sq + i) * h + hh) * d;
          const float lf = float(l);
          for (int64_t z = 0; z < d; ++z)
            oi[z] = acc[size_t(z)] / lf;
        }
      }
    });
    out(std::move(o));
  } else if (op == "PtpuGelu") {
    /* Fused tanh-GELU (load-time fuse_gelu): replays the exported
     * chain's float ops in the same order — x*x*x (the Pow-3 special
     * case), the same scalar mul/add sequence, double tanh — so the
     * output is bitwise identical to the 8-pass chain. Threaded at
     * the transcendental grain (tanh-bound). */
    const Tensor& a = in(n, 0);
    if (!a.is_float())
      throw std::runtime_error("PtpuGelu: non-float input at run time");
    const float c1 = attr_f(n, "gelu_c1", 0.f);
    const float c2 = attr_f(n, "gelu_c2", 0.f);
    const float c3 = attr_f(n, "gelu_c3", 0.f);
    const float c4 = attr_f(n, "gelu_c4", 0.f);
    Tensor o;
    o.dims = a.dims;
    o.dtype = DT_F32;
    o.alloc();
    const float* af = a.f.data();
    float* of = o.f.data();
    parallel_for(o.numel(), 1 << 13, [&](int64_t k0, int64_t k1) {
      for (int64_t k = k0; k < k1; ++k) {
        const float x = af[k];
        const float inner = c2 * (x + c1 * (x * x * x));
        const float t = float(std::tanh(double(inner)));
        of[k] = x * (c4 * (c3 + t));
      }
    });
    out(std::move(o));
  } else if (op == "PtpuLayerNorm") {
    /* Fused LayerNorm (load-time fuse_layernorm): the exported chain
     * computes the mean TWICE (one for centering the variance, one for
     * centering the output), a biased variance, a denominator guard
     * (folded to always-true), sqrt, pow(.,-1) and the affine tail —
     * ~16 memory-bound passes. One pass per row here, replaying the
     * same float arithmetic (double-accumulated row sums like the
     * ReduceSum fast path, float divides, pow(sqrt(var+eps), -1)). */
    const Tensor& a = in(n, 0);
    if (!a.is_float() || a.dims.size() < 2)
      throw std::runtime_error("PtpuLayerNorm: non-float or sub-rank-2 "
                               "input at run time");
    const bool hg = attr_i(n, "ln_gamma", 0) != 0;
    const bool hb = attr_i(n, "ln_beta", 0) != 0;
    const Tensor* gt = hg ? &in(n, 1) : nullptr;
    const Tensor* bt = hb ? &in(n, hg ? 2 : 1) : nullptr;
    const float eps = attr_f(n, "ln_eps", 0.f);
    const float mdivA = attr_f(n, "ln_mdiv", 1.f);
    const float mdivB = attr_f(n, "ln_mdiv2", 1.f);
    const float vdiv = attr_f(n, "ln_vdiv", 1.f);
    // same hostile-artifact rank/zero guards as MatMul: LayerNorm
    // normally only appears via fusion, but the PARSER accepts it in
    // an artifact directly
    if (a.dims.empty() || a.dims.back() == 0)
      throw std::runtime_error("LayerNorm: empty normalized axis");
    const int64_t D = a.dims.back();
    const int64_t rows = a.numel() / D;
    Tensor o;
    o.dims = a.dims;
    o.dtype = DT_F32;
    o.alloc();
    const float* af = a.f.data();
    float* of = o.f.data();
    const float* gf = gt ? gt->f.data() : nullptr;
    const float* bf = bt ? bt->f.data() : nullptr;
    parallel_for(rows, std::max<int64_t>(1, 65536 / std::max<int64_t>(
                                                      D, 1)),
                 [&](int64_t r0, int64_t r1) {
      for (int64_t row = r0; row < r1; ++row) {
        const float* xr = af + row * D;
        double sum = 0.0;
        for (int64_t j = 0; j < D; ++j) sum += xr[j];
        const float meanA = float(sum) / mdivA;
        const float meanB = float(sum) / mdivB;
        double s2 = 0.0;
        for (int64_t j = 0; j < D; ++j) {
          const float c = xr[j] - meanB;
          s2 += double(c * c);
        }
        const float var = float(s2) / vdiv;
        const float rstd = std::pow(std::sqrt(var + eps), -1.0f);
        float* orow = of + row * D;
        for (int64_t j = 0; j < D; ++j) {
          float val = (xr[j] - meanA) * rstd;
          if (gf) val *= gf[j];
          if (bf) val += bf[j];
          orow[j] = val;
        }
      }
    });
    out(std::move(o));
  } else {
    throw std::runtime_error("op '" + op + "' not supported by the native "
                             "predictor (re-export or extend "
                             "csrc/ptpu_predictor.cc)");
  }
}

void fill_error(char* err, int err_len, const std::string& msg) {
  if (err && err_len > 0) {
    std::snprintf(err, size_t(err_len), "%s", msg.c_str());
  }
}

/* ---- zero-copy reply pinning (ISSUE 17b) --------------------------
 * run() deep-copies every output tensor out of the planned arena into
 * owned heap storage (Buf copy semantics), so "pinning the run's
 * output block" is a MOVE: ptpu_predictor_outputs_detach transfers
 * the outputs vector into a refcounted holder, the serving layer
 * points reply iovecs straight at ptpu_outputs_pin_data, and the
 * holder returns to a small bounded free list when the net core
 * reports the last reply byte flushed. The free-list lock is its own
 * class: release runs on net event threads while the conn's output
 * lock (net.conn_out, rank 100) is held, so pred.outpin ranks above
 * it (105) and below net.inbox (110). */
PTPU_LOCK_CLASS(kLockPredOutpin, "pred.outpin", 105);

struct OutPin {
  std::vector<Tensor> outs;
};

ptpu::Mutex g_outpin_mu{kLockPredOutpin};
std::vector<std::unique_ptr<OutPin>> g_outpin_free;
constexpr size_t kOutPinPoolCap = 16;

OutPin* outpin_acquire() {
  {
    ptpu::MutexLock l(g_outpin_mu);
    if (!g_outpin_free.empty()) {
      OutPin* p = g_outpin_free.back().release();
      g_outpin_free.pop_back();
      return p;
    }
  }
  return new OutPin();
}

}  // namespace

// -------------------------------------------------------------------- C ABI
/* Integer inputs (token ids, lengths) — the reference C API exposes
 * PD_DataType INT32/INT64 (`capi_exp/pd_inference_api.h`); without
 * these, embedding/transformer artifacts cannot be served natively. */
/* Caller-supplied dims are untrusted: a negative ndim/dim or an
 * int64-overflowing product would produce a bogus numel() and an
 * out-of-bounds read of `data`. ndim == 0 is a valid scalar (empty
 * dims, numel 1); dims may then be null. */
static void check_dims(const int64_t* dims, int ndim) {
  if (ndim < 0) throw std::runtime_error("set_input: ndim must be >= 0");
  if (ndim > 0 && !dims)
    throw std::runtime_error("set_input: dims is null");
  int64_t n = 1;
  for (int k = 0; k < ndim; ++k) {
    if (dims[k] < 0)
      throw std::runtime_error("set_input: negative dim at index " +
                               std::to_string(k));
    if (dims[k] > 0 && n > (int64_t(1) << 40) / dims[k])
      throw std::runtime_error("set_input: element count overflows "
                               "the 2^40 sanity cap");
    n *= dims[k];
  }
}

template <class T>
static int set_input_int(void* h, const char* name, const T* data,
                         const int64_t* dims, int ndim, int dtype,
                         char* err, int err_len) {
  try {
    if (!h || !name || !data)
      throw std::runtime_error("set_input: null handle or buffer");
    check_dims(dims, ndim);
    auto* p = (Predictor*)h;
    Tensor t;
    t.dtype = dtype;
    t.dims.assign(dims, dims + ndim);
    t.i.assign(data, data + t.numel());
    p->env[name] = std::move(t);
    return 0;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return 1;
  }
}

extern "C" {

typedef struct PTPU_Predictor PTPU_Predictor;
typedef struct PTPU_KvPool PTPU_KvPool;

static PTPU_Predictor* predictor_create_impl(const char* model_path,
                                             int64_t batch_override,
                                             int threads, char* err,
                                             int err_len) {
  try {
    std::ifstream f(model_path, std::ios::binary);
    if (!f) throw std::runtime_error(std::string("cannot open ") +
                                     model_path);
    std::stringstream ss;
    ss << f.rdbuf();
    std::unique_ptr<Predictor> p(new Predictor());
    p->g = parse_model(ss.str());
    /* Structural validation before ANY pass touches the graph
     * (fuzzing finding, ISSUE 11; repro:
     * csrc/fuzz/corpus/onnx/crash-identity-no-operands.bin): every op
     * in this dialect consumes at least one input and produces at
     * least one output — the load-time rewrites (identity
     * elimination, fusion matchers) index inputs[0]/outputs[0] on
     * matched nodes, so a hostile arity is rejected here once
     * instead of guarded at every matcher. */
    for (const auto& vn : p->g.nodes)
      if (vn.inputs.empty() || vn.outputs.empty())
        throw std::runtime_error("node '" + vn.op +
                                 "' has no inputs or no outputs");
    /* Bucket-ladder support (the serving micro-batcher): re-plan the
     * SAME artifact for a different leading (batch) dim — every
     * overridable graph input's axis 0 is rewritten before the
     * load-time dry run, so fusion, weight pre-packing and the arena
     * plan all settle at the override batch and batched runs stay on
     * the zero-alloc path. */
    if (batch_override > 0) {
      int64_t orig_batch = 0;
      for (const auto& name : p->g.input_names) {
        if (p->g.initializers.count(name)) continue;  // default-valued
        auto it = p->g.input_dims.find(name);
        if (it != p->g.input_dims.end() && !it->second.empty()) {
          if (orig_batch == 0) orig_batch = it->second[0];
          it->second[0] = batch_override;
        }
      }
      /* Exporters bake the trace batch into Reshape shape constants
       * and Expand targets (jax resolves every -1 before lowering),
       * which pinned each re-planned bucket to graphs with no
       * batch-carrying reshapes. Record the export->override batch
       * pair: the Reshape/Expand kernels repair a batch-baked target
       * at run time (see the batch-repair notes in those branches),
       * and the serving layer PROBES every bucket before trusting it,
       * so a graph the repair cannot carry degrades to a dropped
       * bucket — never to silent wrong shapes. */
      if (orig_batch > 1 && batch_override != orig_batch) {
        p->bo_from_ = orig_batch;
        p->bo_to_ = batch_override;
      }
    }
    if (std::getenv("PTPU_DUMP_GRAPH")) {
      for (const auto& nd : p->g.nodes) {
        std::fprintf(stderr, "[graph] %s(", nd.op.c_str());
        for (const auto& i2 : nd.inputs) {
          auto it2 = p->g.initializers.find(i2);
          if (it2 != p->g.initializers.end() && !it2->second.is_float() &&
              it2->second.i.size() <= 8) {
            std::fprintf(stderr, "%s=[", i2.c_str());
            for (auto v : it2->second.i)
              std::fprintf(stderr, "%lld,", (long long)v);
            std::fprintf(stderr, "] ");
          } else {
            std::fprintf(stderr, "%s ", i2.c_str());
          }
        }
        std::fprintf(stderr, ") -> %s\n",
                     nd.outputs.empty() ? "?" : nd.outputs[0].c_str());
      }
    }
    for (const auto& kv : p->g.initializers) p->env[kv.first] = kv.second;
    p->fold_constants();
    // PTPU_PREDICTOR_OPT=0 keeps the unoptimized graph — the parity
    // baseline the fused/planned path is tested against
    const char* opt = std::getenv("PTPU_PREDICTOR_OPT");
    if (!opt || std::strcmp(opt, "0") != 0) {
      p->eliminate_identities();
      p->fuse_quant_ops();
      // transformer fusions validate against dims recorded by one
      // load-time dry run; dynamic-shape artifacts skip them exactly
      // like they skip the memory plan
      std::map<std::string, std::vector<int64_t>> shp;
      std::map<std::string, int> dty;
      if (p->dry_run_shapes(&shp, &dty)) {
        p->eliminate_noop_casts(dty);
        p->fuse_attention(shp);
        p->fuse_layernorm(shp);
      }
      p->fuse_gelu();
      p->fuse_ops();
      p->prepack_weights();
      p->plan_memory();
      // plan_memory's dry run executed every GEMM, so all autotune
      // probes for this artifact's shapes have fired — persist any
      // new winners now (no-op when the cache was already warm)
      if (ptpu::tune::Registry::Enabled())
        ptpu::tune::Registry::Inst().SaveIfDirty();
    }
    p->build_stats_index();
    if (threads > 0) {
      // private execution context: this instance's parallel_for work
      // runs on its own sub-pool instead of the shared global one
      p->owned_pool_.reset(new WorkPool(threads - 1));
      p->pool_ = p->owned_pool_.get();
    }
    return (PTPU_Predictor*)p.release();
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return nullptr;
  }
}

__attribute__((visibility("default")))
PTPU_Predictor* ptpu_predictor_create(const char* model_path, char* err,
                                      int err_len) {
  return predictor_create_impl(model_path, 0, 0, err, err_len);
}

/* Extended create: `batch_override` > 0 re-plans the artifact's input
 * batch dim (bucket-ladder serving); `threads` > 0 gives the instance
 * a PRIVATE worker sub-pool of that many threads (including the
 * calling thread) so concurrent instances scale instead of
 * serializing on the shared pool's dispatch mutex. 0/0 behaves
 * exactly like ptpu_predictor_create. */
__attribute__((visibility("default")))
PTPU_Predictor* ptpu_predictor_create_opts(const char* model_path,
                                           int64_t batch_override,
                                           int threads, char* err,
                                           int err_len) {
  return predictor_create_impl(model_path, batch_override, threads, err,
                               err_len);
}

/* Shared execution contexts for multi-predictor hosts (the serving
 * runtime attaches ONE sub-pool per instance to all of that
 * instance's bucket predictors). A pool attached via set_pool is
 * BORROWED: the caller owns it and must destroy it after every
 * predictor using it. Passing a null pool detaches (back to the
 * shared global pool). */
__attribute__((visibility("default")))
void* ptpu_workpool_create(int threads) {
  return new WorkPool(threads > 0 ? threads - 1 : 0);
}

__attribute__((visibility("default")))
void ptpu_workpool_destroy(void* pool) {
  if (!pool) return;
  delete (WorkPool*)pool;
}

__attribute__((visibility("default")))
void ptpu_predictor_set_pool(PTPU_Predictor* h, void* pool) {
  auto* p = (Predictor*)h;
  if (!p) return;
  p->pool_ = (WorkPool*)pool;
  if (p->owned_pool_.get() != p->pool_) p->owned_pool_.reset();
}

__attribute__((visibility("default")))
void ptpu_predictor_destroy(PTPU_Predictor* h) {
  if (!h) return;
  delete (Predictor*)h;
}

__attribute__((visibility("default")))
int ptpu_predictor_num_inputs(PTPU_Predictor* h) {
  if (!h) return 0;
  return int(((Predictor*)h)->g.input_names.size());
}

// introspection: node count after load-time rewrites (fusion shrinks
// it), count of nodes eliminated by fusion, and the planned arena size
// in bytes (0 when the artifact has dynamic shapes and serving fell
// back to per-tensor allocation)
__attribute__((visibility("default")))
int ptpu_predictor_num_nodes(PTPU_Predictor* h) {
  if (!h) return 0;
  return int(((Predictor*)h)->g.nodes.size());
}

__attribute__((visibility("default")))
int ptpu_predictor_fused_nodes(PTPU_Predictor* h) {
  if (!h) return 0;
  return ((Predictor*)h)->fused_nodes_;
}

__attribute__((visibility("default")))
int64_t ptpu_predictor_arena_bytes(PTPU_Predictor* h) {
  auto* p = (Predictor*)h;
  if (!p) return 0;
  return p->planned_ ? int64_t(p->arena_bytes_) : 0;
}

__attribute__((visibility("default")))
int ptpu_predictor_num_outputs(PTPU_Predictor* h) {
  if (!h) return 0;
  return int(((Predictor*)h)->g.output_names.size());
}

__attribute__((visibility("default")))
const char* ptpu_predictor_input_name(PTPU_Predictor* h, int i) {
  auto* p = (Predictor*)h;
  if (!p) return "";
  if (i < 0 || size_t(i) >= p->g.input_names.size()) return "";
  return p->g.input_names[size_t(i)].c_str();
}

/* Input signature introspection (the serving runtime validates and
 * stitches request tensors against these; after a create_opts batch
 * override the dims reflect the OVERRIDDEN batch). dtype is the ONNX
 * TensorProto code (1 f32, 6 i32, 7 i64). */
__attribute__((visibility("default")))
int ptpu_predictor_input_ndim(PTPU_Predictor* h, int i) {
  auto* p = (Predictor*)h;
  if (!p) return -1;
  if (i < 0 || size_t(i) >= p->g.input_names.size()) return -1;
  auto it = p->g.input_dims.find(p->g.input_names[size_t(i)]);
  return it == p->g.input_dims.end() ? -1 : int(it->second.size());
}

__attribute__((visibility("default")))
const int64_t* ptpu_predictor_input_dims(PTPU_Predictor* h, int i) {
  auto* p = (Predictor*)h;
  if (!p) return nullptr;
  if (i < 0 || size_t(i) >= p->g.input_names.size()) return nullptr;
  auto it = p->g.input_dims.find(p->g.input_names[size_t(i)]);
  return it == p->g.input_dims.end() ? nullptr : it->second.data();
}

__attribute__((visibility("default")))
int ptpu_predictor_input_dtype(PTPU_Predictor* h, int i) {
  auto* p = (Predictor*)h;
  if (!p) return -1;
  if (i < 0 || size_t(i) >= p->g.input_names.size()) return -1;
  auto it = p->g.input_dtypes.find(p->g.input_names[size_t(i)]);
  return it == p->g.input_dtypes.end() ? DT_F32 : it->second;
}

// runs that missed the planned-arena path since load/reset
__attribute__((visibility("default")))
int64_t ptpu_predictor_dynamic_fallbacks(PTPU_Predictor* h) {
  if (!h) return 0;
  return int64_t(((Predictor*)h)->dyn_fallback_runs_.load(
      std::memory_order_relaxed));
}

__attribute__((visibility("default")))
int ptpu_predictor_set_input(PTPU_Predictor* h, const char* name,
                             const float* data, const int64_t* dims,
                             int ndim, char* err, int err_len) {
  try {
    if (!h || !name || !data)
      throw std::runtime_error("set_input: null handle or buffer");
    check_dims(dims, ndim);
    auto* p = (Predictor*)h;
    Tensor t;
    t.dtype = DT_F32;
    t.dims.assign(dims, dims + ndim);
    t.f.assign(data, data + t.numel());
    p->env[name] = std::move(t);
    return 0;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return 1;
  }
}

__attribute__((visibility("default")))
int ptpu_predictor_set_input_i32(PTPU_Predictor* h, const char* name,
                                 const int32_t* data, const int64_t* dims,
                                 int ndim, char* err, int err_len) {
  return set_input_int(h, name, data, dims, ndim, DT_I32, err, err_len);
}

__attribute__((visibility("default")))
int ptpu_predictor_set_input_i64(PTPU_Predictor* h, const char* name,
                                 const int64_t* data, const int64_t* dims,
                                 int ndim, char* err, int err_len) {
  return set_input_int(h, name, data, dims, ndim, DT_I64, err, err_len);
}

__attribute__((visibility("default")))
int ptpu_predictor_run(PTPU_Predictor* h, char* err, int err_len) {
  try {
    if (!h) throw std::runtime_error("run: null predictor handle");
    ((Predictor*)h)->run();
    return 0;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return 1;
  }
}

__attribute__((visibility("default")))
int ptpu_predictor_output_ndim(PTPU_Predictor* h, int i) {
  auto* p = (Predictor*)h;
  if (!p) return -1;
  if (i < 0 || size_t(i) >= p->outputs.size()) return -1;
  return int(p->outputs[size_t(i)].dims.size());
}

__attribute__((visibility("default")))
const int64_t* ptpu_predictor_output_dims(PTPU_Predictor* h, int i) {
  auto* p = (Predictor*)h;
  if (!p) return nullptr;
  if (i < 0 || size_t(i) >= p->outputs.size()) return nullptr;
  return p->outputs[size_t(i)].dims.data();
}

// ---- observability --------------------------------------------------
// Serving stats snapshot as JSON: {"runs":N,"total_run_us":T,
// "run_us":{count,sum,buckets[32]},"ops":{op:{calls,time_us,bytes}}}.
// The returned pointer is owned by the predictor and valid until the
// next stats_json call on the same handle (or destroy). Same
// thread-compatibility contract as run().
__attribute__((visibility("default")))
const char* ptpu_predictor_stats_json(PTPU_Predictor* h) {
  auto* p = (Predictor*)h;
  if (!p) return "{}";
  std::string out = "{";
  ptpu::AppendJsonU64(&out, "runs", p->runs_);
  out += ',';
  ptpu::AppendJsonU64(&out, "total_run_us", p->run_time_us_);
  out += ',';
  ptpu::AppendJsonU64(
      &out, "dynamic_shape_fallback",
      p->dyn_fallback_runs_.load(std::memory_order_relaxed));
  out += ',';
  ptpu::AppendJsonHist(&out, "run_us", p->run_us_);
  out += ",\"ops\":{";
  bool first = true;
  for (const auto& kv : p->op_stats_) {
    if (kv.second.calls == 0) continue;  // index entries never executed
    if (!first) out += ',';
    first = false;
    out += '"';
    out += ptpu::JsonEscape(kv.first);
    out += "\":{";
    ptpu::AppendJsonU64(&out, "calls", kv.second.calls);
    out += ',';
    ptpu::AppendJsonU64(&out, "time_us", kv.second.time_us);
    out += ',';
    ptpu::AppendJsonU64(&out, "bytes", kv.second.bytes);
    out += '}';
  }
  out += "}}";
  p->stats_json_.swap(out);
  return p->stats_json_.c_str();
}

__attribute__((visibility("default")))
void ptpu_predictor_stats_reset(PTPU_Predictor* h) {
  if (!h) return;
  ((Predictor*)h)->reset_stats();
}

// Wire the host profiler (csrc/ptpu_runtime.cc Profiler) into this TU:
// `record_fn` = ptpu_profiler_record, `enabled_fn` =
// ptpu_profiler_enabled, both passed as raw addresses by the binding
// layer (the two .so files must stay independent). Timestamps are
// steady-clock microseconds on both sides, so predictor spans align
// with RecordEvent spans in one chrome trace. Process-global; pass
// nulls to unwire.
__attribute__((visibility("default")))
void ptpu_predictor_set_profiler(ProfRecordFn record_fn,
                                 ProfEnabledFn enabled_fn) {
  g_prof_record.store(record_fn, std::memory_order_relaxed);
  g_prof_enabled.store(enabled_fn, std::memory_order_relaxed);
}

// ---- KV-cached decode (ISSUE r9 tentpole c) -------------------------
/* Validate the decode-artifact convention and allocate the per-session
 * KV arena (`sessions` slots). Returns 0 on success. Must be called
 * before any other kv/decode entry. */
__attribute__((visibility("default")))
int ptpu_predictor_kv_plan(PTPU_Predictor* h, int sessions, char* err,
                           int err_len) {
  try {
    if (!h) throw std::runtime_error("kv_plan: null predictor handle");
    ((Predictor*)h)->kv_plan(sessions);
    return 0;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return 1;
  }
}

__attribute__((visibility("default")))
int ptpu_predictor_kv_sessions(PTPU_Predictor* h) {
  if (!h) return 0;
  auto* p = (Predictor*)h;
  if (p->kv_pool_) return p->kv_pool_->max_sessions();
  return p->kv_sessions_;
}

// free slot id (len 0), or -1 when every slot is busy (the caller —
// the serving layer — owns the eviction policy). With a paged pool
// attached this delegates to the shared pool's session space.
__attribute__((visibility("default")))
int ptpu_predictor_kv_open(PTPU_Predictor* h) {
  if (!h) return -1;
  auto* p = (Predictor*)h;
  if (p->kv_pool_) return p->kv_pool_->open();
  return p->kv_open();
}

__attribute__((visibility("default")))
void ptpu_predictor_kv_close(PTPU_Predictor* h, int sid) {
  if (!h) return;
  auto* p = (Predictor*)h;
  if (p->kv_pool_) return p->kv_pool_->close(sid);
  p->kv_close(sid);
}

// positions fed per session per decode step (the artifact's baked
// ids width W — 1 for the classic step, k+1 for a speculative-verify
// artifact); 0 before kv_plan/kv_attach validated the convention
__attribute__((visibility("default")))
int ptpu_predictor_kv_width(PTPU_Predictor* h) {
  if (!h) return 0;
  auto* p = (Predictor*)h;
  if (p->kv_sessions_ == 0 && !p->kv_pool_) return 0;
  return int(p->kv_width_);
}

/* Truncate a session to `new_len` positions — speculative-decoding
 * rollback. Paged sessions release page groups past the new tail (a
 * shared group is unreferenced, never mutated: published prefix pages
 * and fork siblings keep their bytes); the next append COW-unshares
 * the kept tail if needed. No-op when new_len >= len. */
__attribute__((visibility("default")))
int ptpu_predictor_kv_trim(PTPU_Predictor* h, int sid, int64_t new_len,
                           char* err, int err_len) {
  try {
    if (!h) throw std::runtime_error("kv_trim: null predictor handle");
    ((Predictor*)h)->kv_trim(sid, new_len);
    return 0;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return 1;
  }
}

// current appended length of a session (-1: bad/closed session)
__attribute__((visibility("default")))
int64_t ptpu_predictor_kv_len(PTPU_Predictor* h, int sid) {
  auto* p = (Predictor*)h;
  if (!p) return -1;
  if (p->kv_pool_) return p->kv_pool_->len(sid);
  if (sid < 0 || sid >= p->kv_sessions_ ||
      !p->kv_sess_[size_t(sid)].open)
    return -1;
  return p->kv_sess_[size_t(sid)].len;
}

// ---- paged KV pool (ISSUE 12 tentpole) ------------------------------
/* Create a shared paged KV pool. Arguments <= 0 resolve from the
 * environment: pool_tokens ($PTPU_KV_POOL_TOKENS; 0 defers sizing to
 * the first attach as 64 x context — the r9 fixed-slot RAM envelope),
 * page_tokens ($PTPU_KV_PAGE, default 16), max_sessions
 * ($PTPU_KV_SESSIONS, default 4096); prefix_cache < 0 reads
 * $PTPU_KV_PREFIX (default on). Attach it to every ladder-bucket
 * predictor of ONE decode artifact; sessions live in the pool. */
__attribute__((visibility("default")))
PTPU_KvPool* ptpu_kvpool_create(int64_t pool_tokens, int page_tokens,
                                int max_sessions, int prefix_cache,
                                char* err, int err_len) {
  try {
    const auto env_i64 = [](const char* name, int64_t dflt) {
      const char* e = std::getenv(name);
      if (!e) return dflt;
      const int64_t v = std::atoll(e);
      return v > 0 ? v : dflt;
    };
    if (pool_tokens <= 0)
      pool_tokens = env_i64("PTPU_KV_POOL_TOKENS", 0);
    if (page_tokens <= 0)
      page_tokens = int(env_i64("PTPU_KV_PAGE", 16));
    if (max_sessions <= 0)
      max_sessions = int(env_i64("PTPU_KV_SESSIONS", 4096));
    if (prefix_cache < 0) {
      const char* e = std::getenv("PTPU_KV_PREFIX");
      prefix_cache = e && std::strcmp(e, "0") == 0 ? 0 : 1;
    }
    auto* pool = new KvPool(pool_tokens, page_tokens, max_sessions,
                            prefix_cache != 0);
    return (PTPU_KvPool*)pool;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return nullptr;
  }
}

__attribute__((visibility("default")))
void ptpu_kvpool_destroy(PTPU_KvPool* h) {
  if (!h) return;
  delete (KvPool*)h;
}

/* Bind a decode-artifact predictor to the pool (validates the decode
 * convention, fixes the pool geometry on first attach, and — unless
 * PTPU_KV_DIRECT=0 — rewrites the attention graph onto the
 * block-table read path). The pool must outlive the predictor. */
__attribute__((visibility("default")))
int ptpu_predictor_kv_attach(PTPU_Predictor* h, PTPU_KvPool* pool,
                             char* err, int err_len) {
  try {
    if (!h || !pool)
      throw std::runtime_error("kv_attach: null handle");
    ((Predictor*)h)->kv_attach((KvPool*)pool);
    return 0;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return 1;
  }
}

// 1 when the attention graph rewrote onto the block-table read path
// (gather fallback otherwise) — introspection for tests and stats
__attribute__((visibility("default")))
int ptpu_predictor_kv_direct(PTPU_Predictor* h) {
  if (!h) return 0;
  return ((Predictor*)h)->kv_direct_ ? 1 : 0;
}

__attribute__((visibility("default")))
int ptpu_kvpool_open(PTPU_KvPool* h) {
  if (!h) return -1;
  return ((KvPool*)h)->open();
}

// clone src sharing every page group (copy-on-write on divergence);
// -1 when src is closed or the session table is full
__attribute__((visibility("default")))
int ptpu_kvpool_fork(PTPU_KvPool* h, int sid) {
  if (!h) return -1;
  return ((KvPool*)h)->fork(sid);
}

__attribute__((visibility("default")))
void ptpu_kvpool_close(PTPU_KvPool* h, int sid) {
  if (!h) return;
  ((KvPool*)h)->close(sid);
}

__attribute__((visibility("default")))
int64_t ptpu_kvpool_len(PTPU_KvPool* h, int sid) {
  if (!h) return -1;
  return ((KvPool*)h)->len(sid);
}

// truncate a pool session to new_len (COW-safe rollback; see
// ptpu_predictor_kv_trim). Returns 0, or 1 on a closed/bad session.
__attribute__((visibility("default")))
int ptpu_kvpool_trim(PTPU_KvPool* h, int sid, int64_t new_len) {
  if (!h) return 1;
  try {
    ((KvPool*)h)->trim(sid, new_len);
    return 0;
  } catch (const std::exception&) {
    return 1;
  }
}

/* Prefix-cache adoption for a freshly opened (or page-aligned)
 * session: extend it with published page groups matching `tokens`,
 * never past n-1 (the final prompt token must be stepped for its
 * logits). Returns tokens adopted, 0 on any mismatch/miss. */
__attribute__((visibility("default")))
int64_t ptpu_kvpool_adopt(PTPU_KvPool* h, int sid,
                          const int64_t* tokens, int64_t n) {
  if (!h || !tokens || n < 1) return 0;
  try {
    return ((KvPool*)h)->adopt(sid, tokens, n);
  } catch (const std::exception&) {
    return 0;
  }
}

// publish every full PROMPT page of `sid` into the prefix cache
// (pass the prompt length as n so generated tokens stay private)
__attribute__((visibility("default")))
int ptpu_kvpool_publish(PTPU_KvPool* h, int sid,
                        const int64_t* tokens, int64_t n) {
  if (!h || !tokens || n < 1) return 1;
  try {
    ((KvPool*)h)->publish(sid, tokens, n);
    return 0;
  } catch (const std::exception&) {
    return 1;
  }
}

// pages_total/in_use/cached gauges + prefix/cow/exhaustion counters
__attribute__((visibility("default")))
const char* ptpu_kvpool_stats_json(PTPU_KvPool* h) {
  if (!h) return "{}";
  auto* p = (KvPool*)h;
  p->stats_json_ = p->stats_json();
  return p->stats_json_.c_str();
}

// ---- KV tiering + session hibernation (ISSUE 19) --------------------
/* Attach the mmap'd spill tier at `path` (created 0600 if missing; a
 * malformed pre-existing file is rejected + counted, never scribbled
 * over). Arguments <= 0 resolve from the environment: max_bytes
 * ($PTPU_KV_SPILL_MAX_BYTES, default 1 GiB; 0 stays 0 = unbounded
 * only when passed explicitly). Requires an attached decode artifact
 * (the slot size is the page-group slab size). */
__attribute__((visibility("default")))
int ptpu_kvpool_spill_attach(PTPU_KvPool* h, const char* path,
                             int64_t max_bytes, char* err,
                             int err_len) {
  try {
    if (!h || !path || !*path)
      throw std::runtime_error("spill_attach: null handle or path");
    if (max_bytes < 0) {
      const char* e = std::getenv("PTPU_KV_SPILL_MAX_BYTES");
      max_bytes = e ? std::atoll(e) : 0;
      if (max_bytes <= 0) max_bytes = int64_t(1) << 30;
    }
    ((KvPool*)h)->spill_attach(path, uint64_t(max_bytes));
    return 0;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return 1;
  }
}

/* Hibernate session `sid`: serialize it out of the pool (cold groups
 * spill to disk, the session slot frees). Two-call protocol: returns
 * the record size in bytes; the hibernation EXECUTES only when `cap`
 * holds it (query with cap=0 first, then call again with a buffer).
 * Returns -1 with `err` filled on failure — "kv spill exhausted" is
 * the soft retryable case, mirroring "kv pool exhausted". */
__attribute__((visibility("default")))
int64_t ptpu_kvpool_hibernate(PTPU_KvPool* h, int sid, uint8_t* buf,
                              int64_t cap, char* err, int err_len) {
  try {
    if (!h) throw std::runtime_error("hibernate: null handle");
    auto* p = (KvPool*)h;
    int64_t need = 0;
    const std::vector<uint8_t> rec =
        p->hibernate(sid, buf == nullptr ? -1 : cap, &need);
    if (rec.empty()) return need;  // query mode / cap too small
    std::memcpy(buf, rec.data(), rec.size());
    return int64_t(rec.size());
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return -1;
  }
}

/* Restore a hibernated session from its record bytes. Returns the
 * new sid, -1 when every session slot is taken (free one and retry —
 * the open() contract, no error), or -2 with `err` filled ("kv pool
 * exhausted" is the soft retryable case; "hibernation record
 * corrupt" is terminal for these bytes). */
__attribute__((visibility("default")))
int ptpu_kvpool_restore(PTPU_KvPool* h, const uint8_t* data,
                        int64_t size, char* err, int err_len) {
  try {
    if (!h || !data || size < 1)
      throw std::runtime_error("restore: null handle or buffer");
    return ((KvPool*)h)->restore(data, size_t(size));
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return -2;
  }
}

// discard a hibernation record without restoring (the hibernated
// session was closed) — frees its spill slots and shared-group refs
__attribute__((visibility("default")))
void ptpu_kvpool_hibernate_drop(PTPU_KvPool* h, const uint8_t* data,
                                int64_t size) {
  if (!h || !data || size < 1) return;
  ((KvPool*)h)->hibernate_drop(data, size_t(size));
}

// sessions currently hibernated (the RAM-side registry size)
__attribute__((visibility("default")))
int64_t ptpu_kvpool_hibernated(PTPU_KvPool* h) {
  if (!h) return 0;
  return ((KvPool*)h)->hibernated();
}

// persist the content-addressed adopt index (tmp+rename). Returns
// records written, -1 on I/O failure.
__attribute__((visibility("default")))
int64_t ptpu_kvpool_prefix_save(PTPU_KvPool* h, const char* path,
                                char* err, int err_len) {
  try {
    if (!h || !path || !*path)
      throw std::runtime_error("prefix_save: null handle or path");
    return ((KvPool*)h)->prefix_save(path);
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return -1;
  }
}

// warm the adopt index from a persisted file. Returns records
// adopted (missing file -> 0; malformed file -> whole-file reject,
// counted, 0).
__attribute__((visibility("default")))
int64_t ptpu_kvpool_prefix_load(PTPU_KvPool* h, const char* path,
                                char* err, int err_len) {
  try {
    if (!h || !path || !*path)
      throw std::runtime_error("prefix_load: null handle or path");
    return ((KvPool*)h)->prefix_load(path);
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return -1;
  }
}

/* One batched decode step: row r feeds tokens[r] into open session
 * sids[r] (n <= the artifact batch; a session may appear at most once
 * per call). On success the per-row next-token logits are output 0 of
 * the run (rows beyond n are padding) and each session's cache grew by
 * one position. Same thread-compatibility contract as run(). */
__attribute__((visibility("default")))
int ptpu_predictor_decode_step(PTPU_Predictor* h, const int64_t* sids,
                               const int64_t* tokens, int n, char* err,
                               int err_len) {
  try {
    if (!h || !sids || !tokens)
      throw std::runtime_error("decode_step: null handle or buffer");
    ((Predictor*)h)->decode_step(sids, tokens, n);
    return 0;
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return 1;
  }
}

// Output data as float32 (int outputs are converted in place once).
__attribute__((visibility("default")))
const float* ptpu_predictor_output_data(PTPU_Predictor* h, int i) {
  auto* p = (Predictor*)h;
  if (!p) return nullptr;
  if (i < 0 || size_t(i) >= p->outputs.size()) return nullptr;
  Tensor& t = p->outputs[size_t(i)];
  if (!t.is_float() && t.f.size() != size_t(t.numel())) {
    t.f.resize(size_t(t.numel()));
    for (int64_t k = 0; k < t.numel(); ++k) t.f[size_t(k)] = float(t.i[k]);
  }
  return t.f.data();
}

/* ---- zero-copy serving hooks (ISSUE 17) ---------------------------
 * input_alloc: resolve the named graph input at the given dims and
 * hand back its WRITABLE storage — the serving gather writes wire
 * rows straight into the batch tensor, collapsing the old
 * stage-buffer memcpy + set_input copy into one pass. f32 returns
 * float storage; i32/i64 return the predictor's internal int64
 * storage (i32 callers widen as they gather, exactly the widening
 * set_input_i32 performed on its copy). The tensor is reused across
 * calls, so steady-state batches allocate nothing. The caller must
 * fill every element (pad rows included) before run(). */
__attribute__((visibility("default")))
void* ptpu_predictor_input_alloc(PTPU_Predictor* h, const char* name,
                                 int dtype, const int64_t* dims,
                                 int ndim, char* err, int err_len) {
  try {
    if (!h || !name)
      throw std::runtime_error("input_alloc: null handle or name");
    if (dtype != DT_F32 && dtype != DT_I32 && dtype != DT_I64)
      throw std::runtime_error("input_alloc: unsupported dtype " +
                               std::to_string(dtype));
    check_dims(dims, ndim);
    auto* p = (Predictor*)h;
    Tensor& t = p->env[name];
    t.dtype = dtype;
    t.dims.assign(dims, dims + ndim);
    const size_t n = size_t(t.numel());
    if (t.is_float()) {
      t.i.resize(0);
      t.f.resize(n);
      return t.f.data();
    }
    t.f.resize(0);
    t.i.resize(n);
    return t.i.data();
  } catch (const std::exception& e) {
    fill_error(err, err_len, e.what());
    return nullptr;
  }
}

/* Detach the last run's outputs into a refcounted pin holder (see the
 * OutPin notes above): after this call the predictor's own
 * output_data/output_dims views are empty until the next run, and the
 * returned handle keeps every output's storage alive until
 * ptpu_outputs_pin_release — reply frames point writev iovecs at
 * pin_data and release on flush completion. Returns NULL when the
 * last run produced no outputs. Same thread-compatibility contract as
 * run(); the pin accessors and release are thread-safe. */
__attribute__((visibility("default")))
void* ptpu_predictor_outputs_detach(PTPU_Predictor* h) {
  auto* p = (Predictor*)h;
  if (!p || p->outputs.empty()) return nullptr;
  // int outputs convert once here (output_data's rule) so pin_data
  // stays a const read from any thread
  for (auto& t : p->outputs) {
    if (!t.is_float() && t.f.size() != size_t(t.numel())) {
      t.f.resize(size_t(t.numel()));
      for (int64_t k = 0; k < t.numel(); ++k)
        t.f[size_t(k)] = float(t.i[k]);
    }
  }
  OutPin* pin = outpin_acquire();
  pin->outs = std::move(p->outputs);
  p->outputs.clear();
  return pin;
}

__attribute__((visibility("default")))
int ptpu_outputs_pin_count(void* pin) {
  auto* p = (OutPin*)pin;
  return p ? int(p->outs.size()) : 0;
}

// f32 view of pinned output i (ints were converted at detach)
__attribute__((visibility("default")))
const float* ptpu_outputs_pin_data(void* pin, int i) {
  auto* p = (OutPin*)pin;
  if (!p || i < 0 || size_t(i) >= p->outs.size()) return nullptr;
  return p->outs[size_t(i)].f.data();
}

__attribute__((visibility("default")))
int ptpu_outputs_pin_ndim(void* pin, int i) {
  auto* p = (OutPin*)pin;
  if (!p || i < 0 || size_t(i) >= p->outs.size()) return -1;
  return int(p->outs[size_t(i)].dims.size());
}

__attribute__((visibility("default")))
const int64_t* ptpu_outputs_pin_dims(void* pin, int i) {
  auto* p = (OutPin*)pin;
  if (!p || i < 0 || size_t(i) >= p->outs.size()) return nullptr;
  return p->outs[size_t(i)].dims.data();
}

// Release a pin: tensor storage frees now; the holder itself recycles
// through the bounded free list (pred.outpin). Safe on any thread —
// the serving layer calls it from net event threads as the flush-
// completion signal fires.
__attribute__((visibility("default")))
void ptpu_outputs_pin_release(void* pin) {
  auto* p = (OutPin*)pin;
  if (!p) return;
  p->outs.clear();
  {
    ptpu::MutexLock l(g_outpin_mu);
    if (g_outpin_free.size() < kOutPinPoolCap) {
      g_outpin_free.emplace_back(p);
      return;
    }
  }
  delete p;  // pool full
}

/* Topology-aware pool creation (ISSUE 17c): bind the CREATING thread
 * to `node`'s CPU set before spawning — worker threads inherit the
 * creator's affinity mask — then restore it. node < 0, a single-node
 * box, or PTPU_TOPO=0 degrade to plain creation with no affinity
 * syscalls at all (the ptpu_topo.h probe gate). */
__attribute__((visibility("default")))
void* ptpu_workpool_create_bound(int threads, int node) {
  ptpu::topo::BindCurrentThreadToNode(node);
  WorkPool* p = new WorkPool(threads > 0 ? threads - 1 : 0);
  ptpu::topo::UnbindCurrentThread();
  return p;
}

}  // extern "C"
