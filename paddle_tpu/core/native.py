"""ctypes bridge to the native runtime (csrc/ptpu_runtime.cc).

The reference binds C++ via pybind11 (`fluid/pybind/pybind.cc:459`);
pybind11 isn't in this image, so the native core exposes a flat C ABI and
this module is the binding layer. In a checkout the three shipping
libraries are built on first use THROUGH `csrc/Makefile` (`_make`, the
one build recipe — the reference's analogue: `utils/cpp_extension` JIT
builds): make rebuilds what is older than its sources, so a loaded
library is never stale, and nothing depends on a prebuilt one lying in
the tree. An installed package (no `csrc/`) loads the .so it ships.

Everything degrades gracefully: if no C++ toolchain exists, `available()`
is False and pure-Python fallbacks take over (profiler no-ops, queue →
`queue.Queue`, arena → numpy allocation).
"""
from __future__ import annotations

import contextlib
import ctypes
import fcntl
import os
import queue as _pyqueue
import subprocess
import threading
from typing import Optional

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_LOCK = threading.Lock()

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SO_PATH = os.path.join(_PKG_DIR, "_native.so")
_CSRC = os.path.join(os.path.dirname(_PKG_DIR), "csrc")


@contextlib.contextmanager
def build_lock(name: str):
    """One `make` of `name` in csrc/ at a time across processes.

    Concurrent builds have two guards, each for its own hazard. RENAME
    (csrc/Makefile): a shipping .so or the demo appears under its name
    complete or not at all, so a process may dlopen or exec it while
    another rebuilds it — that protects every READER and needs no lock.
    This LOCK is about the recipes: it spares N processes that reach a
    cold tree together (pytest-xdist workers, bench legs) N identical
    compiles, and it keeps the `make` targets that RUN what they build
    (selftest, sancheck, schedck: fixed /tmp files, shared binaries;
    tests/_csrc.py) from running twice at once. A lock dies with its
    holder, so a killed build never wedges the next."""
    with open(os.path.join(_CSRC, f".{name}.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        yield


def _make(so_path: str) -> bool:
    """Bring one shipping .so up to date through csrc/Makefile; False when
    there is no checkout or no toolchain, or the build fails (the caller
    then loads what exists, or reports the library unavailable)."""
    if not os.path.exists(os.path.join(_CSRC, "Makefile")):
        return False
    name = os.path.basename(so_path)
    target = os.path.join("..", "paddle_tpu", name)
    try:
        with build_lock(name):
            subprocess.run(["make", "-C", _CSRC, target], check=True,
                           capture_output=True, timeout=900)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        if not _make(_SO_PATH) and not os.path.exists(_SO_PATH):
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            return None
        # signatures
        lib.ptpu_last_error.restype = ctypes.c_char_p
        lib.ptpu_version.restype = ctypes.c_char_p
        lib.ptpu_arena_create.restype = ctypes.c_void_p
        lib.ptpu_arena_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
        lib.ptpu_arena_destroy.argtypes = [ctypes.c_void_p]
        lib.ptpu_arena_alloc.restype = ctypes.c_void_p
        lib.ptpu_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ptpu_arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        for f in ("ptpu_arena_in_use", "ptpu_arena_peak",
                  "ptpu_arena_reserved"):
            getattr(lib, f).restype = ctypes.c_uint64
            getattr(lib, f).argtypes = [ctypes.c_void_p]
        lib.ptpu_queue_create.restype = ctypes.c_void_p
        lib.ptpu_queue_create.argtypes = [ctypes.c_uint64]
        lib.ptpu_queue_destroy.argtypes = [ctypes.c_void_p]
        lib.ptpu_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_int]
        lib.ptpu_queue_pop.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_int64),
                                       ctypes.c_int]
        lib.ptpu_queue_close.argtypes = [ctypes.c_void_p]
        lib.ptpu_queue_size.restype = ctypes.c_uint64
        lib.ptpu_queue_size.argtypes = [ctypes.c_void_p]
        lib.ptpu_profiler_now_us.restype = ctypes.c_int64
        lib.ptpu_profiler_record.argtypes = [ctypes.c_char_p,
                                             ctypes.c_int64, ctypes.c_int64]
        lib.ptpu_profiler_dump.argtypes = [ctypes.c_char_p]
        lib.ptpu_profiler_count.restype = ctypes.c_uint64
        lib.ptpu_stat_add.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.ptpu_stat_get.restype = ctypes.c_int64
        lib.ptpu_stat_get.argtypes = [ctypes.c_char_p]
        lib.ptpu_stat_reset.argtypes = [ctypes.c_char_p]
        lib.ptpu_aes_ctr_xcrypt.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_uint64]
        lib.ptpu_feed_count.restype = ctypes.c_int
        lib.ptpu_feed_count.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64)]
        lib.ptpu_feed_parse.restype = ctypes.c_int
        lib.ptpu_feed_parse.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64)]
        lib.ptpu_profiler_enabled.restype = ctypes.c_int
        _LIB = lib
        return _LIB


def available() -> bool:
    return _load() is not None


def lib() -> ctypes.CDLL:
    l = _load()
    if l is None:
        raise RuntimeError("native runtime unavailable (no _native.so and "
                           "no g++ to build it)")
    return l


class Arena:
    """Best-fit host staging arena (reference:
    auto_growth_best_fit_allocator.cc). `buffer(nbytes)` returns a numpy
    uint8 view of arena memory; `release(buf)` returns it to the pool."""

    def __init__(self, chunk_size: int = 64 << 20, alignment: int = 64):
        import numpy as np
        self._np = np
        self._l = lib()
        self._h = self._l.ptpu_arena_create(chunk_size, alignment)
        self._live = {}

    def buffer(self, nbytes: int):
        p = self._l.ptpu_arena_alloc(self._h, nbytes)
        if not p:
            raise MemoryError(self._l.ptpu_last_error().decode())
        buf = (ctypes.c_uint8 * nbytes).from_address(p)
        arr = self._np.frombuffer(buf, dtype=self._np.uint8)
        # keyed by base address (== arr.ctypes.data for the returned view)
        self._live[int(p)] = buf
        return arr

    def release(self, arr) -> None:
        p = int(arr.ctypes.data)
        if p not in self._live:
            raise ValueError("not an arena buffer (release the object "
                             "returned by buffer(), not a slice)")
        del self._live[p]
        self._l.ptpu_arena_free(self._h, ctypes.c_void_p(p))

    @property
    def in_use(self) -> int:
        return int(self._l.ptpu_arena_in_use(self._h))

    @property
    def peak(self) -> int:
        return int(self._l.ptpu_arena_peak(self._h))

    @property
    def reserved(self) -> int:
        return int(self._l.ptpu_arena_reserved(self._h))

    def __del__(self):
        try:
            self._l.ptpu_arena_destroy(self._h)
        except Exception:
            pass


class NativeQueue:
    """Bounded blocking queue whose synchronization lives in C++
    (reference: `lod_tensor_blocking_queue.h` feeding `read_op`). Objects
    are kept in a Python-side registry keyed by monotonically increasing
    tokens; C++ carries only the tokens, so arbitrary batches (numpy trees)
    flow through without serialization."""

    _CLOSED = object()

    def __init__(self, capacity: int):
        self._l = lib()
        self._h = self._l.ptpu_queue_create(capacity)
        self._objs = {}
        self._next = 0
        self._mu = threading.Lock()

    def push(self, obj, timeout_ms: int = -1) -> bool:
        with self._mu:
            tok = self._next
            self._next += 1
            self._objs[tok] = obj
        rc = self._l.ptpu_queue_push(self._h, tok, timeout_ms)
        if rc != 0:
            with self._mu:
                self._objs.pop(tok, None)
            if rc == -1:
                raise RuntimeError("queue closed")
            return False
        return True

    def pop(self, timeout_ms: int = -1):
        out = ctypes.c_int64()
        rc = self._l.ptpu_queue_pop(self._h, ctypes.byref(out), timeout_ms)
        if rc == -1:
            return self._CLOSED
        if rc == -2:
            return None
        with self._mu:
            return self._objs.pop(out.value)

    @property
    def closed_sentinel(self):
        return self._CLOSED

    def close(self):
        self._l.ptpu_queue_close(self._h)

    def __len__(self):
        return int(self._l.ptpu_queue_size(self._h))

    def __del__(self):
        try:
            self._l.ptpu_queue_destroy(self._h)
        except Exception:
            pass


class PyQueueFallback:
    """Pure-Python stand-in with the NativeQueue interface."""

    _CLOSED = object()

    def __init__(self, capacity: int):
        self._q = _pyqueue.Queue(maxsize=capacity)
        self._closed = False

    def push(self, obj, timeout_ms: int = -1) -> bool:
        if self._closed:
            raise RuntimeError("queue closed")
        try:
            self._q.put(obj, timeout=None if timeout_ms < 0
                        else timeout_ms / 1000)
            return True
        except _pyqueue.Full:
            return False

    def pop(self, timeout_ms: int = -1):
        while True:
            try:
                return self._q.get(
                    timeout=0.05 if timeout_ms < 0 else timeout_ms / 1000)
            except _pyqueue.Empty:
                if self._closed:
                    return self._CLOSED
                if timeout_ms >= 0:
                    return None

    @property
    def closed_sentinel(self):
        return self._CLOSED

    def close(self):
        self._closed = True

    def __len__(self):
        return self._q.qsize()


def make_queue(capacity: int):
    return NativeQueue(capacity) if available() else \
        PyQueueFallback(capacity)


def aes_ctr_xcrypt(key: bytes, iv: bytes, data: bytes) -> bytes:
    """AES-128-CTR (encrypt == decrypt). Pure-python fallback is
    intentionally absent — encrypted save requires the native lib, like the
    reference requires cryptopp (`framework/io/crypto/aes_cipher.cc`)."""
    if len(key) != 16 or len(iv) != 16:
        raise ValueError("key and iv must be 16 bytes (AES-128-CTR)")
    out = ctypes.create_string_buffer(len(data))
    lib().ptpu_aes_ctr_xcrypt(key, iv, data, out, len(data))
    return out.raw


# ---------------------------------------------------------------------------
# Native PS shard table binding (csrc/ptpu_ps_table.cc — the C-hosted
# parameter-server hot path). The table service (distributed/ps/table.py)
# routes its per-row gather/scatter-update work here; the numpy _Shard
# stays as the parity fallback when the .so is absent.
# ---------------------------------------------------------------------------

# PTPU_PS_SO points a process at an alternate build — the benches'
# interleaved old-vs-new A/B legs run each side in a subprocess with
# this set (ISSUE 17 cycles-per-request methodology)
_PS_SO = os.environ.get("PTPU_PS_SO",
                        os.path.join(_PKG_DIR, "_native_ps.so"))
_PS_LIB: Optional[ctypes.CDLL] = None
_PS_TRIED = False
_PS_LOCK = threading.Lock()

PS_OPTIMIZERS = {"sgd": 0, "adagrad": 1, "adam": 2}


def _ps_load() -> Optional[ctypes.CDLL]:
    global _PS_LIB, _PS_TRIED
    with _PS_LOCK:
        if _PS_TRIED:
            return _PS_LIB
        _PS_TRIED = True
        # an override names somebody else's build (an A/B leg's older
        # .so): load it as it is. The `except AttributeError` arms below
        # exist for exactly those — this tree's own build has every symbol
        if "PTPU_PS_SO" not in os.environ:
            _make(_PS_SO)
        try:
            lib = ctypes.CDLL(_PS_SO)
        except OSError:
            return None
        c = ctypes
        try:
            lib.ptpu_ps_last_error.restype = c.c_char_p
            lib.ptpu_ps_version.restype = c.c_char_p
            lib.ptpu_ps_table_create.restype = c.c_void_p
            lib.ptpu_ps_table_create.argtypes = [
                c.c_int64, c.c_int64, c.c_int, c.c_float, c.c_float,
                c.c_float, c.c_float]
            lib.ptpu_ps_table_destroy.argtypes = [c.c_void_p]
            lib.ptpu_ps_table_data.restype = c.POINTER(c.c_float)
            lib.ptpu_ps_table_data.argtypes = [c.c_void_p]
            for f in ("ptpu_ps_table_rows", "ptpu_ps_table_dim"):
                getattr(lib, f).restype = c.c_int64
                getattr(lib, f).argtypes = [c.c_void_p]
            lib.ptpu_ps_table_bytes.restype = c.c_uint64
            lib.ptpu_ps_table_bytes.argtypes = [c.c_void_p]
            lib.ptpu_ps_table_pull.argtypes = [
                c.c_void_p, c.POINTER(c.c_int64), c.c_int64,
                c.POINTER(c.c_float)]
            lib.ptpu_ps_table_push.argtypes = [
                c.c_void_p, c.POINTER(c.c_int64), c.c_int64,
                c.POINTER(c.c_float)]
        except AttributeError:
            # an override .so without the base table ABI: unavailable
            return None
        try:
            lib.ptpu_ps_table_stats_json.restype = c.c_char_p
            lib.ptpu_ps_table_stats_json.argtypes = [c.c_void_p]
            lib.ptpu_ps_table_stats_reset.argtypes = [c.c_void_p]
            lib.ptpu_ps_table_note_pull.argtypes = [c.c_void_p,
                                                    c.c_int64]
            lib._ptpu_has_ps_stats = True
        except AttributeError:   # older override .so: stats degrade
            lib._ptpu_has_ps_stats = False
        try:
            lib.ptpu_ps_server_last_error.restype = c.c_char_p
            lib.ptpu_ps_server_start.restype = c.c_void_p
            lib.ptpu_ps_server_start.argtypes = [c.c_int, c.c_char_p,
                                                 c.c_int, c.c_int]
            lib.ptpu_ps_server_port.restype = c.c_int
            lib.ptpu_ps_server_port.argtypes = [c.c_void_p]
            lib.ptpu_ps_server_register.argtypes = [
                c.c_void_p, c.c_char_p, c.c_void_p, c.c_int64]
            lib.ptpu_ps_server_stop.argtypes = [c.c_void_p]
            lib._ptpu_has_ps_server = True
        except AttributeError:
            lib._ptpu_has_ps_server = False
        try:
            lib.ptpu_ps_server_stats_json.restype = c.c_char_p
            lib.ptpu_ps_server_stats_json.argtypes = [c.c_void_p]
            lib.ptpu_ps_server_stats_reset.argtypes = [c.c_void_p]
            lib._ptpu_has_ps_server_stats = True
        except AttributeError:
            lib._ptpu_has_ps_server_stats = False
        try:
            # telemetry HTTP + request tracing ABI (r10)
            lib.ptpu_ps_server_start2.restype = c.c_void_p
            lib.ptpu_ps_server_start2.argtypes = [
                c.c_int, c.c_char_p, c.c_int, c.c_int, c.c_int]
            lib.ptpu_ps_server_http_port.restype = c.c_int
            lib.ptpu_ps_server_http_port.argtypes = [c.c_void_p]
            lib.ptpu_ps_server_prom_text.restype = c.c_char_p
            lib.ptpu_ps_server_prom_text.argtypes = [c.c_void_p]
            lib.ptpu_trace_set.argtypes = [c.c_int64, c.c_int64]
            lib.ptpu_trace_json.restype = c.c_char_p
            lib.ptpu_trace_json.argtypes = [c.c_int64]
            lib._ptpu_has_ps_http = True
        except AttributeError:   # older override .so: telemetry off
            lib._ptpu_has_ps_http = False
        try:
            # raw-frame capture ring ABI (production drills)
            lib.ptpu_capture_set.argtypes = [c.c_int64]
            lib.ptpu_capture_json.restype = c.c_char_p
            lib.ptpu_capture_json.argtypes = [c.c_int64]
            lib.ptpu_capture_save.restype = c.c_int
            lib.ptpu_capture_save.argtypes = [c.c_char_p]
            lib._ptpu_has_capture = True
        except AttributeError:   # older override .so: capture off
            lib._ptpu_has_capture = False
        try:
            # counter-conservation invariant gate (ISSUE 20): the C
            # evaluator over the same manifest profiler/stats.py twins
            lib.ptpu_invar_check_json.restype = c.c_char_p
            lib.ptpu_invar_check_json.argtypes = [c.c_char_p,
                                                  c.c_char_p]
            lib.ptpu_invar_manifest.restype = c.c_char_p
            lib.ptpu_invar_manifest.argtypes = []
            lib._ptpu_has_invar = True
        except AttributeError:   # older override .so: gate off
            lib._ptpu_has_invar = False
        _PS_LIB = lib
        return _PS_LIB


def ps_table_available() -> bool:
    return _ps_load() is not None


def ps_server_available() -> bool:
    l = _ps_load()
    return l is not None and l._ptpu_has_ps_server


class PsDataServer:
    """C-hosted PS data-plane server: a thread-per-connection TCP loop
    inside _native_ps.so that serves the wire.py fast pull/push frames
    for registered `NativePsTable` shards — Python never touches a hot
    frame (reference: the brpc worker threads of brpc_ps_server.cc).
    The Python TableService keeps the control plane and advertises this
    port over it."""

    def __init__(self, port: int, authkey: bytes,
                 loopback_only: bool = True,
                 http_port: Optional[int] = None):
        l = _ps_load()
        if l is None or not l._ptpu_has_ps_server:
            raise RuntimeError("native PS data-plane server unavailable")
        self._l = l
        self._tables = {}   # name -> NativePsTable (keep shards alive)
        has_http = getattr(l, "_ptpu_has_ps_http", False)
        if http_port is not None and not has_http:
            raise RuntimeError(
                "telemetry HTTP needs the r10 PS ABI (stale "
                "_native_ps.so: delete it and re-import)")
        if has_http:
            self._h = l.ptpu_ps_server_start2(
                port, authkey, len(authkey), 1 if loopback_only else 0,
                -1 if http_port is None else http_port)
        else:
            self._h = l.ptpu_ps_server_start(port, authkey,
                                             len(authkey),
                                             1 if loopback_only else 0)
        if not self._h:
            raise OSError(l.ptpu_ps_server_last_error().decode())
        self.port = int(l.ptpu_ps_server_port(self._h))
        # telemetry HTTP port (-1 disabled); PTPU_NET_HTTP forces it
        # on regardless of the http_port argument
        self.http_port = (int(l.ptpu_ps_server_http_port(self._h))
                          if has_http else -1)

    def prom_text(self) -> Optional[str]:
        """Prometheus exposition text (C-rendered; the GET /metrics
        bytes). None when the .so predates the r10 ABI."""
        if not getattr(self, "_h", None) or \
                not getattr(self._l, "_ptpu_has_ps_http", False):
            return None
        return self._l.ptpu_ps_server_prom_text(self._h).decode()

    def register(self, name: str, table: NativePsTable, lo: int):
        """Expose `table` as `name`; the server maps global ids by
        subtracting `lo` (the shard's first global row)."""
        self._l.ptpu_ps_server_register(self._h, name.encode(),
                                        table._h, lo)
        self._tables[name] = table

    def stats(self) -> Optional[dict]:
        """Wire + per-table stats snapshot of the C serve loop
        (`ptpu_ps_server_stats_json`): {"server": {...counters,
        pull_us/push_us histograms...}, "tables": {name: {"wire": ...,
        "table": storage counters}}}. None when the .so predates the
        stats ABI."""
        if not getattr(self, "_h", None) or \
                not self._l._ptpu_has_ps_server_stats:
            return None
        import json
        return json.loads(
            self._l.ptpu_ps_server_stats_json(self._h).decode())

    def stats_reset(self) -> None:
        if getattr(self, "_h", None) and \
                self._l._ptpu_has_ps_server_stats:
            self._l.ptpu_ps_server_stats_reset(self._h)

    def stop(self):
        if getattr(self, "_h", None):
            self._l.ptpu_ps_server_stop(self._h)
            self._h = None

    def __del__(self):
        try:
            self.stop()
        except Exception:   # interpreter teardown
            pass


class NativePsTable:
    """One C-hosted shard: `rows` x `dim` float32 weights plus the
    optimizer's per-row slots in one contiguous arena block. pull() is
    a bounds-checked gather (concurrent pulls run in parallel under a
    shared lock in C); push() coalesces duplicate ids then applies the
    server-side optimizer (sgd / adagrad / adam)."""

    def __init__(self, rows: int, dim: int, optimizer: str = "sgd",
                 lr: float = 0.1, beta1: float = 0.9,
                 beta2: float = 0.999, eps: float = 1e-8):
        import numpy as np
        self._np = np
        l = _ps_load()
        if l is None:
            raise RuntimeError("native PS table unavailable (no "
                               "_native_ps.so and no g++ to build it)")
        if optimizer not in PS_OPTIMIZERS:
            raise ValueError(f"unknown PS optimizer {optimizer!r}; "
                             f"expected one of {sorted(PS_OPTIMIZERS)}")
        self._l = l
        self.rows, self.dim = int(rows), int(dim)
        self._h = l.ptpu_ps_table_create(
            self.rows, self.dim, PS_OPTIMIZERS[optimizer], lr, beta1,
            beta2, eps)
        if not self._h:
            raise MemoryError(l.ptpu_ps_last_error().decode())

    @property
    def data(self):
        """numpy view of the weight block (rows, dim) — writable, used
        for seeded init and parity inspection."""
        ptr = self._l.ptpu_ps_table_data(self._h)
        return self._np.ctypeslib.as_array(
            ptr, shape=(self.rows, self.dim))

    @property
    def nbytes(self) -> int:
        return int(self._l.ptpu_ps_table_bytes(self._h))

    def pull_into(self, local_ids, out) -> None:
        """Gather rows[local_ids] into the preallocated float32 array
        `out` (n, dim) — the wire fast path hands the reply buffer's
        body view straight in, so the gather IS the serialization."""
        np, c = self._np, ctypes
        ids = np.ascontiguousarray(local_ids, np.int64)
        if out.dtype != np.float32 or not out.flags.c_contiguous:
            raise ValueError("pull_into needs a C-contiguous float32 out")
        if out.size != ids.size * self.dim:
            # the C gather writes ids.size*dim floats unconditionally —
            # a short buffer would be a heap overrun, not an exception
            raise ValueError(f"pull_into out size {out.size} != "
                             f"{ids.size} ids x dim {self.dim}")
        rc = self._l.ptpu_ps_table_pull(
            self._h, ids.ctypes.data_as(c.POINTER(c.c_int64)), ids.size,
            out.ctypes.data_as(c.POINTER(c.c_float)))
        if rc != 0:
            raise ValueError(self._l.ptpu_ps_last_error().decode())

    def pull(self, local_ids):
        np = self._np
        ids = np.ascontiguousarray(local_ids, np.int64)
        out = np.empty((ids.size, self.dim), np.float32)
        self.pull_into(ids, out)
        return out

    def push(self, local_ids, grads) -> None:
        np, c = self._np, ctypes
        ids = np.ascontiguousarray(local_ids, np.int64)
        g = np.ascontiguousarray(grads, np.float32)
        if g.size != ids.size * self.dim:
            raise ValueError(f"push grads size {g.size} != "
                             f"{ids.size} ids x dim {self.dim}")
        rc = self._l.ptpu_ps_table_push(
            self._h, ids.ctypes.data_as(c.POINTER(c.c_int64)), ids.size,
            g.ctypes.data_as(c.POINTER(c.c_float)))
        if rc != 0:
            raise ValueError(self._l.ptpu_ps_last_error().decode())

    def stats(self) -> Optional[dict]:
        """Storage-level counters (pull/push ops, rows, coalesced
        rows) — the same names the numpy fallback shard keeps, so
        native-vs-fallback snapshots are comparable. None when the .so
        predates the stats ABI."""
        if not getattr(self, "_h", None) or \
                not self._l._ptpu_has_ps_stats:
            return None
        import json
        return json.loads(
            self._l.ptpu_ps_table_stats_json(self._h).decode())

    def stats_reset(self) -> None:
        if getattr(self, "_h", None) and self._l._ptpu_has_ps_stats:
            self._l.ptpu_ps_table_stats_reset(self._h)

    def close(self):
        if getattr(self, "_h", None):
            self._l.ptpu_ps_table_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:   # interpreter teardown
            pass


# ---------------------------------------------------------------------------
# Native predictor binding (csrc/ptpu_predictor.cc — the no-Python C
# serving engine). This is the Python-side convenience wrapper over the
# same C ABI the Go binding and the pure-C demo use; tests keep their
# hand-rolled ctypes to exercise the raw ABI.
# ---------------------------------------------------------------------------

# PTPU_PREDICTOR_SO: same A/B-leg override as PTPU_PS_SO above
_PRED_SO = os.environ.get("PTPU_PREDICTOR_SO",
                          os.path.join(_PKG_DIR, "_native_predictor.so"))
_PRED_LIB: Optional[ctypes.CDLL] = None
_PRED_LOCK = threading.Lock()


def _predictor_lib() -> ctypes.CDLL:
    global _PRED_LIB
    with _PRED_LOCK:
        if _PRED_LIB is not None:
            return _PRED_LIB
        # same rule as _ps_load: an override is loaded as it is (the
        # `except AttributeError` arms below serve older A/B builds)
        if "PTPU_PREDICTOR_SO" not in os.environ:
            _make(_PRED_SO)
        lib = ctypes.CDLL(_PRED_SO)
        c = ctypes
        lib.ptpu_predictor_create.restype = c.c_void_p
        lib.ptpu_predictor_create.argtypes = [c.c_char_p, c.c_char_p,
                                              c.c_int]
        try:
            lib.ptpu_predictor_create_opts.restype = c.c_void_p
            lib.ptpu_predictor_create_opts.argtypes = [
                c.c_char_p, c.c_int64, c.c_int, c.c_char_p, c.c_int]
            lib.ptpu_workpool_create.restype = c.c_void_p
            lib.ptpu_workpool_create.argtypes = [c.c_int]
            lib.ptpu_workpool_destroy.argtypes = [c.c_void_p]
            lib.ptpu_predictor_set_pool.argtypes = [c.c_void_p,
                                                    c.c_void_p]
            lib.ptpu_predictor_input_ndim.argtypes = [c.c_void_p,
                                                      c.c_int]
            lib.ptpu_predictor_input_dims.restype = c.POINTER(c.c_int64)
            lib.ptpu_predictor_input_dims.argtypes = [c.c_void_p,
                                                      c.c_int]
            lib.ptpu_predictor_input_dtype.argtypes = [c.c_void_p,
                                                       c.c_int]
            lib.ptpu_predictor_dynamic_fallbacks.restype = c.c_int64
            lib.ptpu_predictor_dynamic_fallbacks.argtypes = [c.c_void_p]
            lib.ptpu_serving_start.restype = c.c_void_p
            lib.ptpu_serving_start.argtypes = [
                c.c_char_p, c.c_int, c.c_char_p, c.c_int, c.c_int,
                c.c_int64, c.c_int, c.c_int, c.c_int, c.c_char_p,
                c.c_int]
            lib.ptpu_serving_port.argtypes = [c.c_void_p]
            lib.ptpu_serving_config_json.restype = c.c_char_p
            lib.ptpu_serving_config_json.argtypes = [c.c_void_p]
            lib.ptpu_serving_stats_json.restype = c.c_char_p
            lib.ptpu_serving_stats_json.argtypes = [c.c_void_p]
            lib.ptpu_serving_stats_reset.argtypes = [c.c_void_p]
            lib.ptpu_serving_stop.argtypes = [c.c_void_p]
            lib._ptpu_has_serving = True
        except AttributeError:   # older override .so: serving degrades
            lib._ptpu_has_serving = False
        lib.ptpu_predictor_destroy.argtypes = [c.c_void_p]
        lib.ptpu_predictor_num_inputs.argtypes = [c.c_void_p]
        lib.ptpu_predictor_num_outputs.argtypes = [c.c_void_p]
        lib.ptpu_predictor_num_nodes.argtypes = [c.c_void_p]
        lib.ptpu_predictor_fused_nodes.argtypes = [c.c_void_p]
        lib.ptpu_predictor_arena_bytes.restype = c.c_int64
        lib.ptpu_predictor_arena_bytes.argtypes = [c.c_void_p]
        lib.ptpu_predictor_input_name.restype = c.c_char_p
        lib.ptpu_predictor_input_name.argtypes = [c.c_void_p, c.c_int]
        lib.ptpu_predictor_set_input.argtypes = [
            c.c_void_p, c.c_char_p, c.POINTER(c.c_float),
            c.POINTER(c.c_int64), c.c_int, c.c_char_p, c.c_int]
        lib.ptpu_predictor_set_input_i32.argtypes = [
            c.c_void_p, c.c_char_p, c.POINTER(c.c_int32),
            c.POINTER(c.c_int64), c.c_int, c.c_char_p, c.c_int]
        lib.ptpu_predictor_set_input_i64.argtypes = [
            c.c_void_p, c.c_char_p, c.POINTER(c.c_int64),
            c.POINTER(c.c_int64), c.c_int, c.c_char_p, c.c_int]
        lib.ptpu_predictor_run.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
        lib.ptpu_predictor_output_ndim.argtypes = [c.c_void_p, c.c_int]
        lib.ptpu_predictor_output_dims.restype = c.POINTER(c.c_int64)
        lib.ptpu_predictor_output_dims.argtypes = [c.c_void_p, c.c_int]
        lib.ptpu_predictor_output_data.restype = c.POINTER(c.c_float)
        lib.ptpu_predictor_output_data.argtypes = [c.c_void_p, c.c_int]
        try:
            # KV-cached decode ABI (r9) — absent from stale .so builds
            lib.ptpu_predictor_kv_plan.argtypes = [
                c.c_void_p, c.c_int, c.c_char_p, c.c_int]
            lib.ptpu_predictor_kv_sessions.argtypes = [c.c_void_p]
            lib.ptpu_predictor_kv_open.argtypes = [c.c_void_p]
            lib.ptpu_predictor_kv_close.argtypes = [c.c_void_p, c.c_int]
            lib.ptpu_predictor_kv_len.restype = c.c_int64
            lib.ptpu_predictor_kv_len.argtypes = [c.c_void_p, c.c_int]
            lib.ptpu_predictor_decode_step.argtypes = [
                c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_int64),
                c.c_int, c.c_char_p, c.c_int]
            lib.ptpu_serving_start2.restype = c.c_void_p
            lib.ptpu_serving_start2.argtypes = [
                c.c_char_p, c.c_char_p, c.c_int, c.c_char_p, c.c_int,
                c.c_int, c.c_int64, c.c_int, c.c_int, c.c_int, c.c_int,
                c.c_char_p, c.c_int]
            lib._ptpu_has_decode = True
        except AttributeError:   # older override .so: decode degrades
            lib._ptpu_has_decode = False
        try:
            # paged KV pool ABI (r12) — absent from stale .so builds
            lib.ptpu_kvpool_create.restype = c.c_void_p
            lib.ptpu_kvpool_create.argtypes = [
                c.c_int64, c.c_int, c.c_int, c.c_int, c.c_char_p,
                c.c_int]
            lib.ptpu_kvpool_destroy.argtypes = [c.c_void_p]
            lib.ptpu_predictor_kv_attach.argtypes = [
                c.c_void_p, c.c_void_p, c.c_char_p, c.c_int]
            lib.ptpu_predictor_kv_direct.argtypes = [c.c_void_p]
            lib.ptpu_kvpool_open.argtypes = [c.c_void_p]
            lib.ptpu_kvpool_fork.argtypes = [c.c_void_p, c.c_int]
            lib.ptpu_kvpool_close.argtypes = [c.c_void_p, c.c_int]
            lib.ptpu_kvpool_len.restype = c.c_int64
            lib.ptpu_kvpool_len.argtypes = [c.c_void_p, c.c_int]
            lib.ptpu_kvpool_adopt.restype = c.c_int64
            lib.ptpu_kvpool_adopt.argtypes = [
                c.c_void_p, c.c_int, c.POINTER(c.c_int64), c.c_int64]
            lib.ptpu_kvpool_publish.argtypes = [
                c.c_void_p, c.c_int, c.POINTER(c.c_int64), c.c_int64]
            lib.ptpu_kvpool_stats_json.restype = c.c_char_p
            lib.ptpu_kvpool_stats_json.argtypes = [c.c_void_p]
            lib._ptpu_has_kvpool = True
        except AttributeError:   # older override .so: paging degrades
            lib._ptpu_has_kvpool = False
        try:
            # telemetry HTTP + two-phase drain + tracing ABI (r10)
            lib.ptpu_serving_start3.restype = c.c_void_p
            lib.ptpu_serving_start3.argtypes = [
                c.c_char_p, c.c_char_p, c.c_int, c.c_char_p, c.c_int,
                c.c_int, c.c_int64, c.c_int, c.c_int, c.c_int, c.c_int,
                c.c_int, c.c_char_p, c.c_int]
            lib.ptpu_serving_http_port.restype = c.c_int
            lib.ptpu_serving_http_port.argtypes = [c.c_void_p]
            lib.ptpu_serving_drain_begin.argtypes = [c.c_void_p]
            lib.ptpu_serving_prom_text.restype = c.c_char_p
            lib.ptpu_serving_prom_text.argtypes = [c.c_void_p]
            lib.ptpu_trace_set.argtypes = [c.c_int64, c.c_int64]
            lib.ptpu_trace_json.restype = c.c_char_p
            lib.ptpu_trace_json.argtypes = [c.c_int64]
            lib._ptpu_has_http = True
        except AttributeError:   # older override .so: telemetry off
            lib._ptpu_has_http = False
        try:
            # raw-frame capture ring ABI (production drills)
            lib.ptpu_capture_set.argtypes = [c.c_int64]
            lib.ptpu_capture_json.restype = c.c_char_p
            lib.ptpu_capture_json.argtypes = [c.c_int64]
            lib.ptpu_capture_save.restype = c.c_int
            lib.ptpu_capture_save.argtypes = [c.c_char_p]
            lib._ptpu_has_capture = True
        except AttributeError:   # older override .so: capture off
            lib._ptpu_has_capture = False
        try:
            # speculative decoding ABI (r13) — width-k verify steps,
            # COW-safe session trims, draft/verify server start
            lib.ptpu_predictor_kv_width.argtypes = [c.c_void_p]
            lib.ptpu_predictor_kv_trim.argtypes = [
                c.c_void_p, c.c_int, c.c_int64, c.c_char_p, c.c_int]
            lib.ptpu_kvpool_trim.argtypes = [
                c.c_void_p, c.c_int, c.c_int64]
            lib.ptpu_serving_start4.restype = c.c_void_p
            lib.ptpu_serving_start4.argtypes = [
                c.c_char_p, c.c_char_p, c.c_char_p, c.c_char_p,
                c.c_int, c.c_char_p, c.c_int, c.c_int, c.c_int64,
                c.c_int, c.c_int, c.c_int, c.c_int, c.c_int,
                c.c_char_p, c.c_int]
            lib._ptpu_has_spec = True
        except AttributeError:   # older override .so: spec degrades
            lib._ptpu_has_spec = False
        try:
            lib.ptpu_predictor_stats_json.restype = c.c_char_p
            lib.ptpu_predictor_stats_json.argtypes = [c.c_void_p]
            lib.ptpu_predictor_stats_reset.argtypes = [c.c_void_p]
            lib.ptpu_predictor_set_profiler.argtypes = [c.c_void_p,
                                                        c.c_void_p]
            lib._ptpu_has_pred_stats = True
        except AttributeError:   # older override .so: stats degrade
            lib._ptpu_has_pred_stats = False
        try:
            # persisted kernel autotuning ABI (r15) — process-global
            lib.ptpu_tune_stats_json.restype = c.c_char_p
            lib.ptpu_tune_stats_json.argtypes = []
            lib.ptpu_tune_save.restype = c.c_int
            lib.ptpu_tune_save.argtypes = [c.c_char_p]
            lib.ptpu_tune_load.restype = c.c_int
            lib.ptpu_tune_load.argtypes = [c.c_char_p]
            lib.ptpu_tune_clear.argtypes = []
            lib._ptpu_has_tune = True
        except AttributeError:   # older override .so: autotune off
            lib._ptpu_has_tune = False
        try:
            # KV tiering + session hibernation ABI (r19)
            lib.ptpu_kvpool_spill_attach.restype = c.c_int
            lib.ptpu_kvpool_spill_attach.argtypes = [
                c.c_void_p, c.c_char_p, c.c_int64, c.c_char_p, c.c_int]
            lib.ptpu_kvpool_hibernate.restype = c.c_int64
            lib.ptpu_kvpool_hibernate.argtypes = [
                c.c_void_p, c.c_int, c.POINTER(c.c_uint8), c.c_int64,
                c.c_char_p, c.c_int]
            lib.ptpu_kvpool_restore.restype = c.c_int
            lib.ptpu_kvpool_restore.argtypes = [
                c.c_void_p, c.POINTER(c.c_uint8), c.c_int64,
                c.c_char_p, c.c_int]
            lib.ptpu_kvpool_hibernate_drop.argtypes = [
                c.c_void_p, c.POINTER(c.c_uint8), c.c_int64]
            lib.ptpu_kvpool_hibernated.restype = c.c_int64
            lib.ptpu_kvpool_hibernated.argtypes = [c.c_void_p]
            lib.ptpu_kvpool_prefix_save.restype = c.c_int64
            lib.ptpu_kvpool_prefix_save.argtypes = [
                c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]
            lib.ptpu_kvpool_prefix_load.restype = c.c_int64
            lib.ptpu_kvpool_prefix_load.argtypes = [
                c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]
            lib._ptpu_has_spill = True
        except AttributeError:   # older override .so: tiering off
            lib._ptpu_has_spill = False
        try:
            # counter-conservation invariant gate (ISSUE 20): the C
            # evaluator over the same manifest profiler/stats.py twins
            lib.ptpu_invar_check_json.restype = c.c_char_p
            lib.ptpu_invar_check_json.argtypes = [c.c_char_p,
                                                  c.c_char_p]
            lib.ptpu_invar_manifest.restype = c.c_char_p
            lib.ptpu_invar_manifest.argtypes = []
            lib._ptpu_has_invar = True
        except AttributeError:   # older override .so: gate off
            lib._ptpu_has_invar = False
        # Wire the host profiler (csrc/ptpu_runtime.cc, a separate .so)
        # into the predictor: per-op RecordEvent spans when profiling
        # is on, so serving runs land in the same chrome trace as
        # training ranks (profiler/timeline.py merges them).
        if lib._ptpu_has_pred_stats and available():
            rl = _load()
            lib.ptpu_predictor_set_profiler(
                c.cast(rl.ptpu_profiler_record, c.c_void_p),
                c.cast(rl.ptpu_profiler_enabled, c.c_void_p))
        _PRED_LIB = lib
        return lib


class NativePredictor:
    """One loaded artifact. Thread-compatible: one instance per thread.

    `threads` > 0 gives the instance a PRIVATE worker sub-pool so
    concurrent instances scale instead of serializing on the shared
    pool's dispatch mutex; `batch_override` > 0 re-plans the artifact
    for that leading (batch) dim — the serving bucket ladder."""

    def __init__(self, model_path: str, batch_override: int = 0,
                 threads: int = 0):
        import numpy as np  # local: keep module import light
        self._np = np
        self._lib = _predictor_lib()
        self._err = ctypes.create_string_buffer(512)
        if (batch_override or threads) and \
                not getattr(self._lib, "_ptpu_has_serving", False):
            raise RuntimeError(
                "batch_override/threads need the serving-era ABI "
                "(stale _native_predictor.so: delete it and re-import)")
        if batch_override or threads:
            self._h = self._lib.ptpu_predictor_create_opts(
                model_path.encode(), batch_override, threads,
                self._err, 512)
        else:
            self._h = self._lib.ptpu_predictor_create(
                model_path.encode(), self._err, 512)
        if not self._h:
            raise RuntimeError("ptpu_predictor_create: " +
                               self._err.value.decode())

    def close(self):
        if self._h:
            self._lib.ptpu_predictor_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:   # interpreter teardown
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _handle(self):
        # a NULL handle would segfault inside the C library; fail here
        if self._h is None:
            raise RuntimeError("NativePredictor is closed")
        return self._h

    # load-time optimization introspection
    @property
    def num_nodes(self) -> int:
        return self._lib.ptpu_predictor_num_nodes(self._handle())

    @property
    def fused_nodes(self) -> int:
        return self._lib.ptpu_predictor_fused_nodes(self._handle())

    @property
    def arena_bytes(self) -> int:
        """Planned serving arena size; 0 when shapes were dynamic and
        the engine fell back to per-tensor allocation."""
        return self._lib.ptpu_predictor_arena_bytes(self._handle())

    def input_name(self, i: int = 0) -> str:
        return self._lib.ptpu_predictor_input_name(self._handle(),
                                                   i).decode()

    def input_signature(self, i: int = 0):
        """(name, onnx_dtype_code, dims) of input i — dims reflect a
        batch_override. Needs the serving-era ABI; None otherwise."""
        if not getattr(self._lib, "_ptpu_has_serving", False):
            return None
        h = self._handle()
        nd = self._lib.ptpu_predictor_input_ndim(h, i)
        dims = self._lib.ptpu_predictor_input_dims(h, i)
        return (self.input_name(i),
                int(self._lib.ptpu_predictor_input_dtype(h, i)),
                [dims[k] for k in range(nd)] if nd > 0 else [])

    @property
    def dynamic_fallbacks(self) -> int:
        """Runs since load/reset that missed the planned-arena
        zero-alloc path (also in stats()['dynamic_shape_fallback'])."""
        if not getattr(self._lib, "_ptpu_has_serving", False):
            return -1
        return int(self._lib.ptpu_predictor_dynamic_fallbacks(
            self._handle()))

    def set_input(self, name: str, arr) -> None:
        np = self._np
        c = ctypes
        arr = np.ascontiguousarray(arr)
        dims = (c.c_int64 * arr.ndim)(*arr.shape)
        if arr.dtype == np.float32:
            rc = self._lib.ptpu_predictor_set_input(
                self._handle(), name.encode(),
                arr.ctypes.data_as(c.POINTER(c.c_float)), dims, arr.ndim,
                self._err, 512)
        elif arr.dtype == np.int32:
            rc = self._lib.ptpu_predictor_set_input_i32(
                self._handle(), name.encode(),
                arr.ctypes.data_as(c.POINTER(c.c_int32)), dims, arr.ndim,
                self._err, 512)
        elif arr.dtype == np.int64:
            rc = self._lib.ptpu_predictor_set_input_i64(
                self._handle(), name.encode(),
                arr.ctypes.data_as(c.POINTER(c.c_int64)), dims, arr.ndim,
                self._err, 512)
        else:
            raise TypeError(f"unsupported input dtype {arr.dtype}")
        if rc != 0:
            raise RuntimeError("set_input: " + self._err.value.decode())

    def run(self) -> None:
        if self._lib.ptpu_predictor_run(self._handle(), self._err, 512) != 0:
            raise RuntimeError("run: " + self._err.value.decode())

    def stats(self) -> Optional[dict]:
        """Serving stats since load/reset: {"runs", "total_run_us",
        "run_us": log2-histogram, "ops": {op: {"calls", "time_us",
        "bytes"}}}. Always-on in the C engine; None when the .so
        predates the stats ABI."""
        if not self._lib._ptpu_has_pred_stats:
            return None
        import json
        return json.loads(
            self._lib.ptpu_predictor_stats_json(self._handle()).decode())

    def stats_reset(self) -> None:
        if self._lib._ptpu_has_pred_stats:
            self._lib.ptpu_predictor_stats_reset(self._handle())

    # ---- KV-cached decode (r9) ----
    def _need_decode(self):
        if not getattr(self._lib, "_ptpu_has_decode", False):
            raise RuntimeError(
                "KV decode needs the r9 ABI (stale _native_predictor.so:"
                " delete it and re-import)")

    def kv_plan(self, sessions: int) -> None:
        """Validate the decode-artifact convention and allocate the
        per-session KV arena (see models.gpt.export_gpt_decode)."""
        self._need_decode()
        if self._lib.ptpu_predictor_kv_plan(self._handle(), sessions,
                                            self._err, 512) != 0:
            raise RuntimeError("kv_plan: " + self._err.value.decode())

    def kv_open(self) -> int:
        """Free session slot id, or -1 when every slot is busy."""
        self._need_decode()
        return int(self._lib.ptpu_predictor_kv_open(self._handle()))

    def kv_close(self, sid: int) -> None:
        self._need_decode()
        self._lib.ptpu_predictor_kv_close(self._handle(), sid)

    def kv_len(self, sid: int) -> int:
        self._need_decode()
        return int(self._lib.ptpu_predictor_kv_len(self._handle(), sid))

    def kv_width(self) -> int:
        """Step width W baked into the artifact's ids input [B, W]: 1
        for the classic autoregressive step, k+1 for a
        speculative-verify export. 0 before kv_plan/kv_attach."""
        if not getattr(self._lib, "_ptpu_has_spec", False):
            return 1
        return int(self._lib.ptpu_predictor_kv_width(self._handle()))

    def kv_trim(self, sid: int, new_len: int) -> None:
        """Truncate a session to ``new_len`` positions — the
        speculative-decoding rollback. Paged sessions release page
        groups past the new tail COW-safely (shared groups are
        unreferenced, never mutated; published prefix pages and fork
        siblings keep their bytes). No-op when new_len >= len."""
        self._need_decode()
        if not getattr(self._lib, "_ptpu_has_spec", False):
            raise RuntimeError(
                "kv_trim needs the r13 ABI (stale "
                "_native_predictor.so: delete it and re-import)")
        if self._lib.ptpu_predictor_kv_trim(self._handle(), sid,
                                            new_len, self._err,
                                            512) != 0:
            raise RuntimeError("kv_trim: " + self._err.value.decode())

    def decode_step(self, sids, tokens):
        """One batched decode step: feed tokens[r*W .. r*W+W-1] into
        open session sids[r] (W == :meth:`kv_width`, 1 for classic
        artifacts); returns the per-row next-token logits (len(sids)
        rows of output 0). Appends each row's k/v into its session
        cache and advances its length by W."""
        self._need_decode()
        np = self._np
        c = ctypes
        sids = np.ascontiguousarray(sids, np.int64)
        tokens = np.ascontiguousarray(tokens, np.int64)
        w = max(1, self.kv_width())
        if tokens.size != sids.size * w:
            raise ValueError(
                f"decode_step: need len(sids) * width ({sids.size} * "
                f"{w}) tokens, got {tokens.size}")
        rc = self._lib.ptpu_predictor_decode_step(
            self._handle(), sids.ctypes.data_as(c.POINTER(c.c_int64)),
            tokens.ctypes.data_as(c.POINTER(c.c_int64)), sids.size,
            self._err, 512)
        if rc != 0:
            raise RuntimeError("decode_step: " +
                               self._err.value.decode())
        return self.output(0)[:sids.size]

    # ---- paged KV pool (r12) ----
    def kv_attach(self, pool: "KvPool") -> None:
        """Bind this decode-artifact predictor to a shared paged
        :class:`KvPool` (instead of :meth:`kv_plan`'s fixed slots).
        Sessions then live in the pool; kv_open/close/len and
        decode_step delegate to it. Unless ``PTPU_KV_DIRECT=0``, the
        attention graph rewrites onto the block-table read path
        (``kv_direct()`` reports whether it fired)."""
        self._need_decode()
        if not getattr(self._lib, "_ptpu_has_kvpool", False):
            raise RuntimeError(
                "paged KV needs the r12 ABI (stale "
                "_native_predictor.so: delete it and re-import)")
        if self._lib.ptpu_predictor_kv_attach(self._handle(),
                                              pool._handle(),
                                              self._err, 512) != 0:
            raise RuntimeError("kv_attach: " + self._err.value.decode())

    def kv_direct(self) -> bool:
        """True when the attention graph rewrote onto the paged
        (block-table) read path at :meth:`kv_attach` time."""
        self._need_decode()
        return bool(self._lib.ptpu_predictor_kv_direct(self._handle()))

    def output(self, i: int = 0):
        np = self._np
        nd = self._lib.ptpu_predictor_output_ndim(self._handle(), i)
        dims = self._lib.ptpu_predictor_output_dims(self._handle(), i)
        shape = tuple(dims[k] for k in range(nd))
        data = self._lib.ptpu_predictor_output_data(self._handle(), i)
        n = int(np.prod(shape)) if shape else 1
        return np.ctypeslib.as_array(data, shape=(n,)).reshape(shape).copy()


class KvPool:
    """Shared paged KV-cache pool for decode predictors (r12).

    Fixed-size page groups (``page_tokens`` positions x all layers x
    k+v) back every decode session, so RAM scales with tokens held
    instead of sessions x max-context. Attach the pool to every
    ladder-bucket predictor of ONE decode artifact via
    :meth:`NativePredictor.kv_attach`; open/fork/close/len address the
    pool's shared session space. ``adopt``/``publish`` drive the
    prefix/prompt cache; ``stats()`` parses the C snapshot
    (pages_total/in_use/cached gauges, prefix_hits, cow_copies, ...).

    Arguments <= 0 resolve from ``$PTPU_KV_POOL_TOKENS`` (0 = 64 x
    context at first attach), ``$PTPU_KV_PAGE`` (16) and
    ``$PTPU_KV_SESSIONS`` (4096); ``prefix_cache=None`` reads
    ``$PTPU_KV_PREFIX`` (on)."""

    def __init__(self, pool_tokens: int = 0, page_tokens: int = 0,
                 max_sessions: int = 0, prefix_cache=None):
        lib = _predictor_lib()
        if not getattr(lib, "_ptpu_has_kvpool", False):
            raise RuntimeError(
                "paged KV needs the r12 ABI (stale "
                "_native_predictor.so: delete it and re-import)")
        self._lib = lib
        self._err = ctypes.create_string_buffer(512)
        pc = -1 if prefix_cache is None else (1 if prefix_cache else 0)
        self._h = lib.ptpu_kvpool_create(pool_tokens, page_tokens,
                                         max_sessions, pc, self._err,
                                         512)
        if not self._h:
            raise RuntimeError("kvpool_create: " +
                               self._err.value.decode())

    def _handle(self):
        if not getattr(self, "_h", None):
            raise RuntimeError("KvPool is closed")
        return self._h

    def open(self) -> int:
        return int(self._lib.ptpu_kvpool_open(self._handle()))

    def fork(self, sid: int) -> int:
        """Clone ``sid`` sharing every page group copy-on-write;
        returns the new session id (-1 when full/closed)."""
        return int(self._lib.ptpu_kvpool_fork(self._handle(), sid))

    def close_session(self, sid: int) -> None:
        self._lib.ptpu_kvpool_close(self._handle(), sid)

    def len(self, sid: int) -> int:
        return int(self._lib.ptpu_kvpool_len(self._handle(), sid))

    def adopt(self, sid: int, tokens) -> int:
        """Adopt published prefix pages matching ``tokens`` into a
        page-aligned session; returns tokens adopted (never the final
        token — its logits must come from a step)."""
        import numpy as np
        c = ctypes
        t = np.ascontiguousarray(tokens, np.int64)
        return int(self._lib.ptpu_kvpool_adopt(
            self._handle(), sid,
            t.ctypes.data_as(c.POINTER(c.c_int64)), t.size))

    def publish(self, sid: int, tokens) -> None:
        """Publish the full prompt pages of ``sid`` (``tokens`` is the
        prompt) into the prefix cache for later adoption."""
        import numpy as np
        c = ctypes
        t = np.ascontiguousarray(tokens, np.int64)
        self._lib.ptpu_kvpool_publish(
            self._handle(), sid,
            t.ctypes.data_as(c.POINTER(c.c_int64)), t.size)

    def trim(self, sid: int, new_len: int) -> bool:
        """Truncate a pool session to ``new_len`` positions
        (speculative rollback: groups past the new tail are released
        or merely unreferenced when shared — published prefix pages
        and fork siblings are never mutated). False on a closed/bad
        session."""
        if not getattr(self._lib, "_ptpu_has_spec", False):
            raise RuntimeError(
                "trim needs the r13 ABI (stale _native_predictor.so: "
                "delete it and re-import)")
        return self._lib.ptpu_kvpool_trim(self._handle(), sid,
                                          new_len) == 0

    def stats(self) -> dict:
        import json
        return json.loads(
            self._lib.ptpu_kvpool_stats_json(self._handle()).decode())

    # ---- KV tiering + session hibernation (r19) ----
    def _spill_abi(self):
        if not getattr(self._lib, "_ptpu_has_spill", False):
            raise RuntimeError(
                "KV tiering needs the r19 ABI (stale "
                "_native_predictor.so: delete it and re-import)")
        return self._lib

    def spill_attach(self, path: str, max_bytes: int = -1) -> None:
        """Attach the mmap'd spill tier at ``path``. ``max_bytes`` < 0
        resolves ``$PTPU_KV_SPILL_MAX_BYTES`` (default 1 GiB); 0 is
        unbounded. The file is per-machine scratch — safe to delete
        between runs."""
        lib = self._spill_abi()
        if lib.ptpu_kvpool_spill_attach(
                self._handle(), path.encode(), max_bytes, self._err,
                512) != 0:
            raise RuntimeError("spill_attach: " +
                               self._err.value.decode())

    def hibernate(self, sid: int) -> bytes:
        """Serialize session ``sid`` into the spill tier and free its
        pool slot + sole-owner pages. Returns the opaque record —
        a handle cross-validated by the pool on :meth:`restore`, not a
        capability. Raises the retryable ``kv spill exhausted`` error
        when the spill file is full (record untouched)."""
        c = ctypes
        lib = self._spill_abi()
        need = lib.ptpu_kvpool_hibernate(
            self._handle(), sid, None, 0, self._err, 512)
        if need < 0:
            raise RuntimeError("hibernate: " + self._err.value.decode())
        buf = (c.c_uint8 * int(need))()
        got = lib.ptpu_kvpool_hibernate(
            self._handle(), sid, buf, need, self._err, 512)
        if got < 0:
            raise RuntimeError("hibernate: " + self._err.value.decode())
        return bytes(buf[:int(got)])

    def restore(self, record: bytes) -> int:
        """Re-open a hibernated session from its record; returns the
        new session id. Raises the retryable ``kv pool exhausted``
        error under page pressure (record stays valid) and -1 becomes
        a ``no session slots`` error."""
        c = ctypes
        lib = self._spill_abi()
        buf = (c.c_uint8 * len(record)).from_buffer_copy(record)
        sid = lib.ptpu_kvpool_restore(self._handle(), buf, len(record),
                                      self._err, 512)
        if sid == -1:
            raise RuntimeError("restore: no session slots")
        if sid < 0:
            raise RuntimeError("restore: " + self._err.value.decode())
        return int(sid)

    def hibernate_drop(self, record: bytes) -> None:
        """Release a hibernated session's spill state without
        restoring it (the close() of the tiered world)."""
        c = ctypes
        lib = self._spill_abi()
        buf = (c.c_uint8 * len(record)).from_buffer_copy(record)
        lib.ptpu_kvpool_hibernate_drop(self._handle(), buf,
                                       len(record))

    def hibernated(self) -> int:
        """Sessions currently parked in the spill tier."""
        return int(self._spill_abi().ptpu_kvpool_hibernated(
            self._handle()))

    def prefix_save(self, path: str) -> int:
        """Persist the content-addressed prefix cache to ``path``
        (tmp+rename); returns records written."""
        lib = self._spill_abi()
        n = lib.ptpu_kvpool_prefix_save(self._handle(), path.encode(),
                                        self._err, 512)
        if n < 0:
            raise RuntimeError("prefix_save: " +
                               self._err.value.decode())
        return int(n)

    def prefix_load(self, path: str) -> int:
        """Warm the prefix cache from a :meth:`prefix_save` file;
        returns pages adopted into the cache. A missing/malformed/
        stale file loads 0 pages (the cache can only miss, never
        serve wrong KV)."""
        lib = self._spill_abi()
        n = lib.ptpu_kvpool_prefix_load(self._handle(), path.encode(),
                                        self._err, 512)
        if n < 0:
            raise RuntimeError("prefix_load: " +
                               self._err.value.decode())
        return int(n)

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.ptpu_kvpool_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:   # interpreter teardown
            pass


def serving_available() -> bool:
    """True when _native_predictor.so carries the concurrent serving
    runtime (ptpu_serving_* ABI)."""
    try:
        return bool(getattr(_predictor_lib(), "_ptpu_has_serving",
                            False))
    except OSError:
        return False


# ---------------------------------------------------------------------------
# Persisted kernel autotuning (csrc/ptpu_tune.{h,cc}, r15). Process-
# global per .so and opt-in via PTPU_TUNE=1; these helpers only
# snapshot/steer it from Python (benches and tests).
# ---------------------------------------------------------------------------

def tune_available() -> bool:
    """True when _native_predictor.so carries the autotuning ABI."""
    try:
        return bool(getattr(_predictor_lib(), "_ptpu_has_tune", False))
    except OSError:
        return False


def _tune_lib() -> ctypes.CDLL:
    l = _predictor_lib()
    if not getattr(l, "_ptpu_has_tune", False):
        raise RuntimeError(
            "autotuning needs the r15 ABI (stale _native_predictor.so:"
            " delete it and re-import)")
    return l


def tune_stats() -> dict:
    """Autotuner counters: entries, hits/misses, probes + probe_us,
    cache-file loads/rejects/wrong-cpu, saves."""
    import json
    return json.loads(_tune_lib().ptpu_tune_stats_json().decode())


def tune_save(path: str = "") -> int:
    """Persist the in-memory winners (empty path = PTPU_TUNE_CACHE
    default). Returns entries written, -1 on I/O error."""
    return int(_tune_lib().ptpu_tune_save(path.encode()))


def tune_load(path: str = "") -> int:
    """Merge-load a tuning cache. Returns entries adopted; corrupt or
    foreign-machine files adopt 0 (silent re-probe contract)."""
    return int(_tune_lib().ptpu_tune_load(path.encode()))


def tune_clear() -> None:
    """Drop the in-memory entries/counters (cache file untouched)."""
    _tune_lib().ptpu_tune_clear()


# ---------------------------------------------------------------------------
# C ABI manifest — every exported symbol this binding layer (or the
# tests' hand-rolled ctypes) relies on, per shared object. The tier-1
# ABI-drift test (tests/test_observability.py) dlopen-checks each list
# against the built .so, so a symbol dropped or renamed in csrc fails
# at test time instead of at the first ctypes call in production.
# Adding a binding above? Add its symbol here.
# ---------------------------------------------------------------------------

ABI_SYMBOLS = {
    "_native.so": (
        "ptpu_last_error", "ptpu_version",
        "ptpu_arena_create", "ptpu_arena_destroy", "ptpu_arena_alloc",
        "ptpu_arena_free", "ptpu_arena_in_use", "ptpu_arena_peak",
        "ptpu_arena_reserved",
        "ptpu_queue_create", "ptpu_queue_destroy", "ptpu_queue_push",
        "ptpu_queue_pop", "ptpu_queue_close", "ptpu_queue_size",
        "ptpu_profiler_enable", "ptpu_profiler_disable",
        "ptpu_profiler_enabled", "ptpu_profiler_now_us",
        "ptpu_profiler_record", "ptpu_profiler_dump",
        "ptpu_profiler_count", "ptpu_profiler_clear",
        "ptpu_stat_add", "ptpu_stat_get", "ptpu_stat_reset",
        "ptpu_aes_ctr_xcrypt", "ptpu_feed_count", "ptpu_feed_parse",
    ),
    "_native_ps.so": (
        "ptpu_ps_last_error", "ptpu_ps_version",
        "ptpu_ps_table_create", "ptpu_ps_table_destroy",
        "ptpu_ps_table_data", "ptpu_ps_table_rows",
        "ptpu_ps_table_dim", "ptpu_ps_table_bytes",
        "ptpu_ps_table_pull", "ptpu_ps_table_push",
        "ptpu_ps_table_push_raw",
        "ptpu_ps_table_rdlock", "ptpu_ps_table_rdunlock",
        "ptpu_ps_table_stats_json", "ptpu_ps_table_stats_reset",
        "ptpu_ps_table_note_pull",
        "ptpu_ps_server_last_error", "ptpu_ps_server_start",
        "ptpu_ps_server_start2", "ptpu_ps_server_port",
        "ptpu_ps_server_http_port", "ptpu_ps_server_register",
        "ptpu_ps_server_stop", "ptpu_ps_server_stats_json",
        "ptpu_ps_server_stats_reset", "ptpu_ps_server_prom_text",
        "ptpu_trace_set", "ptpu_trace_json",
        "ptpu_capture_set", "ptpu_capture_json", "ptpu_capture_save",
        "ptpu_invar_check_json", "ptpu_invar_manifest",
    ),
    "_native_predictor.so": (
        "ptpu_predictor_create", "ptpu_predictor_create_opts",
        "ptpu_predictor_destroy",
        "ptpu_workpool_create", "ptpu_workpool_destroy",
        "ptpu_predictor_set_pool",
        "ptpu_predictor_num_inputs", "ptpu_predictor_num_outputs",
        "ptpu_predictor_num_nodes", "ptpu_predictor_fused_nodes",
        "ptpu_predictor_arena_bytes", "ptpu_predictor_input_name",
        "ptpu_predictor_input_ndim", "ptpu_predictor_input_dims",
        "ptpu_predictor_input_dtype",
        "ptpu_predictor_dynamic_fallbacks",
        "ptpu_predictor_set_input", "ptpu_predictor_set_input_i32",
        "ptpu_predictor_set_input_i64", "ptpu_predictor_run",
        "ptpu_predictor_output_ndim", "ptpu_predictor_output_dims",
        "ptpu_predictor_output_data",
        "ptpu_predictor_input_alloc", "ptpu_predictor_outputs_detach",
        "ptpu_outputs_pin_count", "ptpu_outputs_pin_data",
        "ptpu_outputs_pin_ndim", "ptpu_outputs_pin_dims",
        "ptpu_outputs_pin_release", "ptpu_workpool_create_bound",
        "ptpu_predictor_stats_json",
        "ptpu_predictor_stats_reset", "ptpu_predictor_set_profiler",
        "ptpu_predictor_kv_plan", "ptpu_predictor_kv_sessions",
        "ptpu_predictor_kv_open", "ptpu_predictor_kv_close",
        "ptpu_predictor_kv_len", "ptpu_predictor_kv_width",
        "ptpu_predictor_kv_trim", "ptpu_predictor_decode_step",
        "ptpu_kvpool_create", "ptpu_kvpool_destroy",
        "ptpu_predictor_kv_attach", "ptpu_predictor_kv_direct",
        "ptpu_kvpool_open", "ptpu_kvpool_fork", "ptpu_kvpool_close",
        "ptpu_kvpool_len", "ptpu_kvpool_adopt", "ptpu_kvpool_publish",
        "ptpu_kvpool_trim", "ptpu_kvpool_stats_json",
        "ptpu_kvpool_spill_attach", "ptpu_kvpool_hibernate",
        "ptpu_kvpool_restore", "ptpu_kvpool_hibernate_drop",
        "ptpu_kvpool_hibernated", "ptpu_kvpool_prefix_save",
        "ptpu_kvpool_prefix_load",
        "ptpu_serving_start", "ptpu_serving_start2",
        "ptpu_serving_start3", "ptpu_serving_start4",
        "ptpu_serving_port",
        "ptpu_serving_http_port", "ptpu_serving_drain_begin",
        "ptpu_serving_config_json", "ptpu_serving_stats_json",
        "ptpu_serving_stats_reset", "ptpu_serving_prom_text",
        "ptpu_serving_stop", "ptpu_trace_set", "ptpu_trace_json",
        "ptpu_capture_set", "ptpu_capture_json", "ptpu_capture_save",
        "ptpu_invar_check_json", "ptpu_invar_manifest",
        "ptpu_tune_stats_json", "ptpu_tune_save", "ptpu_tune_load",
        "ptpu_tune_clear",
    ),
}
