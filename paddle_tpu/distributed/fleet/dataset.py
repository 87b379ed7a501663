"""Fleet datasets: InMemoryDataset / QueueDataset + the epoch driver.

Reference mapping:
  * `DatasetImpl::LoadIntoMemory` / `LocalShuffle` / `GlobalShuffle`
    (`paddle/fluid/framework/data_set.h:101`) — C++ record store fed by
    MultiSlotDataFeed parsing slot text files (`data_feed.h:120`);
  * Python wrappers `fleet/dataset/dataset.py:24,253`
    (DatasetBase/InMemoryDataset/QueueDataset);
  * `Executor::RunFromDataset` + Trainer/DeviceWorker
    (`framework/trainer.h:57-292`, `executor.cc:152`) — the epoch driver.

TPU-native shape: records are host-side numpy structures (the device step
is one compiled function — there is no per-op DeviceWorker to mirror), and
GlobalShuffle rides the PS TCP service (`..ps.table.TableService`) the way
the reference rides brpc. The driver (`train_from_dataset`) feeds batches
to a user step callable — the jitted train step IS the trainer thread.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


def _native_parse_numeric(path: str):
    """Bulk-parse a plain numeric slot file through the C++ runtime
    (reference: MultiSlotDataFeed's native parse loop,
    `framework/data_feed.cc` — Python tokenization is the LoadIntoMemory
    bottleneck). Returns a list of per-line float32 arrays, or None when
    the native lib is unavailable or the file isn't plain numeric
    (slot-name syntax etc. — caller falls back to the Python parser)."""
    import ctypes

    from ...core import native
    if not native.available():
        return None
    lib = native.lib()
    # single allocation: file bytes + trailing NUL (strtof needs it)
    size = os.path.getsize(path)
    ba = bytearray(size + 1)
    with open(path, "rb") as f:
        f.readinto(memoryview(ba)[:size])
    if b":" in ba:           # named-slot format: python parser handles it
        return None
    cbuf = (ctypes.c_char * len(ba)).from_buffer(ba)
    n_vals = ctypes.c_int64()
    n_lines = ctypes.c_int64()
    if lib.ptpu_feed_count(cbuf, size, ctypes.byref(n_vals),
                           ctypes.byref(n_lines)) != 0:
        return None
    vals = np.empty(n_vals.value, np.float32)
    starts = np.empty(n_lines.value + 1, np.int64)
    parsed = ctypes.c_int64()
    rc = lib.ptpu_feed_parse(
        ctypes.cast(cbuf, ctypes.c_void_p), size,
        vals.ctypes.data_as(ctypes.c_void_p), n_vals.value,
        starts.ctypes.data_as(ctypes.c_void_p), n_lines.value,
        ctypes.byref(parsed))
    # STRICT count verification: an early stop (embedded NUL, locale
    # surprises) must fall back to the python parser rather than hand
    # back records spanning uninitialized memory
    if rc != n_lines.value or parsed.value != n_vals.value:
        return None
    starts[rc] = n_vals.value
    return [vals[starts[i]:starts[i + 1]] for i in range(rc)]


def _default_parse(line: str):
    """Default slot parser: whitespace-separated `name:v1,v2,...` slots or
    plain numbers (one record per line)."""
    line = line.strip()
    if not line:
        return None
    if ":" in line:
        rec = {}
        for tok in line.split():
            name, _, vals = tok.partition(":")
            rec[name] = np.array([float(v) for v in vals.split(",") if v],
                                 np.float32)
        return rec
    # commas are separators like whitespace (matches the native parser)
    vals = [float(v) for v in line.replace(",", " ").split()]
    # separator-only lines produce no record on EITHER parser path
    return np.array(vals, np.float32) if vals else None


class DatasetBase:
    """Reference: `fleet/dataset/dataset.py:24 DatasetBase`."""

    def __init__(self):
        self.batch_size = 1
        self.thread_num = 1
        self.filelist: List[str] = []
        self.use_var: List[str] = []
        self.pipe_command = "cat"
        self.parse_fn: Callable = _default_parse
        self._seed = 0

    def init(self, batch_size=1, thread_num=1, use_var=None,
             pipe_command="cat", input_type=0, fs_name="", fs_ugi="",
             download_cmd="cat", **kw):
        self.batch_size = batch_size
        self.thread_num = thread_num
        self.use_var = use_var or []
        self.pipe_command = pipe_command
        return self

    # reference setters (set_* API parity)
    def set_batch_size(self, b):
        self.batch_size = b

    def set_thread(self, t):
        self.thread_num = t

    def set_filelist(self, files: Sequence[str]):
        self.filelist = list(files)

    def set_use_var(self, var_list):
        self.use_var = list(var_list)

    def set_pipe_command(self, cmd):
        self.pipe_command = cmd

    def set_parse_ins(self, fn: Callable):
        """TPU-native replacement for the C++ DataFeed parser plugins."""
        self.parse_fn = fn

    # bulk native parsing is for load-into-memory datasets; streaming
    # datasets (QueueDataset) keep the O(1)-memory line path
    _bulk_native = False

    def _read_lines(self, path: str):
        if self._bulk_native and self.parse_fn is _default_parse:
            recs = _native_parse_numeric(path)
            if recs is not None:
                yield from recs
                return
        with open(path, "r") as f:
            for line in f:
                rec = self.parse_fn(line)
                if rec is not None:
                    yield rec


class InMemoryDataset(DatasetBase):
    """Reference: `DatasetImpl` with `LoadIntoMemory`/`GlobalShuffle`
    (`data_set.h:101`); Python `fleet/dataset/dataset.py:253`."""

    _bulk_native = True    # LoadIntoMemory wants the C++ parse hot path

    def __init__(self):
        super().__init__()
        self._records: List = []
        self._loaded = False

    # -- loading ----------------------------------------------------------

    def load_into_memory(self):
        """Parse the rank's filelist into host memory. With a launcher
        world, each rank loads its own (disjoint) filelist slice exactly
        like the reference's per-node file assignment."""
        self._records = []
        for path in self.filelist:
            self._records.extend(self._read_lines(path))
        self._loaded = True

    def set_sample_list(self, samples: Sequence):
        """Directly install records (tests / in-process producers)."""
        self._records = list(samples)
        self._loaded = True

    # -- shuffle ----------------------------------------------------------

    def local_shuffle(self, seed: Optional[int] = None):
        rs = np.random.RandomState(self._seed if seed is None else seed)
        rs.shuffle(self._records)
        self._seed += 1

    def global_shuffle(self, fleet=None, thread_num: int = 12,
                       seed: Optional[int] = None):
        """Cross-rank repartition + shuffle (reference:
        `DatasetImpl::GlobalShuffle` exchanging records over brpc).

        Every record is assigned a uniformly random target rank; records
        ship over the PS TCP service; each rank locally shuffles what it
        received. Single-process (no service/world=1) degrades to
        local_shuffle like the reference does.
        """
        from ..ps.table import init_table_service
        world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        if world <= 1:
            self.local_shuffle(seed)
            return
        svc = init_table_service()
        rank = svc.rank
        rs = np.random.RandomState(
            (self._seed if seed is None else seed) * 7919 + rank)
        targets = rs.randint(0, world, size=len(self._records))
        per_target: Dict[int, list] = {}
        for rec, t in zip(self._records, targets):
            per_target.setdefault(int(t), []).append(rec)
        try:
            self._records = svc.exchange_records(per_target,
                                                 tag=f"ds{self._seed}")
        except TypeError as e:
            # the PS wire moves DATA (arrays/scalars/str/bytes/
            # lists/tuples/dicts), never pickled objects; custom record
            # classes from set_parse_ins must be converted to tuples of
            # arrays before a multi-rank global_shuffle
            raise TypeError(
                "global_shuffle records must be wire-encodable data "
                "(tuples/lists of numpy arrays, scalars, str/bytes) — "
                f"{e}") from e
        self.local_shuffle(seed)

    # -- sizes ------------------------------------------------------------

    def get_memory_data_size(self, fleet=None) -> int:
        """Local record count; with fleet/world>1, the GLOBAL count
        (reference: returns allreduced size)."""
        world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        if fleet is None or world <= 1:
            return len(self._records)
        from ..ps.table import init_table_service
        svc = init_table_service()
        svc.kv_put(f"__dsize__/{svc.rank}", str(len(self._records)).encode())
        svc.barrier("dsize")
        sizes = svc.kv_prefix("__dsize__/")
        return sum(int(v.decode()) for v in sizes.values())

    get_shuffle_data_size = get_memory_data_size

    def release_memory(self):
        self._records = []
        self._loaded = False

    # -- iteration --------------------------------------------------------

    def __len__(self):
        return len(self._records)

    def batch_iter(self, drop_last: bool = False):
        n = len(self._records)
        bs = self.batch_size
        end = (n // bs) * bs if drop_last else n
        for i in range(0, end, bs):
            yield self._records[i:i + bs]

    def __iter__(self):
        return self.batch_iter()


class QueueDataset(DatasetBase):
    """Streaming dataset: no LoadIntoMemory; files are read on the fly
    (reference: `QueueDataset` / MultiSlotDataFeed streaming mode)."""

    def batch_iter(self, drop_last: bool = False):
        batch = []
        for path in self.filelist:
            for rec in self._read_lines(path):
                batch.append(rec)
                if len(batch) == self.batch_size:
                    yield batch
                    batch = []
        if batch and not drop_last:
            yield batch

    def __iter__(self):
        return self.batch_iter()


def train_from_dataset(step_fn: Callable, dataset,
                       epochs: int = 1,
                       collate_fn: Optional[Callable] = None,
                       print_period: int = 100,
                       debug: bool = False):
    """Epoch driver (reference: `Executor.train_from_dataset` →
    `Executor::RunFromDataset` spinning DeviceWorkers, `executor.cc:152`).

    TPU-native: the compiled `step_fn(batch) -> loss/metrics` IS the
    device worker; this loop is the Trainer. Returns the list of per-epoch
    mean losses (floats) for anything step_fn returns that is castable.
    """
    epoch_means = []
    for ep in range(epochs):
        losses = []
        for i, batch in enumerate(dataset.batch_iter()):
            if collate_fn is not None:
                batch = collate_fn(batch)
            out = step_fn(batch)
            try:
                losses.append(float(np.asarray(out).mean()))
            except (TypeError, ValueError):
                pass
            if debug and print_period and (i + 1) % print_period == 0:
                print(f"epoch {ep} step {i + 1}: "
                      f"loss={losses[-1] if losses else 'n/a'}")
        epoch_means.append(float(np.mean(losses)) if losses else 0.0)
    return epoch_means
