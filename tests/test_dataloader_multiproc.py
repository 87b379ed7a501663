"""Multiprocess DataLoader tests.

Reference parity targets (VERDICT round 1 item 7):
  * `num_workers>0` spawns real processes (`dataloader_iter.py:317`);
  * shared-memory batch transport (`mmap_allocator.cc`);
  * watchdog survives a killed worker (`worker.py:251` + SIGCHLD —
    here: respawn + re-dispatch);
  * beats the thread pool on a Python-heavy (GIL-bound) decode pipeline.
"""
import os
import signal
import time

import numpy as np
import pytest

from paddle_tpu.io import DataLoader, Dataset


class ArrayDS(Dataset):
    def __init__(self, n=64, d=128):
        self.x = np.arange(n * d, dtype=np.float32).reshape(n, d)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], np.int64(i)


class SlowPythonDS(Dataset):
    """GIL-bound decode: pure-Python work per item."""

    def __init__(self, n=48, iters=40000):
        self.n, self.iters = n, iters

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        acc = 0
        for k in range(self.iters):  # holds the GIL
            acc = (acc + k * i) % 1000003
        return np.asarray([acc, i], dtype=np.float32)


class PidDS(Dataset):
    def __len__(self):
        return 32

    def __getitem__(self, i):
        return np.asarray([os.getpid(), i], dtype=np.int64)


class TestMultiprocessDataLoader:
    def test_order_content_and_real_processes(self):
        ds = ArrayDS()
        dl = DataLoader(ds, batch_size=8, num_workers=3,
                        use_buffer_reader=False)
        got_x, got_i = [], []
        for xb, ib in dl:
            got_x.append(np.asarray(xb))
            got_i.append(np.asarray(ib))
        x = np.concatenate(got_x)
        np.testing.assert_array_equal(x, ds.x)
        np.testing.assert_array_equal(np.concatenate(got_i), np.arange(64))

    def test_workers_are_separate_processes(self):
        dl = DataLoader(PidDS(), batch_size=4, num_workers=3,
                        use_buffer_reader=False)
        pids = set()
        for b in dl:
            pids.update(np.asarray(b)[:, 0].tolist())
        assert os.getpid() not in pids
        assert len(pids) >= 2, pids  # work actually spread over processes

    def test_shared_memory_large_batches(self):
        ds = ArrayDS(n=32, d=8192)  # 32KB/sample → shm path
        dl = DataLoader(ds, batch_size=8, num_workers=2,
                        use_buffer_reader=False, use_shared_memory=True)
        out = np.concatenate([np.asarray(xb) for xb, _ in dl])
        np.testing.assert_array_equal(out, ds.x)

    def test_survives_killed_worker(self):
        """SIGKILL one worker mid-epoch: the watchdog respawns it and every
        batch still arrives exactly once, in order."""
        ds = ArrayDS(n=96, d=64)
        dl = DataLoader(ds, batch_size=4, num_workers=3,
                        use_buffer_reader=False)
        it = iter(dl)
        first = next(it)
        # reach into the live iterator and kill one child
        import gc
        from paddle_tpu.io.worker import MultiprocessBatchIterator
        mp_iters = [o for o in gc.get_objects()
                    if isinstance(o, MultiprocessBatchIterator)
                    and getattr(o, "_procs", None)]
        assert mp_iters, "no live multiprocess iterator found"
        victim = mp_iters[-1]._procs[0]
        os.kill(victim.pid, signal.SIGKILL)
        got = [first] + list(it)
        x = np.concatenate([np.asarray(xb) for xb, _ in got])
        np.testing.assert_array_equal(x, ds.x)

    def test_worker_exception_propagates(self):
        class Bad(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                if i == 5:
                    raise ValueError("boom-item-5")
                return np.zeros(4, np.float32)

        dl = DataLoader(Bad(), batch_size=2, num_workers=2,
                        use_buffer_reader=False)
        with pytest.raises(RuntimeError, match="boom-item-5"):
            list(dl)

    def test_processes_beat_threads_on_gil_bound_decode(self):
        if (os.cpu_count() or 1) < 4:
            pytest.skip("needs >=4 cpus for a meaningful comparison")
        # ~1 s of GIL-bound decode in all: starting four worker
        # processes costs ~0.2 s here, which a 0.1 s workload (the
        # default size on this interpreter) cannot win back
        ds = SlowPythonDS(iters=300000)

        def run(mode):
            dl = DataLoader(ds, batch_size=4, num_workers=4,
                            use_buffer_reader=False, worker_mode=mode)
            t0 = time.perf_counter()
            n = sum(1 for _ in dl)
            assert n == 12
            return time.perf_counter() - t0

        t_thread = run("thread")
        t_proc = run("process")
        # GIL serializes the thread pool; processes parallelize the decode
        assert t_proc < t_thread * 0.9, (t_proc, t_thread)

    def test_worker_init_fn(self):
        seen = []

        def init(worker_id):
            os.environ["PTPU_TEST_WID"] = str(worker_id)

        class EnvDS(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return np.asarray([int(os.environ["PTPU_TEST_WID"])],
                                  np.int64)

        dl = DataLoader(EnvDS(), batch_size=2, num_workers=2,
                        use_buffer_reader=False, worker_init_fn=init)
        wids = {int(np.asarray(b)[0, 0]) for b in dl}
        assert wids <= {0, 1} and wids, wids


class TestDeviceBufferedReader:
    """BufferedReader analogue (reference operators/reader/
    buffered_reader.h): device-resident batches, order preserved,
    partial tail kept."""

    def test_order_and_device(self):
        import jax
        import numpy as np
        from paddle_tpu.io import DeviceBufferedReader

        batches = [np.full((2, 3), i, np.float32) for i in range(7)]
        out = list(DeviceBufferedReader(batches, buffer_size=3))
        assert len(out) == 7
        for i, b in enumerate(out):
            assert float(b[0, 0]) == i
            assert isinstance(b, jax.Array)

    def test_short_iterable_and_pytree(self):
        import numpy as np
        from paddle_tpu.io import device_buffered

        batches = [{"x": np.ones((2,)), "y": np.zeros((1,))}]
        out = list(device_buffered(batches, buffer_size=4))
        assert len(out) == 1 and set(out[0]) == {"x", "y"}

    def test_wraps_dataloader(self):
        import numpy as np
        import paddle_tpu as pt
        from paddle_tpu.io import DataLoader, TensorDataset, \
            device_buffered

        ds = TensorDataset([np.arange(12, dtype=np.float32).reshape(6, 2)])
        dl = DataLoader(ds, batch_size=2)
        got = [np.asarray(b[0] if isinstance(b, (list, tuple)) else b)
               for b in device_buffered(dl)]
        assert sum(g.shape[0] for g in got) == 6


class TestHostPrefetcher:
    """Host-side double buffering (ISSUE r8 satellite): a background
    thread pulls batches ahead so collate overlaps consumer compute.
    The overlap path must yield IDENTICAL batches, in order, to the
    serial path."""

    def test_overlap_matches_serial_dataloader(self):
        import numpy as np
        from paddle_tpu.io import DataLoader, TensorDataset

        rs = np.random.RandomState(0)
        data = rs.randn(23, 4).astype(np.float32)
        ds = TensorDataset([data])
        # serial: no buffer reader, no prefetch thread
        serial = [np.asarray(b[0] if isinstance(b, (list, tuple)) else b)
                  for b in DataLoader(ds, batch_size=4, shuffle=False,
                                      use_buffer_reader=False)]
        # overlapped: buffer reader on -> HostPrefetcher + device buffer
        overlap = [np.asarray(b[0] if isinstance(b, (list, tuple)) else b)
                   for b in DataLoader(ds, batch_size=4, shuffle=False,
                                       use_buffer_reader=True)]
        assert len(serial) == len(overlap) == 6  # 5 full + tail of 3
        for s, o in zip(serial, overlap):
            np.testing.assert_array_equal(s, o)

    def test_prefetcher_preserves_order_and_reraises(self):
        import numpy as np
        import pytest
        from paddle_tpu.io import host_prefetched

        out = list(host_prefetched((np.full((2,), i) for i in range(50)),
                                   depth=3))
        assert [int(b[0]) for b in out] == list(range(50))

        def boom():
            yield np.zeros((1,))
            raise ValueError("producer failed")

        it = iter(host_prefetched(boom(), depth=2))
        next(it)
        with pytest.raises(ValueError, match="producer failed"):
            for _ in it:
                pass

    def test_early_consumer_exit_stops_worker(self):
        import threading
        import numpy as np
        from paddle_tpu.io import host_prefetched

        n0 = threading.active_count()
        it = iter(host_prefetched((np.zeros((1,)) for _ in range(1000)),
                                  depth=2))
        next(it)
        it.close()  # generator finally: stop flag + join
        assert threading.active_count() <= n0 + 1
