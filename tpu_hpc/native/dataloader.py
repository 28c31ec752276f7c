"""ctypes binding for the C++ data pipeline (src/dataloader.cpp).

The library is built on first use with g++ (no pybind11 in the image;
ctypes keeps the binding dependency-free). Role parity with the
reference's DataLoader(num_workers=4, pin_memory=True) input path
(multinode_ddp_unet.py:283-292): background native threads keep batches
ahead of the training loop.

Use ``models.datasets.ERA5Synthetic`` (on-device traced generation) for
synthetic benchmarks; use this loader where the host must produce the
data (real datasets, CPU-side preprocessing).
"""
from __future__ import annotations

import ctypes
import dataclasses
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "dataloader.cpp")
_FLAGS = (
    "-O3", "-march=native", "-std=c++17", "-shared", "-fPIC", "-pthread",
)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _lib_path() -> str:
    """Where THIS host's build of THIS source lives. The name carries a
    digest of everything the binary depends on -- the source text, the
    compile flags and, because of ``-march=native``, the host CPU's
    feature set -- so a binary from another machine (a copied tree) or
    from an older source is simply not the file that gets loaded. A
    modification-time test cannot tell either case apart."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(platform.machine().encode())
    try:
        with open("/proc/cpuinfo") as f:
            flags = next(
                (l for l in f if l.startswith(("flags", "Features"))), ""
            )
    except OSError:
        flags = platform.processor()
    h.update(flags.encode())
    return os.path.join(
        _HERE, f"libtpu_hpc_data.{h.hexdigest()[:16]}.so"
    )


def _build(lib_path: str) -> None:
    # Compile next to the target and rename into place: a concurrent
    # loader (another host process) never sees a half-written library.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", *_FLAGS, _SRC, "-o", tmp],
            check=True, capture_output=True, text=True,
        )
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load() -> ctypes.CDLL:
    """The native library, built on first use. Raises ``RuntimeError``
    (compiler output included) when it cannot be built or loaded --
    the stream classes below were asked for by name, and a missing
    loader must stop them there. ``native_available`` is the one
    caller that turns the failure into an answer."""
    global _lib, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            raise RuntimeError(_build_error)
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path):
                _build(lib_path)
            lib = ctypes.CDLL(lib_path)
        except (OSError, subprocess.CalledProcessError) as e:
            _build_error = (
                f"native dataloader unavailable: {e}"
                + (f"\n{e.stderr}" if getattr(e, "stderr", None) else "")
            )
            raise RuntimeError(_build_error) from e
        lib.era5_gen.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_uint64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ]
        lib.era5_prefetcher_create.restype = ctypes.c_void_p
        lib.era5_prefetcher_create.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_int,
        ]
        lib.era5_prefetcher_next.restype = ctypes.c_int
        lib.era5_prefetcher_next.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.era5_prefetcher_seek.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.era5_prefetcher_destroy.argtypes = [ctypes.c_void_p]
        lib.file_dataset_open.restype = ctypes.c_void_p
        lib.file_dataset_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_int,
        ]
        lib.file_dataset_info.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.file_dataset_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ]
        lib.file_dataset_next.restype = ctypes.c_int
        lib.file_dataset_next.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.file_dataset_seek.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.file_dataset_close.argtypes = [ctypes.c_void_p]
        lib.token_dataset_open.restype = ctypes.c_void_p
        lib.token_dataset_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
        ]
        lib.token_dataset_info.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.token_dataset_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ]
        lib.token_dataset_next.restype = ctypes.c_int
        lib.token_dataset_next.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.token_dataset_seek.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.token_dataset_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    """True when the C++ library built (g++ present); callers fall back
    to the on-device generator otherwise."""
    try:
        _load()
    except RuntimeError:
        return False
    return True


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


class _PrefetchedStream:
    """Shared ring-resync protocol over a native prefetcher.

    Subclasses provide ``batch_size`` plus the four raw hooks
    (``_alloc``, ``_ring_next``, ``_ring_seek``, ``_sync_batch``);
    this class owns the access-pattern policy so it exists in exactly
    one place:

    * sequential reads ride the C++ prefetch ring;
    * a one-off jump is served synchronously, ring untouched (a
      mid-training eval re-read must not discard the training
      stream's prefetched window);
    * a jump followed by a sequential read -- the checkpoint-resume
      pattern -- reseeks the ring there and prefetching resumes.

    Identical bytes on every path: batches are pure functions of
    (seed, step).
    """

    def _init_stream(self):
        self._next_seq = 0
        self._resync_at: Optional[int] = None

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        """Next sequential batch from the prefetch ring."""
        x, y = self._alloc()
        step = ctypes.c_int64()
        rc = self._ring_next(x, y, step)
        if rc != 0:
            # Shutdown raced the wait: outputs are uninitialized
            # memory, never hand them to the caller.
            raise RuntimeError("native prefetcher shut down mid-read")
        self._next_seq = step.value + 1
        return x, y

    def batch_at(self, step: int, batch_size: int):
        """Random-access batch (Trainer contract)."""
        if batch_size != self.batch_size:
            raise ValueError(
                f"batch {batch_size} != stream batch {self.batch_size}"
            )
        if step == self._next_seq:
            self._resync_at = None
            return self.next()
        if step == self._resync_at:
            # Second sequential read after a jump: this is a new
            # stream, not random access -- move the ring to it.
            self._ring_seek(step)
            self._next_seq = step
            self._resync_at = None
            return self.next()
        self._resync_at = step + 1
        x, y = self._alloc()
        self._sync_batch(step, x, y)
        return x, y

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


@dataclasses.dataclass
class NativeERA5Stream(_PrefetchedStream):
    """Host-side ERA5-like stream with native prefetching.

    Same dataset contract as models/datasets.py (``batch_at(step,
    batch_size)``; deterministic in (seed, step)) so the Trainer's
    host-fed path accepts it directly.
    """

    batch_size: int
    lat: int = 181
    lon: int = 360
    channels: int = 20
    seed: int = 0
    prefetch_depth: int = 4
    n_threads: int = 2

    def __post_init__(self):
        lib = self._lib = _load()
        self._handle = lib.era5_prefetcher_create(
            self.batch_size, self.lat, self.lon, self.channels,
            self.seed, self.prefetch_depth, self.n_threads,
        )
        self._init_stream()

    @property
    def sample_shape(self) -> Tuple[int, int, int]:
        return (self.lat, self.lon, self.channels)

    def _alloc(self) -> Tuple[np.ndarray, np.ndarray]:
        shape = (self.batch_size, self.lat, self.lon, self.channels)
        return (
            np.empty(shape, np.float32), np.empty(shape, np.float32)
        )

    def _ring_next(self, x, y, step) -> int:
        return self._lib.era5_prefetcher_next(
            self._handle, _fptr(x), _fptr(y), ctypes.byref(step)
        )

    def _ring_seek(self, step: int) -> None:
        self._lib.era5_prefetcher_seek(self._handle, step)

    def _sync_batch(self, step: int, x, y) -> None:
        self._lib.era5_gen(
            self.batch_size, self.lat, self.lon, self.channels,
            self.seed, step, _fptr(x), _fptr(y),
        )

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.era5_prefetcher_destroy(self._handle)
            self._handle = None


_FILE_MAGIC = 0x3144435048555054  # "TPUHPCD1" little-endian


def prepare_on_host0(prepare_fn, paths) -> None:
    """Host 0 materializes ``paths`` via ``prepare_fn`` if any is
    missing; every host then synchronizes before reading them -- the
    reference's rank-0-download + dist.barrier() pattern
    (resnet_fsdp_training.py:60-65) without the race. Generic over
    what is being prepared (image records, token corpora, ...)."""
    import jax

    if jax.process_index() == 0 and not all(
        os.path.exists(p) for p in paths
    ):
        prepare_fn()
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("tpu_hpc_prepare")

    def check_visible():
        missing = [p for p in paths if not os.path.exists(p)]
        if missing:
            raise FileNotFoundError(
                f"prepare did not produce {missing} -- is the data "
                "directory shared across hosts (GCS/NFS)? Each host "
                "needs to see the same files."
            )

    # Shared filesystems are close-to-open consistent at best: a file
    # host 0 just wrote can take seconds to appear to the other hosts
    # even after the barrier. Bounded retry instead of failing the
    # whole job on the propagation race (resilience.retry).
    from tpu_hpc.resilience.retry import retry_call

    retry_call(
        check_visible, retries=4, base_delay=0.5, max_delay=8.0,
        retry_on=(FileNotFoundError,),
        describe="shared-filesystem dataset visibility",
    )


def write_dataset(path: str, x: np.ndarray, y: np.ndarray) -> str:
    """Write (x, y) sample arrays as a tpu_hpc binary dataset.

    x: [N, ...], y: [N, ...], converted to float32. Records are stored
    contiguously (x then y per sample) so the mmap'd reader gathers a
    batch with two memcpys per sample. The real-data counterpart of
    the reference's downloaded-dataset path (resnet_fsdp_training.py:
    45-87) -- convert once, then train from the file on every host.
    """
    x = np.ascontiguousarray(x, np.float32)
    y = np.ascontiguousarray(y, np.float32)
    if x.shape[0] != y.shape[0]:
        raise ValueError(f"sample counts differ: {x.shape[0]} vs {y.shape[0]}")
    n = x.shape[0]
    xe = int(np.prod(x.shape[1:], dtype=np.int64))
    ye = int(np.prod(y.shape[1:], dtype=np.int64))
    rec = np.empty((n, xe + ye), np.float32)
    rec[:, :xe] = x.reshape(n, xe)
    rec[:, xe:] = y.reshape(n, ye)
    with open(path, "wb") as f:
        np.asarray([_FILE_MAGIC, n, xe, ye], np.uint64).tofile(f)
        rec.tofile(f)
    return path


@dataclasses.dataclass
class NativeFileDataset(_PrefetchedStream):
    """Train from a tpu_hpc binary file via the mmap'd C++ reader.

    Same Trainer contract and ring semantics as NativeERA5Stream
    (the shared ``_PrefetchedStream`` protocol). Epoch shuffling is a
    per-epoch Feistel permutation -- every epoch visits every sample
    exactly once in a different deterministic order
    (DistributedSampler.set_epoch semantics with no sampler state).
    ``x_shape``/``y_shape`` restore the per-sample shapes the flat
    records lost.
    """

    path: str
    batch_size: int
    x_shape: Tuple[int, ...]
    y_shape: Tuple[int, ...]
    seed: int = 0
    prefetch_depth: int = 4
    n_threads: int = 2

    def __post_init__(self):
        lib = self._lib = _load()
        self._handle = lib.file_dataset_open(
            self.path.encode(), self.batch_size, self.seed,
            self.prefetch_depth, self.n_threads,
        )
        if not self._handle:
            raise ValueError(f"not a tpu_hpc dataset file: {self.path}")
        n, xe, ye = ctypes.c_int64(), ctypes.c_int64(), ctypes.c_int64()
        lib.file_dataset_info(
            self._handle, ctypes.byref(n), ctypes.byref(xe), ctypes.byref(ye)
        )
        self.n_samples = n.value
        if xe.value != int(np.prod(self.x_shape, dtype=np.int64)):
            raise ValueError(
                f"x_shape {self.x_shape} != {xe.value} elems in file"
            )
        if ye.value != int(np.prod(self.y_shape, dtype=np.int64)):
            raise ValueError(
                f"y_shape {self.y_shape} != {ye.value} elems in file"
            )
        self._init_stream()

    def _alloc(self):
        return (
            np.empty((self.batch_size, *self.x_shape), np.float32),
            np.empty((self.batch_size, *self.y_shape), np.float32),
        )

    def _ring_next(self, x, y, step) -> int:
        return self._lib.file_dataset_next(
            self._handle, _fptr(x), _fptr(y), ctypes.byref(step)
        )

    def _ring_seek(self, step: int) -> None:
        self._lib.file_dataset_seek(self._handle, step)

    def _sync_batch(self, step: int, x, y) -> None:
        self._lib.file_dataset_batch(
            self._handle, step, _fptr(x), _fptr(y)
        )

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.file_dataset_close(self._handle)
            self._handle = None


_TOKEN_MAGIC = 0x3154435048555054  # "TPUHPCT1" little-endian


def write_token_dataset(path: str, tokens: np.ndarray) -> str:
    """Write a flat token-id corpus as a tpu_hpc token dataset.

    ``tokens``: 1D integer array (any integer dtype); stored uint16
    when every id fits, else uint32 -- halving disk and page-cache
    footprint for <=65536-vocab corpora. The LLM counterpart of
    ``write_dataset``: pretokenize once, then every host trains from
    the mmap'd file (the reference's Llama examples never got past
    random tokens -- 03_pipeline_training.py:220-230)."""
    tokens = np.asarray(tokens)
    if tokens.ndim != 1:
        raise ValueError(f"tokens must be 1D, got shape {tokens.shape}")
    if tokens.size < 2:
        raise ValueError("corpus needs at least 2 tokens")
    if not np.issubdtype(tokens.dtype, np.integer):
        raise ValueError(f"tokens must be integers, got {tokens.dtype}")
    lo, hi = int(tokens.min()), int(tokens.max())  # one scan each --
    # billion-token corpora make repeated reductions expensive
    if lo < 0 or hi > 0xFFFFFFFF:
        raise ValueError("token ids must fit in uint32")
    dtype = np.uint16 if hi <= 0xFFFF else np.uint32
    data = np.ascontiguousarray(tokens, dtype)
    with open(path, "wb") as f:
        # Header word 3 carries the max id so loaders can validate
        # the corpus against a model's vocab_size at open time.
        np.asarray(
            [_TOKEN_MAGIC, data.size, data.dtype.itemsize, hi],
            np.uint64,
        ).tofile(f)
        data.tofile(f)
    return path


@dataclasses.dataclass
class NativeTokenDataset(_PrefetchedStream):
    """Next-token training batches from a mmap'd token corpus.

    Window w covers tokens [w*seq_len, w*seq_len + seq_len]; a batch
    is (inputs, targets) int32 [B, S] with targets shifted one token.
    Same Trainer contract, ring semantics, and per-epoch Feistel
    shuffle as NativeFileDataset (every window exactly once per epoch,
    deterministic in (seed, step)). Drop-in for datasets.TokenStream
    where the tokens come from disk instead of an RNG.
    """

    path: str
    batch_size: int
    seq_len: int
    seed: int = 0
    prefetch_depth: int = 4
    n_threads: int = 2

    def __post_init__(self):
        if self.seq_len <= 0 or self.batch_size <= 0:
            raise ValueError(
                f"seq_len {self.seq_len} and batch_size "
                f"{self.batch_size} must be positive"
            )
        lib = self._lib = _load()
        self._handle = lib.token_dataset_open(
            self.path.encode(), self.batch_size, self.seq_len,
            self.seed, self.prefetch_depth, self.n_threads,
        )
        if not self._handle:
            # The C++ opener only reports "no": distinguish the three
            # user-facing causes here so a valid-but-short corpus is
            # not reported as corrupt.
            if not os.path.exists(self.path):
                raise FileNotFoundError(self.path)
            try:
                hdr = np.fromfile(self.path, np.uint64, count=2)
            except OSError:
                hdr = np.zeros(0, np.uint64)
            if (
                len(hdr) == 2 and hdr[0] == _TOKEN_MAGIC
                and int(hdr[1]) <= self.seq_len
            ):
                raise ValueError(
                    f"corpus too short: {int(hdr[1])} tokens cannot "
                    f"fill one seq_len={self.seq_len} window "
                    "(needs seq_len + 1)"
                )
            raise ValueError(
                f"not a tpu_hpc token dataset (corrupt header?): "
                f"{self.path}"
            )
        nt, nw, mx = (ctypes.c_int64(), ctypes.c_int64(),
                      ctypes.c_int64())
        lib.token_dataset_info(
            self._handle, ctypes.byref(nt), ctypes.byref(nw),
            ctypes.byref(mx),
        )
        self.n_tokens = nt.value
        self.n_windows = nw.value
        self.max_token_id = mx.value
        self._init_stream()

    def _alloc(self):
        # int32 buffers ride the ring's float* interface as raw bit
        # patterns (the C++ side reinterprets; the ring moves bytes).
        shape = (self.batch_size, self.seq_len)
        return np.empty(shape, np.int32), np.empty(shape, np.int32)

    def _ring_next(self, x, y, step) -> int:
        return self._lib.token_dataset_next(
            self._handle, _fptr(x), _fptr(y), ctypes.byref(step)
        )

    def _ring_seek(self, step: int) -> None:
        self._lib.token_dataset_seek(self._handle, step)

    def _sync_batch(self, step: int, x, y) -> None:
        self._lib.token_dataset_batch(
            self._handle, step, _fptr(x), _fptr(y)
        )

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.token_dataset_close(self._handle)
            self._handle = None
