"""Native (C++) host-side data pipeline (ref: paddle/fluid/operators/reader/*,
python/paddle/distributed/fleet/data_generator/*).

The reference keeps the GPU fed with C++ DataLoader workers; on TPU the
equivalent job is assembling token batches on the host fast enough to overlap
with jitted device steps. ``native/dataio.cpp`` provides:

  * mmap'd token-corpus reader (u16 / u32 / i64 token files)
  * a *stateless-permutation* sampler: sample order is a Feistel permutation
    of window indices keyed by (seed, epoch) — deterministic, infinitely
    streaming, and checkpointable with a single integer (the batch cursor)
  * multithreaded batch assembly with strict in-order emission

The pure-Python fallback below implements bit-identical sampling (same
splitmix64/Feistel arithmetic) so behavior is unchanged when a C++ toolchain
is unavailable; tests assert C++/Python parity.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "dataio.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
_LIB_PATH = os.path.join(_BUILD_DIR, "libdataio.so")

_lib = None
_lib_lock = threading.Lock()
_MASK64 = (1 << 64) - 1


_build_error = None


def _compile_lib():
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"  # per-pid: concurrent ranks may race
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"g++ build of {_SRC} failed:\n{e.stderr.decode(errors='replace')}") from e
    os.replace(tmp, _LIB_PATH)


def load_library(rebuild=False):
    """Build (if needed) and load the native dataio library, or None."""
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None and not rebuild:
            return _lib
        if _build_error is not None and not rebuild:
            return None  # don't retry a known-broken toolchain every call
        try:
            # native/build/ is not committed: the library is always built
            # here, from the source beside it, when missing or older
            if (rebuild or not os.path.exists(_LIB_PATH)
                    or os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC)):
                _compile_lib()
            lib = ctypes.CDLL(_LIB_PATH)
        except (OSError, RuntimeError, FileNotFoundError) as e:
            _build_error = e
            return None
        lib.dio_corpus_open.restype = ctypes.c_void_p
        lib.dio_corpus_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.dio_corpus_len.restype = ctypes.c_longlong
        lib.dio_corpus_len.argtypes = [ctypes.c_void_p]
        lib.dio_corpus_close.argtypes = [ctypes.c_void_p]
        lib.dio_stream_create.restype = ctypes.c_void_p
        lib.dio_stream_create.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_ulonglong, ctypes.c_int, ctypes.c_int,
        ]
        lib.dio_stream_nwindows.restype = ctypes.c_longlong
        lib.dio_stream_nwindows.argtypes = [ctypes.c_void_p]
        lib.dio_stream_next.restype = ctypes.c_int
        lib.dio_stream_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
        lib.dio_stream_state.restype = ctypes.c_longlong
        lib.dio_stream_state.argtypes = [ctypes.c_void_p]
        lib.dio_stream_seek.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.dio_stream_destroy.argtypes = [ctypes.c_void_p]
        lib.dio_feistel.restype = ctypes.c_longlong
        lib.dio_feistel.argtypes = [ctypes.c_longlong, ctypes.c_longlong, ctypes.c_ulonglong]
        _lib = lib
        return _lib


# ---------------------------------------------------------------------------
# Python mirror of the C++ sampling arithmetic (bit-identical).
# ---------------------------------------------------------------------------

def splitmix64(x):
    x = (x + 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


def feistel_permute(idx, n, key):
    """Stateless pseudo-random permutation of [0, n) (cycle-walking Feistel)."""
    if n <= 1:
        return 0
    bits = 0
    while (1 << bits) < n:
        bits += 1
    half = (bits + 1) // 2
    mask = (1 << half) - 1
    x = idx
    while True:
        l, r = x >> half, x & mask
        for rnd in range(4):
            f = splitmix64(r ^ splitmix64((key + rnd) & _MASK64)) & mask
            l, r = r, l ^ f
        x = (l << half) | r
        if x < n:
            return x


def _epoch_key(seed, epoch):
    return splitmix64((seed ^ splitmix64(epoch)) & _MASK64)


def sample_to_window(sample, nwindows, seed):
    epoch, in_epoch = divmod(sample, nwindows)
    return feistel_permute(in_epoch, nwindows, _epoch_key(seed, epoch))


_TOKEN_BYTES = {np.dtype(np.uint16): 2, np.dtype(np.uint32): 4, np.dtype(np.int32): 4,
                np.dtype(np.int64): 8}


class TokenStream:
    """Deterministic infinite (input, label) batch stream over a token file.

    Each sample is a non-overlapping window of ``seq_len + 1`` tokens; inputs
    are tokens [0:seq_len), labels are shifted by one. ``state_dict`` /
    ``set_state_dict`` checkpoint the cursor for exact resume, which the
    elastic restart harness builds on.
    """

    def __init__(self, path, seq_len, batch_size, seed=0, dtype=np.uint16,
                 num_threads=4, queue_depth=8, backend="auto"):
        self.path = os.fspath(path)
        self.seq_len = int(seq_len)
        self.batch_size = int(batch_size)
        self.seed = int(seed) & _MASK64
        self.dtype = np.dtype(dtype)
        if self.dtype not in _TOKEN_BYTES:
            raise ValueError(f"unsupported token dtype {dtype}")
        self._token_bytes = _TOKEN_BYTES[self.dtype]
        self._native = None
        self._mmap = None
        self._cursor = 0  # python-backend batch cursor

        lib = load_library() if backend in ("auto", "native") else None
        if backend == "native" and lib is None:
            raise RuntimeError(f"native dataio library unavailable: {_build_error}")
        if lib is not None:
            corpus = lib.dio_corpus_open(self.path.encode(), self._token_bytes)
            if not corpus:
                raise FileNotFoundError(f"cannot open token corpus {self.path}")
            stream = lib.dio_stream_create(
                corpus, self.seq_len, self.batch_size, self.seed,
                int(num_threads), int(queue_depth))
            if not stream:
                lib.dio_corpus_close(corpus)
                raise ValueError("corpus too small for seq_len")
            self._native = (lib, corpus, stream)
            self.ntokens = int(lib.dio_corpus_len(corpus))
            self.nwindows = int(lib.dio_stream_nwindows(stream))
        else:
            self._mmap = np.memmap(self.path, dtype=self.dtype, mode="r")
            self.ntokens = int(self._mmap.shape[0])
            self.nwindows = (self.ntokens - 1) // self.seq_len
            if self.nwindows <= 0:
                raise ValueError("corpus too small for seq_len")
        self.batches_per_epoch = self.nwindows // self.batch_size

    @property
    def backend(self):
        return "native" if self._native is not None else "python"

    def _next_python(self):
        row = self.seq_len + 1
        out = np.empty((self.batch_size, row), dtype=np.int32)
        base_sample = self._cursor * self.batch_size
        for j in range(self.batch_size):
            w = sample_to_window(base_sample + j, self.nwindows, self.seed)
            out[j] = self._mmap[w * self.seq_len: w * self.seq_len + row].astype(np.int32)
        self._cursor += 1
        return out

    def next(self):
        """Return (inputs, labels), each int32 [batch_size, seq_len]."""
        if self._native is not None:
            lib, _, stream = self._native
            buf = np.empty((self.batch_size, self.seq_len + 1), dtype=np.int32)
            ok = lib.dio_stream_next(stream, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            if not ok:
                raise RuntimeError("native stream stopped")
        else:
            buf = self._next_python()
        return buf[:, :-1], buf[:, 1:]

    def __iter__(self):
        while True:
            yield self.next()

    def state_dict(self):
        if self._native is not None:
            lib, _, stream = self._native
            cursor = int(lib.dio_stream_state(stream))
        else:
            cursor = self._cursor
        return {"cursor": cursor, "seed": self.seed, "seq_len": self.seq_len,
                "batch_size": self.batch_size}

    def set_state_dict(self, state):
        for k in ("seed", "seq_len", "batch_size"):
            if k in state and int(state[k]) != getattr(self, k):
                raise ValueError(
                    f"stream {k}={getattr(self, k)} does not match checkpoint "
                    f"{k}={state[k]}; exact resume would replay different data")
        cursor = int(state["cursor"])
        if self._native is not None:
            lib, _, stream = self._native
            lib.dio_stream_seek(stream, cursor)
        else:
            self._cursor = cursor

    def close(self):
        if self._native is not None:
            lib, corpus, stream = self._native
            lib.dio_stream_destroy(stream)
            lib.dio_corpus_close(corpus)
            self._native = None
        self._mmap = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def write_token_file(path, tokens, dtype=np.uint16):
    """Helper: write a flat token array as a corpus file TokenStream can read."""
    np.asarray(tokens, dtype=dtype).tofile(os.fspath(path))
