"""ctypes binding for the native batch-assembly engine (native/hvt_data.cc).

The framework's native-runtime component (SURVEY.md §2.3: the reference's
C++ layer is Horovod's core; the collective half of that role is owned by
XLA here, the host-IO half is this): a C++ producer thread permutes,
gathers and stages training batches into a ring of reusable buffers while
the accelerator runs the previous step.

`NativeBatchLoader` is a drop-in for the training-path `ArrayDataset`
pipeline (full reshuffle each epoch, repeat-forever, drop-remainder — the
same semantics `Trainer.fit(x=, y=)` builds). The shared library is built
from native/hvt_data.cc at first use (`make`, g++); `available()` reports
whether that worked, and callers fall back to the Python pipeline — after a
warning — when it didn't, so the framework works without a toolchain.

By default each yielded array is an owned copy (safe under any lifetime —
JAX's async device_put may read host buffers after dispatch, and a GC'd
loader frees its slots). The shuffle/gather still happens off-thread; the
one extra memcpy per batch is noise. ``copy=False`` yields zero-copy views
valid only until the next ``__next__`` call and only while the loader
object is alive — for callers that consume synchronously.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Sequence

import numpy as np

from horovod_tpu.analysis import registry

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libhvt_data.so")

_lib = None
_lib_lock = threading.Lock()
_load_failed = False


def _unavailable(why: str) -> None:
    """Record (once — the failure is cached) that the native engine cannot
    be used, and say so: the Python assembler that takes over is a
    different engine, not a silent equivalent."""
    global _load_failed
    _load_failed = True
    warnings.warn(
        f"native batch-assembly engine unavailable ({why}); "
        "Trainer.fit(x=, y=) assembles batches in Python instead"
    )


def _load():
    """Load the shared library, building it from native/hvt_data.cc on
    first use (it is not committed); None — after a warning — on failure."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        if registry.get_flag("HVT_NO_NATIVE"):
            _load_failed = True
            return None
        # Always run make (a no-op when up to date) so the Makefile's source
        # dependency governs rebuilds — a stale .so never shadows an edited
        # hvt_data.cc.
        try:
            subprocess.run(
                ["make", "-s", "libhvt_data.so"],
                cwd=_NATIVE_DIR,
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.SubprocessError) as e:
            if not os.path.exists(_LIB_PATH):
                _unavailable(f"building {_LIB_PATH} failed: {e!r}")
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            _unavailable(f"loading {_LIB_PATH} failed: {e!r}")
            return None
        # ABI handshake: a stale prebuilt .so (no compiler to rebuild,
        # make failed above) predating the epoch-anchored stream would
        # silently IGNORE the extra create arguments — the cursors would
        # then describe a stream nobody produces. Missing symbol or
        # version mismatch → treat the native engine as unavailable
        # (fail-safe, never fail-different-bytes).
        if getattr(lib, "hvt_loader_abi_version", lambda: None)() != 2:
            _unavailable(f"{_LIB_PATH} is a stale build (ABI != 2)")
            return None
        lib.hvt_loader_create.restype = ctypes.c_void_p
        lib.hvt_loader_create.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_int64, ctypes.c_int64,
        ]
        lib.hvt_loader_next.restype = ctypes.c_int
        lib.hvt_loader_next.argtypes = [ctypes.c_void_p]
        lib.hvt_loader_slot_ptr.restype = ctypes.c_void_p
        lib.hvt_loader_slot_ptr.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        lib.hvt_loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.hvt_loader_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class NativeBatchLoader:
    """Infinite iterator of ``(arr_0[batch], arr_1[batch], ...)`` tuples
    assembled off-thread in C++. Fresh full permutation per epoch
    (``shuffle=True``), batches never straddle the epoch remainder."""

    def __init__(
        self,
        arrays: Sequence[np.ndarray],
        batch_size: int,
        seed: int = 0,
        shuffle: bool = True,
        n_slots: int = 4,
        copy: bool = True,
        start_epoch: int = 0,
        batches_per_epoch: int = 0,
    ):
        """``start_epoch``/``batches_per_epoch`` anchor the stream's
        epochs (the durable-cursor contract — see `data.stream` and the
        hvt_data.cc header): every pass's permutation is a pure function
        of ``(seed, epoch, pass)``, so the stream can start at ANY
        absolute epoch without replaying the ones before it.
        ``batches_per_epoch=0`` keeps one-permutation-pass-per-epoch
        semantics; > 0 cuts epochs at exactly that many batches."""
        self.copy = copy
        lib = _load()
        if lib is None:
            raise RuntimeError(
                "native loader unavailable (build native/libhvt_data.so)"
            )
        self._lib = lib
        # Keep C-contiguous copies alive for the library's lifetime — it
        # borrows these base pointers.
        self._arrays = [np.ascontiguousarray(a) for a in arrays]
        n = self._arrays[0].shape[0]
        if any(a.shape[0] != n for a in self._arrays):
            raise ValueError("all arrays must share the leading dimension")
        if batch_size > n:
            raise ValueError(f"batch_size {batch_size} > dataset size {n}")
        self.batch_size = int(batch_size)
        self._shapes = [(self.batch_size,) + a.shape[1:] for a in self._arrays]
        self._dtypes = [a.dtype for a in self._arrays]

        ptrs = (ctypes.c_void_p * len(self._arrays))(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in self._arrays]
        )
        row_bytes = (ctypes.c_int64 * len(self._arrays))(
            *[a.strides[0] for a in self._arrays]
        )
        self._handle = lib.hvt_loader_create(
            ptrs, row_bytes, len(self._arrays), n, self.batch_size,
            n_slots, seed, 1 if shuffle else 0,
            int(start_epoch), int(batches_per_epoch),
        )
        if not self._handle:
            raise RuntimeError("hvt_loader_create failed")
        self._held_slot = -1
        # Cursor bookkeeping (mirrors the producer's position exactly:
        # both sides count consumed batches of the same deterministic
        # stream). Epoch length in batches: the explicit cut when given,
        # else the pass length (drop-remainder permutation batches).
        self._seed = int(seed)
        self._shuffle = bool(shuffle)
        self._batches_per_epoch = (
            int(batches_per_epoch) or n // self.batch_size
        )
        self._epoch = int(start_epoch)
        self._batch_in_epoch = 0

    def _advance(self, n_batches: int = 1) -> None:
        self._batch_in_epoch += n_batches
        while self._batch_in_epoch >= self._batches_per_epoch:
            self._batch_in_epoch -= self._batches_per_epoch
            self._epoch += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self._handle is None:
            raise StopIteration
        if self._held_slot >= 0:
            # Previous batch's buffers are recycled now (documented lifetime).
            self._lib.hvt_loader_release(self._handle, self._held_slot)
            self._held_slot = -1
        slot = self._lib.hvt_loader_next(self._handle)
        if slot < 0:
            raise StopIteration
        self._held_slot = slot
        self._advance()
        out = []
        for idx, (shape, dtype) in enumerate(zip(self._shapes, self._dtypes)):
            ptr = self._lib.hvt_loader_slot_ptr(self._handle, slot, idx)
            size = int(np.prod(shape)) * dtype.itemsize
            buf = (ctypes.c_char * size).from_address(ptr)
            arr = np.frombuffer(buf, dtype=dtype).reshape(shape)
            out.append(arr.copy() if self.copy else arr)
        return tuple(out)

    def skip(self, n_batches: int) -> None:
        """Fast-forward the stream past ``n_batches`` batches without a
        host copy: each skipped slot is advanced and released unread (the
        C++ producer's ring recycles it), so the loader's permutation
        stream lands exactly where an uninterrupted consumer would be —
        the step-granular resume hook (`Trainer.fit(initial_step=)`)."""
        if self._handle is None:
            raise RuntimeError("loader is closed")
        if self._held_slot >= 0:
            self._lib.hvt_loader_release(self._handle, self._held_slot)
            self._held_slot = -1
        for _ in range(int(n_batches)):
            slot = self._lib.hvt_loader_next(self._handle)
            if slot < 0:
                raise RuntimeError("native loader stream ended during skip")
            self._lib.hvt_loader_release(self._handle, slot)
            self._advance()

    def cursor(self):
        """The position of the NEXT batch this loader will yield, as a
        serializable `data.stream.StreamCursor`. Reconstruct with
        `NativeBatchLoader.from_cursor(arrays, cursor)` — byte-identical
        continuation of the same (seed, epoch, pass)-anchored stream."""
        from horovod_tpu.data import stream as stream_lib

        return stream_lib.StreamCursor(
            kind="native", seed=self._seed, epoch=self._epoch,
            step=self._batch_in_epoch,
            position={
                "n_examples": self._arrays[0].shape[0],
                "batch_size": self.batch_size,
                "shuffle": self._shuffle,
                "batches_per_epoch": self._batches_per_epoch,
            },
        )

    @classmethod
    def from_cursor(cls, arrays: Sequence[np.ndarray], cursor, **kw):
        """Rebuild a loader positioned exactly at ``cursor`` (validated
        loudly — format, kind, seed, geometry; `stream.StreamCursorError`
        on any mismatch). The within-epoch offset is skipped natively
        (slots advanced and released, no host copy)."""
        from horovod_tpu.data import stream as stream_lib

        if not isinstance(cursor, stream_lib.StreamCursor):
            cursor = stream_lib.StreamCursor.from_dict(cursor)
        n = int(np.asarray(arrays[0]).shape[0])
        cursor.require("native", n_examples=n)
        try:
            batch_size = int(cursor.position["batch_size"])
            if batch_size < 1:
                raise ValueError(batch_size)
        except (KeyError, TypeError, ValueError):
            raise stream_lib.StreamCursorError(
                "native cursor carries no usable batch_size — refusing "
                "to guess the stream geometry"
            ) from None
        bpe = int(cursor.position.get("batches_per_epoch") or 0)
        loader = cls(
            arrays, batch_size, seed=cursor.seed,
            shuffle=bool(cursor.position.get("shuffle", True)),
            start_epoch=cursor.epoch,
            batches_per_epoch=(
                0 if bpe == n // batch_size else bpe
            ),
            **kw,
        )
        if cursor.step:
            loader.skip(cursor.step)
        return loader

    def close(self):
        if self._handle is not None:
            self._lib.hvt_loader_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
