"""ctypes bindings for the native host-side components (native/dllama_native.cpp).

Loading order: $DLLAMA_NATIVE_LIB, then the in-repo build
(native/build/libdllama_native.so — never committed: built with `make` from
native/dllama_native.cpp on first use, so the library that runs is the source
that is checked out; set DLLAMA_NATIVE=0 to disable everything). All callers
must keep a pure-Python fallback — `available()` gating is the contract, a
failed build is logged (not swallowed) when it forces that fallback, and
tests/test_native.py pins C++ == Python semantics.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess

import numpy as np

log = logging.getLogger("dllama_tpu")

_REPO_NATIVE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_lib = None
_tried = False


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("DLLAMA_NATIVE", "1") == "0":
        return None
    candidates = []
    if os.environ.get("DLLAMA_NATIVE_LIB"):
        candidates.append(os.environ["DLLAMA_NATIVE_LIB"])
    built = os.path.join(_REPO_NATIVE, "build", "libdllama_native.so")
    candidates.append(built)
    if not any(os.path.exists(c) for c in candidates) and os.path.exists(
        os.path.join(_REPO_NATIVE, "Makefile")
    ):
        try:
            subprocess.run(
                ["make", "-C", _REPO_NATIVE],
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.SubprocessError) as e:
            log.warning(
                "native library build failed (%r%s); the tokenizer and "
                "quantizer fall back to pure Python", e,
                ": " + e.stderr.decode(errors="replace")[-300:]
                if getattr(e, "stderr", None) else "")
            return None
    for c in candidates:
        if os.path.exists(c):
            try:
                lib = ctypes.CDLL(c)
            except OSError:
                continue
            _bind(lib)
            _lib = lib
            return lib
    return None


def _bind(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.dllama_quantize_q40.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64, u8p, ctypes.POINTER(ctypes.c_uint16)]
    lib.dllama_quantize_q40.restype = None
    lib.dllama_quantize_q80.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int8), ctypes.POINTER(ctypes.c_uint16)]
    lib.dllama_quantize_q80.restype = None
    lib.dllama_tok_create.argtypes = [
        u8p, ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
    lib.dllama_tok_create.restype = ctypes.c_void_p
    lib.dllama_tok_destroy.argtypes = [ctypes.c_void_p]
    lib.dllama_tok_destroy.restype = None
    lib.dllama_tok_encode.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
    lib.dllama_tok_encode.restype = ctypes.c_int32
    # optional symbol: older prebuilt libraries (DLLAMA_NATIVE_LIB) predate
    # it; callers gate on has_q40_shard(), everything else keeps working
    if hasattr(lib, "dllama_q40_shard"):
        lib.dllama_q40_shard.argtypes = [
            u8p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            u8p, ctypes.POINTER(ctypes.c_float)]
        lib.dllama_q40_shard.restype = None


def available() -> bool:
    return _load() is not None


def quantize_q40(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """f32[..., K] -> (packed u8[..., K/32, 16], scales f16[..., K/32]);
    same contract as ops.quant.quantize_q40_np."""
    lib = _load()
    assert lib is not None
    flat = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    nb = flat.size // 32
    packed = np.empty(nb * 16, dtype=np.uint8)
    scales = np.empty(nb, dtype=np.uint16)
    lib.dllama_quantize_q40(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), flat.size,
        packed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        scales.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)))
    shape = x.shape
    return (packed.reshape(*shape[:-1], shape[-1] // 32, 16),
            scales.view(np.float16).reshape(*shape[:-1], shape[-1] // 32))


def quantize_q80(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    lib = _load()
    assert lib is not None
    flat = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    nb = flat.size // 32
    codes = np.empty(flat.size, dtype=np.int8)
    scales = np.empty(nb, dtype=np.uint16)
    lib.dllama_quantize_q80(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), flat.size,
        codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        scales.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)))
    shape = x.shape
    return (codes.reshape(*shape[:-1], shape[-1] // 32, 32),
            scales.view(np.float16).reshape(*shape[:-1], shape[-1] // 32))


def has_q40_shard() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "dllama_q40_shard")


def q40_shard(rec: np.ndarray, n0: int, n1: int, b0: int, b1: int,
              want_packed: bool, want_scales: bool):
    """Decode a device-layout shard from a `.m` Q40 record array
    rec u8[n_out, nb_total, 18] — the C++ twin of LazyQ40's numpy path.
    Returns (packed u8[(b1-b0)*16, n1-n0] | None, scales f32[...] | None)."""
    lib = _load()
    assert lib is not None
    assert rec.ndim == 3 and rec.shape[2] == 18 and rec.dtype == np.uint8
    assert rec.flags["C_CONTIGUOUS"]  # the C++ kernel assumes row stride nb*18
    ns, nbs = n1 - n0, b1 - b0
    packed = np.empty((nbs * 16, ns), np.uint8) if want_packed else None
    scales = np.empty((nbs, ns), np.float32) if want_scales else None
    null_u8 = ctypes.cast(None, ctypes.POINTER(ctypes.c_uint8))
    null_f = ctypes.cast(None, ctypes.POINTER(ctypes.c_float))
    lib.dllama_q40_shard(
        rec.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), rec.shape[1],
        n0, n1, b0, b1,
        packed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)) if want_packed else null_u8,
        scales.ctypes.data_as(ctypes.POINTER(ctypes.c_float)) if want_scales else null_f,
    )
    return packed, scales


class NativeBpe:
    """Persistent native tokenizer handle (built once per Tokenizer)."""

    def __init__(self, vocab: list[bytes], scores: list[float], special_ids: list[int]):
        lib = _load()
        assert lib is not None
        self._lib = lib
        blob = b"".join(vocab)
        offsets = np.zeros(len(vocab) + 1, dtype=np.int64)
        np.cumsum([len(v) for v in vocab], out=offsets[1:])
        self._blob = np.frombuffer(blob, dtype=np.uint8) if blob else np.zeros(1, np.uint8)
        self._offsets = offsets
        self._scores = np.asarray(scores, dtype=np.float32)
        self._specials = np.asarray(special_ids, dtype=np.int32)
        self._handle = lib.dllama_tok_create(
            self._blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            self._scores.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            len(vocab),
            self._specials.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            len(special_ids))

    def encode(self, data: bytes, add_special_tokens: bool) -> list[int] | None:
        """None signals 'cannot tokenize' (caller raises with its own message)."""
        out = np.empty(max(16, 2 * len(data) + 16), dtype=np.int32)
        n = self._lib.dllama_tok_encode(
            self._handle,
            np.frombuffer(data, dtype=np.uint8).ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
            if data else ctypes.cast(0, ctypes.POINTER(ctypes.c_uint8)),
            len(data), int(add_special_tokens),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), out.size)
        if n == -1:
            return None
        assert n >= 0, "native encode output buffer overflow"
        return out[:n].tolist()

    def __del__(self):
        try:
            self._lib.dllama_tok_destroy(self._handle)
        except Exception:
            pass
