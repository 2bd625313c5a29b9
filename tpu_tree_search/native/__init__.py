"""ctypes bindings to the native host runtime (libtreesearch_host.so).

Builds the shared library on first use with the system C++ compiler (no
pybind11 in the image; plain C ABI + ctypes keeps the binding dependency-
free). See src/treesearch_host.cpp for what lives natively and why.
"""

from __future__ import annotations

import ctypes
import pathlib
import subprocess

import numpy as np

_DIR = pathlib.Path(__file__).parent
_SRC = _DIR / "src" / "treesearch_host.cpp"
_LIB = _DIR / "libtreesearch_host.so"

_lib = None


def build(force: bool = False) -> pathlib.Path:
    # no -march=native: a checkout is copied between hosts (to the
    # chip's machine among them), and a library tuned for another CPU
    # dies there with SIGILL
    if force or not _LIB.exists() or _LIB.stat().st_mtime < _SRC.stat().st_mtime:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
             "-pthread", str(_SRC), "-o", str(_LIB)],
            check=True, capture_output=True,
        )
    return _LIB


def lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        handle = ctypes.CDLL(str(build()))
        handle.tts_search.restype = ctypes.c_longlong
        handle.tts_search_from.restype = ctypes.c_longlong
        handle.tts_bfs_frontier.restype = ctypes.c_longlong
        handle.tts_nqueens.restype = ctypes.c_longlong
        handle.tts_async_start.restype = ctypes.c_void_p
        handle.tts_async_best.restype = ctypes.c_int
        handle.tts_async_best.argtypes = [ctypes.c_void_p]
        handle.tts_async_offer.restype = None
        handle.tts_async_offer.argtypes = [ctypes.c_void_p, ctypes.c_int]
        handle.tts_async_done.restype = ctypes.c_int
        handle.tts_async_done.argtypes = [ctypes.c_void_p]
        handle.tts_async_join.restype = ctypes.c_longlong
        handle.tts_async_join.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_ulonglong),
            ctypes.POINTER(ctypes.c_ulonglong), ctypes.POINTER(ctypes.c_int)]
        _lib = handle
    return _lib


def processing_times(inst: int) -> np.ndarray:
    h = lib()
    m, n = h.tts_nb_machines(inst), h.tts_nb_jobs(inst)
    out = np.zeros((m, n), dtype=np.int32)
    h.tts_processing_times(inst, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int)))
    return out


def optimal_makespan(inst: int) -> int:
    return lib().tts_optimal_makespan(inst)


def search(p_times: np.ndarray, lb_kind: int = 1, init_ub: int | None = None,
           max_nodes: int = 0):
    """Fast sequential DFS oracle. Returns (tree, sol, best, expanded)."""
    p = np.ascontiguousarray(p_times, dtype=np.int32)
    m, n = p.shape
    tree = ctypes.c_ulonglong()
    sol = ctypes.c_ulonglong()
    best = ctypes.c_int()
    expanded = lib().tts_search(
        p.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), n, m, lb_kind,
        0 if init_ub is None else int(init_ub), ctypes.c_longlong(max_nodes),
        ctypes.byref(tree), ctypes.byref(sol), ctypes.byref(best))
    return int(tree.value), int(sol.value), int(best.value), int(expanded)


def search_from(p_times: np.ndarray, prmu: np.ndarray, depth: np.ndarray,
                lb_kind: int = 1, init_ub: int | None = None,
                n_threads: int = 0):
    """Multi-threaded DFS from a seed set — the heterogeneous hand-off
    path (device residual pool -> host threads). Returns
    (tree, sol, best, expanded)."""
    import os
    p = np.ascontiguousarray(p_times, dtype=np.int32)
    m, n = p.shape
    prmu = np.ascontiguousarray(prmu, dtype=np.int16).reshape(-1, n)
    depth = np.ascontiguousarray(depth, dtype=np.int16).reshape(-1)
    if n_threads <= 0:
        n_threads = max(1, (os.cpu_count() or 2) - 1)
    tree = ctypes.c_ulonglong()
    sol = ctypes.c_ulonglong()
    best = ctypes.c_int()
    expanded = lib().tts_search_from(
        p.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), n, m, lb_kind,
        0 if init_ub is None else int(init_ub),
        prmu.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        depth.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        ctypes.c_longlong(prmu.shape[0]), int(n_threads),
        ctypes.byref(tree), ctypes.byref(sol), ctypes.byref(best))
    return int(tree.value), int(sol.value), int(best.value), int(expanded)


def bfs_frontier(p_times: np.ndarray, lb_kind: int, init_ub: int | None,
                 target: int, cap: int = 1 << 22):
    """Native BFS warm-up. Returns (prmu, depth, tree, sol, best)."""
    p = np.ascontiguousarray(p_times, dtype=np.int32)
    m, n = p.shape
    prmu = np.zeros((cap, n), dtype=np.int16)
    depth = np.zeros(cap, dtype=np.int16)
    tree = ctypes.c_ulonglong()
    sol = ctypes.c_ulonglong()
    best = ctypes.c_int()
    got = lib().tts_bfs_frontier(
        p.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), n, m, lb_kind,
        0 if init_ub is None else int(init_ub),
        ctypes.c_longlong(target), ctypes.c_longlong(cap),
        prmu.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        depth.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        ctypes.byref(tree), ctypes.byref(sol), ctypes.byref(best))
    if got < 0:
        raise RuntimeError("frontier exceeded cap")
    n_nodes = int(got)
    return (prmu[:n_nodes].copy(), depth[:n_nodes].copy(),
            int(tree.value), int(sol.value), int(best.value))


def async_start(p_times: np.ndarray, prmu: np.ndarray, depth: np.ndarray,
                lb_kind: int = 1, init_ub: int | None = None,
                n_threads: int = 0):
    """Start a background multi-threaded DFS over a seed set and return an
    opaque session handle — the CONCURRENT heterogeneous tier: the caller
    keeps driving the device loop while these threads run, merging
    incumbents through async_best/async_offer (checkBest semantics,
    reference: pfsp_multigpu_cuda.c:30-50, 159-263). The native side
    copies all inputs before returning."""
    import os
    p = np.ascontiguousarray(p_times, dtype=np.int32)
    m, n = p.shape
    prmu = np.ascontiguousarray(prmu, dtype=np.int16).reshape(-1, n)
    depth = np.ascontiguousarray(depth, dtype=np.int16).reshape(-1)
    if n_threads <= 0:
        n_threads = max(1, (os.cpu_count() or 2) - 1)
    h = lib().tts_async_start(
        p.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), n, m, lb_kind,
        0 if init_ub is None else int(init_ub),
        prmu.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        depth.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        ctypes.c_longlong(prmu.shape[0]), int(n_threads))
    return h


def async_best(handle) -> int:
    """Current shared incumbent of a running session."""
    return int(lib().tts_async_best(handle))


def async_offer(handle, best: int) -> None:
    """Merge an externally-found incumbent into the session (CAS min)."""
    lib().tts_async_offer(handle, int(best))


def async_done(handle) -> bool:
    """True when every session thread has drained its pool."""
    return bool(lib().tts_async_done(handle))


def async_join(handle):
    """Join the session and free it. Returns (tree, sol, best, expanded)."""
    tree = ctypes.c_ulonglong()
    sol = ctypes.c_ulonglong()
    best = ctypes.c_int()
    expanded = lib().tts_async_join(handle, ctypes.byref(tree),
                                    ctypes.byref(sol), ctypes.byref(best))
    return int(tree.value), int(sol.value), int(best.value), int(expanded)


def nqueens(n: int, g: int = 1):
    """Native N-Queens backtracking. Returns (tree, sol, expanded)."""
    tree = ctypes.c_ulonglong()
    sol = ctypes.c_ulonglong()
    expanded = lib().tts_nqueens(n, g, ctypes.byref(tree), ctypes.byref(sol))
    return int(tree.value), int(sol.value), int(expanded)
