# R4 fixture — CONFORMING: every mutation under the lock.
import threading

_LOCK = threading.RLock()
_DROPPED_T1 = 0.0
_JIT_FNS = {}


def record(key, fn):
    global _DROPPED_T1
    with _LOCK:
        _DROPPED_T1 += 1.0
        _JIT_FNS[key] = fn


def snapshot():
    with _LOCK:
        return dict(_JIT_FNS), _DROPPED_T1   # reads are fine anywhere
