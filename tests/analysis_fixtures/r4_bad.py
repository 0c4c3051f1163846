# R4 fixture — VIOLATING: shared-state mutation outside the lock.
_DROPPED_T1 = 0.0        # module-level init is exempt
_JIT_FNS = {}


def record(key, fn):
    global _DROPPED_T1
    _DROPPED_T1 += 1.0   # unlocked increment
    _JIT_FNS[key] = fn   # unlocked subscript store
    _JIT_FNS.clear()     # unlocked mutating method
