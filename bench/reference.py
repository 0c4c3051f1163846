"""Plain reference of the SparseMap cost model, for deciding `correct`.

A straightforward restatement of the paper's Sparseloop-class analytical
model (SparseMap, arXiv:2508.12906, section IV and Figs. 4-6 and 13):
genome decoding (Cantor-coded loop orders, prime-factor tiling, per
sub-dimension compression formats, skip/gate sites), Timeloop-style
reuse analysis for the fills of every storage level, fiber-tree byte
accounting for the formats, and energy times cycles for the EDP.

It reads the accelerator and the Table III shapes from a configuration
file of the benchmark and imports nothing of the program under test.
Arithmetic is float64.  ``Reference(cfg, rounding=...)`` rounds every
intermediate through ``rounding``; the control passes a bfloat16
rounding (``bf16``) to stand in for a search that prices designs one
precision below the float32 the configuration states.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

FMT_U, FMT_B, FMT_RLE, FMT_CP, FMT_UOP = range(5)
N_FMT_GENES = 5
N_SG = 7
WORD_BYTES = 2.0


def bf16(x: float) -> float:
    """Round a float to the nearest bfloat16 (round half to even)."""
    import ml_dtypes
    import numpy as np
    return float(np.asarray(x, dtype=np.float64).astype(ml_dtypes.bfloat16))


def _exact(x: float) -> float:
    return x


# ------------------------------------------------------------- shapes


def prime_factors(n: int) -> List[int]:
    out, d = [], 2
    while d * d <= n:
        while n % d == 0:
            out.append(d)
            n //= d
        d += 1
    if n > 1:
        out.append(n)
    return out


def pad_to_composite(n: int, max_prime: int = 7) -> int:
    """A size whose largest prime factor exceeds 7 is searched as the
    next larger size that factors into primes <= 7 (paper section IV.B)."""
    while max(prime_factors(n), default=1) > max_prime:
        n += 1
    return n


class Density:
    """A tensor's nonzero statistics: uniform at density ``d``, or n:m
    structured (every aligned block of m elements holds exactly n)."""

    def __init__(self, spec):
        if isinstance(spec, dict):
            self.n, self.m = int(spec["n"]), int(spec["m"])
            self.mean = self.n / self.m
        else:
            self.n = self.m = None
            self.mean = float(spec)

    def block_nonempty(self, elems: float) -> float:
        if self.m is None:
            return 1.0 - (1.0 - self.mean) ** elems
        free = self.m - self.n
        if float(elems) > free:
            return 1.0
        e = float(elems)
        return 1.0 - math.exp(
            math.lgamma(free + 1.0) + math.lgamma(self.m - e + 1.0)
            - math.lgamma(free - e + 1.0) - math.lgamma(self.m + 1.0))


class Shape:
    """One Table III SpMM (or a convolution lowered to one): Z[M,N] +=
    P[M,K] * Q[K,N], with the searched (padded) sizes."""

    DIMS = ("M", "K", "N")
    TENSOR_DIMS = {"P": ("M", "K"), "Q": ("K", "N"), "Z": ("M", "N")}

    def __init__(self, entry: Dict):
        self.name = entry["name"]
        if entry["kind"] == "spmm":
            m, k, n = entry["M"], entry["K"], entry["N"]
            dp = entry.get("structured_P") or entry["density_P_pct"] / 100.0
            dq = entry.get("structured_Q") or entry["density_Q_pct"] / 100.0
        else:
            # implicit GEMM: M = out channels, K = C*R*S, N = output
            # pixels (stride 1, "same" padding r // 2); P holds the
            # weights, Q the im2col input
            pad = entry["R"] // 2
            p_out = entry["H"] + 2 * pad - entry["R"] + 1
            q_out = entry["W"] + 2 * pad - entry["S"] + 1
            m = entry["Kout"]
            k = entry["C"] * entry["R"] * entry["S"]
            n = p_out * q_out
            dp = entry["density_weight_pct"] / 100.0
            dq = entry["density_input_pct"] / 100.0
        self.size = {"M": pad_to_composite(m), "K": pad_to_composite(k),
                     "N": pad_to_composite(n)}
        self.primes = [(d, p) for d in self.DIMS
                       for p in prime_factors(self.size[d])]
        P, Q = Density(dp), Density(dq)
        dz = P.mean * Q.mean
        out = 1.0 - (1.0 - dz) ** self.size["K"] if dz < 1.0 else 1.0
        self.density = {"P": P, "Q": Q, "Z": Density(out)}
        self.macs = self.size["M"] * self.size["K"] * self.size["N"]

    def elems(self, tensor: str, ext: Dict[str, int]) -> int:
        n = 1
        for d in self.TENSOR_DIMS[tensor]:
            n *= ext[d]
        return n


# ------------------------------------------------------------- accelerator


class Accelerator:
    """A memory hierarchy from the configuration file: stores outermost
    first; every store below the backing one owns a temporal mapping
    level and, when spatial, a spatial level right below it."""

    def __init__(self, desc: Dict):
        self.name = desc["name"]
        self.e_mac = float(desc["mac_energy_pj"])
        self.stores = desc["levels"]
        self.level_names, self.level_store, self.spatial = [], [], []
        for k in range(1, len(self.stores)):
            self.level_names.append(f"L{k}_T")
            self.level_store.append(k)
            self.spatial.append(False)
            if self.stores[k].get("spatial", False):
                self.level_names.append(f"L{k}_S")
                self.level_store.append(k)
                self.spatial.append(True)
        self.n_levels = len(self.level_names)
        self.sites = [s["sg_site"] for s in self.stores
                      if s.get("sg_site")] + ["C"]

    def outer_levels(self, k: int) -> List[int]:
        """Mapping levels above the fill edge into store k."""
        return [i for i, s in enumerate(self.level_store) if s <= k]

    def inner_levels(self, k: int) -> List[int]:
        return [i for i, s in enumerate(self.level_store) if s > k]

    def word(self, k: int) -> float:
        return float(self.stores[k].get("word_bytes", WORD_BYTES))

    def edge_site(self, k: int) -> Optional[str]:
        """S/G site filtering the fill edge into store k: the site the
        parent store declares (the backing store declares none)."""
        return self.stores[k - 1].get("sg_site")


# ------------------------------------------------------------- decoding


def cantor_decode(code: int, d: int) -> Tuple[int, ...]:
    avail = list(range(d))
    out = []
    for i in range(d):
        f = math.factorial(d - 1 - i)
        idx, code = divmod(code, f)
        out.append(avail.pop(idx))
    return tuple(out)


def leaders(sg: int) -> Tuple[str, ...]:
    return {1: ("Q",), 4: ("Q",), 2: ("P",), 5: ("P",),
            3: ("P", "Q"), 6: ("P", "Q")}.get(sg, ())


def followers(sg: int) -> Tuple[str, ...]:
    return {1: ("P",), 4: ("P",), 2: ("Q",), 5: ("Q",),
            3: ("P", "Q"), 6: ("P", "Q")}.get(sg, ())


def is_skip(sg: int) -> bool:
    return sg in (4, 5, 6)


def is_gate(sg: int) -> bool:
    return sg in (1, 2, 3)


class Design:
    """A decoded genome: per-level tiling factors and loop orders, the
    formats of each tensor's tiled sub-dimensions, the S/G choices."""

    def __init__(self, shape: Shape, acc: Accelerator, genome: Sequence[int]):
        nl = acc.n_levels
        g = [int(x) for x in genome]
        np_ = len(shape.primes)
        n_sites = len(acc.sites)
        length = nl + np_ + 3 * N_FMT_GENES + n_sites
        if len(g) != length:
            raise ValueError(f"genome length {len(g)} != {length}")
        perm_g, til_g = g[:nl], g[nl:nl + np_]
        fmt_g = g[nl + np_:nl + np_ + 3 * N_FMT_GENES]
        sg_g = g[nl + np_ + 3 * N_FMT_GENES:]
        if any(not 0 <= c < 6 for c in perm_g) or \
                any(not 0 <= t < nl for t in til_g) or \
                any(not 0 <= f < 5 for f in fmt_g) or \
                any(not 0 <= s < N_SG for s in sg_g):
            raise ValueError("gene out of range")
        self.factors = [dict.fromkeys(Shape.DIMS, 1) for _ in range(nl)]
        for (dim, p), lvl in zip(shape.primes, til_g):
            self.factors[lvl][dim] *= p
        self.perms = [tuple(Shape.DIMS[i] for i in cantor_decode(c, 3))
                      for c in perm_g]
        self.shape, self.acc = shape, acc
        self.formats = {}
        for j, t in enumerate(("P", "Q", "Z")):
            genes = fmt_g[j * N_FMT_GENES:(j + 1) * N_FMT_GENES]
            subs = self.subdims(t)
            k = len(subs)
            fm = genes[N_FMT_GENES - k:] if k <= N_FMT_GENES else \
                genes + [FMT_U] * (k - N_FMT_GENES)
            self.formats[t] = (tuple(fm), tuple(s for _, _, s in subs))
        self.sg = dict(zip(acc.sites, sg_g))

    def subdims(self, t: str) -> List[Tuple[int, str, int]]:
        """Tiled sub-dimensions of tensor t (factor > 1), outer first."""
        dims = Shape.TENSOR_DIMS[t]
        return [(lvl, d, self.factors[lvl][d])
                for lvl in range(self.acc.n_levels)
                for d in self.perms[lvl]
                if d in dims and self.factors[lvl][d] > 1]

    def tile(self, k: int) -> Dict[str, int]:
        ext = dict.fromkeys(Shape.DIMS, 1)
        for lvl in self.acc.inner_levels(k):
            for d in ext:
                ext[d] *= self.factors[lvl][d]
        return ext


# ------------------------------------------------------------- the model


class Reference:
    """Prices decoded designs.  ``price(genome)`` returns (valid, edp,
    occupancy margins); ``rounding`` is applied to every intermediate."""

    def __init__(self, accelerator: Dict,
                 rounding: Callable[[float], float] = _exact):
        self.acc = Accelerator(accelerator)
        self.q = rounding

    def _format_bytes(self, fmt, dens: Density, n_tile: float,
                      word: float) -> float:
        """Bytes of an n_tile-element tile under a fiber-tree format,
        scaled from the whole tensor's accounting."""
        q = self.q
        formats, lens = fmt
        n = 1
        for L in lens:
            n *= L
        if all(f == FMT_U for f in formats):
            return q(q(n * word) * q(n_tile / max(n, 1)))
        data = q(q(n * dens.mean) * word)
        meta = 0.0
        fibers = 1.0
        below = n
        for f, L in zip(formats, lens):
            below //= max(L, 1)
            kept = q(L * q(dens.block_nonempty(max(below, 1))))
            if f == FMT_B:
                meta = q(meta + q(fibers * L))
            elif f in (FMT_RLE, FMT_CP):
                meta = q(meta + q(q(fibers * kept) * _clog2(L)))
            elif f == FMT_UOP:
                meta = q(meta + q(q(fibers * (L + 1)) *
                                  _clog2(max(n, 2))))
            fibers = q(fibers * (L if f == FMT_U else kept))
        return q(q(data + q(meta / 8.0)) * q(n_tile / max(n, 1)))

    def _fills(self, dz: Design, k: int, t: str) -> float:
        """Element fills of tensor t into store k (dense)."""
        acc, q = self.acc, self.q
        rel = set(Shape.TENSOR_DIMS[t])
        outer_set = set(acc.outer_levels(k))
        loops = [(lvl, d, dz.factors[lvl][d], acc.spatial[lvl])
                 for lvl in range(acc.n_levels) for d in dz.perms[lvl]
                 if lvl in outer_set and dz.factors[lvl][d] > 1]
        noc = acc.stores[k].get("noc", {})
        out = t == "Z"
        scheme = noc.get("reduction" if out else "multicast", "all")
        fan = noc.get("reduction_fanout" if out else "multicast_fanout")
        suffix = 0
        for _, d, _, _ in reversed(loops):
            if d in rel:
                break
            suffix += 1
        body = loops[:len(loops) - suffix]
        mult = 1.0
        for _, d, b, sp in body:
            if d in rel or not sp or scheme == "none":
                mult = q(mult * b)
        if scheme == "none":
            for _, d, b, sp in loops[len(loops) - suffix:]:
                if sp:
                    mult = q(mult * b)
        elif scheme not in ("all", "none"):
            s_irrel = 1.0
            for _, d, b, sp in loops:
                if sp and d not in rel:
                    s_irrel = q(s_irrel * b)
            mult = q(mult * max(q(s_irrel / fan), 1.0))
        return q(dz.shape.elems(t, dz.tile(k)) * mult)

    def price(self, shape: Shape, genome: Sequence[int]) -> Dict:
        acc, q = self.acc, self.q
        dz = Design(shape, acc, genome)
        # spatial fanout caps
        for lvl in range(acc.n_levels):
            if acc.spatial[lvl]:
                fan = 1
                for d in Shape.DIMS:
                    fan *= dz.factors[lvl][d]
                if fan > acc.stores[acc.level_store[lvl]].get("fanout", 1):
                    return dict(valid=False, why="fanout", margin=1.0)
        # formats and S/G
        for t, (formats, _) in dz.formats.items():
            if formats and formats[-1] == FMT_UOP:
                return dict(valid=False, why="UOP innermost", margin=1.0)
            for i, f in enumerate(formats):
                if f == FMT_UOP and all(g == FMT_U for g in formats[i + 1:]):
                    return dict(valid=False, why="UOP alone", margin=1.0)
            for i, (lvl, _, _) in enumerate(dz.subdims(t)):
                if acc.spatial[lvl] and i < len(formats) and \
                        formats[i] != FMT_U:
                    return dict(valid=False, why="spatial compressed",
                                margin=1.0)
        for site, sg in dz.sg.items():
            if is_skip(sg) and any(
                    all(f == FMT_U for f in dz.formats[ld][0])
                    for ld in leaders(sg)):
                return dict(valid=False, why="skip uncompressed leader",
                            margin=1.0)
        dens = shape.density
        # capacities, at each store's word width
        margin = 1.0
        over = False
        for k in range(1, len(acc.stores)):
            cap = acc.stores[k].get("capacity_bytes")
            if cap is None:
                continue
            ext = dz.tile(k)
            occ = 0.0
            for t in ("P", "Q", "Z"):
                occ = q(occ + self._format_bytes(
                    dz.formats[t], dens[t], shape.elems(t, ext),
                    acc.word(k)))
            margin = min(margin, abs(occ - cap) / cap)
            over = over or occ > cap
        # bytes per dense position, per edge word width
        full = {t: shape.elems(t, shape.size) for t in ("P", "Q", "Z")}
        ratio = {}
        for k in range(1, len(acc.stores)):
            wb = acc.word(k)
            for t in ("P", "Q", "Z"):
                if (t, wb) not in ratio:
                    ratio[(t, wb)] = q(self._format_bytes(
                        dz.formats[t], dens[t], full[t], wb) /
                        max(q(full[t] * wb), 1))
        hit = {t: dens[t].mean for t in ("P", "Q")}

        def frac(site, t, energy):
            sg = dz.sg[site]
            if t not in followers(sg):
                return 1.0
            if is_skip(sg) or (energy and is_gate(sg)):
                f = 1.0
                for ld in leaders(sg):
                    if ld != t:
                        f = q(f * hit[ld])
                return f
            return 1.0

        energy_bytes, time_bytes = {}, {}
        for k in range(1, len(acc.stores)):
            wb = acc.word(k)
            site = acc.edge_site(k)
            eb = tb = 0.0
            for t in ("P", "Q", "Z"):
                fills = self._fills(dz, k, t)
                if t == "Z":
                    fills = max(q(q(2.0 * fills) - full["Z"]),
                                float(full["Z"]))
                b = q(q(fills * wb) * ratio[(t, wb)])
                fe = ft = 1.0
                if site is not None:
                    fe, ft = frac(site, t, True), frac(site, t, False)
                eb = q(eb + q(b * fe))
                tb = q(tb + q(b * ft))
            energy_bytes[k], time_bytes[k] = eb, tb
        cyc_lead, e_lead = set(), set()
        for site in acc.sites:
            sg = dz.sg[site]
            if is_skip(sg):
                cyc_lead.update(leaders(sg))
                e_lead.update(leaders(sg))
            elif is_gate(sg):
                e_lead.update(leaders(sg))
        cyc_frac = e_frac = 1.0
        for ld in cyc_lead:
            cyc_frac = q(cyc_frac * hit[ld])
        for ld in e_lead:
            e_frac = q(e_frac * hit[ld])
        iters = 1
        for lvl in range(acc.n_levels):
            if not acc.spatial[lvl]:
                for d in Shape.DIMS:
                    iters *= dz.factors[lvl][d]
        cycles = q(float(iters) * cyc_frac)
        energy = 0.0
        for k in range(1, len(acc.stores)):
            for _, comps in acc.stores[k]["fill_energy_pj_per_byte"]:
                s = 0.0
                for c in comps:
                    s = q(s + c)
                energy = q(energy + q(energy_bytes[k] * s))
        energy = q(energy + q(q(float(shape.macs) * e_frac) * acc.e_mac))
        for k in range(1, len(acc.stores)):
            bw = acc.stores[k].get("fill_bytes_per_cycle")
            if bw is not None:
                cycles = max(cycles, q(time_bytes[k] / bw))
        edp = q(cycles * energy)
        return dict(valid=not over, why="capacity" if over else "",
                    margin=margin, edp=edp)


def _clog2(x: float) -> float:
    return max(1.0, math.ceil(math.log2(max(x, 2))))
