"""Plain reference of the search's hardware score on a TPU v5e chip.

The paper's dataflow model (HASS, arXiv 2406.03088, Eq. 1-5) with the TPU
mapping the configuration states: a layer's engine is ``spe`` MXU tile-row
lanes of ``n`` multiply-accumulates each, it skips whole all-zero weight
tiles only, and one chip offers 4 MXUs x 128 lanes. The design search is
the paper's greedy: start every layer at one lane, grow the slowest layer
by the doubling that buys the most rate per lane, shrink every other layer
to the least design that still keeps up, stop when the chip is full. The
Eq. 6 hardware terms are read off the (lanes, rate) frontier of that path
at a quarter, half, three quarters and all of the chip.

Written from those equations, in plain Python floats, layer by layer; it
shares no code with the program. For the control, a layer built with
``low_precision`` computes its rates in float32, and every sum and ratio
that takes them stays in float32.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import numpy as np

FREQ_HZ = 940e6            # v5e MXU clock
LANES = 4 * 128            # one chip: 4 MXUs x 128 rows
TILE = 128                 # a lane's n MACs occupy n / 128 of a tile row
BUDGET_FRACS = (0.25, 0.5, 0.75, 1.0)


class Layer:
    """One pipeline stage: dense MACs per image, dot-product length, and
    the fraction of its weight tiles that are all zero."""

    def __init__(self, macs: int, m_dot: int, tile_sparsity: float = 0.0,
                 low_precision: bool = False):
        self.macs, self.m_dot = int(macs), int(m_dot)
        self.s = float(tile_sparsity)
        self.max_n = max(1, self.m_dot)
        self.max_spe = max(1, self.macs // max(self.m_dot, 1))
        self.f = np.float32 if low_precision else float

    def rate(self, spe: int, n: int) -> float:
        """Images per cycle (Eq. 1-2): each lane needs
        ceil((1 - s) M / n) cycles per dot product."""
        if not self.macs:
            return math.inf
        f = self.f
        t = max(1, math.ceil(f(1.0 - self.s) * f(self.m_dot) / f(max(n, 1))))
        return f(spe) * f(self.m_dot) / (f(self.macs) * f(t))


def _lanes(design) -> float:
    return sum(s * n / TILE for s, n in design)


def _balance(layers, design, protect, lo) -> List[list]:
    """Shrink each unprotected layer, halving n first and then spe, while
    its rate stays at or above ``lo`` (Eq. 4-5)."""
    out = []
    for i, (l, (s, n)) in enumerate(zip(layers, design)):
        if i not in protect:
            while True:
                if n > 1 and l.rate(s, n // 2) >= lo:
                    n //= 2
                elif s > 1 and l.rate(s // 2, n) >= lo:
                    s //= 2
                else:
                    break
        out.append([s, n])
    return out


def greedy(layers: Sequence[Layer], budget: float, max_iters: int):
    """Returns the path's (lanes, rate) points, the final trimmed design's
    point last."""
    design = [[1, 1] for _ in layers]
    path = []
    for _ in range(max_iters):
        rates = [l.rate(s, n) for l, (s, n) in zip(layers, design)]
        cur = min(rates)
        path.append((_lanes(design), cur))
        slow = rates.index(cur)
        l, (s, n) = layers[slow], design[slow]
        opts = []
        if n < l.max_n:
            opts.append([s, min(2 * n, l.max_n)])
        if s < l.max_spe:
            opts.append([min(2 * s, l.max_spe), n])
        if not opts:
            break
        here = s * n / TILE

        def gain(o):
            return (l.rate(*o) - cur) / max(o[0] * o[1] / TILE - here, 1e-9)
        best = opts[0]
        for o in opts[1:]:
            if gain(o) > gain(best):
                best = o
        cand = [list(d) for d in design]
        cand[slow] = best
        theta = min(x.rate(*d) for x, d in zip(layers, cand))
        cand = _balance(layers, cand, {slow}, theta * (1 + 1e-9))
        if _lanes(cand) > budget:
            break
        design = cand
    rates = [l.rate(s, n) for l, (s, n) in zip(layers, design)]
    theta = min(rates)
    keep = {i for i, r in enumerate(rates) if r <= theta * (1 + 1e-9)}
    design = _balance(layers, design, keep, theta * (1 - 1e-12))
    final = (_lanes(design), min(l.rate(s, n)
                                 for l, (s, n) in zip(layers, design)))
    return path + [final]


def frontier(points):
    """Non-dominated (lanes, rate) points of a search path, cheapest first.
    Path states that tie the final rate, or cost at least as much without
    beating it, give way to the final point."""
    f_res, f_thr = points[-1]
    hi = f_thr * (1 + 1e-9)
    cand = [p for p in points[:-1]
            if not (f_thr * (1 - 1e-9) <= p[1] <= hi
                    or (p[0] >= f_res and p[1] <= hi))] + [points[-1]]
    cand.sort(key=lambda p: (p[0], -p[1]))
    out, best = [], -math.inf
    for p in cand:
        if p[1] > best:
            out.append(p)
            best = p[1]
    return out


def hardware_terms(layers: Sequence[Layer], dense_rate_hz: float,
                   max_iters: int, budget: float = LANES) -> dict:
    """Eq. 6 hardware terms of one proposal: ``thr`` (images/s at the whole
    chip), ``eff`` (images/s per lane there), and the mean over the budget
    fractions of ``thr_norm`` = log2(1 + thr / dense thr) / 4 and of
    ``dsp`` = lanes used / lanes of the chip."""
    front = frontier(greedy(layers, budget, max_iters))

    def under(b):
        k = -1
        for j, (r, _) in enumerate(front):
            if r <= b:
                k = j
        return max(k, 0)

    def thr_hz(k):
        return front[k][1] * FREQ_HZ

    tn = [math.log2(1.0 + thr_hz(under(f * budget)) / max(dense_rate_hz, 1e-9))
          / 4.0 for f in BUDGET_FRACS]
    dp = [front[under(f * budget)][0] / budget for f in BUDGET_FRACS]
    k = under(budget)
    return {"thr": thr_hz(k), "thr_norm": sum(tn) / len(tn),
            "dsp": sum(dp) / len(dp),
            "eff": thr_hz(k) / max(front[k][0], 1e-9)}


def dense_rate_hz(layers: Sequence[Layer], max_iters: int,
                  budget: float = LANES) -> float:
    """Images/s of the unpruned network at the whole chip."""
    dense = [Layer(l.macs, l.m_dot, 0.0, l.f is np.float32) for l in layers]
    return greedy(dense, budget, max_iters)[-1][1] * FREQ_HZ
