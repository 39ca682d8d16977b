"""Client-side rates from polled cumulative counters.

The program's ledger is fed in bursts (once per drained window of epochs),
so a counter polled over STATUS is a staircase. The information is in the
CHANGE POINTS: the poll time at which each new value was first seen. Each
is late by at most one poll period, uniformly, which shifts the fitted line
but does not tilt it; the least-squares slope over the change points
averages that error away, where last-minus-first over elapsed would carry
two full poll errors into the rate.

The tilt that is left is the END POINTS': a span of feeds is a whole number
of polls, so on a fixed 0.2 s grid a run reads one of two levels 1% apart
(24 gaps in 19.2 or 19.4 s; PERF.md section 6, PR 41). The harness
therefore asks more often just where a feed is due (perf/run.py
``Poller``), and ``feeds`` keeps beside each change point how far apart the
two polls were that it fell between: its stamp is their middle, and a fit
given those widths weighs a point by ``1 / width**2`` — a feed caught on the
coarse grid (the first two of a job, one that came early) tells the line
little, one caught between two close polls pins it.

A program that stalls once (both tenants of ``gpt2-124m.pair`` stood still
for seconds in about one run of eight, PERF.md section 6) puts one long gap
among the feeds, and a single line through all of them then reads a fifth
low in that run and no other. ``steady`` therefore fits the REGULAR
stretches: a gap outside ``median gap / STALL_FACTOR .. x STALL_FACTOR``
breaks the points into segments that share one slope and keep an intercept
each (the least-squares answer when the time lost at a break is unknown),
and the time the breaks lost is reported beside the rate as ``stall_s``. A
run with no such gap gives ``slope``'s numbers to the last bit.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

Point = Tuple[float, float]


def change_points(polls: Sequence[Point]) -> List[Point]:
    """``[(t, value)]`` polls in time order -> the polls at which the value
    differed from the poll before (the first poll is not a change)."""
    out: List[Point] = []
    for (_, prev), (t, value) in zip(polls, polls[1:]):
        if value != prev:
            out.append((t, value))
    return out


def feeds(polls: Sequence[Point]) -> List[Tuple[float, float, float]]:
    """``[(t, value)]`` polls in time order -> ``[(stamp, value, width)]``,
    one for each change of the value: the middle of the two polls between
    which it changed, and their distance."""
    return [(0.5 * (t0 + t), value, t - t0)
            for (t0, prev), (t, value) in zip(polls, polls[1:])
            if value != prev]


def _weights(points: Sequence[Point], widths: Optional[Sequence[float]]
             ) -> List[float]:
    if widths is None:
        return [1.0] * len(points)
    return [1.0 / (w * w) for w in widths]


def slope(points: Sequence[Point], widths: Optional[Sequence[float]] = None
          ) -> Optional[Dict[str, float]]:
    """Least-squares slope of value against time over ``points`` (>= 2),
    each weighed by ``1 / width**2`` where ``widths`` says how well its time
    is known (``feeds``), equally without:
    ``{"rate", "n", "span_s", "residual_s", "max_gap_s"}`` — ``residual_s``
    is the RMS distance of the points from the line, in seconds of the time
    axis; ``max_gap_s`` is the longest time between two points, which tells
    a run in which the program stalled (one gap of several feeds' length)
    from one that was slower throughout."""
    n = len(points)
    if n < 2:
        return None
    ws = _weights(points, widths)
    sw = sum(ws)
    mt = sum(w * t for w, (t, _) in zip(ws, points)) / sw
    mv = sum(w * v for w, (_, v) in zip(ws, points)) / sw
    stt = sum(w * (t - mt) ** 2 for w, (t, _) in zip(ws, points))
    if stt <= 0:
        return None
    rate = sum(w * (t - mt) * (v - mv) for w, (t, v) in zip(ws, points)) / stt
    if rate <= 0:
        return None
    rss = sum(w * (v - mv - rate * (t - mt)) ** 2
              for w, (t, v) in zip(ws, points))
    return {"rate": rate, "n": n, "span_s": points[-1][0] - points[0][0],
            "residual_s": math.sqrt(rss / sw) / rate,
            "max_gap_s": max(b[0] - a[0] for a, b in zip(points, points[1:]))}


#: a gap between two feeds this many times longer (or shorter) than the
#: median gap is a break: polled feeds are regular within a poll period
#: (gap / median 0.8-1.2 in every cell), a stall costs a whole feed or more
STALL_FACTOR = 1.5


def steady(points: Sequence[Point], poll_s: float = 0.0,
           widths: Optional[Sequence[float]] = None
           ) -> Optional[Dict[str, float]]:
    """``slope`` over the regular stretches of ``points`` (module
    docstring): the pooled within-segment least-squares slope, weighed by
    ``widths`` as ``slope`` is. A gap within
    two poll periods (``poll_s``) of the median is never a break: that much
    the polling alone can move it, which matters once feeds come nearly as
    fast as polls. Adds
    ``stalls`` (breaks found), ``stall_s`` (sum over the breaks of gap -
    median gap: the window's seconds lost to them; a late feed's short
    gap counts negative) and ``whole_rate`` (the one-line fit, stalls
    and all); ``residual_s`` is about each segment's own line."""
    whole = slope(points, widths)
    if whole is None:
        return None
    gaps = [b[0] - a[0] for a, b in zip(points, points[1:])]
    med = sorted(gaps)[len(gaps) // 2]
    odd = [(g > med * STALL_FACTOR or g < med / STALL_FACTOR)
           and abs(g - med) > 2 * poll_s for g in gaps]
    ws = _weights(points, widths)
    segments: List[List[Tuple[float, float, float]]] = [[(*points[0], ws[0])]]
    for point, w, broke in zip(points[1:], ws[1:], odd):
        if broke:
            segments.append([])
        segments[-1].append((*point, w))
    out = dict(whole, whole_rate=whole["rate"], stalls=float(sum(odd)),
               stall_s=sum(g - med for g, o in zip(gaps, odd) if o))
    if not any(odd):
        return out
    centred: List[Tuple[float, float, float]] = []
    for seg in segments:
        sw = sum(w for _, _, w in seg)
        mt = sum(w * t for t, _, w in seg) / sw
        mv = sum(w * v for _, v, w in seg) / sw
        centred += [(t - mt, v - mv, w) for t, v, w in seg]
    stt = sum(w * t * t for t, _, w in centred)
    stv = sum(w * t * v for t, v, w in centred)
    if stt <= 0 or stv <= 0:
        return out  # no stretch of two regular feeds: the one line stands
    rate = stv / stt
    rss = sum(w * (v - rate * t) ** 2 for t, v, w in centred)
    out.update(rate=rate, residual_s=math.sqrt(rss / sum(ws)) / rate)
    return out
