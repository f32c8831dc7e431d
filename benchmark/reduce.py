"""From trace events and spans to numbers. Pure functions over lists of
(name, start, end): the same reduction for every PR, checked on synthetic
events and on a recorded trace by the tests."""
import collections


def union(intervals):
    """Merged, sorted [(start, end)] of any (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip_events(events, window):
    """The events cut to `window` (name kept)."""
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def busy(events, window):
    """Time inside `window` in which any event ran: the union of the
    events' intervals, not their sum."""
    return sum(e - s for s, e in union(
        (s, e) for _, s, e in clip_events(events, window)))


def gaps(events, window):
    """The idle intervals of `window`: [(start, end)], longest first."""
    merged = union((s, e) for _, s, e in clip_events(events, window))
    out, at = [], window[0]
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = e
    if window[1] > at:
        out.append((at, window[1]))
    return sorted(out, key=lambda g: g[0] - g[1])


def top_by_name(events, n=10):
    """[[name, total duration]] of the n names that took most time."""
    total = collections.Counter()
    for name, s, e in events:
        total[name] += e - s
    return [[k, v] for k, v in total.most_common(n)]


def kind(name):
    """An operation's kind: its name without the trailing number, so that
    the same operation of every layer counts as one."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def top_by_kind(events, n=10):
    return top_by_name([(kind(name), s, e) for name, s, e in events], n)


def attribute_gaps(gap_list, spans, n=10):
    """Name each idle gap by the innermost span that covers its midpoint
    (what the host was doing), and total the gaps by that name:
    [[name, total], ...] largest first. "no_span" where none covers it."""
    total = collections.Counter()
    for s, e in gap_list:
        mid = (s + e) / 2
        cover = [(b - a, name) for name, a, b in spans if a <= mid <= b]
        total[min(cover)[1] if cover else "no_span"] += e - s
    return [[k, v] for k, v in total.most_common(n)]


def matching(events, patterns):
    """The events whose name contains any of `patterns`."""
    return [ev for ev in events if any(p in ev[0] for p in patterns)]


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation; None if empty."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)
